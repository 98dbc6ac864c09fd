"""Operators, from inside: rows the ``sessions`` vertex of the
``nexmark-user-sessions`` job emitted — one per closed session — per
committed epoch over the whole run: the program's
``window.fired_rows.sessions`` counter, which the fence adds to from the
operator state its health read brings back. On ``nexmark-q11`` every
bidder's life is one session and 22.2 ids become eligible a step: ~90,930
an epoch; half of that would say sessions were merged across lives (an id
of the ring reused), double that they were cut."""

from benchlib import program_spans


def read(run):
    fired = program_spans.of(run).counters.get("window.fired_rows.sessions")
    if fired is None or not run.stamps:
        return None
    return fired / len(run.stamps)
