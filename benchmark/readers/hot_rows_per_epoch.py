"""Operators, from inside: rows the ``max`` stage of the
``nexmark-hot-items`` job emitted — per window the auctions whose bid
count is the largest over all count subtasks' — per committed epoch over
the whole run: the program's ``window.fired_rows.max`` counter, which the
fence adds to from the operator state its health read brings back. An
epoch holds ``steps_per_epoch * clock_ms_per_step / slide_ms`` windows
(227.3 on ``nexmark-q5``) and a window one row plus its ties; sixteen
times that would say the global stage passed every subtask's leaders
through."""

from benchlib import program_spans


def read(run):
    fired = program_spans.of(run).counters.get("window.fired_rows.max")
    if fired is None or not run.stamps:
        return None
    return fired / len(run.stamps)
