"""Operators, from inside: rows the ``winning`` vertex of the
``nexmark-average-price`` job emitted per committed epoch over the whole
run — the program's ``winbid.rows.winning`` counter, which the fence adds
to from the operator state its health read brings back. The witness that
the join closed auctions: on ``nexmark-q4`` 62.4 auction ids open a step,
63 % of them get a record and two in three of those a bid inside their
interval at or over the reserve, ~105,000 rows an epoch; without the
interval's test (the ``no-interval`` control) it reads more, with one
side ignored 0. None on a program that keeps no such counter."""

from benchlib import program_spans


def read(run):
    rows = program_spans.of(run).counters.get("winbid.rows.winning")
    if rows is None or not run.stamps:
        return None
    return rows / len(run.stamps)
