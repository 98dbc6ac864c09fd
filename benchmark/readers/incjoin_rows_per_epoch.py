"""Operators, from inside: rows the ``join`` vertex of the
``nexmark-local-items`` job emitted per committed epoch over the whole
run — the program's ``join.rows.join`` counter, which the fence adds to
from the operator state its health read brings back. The witness that
the join joined: on ``nexmark-q3`` 153.6 auctions of category 10 reach
the join a step and 39.3 % of their sellers are registered (a person of
a local state in the seller's 5 ms), ~247,000 rows an epoch; without the
two predicates it would read ~8x that, with one side ignored 0. None on a
program that keeps no such counter."""

from benchlib import program_spans


def read(run):
    rows = program_spans.of(run).counters.get("join.rows.join")
    if rows is None or not run.stamps:
        return None
    return rows / len(run.stamps)
