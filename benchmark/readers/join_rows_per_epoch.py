"""Operators, from inside: rows the window join emitted — one per (key,
window) in which both inputs had a record — per committed epoch over the
whole run: the program's ``window.fired_rows.<vertex>`` counter of the
job's join vertex (the vertex that also has ``join.left_records.<vertex>``),
which the fence adds to from the operator state its health read brings
back. The witness that the join joined: with one input ignored it reads
0, with the match on the other input left out ~2.6 times too many."""

from benchlib import program_spans


def read(run):
    counters = program_spans.of(run).counters
    joins = [name[len("join.left_records."):] for name in counters
             if name.startswith("join.left_records.")]
    if not joins or not run.stamps:
        return None
    return sum(counters.get("window.fired_rows." + vertex, 0)
               for vertex in joins) / len(run.stamps)
