"""Operators, on the device: per-block self time of the block program
under ``vertex/join/lookup`` — the comparison of a chunk's packed persons
and auctions with every own column of their subtask
(``IncrementalJoinOperator._chunk``; ``benchlib/scope_times.py``): the
part an optimisation of the own-column lookup (ROADMAP S8 (b)) will be
judged on. None where the program has no such scope."""

from benchlib import scope_times


def read(run):
    ms = scope_times.ms_per_block(run, scope_times.VERTEX, "join", "lookup")
    return ms or None      # 0.0: a trace, and no op under such a scope
