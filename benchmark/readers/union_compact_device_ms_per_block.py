"""Operators, on the device: per-block self time of the block program
under ``vertex/union/compact`` — both inputs' records of every (step,
subtask) row packed by rank to the front of the union's capacity, the
histograms beneath it included (``UnionOperator.process_block``;
``benchlib/scope_times.py``). None where the program has no such
scope."""

from benchlib import scope_times


def read(run):
    ms = scope_times.ms_per_block(run, scope_times.VERTEX, "union", "compact")
    return ms or None      # 0.0: a trace, and no op under such a scope
