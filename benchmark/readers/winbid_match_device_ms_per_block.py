"""Operators, on the device: per-block self time of the block program
under ``vertex/winning/lookup`` and ``vertex/winning/place`` — a chunk's
auctions against every own column of their subtask, and the waiting and
the chunk's bids against the chunk's active intervals, the reduction that
carries each interval's best (``BestInIntervalJoinOperator._chunk``;
``benchlib/scope_times.py``): the part an optimisation of the match will
be judged on. None where the program has no such scope."""

from benchlib import scope_times


def read(run):
    ms = sum(scope_times.ms_per_block(run, scope_times.VERTEX, "winning",
                                      part) or 0.0
             for part in ("lookup", "place"))
    return ms or None      # 0.0: no trace, or no op under such a scope
