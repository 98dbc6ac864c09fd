"""Exchange, on the device: per-block self time of the block program
under ``exchange`` — every edge's route, with rank, placement and static
plans beneath it (``benchlib/scope_times.py``)."""

from benchlib import scope_times


def read(run):
    return scope_times.ms_per_block(run, scope_times.EXCHANGE)
