"""Logs, on the device: per-block self time of the block program under
``inflight-ring`` — every vertex's output block into its in-flight log
(``benchlib/scope_times.py``)."""

from benchlib import scope_times


def read(run):
    return scope_times.ms_per_block(run, scope_times.INFLIGHT_RING)
