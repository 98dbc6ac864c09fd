"""Logs, on the device: per-block self time of the block program under
``causal-log/replicas`` alone — what ``sharing_depth`` costs a block;
nothing to read where the job keeps no replica
(``benchlib/scope_times.py``)."""

from benchlib import scope_times


def read(run):
    ms = scope_times.ms_per_block(run, scope_times.CAUSAL_LOG, "replicas")
    return ms or None
