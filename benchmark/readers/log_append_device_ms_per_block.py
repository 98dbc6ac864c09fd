"""Logs, on the device: per-block self time of the block program under
``causal-log`` — the block's determinant rows, their append to each
task's own log and to the replicas downstream tasks keep
(``benchlib/scope_times.py``)."""

from benchlib import scope_times


def read(run):
    return scope_times.ms_per_block(run, scope_times.CAUSAL_LOG)
