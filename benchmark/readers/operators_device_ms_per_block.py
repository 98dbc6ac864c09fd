"""Operators, on the device: per-block self time of the block program
under ``vertex/*`` — every operator's ``process_block*`` with everything
beneath it, the keyed histogram included (``benchlib/scope_times.py``;
the table ``block_unscoped_pct`` prints has it vertex by vertex)."""

from benchlib import scope_times


def read(run):
    return scope_times.ms_per_block(run, scope_times.VERTEX)
