"""Operators, on the device: per-block self time of the block program
under every ``vertex/<name>/readback`` — a keyed reduce's running value
read back for each record of the block (``KeyedReduceOperator
.process_block``: a compare over the key lanes, or a gather from a table
too wide for that; ``process_block_static_keys``: a static gather behind
a static route; ``benchlib/scope_times.py``). None where the program has
no such scope."""

from benchlib import scope_times


def read(run):
    ms = scope_times.ms_per_block(run, leaf="readback")
    return ms or None      # 0.0: a trace, and no op under such a scope
