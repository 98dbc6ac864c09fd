"""Job API, on the device: per-block time of the sink tap's compaction —
the programs named ``jit_sink_pack`` (``runtime/sinktap.py``: they run
between two block programs, under no scope of the block program's) in
the traced steady span, over the block program's launches there. A
read-again through a higher rung is a launch of its own and counts."""

from benchlib import scope_times


def read(run):
    w = run.trace_window("steady")
    if w is None or not run.events.modules:
        return None
    mods = run.events.modules[min(run.events.modules)]
    blocks = scope_times.launches(mods, scope_times.BLOCK_PROGRAM, *w)
    taps = scope_times.launches(mods, "jit_sink_pack", *w)
    if not blocks or not taps:
        return None
    return sum(end - start for start, end in taps) / len(blocks) / 1e6
