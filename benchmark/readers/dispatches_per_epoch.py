"""Block executor: programs launched on the device (events of the trace's
"XLA Modules" line) during one epoch — the median over the whole epochs
of the traced steady span. A count."""

import statistics

from benchlib import trace_reduce


def read(run):
    w = run.trace_window("steady")
    if w is None or not run.events.modules:
        return None
    starts = sorted(s for _, s, _d in
                    run.events.modules[min(run.events.modules)])
    counts = [sum(1 for s in starts if lo <= s < hi)
              for lo, hi in trace_reduce.spans_inside(run.events, "epoch", *w)]
    return statistics.median(counts) if counts else None
