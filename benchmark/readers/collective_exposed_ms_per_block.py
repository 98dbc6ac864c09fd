"""Exchange on a mesh: device time per block in which the first chip sat
in a collective operation and nothing else ran on it (its self time on
the serial "XLA Ops" line), over the traced steady span."""

import re

from benchlib import trace_reduce

COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute",
    re.I)


def read(run):
    w = run.trace_window("steady")
    if w is None or not run.events.ops or len(run.devices) < 2:
        return None
    dev = min(run.events.ops)
    selfs = trace_reduce.self_times(run.events.ops[dev], *w)
    exposed = sum(v for k, v in selfs.items() if COLLECTIVE.search(k))
    blocks = len(trace_reduce.spans_inside(run.events, "feed_pull", *w))
    return exposed * 1e3 / blocks if blocks else None
