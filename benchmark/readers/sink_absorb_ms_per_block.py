"""Job API / sink: host span around the executor's ``on_block_outputs``
(``ClusterRunner._absorb_sink_outputs``: the device-to-host read of a
block's sink emissions and their sharding into the pending transaction),
mean over the window's blocks."""


def read(run):
    d = run.spans.durations_ms("sink_absorb", *run.window)
    return sum(d) / len(d) if d else None
