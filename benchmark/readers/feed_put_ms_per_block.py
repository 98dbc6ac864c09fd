"""Job API / feed, from inside: the program's ``block.feed.put`` span (the
``jnp.asarray`` / ``jnp.zeros`` that make the pulled block a device
``RecordBatch``: host to device, and the eager one-op programs), mean over
the window's blocks. The outside ``feed_pull_ms_per_block`` is this plus
``block.feed.pull``."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_ms(run, "block.feed.put")
