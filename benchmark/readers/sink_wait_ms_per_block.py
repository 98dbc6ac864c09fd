"""Job API / sink, from inside: the program's ``block.sink.wait`` span
(``ClusterRunner._absorb_sink_outputs`` waiting out the block program whose
sink batch it is about to read: device busy, not idle), mean over the
window's blocks. First of the three parts of the outside
``sink_absorb_ms_per_block``."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_ms(run, "block.sink.wait")
