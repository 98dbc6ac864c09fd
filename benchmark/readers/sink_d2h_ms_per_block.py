"""Job API / sink, from inside: the program's ``block.sink.d2h`` span (the
``np.asarray`` of the sink batch's keys, values, timestamps and valid:
device to host), mean over the window's blocks. Second of the three parts
of the outside ``sink_absorb_ms_per_block``."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_ms(run, "block.sink.d2h")
