"""Job API / sink, from inside: bytes the program's ``sink.d2h_bytes``
counter took per block (as copied into each ``block.sink.d2h`` span that
closed around the copy), in MB of 10^6 bytes, mean over the window's
blocks."""

from benchlib import program_spans


def read(run):
    b = program_spans.mean_arg(run, "block.sink.d2h", "bytes")
    return None if b is None else b / 1e6
