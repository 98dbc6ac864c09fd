"""Job API / sink, from inside: the share of the window's blocks whose
``block.sink.wait`` began after the next block was dispatched (the
span's ``trailing``: 1 for a tap that trails, 0 for a drain at an
epoch's last block): (B-1)/B for epochs of B blocks, the witness that
the host prepares a block while the chip runs the one before. None for
a program whose wait carries no such arg."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_arg(run, "block.sink.wait", "trailing")
