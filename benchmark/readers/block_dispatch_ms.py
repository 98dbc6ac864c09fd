"""Block executor, from inside: the program's ``block.dispatch`` span — the
host wall of launching the block program (large: the launch blocked),
mean over the window's blocks."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_ms(run, "block.dispatch")
