"""Block executor, from inside: the program's ``block.causal-inputs`` span
(the ``now()`` / RNG draw per step of the block and the upload of the two
arrays), mean over the window's blocks."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_ms(run, "block.causal-inputs")
