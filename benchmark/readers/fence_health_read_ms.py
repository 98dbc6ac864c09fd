"""Control plane, from inside: the program's ``fence.health-read`` span (the
one fused device read of a fence: overflow flags, record total, log
heads), median over the window's epochs."""

from benchlib import program_spans


def read(run):
    return program_spans.median_ms(run, "fence.health-read")
