"""Recovery, from inside: the ``recovery.fetch_determinants`` spans of the kill phase's
``recovery`` span (``ClusterRunner.recover``), summed over the failed
subtasks."""

from benchlib import program_spans


def read(run):
    return program_spans.recovery_phase_ms(run, "fetch_determinants")
