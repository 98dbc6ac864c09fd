"""Recovery, from inside: the ``recovery.finalize`` span of the kill
phase's ``recovery`` span (``ClusterRunner.recover``: the barrier over
the patched carry, its read and the state check)."""

from benchlib import program_spans


def read(run):
    return program_spans.recovery_phase_ms(run, "finalize")
