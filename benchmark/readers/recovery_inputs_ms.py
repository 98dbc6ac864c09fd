"""Recovery, from inside: the ``recovery.inputs`` spans of the kill
phase's ``recovery`` span (``ClusterRunner.recover``: a failed subtask's
input batches rebuilt from the upstream in-flight rings), summed over the
failed subtasks."""

from benchlib import program_spans


def read(run):
    return program_spans.recovery_phase_ms(run, "inputs")
