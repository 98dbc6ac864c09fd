"""Operators, on the device: per-block self time of the block program
under every ``vertex/<name>/lookup`` — the comparison of a vertex's
receive windows with its subtasks' own columns (``_OwnColumns._column``,
``SessionWindowOperator._arrivals`` and, since PR 49, their block forms
on head and tails, with the selects that pick the tails out and put them
back; ``benchlib/scope_times.py``): what ROADMAP S8 (b) is judged on in
the cells whose lookups they are (``count`` and ``max`` on
``nexmark-q5``, ``sessions`` on ``nexmark-q11``, ``join`` on
``nexmark-q8``). None where the program has no such scope."""

from benchlib import scope_times


def read(run):
    ms = scope_times.ms_per_block(run, leaf="lookup")
    return ms or None      # 0.0: a trace, and no op under such a scope
