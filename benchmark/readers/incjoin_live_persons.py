"""Operators, from inside: the most live persons one ``join`` subtask of
the ``nexmark-local-items`` job has held after a step — the program's
``join.live_persons.join`` mark (``Operator.fence_peaks``: it only grows,
reduced by the maximum over the subtasks in the fence's one health
read). On ``nexmark-q3`` 100.7 ids register a step and live 600 s
(468.75 steps): ~47,200 over 16 subtasks, ~3,100 on the fullest, of its
8,448 own columns. None on a program that keeps no such mark."""

from benchlib import program_spans


def read(run):
    live = program_spans.of(run).counters.get("join.live_persons.join")
    return None if live is None else float(live)
