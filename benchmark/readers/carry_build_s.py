"""Set-up, from inside: seconds of the span ``setup.init-carry``
(``CompiledJob.build_carry``: the initial carry built on the devices —
under a mesh by one jitted program whose ``out_shardings`` are the
carry's own, so that no device ever holds a leaf whole — with the
dedupe of shared buffers and the wait for the last leaf). The span is
the run's first and a long window may push it out of the recorder's
ring; the program counts the same duration as ``carry.build_us``, which
is read then. One executor a run, so the counter is that build's. None
on a program that has neither (the parent of PR 51 builds the carry
under no span)."""

from benchlib import program_spans


def read(run):
    prog = program_spans.of(run)
    built = [s["dur"] for s in prog.spans if s["name"] == "setup.init-carry"]
    if built:
        return float(built[-1])
    us = prog.counters.get("carry.build_us")
    return None if us is None else us / 1e6
