"""Recovery: milliseconds in which an operation ran on the device inside
the ``recover`` span of the kill phase, averaged over the chips used."""

from benchlib import trace_reduce


def read(run):
    w = run.trace_window("recover")
    if w is None or not run.events.ops:
        return None
    busy = trace_reduce.device_busy_s(run.events, *w)
    return sum(busy.values()) / len(busy) * 1e3
