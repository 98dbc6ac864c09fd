"""Recovery: the ``recover`` span's wall minus the time an operation ran
on the device inside it — what the host spent with the device idle."""

from benchlib import trace_reduce


def read(run):
    w = run.trace_window("recover")
    if w is None or not run.events.ops:
        return None
    busy = trace_reduce.device_busy_s(run.events, *w)
    return (w[1] - w[0]) / 1e6 - sum(busy.values()) / len(busy) * 1e3
