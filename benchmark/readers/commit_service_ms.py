"""Control plane: median, over the epochs committed inside the window, of
the commit stamp minus the due instant of the epoch's last record —
commit latency with the epoch's own length taken out."""

import statistics

from benchlib import pacing


def read(run):
    if run.schedule is None:
        return None
    s = pacing.service_ms(run.stamps, run.schedule, *run.window)
    return statistics.median(s) if len(s) else None
