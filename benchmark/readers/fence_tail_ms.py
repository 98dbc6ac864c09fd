"""Control plane: the program's own ``last_fence_phases["fence-tail"]``
(the wall an epoch waited on its fence), median over the window's
epochs."""

import statistics


def read(run):
    return statistics.median(run.fence_tail_ms) if run.fence_tail_ms else None
