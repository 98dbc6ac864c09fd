"""End to end, host clock: the 95th percentile of the sample that
``commit_latency_p50_ms`` takes the median of. p95 and not p99: records of
one epoch commit together, so the independent samples are the window's
100-200 epochs."""

import numpy as np

from benchlib import pacing


def read(run):
    sample = pacing.latency_sample(run)
    if sample is None:
        return None
    epochs = len(pacing.stamps_in(run.stamps, *run.window))
    print(f"latency sample: {sample.size} steps of "
          f"{run.cfg['parallelism'] * run.cfg['batch']} records each, in "
          f"{epochs} epochs committed inside the window", flush=True)
    return float(np.percentile(sample, 95))
