"""End to end, host clock: per record, the commit stamp of the epoch that
makes it visible minus its intended send instant; median over the records
whose commit falls inside the window."""

import numpy as np

from benchlib import pacing


def read(run):
    sample = pacing.latency_sample(run)
    return None if sample is None else float(np.percentile(sample, 50))
