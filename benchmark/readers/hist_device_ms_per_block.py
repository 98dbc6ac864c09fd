"""Kernels, on the device: per-block self time of the block program
under a ``hist`` leaf, whichever scope called it — the keyed histogram's
kernel **with** the one-hot and the relayouts around it, where
``hist_kernel_roofline`` times ``_hist_pallas`` alone
(``benchlib/scope_times.py``)."""

from benchlib import scope_times


def read(run):
    return scope_times.ms_per_block(run, leaf=scope_times.HIST)
