#!/usr/bin/env python
"""Determinant-log append-path measurement — the decision record for
removing ops/log_kernels.py (round-3 verdict item: wire the Pallas
ring-append into the runtime or commit the benchmark showing the XLA
path wins, then delete it).

Findings (from the pre-PR-1 chip record; run this script on the chip to
reproduce — not re-measured on the v5e):

- The BULK path (one [L, K*4, 8] block append per superstep-block,
  clog.v_append_full) moves ~12MB in ~10-15ms — and the Pallas
  ``ring_append_stacked`` kernel cannot serve it at all: its design was
  one cache line (16 rows) per call, so a 2048-row block append would
  need 128 sequential kernel launches where the scatter is one.
- The ASYNC path (single determinant row to a set of logs + replicas)
  is a fused masked one-row set (executor._jit_append_many): one
  dispatch, ~1ms. The kernel's per-log scalar-prefetch machinery buys
  nothing over that.

Hence: no runtime niche; the kernel was deleted. The framework's Pallas
usage lives where it actually wins: the keyed histogram
(ops/histogram.py, ~8x over XLA scatter-add in the window/reduce
blocks).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.causal import log as clog
from clonos_tpu.utils.devsync import device_sync


def timeit(name, fn, *args, n=10):
    jfn = jax.jit(fn)
    out = jfn(*args)
    device_sync(out)
    t0 = time.monotonic()
    device_sync(out)
    rt = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(n):
        out = jfn(*args)
    device_sync(out)
    ms = max(((time.monotonic() - t0) - rt) / n * 1e3, 0.0)
    print(f"{name:48s} {ms:9.2f} ms")


def main():
    print("device:", jax.devices()[0].platform)
    rng = np.random.RandomState(0)
    for L, k in ((32, 2048), (192, 2048)):
        logs = jax.vmap(lambda _: clog.create(1 << 14, 16))(jnp.arange(L))
        rows = jnp.asarray(rng.randint(0, 99, (L, k, 8)), jnp.int32)
        timeit(f"v_append_full [{L},{k},8] (the bulk block path)",
               clog.v_append_full, logs, rows)
        one = jnp.asarray(rng.randint(0, 99, (L, 1, 8)), jnp.int32)
        counts = jnp.ones((L,), jnp.int32)
        timeit(f"v_append [{L},1,8] (the async row path)",
               clog.v_append, logs, one, counts)


if __name__ == "__main__":
    main()
