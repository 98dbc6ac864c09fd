#!/usr/bin/env python
"""Micro-profile of the steady-state block program's pieces at bench shapes
(K=512, P=8), plus the FULL fused block program from the bench topology —
so optimization targets the real hot spot, not a guess.

Timing method: enqueue n calls, one sync at the end, subtract a measured
sync of an already-finished result.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.api.operators import (BlockContext, SyntheticSource,
                                      TumblingWindowCountOperator,
                                      KeyedReduceOperator, SinkOperator)
from clonos_tpu.api.records import RecordBatch
from clonos_tpu.parallel import routing

K, P, B, CAP, NK = 512, 8, 128, 1024, 997


from clonos_tpu.utils.devsync import device_sync as _sync  # noqa: E402


def timeit(name, fn, *args, n=10):
    jfn = jax.jit(fn)
    out = jfn(*args)
    _sync(out)
    rts = []
    for _ in range(3):
        t0 = time.monotonic()
        _sync(out)
        rts.append(time.monotonic() - t0)
    rt = min(rts)
    t0 = time.monotonic()
    for _ in range(n):
        out = jfn(*args)
    _sync(out)
    ms = max(((time.monotonic() - t0) - rt) / n * 1e3, 0.0)
    print(f"{name:48s} {ms:9.2f} ms")
    return ms


def main():
    rng = np.random.RandomState(0)
    bctx = BlockContext(
        times=jnp.asarray(rng.randint(0, 1 << 20, K), jnp.int32),
        rng_bits=jnp.asarray(rng.randint(0, 1 << 30, K), jnp.int32),
        epoch=jnp.zeros((), jnp.int32), step0=jnp.zeros((), jnp.int32),
        subtask=jnp.arange(P, dtype=jnp.int32))

    def mkbatch(k, p, b, fill, vocab=NK):
        keys = jnp.asarray(rng.randint(0, vocab, (k, p, b)), jnp.int32)
        vals = jnp.ones((k, p, b), jnp.int32)
        ts = jnp.zeros((k, p, b), jnp.int32)
        valid = jnp.asarray(
            np.arange(b)[None, None, :] < fill, jnp.bool_)
        valid = jnp.broadcast_to(valid, (k, p, b))
        return RecordBatch(keys, vals, ts, valid)

    win = TumblingWindowCountOperator(num_keys=NK, window_size=1 << 30)
    red = KeyedReduceOperator(num_keys=NK)

    src_out = mkbatch(K, P, B, B)          # [K,P,128]
    win_in = mkbatch(K, P, CAP, 128)       # [K,P,1024], ~128 valid
    win_out = mkbatch(K, P, NK, 200)       # [K,P,997]

    plan = routing.plan_static_hash(
        np.arange(NK, dtype=np.int32), P, P, 64, CAP)
    red_in, _ = jax.jit(plan.apply)(win_out)

    timeit("window.process_block [K,P,1024]",
           lambda s, b: win.process_block(s, b, bctx),
           win.init_state(P), win_in)
    timeit("reduce.process_block dynamic [K,P,1024]",
           lambda s, b: red.process_block(s, b, bctx),
           red.init_state(P), red_in)
    timeit("reduce.process_block_static_keys",
           lambda s, b: red.process_block_static_keys(
               s, b, bctx, plan.slot_keys),
           red.init_state(P), red_in)

    timeit("route_hash_block src->win [K,P,128]->1024",
           lambda b: routing.route_hash_block(b, P, 64, CAP), src_out)
    timeit("route_hash_block win->red [K,P,997]->1024",
           lambda b: routing.route_hash_block(b, P, 64, CAP), win_out)
    timeit("static plan.apply win->red",
           lambda b: plan.apply(b), win_out)

    # --- the real thing: bench topology full block --------------------------
    sys.argv = ["profile"]
    import bench
    from clonos_tpu.runtime.executor import LocalExecutor
    job = bench.build_job()
    ex = LocalExecutor(job, steps_per_epoch=K, log_capacity=1 << 13,
                       max_epochs=16, inflight_ring_steps=1 << 10, seed=7)
    bi = ex._next_block_inputs(K)
    carry = ex.carry
    ms = timeit("FULL run_block (bench topology, K=512)",
                lambda c, i: ex.compiled.run_block(c, i), carry, bi)
    print(f"  -> steady-state ceiling ~{K * P * B / ms * 1e3 / 1e6:.2f} "
          f"M records/s")


if __name__ == "__main__":
    main()
