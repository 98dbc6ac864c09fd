#!/usr/bin/env python
"""Amortized device-compute cost of each warm-recovery program at bench
shapes (host sync amortized by chaining N dispatches per sync): routing,
replay block, log restore, graft, ring write, replica copy."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import bench
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP
    from clonos_tpu.utils.devsync import device_sync

    SPE = bench.STEPS_PER_EPOCH
    job = bench.build_job()
    need = bench.FILL_EPOCHS * SPE * DETS_PER_STEP
    cap = 1 << need.bit_length()
    span = bench.FILL_EPOCHS * SPE
    runner = ClusterRunner(job, steps_per_epoch=SPE, log_capacity=cap,
                           max_epochs=16,
                           inflight_ring_steps=1 << (span - 1).bit_length(),
                           recovery_block_steps=8192, block_steps=1024,
                           seed=7)
    runner.run_epoch(complete_checkpoint=True)
    for _ in range(bench.FILL_EPOCHS):
        runner.run_epoch(complete_checkpoint=False)
    device_sync(runner.executor.carry)
    print("setup done", flush=True)

    failed = bench.PAR + 1
    runner.inject_failure([failed])
    t0 = time.monotonic()
    report = runner.recover()
    print("cold recover:", round(time.monotonic() - t0, 1), "s", flush=True)

    carry = runner.executor.carry
    ch = runner._chunk()
    eidx = 0                      # source->window edge
    ri = runner.executor.compiled.ring_index[0]
    el = carry.out_rings[ri]
    z = jnp.asarray(0, jnp.int32)
    n_steps = span

    def amort(label, fn, n=8):
        fn()
        device_sync(carry)
        t1 = time.monotonic()
        for _ in range(n):
            fn()
        device_sync(carry)
        print(f"{label}: {(time.monotonic() - t1) * 1e3 / n:.1f}ms",
              flush=True)

    rt = runner._route_chunk_fn(eidx, ch)
    amort("route lane 8192 window",
          lambda: rt(el, z, jnp.asarray(1, jnp.int32), z,
                     jnp.asarray(n_steps, jnp.int32), z))
    rta = runner._route_chunk_fn(eidx, ch, all_lanes=True)
    amort("route all-lanes 8192 window",
          lambda: rta(el, z, z, jnp.asarray(n_steps, jnp.int32), z))

    mgr = report.managers[0]
    plan = mgr.plan
    t_d, r_d, e_d = plan.det_device
    state0 = jax.tree_util.tree_map(
        lambda x: x[plan.subtask][None], plan.checkpoint_op_state)
    chunk = plan.input_steps[0]
    jb = mgr.replayer._jit_block
    amort("replay block 8192",
          lambda: jb(state0, chunk, t_d[:ch], r_d[:ch],
                     jnp.asarray(1, jnp.int32), jnp.zeros((), jnp.int32)))

    me = runner.executor.compiled.max_epochs
    lr = runner._log_restore_from_replica_fn()
    amort("log restore from replica",
          lambda: lr(carry.replicas, z, z, z, z,
                     jnp.zeros((me,), jnp.int32),
                     jnp.zeros((me,), jnp.bool_), z, z))

    rw = runner._ring_write_fn(ri, ch)
    ring_dummy = jax.tree_util.tree_map(jnp.zeros_like, el)
    out_cap = runner.executor.compiled.vertex_out_capacity(0)
    from clonos_tpu.api.records import RecordBatch as RB
    zb = RB(jnp.zeros((ch, out_cap), jnp.int32),
            jnp.zeros((ch, out_cap), jnp.int32),
            jnp.zeros((ch, out_cap), jnp.int32),
            jnp.zeros((ch, out_cap), jnp.bool_))

    def ring_once():
        nonlocal ring_dummy
        ring_dummy, _ = rw(ring_dummy, zb, z, z,
                           jnp.asarray(1, jnp.int32), z)
    amort("ring write 8192 chunk (donated)", ring_once)

    nr = runner.plan.num_replicas
    rc = runner._replica_copy_fn()
    reps_dummy = jax.tree_util.tree_map(jnp.zeros_like, carry.replicas)

    def rep_once():
        nonlocal reps_dummy
        reps_dummy = rc(reps_dummy, carry.logs,
                        jnp.full((nr,), nr, jnp.int32),
                        jnp.zeros((nr,), jnp.int32))
    amort("replica copy (donated)", rep_once)

    # graft
    gf = runner._graft_fn(1)
    st_log = jax.tree_util.tree_map(lambda x: x[0],
                                    (carry.logs,))[0]
    import clonos_tpu.causal.log as clog
    one_log = jax.tree_util.tree_map(lambda x: x[0], carry.logs)
    carry_dummy = jax.tree_util.tree_map(jnp.zeros_like, carry)

    def graft_once():
        nonlocal carry_dummy
        carry_dummy = gf(carry_dummy, state0, one_log, z, z, z)
    amort("graft (donated)", graft_once)


if __name__ == "__main__":
    main()
