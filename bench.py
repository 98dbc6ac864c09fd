#!/usr/bin/env python
"""Headline benchmark: causal-recovery replay rate.

Workload (BASELINE.json north star): a 32-subtask keyed topology
(8 sources -> 8 windows -> 8 reduces -> 8 sinks), ~1M determinants buffered
cluster-wide across two un-truncated epochs; fail a window subtask; run the
full causal-recovery protocol (determinant fetch from downstream replicas,
merge, in-flight input fetch, vectorized on-device replay scan, verified
bit-identical against the recorded log).

Metric: records/sec through the replay path. The reference's replay is a
per-record JVM loop where every replayed record consumes ~1 determinant
(order/timestamp per buffer/record), so JVM determinants/sec ~= JVM
records/sec; ``vs_baseline`` is measured against
JVM_BASELINE_RECORDS_PER_SEC = 1e6 (the reference publishes no numbers —
BASELINE.md — so the baseline is a generous stand-in for a JVM core's
stream-replay rate; north-star target is vs_baseline >= 10).

Prints ONE JSON line.
"""

import json
import os
import sys
import time

import numpy as np

# Persistent XLA compile cache: a restarted job pays ~zero for the
# prewarm compiles (the reference's standby deploy survives restarts).
# JAX_COMPILATION_CACHE_DIR places it; default <checkout>/.jax_cache.
from clonos_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

JVM_BASELINE_RECORDS_PER_SEC = 1.0e6

from clonos_tpu.utils.devsync import device_sync  # noqa: E402
from clonos_tpu.soak import slo as _soak_slo  # noqa: E402

PAR = 8                      # per-vertex parallelism -> 32 subtasks
BATCH = 128                  # records per source subtask per superstep
STEPS_PER_EPOCH = int(os.environ.get("BENCH_STEPS_PER_EPOCH", 4096))
#: un-truncated epochs to accumulate the recovery backlog: 4 epochs x
#: 4096 steps x 32 tasks x 4 rows = ~2.1M buffered determinants (>= 2x
#: the BASELINE.json 1M floor; the replay must chew through all of it).
FILL_EPOCHS = int(os.environ.get("BENCH_FILL_EPOCHS", 4))


def build_job():
    from clonos_tpu.api.environment import StreamEnvironment

    env = StreamEnvironment(name="bench-allround", num_key_groups=64,
                            default_edge_capacity=1024)
    (env.synthetic_source(vocab=997, batch_size=BATCH, parallelism=PAR)
        .key_by()
        .window_count(num_keys=997, window_size=1 << 30, name="window")
        .key_by()
        .reduce(num_keys=997, name="reduce")
        .sink())
    return env.build()


def bench_config4():
    """BASELINE config #4: Kafka-like feed source -> keyBy -> window ->
    keyBy -> reduce -> sink, 64 tasks, connected/cascading failures
    (scaled-down steps to bound bench wall-clock; full protocol)."""
    import jax
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.feeds import ListFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner

    P4, B4 = 16, 32
    SPE = int(os.environ.get("BENCH_C4_SPE", 1024))
    env = StreamEnvironment(name="bench-c4", num_key_groups=64,
                            default_edge_capacity=512)
    (env.host_source(batch_size=B4, parallelism=P4)
        .key_by().window_count(num_keys=499, window_size=1 << 30,
                               parallelism=P4)
        .key_by().reduce(num_keys=499, parallelism=P4)
        .sink(parallelism=P4))
    job = env.build()
    rng = np.random.RandomState(5)
    total = 4 * SPE * B4
    feed = ListFeedReader([
        [(int(k), 1) for k in rng.randint(0, 499, total)]
        for _ in range(P4)])
    runner = ClusterRunner(job, steps_per_epoch=SPE,
                           log_capacity=1 << (SPE * 8 - 1).bit_length(),
                           max_epochs=16,
                           inflight_ring_steps=1 << (SPE - 1).bit_length(),
                           seed=5)
    runner.executor.register_feed(0, feed)
    runner.run_epoch(complete_checkpoint=True)
    # Deployed standbys for this topology too: the cascading number
    # should measure the protocol, not XLA compiles or first-execution
    # warmup (prewarm compiles; the drill runs everything hot).
    prewarm_s = runner.prewarm_recovery()
    t_live = time.monotonic()
    runner.run_epoch(complete_checkpoint=False)
    device_sync(runner.executor.carry)
    live_s = time.monotonic() - t_live
    wbase = job.subtask_base(1)
    rbase = job.subtask_base(2)
    # One subtask of EVERY class the measured cascading failure hits —
    # the recovery number must measure the protocol, not warmup.
    runner.failover_drill([1, wbase + 2, rbase + 6])
    device_sync(runner.executor.carry)
    # Cascading connected failures: feed source + window + reduce subtasks
    # on one path (3 vertex classes at once).
    runner.inject_failure([2, wbase + 3, rbase + 7])
    t0 = time.monotonic()
    report = runner.recover()
    device_sync(runner.executor.carry)
    return {
        "subtasks": job.total_subtasks(),
        "failed": list(report.failed_subtasks),
        "steps_replayed": report.steps_replayed,
        "records_replayed": report.records_replayed,
        "recovery_ms": round((time.monotonic() - t0) * 1e3, 1),
        "steady_state_records_per_sec": round(
            SPE * P4 * B4 / live_s, 1),
        "prewarm_s": round(prewarm_s, 1),
    }


def bench_config5():
    """BASELINE config #5: NEXMark-style two-source keyed interval join
    with CausalSerializableService calls, 128 tasks (scaled-down
    determinant volume; external-call sidecar replay exercised)."""
    import jax
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.causal import determinant as det
    from clonos_tpu.runtime.cluster import ClusterRunner

    # BASELINE scale: 10M buffered determinants cluster-wide. 128 tasks x
    # 4 rows/step x (fill_epochs x SPE) steps >= 1e7 -> 20480 backlog
    # steps at the defaults below (5 x 4096).
    P5 = 32
    SPE = int(os.environ.get("BENCH_C5_SPE", 4096))
    fill = int(os.environ.get("BENCH_C5_FILL", 5))
    env = StreamEnvironment(name="bench-c5", num_key_groups=64,
                            default_edge_capacity=256)
    left = env.synthetic_source(vocab=211, batch_size=16,
                                parallelism=P5).key_by()
    right = env.synthetic_source(vocab=211, batch_size=16,
                                 parallelism=P5, name="source-r").key_by()
    (left.join(right, num_keys=211, window=4, interval=1 << 30,
               parallelism=P5)
         .sink(parallelism=P5))
    job = env.build()
    span = fill * SPE
    # At 10M buffered determinants the full bipartite replication (5120
    # holder logs for this topology) would need ~21GB of HBM for replica
    # storage alone — replication_factor=2 is the memory-scalable knob
    # (2 holders per owner per edge: survives any single failure and
    # all non-adjacent doubles; causal/replication.py:53-66).
    runner = ClusterRunner(job, steps_per_epoch=SPE,
                           log_capacity=1 << (span * 4 - 1).bit_length(),
                           max_epochs=16,
                           inflight_ring_steps=1 << (span - 1).bit_length(),
                           replication_factor=2,
                           seed=9)
    # External CausalSerializableService calls on a join subtask: values
    # record to its log (+ sidecar) and replay after failure.
    jbase = job.subtask_base(2)
    sidecar = det.SidecarStore()
    svc = runner.executor.service_factory(jbase + 1, sidecar)
    ext = svc.serializable_service(lambda q: b"answer:" + q)
    runner.run_epoch(complete_checkpoint=True)
    prewarm_s = runner.prewarm_recovery(vertex_ids=[2])   # join class only
    calls_live = [ext.apply(b"q%d" % i) for i in range(3)]
    for _ in range(fill):
        runner.run_epoch(complete_checkpoint=False)
    device_sync(runner.executor.carry)
    runner.failover_drill([jbase])        # join-class rehearsal
    device_sync(runner.executor.carry)
    dets = int(np.sum(runner.executor.log_sizes()))
    runner.inject_failure([jbase + 1])
    t0 = time.monotonic()
    report = runner.recover()
    device_sync(runner.executor.carry)
    # The recovered log must still hold the external-call determinants.
    replayed_async = sum(
        1 for _s, d in report.managers[0].result.async_events
        if d.TAG == det.SERIALIZABLE)
    return {
        "subtasks": job.total_subtasks(),
        "buffered_determinants": dets,
        "external_calls_live": len(calls_live),
        "external_calls_replayed": replayed_async,
        "steps_replayed": report.steps_replayed,
        "records_replayed": report.records_replayed,
        "recovery_ms": round((time.monotonic() - t0) * 1e3, 1),
        "recovery_phase_ms": {k: round(v, 1)
                              for k, v in report.phase_ms.items()},
        "prewarm_s": round(prewarm_s, 1),
    }


def sharing_depth_sweep():
    """THE Clonos trade-off knob (ExecutionConfig.setDeterminantSharingDepth,
    reference .../api/common/ExecutionConfig.java:297-310): replication
    memory vs how many connected failures survive — MEASURED, not
    analytic: each depth runs the bench topology live (its piggyback
    replication overhead lands in steady_state_records_per_sec) and then
    takes a REAL owner+holder connected failure. Depth 1 must fail loudly
    (the only surviving copy of the owner's log died with its holder);
    depth >= 2 must recover. Analytic replica counts stay as columns."""
    from clonos_tpu.causal import recovery as rec_mod
    from clonos_tpu.causal.replication import ReplicationPlan
    from clonos_tpu.runtime.cluster import ClusterRunner

    SPE = 512
    out = []
    for depth in (1, 2, -1):
        from clonos_tpu.api.environment import StreamEnvironment
        env = StreamEnvironment(name=f"bench-depth{depth}",
                                num_key_groups=64,
                                default_edge_capacity=1024)
        (env.synthetic_source(vocab=997, batch_size=BATCH, parallelism=PAR)
            .key_by()
            .window_count(num_keys=997, window_size=1 << 30, name="window")
            .key_by()
            .reduce(num_keys=997, name="reduce")
            .sink())
        job = env.build()
        job.sharing_depth = depth
        # replication_factor=1: ONE holder per owner per depth level, so
        # "survives k connected failures" maps exactly to the depth knob
        # (with full bipartite replication every depth-1 owner has P
        # holders and even owner+holder failures survive — that measures
        # the factor, not the depth).
        plan = ReplicationPlan.from_job(job, depth, replication_factor=1)
        cap = 1 << (SPE * 4 * 2 - 1).bit_length()
        runner = ClusterRunner(job, steps_per_epoch=SPE, log_capacity=cap,
                               max_epochs=16, inflight_ring_steps=1 << 10,
                               block_steps=512, replication_factor=1,
                               seed=7)
        runner.run_epoch(complete_checkpoint=True)
        device_sync(runner.executor.carry)
        t_w = time.monotonic()
        runner.run_epoch(complete_checkpoint=False)
        device_sync(runner.executor.carry)
        live_s = time.monotonic() - t_w
        entry = {
            "depth": depth,
            "replication_factor": 1,
            "replica_logs": plan.num_replicas,
            "replica_bytes": plan.num_replicas * cap * 8 * 4,
            "survives_connected_failures": (
                "any" if depth == -1 else depth),
            "steady_state_records_per_sec": round(
                SPE * PAR * BATCH / live_s, 1),
        }
        # Connected owner+holder failure: the window subtask AND the
        # downstream subtask holding its (depth-1) replica die together.
        wflat = PAR + 1
        holder = next(h for (o, h) in plan.pairs if o == wflat)
        runner.inject_failure([wflat, holder])
        try:
            runner.recover()
            device_sync(runner.executor.carry)
            entry["recovery_ok"] = True
        except rec_mod.RecoveryError as e:
            entry["recovery_ok"] = False
            entry["recovery_error"] = str(e)[:160]
        if depth == 1 and entry["recovery_ok"]:
            entry["recovery_error"] = (
                "UNEXPECTED: depth-1 survived an owner+holder failure")
        out.append(entry)
        del runner
        import gc
        gc.collect()
    return out


def overhead_probe():
    """FT-overhead attribution at bench shapes (obs/profile.py): a
    short PROFILED run. The profiler fences device dispatch to make
    section walls meaningful, which serializes the pipeline — so this
    runs separately, after the headline measurement, and never touches
    the pipelined throughput numbers. Reports the per-epoch
    ``overhead.ft-fraction`` (last closed window, warm) plus the
    lifetime per-section breakdown."""
    import gc
    from clonos_tpu.obs import profile as prof_mod
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    SPE = int(os.environ.get("BENCH_PROFILE_SPE", 1024))
    prof_mod.configure_profile()
    try:
        job = build_job()
        need = 2 * SPE * DETS_PER_STEP
        runner = ClusterRunner(
            job, steps_per_epoch=SPE,
            log_capacity=1 << need.bit_length(), max_epochs=16,
            inflight_ring_steps=1 << (SPE - 1).bit_length(), seed=7)
        runner.run_epoch(complete_checkpoint=True)   # compile warmup
        for _ in range(2):
            runner.run_epoch(complete_checkpoint=True)
        device_sync(runner.executor.carry)
        prof = prof_mod.get_profiler()
        sections = {k: round(v * 1e3, 2)
                    for k, v in sorted(prof.lifetime().items())}
        out = {
            # Last closed epoch window — warm, the gauge /metrics serves.
            "overhead_ft_fraction": prof.ft_fraction(),
            # Whole probe incl. the compile-warmup epoch (upper bound).
            "overhead_ft_fraction_lifetime": round(
                prof.lifetime_ft_fraction(), 6),
            "sections_ms_lifetime": sections,
            "steps_per_epoch": SPE,
        }
        del runner
        gc.collect()
    finally:
        prof_mod.reset_profile()
    # Lineage on/off cost rides along (unprofiled — the dye plane's
    # cost is fence-side wall, not a profiler section).
    try:
        out["lineage"] = lineage_overhead_probe()
    except Exception as e:                            # pragma: no cover
        out["lineage"] = {"error": str(e)}
    return out


def lineage_overhead_probe():
    """Record-lineage cost at a bench shape (obs/lineage.py): the same
    short run twice — dye plane disabled (NullLineage: the identity,
    zero wire fields, zero per-record work, the fence never even
    extracts the epoch window for it) vs enabled (k records dyed per
    epoch, hops/determinants/sinks appended to a JSONL observation
    log at every fence). The disabled wall IS the baseline; the
    enabled-over-disabled fraction is the full price of answering
    \"explain this output record\" after the fact."""
    import gc
    import tempfile
    from clonos_tpu.obs.lineage import LineagePlane, NullLineage
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    SPE = int(os.environ.get("BENCH_LINEAGE_SPE", 256))
    EPOCHS = 3

    def timed(lin):
        job = build_job()
        need = 2 * SPE * DETS_PER_STEP
        runner = ClusterRunner(
            job, steps_per_epoch=SPE,
            log_capacity=1 << need.bit_length(), max_epochs=16,
            inflight_ring_steps=1 << (SPE - 1).bit_length(), seed=7,
            lineage=lin)
        runner.run_epoch(complete_checkpoint=True)   # compile warmup
        device_sync(runner.executor.carry)
        t0 = time.monotonic()
        for _ in range(EPOCHS):
            runner.run_epoch(complete_checkpoint=True)
        device_sync(runner.executor.carry)
        wall = time.monotonic() - t0
        del runner
        gc.collect()
        return wall

    off_s = timed(NullLineage())
    with tempfile.TemporaryDirectory() as td:
        lin = LineagePlane(td, service="bench", k=4)
        on_s = timed(lin)
        lin.close()
        dyed, n_obs = lin.dyed, lin.observations
    return {
        "lineage_off_s": round(off_s, 3),
        "lineage_on_s": round(on_s, 3),
        "lineage_overhead_fraction": (
            round(max(0.0, on_s / off_s - 1.0), 4) if off_s > 0
            else None),
        "records_dyed": dyed,
        "observations": n_obs,
        "steps_per_epoch": SPE,
        "epochs": EPOCHS,
    }


def ablation_probe():
    """FT-cost ablation (``bench.py --ablate``): the no-FT twin
    (analysis/ablate.py) head-to-head against the real executor on the
    same job, same seed, ``logical_time=True`` — so both see identical
    causal inputs and the twin's outputs are asserted bit-identical
    before its time is trusted. The wall delta is the *measured*
    ft-fraction; the census cost model (analysis/census.py) predicts a
    *static* ft-fraction from the same source; their relative error is
    the model's report card. The profiler's ``overhead.ft-fraction``
    gauge rides along as the third, runtime view (host-visible FT
    sections only — the in-block append cost is jitted away from it,
    so it lower-bounds the measured number)."""
    import gc
    import jax
    from clonos_tpu.analysis import (ablated_executor, build_census,
                                     static_cost_model)
    from clonos_tpu.analysis.census import _repo_contexts, fingerprint
    from clonos_tpu.obs import profile as prof_mod
    from clonos_tpu.runtime import executor as real_ex
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    SPE = int(os.environ.get("BENCH_ABLATE_SPE", 512))
    EPOCHS = int(os.environ.get("BENCH_ABLATE_EPOCHS", 3))
    twin_mod, report = ablated_executor()

    def drive(ex_mod, profiled=False):
        job = build_job()
        need = (EPOCHS + 1) * SPE * DETS_PER_STEP
        ex = ex_mod.LocalExecutor(
            job, steps_per_epoch=SPE,
            log_capacity=1 << need.bit_length(), max_epochs=16,
            inflight_ring_steps=1 << (SPE - 1).bit_length(),
            block_steps=min(256, SPE), seed=7, logical_time=True)
        ex.run_epoch()                       # compile warmup
        device_sync(ex.carry)
        prof = prof_mod.get_profiler()
        t0 = time.monotonic()
        outs = None
        for _ in range(EPOCHS):
            if profiled:
                ft0 = sum(v for n, v in prof.lifetime().items())
                e0 = time.monotonic()
            outs = ex.run_epoch()
            device_sync(ex.carry)
            if profiled:
                # Attribute the epoch's non-FT wall as compute so the
                # gauge's rollup denominator is the full epoch.
                ft = sum(v for n, v in prof.lifetime().items()) - ft0
                wall = time.monotonic() - e0
                prof.observe("block-drive", max(wall - ft, 0.0),
                             kind=prof_mod.COMPUTE)
        wall_s = time.monotonic() - t0
        digest = (
            tuple(np.asarray(x) for x in
                  jax.tree_util.tree_leaves((ex.carry.op_states,
                                             ex.carry.edge_bufs,
                                             ex.carry.record_counts))),
            tuple(np.asarray(x) for x in
                  jax.tree_util.tree_leaves(outs.sinks)),
        )
        log_head = int(np.asarray(ex.carry.logs.head).max())
        rings = len(ex.carry.out_rings)
        subtasks = job.total_subtasks()
        del ex, job
        gc.collect()
        return wall_s, digest, log_head, rings, subtasks

    # Real run, profiled: the runtime gauge's view of the same epochs.
    prof_mod.configure_profile()
    try:
        t_real, d_real, head_real, rings, subtasks = drive(
            real_ex, profiled=True)
        prof = prof_mod.get_profiler()
        prof.rollup()
        gauge = prof.snapshot()
    finally:
        prof_mod.reset_profile()
    t_twin, d_twin, head_twin, _r, _s = drive(twin_mod)

    # Equivalence gate: the twin only measures FT cost if everything
    # BUT the logs is bit-identical.
    real_leaves = d_real[0] + d_real[1]
    twin_leaves = d_twin[0] + d_twin[1]
    identical = (len(real_leaves) == len(twin_leaves) and all(
        np.array_equal(a, b)
        for a, b in zip(real_leaves, twin_leaves)))
    if not identical:
        raise AssertionError(
            "ablation twin diverged from the real executor — the "
            "no-FT transform is not semantics-preserving for this "
            "job; refusing to report an ft-fraction")
    assert head_real > 0 and head_twin == 0, \
        (head_real, head_twin)

    measured = max(0.0, (t_real - t_twin) / t_real) if t_real else 0.0
    ctxs = _repo_contexts(("clonos_tpu", "examples"))
    census = build_census(ctxs)
    model = static_cost_model(
        census, steps_per_epoch=SPE, subtasks=subtasks,
        records_per_step=BATCH * PAR, ring_vertices=rings,
        record_touches=4)
    static = model["ft_fraction_static"]
    rel_err = abs(static - measured) / max(abs(measured), 1e-9)
    return {
        "ft_fraction_measured": round(measured, 6),
        "ft_fraction_static": static,
        "model_rel_error": round(rel_err, 6),
        "ft_fraction_gauge": gauge["lifetime_ft_fraction"],
        "t_real_s": round(t_real, 4),
        "t_twin_s": round(t_twin, 4),
        "epochs": EPOCHS,
        "steps_per_epoch": SPE,
        "subtasks": subtasks,
        "stripped_sites": len(report.stripped),
        "outputs_bit_identical": True,
        "log_rows_real": head_real,
        "log_rows_twin": head_twin,
        "static_model": model,
        "census_fingerprint": fingerprint(census),
    }


def multi_job_probe(n_jobs: int):
    """Multi-job throughput probe (``bench.py --jobs N`` /
    ``clonos_tpu bench --jobs N``): N independent small jobs sharing one
    device, stepped round-robin one epoch at a time — the in-process
    analog of the dispatcher's shared slot pool (runtime/dispatcher.py).
    Reports each job's sustained rate, the aggregate rate, and the
    min/max fairness ratio (1.0 = a perfectly fair interleave; the
    round-robin drive means any skew is runtime overhead, not
    scheduling bias)."""
    import gc
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    P, B = 2, 64
    SPE = int(os.environ.get("BENCH_JOBS_SPE", 256))
    EPOCHS = int(os.environ.get("BENCH_JOBS_EPOCHS", 4))
    runners = []
    for j in range(n_jobs):
        env = StreamEnvironment(name=f"bench-job{j}", num_key_groups=16,
                                default_edge_capacity=256)
        (env.synthetic_source(vocab=211, batch_size=B, parallelism=P)
            .key_by()
            .window_count(num_keys=211, window_size=1 << 30)
            .key_by()
            .reduce(num_keys=211)
            .sink())
        # Two epochs of log headroom: truncation lands at the NEXT
        # fence, so a ring sized to one epoch overflows mid-epoch.
        runners.append(ClusterRunner(
            env.build(), steps_per_epoch=SPE,
            log_capacity=1 << (2 * SPE * DETS_PER_STEP).bit_length(),
            max_epochs=EPOCHS + 4,
            inflight_ring_steps=1 << (2 * SPE - 1).bit_length(),
            seed=7 + j))
    for r in runners:                 # compile warmup, unmeasured
        r.run_epoch(complete_checkpoint=True)
        device_sync(r.executor.carry)
    walls = [0.0] * n_jobs
    t_all = time.monotonic()
    for _ in range(EPOCHS):
        for j, r in enumerate(runners):    # round-robin interleave
            t0 = time.monotonic()
            r.run_epoch(complete_checkpoint=True)
            device_sync(r.executor.carry)
            walls[j] += time.monotonic() - t0
    total_s = time.monotonic() - t_all
    records = EPOCHS * SPE * P * B
    rates = [round(records / w, 1) for w in walls]
    out = {
        "metric": "multi_job_aggregate_records_per_sec",
        "value": round(n_jobs * records / total_s, 1),
        "unit": "records/sec across all jobs",
        "jobs": n_jobs,
        "per_job_records_per_sec": rates,
        "fairness_min_over_max": round(min(rates) / max(rates), 3),
        "epochs_per_job": EPOCHS,
        "steps_per_epoch": SPE,
    }
    del runners
    gc.collect()
    return out


def multichip_probe(n_devices: int = 8):
    """Mesh-sharding probe (``bench.py --multichip [N]``): the SAME job
    run twice — once on a 1-device task mesh, once sharded over an
    N-device mesh (rule-driven PartitionSpec tree over carry, causal
    logs, and in-flight rings; parallel/distributed.py) — with the audit
    ledger sealing every epoch in both runs. Reports aggregate and
    per-shard steady-state throughput, the speedup and scaling
    efficiency, and whether the sharded run's sealed epoch digests are
    bit-identical to the unsharded run's (``diff_ledgers`` empty — the
    exactly-once fence contract is sharding-invariant).

    One process drives every chip of the host; with fewer than N
    devices the probe fails with the counts (a child forced onto
    virtual CPU devices is not a multi-chip result)."""
    import gc
    import tempfile

    import jax

    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"multichip probe needs {n_devices} devices, JAX found "
            f"{len(jax.devices())} ({jax.devices()[0].device_kind})")

    from clonos_tpu.obs.digest import diff_ledgers
    from clonos_tpu.parallel import distributed as dist
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    SPE = int(os.environ.get("BENCH_MC_SPE", 512))
    EPOCHS = int(os.environ.get("BENCH_MC_EPOCHS", 3))

    def run_one(ndev: int, ckdir: str):
        from clonos_tpu.api.environment import StreamEnvironment
        env = StreamEnvironment(name="bench-mc", num_key_groups=64,
                                default_edge_capacity=512)
        (env.synthetic_source(vocab=997, batch_size=BATCH, parallelism=PAR)
            .key_by()
            .window_count(num_keys=997, window_size=1 << 30, name="window")
            .key_by()
            .reduce(num_keys=997, name="reduce")
            .sink())
        runner = ClusterRunner(
            env.build(), steps_per_epoch=SPE,
            log_capacity=1 << (2 * SPE * DETS_PER_STEP).bit_length(),
            max_epochs=EPOCHS + 8,
            inflight_ring_steps=1 << (2 * SPE - 1).bit_length(),
            checkpoint_dir=ckdir, audit=True,
            mesh=dist.task_mesh(max_devices=ndev),
            logical_time=True, seed=7)
        runner.run_epoch(complete_checkpoint=True)   # compile warmup
        device_sync(runner.executor.carry)
        t0 = time.monotonic()
        for _ in range(EPOCHS):
            runner.run_epoch(complete_checkpoint=True)
        device_sync(runner.executor.carry)
        wall = time.monotonic() - t0
        shards = runner.per_shard_health()
        ledger = runner.coordinator.read_ledger()
        rate = EPOCHS * SPE * PAR * BATCH / wall
        rec_total = max(
            1, int(np.asarray(runner.executor.carry.record_counts).sum()))
        per_shard = None
        if shards is not None and ndev > 1:
            # Deal the aggregate rate out by each shard's actual record
            # share (the mesh partitions work, not just storage).
            per_shard = [round(rate * int(s) / rec_total, 1)
                         for s in np.asarray(shards)[:, 0]]
        del runner
        gc.collect()
        return rate, per_shard, ledger

    with tempfile.TemporaryDirectory() as td:
        rate_1, _ps1, ledger_1 = run_one(1, os.path.join(td, "m1"))
        rate_n, per_shard, ledger_n = run_one(n_devices,
                                              os.path.join(td, "mn"))
    problems = diff_ledgers(ledger_1, ledger_n)
    return {
        "metric": "multichip_aggregate_records_per_sec",
        "value": round(rate_n, 1),
        "unit": "records/sec (sharded over the task mesh)",
        "n_devices": n_devices,
        "records_per_sec_1dev": round(rate_1, 1),
        "records_per_sec_sharded": round(rate_n, 1),
        "per_shard_records_per_sec": per_shard,
        "speedup": round(rate_n / rate_1, 3) if rate_1 else None,
        "scaling_efficiency": (round(rate_n / rate_1 / n_devices, 3)
                               if rate_1 else None),
        "digests_equal": not problems,
        "ledger_problems": problems[:8],
        "epochs_sealed": min(len(ledger_1), len(ledger_n)),
        "steps_per_epoch": SPE,
    }


def soak_probe(duration_s: float = 30.0):
    """Open-loop soak probe (``bench.py --soak [SECONDS]``): every
    other number this file prints is closed-loop — the driver pushes
    epochs back-to-back and measures how fast they drain. This probe is
    the open-loop counterpart: a token bucket releases load at a fixed
    rate (``BENCH_SOAK_RATE`` records/sec) whether or not the cluster
    keeps up, a seeded chaos schedule injects a kill cascade, a gray
    failure, and a leader-lease loss mid-run, and latency is charged
    from each chunk's *intended*-send instant — the
    coordinated-omission-corrected view. The exactly-once audit ledger
    is re-diffed against a fault-free control twin after every fault;
    any divergence fails the probe."""
    import tempfile

    from clonos_tpu.soak import (ChaosSchedule, SLOSpec, SoakConfig,
                                 SoakDriver, build_soak_fixture,
                                 default_kill_targets)

    rate = float(os.environ.get("BENCH_SOAK_RATE", 2000))
    seed = int(os.environ.get("BENCH_SOAK_SEED", 11))
    with tempfile.TemporaryDirectory() as td:
        # The probe's runner pipelines its fence (the deployment
        # stance); the control twin inside the fixture stays
        # sequential, so every audit diff is overlapped-vs-sequential
        # and chaos kills can land mid-fence-tail.
        runner, control, election = build_soak_fixture(
            td, rate=rate, duration_s=duration_s, seed=seed,
            overlap_epoch=True)
        schedule = ChaosSchedule.seeded(
            seed, duration_s, default_kill_targets(runner.job))
        driver = SoakDriver(
            runner, SoakConfig(rate=rate, duration_s=duration_s),
            schedule=schedule, spec=SLOSpec(),
            control=control, election=election)
        v = driver.run()
    return {
        "metric": "soak_corrected_p99_ms",
        "value": v["latency"]["p99_ms"],
        "unit": "ms from intended-send (coordinated-omission-free)",
        "pass": v["pass"],
        "rate_target": v["rate_target"],
        "rate_achieved": v["rate_achieved"],
        "duration_s": v["duration_s"],
        "latency": v["latency"],
        "windows_breached": v["windows_breached"],
        "worst_window": v["worst_window"],
        "faults": v["faults"],
        "audit": v["audit"],
        "schedule": v["schedule"],
        "truncated": v["truncated"],
        "census_fingerprint": v.get("census_fingerprint"),
    }


def serve_probe(duration_s: float = 20.0):
    """Read-path probe (``bench.py --serve [SECONDS]``): prices the
    read tier (runtime/serve.py) honestly, one JSON line.

    1. **Batched vs sequential** at the headline 32-subtask shape: the
       same lookups issued as sequential point queries and as batched
       reads against a tailed replica (one coalesced jitted gather per
       device dispatch). The acceptance bar is >= 5x.
    2. **Bit-identity**: replica-served values vs owner-served values
       for the same keys at the same epoch stamp — must match exactly.
    3. **Mixed load + degradation**: the soak driver pumps routed reads
       between ingest chunks with read-latency SLO windows, and a
       ``replica-kill`` chaos event mid-run must degrade (re-route to
       owner, staleness spike then recovery) with ZERO client-visible
       errors, audit still clean."""
    import gc
    import tempfile

    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP
    from clonos_tpu.runtime.serve import build_serve_tier
    from clonos_tpu.soak import (ServeLoad, SLOSpec, SoakConfig,
                                 SoakDriver, build_soak_fixture,
                                 parse_schedule)

    def reduce_vid(job):
        return next(v.vertex_id for v in job.vertices
                    if getattr(v.operator, "emits_running_value", False))

    # -- part 1+2: batched vs sequential + bit-identity, 32 subtasks --
    SPE = int(os.environ.get("BENCH_SERVE_SPE", 256))
    N_SEQ = int(os.environ.get("BENCH_SERVE_SEQ_READS", 256))
    N_BATCH = int(os.environ.get("BENCH_SERVE_BATCH_READS", 4096))
    CHUNK = 256
    job = build_job()
    vid = reduce_vid(job)
    runner = ClusterRunner(
        job, steps_per_epoch=SPE,
        log_capacity=1 << (2 * SPE * DETS_PER_STEP).bit_length(),
        max_epochs=16,
        inflight_ring_steps=1 << (2 * SPE - 1).bit_length(),
        block_steps=min(256, SPE), seed=7)
    # tier FIRST: replicas subscribe to the serve feed before any epoch
    # seals, so they tail every fence from the start
    tier = build_serve_tier(runner, vid, n_replicas=2)
    for _ in range(3):
        runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    rng = np.random.RandomState(13)
    rep = tier.clients[0]
    # warm the gather compile off the measured clock
    rep.query_batch(vid, [0])
    rep.query(vid, 0)
    keys_seq = rng.randint(0, 997, N_SEQ)
    t0 = time.monotonic()
    seq_out = [rep.query(vid, int(k)) for k in keys_seq]
    seq_s = time.monotonic() - t0
    keys_b = rng.randint(0, 997, N_BATCH)
    t0 = time.monotonic()
    batch_epochs = []
    batch_vals = {}
    for i in range(0, N_BATCH, CHUNK):
        chunk = [int(k) for k in keys_b[i:i + CHUNK]]
        out = rep.query_batch(vid, chunk)
        batch_epochs.append(out["epoch"])
        batch_vals.update(zip(chunk, out["values"]))
    batch_s = time.monotonic() - t0
    qps_seq = N_SEQ / seq_s if seq_s else 0.0
    qps_batch = N_BATCH / batch_s if batch_s else 0.0
    speedup = qps_batch / qps_seq if qps_seq else 0.0
    # bit-identity vs the owner at the same epoch stamp
    probe_keys = sorted(batch_vals)
    own = tier.owner_client.query_batch(vid, probe_keys)
    same_epoch = (own["epoch"] == batch_epochs[-1]
                  and all(e == own["epoch"] for e in batch_epochs))
    mismatches = [int(k) for k, ov in zip(probe_keys, own["values"])
                  if batch_vals[k] != ov]
    # point reads must agree with batched reads too
    point_ok = all(o["value"] == batch_vals.get(int(k), o["value"])
                   for k, o in zip(keys_seq, seq_out))
    replica_status = [r.status() for r in tier.replicas]
    dispatches = [ep.dispatches for ep in tier.endpoints]
    keys_served = [ep.keys_served for ep in tier.endpoints]
    tier.close()
    del runner, job
    gc.collect()

    # -- part 3: mixed read/ingest load with a replica-kill mid-run --
    rate = float(os.environ.get("BENCH_SERVE_RATE", 2000))
    slo_ms = float(os.environ.get("BENCH_SERVE_SLO_MS", 2000))
    with tempfile.TemporaryDirectory() as td:
        srun, control, election = build_soak_fixture(
            td, rate=rate, duration_s=duration_s, seed=11,
            overlap_epoch=True, serve_vertex=True)
        svid = reduce_vid(srun.job)
        stier = build_serve_tier(srun, svid, n_replicas=2,
                                 staleness_bound=2)
        load = ServeLoad(stier, svid, num_keys=101,
                         reads_per_pump=32, slo_ms=slo_ms)
        kill_at = round(0.4 * duration_s, 1)
        schedule = parse_schedule(f"at {kill_at}s replica-kill 0")
        driver = SoakDriver(
            srun, SoakConfig(rate=rate, duration_s=duration_s),
            schedule=schedule, spec=SLOSpec(),
            control=control, election=election, read_load=load)
        v = driver.run()
        stier.close()
    serve = v["serve"]
    audit_ok = bool(v["audit"]["exactly_once"])
    degraded_not_failed = (serve["errors"] == 0
                           and serve["reroutes"] > 0
                           and serve["staleness_peak"]
                           > serve["staleness_final"])

    return {
        "metric": "serve_batched_read_speedup",
        "value": round(speedup, 2),
        "unit": "batched replica reads vs sequential point queries "
                "(same keys, 32-subtask shape)",
        "pass": bool(speedup >= 5.0 and same_epoch and not mismatches
                     and point_ok and serve["ok"]
                     and degraded_not_failed and audit_ok),
        "read_qps_sequential": round(qps_seq, 1),
        "read_qps_batched": round(qps_batch, 1),
        "sequential_reads": N_SEQ,
        "batched_reads": N_BATCH,
        "batch_chunk": CHUNK,
        "device_dispatches": dispatches,
        "keys_served": keys_served,
        "bit_identical_vs_owner": not mismatches,
        "bit_identity_keys_checked": len(probe_keys),
        "bit_identity_mismatched_keys": mismatches[:8],
        "same_epoch_stamp": same_epoch,
        "point_vs_batch_consistent": point_ok,
        "replica_status": replica_status,
        "mixed_load": {
            "ingest_rate_target": v["rate_target"],
            "ingest_rate_achieved": v["rate_achieved"],
            "ingest_p99_ms": v["latency"]["p99_ms"],
            "serve": serve,
            "degraded_not_failed": degraded_not_failed,
            "audit": v["audit"],
            "schedule": v["schedule"],
        },
        "census_fingerprint": v.get("census_fingerprint"),
    }


def rescale_probe(duration_s: float = 12.0):
    """Elastic-repartition probe (``bench.py --rescale [SECONDS]``):
    prices a live 2->4 re-cut at a checkpoint fence, one JSON line.

    Throughput is sampled before and after the re-cut under the same
    epoch cadence, and the handoff itself is timed — the fence stall a
    paced client would see: drain + keyed-state migration + the
    new-shape restore point (the new incarnation's first-epoch compile
    is reported separately; it overlaps the stall only on a multi-core
    host). On a 1-core CI host doubling the keyed cut cannot raise
    throughput, so the honest acceptance bar is the exactly-once
    evidence, not a throughput win: the protocol transitions observed
    in fence -> drain -> migrate -> redirect order, every in-flight
    record drained and re-routed, the fenced-off incarnation refusing
    to run, and the post-re-cut ledger diffing EMPTY against a
    never-rescaled control via the key-group directory
    (obs/audit.diff_ledgers_cross) while the exact byte diff refuses —
    proof the mapped cross-layout path engaged, not a trivial pass."""
    import tempfile

    from clonos_tpu.causal import recovery as rec
    from clonos_tpu.obs import audit as audit_mod
    from clonos_tpu.obs.digest import diff_ledgers
    from clonos_tpu.soak import build_soak_fixture

    SPE = int(os.environ.get("BENCH_RESCALE_SPE", 32))
    EPOCHS = int(os.environ.get("BENCH_RESCALE_EPOCHS", 4))
    TARGET = int(os.environ.get("BENCH_RESCALE_TARGET", 4))
    PAR, BATCH = 2, 8                     # build_soak_fixture defaults
    per_epoch = SPE * PAR * BATCH
    with tempfile.TemporaryDirectory() as td:
        runner, control, _election = build_soak_fixture(
            td, rate=2000.0, duration_s=duration_s,
            steps_per_epoch=SPE, par=PAR, batch=BATCH, seed=11)
        # warm both epoch programs off the measured clock
        runner.run_epoch(complete_checkpoint=True)
        control.run_epoch(complete_checkpoint=True)
        runner.drain_fence()

        t0 = time.monotonic()
        for _ in range(EPOCHS):
            runner.run_epoch(complete_checkpoint=True)
        runner.drain_fence()
        before_s = time.monotonic() - t0

        # the live re-cut: everything between the old incarnation's
        # last fence and the new one being runnable is fence stall
        t0 = time.monotonic()
        new_runner, stats = runner._soak_rescaler(TARGET)
        stall_s = time.monotonic() - t0

        t0 = time.monotonic()
        new_runner.run_epoch(complete_checkpoint=True)
        new_runner.drain_fence()
        first_epoch_s = time.monotonic() - t0   # compile-dominated

        t0 = time.monotonic()
        for _ in range(EPOCHS):
            new_runner.run_epoch(complete_checkpoint=True)
        new_runner.drain_fence()
        after_s = time.monotonic() - t0

        # the fenced-off incarnation must refuse to double-apply
        stale_fenced = False
        try:
            runner.run_epoch()
        except rec.RecoveryError:
            stale_fenced = True

        # never-rescaled control reaches the same sealed epoch; the
        # cross-layout diff must be clean AND the exact diff must not
        # be (same mapping `clonos_tpu audit A --diff B` uses)
        while control.auditor.last_epoch < new_runner.auditor.last_epoch:
            control.run_epoch(complete_checkpoint=True)
        control.drain_fence()
        hi = new_runner.auditor.last_epoch
        expected = [e for e in control.auditor.ledger()
                    if e["epoch"] <= hi]
        actual = [e for e in new_runner.auditor.ledger()
                  if e["epoch"] <= hi]
        cross = audit_mod.diff_ledgers_cross(expected, actual)
        exact = diff_ledgers(expected, actual)

    kinds = [k for k, _ in stats["transitions"]]
    first = {k: kinds.index(k) for k in dict.fromkeys(kinds)}
    proto_ok = ("fence" in first and "migrate" in first
                and kinds[-1] == "redirect"
                and first["fence"] < first["migrate"]
                and kinds.count("migrate") == stats["groups"]
                and ("drain" not in first
                     or first["drain"] > first["fence"]))
    moved = stats["moved_key_groups"]
    stall_ms = stall_s * 1e3
    passed = bool(cross == [] and exact and stale_fenced and proto_ok
                  and moved and all(m > 0 for m in moved.values())
                  and hi > stats["fence_checkpoint"])
    out = {
        "metric": "rescale_live_recut",
        "value": round(stall_ms, 1),
        "unit": f"ms fence stall for a live {PAR}->{TARGET} keyed "
                f"re-cut (drain + migrate + new-shape restore point)",
        "pass": passed,
        "target_parallelism": TARGET,
        "steps_per_epoch": SPE,
        "epochs_each_side": EPOCHS,
        "throughput_before": round(EPOCHS * per_epoch / before_s, 1),
        "throughput_after": round(EPOCHS * per_epoch / after_s, 1),
        "fence_stall_ms": round(stall_ms, 1),
        "migrate_ms": round(stats["migrate_ms"], 1),
        "post_recut_first_epoch_ms": round(first_epoch_s * 1e3, 1),
        "drained_records": stats["drained_records"],
        "moved_key_groups": moved,
        "protocol_groups": stats["groups"],
        "transitions": kinds,
        "protocol_order_ok": proto_ok,
        "stale_writer_fenced": stale_fenced,
        "cross_ledger_diff_clean": cross == [],
        "cross_ledger_diff": cross[:4],
        "exact_diff_refuses": bool(exact),
        "exact_diff_lines": len(exact),
        "epochs_checked": len(actual),
        "note": "single-host CI shape: throughput_before/after share "
                "one core, so the re-cut prices the protocol (stall + "
                "exactly-once evidence), not a scaling win",
    }
    try:
        from clonos_tpu.analysis import census_fingerprint
        out["census_fingerprint"] = census_fingerprint()
    except Exception:                                 # pragma: no cover
        out["census_fingerprint"] = None
    return out


def spill_probe():
    """Tiered-storage probe (``bench.py --spill``): prices the spill
    fabric (clonos_tpu/storage/) three ways, one JSON line.

    1. **Steady state**: the same job three ways — spill OFF, spill ON
       under the ``availability`` policy (the production steady state:
       checkpoints complete every epoch, the ring keeps headroom, ring
       payloads stay put and only the small determinant windows move),
       and spill ON ``eager`` (the upper bound: every in-flight byte
       made durable every epoch). The 5% acceptance bound is
       availability vs off; eager is reported alongside — on a
       many-core host its writer thread overlaps compute, on this
       box's core count it shows up as foreground cost.
    2. **Deep backlog**: pending epochs accumulate until the replay
       span EXCEEDS device ring capacity, then a kill — recovery must
       refill the missing leading steps from the host/disk tiers.
       Timed, and verified bit-identical: the audit ledger diffs empty
       against a no-spill control run whose ring holds the whole span
       (``diff_ledgers == []``).
    3. **Tiers**: occupancy at the moment of the kill plus cumulative
       movement counters (the ``spill.*`` gauges' source), emitted as
       BENCH_r0N.json fields.
    """
    import gc
    import tempfile

    from clonos_tpu.obs.digest import diff_ledgers
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    SPE = int(os.environ.get("BENCH_SPILL_SPE", 512))
    EPOCHS = int(os.environ.get("BENCH_SPILL_EPOCHS", 3))
    FILL = int(os.environ.get("BENCH_SPILL_FILL_EPOCHS", 4))

    def steady(spool_dir, policy=None):
        job = build_job()
        need = (EPOCHS + 2) * SPE * DETS_PER_STEP
        # Ring holds 4 epochs so the availability policy has headroom:
        # with checkpoints completing every epoch, occupancy stays at
        # ~0.25 < the 0.3 trigger and nothing needs to move — the
        # production steady state. Same ring for every mode (eager's
        # cost is ring-size independent) so the comparison is fair.
        kw = dict(steps_per_epoch=SPE,
                  log_capacity=1 << need.bit_length(), max_epochs=16,
                  inflight_ring_steps=1 << (4 * SPE - 1).bit_length(),
                  block_steps=min(1024, SPE), seed=7)
        if spool_dir:
            kw["spool_dir"] = spool_dir
            kw["spill_policy"] = policy
        runner = ClusterRunner(job, **kw)
        runner.run_epoch(complete_checkpoint=True)    # compile warmup
        device_sync(runner.executor.carry)
        t0 = time.monotonic()
        for _ in range(EPOCHS):                       # pipelined
            runner.run_epoch(complete_checkpoint=True)
        device_sync(runner.executor.carry)
        wall = time.monotonic() - t0
        drain_s = 0.0
        if spool_dir:
            # The writer thread overlaps compute; what's LEFT in its
            # queue at the fence is the true async residue — timed
            # separately so steady state measures overlap, not total
            # spill bandwidth.
            t1 = time.monotonic()
            runner.executor.drain_spill()
            drain_s = time.monotonic() - t1
        rate = EPOCHS * SPE * PAR * BATCH / wall if wall else 0.0
        stats = dict(runner.executor.spill_stats()) if spool_dir else {}
        if spool_dir:
            stats["drain_residue_ms"] = round(drain_s * 1e3, 1)
        del runner, job
        gc.collect()
        return rate, stats

    def backlog_run(spool_dir, ring_steps, budget):
        job = build_job()
        need = (FILL + 2) * SPE * DETS_PER_STEP
        kw = dict(steps_per_epoch=SPE,
                  log_capacity=1 << need.bit_length(), max_epochs=16,
                  inflight_ring_steps=ring_steps,
                  block_steps=min(1024, SPE), seed=7,
                  logical_time=True, audit=True)
        if spool_dir:
            kw["spool_dir"] = spool_dir
            kw["spill_host_budget_epochs"] = budget
        runner = ClusterRunner(job, **kw)
        runner.run_epoch(complete_checkpoint=True)    # restore point
        for _ in range(FILL):                         # pending backlog
            runner.run_epoch(complete_checkpoint=False)
        device_sync(runner.executor.carry)
        return runner

    with tempfile.TemporaryDirectory() as td:
        rate_avail, avail_stats = steady(os.path.join(td, "a"),
                                         "availability")
    with tempfile.TemporaryDirectory() as td:
        rate_eager, eager_stats = steady(os.path.join(td, "e"), "eager")
    rate_off, _ = steady(None)
    overhead = ((rate_off - rate_avail) / rate_off) if rate_off else 0.0
    eager_overhead = ((rate_off - rate_eager) / rate_off
                      if rate_off else 0.0)

    # Deep backlog: the spill run's ring holds ONE epoch, the replay
    # span is FILL of them; host budget 1 forces most epochs disk-only.
    with tempfile.TemporaryDirectory() as td:
        r = backlog_run(os.path.join(td, "spill"),
                        ring_steps=1 << (SPE - 1).bit_length(), budget=1)
        r.executor.drain_spill()
        occupancy = r.executor.spill_occupancy()
        r.inject_failure([PAR + 1])                   # window subtask 1
        t0 = time.monotonic()
        report = r.recover()
        device_sync(r.executor.carry)
        backlog_recovery_ms = (time.monotonic() - t0) * 1e3
        move_stats = r.executor.spill_stats()
        ledger_spill = list(r.auditor.ledger())
        steps_replayed = report.steps_replayed
        ring_cap = 1 << (SPE - 1).bit_length()
        del r
        gc.collect()
    control = backlog_run(None,
                          ring_steps=1 << (FILL * SPE).bit_length(),
                          budget=0)
    ledger_ctrl = list(control.auditor.ledger())
    del control
    gc.collect()
    problems = diff_ledgers(ledger_ctrl, ledger_spill)

    return {
        "metric": "spill_throughput_overhead_fraction",
        "value": round(overhead, 6),
        "unit": "1 - rate(spill availability)/rate(spill off), steady "
                "state; eager upper bound reported alongside",
        "pass": bool(overhead <= 0.05 and not problems
                     and steps_replayed > ring_cap
                     and move_stats.get("disk_hits", 0) > 0),
        "steady_state_records_per_sec_spill_availability":
            round(rate_avail, 1),
        "steady_state_records_per_sec_spill_eager": round(rate_eager, 1),
        "steady_state_records_per_sec_spill_off": round(rate_off, 1),
        "eager_overhead_fraction": round(eager_overhead, 6),
        "steady_spill_stats": {"availability": avail_stats,
                               "eager": eager_stats},
        "backlog_recovery_ms": round(backlog_recovery_ms, 1),
        "backlog_steps_replayed": steps_replayed,
        "backlog_ring_capacity_steps": ring_cap,
        "backlog_exceeds_ring": bool(steps_replayed > ring_cap),
        "tier_occupancy_at_kill": occupancy,
        "spill_movement": move_stats,
        "digests_equal": not problems,
        "ledger_diff": problems[:8],
        "steps_per_epoch": SPE,
        "fill_epochs": FILL,
    }


def main(jobs=None, multichip=None, soak=None, ablate=False,
         spill=False, serve=None, rescale=None, overhead=False):
    global T_START
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # Every number this file prints is named as a device metric.
        print(f"bench.py measures the chip and JAX found only "
              f"{dev.platform} ({dev.device_kind}); refusing to run",
              file=sys.stderr)
        return 2
    if overhead:
        # --overhead: run ONLY the FT-overhead attribution probe (the
        # profiled section breakdown + the lineage on/off cost) — the
        # standalone escape hatch so a budget-starved headline run
        # never leaves the overhead numbers unmeasured.
        T_START = time.monotonic()
        print(json.dumps(overhead_probe()))
        return
    if rescale:
        # --rescale [SECONDS]: run ONLY the elastic-repartition probe
        # (one JSON line, same contract as the headline bench) and
        # persist it as the next free RESCALE_r0N.json artifact.
        from clonos_tpu.soak import next_rescale_artifact_path
        out = rescale_probe(float(rescale))
        path = next_rescale_artifact_path()
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        out["artifact"] = os.path.basename(path)
        print(json.dumps(out))
        return 0 if out["pass"] else 1
    if serve:
        # --serve [SECONDS]: run ONLY the read-path probe (one JSON
        # line, same contract as the headline bench) and persist it as
        # the next free SERVE_r0N.json artifact.
        from clonos_tpu.soak import next_serve_artifact_path
        out = serve_probe(float(serve))
        path = next_serve_artifact_path()
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        out["artifact"] = os.path.basename(path)
        print(json.dumps(out))
        return 0 if out["pass"] else 1
    if spill:
        # --spill: run ONLY the tiered-storage probe (one JSON line,
        # same contract as the headline bench).
        print(json.dumps(spill_probe()))
        return
    if ablate:
        # --ablate: run ONLY the no-FT ablation probe (one JSON line,
        # same contract as the headline bench).
        print(json.dumps(ablation_probe()))
        return
    if soak:
        # --soak [SECONDS]: run ONLY the open-loop soak probe (one JSON
        # line, same contract as the headline bench).
        print(json.dumps(soak_probe(float(soak))))
        return
    if multichip:
        # --multichip [N]: run ONLY the mesh-sharding probe (one JSON
        # line, same contract as the headline bench).
        print(json.dumps(multichip_probe(int(multichip))))
        return
    if jobs:
        # --jobs N: run ONLY the multi-job probe (one JSON line, same
        # contract as the headline bench).
        print(json.dumps(multi_job_probe(int(jobs))))
        return

    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP
    from clonos_tpu.causal import recovery as rec

    T_START = time.monotonic()
    job = build_job()
    # Log capacity sized to hold FILL_EPOCHS * STEPS_PER_EPOCH * 4 sync
    # rows plus control-plane determinants (SOURCE_CHECKPOINT per trigger).
    need = FILL_EPOCHS * STEPS_PER_EPOCH * DETS_PER_STEP
    cap = 1 << need.bit_length()
    # Ring sized to EXACTLY the fill span (power of two): doubling the
    # backlog must not double HBM — the ring holds precisely the
    # un-truncated window recovery can need.
    span = max(FILL_EPOCHS * STEPS_PER_EPOCH, 2)
    # The headline runs the PIPELINED fence (overlap_epoch=True): each
    # epoch's seal/ledger/checkpoint tail executes on the fence worker
    # while the next epoch's compute is already dispatched. The
    # sequential control below re-measures the same schedule with the
    # tail on the critical path.
    # max_epochs=32: the headline schedule (warm + 3+4 measured), the
    # same-runner sequential control (3+4), and the A-B-A overlap
    # re-measurement (3+4) stack to epoch 22 in ONE runner — per-epoch
    # index vectors are 4 bytes/epoch/log, so the headroom is free.
    runner = ClusterRunner(job, steps_per_epoch=STEPS_PER_EPOCH,
                           log_capacity=cap, max_epochs=32,
                           inflight_ring_steps=1 << (span - 1).bit_length(),
                           recovery_block_steps=8192,
                           block_steps=1024,
                           latency_marker_every=64,
                           seed=7,
                           overlap_epoch=True)

    t_warm0 = time.monotonic()
    runner.run_epoch(complete_checkpoint=True)    # epoch 0: restore point
    device_sync(runner.executor.carry)
    warm_epoch_s = time.monotonic() - t_warm0

    # Warm standby: deploy (= compile) the recovery programs up front, the
    # analog of the reference keeping standby tasks deployed and
    # state-refreshed (RunStandbyTaskStrategy). Off the failure path.
    prewarm_s = runner.prewarm_recovery()

    # Steady state is measured over PIPELINED epoch windows — no device
    # sync between epochs (a real deployment never waits for the device
    # per fence). The reported rate is the SUSTAINED aggregate across
    # all 3+FILL_EPOCHS epochs (total records / total wall, drill
    # excluded) — transient stalls average in rather than being
    # cherry-picked around.
    run_s = 0.0
    # Fence walls (global_step, monotonic_s) at each measured epoch's
    # dispatch return: the schedule anchor coordinated-omission
    # correction needs — a fence that blocked late makes every marker
    # sample in its epoch late too, which the markers alone never show.
    fence_walls = []
    t_w = time.monotonic()
    for i in range(3):                # completed epochs: logs truncate
        runner.run_epoch(complete_checkpoint=True)
        fence_walls.append((runner.global_step, time.monotonic()))
    device_sync(runner.executor.carry)
    run_s += time.monotonic() - t_w
    # Failover drill (standby rehearsal): one full multi-class recovery
    # with real replay work, leaving state bit-identical. After this the
    # first REAL failure pays no first-execution warmup — the
    # RunStandbyTaskStrategy "standbys run hot" capability, measured
    # below as recovery_time_cold_ms. (Run mid-data: after the first
    # fill epoch there are steps to replay.)
    t_w = time.monotonic()
    runner.run_epoch(complete_checkpoint=False)
    fence_walls.append((runner.global_step, time.monotonic()))
    device_sync(runner.executor.carry)
    run_s += time.monotonic() - t_w
    drill_s = runner.failover_drill()
    device_sync(runner.executor.carry)
    t_w = time.monotonic()
    for _ in range(FILL_EPOCHS - 1):
        runner.run_epoch(complete_checkpoint=False)
        fence_walls.append((runner.global_step, time.monotonic()))
    device_sync(runner.executor.carry)
    run_s += time.monotonic() - t_w
    throughput = ((3 + FILL_EPOCHS) * STEPS_PER_EPOCH * PAR * BATCH
                  / run_s)

    buffered = int(np.sum(runner.executor.log_sizes()))

    # Sequential control: the SAME runner, back-to-back, re-measured
    # over the identical epoch schedule with per-call
    # overlap_fence=False — the strict-order fence tail (health read,
    # snapshot trigger, source append) on the critical path. Same
    # process, same warm state, same memory: the only variable is the
    # fence mode. headline / control is the pipelined fence's
    # steady-state delta; the control never writes fence.overlap-saved.
    runner.drain_fence()    # join the last overlapped tail off-clock
    # Off-clock ring reset: the fill epochs left the ring exactly full
    # (that's the point — recovery below replays them), so the control
    # epochs would overflow it. Completing the NEWEST pending fence
    # truncates the ring through it without running a single step;
    # older pendings are discarded first (completing them late would
    # regress the truncation watermark — same barrier the soak driver
    # uses pre-kill).
    runner.coordinator.drain()            # async snapshot writes durable
    last_fence = runner.executor.epoch_id - 1
    runner.coordinator.discard_pending_through(last_fence - 1)
    runner.coordinator.ack_all(last_fence)
    device_sync(runner.executor.carry)
    t_c = time.monotonic()
    for _ in range(3):
        runner.run_epoch(complete_checkpoint=True, overlap_fence=False)
    for _ in range(FILL_EPOCHS):
        runner.run_epoch(complete_checkpoint=False, overlap_fence=False)
    device_sync(runner.executor.carry)
    ctrl_s = time.monotonic() - t_c
    throughput_ctrl = ((3 + FILL_EPOCHS) * STEPS_PER_EPOCH * PAR * BATCH
                       / ctrl_s)
    assert "fence.overlap-saved" not in runner.last_fence_phases, \
        "sequential control must never write the overlap key"

    # A-B-A: re-measure the PIPELINED mode after the control. On this
    # host a ~20-minute single-core process drifts run-to-run by more
    # than the fence tail costs, and whichever mode runs later measures
    # warmer — comparing A2 against the control (adjacent windows)
    # bounds that bias in the artifact itself instead of pretending the
    # first A and B were exchangeable.
    budget_s = float(os.environ.get("BENCH_MAX_S", 1500))
    # The tail of the budget is RESERVED for the overhead probe:
    # BENCH_r06 let the secondary configs eat the whole budget and the
    # probe starved ({"skipped": ...}). Everything optional before the
    # probe now stops at soft_budget_s so the probe always gets its
    # slice; `bench.py --overhead` runs it standalone besides.
    overhead_reserve_s = float(
        os.environ.get("BENCH_OVERHEAD_RESERVE_S", 180))
    soft_budget_s = max(0.0, budget_s - overhead_reserve_s)
    throughput_rerun = None
    if time.monotonic() - T_START <= soft_budget_s:
        runner.coordinator.drain()
        last_fence = runner.executor.epoch_id - 1
        runner.coordinator.discard_pending_through(last_fence - 1)
        runner.coordinator.ack_all(last_fence)
        device_sync(runner.executor.carry)
        t_r = time.monotonic()
        for _ in range(3):
            runner.run_epoch(complete_checkpoint=True)
        for _ in range(FILL_EPOCHS):
            runner.run_epoch(complete_checkpoint=False)
        device_sync(runner.executor.carry)
        throughput_rerun = ((3 + FILL_EPOCHS) * STEPS_PER_EPOCH * PAR
                            * BATCH / (time.monotonic() - t_r))
        runner.drain_fence()   # join the last tail before the kill below

    failed_flat = PAR + 1     # window vertex, subtask 1
    runner.inject_failure([failed_flat])
    t0 = time.monotonic()
    report = runner.recover()
    device_sync(runner.executor.carry)
    cold_recovery_s = time.monotonic() - t0

    # Recovery-time-to-resume, steady state: fail the same subtask again —
    # the full protocol (determinant fetch, input reconstruction, replay,
    # verify, patch, replica rebuild) on prewarmed programs. Min sheds
    # host-scheduling noise; the mean is reported alongside. Phases and
    # the headline come from the SAME run and statistic: the best run's
    # own report feeds
    # recovery_phase_ms (BENCH_r05 mixed the cold run's breakdown with
    # the warm minimum, so sub-phases summed past the headline).
    warm_runs = []                                # (seconds, report)
    for _ in range(3):
        runner.inject_failure([failed_flat])
        t2 = time.monotonic()
        rep_w = runner.recover()
        device_sync(runner.executor.carry)
        warm_runs.append((time.monotonic() - t2, rep_w))
    warm_recovery_s, warm_report = min(warm_runs, key=lambda sr: sr[0])
    warm_recovery_runs = [s for s, _ in warm_runs]

    # Bit-identity of the overlapped pipeline vs a strictly sequential
    # control: digest the live state over the replayed window after the
    # overlapped runs, recover the same failure once more with
    # overlap_finalize=False (the pre-PR12 ordering), digest again, and
    # diff. An empty diff says the overlap changed WHEN finalize work
    # ran, not WHAT state the job resumed on.
    from clonos_tpu.causal.recovery import AuditValidator
    from clonos_tpu.obs.digest import diff_ledgers
    audit_epochs = list(range(warm_report.from_epoch,
                              runner.executor.epoch_id))
    _val = AuditValidator(runner.executor, [])
    entries_overlap = _val.recompute_entries(audit_epochs)
    runner.inject_failure([failed_flat])
    t2 = time.monotonic()
    runner.recover(overlap_finalize=False)
    device_sync(runner.executor.carry)
    seq_recovery_s = time.monotonic() - t2
    entries_seq = _val.recompute_entries(audit_epochs)
    ledger_diff = diff_ledgers(entries_seq, entries_overlap)

    # Warm replay rate: re-run the device replay on the same plan (the cold
    # number includes XLA compilation of the replay scan; steady-state
    # recovery of subsequent failures reuses the compiled program).
    mgr = report.managers[0]
    replayer = mgr.replayer
    warm_replay_runs = []
    for _ in range(5):
        t1 = time.monotonic()
        result = replayer.replay(mgr.plan)
        device_sync(result.emit_counts)
        warm_replay_runs.append(time.monotonic() - t1)
    warm_replay_s = min(warm_replay_runs)

    records_per_sec = (report.records_replayed / warm_replay_s
                       if warm_replay_s > 0 else 0.0)
    dets_per_sec = (report.steps_replayed * DETS_PER_STEP / warm_replay_s
                    if warm_replay_s > 0 else 0.0)

    out = {
        "metric": "recovery_replay_records_per_sec",
        "value": round(records_per_sec, 1),
        "unit": "records/sec (~= JVM determinants/sec)",
        "vs_baseline": round(records_per_sec / JVM_BASELINE_RECORDS_PER_SEC,
                             3),
        "replay_determinant_rows_per_sec": round(dets_per_sec, 1),
        "recovery_time_cold_ms": round(cold_recovery_s * 1e3, 1),
        "recovery_time_warm_ms": round(warm_recovery_s * 1e3, 1),
        "recovery_time_warm_mean_ms": round(
            1e3 * sum(warm_recovery_runs) / len(warm_recovery_runs), 1),
        "recovery_time_warm_sequential_ms": round(seq_recovery_s * 1e3, 1),
        # diff_ledgers(sequential-control digests, overlapped digests)
        # over the replayed epoch window — [] proves the overlapped
        # recovery left bit-identical state.
        "ledger_diff_vs_sequential_control": ledger_diff,
        "prewarm_standby_s": round(prewarm_s, 1),
        "failover_drill_s": round(drill_s, 1),
        "replay_time_warm_ms": round(warm_replay_s * 1e3, 1),
        "replay_time_warm_mean_ms": round(
            1e3 * sum(warm_replay_runs) / len(warm_replay_runs), 1),
        "vs_baseline_mean": round(
            report.records_replayed
            / (sum(warm_replay_runs) / len(warm_replay_runs))
            / JVM_BASELINE_RECORDS_PER_SEC, 3),
        # Same-run statistic: the BEST warm run's own breakdown (its
        # values sum to ~recovery_time_warm_ms), the per-phase mean
        # across all warm runs, and the cold run's breakdown under its
        # own explicitly-cold key.
        "recovery_phase_ms": {k: round(v, 1)
                              for k, v in warm_report.phase_ms.items()},
        "recovery_phase_mean_ms": {
            k: round(sum(r.phase_ms.get(k, 0.0) for _s, r in warm_runs)
                     / len(warm_runs), 1)
            for k in sorted({k for _s, r in warm_runs for k in r.phase_ms})},
        "recovery_phase_cold_ms": {k: round(v, 1)
                                   for k, v in report.phase_ms.items()},
        # The finalize mystery, attributable: named sub-spans of the
        # finalize phase (barrier read, state verify, and — on standby
        # bootstraps — rehydrate/reattach/reregister/recompile), plus
        # finalize.overlap-saved: wall time the overlapped tail removed
        # from the critical path (sum(sub-spans) - saved == finalize).
        "finalize_phase_ms": {k: round(v, 1)
                              for k, v in warm_report.phase_ms.items()
                              if k == "finalize"
                              or k.startswith("finalize.")},
        "finalize_overlap_saved_ms": round(
            warm_report.phase_ms.get("finalize.overlap-saved", 0.0), 1),
        "steps_replayed": report.steps_replayed,
        "records_replayed": report.records_replayed,
        "buffered_determinants_cluster": buffered,
        "steady_state_records_per_sec": round(throughput, 1),
        # Cumulative wall time the pipelined fence removed from the
        # critical path across every overlapped epoch above:
        # sum over epochs of max(0, sum(fence.* sub-spans) - joined
        # tail wall). The per-epoch identity
        # sum(fence.*) - overlap-saved == fence-tail always holds.
        "fence_overlap_saved_ms": round(
            runner.fence_overlap_saved_total_ms, 1),
        # Same-runner strict-order re-measurement: the identical epoch
        # schedule re-run back-to-back on the SAME warm runner with
        # overlap_fence=False, so the only variable is the fence mode
        # (a separately built runner drifts ~10% from ordering/warm
        # state alone on a 1-core host).
        "steady_state_records_per_sec_sequential_control": round(
            throughput_ctrl, 1),
        # The A-B-A overlap re-measurement adjacent to the control:
        # rerun vs control is the drift-bounded mode comparison; the
        # headline vs control spans ~15 minutes of warm-up drift.
        "steady_state_records_per_sec_overlap_rerun": (
            round(throughput_rerun, 1)
            if throughput_rerun is not None else None),
        "subtasks": job.total_subtasks(),
        "device": str(jax.devices()[0].platform),
        # Latency markers (causal-RNG scheduled, replay-stable): pipeline
        # transit time source->sink in causal-time ms. The marker number
        # is CLOSED-LOOP: epochs are pushed back-to-back, so a fence that
        # ran long delays every later record's send without the marker
        # ever seeing it (coordinated omission). "corrected" re-charges
        # each sample the queueing delay of its epoch's fence against a
        # fixed-rate schedule anchored at the first measured fence —
        # the open-loop view (`bench.py --soak` measures it directly).
        "latency_markers": {
            "count": runner.latency.hist.count,
            "p50_ms": runner.latency.hist.quantile(0.5),
            "p99_ms": runner.latency.hist.quantile(0.99),
            "corrected": _soak_slo.corrected_closed_loop(
                runner.latency.samples, fence_walls,
                STEPS_PER_EPOCH, PAR * BATCH),
            "note": "p50/p99 = in-pipeline dwell (closed-loop); "
                    "corrected = dwell + fence queueing delay vs a "
                    "fixed-rate schedule (open-loop equivalent)",
        },
    }
    # Free the headline runner's device state BEFORE the secondary
    # configs build theirs — two multi-GB carries do not coexist on one
    # chip (jax frees buffers on GC).
    import gc
    del runner, report, mgr, replayer, result, warm_runs, warm_report
    # _val retains the executor (and its carry) — dropping `runner`
    # alone would keep the device state alive through the secondary
    # configs below.
    del _val, entries_overlap, entries_seq
    gc.collect()
    # Fence bit-identity at the full 32-subtask shape: two short
    # AUDITED runs of the same job/seed/schedule — pipelined vs strict
    # sequential — then diff their durable digest ledgers. [] proves
    # the overlap changed WHEN the seal/ledger/checkpoint tail ran,
    # never WHAT it recorded.
    if time.monotonic() - T_START > budget_s:
        out["fence_ledger_diff_vs_sequential_control"] = None
    else:
        try:
            import tempfile
            from clonos_tpu.obs.digest import diff_ledgers

            def _audited_ledger(overlap):
                with tempfile.TemporaryDirectory() as td:
                    r = ClusterRunner(job, steps_per_epoch=256,
                                      log_capacity=4096, max_epochs=8,
                                      inflight_ring_steps=1024,
                                      block_steps=256, seed=7,
                                      logical_time=True, audit=True,
                                      checkpoint_dir=td,
                                      overlap_epoch=overlap)
                    r.run_epoch(complete_checkpoint=True)
                    r.run_epoch(complete_checkpoint=False)
                    r.run_epoch(complete_checkpoint=True)
                    r.drain_fence()
                    entries = r.coordinator.read_ledger()
                del r
                gc.collect()
                return entries

            out["fence_ledger_diff_vs_sequential_control"] = diff_ledgers(
                _audited_ledger(False), _audited_ledger(True))
        except Exception as e:                        # pragma: no cover
            out["fence_ledger_diff_vs_sequential_control"] = \
                {"error": str(e)}
    # Secondary BASELINE configs (#4 cascading, #5 join + external-service
    # calls) and the determinant-sharing-depth trade-off sweep. Guarded by
    # a wall-clock budget so the primary metric always prints.
    for key, fn in (("config4_kafka_window_64task_cascading",
                     bench_config4),
                    ("config5_join_128task_external_services",
                     bench_config5)):
        if time.monotonic() - T_START > soft_budget_s:
            out[key] = {"skipped": "bench wall-clock budget exhausted"}
            continue
        try:
            out[key] = fn()
        except Exception as e:                        # pragma: no cover
            out[key] = {"error": str(e)}
        gc.collect()
    try:
        out["sharing_depth_sweep"] = sharing_depth_sweep()
    except Exception as e:                            # pragma: no cover
        out["sharing_depth_sweep"] = {"error": str(e)}
    # FT-overhead attribution probe (profiled, serialized dispatch —
    # never shares the pipelined headline run). Hoists the headline
    # fraction to the top level for dashboards. Runs inside its own
    # reserved slice (see soft_budget_s above) — only a headline run
    # that itself blew through the FULL budget skips it.
    if time.monotonic() - T_START > budget_s:
        out["overhead_probe"] = {"skipped": "bench wall-clock budget "
                                            "exhausted"}
        out["overhead_ft_fraction"] = None
    else:
        try:
            out["overhead_probe"] = overhead_probe()
            out["overhead_ft_fraction"] = \
                out["overhead_probe"]["overhead_ft_fraction"]
        except Exception as e:                        # pragma: no cover
            out["overhead_probe"] = {"error": str(e)}
            out["overhead_ft_fraction"] = None
    # The FT call-site population these numbers were measured against
    # (analysis/census.py): ties the artifact to the exact source shape.
    try:
        from clonos_tpu.analysis import census_fingerprint
        out["census_fingerprint"] = census_fingerprint()
    except Exception:                                 # pragma: no cover
        out["census_fingerprint"] = None
    print(json.dumps(out))
    failed = sorted(k for k, v in out.items()
                    if isinstance(v, dict) and "error" in v)
    if failed:
        print(f"bench phases failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=None,
                    help="run the multi-job throughput probe with N "
                         "concurrent jobs instead of the headline bench")
    ap.add_argument("--multichip", type=int, nargs="?", const=8,
                    default=None, metavar="N",
                    help="run the mesh-sharding probe over N devices "
                         "(fails when the host has fewer) instead of "
                         "the headline bench")
    ap.add_argument("--soak", type=float, nargs="?", const=30.0,
                    default=None, metavar="SECONDS",
                    help="run the open-loop soak probe (fixed-rate "
                         "load + seeded chaos + exactly-once audit) "
                         "instead of the headline bench")
    ap.add_argument("--overhead", action="store_true",
                    help="run ONLY the FT-overhead attribution probe "
                         "(profiled section breakdown + lineage "
                         "on/off cost) instead of the headline bench")
    ap.add_argument("--ablate", action="store_true",
                    help="run the no-FT ablation probe (twin executor "
                         "head-to-head, measured vs static ft-fraction) "
                         "instead of the headline bench")
    ap.add_argument("--spill", action="store_true",
                    help="run the tiered-storage probe (steady-state "
                         "throughput spill on vs off + deep-backlog "
                         "disk-tier recovery, audit-verified) instead "
                         "of the headline bench")
    ap.add_argument("--serve", type=float, nargs="?", const=20.0,
                    default=None, metavar="SECONDS",
                    help="run the read-path probe (batched replica "
                         "reads vs sequential point queries, "
                         "bit-identity vs the owner, mixed read/ingest "
                         "load with a replica-kill) instead of the "
                         "headline bench; writes SERVE_r0N.json")
    ap.add_argument("--rescale", type=float, nargs="?", const=12.0,
                    default=None, metavar="SECONDS",
                    help="run the elastic-repartition probe (live 2->4 "
                         "re-cut at a checkpoint fence under load: "
                         "throughput before/after, fence-stall cost, "
                         "cross-layout ledger diff vs a never-rescaled "
                         "control) instead of the headline bench; "
                         "writes RESCALE_r0N.json")
    _a = ap.parse_args()
    sys.exit(main(jobs=_a.jobs, multichip=_a.multichip, soak=_a.soak,
                  ablate=_a.ablate, spill=_a.spill, serve=_a.serve,
                  rescale=_a.rescale, overhead=_a.overhead))
