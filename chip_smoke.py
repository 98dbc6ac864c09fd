#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process, one pass over the main path — ``StreamEnvironment`` ->
``ClusterRunner``: host feed in, fused block program, keyed exchange,
determinant log, in-flight ring, checkpoint fence, kill, causal recovery,
committed sink out — on whatever TPU JAX finds, with nothing that lets
it pass off the device:

  K  the kernels alone at deployment width: the Pallas histogram against
     the XLA scatter and NumPy at the cells' call shapes (and which form
     each call took: the ``hist.kernel`` instants), the counting
     exchange (both placements, kernel and scatter, and a block over the
     scratch budget counted in chunks of steps; with four chips also the
     mesh cell's own block, sharded over the task mesh) against the
     per-step exchange, the MXU one-hot gather against NumPy over the
     whole int32 range, and that ``jax.block_until_ready`` returns only
     when the work is done.
  A  the served path, host-fed, at config4's recorded width
     (``BASELINE.json.configs[3]``): 64 subtasks,
     a cascading kill of one source, one window and one reduce subtask
     mid-epoch, recovery. Pass = the committed stream equals a NumPy fold
     of the same fed records written here, every record exactly once,
     and the audit ledger shows no divergence.
  B  the device-source headline deployment (32 subtasks,
     5.24 GiB of carry on the device, wall-clock causal time, pipelined
     fence): kill one window subtask over two un-truncated epochs,
     recover. Pass = recovery's bit-identity verification and the audit
     validator; peak HBM is printed.
  J  the window join, its tables on own columns. Alone, at a
     deployment's ids (4,096 keys x 2 open windows a side, 640 own
     columns a subtask of 8, bound as the planner binds them): its
     block form — lookup, placements and compaction through the
     histogram kernel — against its step form (scatter-adds, step by
     step), state and rows bit for bit, over blocks that split windows,
     a stretch with one input silent and records of keys the subtask
     does not own. Then inside a job's block program (D17: a block
     form right alone is not verified): the benchmark's
     ``nexmark-window-join`` job at its tiny stand-in's sizes over
     1,024 ids (384 own columns a subtask, derived by ``window_join``),
     once in blocks of 1,024 steps and once in blocks of 16. Pass = both
     committed streams equal the topology's NumPy reference, nothing
     late, nothing dropped. Not in the default parts.
  S  session windows inside a job's block program: bids of a moving hot
     bidder and 1,010 cold ones cut into sessions by a gap of 1,000 ms
     (splits, two open sessions a bidder, bridges), once in blocks of
     1,024 steps and once in blocks of 16. Pass = the two committed
     streams are equal, no loss. The forms agree alone on the CPU
     (tests/test_user_sessions.py); what only the chip's compiler can get
     wrong is the block form fused into a whole block program (PR 40: a
     restarting sum came out wrong there from step 640 on, and not
     alone). Then the same at 8 subtasks and 512 bids a step, receive
     windows of 512 slots: wide enough for the lookup's head and tails
     (PR 49; the hot bidder's owner is sent ~390 bids a step, past the
     128-slot head), and a third run in blocks of 1,024 steps with the
     split switched off, every slot compared: all three streams equal,
     no block on the dense branch in the first two. Then the three again
     with three hot bidders, a quarter of a step's bids each: a step
     with three targets past the head sends its block down the ``cond``'s
     dense branch, inside the block program, and the blocks of 1,024
     steps must take it. Not in the default parts.
  I  the incremental join (NEXmark query 3) inside a job's block program:
     the benchmark's ``nexmark-local-items`` job at its tiny stand-in's
     sizes (persons that expire inside the run, auctions that come after
     them and wait, auctions flushed by a person a step later; chunks that are
     quiet and chunks that run step by step), once in blocks of 1,024
     steps and once in blocks of 16. Pass = the two committed streams
     are equal, and equal to the topology's NumPy reference, no loss.
     Run it on the chip after any change to the join's block form (D17:
     a block form right alone is not verified). Not in the default parts.
  Q  the join whose interval each key takes from its own data and the
     exact windowed mean (NEXmark query 4) inside a job's block program:
     the benchmark's ``nexmark-average-price`` job at its tiny stand-in's
     sizes (bids that wait for their auction, duplicate auctions, bids on
     expired and never-opened ids and under the reserve, auctions no bid
     counted for), once in blocks of 1,024 steps and once in blocks of
     16. Pass = both committed streams equal the topology's NumPy
     reference and the join's totals the reference's, no loss. Run it on
     the chip after any change to either block form (D17). Not in the
     default parts.
  U  the union inside a job's block program: the benchmark's
     ``allround-event-time`` job at ``allround-upstream``'s own widths
     (8 subtasks, the tumbling window's rows over a static route 256
     wide and the sliding window's over one 384 wide, into 256) in
     blocks of 1,024 steps, its union packed by rank (the block form: a
     running count and three keyed histograms) and once more with the
     union's block form patched to the scan of its step form (a stable
     sort and four gathers a step), and a third time with the keyed-
     state mapper's read-back patched to its gather form (the job's 200
     keys take the dense compare over the key lanes; PR 52). Pass = all
     three committed streams equal the topology's NumPy reference. Then
     a union that overflows: two keyed streams of ~96 and ~64 records a
     subtask a step into 160, the union's two forms, equal streams and
     fewer rows than records. Run it on the chip after any change to
     ``UnionOperator`` or to ``KeyedReduceOperator.process_block``
     (D17). Not in the default parts.
  X  ``nexmark-q3-x4`` at the cell's own shape, when there are four
     chips: the carry built under its shardings (16 GiB, 14 of them one
     leaf of 3,584 replica logs: every chip a quarter of every sharded
     leaf, none ever a leaf whole), a warm epoch, the prewarm, two epochs
     whose checkpoints stay pending, then the connected failure of one
     subtask of every vertex on the auctions' path and its recovery on
     the mesh, the join's two inputs re-routed from two rings, one of
     them rebuilt by a victim upstream. Pass = the committed stream,
     before and after, equals the topology's NumPy reference, no loss,
     and the fullest chip holds about a quarter of the carry. Not in the
     default parts.
  C  job A again under a four-chip task mesh, when there are four chips.
     Pass = committed stream byte-identical to A's, ledgers equal, every
     sharded carry leaf on four devices at a quarter each.

Exits non-zero, printing no result, when JAX finds no TPU. Times printed
are host walls for orientation, under no metric's name. The last line of
stdout is ``{"ok": true, "device": {...}}``.
"""

import argparse
import collections
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


def say(msg: str) -> None:
    print(msg, flush=True)


# --- part A/C: the served deployment ----------------------------------------


@dataclasses.dataclass(frozen=True)
class ServedShape:
    """config4's width (``BASELINE.json.configs[3]``). Two differences
    from that record so that answers exist to be checked: the window
    closes every ``window_steps`` (its 1 << 30 never fires, so its reduce
    and sink see nothing) and values are drawn from the seed."""

    parallelism: int = 16
    batch: int = 32
    num_keys: int = 499
    edge_capacity: int = 512
    steps_per_epoch: int = 1024
    window_steps: int = 64
    #: single steps run into the kill epoch before the kill (half the
    #: epoch, so what remains is whole blocks)
    kill_after: int = 512
    #: warm + two + the kill epoch + one more
    epochs: int = 5

    @property
    def total_steps(self) -> int:
        return self.epochs * self.steps_per_epoch


def make_feed(shape: ServedShape, seed: int) -> np.ndarray:
    """``[P, total_steps * batch, 2]`` int32 (key, value) records: uniform
    keys, values in [1, 2^18) so window sums pass 2^24 (the one-hot
    gathers must be exact past an f32 mantissa) and never cancel to 0."""
    rng = np.random.RandomState(seed)
    n = shape.total_steps * shape.batch
    keys = rng.randint(0, shape.num_keys, (shape.parallelism, n))
    vals = rng.randint(1, 1 << 18, (shape.parallelism, n))
    return np.stack([keys, vals], axis=-1).astype(np.int32)


def reference_committed(feed: np.ndarray, batch: int, window_steps: int,
                        steps_run: int) -> np.ndarray:
    """What the sink must have committed after ``steps_run`` supersteps,
    as sorted ``[n, 3]`` (key, running sum, window end) rows — a plain
    NumPy fold, independent of ``clonos_tpu``.

    Source subtask p emits records ``[s*batch, (s+1)*batch)`` of its
    partition at step s; causal time is the step index. Edges are one
    step deep, so that batch reaches the window at step s+1 and counts
    into window ``(s+1) // W``. Window w closes at step ``(w+1)*W``,
    emitting per key its sum of values (if nonzero) stamped
    ``(w+1)*W``; the reduce adds it to the key's running sum one step
    later and the sink sees it one step after that."""
    w = window_steps
    n_closed = max(0, (steps_run - 3) // w)
    keys = feed[:, :, 0]
    win = np.broadcast_to((np.arange(feed.shape[1]) // batch + 1) // w,
                          keys.shape)
    live = win < n_closed
    sums = np.zeros((n_closed, int(keys.max()) + 1), np.int64)
    np.add.at(sums, (win[live], keys[live]),
              feed[:, :, 1][live].astype(np.int64))
    sums = sums.astype(np.int32)                    # the device's wrap
    running = np.cumsum(sums, axis=0, dtype=np.int64).astype(np.int32)
    wi, ki = np.nonzero(sums)
    rows = np.stack([ki, running[wi, ki], (wi + 1) * w],
                    axis=1).astype(np.int32)
    return sort_rows(rows)


def sort_rows(rows: np.ndarray) -> np.ndarray:
    return rows[np.lexsort((rows[:, 1], rows[:, 2], rows[:, 0]))]


def build_served_job(shape: ServedShape):
    from clonos_tpu.api.environment import StreamEnvironment

    p = shape.parallelism
    env = StreamEnvironment(name="smoke-served", num_key_groups=64,
                            default_edge_capacity=shape.edge_capacity)
    (env.host_source(batch_size=shape.batch, parallelism=p)
        .key_by().window_count(num_keys=shape.num_keys,
                               window_size=shape.window_steps,
                               parallelism=p)
        .key_by().reduce(num_keys=shape.num_keys, parallelism=p)
        .sink(parallelism=p, transactional=True))
    return env.build()


def run_served(shape: ServedShape, feed: np.ndarray, ckpt_dir: str,
               seed: int, mesh=None) -> dict:
    """Drive the served deployment through warm epoch, prewarm, two
    epochs, a mid-epoch cascading kill, recovery, the rest of that epoch
    and one more; return what came out and the live runner."""
    from clonos_tpu.api.feeds import ListFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner

    job = build_served_job(shape)
    spe, p = shape.steps_per_epoch, shape.parallelism
    t0 = time.monotonic()
    runner = ClusterRunner(
        job, steps_per_epoch=spe,
        log_capacity=1 << (spe * 8 - 1).bit_length(), max_epochs=16,
        inflight_ring_steps=1 << (spe - 1).bit_length(), seed=seed,
        logical_time=True, audit=True, checkpoint_dir=ckpt_dir, mesh=mesh)
    runner.executor.register_feed(0, ListFeedReader(list(feed)))
    runner.run_epoch(complete_checkpoint=True)
    prewarm_s = runner.prewarm_recovery()
    warm_s = time.monotonic() - t0
    for _ in range(shape.epochs - 3):
        runner.run_epoch(complete_checkpoint=True)
    for _ in range(shape.kill_after):
        runner.step()
    # One subtask of every class on one path (a cascading kill).
    victims = [2 % p, job.subtask_base(1) + (3 % p),
               job.subtask_base(2) + (7 % p)]
    runner.inject_failure(victims)
    t1 = time.monotonic()
    report = runner.recover()
    recover_s = time.monotonic() - t1
    runner.run_epoch(complete_checkpoint=True)
    runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    sink_vid = next(iter(runner.txn_logs))
    return {
        "runner": runner, "report": report,
        "committed": runner.txn_logs[sink_vid].committed_stream(),
        "ledger": runner.coordinator.read_ledger(),
        "warm_s": warm_s, "prewarm_s": prewarm_s, "recover_s": recover_s,
        "steps_run": runner.global_step,
    }


def check_served(shape: ServedShape, feed: np.ndarray, res: dict) -> None:
    """Part A's pass condition; raises on any miss."""
    if res["steps_run"] != shape.total_steps:
        raise AssertionError(
            f"ran {res['steps_run']} steps, meant {shape.total_steps}")
    want = reference_committed(feed, shape.batch, shape.window_steps,
                               res["steps_run"])
    got = sort_rows(np.asarray(res["committed"], np.int32))
    if want.shape[0] == 0:
        raise AssertionError("reference is empty: nothing to check")
    if got.shape != want.shape or not np.array_equal(got, want):
        raise AssertionError(
            f"committed stream != NumPy reference: {got.shape[0]} rows "
            f"committed, {want.shape[0]} expected"
            + (f"; first difference at sorted row "
               f"{int(np.nonzero((got != want).any(axis=1))[0][0])}"
               if got.shape == want.shape else ""))
    # The kill falls in the open epoch and every earlier checkpoint
    # completed, so no CLOSED epoch is replayed: the validator has
    # nothing to recompute here (part B gives it two epochs) and the
    # ledger's own verdict is what counts.
    check_audit(res["runner"], res["report"], min_validated=0)


def job_counter(runner, name: str) -> int:
    return runner.metrics.group(f"job.{runner.job.name}").counter(
        name).value


def check_audit(runner, report, min_validated: int) -> None:
    div = job_counter(runner, "audit.divergences")
    val = job_counter(runner, "audit.epochs-validated")
    if div != 0 or val < min_validated:
        raise AssertionError(
            f"audit: {div} divergences, {val} epochs validated")
    if report.steps_replayed < 1 or report.records_replayed < 1:
        raise AssertionError(
            f"recovery replayed nothing: {report.steps_replayed} steps, "
            f"{report.records_replayed} records")


# --- part B: the headline deployment ----------------------------------------

HEADLINE_PAR, HEADLINE_BATCH = 8, 128
HEADLINE_SPE, HEADLINE_FILL = 4096, 4


def build_headline_job():
    """The headline job: an on-device synthetic source, a count window
    that never closes, a reduce and a sink."""
    from clonos_tpu.api.environment import StreamEnvironment

    env = StreamEnvironment(name="bench-allround", num_key_groups=64,
                            default_edge_capacity=1024)
    (env.synthetic_source(vocab=997, batch_size=HEADLINE_BATCH,
                          parallelism=HEADLINE_PAR)
        .key_by()
        .window_count(num_keys=997, window_size=1 << 30, name="window")
        .key_by()
        .reduce(num_keys=997, name="reduce")
        .sink())
    return env.build()


def run_headline(spe: int = HEADLINE_SPE, fill: int = HEADLINE_FILL,
                 block_steps: int = 1024,
                 recovery_block_steps: int = 8192) -> dict:
    """The headline runner (log and ring sized from FILL x SPE) with the
    audit on: warm epoch, prewarm, two un-truncated epochs, kill window
    subtask 1, recover, one more epoch."""
    from clonos_tpu.runtime.cluster import ClusterRunner
    from clonos_tpu.runtime.executor import DETS_PER_STEP

    need = fill * spe * DETS_PER_STEP
    span = max(fill * spe, 2)
    t0 = time.monotonic()
    runner = ClusterRunner(
        build_headline_job(), steps_per_epoch=spe,
        log_capacity=1 << need.bit_length(), max_epochs=32,
        inflight_ring_steps=1 << (span - 1).bit_length(),
        recovery_block_steps=recovery_block_steps,
        block_steps=block_steps, latency_marker_every=64, seed=7,
        overlap_epoch=True, audit=True)
    runner.run_epoch(complete_checkpoint=True)
    prewarm_s = runner.prewarm_recovery()
    warm_s = time.monotonic() - t0
    runner.run_epoch(complete_checkpoint=False)
    runner.run_epoch(complete_checkpoint=False)
    runner.inject_failure([HEADLINE_PAR + 1])
    t1 = time.monotonic()
    report = runner.recover()
    recover_s = time.monotonic() - t1
    runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    return {"runner": runner, "report": report, "warm_s": warm_s,
            "prewarm_s": prewarm_s, "recover_s": recover_s}


def carry_bytes(carry) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(carry))


# --- part C: the mesh --------------------------------------------------------


def check_sharded(runner, n_dev: int) -> int:
    """Every carry leaf the partition rules shard sits on ``n_dev``
    devices with 1/n of its bytes on each; returns how many there are."""
    import jax
    from jax.sharding import PartitionSpec

    compiled = runner.executor.compiled
    carry = runner.executor.carry
    want = jax.tree_util.tree_leaves(
        compiled.carry_shardings(carry),
        is_leaf=lambda s: hasattr(s, "spec"))
    n = 0
    for leaf, ns in zip(jax.tree_util.tree_leaves(carry), want):
        if ns.spec == PartitionSpec():
            continue
        n += 1
        devs = len(leaf.sharding.device_set)
        per = leaf.addressable_shards[0].data.nbytes
        if devs != n_dev or per * n_dev != leaf.nbytes:
            raise AssertionError(
                f"carry leaf {leaf.shape} {ns.spec}: on {devs} devices, "
                f"{per} of {leaf.nbytes} bytes on the first")
    if n == 0:
        raise AssertionError("no carry leaf is sharded")
    return n


# --- part K: kernels at width -----------------------------------------------


def check_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    from clonos_tpu.obs import trace
    from clonos_tpu.ops.histogram import KERNEL_MAX_KEYS, keyed_hist
    from clonos_tpu.ops.matops import onehot_gather_rows
    from clonos_tpu.parallel import routing

    rng = np.random.RandomState(seed)

    def same(a, b, what):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(f"{what}: results differ")

    # Histogram: kernel == scatter == NumPy modulo 2**32, values over
    # the whole int32 range, at the call shapes of the benchmark's cells
    # (a step's fold, an exchange's placement, the event-time windows'
    # slot x key lanes) and at the widest table callers may hand it.
    for shp, nk in (((64, 8, 300), 997), ((16, 1024), KERNEL_MAX_KEYS),
                    ((1024, 8, 640), 499), ((512, 512), 8192),
                    ((1024, 8, 1152), 997), ((1024, 1024), 8192),
                    ((1024, 8, 1408), 1400), ((1024, 1024), 4096)):
        keys = rng.randint(-3, nk + 5, shp).astype(np.int32)
        vals = rng.randint(-(1 << 31), 1 << 31, shp,
                           dtype=np.int64).astype(np.int32)
        valid = rng.rand(*shp) < 0.7
        args = tuple(map(jnp.asarray, (keys, vals, valid)))
        s1, c1 = keyed_hist(*args, nk, force="pallas")
        s2, c2 = keyed_hist(*args, nk, force="xla")
        s4, _ = keyed_hist(*args, nk, force="pallas", want_counts=False)
        ok = valid & (keys >= 0) & (keys < nk)
        rows = np.broadcast_to(
            np.arange(int(np.prod(shp[:-1]))).reshape(shp[:-1] + (1,)),
            shp)
        s3 = np.zeros((rows.max() + 1, nk), np.int64)
        np.add.at(s3, (rows[ok], keys[ok]), vals[ok].astype(np.int64))
        s3 = (s3 & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
        same(s1, s2, f"histogram sums nk={nk} kernel vs scatter")
        same(c1, c2, f"histogram counts nk={nk} kernel vs scatter")
        same(s4, s2, f"histogram sums nk={nk} sums-only kernel vs scatter")
        same(np.asarray(s1).reshape(-1, nk), s3,
             f"histogram sums nk={nk} kernel vs NumPy")
        say(f"K histogram {shp} nk={nk}: kernel == scatter == NumPy, "
            f"values over int32")

    # Exchange: each route against the per-step (sort) exchange. Shapes
    # are A's (16 x 32 records, 16 targets); what differs picks the
    # placement, and whether the block is counted whole or in chunks.
    def block(k, b=32):
        shp = (k, 16, b)
        return zero_invalid(RecordBatch(
            jnp.asarray(rng.randint(0, 499, shp), jnp.int32),
            jnp.asarray(rng.randint(-1000, 1000, shp), jnp.int32),
            jnp.asarray(rng.randint(0, 100, shp), jnp.int32),
            jnp.asarray(rng.rand(*shp) < 0.7)))

    budget = routing._count_route_budget()
    over_k = 1 << (budget // (512 * 17 * 12)).bit_length()
    tracer = trace.get_tracer()

    def against_per_step(b, cap, want, chunked, mesh=None):
        route = lambda x: routing.route_hash_block(x, 16, 64, cap)
        if mesh is None:
            run = jax.jit(route)
        else:                         # as the mesh's block program has it:
            from jax.sharding import NamedSharding, PartitionSpec
            from clonos_tpu.ops.histogram import over_mesh
            by_task = NamedSharding(mesh, PartitionSpec(None, "tasks"))
            b = jax.device_put(b, by_task)      # subtasks in, targets out
            run = jax.jit(over_mesh(route, mesh, "tasks"),
                          out_shardings=by_task)
        n0 = len(tracer.records())
        got = jax.block_until_ready(run(b))
        took = [(r["args"]["route"], r["args"]["steps"], r["args"]["chunks"])
                for r in tracer.records()[n0:]
                if r["name"] == "exchange.route"]
        K = b.keys.shape[0]
        if (len(took) != 1 or took[0][0] != want
                or took[0][1] * took[0][2] != K
                or (took[0][2] > 1) != chunked):
            raise AssertionError(
                f"exchange K={K} cap={cap}: took {took}, meant {want}, "
                f"{'chunks' if chunked else 'whole'} (budget {budget} "
                f"bytes)")
        t0 = time.monotonic()
        jax.block_until_ready(run(b))
        wall = time.monotonic() - t0
        ref = jax.jit(jax.vmap(lambda x: routing.route_hash(
            x, 16, 64, cap)))(b)
        for x, y in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            same(x, y, f"exchange route {want}")
        _, steps, chunks = took[0]
        say(f"K exchange K={K} n={b.keys[0].size} T=16 cap={cap}"
            f"{'' if mesh is None else ' over the 4-chip task mesh'}: "
            f"route {want}, {chunks} x {steps} steps == per-step exchange "
            f"(second call {wall * 1e3:.1f} ms of host wall)")

    against_per_step(block(64), 512, "kernel", chunked=False)
    against_per_step(block(64), 2048, "scatter", chunked=False)
    against_per_step(block(over_k), 512, "kernel", chunked=True)
    if len(jax.devices()) >= 4:
        # allround-64's first exchange: 1,024 steps of 16 x 128 records
        # to 16 targets at capacity 1,024, over budget on one chip's price
        from clonos_tpu.parallel import distributed
        against_per_step(block(1024, 128), 1024, "kernel", chunked=True,
                         mesh=distributed.task_mesh(max_devices=4))
    say(f"K exchange count-route budget: {budget} bytes")

    # MXU one-hot gather: exact over the whole int32 range.
    table = rng.randint(-(1 << 31), (1 << 31) - 1, (512, 8, 997),
                        dtype=np.int64).astype(np.int32)
    idx = rng.randint(0, 512, (512, 8)).astype(np.int32)
    got = jax.jit(onehot_gather_rows)(jnp.asarray(table), jnp.asarray(idx))
    same(got, table[idx, np.arange(8)[None, :]], "one-hot gather")
    say("K one-hot f32 HIGHEST gather [512, 8, 997]: bit-exact over int32")


def check_window_join(seed: int, blocks: int = 3, K: int = 24, P: int = 8,
                      B: int = 96, num_keys: int = 4096) -> int:
    """The window join's block form against its step form on this
    device, its tables on own columns bound as the planner binds them
    (a subtask's ids under 128 key groups, ascending, then ``NO_KEY``);
    returns the rows compared."""
    import jax
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    from clonos_tpu.parallel import routing

    rng = np.random.RandomState(seed)
    groups = 128
    op = ops.EventTimeWindowJoinOperator(
        num_keys=num_keys, window_size=10000, out_of_orderness=1280,
        capacity=320,
        own_columns=routing.own_columns_width(num_keys, P, groups))
    if op.own_columns is None:
        raise AssertionError(f"window join: no own columns for {num_keys} "
                             f"keys over {P} subtasks")
    own = routing.own_slots(np.arange(num_keys), P, groups)     # [P, nk]
    cols = np.sort(np.where(own, np.arange(num_keys), ops.NO_KEY),
                   axis=1)[:, :op.own_columns].astype(np.int32)

    def draw(blk, silent):
        steps = blk * K + np.arange(K)
        ts = 1280 * steps[:, None, None] + rng.randint(0, 1280, (K, P, B))
        keys = rng.randint(-2, num_keys // 8, (K, P, B))
        # a keyed edge delivers a subtask its own keys; one record in 16
        # of the others stays, and must be no record
        here = own[np.arange(P)[None, :, None],
                   np.clip(keys, 0, num_keys - 1)] & (keys >= 0)
        keep = here | (rng.rand(K, P, B) < 1 / 16)
        return zero_invalid(RecordBatch(
            jnp.asarray(keys, jnp.int32),
            jnp.asarray(rng.randint(-2 ** 31, 2 ** 31 - 1, (K, P, B)),
                        jnp.int32),
            jnp.asarray(ts, jnp.int32),
            jnp.asarray((rng.rand(K, P, B) < 0.7) & keep & (not silent))))

    step = jax.jit(op.process2)
    block = jax.jit(op.process_block)
    by_block = by_step = op.bind_own_columns(op.init_state(P), cols)
    rows = 0
    for blk in range(blocks):
        left, right = draw(blk, False), draw(blk, blk == 1)
        bctx = ops.BlockContext(
            times=jnp.arange(K, dtype=jnp.int32),
            rng_bits=jnp.zeros((K,), jnp.int32),
            epoch=jnp.zeros((), jnp.int32), step0=jnp.zeros((), jnp.int32),
            subtask=jnp.arange(P, dtype=jnp.int32))
        by_block, out = block(by_block, (left, right), bctx)
        outs = []
        for k in range(K):
            at = lambda b: jax.tree_util.tree_map(lambda x: x[k], b)
            by_step, o = step(by_step, at(left), at(right), bctx.at_step(k))
            outs.append(o)
        stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *outs)
        for what, a, b in list(zip(out._fields, out, stacked)) + [
                (k, by_block[k], by_step[k]) for k in by_block]:
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(
                    f"window join, block {blk}: {what} differs between "
                    f"the block form and the step form")
        m = np.asarray(out.valid)
        at = np.broadcast_to(np.arange(P)[None, :, None], m.shape)[m]
        if not own[at, np.asarray(out.keys)[m]].all():
            raise AssertionError(f"window join, block {blk}: a row of a "
                                 f"key its subtask does not own")
        rows += int(m.sum())
    if rows == 0 or int(np.asarray(by_block["late"]).sum()) == 0:
        raise AssertionError(f"window join: {rows} rows, no record refused:"
                             f" the case exercises nothing")
    return rows


def check_window_join_in_a_job(seed: int, spe: int = 2048, epochs: int = 3,
                               num_keys: int = 1024) -> int:
    """Part J, second half: the committed stream of the
    ``nexmark-window-join`` job — its tiny stand-in with ``num_keys``
    ids, so that ``window_join`` derives own columns — run in blocks of
    1,024 steps against the same job run in blocks of 16 and against
    the topology's plain reference; returns the rows compared."""
    import json
    bench = os.path.join(HERE, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import job as bench_job
    from benchlib.byname import module_at

    with open(os.path.join(bench, "tests", "tiny", "bench", "configs",
                           "tiny-nexmark-q8.json")) as f:
        cfg = json.load(f)
    cfg.update(steps_per_epoch=spe, num_keys=num_keys)
    stream = bench_job.make_stream(cfg, {"table_epochs": 2}, seed)
    ref = module_at(bench_job.topology_file(cfg, "reference.py"))
    build = module_at(bench_job.topology_file(cfg, "job.py")).build

    def committed(block_steps: int):
        graph = build(cfg)
        (join,) = (v for v in graph.vertices if v.name == "join")
        if join.operator.own_columns is None:
            raise AssertionError(f"window join in a job: no own columns "
                                 f"for {num_keys} keys")
        got, runner = committed_by_epoch(graph, stream, seed, spe, epochs,
                                         block_steps, "window join")
        state = runner.executor.vertex_state(join.vertex_id)
        return got, {k: int(np.asarray(state[k]).sum())
                     for k in ("late", "fired", "dropped")}

    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    for name, steps in (("1,024", 1024), ("16", 16)):
        got, totals = committed(steps)
        bad, failed, compared = ref.check(got, want, cfg, epochs)
        if bad or totals != {"late": 0, "fired": want.fired, "dropped": 0}:
            raise AssertionError(
                f"window join, blocks of {name} steps: {bad} rows differ "
                f"from the reference in epochs {failed}; {totals} against "
                f"{want.fired} rows fired")
    if compared < epochs * spe // 8:
        raise AssertionError(f"window join in a job: only {compared} rows")
    return compared


def check_block_until_ready() -> None:
    """Show that ``jax.block_until_ready`` returns only when the work is
    done: after it, a device->host read of the same result has nothing
    left to wait for."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(
            0, 2000, lambda _, a: jnp.tanh(a @ a) * 0.5, x)

    x = jnp.full((4096, 4096), 1e-3, jnp.bfloat16)
    np.asarray(work(x)[0, 0])          # compile + warm both programs
    t0 = time.monotonic()
    y = work(x)
    t1 = time.monotonic()
    jax.block_until_ready(y)
    t2 = time.monotonic()
    np.asarray(y[0, 0])
    t3 = time.monotonic()
    say(f"K block_until_ready: dispatch returned after {t1 - t0:.4f}s, "
        f"block_until_ready after {t2 - t0:.4f}s, d2h read of the same "
        f"result then took {t3 - t2:.4f}s")
    if not (t2 - t0) > 5 * (t1 - t0) or not (t3 - t2) < 0.2 * (t2 - t0):
        raise AssertionError(
            "block_until_ready did not wait for the device: the read "
            "after it still waited")


def check_sessions_in_a_job(seed: int, spe: int = 2048, epochs: int = 3,
                            p: int = 4, batch: int = 16, hot: int = 1
                            ) -> int:
    """Part S: the committed stream of a session-window job run in
    blocks of 1,024 steps against the same job run in blocks of 16 and,
    where its receive windows (``p`` of ``p x batch`` slots) are wide
    enough for the lookup's head and tails, against blocks of 1,024
    steps with the split switched off; returns the rows compared. With
    one ``hot`` bidder no block may take the lookup's dense branch; with
    three, each sent a quarter of a step's bids, blocks of 1,024 steps
    must (a step with three targets past the head) and the streams
    agree all the same."""
    from unittest import mock

    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.feeds import ListFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner

    nk = 2048
    splits = ops._OwnColumns._splits(p, p * batch)
    feed = np.random.RandomState(seed).randint(
        1, 1 << 28, (p, epochs * spe * batch, 2)).astype(np.int32)

    def parse(keys, vals, step):
        # NEXmark's bidders at a step of 7 ms: a person every 5 ms, three
        # bids in four by a hot bidder that changes every 100 persons,
        # the rest by the last 1,000 persons and 10 ahead
        ts = 7 * step + ((vals >> 2) & 1023) % 7
        last = ts // 5
        bidder = jnp.where((vals & 3) != 0,
                           last // 100 * 100 + 1 + ((vals & 3) - 1) % hot,
                           last - 999 + (vals >> 12) % 1010)
        return bidder % nk, jnp.ones_like(vals), ts

    def committed(block_steps: int):
        env = StreamEnvironment(name="smoke-sessions", num_key_groups=64,
                                default_edge_capacity=batch)
        (env.host_source(batch_size=batch, parallelism=p)
            .map(parse, name="parse", capacity=batch)
            .key_by().window_session(
                num_keys=nk, gap=1000, out_of_orderness=7, capacity=16,
                own_columns=640, edge_capacity=p * batch, name="sessions")
            .key_by().sink(parallelism=p, transactional=True, capacity=16))
        runner = ClusterRunner(
            env.build(), steps_per_epoch=spe, block_steps=block_steps,
            log_capacity=1 << (spe * 8 - 1).bit_length(), max_epochs=16,
            inflight_ring_steps=2 * spe, seed=seed, logical_time=True,
            audit=False)
        runner.executor.register_feed(0, ListFeedReader(list(feed)))
        for _ in range(epochs):
            runner.run_epoch(complete_checkpoint=True)
        runner.drain_fence()
        lost = runner.executor.check_overflow()
        if lost:
            raise AssertionError(f"sessions, blocks of {block_steps}: {lost}")
        dense = int(np.asarray(runner.executor.vertex_state(2)
                               ["dense_blocks"]).sum())
        if dense and hot == 1:
            raise AssertionError(
                f"sessions, blocks of {block_steps}: {dense} blocks "
                f"compared every slot (more than two targets past the "
                f"head in one step)")
        (txn,) = runner.txn_logs.values()
        return sort_rows(np.asarray(txn.committed_stream(), np.int32)), dense

    (wide, dense), (narrow, dense16) = committed(1024), committed(16)
    if splits and hot > 2:
        if not dense:
            raise AssertionError(
                f"sessions, {hot} hot bidders: no block of 1,024 steps "
                f"took the lookup's dense branch")
        say(f"S sessions, {hot} hot bidders: {dense} of "
            f"{epochs * -(-spe // 1024)} blocks of 1,024 steps and "
            f"{dense16} of {epochs * spe // 16} blocks of 16 on the "
            f"lookup's dense branch")
    if splits:
        with mock.patch.object(ops._OwnColumns, "_splits",
                               staticmethod(lambda p, b: False)):
            every_slot, _ = committed(1024)
        if wide.shape != every_slot.shape or not np.array_equal(
                wide, every_slot):
            raise AssertionError(
                f"sessions: {wide.shape[0]} rows committed by head and "
                f"tails, {every_slot.shape[0]} with every slot compared")
    if wide.shape != narrow.shape or not np.array_equal(wide, narrow):
        raise AssertionError(
            f"sessions: {wide.shape[0]} rows committed in blocks of 1,024 "
            f"steps, {narrow.shape[0]} in blocks of 16"
            + (f"; first difference at sorted row "
               f"{int(np.nonzero((wide != narrow).any(axis=1))[0][0])}"
               if wide.shape == narrow.shape else ""))
    if wide.shape[0] < epochs * spe // 8:
        raise AssertionError(f"sessions: only {wide.shape[0]} rows")
    return int(wide.shape[0])


def committed_by_epoch(graph, stream, seed: int, spe: int, epochs: int,
                       block_steps: int, what: str):
    """``epochs`` epochs of a benchmark topology's job over ``stream`` in
    blocks of ``block_steps`` steps, nothing lost on any edge; returns
    (epoch -> committed row arrays, the runner)."""
    from benchlib.stream import TableFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner
    runner = ClusterRunner(
        graph, steps_per_epoch=spe, block_steps=block_steps,
        log_capacity=1 << (spe * 8 - 1).bit_length(), max_epochs=16,
        inflight_ring_steps=2 * spe, seed=seed, logical_time=True,
        audit=False)
    runner.executor.register_feed(0, TableFeedReader(stream))
    (txn,) = runner.txn_logs.values()
    got = {}
    txn.committer = lambda e, rows: got.setdefault(e, []).append(
        np.asarray(rows))
    for _ in range(epochs):
        runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    lost = runner.executor.check_overflow()
    if lost:
        raise AssertionError(f"{what}, blocks of {block_steps}: {lost}")
    return got, runner


def bench_topology(config: str, spe: int, seed: int, tiny: bool = True):
    """A benchmark topology at its tiny stand-in's sizes (or, ``tiny``
    off, at the sizes of the cell's own file) with epochs of ``spe``
    steps: (configuration, stream, its plain reference, its ``job.py``'s
    ``build``)."""
    import json
    bench = os.path.join(HERE, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import job as bench_job
    from benchlib.byname import module_at

    where = ("tests", "tiny", "bench", "configs") if tiny else ("configs",)
    with open(os.path.join(bench, *where, config + ".json")) as f:
        cfg = json.load(f)
    if spe is not None:
        cfg["steps_per_epoch"] = spe
    return (cfg, bench_job.make_stream(cfg, {"table_epochs": 2}, seed),
            module_at(bench_job.topology_file(cfg, "reference.py")),
            module_at(bench_job.topology_file(cfg, "job.py")).build)


def check_incremental_join_in_a_job(seed: int, spe: int = 2048,
                                    epochs: int = 3):
    """Part I: the committed stream of the ``nexmark-local-items`` job
    run in blocks of 1,024 steps against the same job run in blocks of
    16 and against the topology's plain reference; returns (rows
    compared, of them flushed out of the bag, chunks run step by step)."""
    cfg, stream, ref, build = bench_topology("tiny-nexmark-q3", spe, seed)

    def committed(block_steps: int):
        got, runner = committed_by_epoch(build(cfg), stream, seed, spe,
                                         epochs, block_steps,
                                         "incremental join")
        state = runner.executor.vertex_state(4)
        return got, int(np.asarray(state["step_chunks"]).sum())

    (wide, stepped), (narrow, _) = committed(1024), committed(16)
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    for name, got in (("1,024", wide), ("16", narrow)):
        bad, failed, compared = ref.check(got, want, cfg, epochs)
        if bad:
            raise AssertionError(
                f"incremental join, blocks of {name} steps: {bad} rows "
                f"differ from the reference in epochs {failed}")
    if min(want.flushed, want.bag_expired) < 100 \
            or not 0 < stepped < 4 * epochs * spe // 32:
        raise AssertionError(
            f"incremental join: the traffic left a branch out "
            f"({want.flushed} flushed, {want.bag_expired} expired in the "
            f"bag, {stepped} chunks step by step)")
    return compared, want.flushed, stepped


def check_best_in_interval_in_a_job(seed: int, spe: int = 2048,
                                    epochs: int = 3):
    """Part Q: the committed stream of the ``nexmark-average-price`` job
    (the join whose interval each key takes from its own data, then the
    exact windowed mean) run in blocks of 1,024 steps against the same
    job run in blocks of 16 and against the topology's plain reference,
    and the join's totals against the reference's; returns (rows
    compared, rows the join emitted, bids that counted, chunks run step
    by step)."""
    cfg, stream, ref, build = bench_topology("tiny-nexmark-q4", spe, seed)
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    stepped = 0
    for block_steps in (1024, 16):
        got, runner = committed_by_epoch(build(cfg), stream, seed, spe,
                                         epochs, block_steps,
                                         "best in interval")
        bad, failed, compared = ref.check(got, want, cfg, epochs)
        if bad:
            raise AssertionError(
                f"best in interval, blocks of {block_steps} steps: {bad} "
                f"rows differ from the reference in epochs {failed}")
        state = runner.executor.vertex_state(4)
        total = {k: int(np.asarray(state[k]).sum())
                 for k in ("rows", "valid", "under", "orphans", "duplicates",
                           "no_valid")}
        theirs = dict(rows=want.winning_rows, valid=want.valid,
                      under=want.under, orphans=want.orphans,
                      duplicates=want.duplicates, no_valid=want.no_valid)
        if total != theirs:
            raise AssertionError(
                f"best in interval, blocks of {block_steps} steps: totals "
                f"{total}, the reference's {theirs}")
        stepped = max(stepped, int(np.asarray(state["step_chunks"]).sum()))
    if min(want.valid, want.under, want.duplicates, want.no_valid) < 100:
        raise AssertionError(
            f"best in interval: the traffic left a branch out ({want})")
    return compared, want.winning_rows, want.valid, stepped


def check_union_in_a_job(seed: int, spe: int = 2048, epochs: int = 3,
                         tiny: bool = False):
    """Part U: the committed stream of the ``allround-event-time`` job
    in blocks of 1,024 steps with its union packed by rank against the
    same job with the union's block form patched to the scan of its step
    form, and against the same job with the keyed-state mapper's
    read-back patched from the dense compare to the gather, all three
    against the topology's plain reference; then the union's two forms
    of a union that overflows, against each other. Returns (rows
    compared with the reference, rows the overflowing union committed,
    records it was sent)."""
    import contextlib
    from unittest import mock

    import jax
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.environment import StreamEnvironment

    config = "tiny-allround-upstream" if tiny else "allround-upstream"
    cfg, stream, ref, build = bench_topology(config, spe, seed, tiny)
    unions = (("packed by rank", contextlib.nullcontext()),
              ("the step form's scan", mock.patch.object(
                  ops.UnionOperator, "process_block",
                  ops.TwoInputOperator.process_block)))
    if not 0 < cfg["num_keys"] <= ops._DENSE_READBACK_KEYS:
        raise AssertionError(f"{cfg['num_keys']} keys: the first two runs "
                             f"do not take the dense read-back")
    forms = tuple(u + (False,) for u in unions) + ((
        "the read-back by a gather",
        mock.patch.object(ops, "_DENSE_READBACK_KEYS", 0), True),)

    gathers = []        # per read-back traced: did it hold a gather?

    def read_running(acc_end, keys, real=ops._read_running):
        # a function of its own a call: a trace cached under ``real``
        # would answer for another value of the constant
        gathers.append(" gather[" in str(jax.make_jaxpr(
            lambda a, k: real(a, k))(acc_end, keys)))
        return real(acc_end, keys)

    def committed(graph, form, what):
        with form, mock.patch.object(ops, "_read_running", read_running):
            return committed_by_epoch(graph, stream, seed, spe, epochs,
                                      1024, what)

    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    for name, form, by_gather in forms:
        del gathers[:]
        got, runner = committed(build(cfg), form, "union")
        if set(gathers) != {by_gather}:
            raise AssertionError(f"union, {name}: the block program's "
                                 f"read-backs held a gather: {gathers}")
        compiled = runner.executor.compiled
        (union,) = (v for v in compiled.job.vertices if v.name == "union")
        widths = [compiled.edge_plans[i].width
                  for i, e in enumerate(compiled.job.edges)
                  if e.dst == union.vertex_id] + [union.operator.capacity]
        if not tiny and widths != [256, 384, 256]:
            raise AssertionError(f"union: edges and capacity {widths}, the "
                                 f"cell's are 256, 384 and 256")
        bad, failed, compared = ref.check(got, want, cfg, epochs)
        if bad:
            raise AssertionError(
                f"union, {name}: {bad} rows differ from the reference in "
                f"epochs {failed}")
    if compared < epochs * spe:
        raise AssertionError(f"union in a job: only {compared} rows")

    # two keyed streams into a union too narrow for both in about every
    # other step: three records in four from the left, one in two from
    # the right, over keys spread evenly (an edge must drop nothing)
    p, batch = cfg["parallelism"], cfg["batch"]
    cap = 5 * batch // 4

    def overflowing():
        env = StreamEnvironment(name="smoke-union",
                                num_key_groups=cfg["num_key_groups"],
                                default_edge_capacity=batch)
        spread = env.host_source(batch_size=batch, parallelism=p).map(
            lambda k, v, t: ((k * 8191 + v) & 0xFFFFF, v, t), name="spread")
        left = spread.filter(lambda k, v, t: v % 4 != 0, name="left")
        right = spread.filter(lambda k, v, t: v % 2 == 1, name="right")
        (left.key_by().union(right.key_by(), capacity=cap)
            .sink(parallelism=p, transactional=True, capacity=cap))
        return env.build()

    rows = []
    for name, form in unions:
        got, _ = committed(overflowing(), form, "overflowing union")
        rows.append(sort_rows(np.concatenate(
            [np.asarray(r).reshape(-1, 3) for e in sorted(got)
             for r in got[e]])))
    sent = epochs * spe * p * batch * 5 // 4
    if rows[0].shape != rows[1].shape or not np.array_equal(*rows):
        raise AssertionError(
            f"overflowing union: {rows[0].shape[0]} rows committed packed "
            f"by rank, {rows[1].shape[0]} by the step form's scan")
    if not sent // 2 < rows[0].shape[0] < sent * 99 // 100:
        raise AssertionError(
            f"overflowing union: {rows[0].shape[0]} rows of about {sent} "
            f"records: the case drops next to nothing, or half")
    return compared, int(rows[0].shape[0]), sent


# --- main --------------------------------------------------------------------


def check_cascade_on_the_mesh(seed: int, ckpt_dir: str, chips: int = 4,
                              config: str = "nexmark-q3-x4",
                              tiny: bool = False) -> dict:
    """Part X: the benchmark's ``nexmark-q3-x4`` deployment as its file
    sizes it (``tiny``: its stand-in, for the CPU test) over a task mesh
    of ``chips`` devices, through the cell's own kill; returns what the
    part prints."""
    import jax
    from clonos_tpu.obs import get_tracer
    cfg, stream, ref, _ = bench_topology(config, None, seed, tiny=tiny)
    from benchlib import job as bench_job      # on the path by now
    before = get_tracer().counters()
    t0 = time.monotonic()
    runner = bench_job.make_runner(cfg, stream, seed, ckpt_dir, chips)
    built = {k: get_tracer().counters().get(k, 0) - before.get(k, 0)
             for k in ("carry.bytes", "carry.max_device_bytes",
                       "carry.build_us")}
    if built["carry.max_device_bytes"] > 0.26 * built["carry.bytes"]:
        raise AssertionError(f"cascade: a chip was built more than its "
                             f"quarter of the carry: {built}")
    (txn,) = runner.txn_logs.values()
    got = {}
    txn.committer = lambda e, rows: got.setdefault(e, []).append(
        np.asarray(rows))
    runner.run_epoch(complete_checkpoint=True)
    runner.prewarm_recovery()
    for _ in range(cfg["kill"]["uncompleted_epochs"]):
        runner.run_epoch(complete_checkpoint=False)
    runner.inject_failure([runner.job.subtask_base(v) + s
                           for v, s in cfg["kill"]["victims"]])
    jax.block_until_ready(runner.executor.carry)
    r0 = time.monotonic()
    report = runner.recover()
    jax.block_until_ready(runner.executor.carry)
    recover_s = time.monotonic() - r0
    for _ in range(2):
        runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    lost = runner.executor.check_overflow()
    if lost:
        raise AssertionError(f"cascade: {lost}")
    epochs = runner.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    if bad or report.steps_replayed != (cfg["kill"]["uncompleted_epochs"]
                                        * cfg["steps_per_epoch"]):
        raise AssertionError(
            f"cascade: {bad} rows differ from the reference in epochs "
            f"{failed}; {report.steps_replayed} steps replayed")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
    return {"rows": compared, "epochs": epochs, "victims": report.victims,
            "fetch_hops": report.fetch_hops, "recover_s": recover_s,
            "carry_gib": built["carry.bytes"] / 2**30,
            "fullest_gib": built["carry.max_device_bytes"] / 2**30,
            "build_s": built["carry.build_us"] / 1e6,
            "peak_gib": peak / 2**30, "part_s": time.monotonic() - t0}


def cache_entries(cache_dir: str) -> int:
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


def print_routes(tracer, since: int, part: str) -> int:
    recs = tracer.records()
    counts = {}
    for r in recs[since:]:
        if r["name"] != "exchange.route":
            continue
        a = r["args"]
        if "edge" in a:        # planned, once per HASH edge and job built
            line = (f"edge {a['edge']}: {a['route']}, {a['width']} wide, "
                    f"{a['pairs_kept']} of {a['pairs_total']} pairs kept"
                    + (f" ({a['reason']}; {a['between']}, capacity "
                       f"{a['capacity']})" if "reason" in a else ""))
        else:                  # a dynamic exchange, as it was lowered
            line = (f"K={a['steps']} n={a['records']} T={a['targets']} "
                    f"cap={a['capacity']}: {a['route']}"
                    + (f", {a['chunks']} chunk(s) of steps, rank "
                       f"{a['rank']}" if "rank" in a else ""))
        counts[line] = counts.get(line, 0) + 1
    for line, c in sorted(counts.items()):
        say(f"{part} exchange {line} (traced {c}x)")
    for a in (r["args"] for r in recs[since:]
              if r["name"] == "plan.own-columns"):
        say(f"{part} plan {a['vertex']}: {a['columns']} own columns, "
            f"{a['bound']} bound, of {a['num_keys']}")
    kernels = collections.Counter(
        f"[{a['rows']}, {a['cols']}] -> {a['lanes']} lanes, hi={a['hi']} "
        f"planes={a['planes']}: {a['form']}"
        for a in (r["args"] for r in recs[since:]
                  if r["name"] == "hist.kernel"))
    for line, c in sorted(kernels.items()):
        say(f"{part} histogram {line} (traced {c}x)")
    appends = collections.Counter(
        f"{a['logs']} logs x {a['rows']} rows into {a['capacity']}: "
        f"{a['form']}" + (f", {a['runs']} runs" if a["runs"] else "")
        for a in (r["args"] for r in recs[since:]
                  if r["name"] == "log.append"))
    for line, c in sorted(appends.items()):
        say(f"{part} log append {line} (traced {c}x)")
    # what the fences have read so far of the exchange (totals, which
    # only grow: the fullest step of a dynamic edge, records dropped) and
    # of the event-time windows (fired, late, dropped; the most sessions
    # a subtask has held open)
    for name, n in sorted(tracer.counters().items()):
        if name.startswith(("exchange.", "window.", "join.", "lookup.")):
            say(f"{part} counter {name} = {n}")
    return len(recs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--parts", default="KABC",
                    help="which of K, J, S, I, Q, U, X, A, B, C to run (C needs A)")
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    from clonos_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform}, "
              f"{dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    from importlib.metadata import version
    say(f"device: {dev.device_kind} x{n_dev} (platform {dev.platform}); "
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {version('libtpu')}")
    entries0 = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries0} entries at start)")
    from clonos_tpu.ops import native
    say(f"native.available(): {native.available()}")
    os.makedirs(OUT_DIR, exist_ok=True)

    from clonos_tpu.obs import trace
    from clonos_tpu.obs.digest import diff_ledgers
    tracer = trace.configure("chip-smoke", buffer=1 << 16)
    mark = 0
    parts = set(args.parts.upper())

    if "K" in parts:
        t0 = time.monotonic()
        check_kernels(args.seed)
        check_block_until_ready()
        mark = print_routes(tracer, mark, "K")
        say(f"K pass ({time.monotonic() - t0:.1f}s)")

    if "J" in parts:
        t0 = time.monotonic()
        rows = check_window_join(args.seed)
        say(f"J window join alone, block form == step form over {rows} "
            f"rows ({time.monotonic() - t0:.1f}s)")
        in_job = check_window_join_in_a_job(args.seed)
        mark = print_routes(tracer, mark, "J")
        say(f"J pass: window join on own columns, block form == step form "
            f"over {rows} rows; in a job, blocks of 1,024 steps == blocks "
            f"of 16 == the reference over {in_job} rows "
            f"({time.monotonic() - t0:.1f}s)")

    if "S" in parts:
        t0 = time.monotonic()
        rows = check_sessions_in_a_job(args.seed)
        split = check_sessions_in_a_job(args.seed, p=8, batch=64)
        crowded = check_sessions_in_a_job(args.seed, p=8, batch=64, hot=3)
        mark = print_routes(tracer, mark, "S")
        say(f"S pass: session windows, blocks of 1,024 steps == blocks of "
            f"16 over {rows} rows; receive windows of 8 x 512 by head and "
            f"tails, blocks of 1,024 steps == blocks of 16 == every slot "
            f"compared over {split} rows, and over {crowded} rows with "
            f"three hot bidders, the blocks of 1,024 steps on the dense "
            f"branch ({time.monotonic() - t0:.1f}s)")

    if "I" in parts:
        t0 = time.monotonic()
        rows, flushed, stepped = check_incremental_join_in_a_job(args.seed)
        mark = print_routes(tracer, mark, "I")
        say(f"I pass: incremental join, blocks of 1,024 steps == blocks of "
            f"16 == the reference over {rows} rows, {flushed} of them "
            f"flushed, {stepped} chunks by the step form "
            f"({time.monotonic() - t0:.1f}s)")

    if "Q" in parts:
        t0 = time.monotonic()
        rows, won, valid, stepped = check_best_in_interval_in_a_job(args.seed)
        mark = print_routes(tracer, mark, "Q")
        say(f"Q pass: best in interval and the exact mean, blocks of 1,024 "
            f"steps == blocks of 16 == the reference over {rows} rows "
            f"({won} auctions won by the best of {valid} bids that "
            f"counted), {stepped} chunks by the step form "
            f"({time.monotonic() - t0:.1f}s)")

    if "U" in parts:
        t0 = time.monotonic()
        rows, kept, sent = check_union_in_a_job(args.seed)
        mark = print_routes(tracer, mark, "U")
        say(f"U pass: the union at 8 x 1,024 x (256 + 384) -> 256 in a "
            f"job, packed by rank == the step form's scan == the keyed "
            f"read-back by a gather (dense in the other two) == the "
            f"reference over {rows} rows; overflowing, the union's two "
            f"forms agree on {kept} rows of {sent} records "
            f"({time.monotonic() - t0:.1f}s)")

    shape = ServedShape()
    feed = make_feed(shape, args.seed)
    served = None
    if "A" in parts:
        t0 = time.monotonic()
        res = run_served(shape, feed, os.path.join(OUT_DIR, "ckpt-a"),
                         args.seed)
        check_served(shape, feed, res)
        rep = res["report"]
        say(f"A {res['runner'].job.total_subtasks()} subtasks, "
            f"{shape.total_steps} steps: fed {feed.shape[0] * feed.shape[1]}"
            f" records, committed {res['committed'].shape[0]}; killed "
            f"{list(rep.failed_subtasks)}, replayed {rep.steps_replayed} "
            f"steps / {rep.records_replayed} records")
        say(f"A walls: build+warm epoch+prewarm {res['warm_s']:.1f}s "
            f"(prewarm {res['prewarm_s']:.1f}s), recovery "
            f"{res['recover_s']:.3f}s, part {time.monotonic() - t0:.1f}s")
        mark = print_routes(tracer, mark, "A")
        say("A pass: committed stream == NumPy reference, exactly once; "
            "audit 0 divergences")
        served = {"committed": res["committed"], "ledger": res["ledger"]}
        del res, rep
        gc.collect()

    if "B" in parts:
        t0 = time.monotonic()
        ran0 = tracer.counters().get("block.dispatches.run_block", 0)
        res = run_headline()
        runner, rep = res["runner"], res["report"]
        check_audit(runner, rep, min_validated=2)
        # four epochs (warm, two un-truncated, one after recovery), each
        # block of each one run_block
        ex = runner.executor
        blocks = 4 * ex.steps_per_epoch // ex.block_steps
        ran = tracer.counters()["block.dispatches.run_block"] - ran0
        if ran != blocks:
            raise AssertionError(
                f"B ran {blocks} blocks in {ran} run_block dispatches")
        aot_failed = job_counter(runner, "recovery.aot-lower-failed")
        if aot_failed:
            raise AssertionError(
                f"recovery.aot-lower-failed = {aot_failed}")
        stats = dev.memory_stats()
        say(f"B {runner.job.total_subtasks()} subtasks, carry "
            f"{carry_bytes(runner.executor.carry) / 2**30:.2f} GiB on the "
            f"device; killed {list(rep.failed_subtasks)}, replayed "
            f"{rep.steps_replayed} steps / {rep.records_replayed} records "
            f"from epoch {rep.from_epoch}")
        say(f"B walls: build+warm epoch+prewarm {res['warm_s']:.1f}s "
            f"(prewarm {res['prewarm_s']:.1f}s), recovery "
            f"{res['recover_s']:.3f}s, part {time.monotonic() - t0:.1f}s")
        say(f"B peak_bytes_in_use: {stats['peak_bytes_in_use']} "
            f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"{stats['bytes_limit'] / 2**30:.2f} GiB)")
        say(f"B block loop: {blocks} blocks of {ex.block_steps} steps, "
            f"block.dispatches.run_block {ran}")
        mark = print_routes(tracer, mark, "B")
        say("B pass: recovery verified bit-identical, audit 0 divergences,"
            " recovery.aot-lower-failed 0")
        del res, runner, rep, ex
        gc.collect()

    if "X" in parts:
        if n_dev < 4:
            say(f"X: not run ({n_dev} device)")
        else:
            x = check_cascade_on_the_mesh(
                args.seed, os.path.join(OUT_DIR, "ckpt-x"))
            mark = print_routes(tracer, mark, "X")
            say(f"X pass: nexmark-q3-x4 on a 4-device mesh, carry "
                f"{x['carry_gib']:.2f} GiB built in {x['build_s']:.1f}s, "
                f"{x['fullest_gib']:.2f} on the fullest chip (peak "
                f"{x['peak_gib']:.2f}); {x['victims']} connected victims "
                f"recovered in {x['recover_s']:.3f}s from holders "
                f"{x['fetch_hops']} edge(s) down; {x['rows']} committed "
                f"rows over {x['epochs']} epochs == the reference "
                f"({x['part_s']:.1f}s)")
            gc.collect()

    if "C" in parts:
        if n_dev < 4:
            say(f"mesh: not run ({n_dev} device)")
        else:
            if served is None:
                raise SystemExit("part C compares with part A: run both")
            from clonos_tpu.parallel import distributed
            t0 = time.monotonic()
            res = run_served(shape, feed, os.path.join(OUT_DIR, "ckpt-c"),
                             args.seed,
                             mesh=distributed.task_mesh(max_devices=4))
            check_served(shape, feed, res)
            if not np.array_equal(res["committed"], served["committed"]):
                raise AssertionError(
                    "mesh: committed stream differs from part A's")
            problems = diff_ledgers(served["ledger"], res["ledger"])
            if problems:
                raise AssertionError(f"mesh: ledgers differ: {problems[:4]}")
            n_sharded = check_sharded(res["runner"], 4)
            say(f"C walls: build+warm epoch+prewarm {res['warm_s']:.1f}s "
                f"(prewarm {res['prewarm_s']:.1f}s), recovery "
                f"{res['recover_s']:.3f}s, part "
                f"{time.monotonic() - t0:.1f}s")
            mark = print_routes(tracer, mark, "C")
            say(f"C pass: 4-device mesh, committed stream byte-identical "
                f"to A ({res['committed'].shape[0]} rows), "
                f"diff_ledgers == [] over {len(res['ledger'])} epochs, "
                f"{n_sharded} sharded carry leaves on 4 devices at a "
                f"quarter each")
            del res
            gc.collect()

    entries1 = cache_entries(cache_dir)
    say(f"compile cache: {entries1} entries at end "
        f"({entries1 - entries0} added by this run)")
    say(f"total {time.monotonic() - t_start:.1f}s; parts run: "
        f"{''.join(p for p in 'KJSIQUXABC' if p in parts)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
