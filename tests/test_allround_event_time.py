"""The ``allround-event-time`` job (upstream's all-round test job: event
time assigned on the device, a keyed-state and an operator-state mapper,
a tumbling and a sliding event-time window behind watermarks, a union in
front of a transactional sink) through ``ClusterRunner`` against its
plain NumPy reference, at a tiny size: the whole committed stream before
and after a kill, the late records of a table whose lag exceeds the
bound, the reference's own fold against its periodic extension, and each
control."""

import os
import sys
import zlib

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import job  # noqa: E402
from benchlib.byname import module_at  # noqa: E402

from clonos_tpu import obs  # noqa: E402
from clonos_tpu.parallel import routing  # noqa: E402

TUMBLING, SLIDING = 4, 5          # vertex ids in job.py's build order


def config(**over):
    cfg = {"name": "tiny-allround-upstream",
           "topology": "allround-event-time",
           "parallelism": 4, "batch": 8, "num_keys": 20,
           "key_dist": {"kind": "uniform"}, "value_bits": 18,
           "num_key_groups": 64, "clock_ms_per_step": 100,
           "max_out_of_order_ms": 500, "max_lag_ms": 500,
           "tumbling_ms": 2000, "slide_ms": 250, "slide_factor": 3,
           "edge_capacity": 32, "union_capacity": 64,
           "steps_per_epoch": 64, "block_steps": 16, "log_capacity": 2048,
           "max_epochs": 32, "inflight_ring_steps": 256,
           "recovery_block_steps": 128, "overlap_epoch": True}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


def run_job(cfg, seed, epochs, tmp_path, kill=None, feed_keys=None):
    """``epochs`` completed epochs; ``kill = (vertex, subtask)`` fails
    that subtask half-way, behind two epochs whose checkpoints stay
    pending; the feed draws its keys from ``[0, feed_keys)`` (the job's
    ``num_keys`` by default). Returns (runner, stream, epoch ->
    committed row arrays)."""
    stream = job.make_stream(
        dict(cfg, num_keys=feed_keys or cfg["num_keys"]),
        {"table_epochs": 2}, seed)
    runner = job.make_runner(cfg, stream, seed, str(tmp_path / "ck"), 1)
    (txn,) = runner.txn_logs.values()
    got = {}
    txn.committer = lambda e, rows: got.setdefault(e, []).append(
        np.asarray(rows))
    for i in range(epochs):
        if kill is not None and i == epochs // 2:
            runner.run_epoch(complete_checkpoint=False)
            runner.run_epoch(complete_checkpoint=False)
            runner.inject_failure(
                [runner.job.subtask_base(kill[0]) + kill[1]])
            assert runner.recover().steps_replayed == \
                2 * cfg["steps_per_epoch"]
        runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    assert runner.executor.check_overflow() == []
    return runner, stream, got


def digest(got):
    """(rows, checksum) of a committed stream, each epoch's rows as a
    multiset (sorted), the epochs in order."""
    crc, n = 0, 0
    for e in sorted(got):
        rows = np.concatenate(got[e]).astype(np.int32)
        rows = rows[np.lexsort(rows.T[::-1])]
        crc = zlib.crc32(np.ascontiguousarray(rows).tobytes(),
                         zlib.crc32(np.int32(e).tobytes(), crc))
        n += len(rows)
    return n, crc


def late_of(runner, vid):
    return int(np.asarray(runner.executor.vertex_state(vid)["late"]).sum())


@pytest.mark.parametrize("victim", [(TUMBLING, 1), (SLIDING, 2)],
                         ids=["tumbling", "sliding"])
def test_committed_stream_equals_the_reference_through_a_kill(
        ref, tmp_path, victim):
    cfg = config()
    runner, stream, got = run_job(cfg, 11, 8, tmp_path, kill=victim)
    epochs = runner.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 1000
    stamps = np.concatenate([r for parts in got.values() for r in parts]
                            )[:, 2] % cfg["tumbling_ms"]
    assert set(stamps.tolist()) == {0, 250, 500, 750}
    assert late_of(runner, TUMBLING) == want.late_tumbling == 0
    assert late_of(runner, SLIDING) == want.late_sliding == 0


def test_late_records_are_dropped_and_counted_as_the_reference_counts(
        ref, tmp_path, monkeypatch):
    """A lag of up to 1,300 ms against a bound of 500: the windows drop
    what arrives behind their watermark, the committed stream is the
    reference's all the same, and both count the same records. The
    killed subtask holds 16 replica logs; rebuilt three at a time here,
    so that the rebuild takes several calls of its program."""
    from clonos_tpu.runtime.recovery_programs import RecoveryPrograms
    monkeypatch.setattr(RecoveryPrograms, "REPLICA_COPY_ROWS", 3)
    cfg = config(max_lag_ms=1300)
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 5, 8, tmp_path, kill=(TUMBLING, 3))
    epochs = runner.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    assert ref.check(got, want, cfg, epochs)[:2] == (0, [])
    assert want.late_tumbling > 1000
    assert late_of(runner, TUMBLING) == want.late_tumbling
    assert late_of(runner, SLIDING) == want.late_sliding == 0
    # the fence read the same totals into the tracer's counters
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("window.late_records.tumbling") == want.late_tumbling
    assert grew("window.late_records.sliding") == 0
    fired = sum(len(r) for parts in got.values() for r in parts)
    assert fired <= (grew("window.fired_rows.tumbling")
                     + grew("window.fired_rows.sliding")) <= fired + 400


@pytest.mark.parametrize("max_lag_ms", [500, 1300])
def test_reference_extends_its_fold_as_it_would_fold_on(ref, max_lag_ms):
    """Past two common periods of table and window grid (640 steps here)
    the reference derives windows from the second period; folding every
    record of the run instead gives the same commits and late count."""
    cfg = config(max_lag_ms=max_lag_ms)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 23)
    table = (cfg, stream.keys, stream.vals, 57)
    short = ref.expected(*table)
    whole = ref.expected(*table, direct_periods=100)
    assert short.late_tumbling == whole.late_tumbling
    assert (short.late_tumbling > 0) == (max_lag_ms > 500)
    assert ref.check(ref.committed_of(short, cfg, 57), whole, cfg, 57)[0] == 0
    assert sum(len(r) for r in whole.rows) > 10000


@pytest.mark.parametrize("control,epochs,sizes", [
    ("at-least-once", 12, {}), ("arrival-time", 12, {}),
    ("f32", 40, {"batch": 64, "num_keys": 8})])
def test_each_control_differs_from_the_reference(ref, control, epochs,
                                                 sizes):
    """``f32`` needs window sums past 2**24: with 32 records a key a
    step, counts times records per window get there after ~820 steps."""
    cfg = config(**sizes)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 3)
    table = (cfg, stream.keys, stream.vals, epochs)
    want = ref.expected(*table)
    perturbed = ref.expected(*table, control=control,
                             control_step=epochs * 32)
    bad, failed, _ = ref.check(ref.committed_of(perturbed, cfg, epochs),
                               want, cfg, epochs)
    assert bad > 0 and failed
    assert ref.check(ref.committed_of(want, cfg, epochs), want, cfg,
                     epochs)[:2] == (0, [])


def test_check_counts_missing_duplicated_and_foreign_rows(ref):
    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 3)
    want = ref.expected(cfg, stream.keys, stream.vals, 4)
    got = ref.committed_of(want, cfg, 4)
    rows = got[2][0]
    got[2] = [np.concatenate([rows[1:], rows[-1:]])]    # one lost, one twice
    got[3] = [got[3][0], got[3][0][:0]]                 # committed twice
    got[9] = [rows[:2]]                                 # no such epoch
    del got[1]
    bad, failed, _ = ref.check(got, want, cfg, 4)
    assert bad == 2 + 1 + 2 + len(want.rows[1])
    assert sorted(failed) == [1, 2, 3, 9]


def _build(cfg):
    return module_at(job.topology_file(cfg, "job.py")).build(cfg)


def test_planner_routes_each_hash_edge_by_its_producers_own_keys():
    """Which route ``CompiledJob`` plans for every HASH edge, how wide,
    and how many (producer, slot) pairs it keeps. Behind the first keyBy
    every subtask holds own keys, so the second keyBy (operator-state ->
    tumbling: same key, same parallelism) is routed in place and the
    three window edges are gather plans over the pairs that can occur:
    a key's owner, and the two clamp columns (keys 0 and 19) on every
    subtask. An edge behind a source or a map stays dynamic."""
    from clonos_tpu.runtime.executor import CompiledJob

    tracer = obs.get_tracer()
    seen = len(tracer.records())
    compiled = CompiledJob(_build(config()))
    names = [v.name for v in compiled.job.vertices]
    plans = {(names[e.src], names[e.dst]): tuple(compiled.edge_plans[i])
             for i, e in enumerate(compiled.job.edges)
             if i in compiled.edge_plans}
    assert plans == {
        ("event-time", "keyed-state"): ("dynamic", 32, 16, 16),
        ("operator-state", "tumbling"): ("identity", 32, 4, 16),
        # 2 open windows x (20 keys + 2 clamp keys x 3 other subtasks)
        ("tumbling", "sliding"): ("static", 32, 52, 160),
        ("tumbling", "union"): ("static", 64, 52, 160),
        # 7 open windows x the same 26 pairs
        ("sliding", "union"): ("static", 64, 182, 560)}
    assert set(compiled.static_route) == {4, 5, 6}
    assert all(compiled.static_route[i].ok.sum() == plans[k][2]
               for i, k in ((4, ("tumbling", "sliding")),
                            (6, ("sliding", "union"))))
    noted = [r["args"] for r in tracer.records()[seen:]
             if r["name"] == "exchange.route"]
    assert [(n["route"], n["edge"], n["width"], n["pairs_kept"],
             n["pairs_total"]) for n in noted] == [
        ("dynamic", 1, 32, 16, 16),
        ("identity", 3, 32, 4, 16), ("static", 4, 32, 52, 160),
        ("static", 5, 64, 52, 160), ("static", 6, 64, 182, 560)]
    # the one edge left on the dynamic exchange says why: its producer
    # is a map behind the source, whose keys are the feed's
    assert [n.get("reason") for n in noted] == ["feed-keys"] + [None] * 4

    older = CompiledJob(_build(dict(
        config(), topology="source-window-reduce-sink", window_steps=8)))
    assert [tuple(older.edge_plans[i]) for i in (0, 1)] == [
        ("dynamic", 32, 16, 16),      # behind a source: any key anywhere
        ("static", 32, 20, 80)]       # one producer a key, no clamp
    assert 2 not in older.edge_plans  # reduce -> sink is a FORWARD edge


@pytest.mark.parametrize("declared", [True, False],
                         ids=["clamp-declared", "clamp-left-out"])
def test_keys_past_num_keys_commit_what_the_parent_commits(
        tmp_path, monkeypatch, declared):
    """The feed carries keys 0..29 into a job over 20 keys: the windows
    sum a key at or past ``num_keys`` under key 19 on the subtask that
    received it, mostly not key 19's owner. The rows pinned here are
    what unpruned plans (a full exchange in front of the tumbling
    window, the parent of PR 27) commit through a kill of the tumbling
    window's subtask 1, with the keyed-state mapper as its step form has
    it: a record past the table leaves with key 19's running count. (Pinned
    again in PR 52: until then the mapper's block form read -2**31 for
    such a record, and the pin held that — 1,975 rows, 3156005661; the
    job with the mapper's block form patched to the scan of its step form
    committed this pin before and after.) Such a record reaches the
    windows with the count of key 19 on the subtask it was sent to, 0 on
    all but key 19's owner, so in THIS job the clamp columns hold zero
    sums and a window that left them out of its contract loses nothing;
    the control that they matter is
    ``test_rows_summed_past_num_keys_reach_the_sink[clamp-left-out]``,
    whose windows take the source's own values."""
    from clonos_tpu.api.operators import EventTimeWindow
    if not declared:
        monkeypatch.setattr(EventTimeWindow, "static_clamp_keys",
                            lambda self: np.zeros((0,), np.int32))
    runner, stream, got = run_job(config(), 7, 6, tmp_path,
                                  kill=(TUMBLING, 1), feed_keys=30)
    past = np.asarray(stream.keys)[np.asarray(stream.keys) >= 20]
    assert len(set(routing._static_targets(past, 4, 64).tolist())) > 1
    assert digest(got) == (1940, 4177266801)
    assert late_of(runner, TUMBLING) == late_of(runner, SLIDING) == 0


@pytest.mark.parametrize("declared", [True, False],
                         ids=["clamp-declared", "clamp-left-out"])
def test_rows_summed_past_num_keys_reach_the_sink(monkeypatch, declared):
    """Keys 0..29 into a window over 20 keys, through the runner: what a
    non-owner of key 19 sums under it is fired there and reaches the
    sink (the pruned plan keeps the clamp column from every subtask). A
    window that left its clamp columns out of the contract has those
    rows pruned away."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.operators import EventTimeWindow
    from clonos_tpu.runtime.cluster import ClusterRunner
    if not declared:
        monkeypatch.setattr(EventTimeWindow, "static_clamp_keys",
                            lambda self: np.zeros((0,), np.int32))

    env = StreamEnvironment(name="past-num-keys", num_key_groups=64)
    (env.synthetic_source(vocab=30, batch_size=6, parallelism=4)
        .key_by().window_event_time(num_keys=20, window_size=64,
                                    out_of_orderness=16, name="window")
        .key_by().sink())
    r = ClusterRunner(env.build(), steps_per_epoch=8, seed=3)
    r.executor.time_source.now = lambda it=iter(range(0, 40000, 20)): next(it)
    for _ in range(4):
        r.run_epoch()
    assert r.executor.check_overflow() == []
    assert r.executor.compiled.edge_plans[1].route == "static"
    assert r.executor.compiled.edge_plans[1].pairs_kept == 2 * (
        20 + 2 * 3 * declared)
    fired = int(np.asarray(r.executor.vertex_state(1)["fired"]).sum())
    r.step()                    # the sink takes a step's rows the step after
    base = r.job.subtask_base(2)
    at_sink = int(np.asarray(r.executor.carry.record_counts)[base:base + 4]
                  .sum())
    owner_of_last = int(routing._static_targets(np.asarray([19]), 4, 64)[0])
    elsewhere = np.delete(np.asarray(
        r.executor.vertex_state(1)["acc"])[:, :, 19], owner_of_last, axis=0)
    assert fired > 100 and elsewhere.any()
    assert (at_sink == fired) == declared and at_sink <= fired


# --- the own-keys contract, operator by operator ---------------------------

def _contract_cases():
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    return [
        ("filter", ops.FilterOperator(lambda k, v, t: (k + v) % 3 != 0)),
        ("reduce", ops.KeyedReduceOperator(num_keys=13)),
        ("reduce-max", ops.KeyedReduceOperator(num_keys=13,
                                               reduce_fn=jnp.maximum)),
        ("operator-state", ops.OperatorStateCountOperator()),
        ("union", ops.UnionOperator(capacity=12)),
        ("count-window", ops.TumblingWindowCountOperator(
            num_keys=13, window_size=3)),
        ("tumbling", ops.EventTimeTumblingWindowOperator(
            num_keys=13, window_size=400, out_of_orderness=100)),
        ("sliding", ops.SlidingEventTimeWindowOperator(
            num_keys=13, window_size=300, slide=100,
            out_of_orderness=100)),
        ("window-mean", ops.EventTimeWindowMeanOperator(
            num_keys=13, window_size=300, slide=100,
            out_of_orderness=100)),
        ("window-join", ops.EventTimeWindowJoinOperator(
            num_keys=13, window_size=400, out_of_orderness=100,
            capacity=16)),
        ("window-top", ops.EventTimeWindowTopOperator(
            num_keys=13, window_size=300, slide=100, out_of_orderness=100,
            capacity=16)),
        ("sessions", ops.SessionWindowOperator(
            num_keys=13, gap=300, out_of_orderness=100)),
        ("incremental-join", ops.IncrementalJoinOperator(
            num_keys=13, ttl=300, out_of_orderness=100, capacity=16,
            bag_capacity=32)),
        ("map-rewrites-keys", ops.MapOperator(
            lambda k, v, t: (k + 1, v, t))),
    ]


def _violations(op, seed, blocks=4, K=6, P=3, B=10):
    """Feed ``op`` random blocks (keys from -4 to past every table, any
    key on any subtask) and return the valid records it emits that its
    declarations do not allow: a key the emitting subtask never
    received, or for a dense-table emitter a filled slot whose key the
    subtask neither received nor lists as a clamp column."""
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    rng = np.random.RandomState(seed)
    state = op.init_state(P)
    sk, clamp = op.static_out_keys(), op.static_clamp_keys()
    received = [set() for _ in range(P)]
    bad = emitted = 0

    def draw(step0):
        steps = step0 + np.arange(K)
        keys = rng.randint(-4, 22, (K, P, B)).astype(np.int32)
        ts = (100 * steps[:, None, None]
              - rng.randint(0, 100, (K, P, B))).astype(np.int32)
        return zero_invalid(RecordBatch(
            jnp.asarray(keys), jnp.asarray(rng.randint(1, 9, (K, P, B)),
                                           jnp.int32),
            jnp.asarray(ts), jnp.asarray(rng.rand(K, P, B) < 0.6)))

    for blk in range(blocks):
        two = isinstance(op, ops.TwoInputOperator)
        ins = (draw(blk * K), draw(blk * K)) if two else draw(blk * K)
        for b in (ins if two else (ins,)):
            k, m = np.asarray(b.keys), np.asarray(b.valid)
            for p in range(P):
                received[p] |= set(k[:, p][m[:, p]].tolist())
        bctx = ops.BlockContext(
            times=jnp.arange(blk * K, (blk + 1) * K, dtype=jnp.int32),
            rng_bits=jnp.zeros((K,), jnp.int32),
            epoch=jnp.zeros((), jnp.int32),
            step0=jnp.asarray(blk * K, jnp.int32),
            subtask=jnp.arange(P, dtype=jnp.int32))
        state, out = op.process_block(state, ins, bctx)
        k, m = np.asarray(out.keys), np.asarray(out.valid)
        for p in range(P):
            if sk is None:
                keys = k[:, p][m[:, p]]
            else:                       # the slot's key, not the lane's
                keys = np.broadcast_to(sk, m[:, p].shape)[m[:, p]]
                assert (keys == k[:, p][m[:, p]]).all()
            emitted += len(keys)
            allowed = received[p] | set(
                [] if clamp is None else clamp.tolist())
            bad += sum(int(x) not in allowed for x in keys)
    assert emitted > 50
    return bad


CONTRACT_CASES = _contract_cases()


@pytest.mark.parametrize("name,op", CONTRACT_CASES,
                         ids=[n for n, _ in CONTRACT_CASES])
def test_operators_keep_what_they_declare_of_their_keys(name, op):
    """``emits_received_keys`` and ``static_clamp_keys`` are what the
    planner prunes routes by: each operator that declares one is held to
    it on random input, keys outside every table included. A map that
    rewrites keys declares nothing, and the same check catches it."""
    declares = op.emits_received_keys or (
        op.static_out_keys() is not None
        and op.static_clamp_keys() is not None)
    assert declares == (name != "map-rewrites-keys")
    bad = sum(_violations(op, seed) for seed in (1, 2))
    assert (bad == 0) == declares


def test_every_operator_that_declares_own_keys_is_held_to_it():
    from clonos_tpu.api import operators as ops

    def subclasses(c):
        return [c] + [s for d in c.__subclasses__() for s in subclasses(d)]
    declaring = {c for c in subclasses(ops.Operator)
                 if c.emits_received_keys
                 or c.static_clamp_keys is not ops.Operator.static_clamp_keys}
    tested = {type(op) for _, op in CONTRACT_CASES}
    abstract = {ops.EventTimeWindow}
    assert declaring - abstract <= tested
    assert not ops.MapOperator.emits_received_keys
    assert not ops.HostFeedSource.emits_received_keys
    assert ops.SessionWindowOperator(5, 3).static_out_keys() is None
