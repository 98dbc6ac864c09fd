"""The tumbling event-time window join (``EventTimeWindowJoinOperator``):
its step form against its block form, bit for bit; the
``nexmark-window-join`` job (NEXmark query 8: persons joined with the
auctions they opened in the window they registered in) through
``ClusterRunner`` against its plain NumPy reference, at a tiny size — the
whole committed stream through a kill of a join subtask behind two
pending epochs, the dropped records of a table whose spread passes the
bound, each control — and what the planner makes of its edges."""

import os
import sys

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

PERSONS, AUCTIONS, JOIN, SINK = 2, 3, 4, 5    # vertex ids, job.py's order


def config(**over):
    cfg = {"name": "tiny-nexmark-q8", "topology": "nexmark-window-join",
           "parallelism": 4, "batch": 16, "num_keys": 24,
           "key_dist": {"kind": "uniform"}, "value_bits": 28,
           "num_key_groups": 64, "sharing_depth": 1, "person_every": 4,
           "clock_ms_per_step": 100, "spread_ms": 100,
           "max_out_of_order_ms": 100, "window_ms": 800,
           "edge_capacity": 64, "join_capacity": 32,
           "steps_per_epoch": 64, "block_steps": 16, "log_capacity": 2048,
           "max_epochs": 32, "inflight_ring_steps": 256,
           "recovery_block_steps": 128, "overlap_epoch": True}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


# --- the operator: step form == block form -----------------------------------


def _blocks(seed, n_blocks, K, P, B, silent_right=(), spread=100,
            num_keys=13):
    """Random (left, right) blocks: keys from -2 to past the table, event
    time ``100 * step - [0, spread)``, the right input silent for the
    blocks in ``silent_right``."""
    import jax.numpy as jnp
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    rng = np.random.RandomState(seed)

    def draw(blk, silent):
        steps = blk * K + np.arange(K)
        ts = 100 * steps[:, None, None] - rng.randint(0, spread, (K, P, B))
        valid = (rng.rand(K, P, B) < 0.6) & (not silent)
        return zero_invalid(RecordBatch(
            jnp.asarray(rng.randint(-2, num_keys + 3, (K, P, B)), jnp.int32),
            jnp.asarray(rng.randint(1, 1 << 28, (K, P, B)), jnp.int32),
            jnp.asarray(ts, jnp.int32), jnp.asarray(valid)))
    return [(draw(b, False), draw(b, b in silent_right))
            for b in range(n_blocks)]


def _own(num_keys, P, columns, unbound=()):
    """A binding as the planner makes one: key ``k`` on subtask ``k % P``,
    ascending, then ``NO_KEY``; the keys in ``unbound`` on no subtask."""
    from clonos_tpu.api.operators import NO_KEY
    cols = np.full((P, columns), NO_KEY, np.int32)
    for q in range(P):
        keys = [k for k in range(q, num_keys, P) if k not in unbound]
        cols[q, :len(keys)] = keys
    return cols


def _to_owners(blocks, P):
    """``blocks`` with every record on the subtask that owns its key
    under :func:`_own` (what two ``key_by()`` inputs deliver): a record
    elsewhere is cleared."""
    import jax.numpy as jnp
    from clonos_tpu.api.records import zero_invalid
    at = jnp.arange(P, dtype=jnp.int32)[None, :, None]
    mine = lambda b: zero_invalid(b._replace(
        valid=b.valid & (b.keys % P == at)))
    return [(mine(l), mine(r)) for l, r in blocks]


def _step_and_block(op, blocks, K, P, cols=None):
    """Run ``blocks`` through ``process_block`` and, step by step, through
    ``process2``; assert both agree after every block and return the
    final state and all rows. ``cols``: the own columns to bind first."""
    import jax
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    by_block = by_step = op.init_state(P)
    if cols is not None:
        by_block = by_step = op.bind_own_columns(by_block, cols)
    rows = []
    for blk, (left, right) in enumerate(blocks):
        bctx = ops.BlockContext(
            times=jnp.arange(blk * K, (blk + 1) * K, dtype=jnp.int32),
            rng_bits=jnp.zeros((K,), jnp.int32),
            epoch=jnp.zeros((), jnp.int32),
            step0=jnp.asarray(blk * K, jnp.int32),
            subtask=jnp.arange(P, dtype=jnp.int32))
        by_block, out = op.process_block(by_block, (left, right), bctx)
        outs = []
        for k in range(K):
            at = lambda b: jax.tree_util.tree_map(lambda x: x[k], b)
            by_step, o = op.process2(by_step, at(left), at(right),
                                     bctx.at_step(k))
            outs.append(o)
        for name, a, b in zip(out._fields, out,
                              jax.tree_util.tree_map(
                                  lambda *x: jnp.stack(x), *outs)):
            assert (np.asarray(a) == np.asarray(b)).all(), (blk, name)
        assert set(by_block) == set(by_step)
        for name in by_block:
            assert (np.asarray(by_block[name])
                    == np.asarray(by_step[name])).all(), (blk, name)
        m = np.asarray(out.valid)
        rows.append(np.stack([np.asarray(x)[m] for x in out[:3]], axis=1))
    return by_block, np.concatenate(rows)


_CASES = [
    ("in-order", (), 100, 100),
    # the right input says nothing for three blocks: the watermark stands,
    # the left input runs ahead of the slots and is refused
    ("one-input-silent-for-a-stretch", (3, 4, 5), 100, 100),
    # nothing on the right from the start: placed from the anchor
    ("one-input-silent-at-the-start", (0, 1), 100, 100),
    ("spread-past-the-bound", (), 350, 100),
    ("three-open-windows", (2,), 300, 450)]


@pytest.mark.parametrize("columns", [None, 5, 8], ids=[
    # a column a key on every subtask (a direct construction)
    "a-column-a-key",
    # own columns, every one bound on the fullest subtask: 13 keys over
    # 3 subtasks are 5, 4 and 4
    "own-columns-full",
    # own columns with a tail of NO_KEY on every subtask
    "own-columns-unbound-tail"])
@pytest.mark.parametrize("case,silent,spread,oo", _CASES,
                         ids=[c[0] for c in _CASES])
def test_step_form_equals_block_form_bit_for_bit(case, silent, spread, oo,
                                                 columns):
    """State and output, over blocks of 6 steps against windows of 4
    (every window is split by a block boundary sooner or later). With own
    columns every record sits on the subtask that owns its key, and the
    rows, the totals and the tables' bound columns are those of the
    binding with a column a key, bit for bit."""
    from clonos_tpu.api import operators as ops
    K, P, nk = 6, 3, 13
    blocks = _blocks(7, 9, K, P, 10 if columns is None else 30,
                     silent_right=silent, spread=spread)
    if columns is not None:
        blocks = _to_owners(blocks, P)
    dense = ops.EventTimeWindowJoinOperator(
        num_keys=nk, window_size=400, out_of_orderness=oo, capacity=16)
    assert dense.open_windows == (3 if oo == 450 else 2)
    state, rows = _step_and_block(dense, blocks, K, P)
    total = lambda k: int(np.asarray(state[k]).sum())
    assert len(rows) == total("fired") > 40
    assert total("dropped") == 0
    assert (rows[:, 2] % 400 == 0).all() and (rows[:, 0] < nk).all()
    assert (total("late") > 0) == (case != "in-order")
    if columns is None:
        assert state["left"].shape == (P, dense.open_windows, nk)
        return
    op = ops.EventTimeWindowJoinOperator(
        num_keys=nk, window_size=400, out_of_orderness=oo, capacity=16,
        own_columns=columns)
    cols = _own(nk, P, columns)
    own, own_rows = _step_and_block(op, blocks, K, P, cols)
    assert own["left"].shape == (P, op.open_windows, columns)
    assert np.array_equal(own_rows, rows)
    for name in state:
        a, b = np.asarray(state[name]), np.asarray(own[name])
        if name in op._TABLES:      # column c of q holds key cols[q, c]
            for q in range(P):
                n = int((cols[q] < nk).sum())
                assert np.array_equal(a[q][:, cols[q, :n]], b[q][:, :n])
                assert not b[q][:, n:].any()
        elif name != "cols":
            assert np.array_equal(a, b), name


@pytest.mark.parametrize("sent", ["unbound-keys", "keys-of-NO_KEY"])
def test_a_key_no_column_holds_is_no_record(sent):
    """Keys 4 and 9 are in range and bound on no subtask: their records
    are no records — not late, not counted, no row — and the rest of the
    stream joins as if they had not been sent. So is a key of NO_KEY,
    which names no column, bound or not. Both forms (``_step_and_block``
    holds one to the other)."""
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import zero_invalid
    K, P, nk, unbound = 6, 3, 13, (4, 9)
    blocks = _to_owners(_blocks(7, 6, K, P, 30), P)
    without = [tuple(zero_invalid(b._replace(
        valid=b.valid & ~jnp.isin(b.keys, jnp.asarray(unbound))))
        for b in pair) for pair in blocks]
    if sent == "keys-of-NO_KEY":    # every empty slot 0 holds one, valid
        blocks = [tuple(b._replace(
            keys=jnp.where(b.valid, b.keys, ops.NO_KEY),
            valid=b.valid.at[:, :, 0].set(True)) for b in pair)
            for pair in blocks]
    op = ops.EventTimeWindowJoinOperator(
        num_keys=nk, window_size=400, out_of_orderness=100, capacity=16,
        own_columns=8)
    cols = _own(nk, P, 8, unbound)
    want, want_rows = _step_and_block(op, without, K, P, cols)
    got, got_rows = _step_and_block(op, blocks, K, P, cols)
    assert np.array_equal(got_rows, want_rows) and len(want_rows) > 20
    assert not np.isin(got_rows[:, 0], unbound).any()
    for name in want:
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), name
    assert int(np.asarray(got["late"]).sum()) == 0


def test_rows_past_the_capacity_are_dropped_and_counted():
    from clonos_tpu.api import operators as ops
    op = ops.EventTimeWindowJoinOperator(
        num_keys=13, window_size=400, out_of_orderness=100, capacity=4)
    state, rows = _step_and_block(op, _blocks(7, 6, 6, 3, 10), 6, 3)
    wide = ops.EventTimeWindowJoinOperator(
        num_keys=13, window_size=400, out_of_orderness=100, capacity=16)
    _, all_rows = _step_and_block(wide, _blocks(7, 6, 6, 3, 10), 6, 3)
    dropped = int(np.asarray(state["dropped"]).sum())
    assert dropped > 0 and len(rows) + dropped == len(all_rows)


def test_a_row_needs_both_sides_and_carries_the_right_sides_sum():
    """One subtask, by hand: window [0, 400) holds persons 1, 2 and
    auctions of sellers 2 (twice), 3; only seller 2 has both."""
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch
    op = ops.EventTimeWindowJoinOperator(
        num_keys=8, window_size=400, out_of_orderness=0, capacity=8)

    def batch(recs):
        k, v, t = (jnp.asarray([[r[i] for r in recs]], jnp.int32)
                   for i in range(3))
        return RecordBatch(k, v, t, jnp.ones_like(k, bool))
    state = op.init_state(1)
    state, out = op.process2(
        state, batch([(1, 7, 10), (2, 7, 20)]),
        batch([(2, 100, 30), (2, 2 ** 31 - 1, 40), (3, 5, 50)]), None)
    assert int(out.count().sum()) == 0
    state, out = op.process2(
        state, batch([(5, 0, 400), (5, 0, 401)]),
        batch([(6, 1, 400), (6, 1, 402), (6, 1, 403)]), None)
    rows = [tuple(int(x[0, i]) for x in out[:3])
            for i in range(8) if bool(out.valid[0, i])]
    assert rows == [(2, (100 + 2 ** 31 - 1) - 2 ** 32, 400)]
    assert {k: int(state[k][0]) for k, _ in op.fence_totals} == {
        "late": 0, "fired": 1, "dropped": 0, "left_records": 4,
        "right_records": 6}


# --- the job, through ClusterRunner, against the reference -------------------


def run_job(cfg, seed, epochs, tmp_path, kill=None):
    """``epochs`` completed epochs; ``kill = (vertex, subtask)`` fails
    that subtask half-way, behind two epochs whose checkpoints stay
    pending. Returns (runner, stream, epoch -> committed row arrays)."""
    stream = job.make_stream(cfg, {"table_epochs": 2}, seed)
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


def totals_of(runner):
    state = runner.executor.vertex_state(JOIN)
    return {k: int(np.asarray(state[k]).sum())
            for k in ("late", "fired", "dropped", "left_records",
                      "right_records")}


@pytest.mark.parametrize("victim,num_keys,columns", [
    ((JOIN, 1), 24, None), ((AUCTIONS, 2), 24, None),
    # 1,024 ids over 4 subtasks, 276 at most: 384 own columns
    ((JOIN, 1), 1024, 384)],
    ids=["join", "auctions", "join-on-own-columns"])
def test_committed_stream_equals_the_reference_through_a_kill(
        ref, tmp_path, victim, num_keys, columns):
    """The victim is rebuilt by the two-input replay over a two-epoch gap
    (``join``), or feeds it from a rebuilt ring (``auctions``). The
    tables hold a column a key where the tile of 128 covers every id,
    own columns where it does not: what ``window_join`` derived."""
    cfg = config(num_keys=num_keys)
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 11, 8, tmp_path, kill=victim)
    state = runner.executor.vertex_state(JOIN)
    assert state["left"].shape == (4, 2, columns or num_keys)
    assert runner.job.vertices[JOIN].operator.own_columns == columns
    epochs = runner.executor.epoch_id
    assert epochs == 10
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 1000
    assert totals_of(runner) == {
        "late": 0, "fired": want.fired, "dropped": 0,
        "left_records": want.left, "right_records": want.right}
    # the fence read the same totals into the tracer's counters
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("window.fired_rows.join") == want.fired
    assert grew("window.late_records.join") == 0
    assert grew("join.left_records.join") == want.left
    assert grew("join.right_records.join") == want.right
    assert grew("join.dropped_rows.join") == 0
    # persons are one record in four; every record but the last steps'
    n = epochs * 64 * 4 * 16
    assert n - 4 * 64 <= want.left + want.right <= n
    assert 0.2 < want.left / (want.left + want.right) < 0.3


def test_a_spread_past_the_bound_drops_what_the_reference_drops(
        ref, tmp_path):
    """Events spread over 350 ms of their step against a bound of 100:
    the join drops what arrives behind its watermark, the committed
    stream is the reference's all the same, before and after the kill,
    and both count the same records."""
    cfg = config(spread_ms=350)
    runner, stream, got = run_job(cfg, 5, 8, tmp_path, kill=(JOIN, 3))
    epochs = runner.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    assert ref.check(got, want, cfg, epochs)[:2] == (0, [])
    assert want.late > 1000
    assert totals_of(runner) == {
        "late": want.late, "fired": want.fired, "dropped": 0,
        "left_records": want.left, "right_records": want.right}


@pytest.mark.parametrize("control", ["f32", "at-least-once", "no-join"])
def test_each_control_differs_from_the_reference(ref, control):
    cfg = config()
    epochs = 12
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


def test_reference_reads_windows_off_as_they_close(ref):
    """The reference folds one table period of steps at a time and keeps
    only the windows a later record can still reach; a table as long as
    the run folds everything at once and must agree."""
    cfg = config()
    epochs = 9
    short = job.make_stream(cfg, {"table_epochs": 2}, 23)
    reps = -(-epochs // 2)
    long_keys, long_vals = (np.tile(x, (1, reps))
                            for x in (short.keys, short.vals))
    a = ref.expected(cfg, short.keys, short.vals, epochs)
    b = ref.expected(cfg, long_keys, long_vals, epochs)
    assert ref.check(ref.committed_of(a, cfg, epochs), b, cfg,
                     epochs)[:2] == (0, [])
    assert a[1:] == b[1:] and a.fired > 1000


# --- the planner -------------------------------------------------------------


def _joined(num_keys, p, groups):
    """A job of two keyed host sources into ``window_join``, and the
    join's operator."""
    from clonos_tpu.api.environment import StreamEnvironment
    env = StreamEnvironment(name="own", num_key_groups=groups,
                            default_edge_capacity=16)
    left = env.host_source(batch_size=8, parallelism=p, name="left")
    right = env.host_source(batch_size=8, parallelism=p, name="right")
    joined = left.key_by().window_join(right.key_by(), num_keys=num_keys,
                                       window_size=400, name="join")
    joined.key_by().sink()
    return env.build(), joined.vertex.operator


@pytest.mark.parametrize("num_keys,p,groups,most,columns", [
    # NEXmark Q8's cell: 229-280 ids a subtask, up to the next 128 lanes
    (4096, 16, 128, 280, 384),
    (1024, 4, 64, 276, 384),
    # the tile reaches the table: a column a key
    (24, 4, 64, 7, None), (256, 2, 64, 136, None), (256, 1, 64, 256, None)])
def test_window_join_derives_its_own_columns_from_the_plan(
        num_keys, p, groups, most, columns):
    """``window_join`` takes no width: the most ids any subtask owns
    under the planner's own hash, up to the next 128 lanes, or a column
    a key where that is no narrower."""
    from clonos_tpu.parallel import routing
    owned = routing.own_slots(np.arange(num_keys), p, groups).sum(axis=1)
    assert owned.sum() == num_keys and owned.max() == most
    assert routing.own_columns_width(num_keys, p, groups) == columns
    _, op = _joined(num_keys, p, groups)
    assert op.own_columns == columns
    assert op.init_state(p)["sum"].shape == (p, 2, columns or num_keys)


def test_planner_binds_the_joins_columns_ascending_and_says_so():
    """Every id on exactly one subtask, the one ``routing`` sends it to,
    ascending, then ``NO_KEY``; the plan's note reads ``384 columns, 276
    bound, of 1,024``."""
    from clonos_tpu.api.operators import NO_KEY
    from clonos_tpu.parallel import routing
    from clonos_tpu.runtime.executor import CompiledJob
    tracer = obs.get_tracer()
    seen = len(tracer.records())
    graph, op = _joined(1024, 4, 64)
    compiled = CompiledJob(graph, log_capacity=256, max_epochs=8,
                           inflight_ring_steps=8)
    vid = [v.name for v in graph.vertices].index("join")
    cols = np.asarray(compiled.init_carry().op_states[vid]["cols"])
    assert cols.shape == (4, 384)
    owner = np.asarray(routing.subtask_for_key_group(
        routing.key_group(np.arange(1024, dtype=np.int32), 64), 4, 64))
    for q in range(4):
        mine = cols[q][cols[q] != NO_KEY]
        assert mine.tolist() == np.nonzero(owner == q)[0].tolist()
        assert (cols[q][len(mine):] == NO_KEY).all()
    assert [r["args"] for r in tracer.records()[seen:]
            if r["name"] == "plan.own-columns"] == [
        {"vertex": "join", "columns": 384, "bound": 276, "num_keys": 1024}]


def test_planner_refuses_a_join_whose_subtask_owns_more_than_its_columns():
    """The width is the plan's: under another plan — fewer subtasks, so
    more ids each — the same operator is refused, not run short."""
    from clonos_tpu.runtime.executor import CompiledJob
    kw = dict(log_capacity=256, max_epochs=8, inflight_ring_steps=8)
    graph, op = _joined(1024, 4, 64)
    op.own_columns = 256
    with pytest.raises(ValueError, match="more than the 256 own columns"):
        CompiledJob(graph, **kw)
    narrow, _ = _joined(1024, 4, 64)
    wide, _ = _joined(1024, 2, 64)
    vid = [v.name for v in wide.vertices].index("join")
    wide.vertices[vid].operator = narrow.vertices[vid].operator
    with pytest.raises(ValueError, match="owns 5.. of the 1024 keys"):
        CompiledJob(wide, **kw)
    with pytest.raises(NotImplementedError, match="bound to the keys"):
        op.rescale_keyed_state(op.init_state(4), 2, 64)


def test_planner_routes_the_joins_edges():
    """Both inputs of the join are HASH edges behind filters behind a
    map behind the source: the keys are the feed's, so they stay on the
    dynamic exchange and say so. The join holds own keys and every row
    carries a key it received, so join -> sink is routed in place."""
    from clonos_tpu.runtime.executor import CompiledJob

    tracer = obs.get_tracer()
    seen = len(tracer.records())
    cfg = config()
    compiled = CompiledJob(
        module_at(job.topology_file(cfg, "job.py")).build(cfg))
    names = [v.name for v in compiled.job.vertices]
    assert names == ["host-source", "parse", "persons", "auctions", "join",
                     "sink"]
    plans = {(names[e.src], names[e.dst]): tuple(compiled.edge_plans[i])
             for i, e in enumerate(compiled.job.edges)
             if i in compiled.edge_plans}
    assert plans == {
        ("persons", "join"): ("dynamic", 64, 16, 16),
        ("auctions", "join"): ("dynamic", 64, 16, 16),
        ("join", "sink"): ("identity", 32, 4, 16)}
    assert not compiled.static_route
    noted = [r["args"] for r in tracer.records()[seen:]
             if r["name"] == "exchange.route"]
    assert [(n["route"], n.get("reason")) for n in noted] == [
        ("dynamic", "feed-keys"), ("dynamic", "feed-keys"),
        ("identity", None)]


def test_a_dynamic_edge_behind_an_undeclared_operator_says_so():
    """``IntervalJoinOperator`` holds own keys behind its two keyBys and
    declares nothing the planner could use: its out-edge stays dynamic,
    and reads ``undeclared`` where a feed-keyed edge reads
    ``feed-keys``. ``SessionWindowOperator``, the example until it
    declared ``emits_received_keys``, is routed in place now."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.executor import CompiledJob

    def plans(build):
        env = StreamEnvironment(name="undeclared", num_key_groups=64,
                                default_edge_capacity=16)
        build(env)
        tracer = obs.get_tracer()
        seen = len(tracer.records())
        compiled = CompiledJob(env.build())
        return ([p.route for p in compiled.edge_plans.values()],
                [r["args"].get("reason") for r in tracer.records()[seen:]
                 if r["name"] == "exchange.route"])

    def joined(env):
        left = env.host_source(batch_size=8, parallelism=2, name="left")
        right = env.host_source(batch_size=8, parallelism=2, name="right")
        (left.key_by().join(right.key_by(), num_keys=8, window=4,
                            interval=50)
         .key_by().reduce(num_keys=8).sink())

    assert plans(joined) == (["dynamic"] * 3,
                             ["feed-keys", "feed-keys", "undeclared"])
    assert plans(lambda env: (
        env.host_source(batch_size=8, parallelism=2).key_by()
        .window_session(num_keys=8, gap=50)
        .key_by().reduce(num_keys=8).sink())) == (
            ["dynamic", "identity"], ["feed-keys", None])
