"""NEXmark query 5, "Hot Items": the windowed sum that emits only its
largest (``EventTimeWindowTopOperator``) — step form against block form
bit for bit, own columns and dense, late records and ties included; the
planner's binding of own columns as a leaf of the vertex's state; the
``nexmark-hot-items`` job through ``ClusterRunner`` against its plain
NumPy reference at a tiny size, fault-free and through a kill of a
``count`` subtask and of the ``max`` subtask, its totals and the
exchange's peak against the reference's, each control; and the losses
that have to be loud: a record dropped on an edge, a late record, a row
past a row capacity, a key a subtask does not own."""

import json
import math
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

PARSE, COUNT, MAX, SINK = 1, 2, 3, 4          # vertex ids, job.py's order
EDGE = "parse->count"


def config(**over):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           "tiny-nexmark-q5.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


# --- the operator: step form == block form -----------------------------------


def _top_op(own, slide=20, bound=10, capacity=3, nk=32):
    from clonos_tpu.api.operators import EventTimeWindowTopOperator
    return EventTimeWindowTopOperator(
        num_keys=nk, window_size=100 if slide != 100 else slide,
        slide=slide, out_of_orderness=bound, capacity=capacity,
        own_columns=own)


def _bound_state(op, P, owner):
    """``init_state`` with the columns of ``owner`` (key -> subtask)
    bound, as the planner binds them."""
    from clonos_tpu.api.operators import NO_KEY
    state = op.init_state(P)
    if op.own_columns is None:
        return state
    cols = np.full((P, op.own_columns), NO_KEY, np.int32)
    for q in range(P):
        keys = np.nonzero(owner == q)[0]
        cols[q, :len(keys)] = keys
    return op.bind_own_columns(state, cols)


def _blocks(seed, n_blocks, K, P, B, owner, spread, nk, foreign=0.1,
            share=0.7):
    """Random blocks: a subtask's keys mostly its own (``owner``), some
    from -1 to past the table and some another subtask's; event time
    ``10 * step + [0, spread)``; small values, so that sums tie."""
    import jax.numpy as jnp
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    rng = np.random.RandomState(seed)
    out = []
    for blk in range(n_blocks):
        steps = blk * K + np.arange(K)
        own = np.stack([rng.choice(np.nonzero(owner == q)[0], (K, B))
                        for q in range(P)], axis=1)
        keys = np.where(rng.rand(K, P, B) < foreign,
                        rng.randint(-1, nk + 2, (K, P, B)), own)
        out.append(zero_invalid(RecordBatch(
            jnp.asarray(keys, jnp.int32),
            jnp.asarray(rng.randint(1, 3, (K, P, B)), jnp.int32),
            jnp.asarray(10 * steps[:, None, None]
                        + rng.randint(0, spread, (K, P, B)), jnp.int32),
            jnp.asarray(rng.rand(K, P, B) < share))))
    return out


def _step_and_block(op, state, blocks, K, P):
    """Run ``blocks`` through ``process_block`` and, step by step, through
    ``process``; assert both agree after every block — every leaf but
    ``dense_blocks``, which says how a block's lookup was done and which
    only the block form counts — and return the final state and all
    rows."""
    import jax
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    by_block, by_step, rows = state, state, []
    aside = lambda s: dict(s, dense_blocks=0)
    step_fn = jax.jit(lambda s, b, k, bctx: op.process(s, b,
                                                       bctx.at_step(k)))
    block_fn = jax.jit(op.process_block)
    for i, batches in enumerate(blocks):
        bctx = ops.BlockContext(
            times=jnp.arange(i * K, (i + 1) * K, dtype=jnp.int32),
            rng_bits=jnp.zeros((K,), jnp.int32),
            epoch=jnp.zeros((), jnp.int32),
            step0=jnp.asarray(i * K, jnp.int32),
            subtask=jnp.arange(P, dtype=jnp.int32))
        by_block, out = block_fn(by_block, batches, bctx)
        outs = []
        for k in range(K):
            by_step, o = step_fn(by_step, jax.tree_util.tree_map(
                lambda x: x[k], batches), k, bctx)
            outs.append(o)
        stepped = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *outs)
        assert not np.asarray(by_step["dense_blocks"]).any()
        for a, b in zip(
                jax.tree_util.tree_leaves((aside(by_block), out)),
                jax.tree_util.tree_leaves((aside(by_step), stepped))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rows.append(out)
    return by_block, rows


@pytest.mark.parametrize("own", [None, 12], ids=["dense", "own-columns"])
@pytest.mark.parametrize("slide", [20, 100], ids=["sliding", "tumbling"])
@pytest.mark.parametrize("spread", [10, 160], ids=["in-bound", "late"])
@pytest.mark.parametrize("wide", [False, True],
                         ids=["narrow", "head-and-tails"])
def test_step_form_equals_block_form_bit_for_bit(own, slide, spread, wide):
    """Three blocks of 24 steps over 4 subtasks: the state after each
    block and every row agree, with records from other subtasks' keys
    and from outside the table among them (counted late, with own
    columns; dense, only those outside the table are), records behind
    the watermark at a spread of 160 ms against a bound of 10, and ties
    (values of 1 and 2 over a few keys). ``wide``: four blocks of 8
    steps over 8 receive windows of 384 slots, which the block form
    looks up by head and tails — a block each of steps with up to two
    targets past the head (they change from step to step), of one step
    with three (the dense form, counted in ``dense_blocks``), of two
    every step, and of slots that are no prefix."""
    from test_head_and_tails import WIDE, widely
    K, P, B, nk = WIDE + (32,) if wide else (24, 4, 20, 32)
    owner = (np.random.RandomState(7).permutation(nk) % P if wide
             else np.random.RandomState(7).randint(0, P, nk))
    op = _top_op(own, slide=slide, nk=nk)
    blocks = (widely(_blocks(3, 4, K, P, B, owner, spread, nk, share=1.0), 9)
              if wide else _blocks(3, 3, K, P, B, owner, spread, nk))
    state, rows = _step_and_block(op, _bound_state(op, P, owner), blocks, K,
                                  P)
    total = lambda k: int(np.asarray(state[k]).sum())
    assert total("dense_blocks") == int(wide)
    assert total("fired") == sum(int(r.valid.sum()) for r in rows) > 20
    assert total("late") > 0        # unowned or outside keys at least
    if spread > 10:
        assert total("late") > 200
    # ties: some fire emitted more than one row for one window
    stamps = np.concatenate([np.asarray(r.timestamps)[np.asarray(r.valid)]
                             for r in rows])
    assert len(stamps) > len(np.unique(stamps))


def test_rows_past_the_capacity_are_dropped_and_counted():
    """Eight keys with one record each in one window: all tie at 1; a
    capacity of 3 emits the first three columns and counts five."""
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch
    op = _top_op(None, slide=100, bound=0, capacity=3, nk=8)
    state = op.init_state(1)
    ctx = ops.OpContext(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                        jnp.int32(0), jnp.arange(1, dtype=jnp.int32))
    one = lambda keys, ts: RecordBatch(
        jnp.asarray([keys], jnp.int32), jnp.ones((1, len(keys)), jnp.int32),
        jnp.full((1, len(keys)), ts, jnp.int32),
        jnp.ones((1, len(keys)), jnp.bool_))
    state, out = op.process(state, one(list(range(8)), 50), ctx)
    assert int(out.valid.sum()) == 0
    state, out = op.process(state, one([0], 150), ctx)   # closes [0, 100)
    assert np.asarray(out.keys)[0].tolist() == [0, 1, 2]
    assert np.asarray(out.values)[0].tolist() == [1, 1, 1]
    assert np.asarray(out.timestamps)[0].tolist() == [99, 99, 99]
    assert {k: int(state[k][0]) for k, _ in op.fence_totals} == {
        "late": 0, "fired": 3, "dropped": 5, "dense_blocks": 0}


def test_only_the_largest_goes_on_and_an_unowned_key_is_counted():
    """A subtask that owns keys 2, 5 and 9: key 5 gets 3 records, key 9
    gets 3, key 2 gets 1, key 4 (another subtask's) gets 4: the window's
    rows are keys 5 and 9 with 3 — the tie kept, key 2 not passed on,
    key 4 never counted but counted as lost."""
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch
    op = _top_op(4, slide=100, bound=0, capacity=4, nk=16)
    state = op.bind_own_columns(op.init_state(1), np.asarray(
        [[2, 5, 9, ops.NO_KEY]], np.int32))
    ctx = ops.OpContext(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                        jnp.int32(0), jnp.arange(1, dtype=jnp.int32))
    keys = [5, 9, 4, 5, 9, 4, 2, 5, 9, 4, 4]
    batch = lambda keys, ts: RecordBatch(
        jnp.asarray([keys], jnp.int32), jnp.ones((1, len(keys)), jnp.int32),
        jnp.full((1, len(keys)), ts, jnp.int32),
        jnp.ones((1, len(keys)), jnp.bool_))
    state, _ = op.process(state, batch(keys, 10), ctx)
    state, out = op.process(state, batch([2] * len(keys), 110), ctx)
    got = [tuple(int(x[0, i]) for x in (out.keys, out.values,
                                        out.timestamps))
           for i in range(4) if bool(out.valid[0, i])]
    assert got == [(5, 3, 99), (9, 3, 99)]
    assert int(state["late"][0]) == 4 and int(state["fired"][0]) == 2
    assert op.fence_losses == ("late", "dropped")


# --- the planner: own columns are state --------------------------------------


def _job(p, own_columns, keyed=True, nk=64):
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.operators import EventTimeWindowTopOperator
    env = StreamEnvironment(name="own", num_key_groups=32,
                            default_edge_capacity=16)
    op = EventTimeWindowTopOperator(num_keys=nk, window_size=40, slide=20,
                                    own_columns=own_columns)
    src = env.synthetic_source(vocab=nk, batch_size=16, parallelism=p)
    (src.key_by() if keyed else src)._attach("top", op, p).sink()
    return env.build(), op


def test_the_planner_binds_own_columns_into_the_vertex_state():
    """One operator object under two plans (parallelism 2 and 4): each
    carry holds its own binding, every key on exactly one subtask and on
    the one ``routing`` sends it to; the operator object holds none."""
    from clonos_tpu.parallel import routing
    from clonos_tpu.runtime.executor import CompiledJob
    from clonos_tpu.api.operators import NO_KEY
    seen = {}
    for p in (2, 4):
        graph, op = _job(p, own_columns=48)
        seen.setdefault("op", op)
        graph.vertices[1].operator = seen["op"]     # the same object
        compiled = CompiledJob(graph, log_capacity=256, max_epochs=8,
                               inflight_ring_steps=8)
        cols = np.asarray(compiled.init_carry().op_states[1]["cols"])
        assert cols.shape == (p, 48)
        owner = np.asarray(routing.subtask_for_key_group(
            routing.key_group(np.arange(64, dtype=np.int32), 32), p, 32))
        for q in range(p):
            mine = cols[q][cols[q] != NO_KEY]
            assert mine.tolist() == np.nonzero(owner == q)[0].tolist()
            assert (cols[q][len(mine):] == NO_KEY).all()
    assert not any(isinstance(v, np.ndarray)
                   for v in vars(seen["op"]).values())


def test_the_planner_refuses_what_own_columns_cannot_hold():
    from clonos_tpu.runtime.executor import CompiledJob
    kw = dict(log_capacity=256, max_epochs=8, inflight_ring_steps=8)
    with pytest.raises(ValueError, match="key_by"):
        CompiledJob(_job(2, 48, keyed=False)[0], **kw)
    with pytest.raises(ValueError, match="more than the 20 own columns"):
        CompiledJob(_job(2, 20)[0], **kw)
    graph, op = _job(2, 48)
    with pytest.raises(NotImplementedError, match="bound to the keys"):
        op.rescale_keyed_state(op.init_state(2), 4, 32)
    from clonos_tpu.api.environment import StreamEnvironment
    with pytest.raises(ValueError, match="key_by"):
        StreamEnvironment().synthetic_source(8, 8).window_top(8, 10)


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
    return runner, stream, got


def totals_of(runner, vid):
    state = runner.executor.vertex_state(vid)
    return {k: int(np.asarray(state[k]).sum())
            for k in ("late", "fired", "dropped")}


@pytest.mark.parametrize("victim", [None, (COUNT, 1), (MAX, 0)],
                         ids=["fault-free", "count", "max"])
def test_committed_stream_equals_the_reference(ref, tmp_path, victim):
    """Limit 0 over the whole committed stream; the program's totals and
    the exchange's fullest step are the reference's; the fence read them
    into the tracer's counters."""
    cfg = config()
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 11, 8, tmp_path, kill=victim)
    assert runner.executor.check_overflow() == []
    epochs = runner.executor.epoch_id
    assert epochs == (8 if victim is None else 10)
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 150
    assert (want.late, want.over_capacity, want.dropped) == (0, 0, 0)
    assert totals_of(runner, COUNT) == {
        "late": 0, "fired": want.partial_rows, "dropped": 0}
    assert totals_of(runner, MAX) == {
        "late": 0, "fired": want.fired, "dropped": 0}
    # ties at a subtask, fewer over all of them
    windows = epochs * 64 * 7 / 20
    assert want.fired >= windows - 6
    assert want.partial_rows > 4 * want.fired * 0.8
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["peak"].tolist()[0] == want.peak > 32
    assert not parts["dropped"].any()
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("exchange.peak_records." + EDGE) == want.peak
    assert "exchange.dropped_records." + EDGE not in after
    assert grew("window.fired_rows.count") == want.partial_rows
    assert grew("window.fired_rows.max") == want.fired
    for v in ("count", "max"):
        assert grew(f"window.late_records.{v}") == 0
        assert grew(f"window.dropped_rows.{v}") == 0


@pytest.mark.parametrize("control", ["at-least-once", "arrival-time",
                                     "local-max"])
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
    if control == "local-max":      # every subtask's leaders, not one's
        assert perturbed.fired > 3 * want.fired
    assert ref.check(ref.committed_of(want, cfg, epochs), want, cfg,
                     epochs)[:2] == (0, [])


def test_all_subtasks_leaders_give_the_uncut_folds_maximum_and_ties(ref):
    """The cut that makes the table fit — a subtask counts only the
    auctions it owns and passes on its own leaders — loses nothing: over
    every window, the largest of the subtasks' rows and the rows that
    reach it are the maximum and the ties of a fold that knows no
    subtasks (written here, bid by bid, from the table)."""
    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 17)
    n_steps = 200
    fold = ref.fold(cfg, stream.vals, n_steps, None, 0)
    partial, top = ref.leaders(cfg, fold.panes)
    # the uncut fold: every bid into its five windows, by its true id
    period = stream.vals.shape[1] // 16
    v = stream.vals.reshape(4, period, 16).transpose(1, 0, 2).reshape(
        period, -1)[np.arange(n_steps) % period].astype(np.int64)
    ts = 7 * np.arange(n_steps)[:, None] + ((v >> 1) & 1023) % 7
    last = ts * 3 // 5
    auction = np.where(v & 1 == 1, last // 10 * 10, last - (v >> 11) % 11)
    counts = {}
    for t, a in zip(ts.ravel().tolist(), auction.ravel().tolist()):
        for j in range(5):
            end = (t // 20 - j) * 20 + 100
            counts.setdefault(end, {}).setdefault(a, 0)
            counts[end][a] += 1
    whole = lambda rows: sorted(r for r in rows if r[0] <= 7 * n_steps)
    uncut = whole((end, a, n) for end, c in counts.items()
                  for a, n in c.items() if n == max(c.values()))
    assert whole(zip(top.end.tolist(), top.auction.tolist(),
                     top.num.tolist())) == uncut
    best = {}
    for end, n in zip(partial.end.tolist(), partial.num.tolist()):
        best[end] = max(best.get(end, 0), n)
    cut = whole((e, a, n) for e, a, n in zip(
        partial.end.tolist(), partial.auction.tolist(),
        partial.num.tolist()) if n == best[e])
    assert cut == uncut and len(partial.end) > 3 * len(uncut) > 100


# --- losses are loud ---------------------------------------------------------


def test_a_capacity_under_the_hot_targets_load_drops_loudly(ref, tmp_path):
    """``parse -> count`` cut to 24 records a target a step, under the
    hot target's ~40: the exchange's counter holds exactly the bids the
    reference says do not fit, the overflow message names the edge, and
    the fence stops the run."""
    from clonos_tpu.runtime.cluster import OverflowError_
    cfg = config(edge_capacity=24, overlap_epoch=False)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 29)
    runner = job.make_runner(cfg, stream, 29, str(tmp_path / "ck"), 1)
    before = obs.get_tracer().counters()
    with pytest.raises(OverflowError_, match="edge parse->count dropped"):
        runner.run_epoch()
    want = ref.expected(cfg, stream.keys, stream.vals, 1)
    assert want.peak > 24 and want.dropped > 100
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["dropped"].tolist()[1] == want.dropped
    assert parts["peak"].tolist()[0] == want.peak
    (message,) = [m for m in ex.check_overflow() if "parse->count" in m]
    assert message == (f"edge parse->count dropped {want.dropped} records "
                       f"past its capacity 24")
    after = obs.get_tracer().counters()
    assert after["exchange.dropped_records." + EDGE] \
        - before.get("exchange.dropped_records." + EDGE, 0) == want.dropped


@pytest.mark.parametrize("cut, counter", [
    ({"spread_ms": 150}, "window.late_records"),
    ({"partial_capacity": 1}, "window.dropped_rows")],
    ids=["late-record", "row-past-capacity"])
def test_a_loss_at_a_window_vertex_is_an_overflow_message(tmp_path, cut,
                                                          counter):
    """Bids spread over 150 ms, more than a window and its bound, come
    late at ``count``; one row a step there drops a subtask's ties:
    either is a line of
    ``check_overflow()`` that names the vertex, in a run with no
    tracing."""
    cfg = config(**cut)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 31)
    runner = job.make_runner(cfg, stream, 31, str(tmp_path / "ck"), 1)
    assert runner.executor.check_overflow() == []
    for _ in range(48):
        runner.step()
    lost = totals_of(runner, COUNT)[
        "late" if counter.endswith("late_records") else "dropped"]
    assert lost > 0
    assert runner.executor.check_overflow() == [
        f"vertex 'count' lost {lost} ({counter})"]


# --- the configuration file --------------------------------------------------


def test_the_edge_capacity_is_the_rules_arithmetic():
    """``configs/nexmark-q5.json``: the fullest target of ``parse ->
    count`` is the hot auction's owner; over the whole id ring, the most
    in-flight ids that owner also owns gives its worst mean load; six
    binomial deviations over it, up to the next 128-lane tile."""
    import jax.numpy as jnp
    from clonos_tpu.parallel import routing
    with open(os.path.join(BENCH, "configs", "nexmark-q5.json")) as f:
        cfg = json.load(f)
    nk, groups, p = cfg["num_keys"], cfg["num_key_groups"], \
        cfg["parallelism"]
    every, behind = cfg["hot_auction_every"], cfg["in_flight_auctions"]
    owner = np.asarray(routing.subtask_for_key_group(
        routing.key_group(jnp.arange(nk, dtype=jnp.int32), groups), p,
        groups))
    assert np.bincount(owner, minlength=p).max() <= cfg["own_columns"]
    newest = np.arange(math.lcm(every, nk))    # every (newest, hot) pair
    hot_owner = owner[newest // every * every % nk]
    shared = sum((owner[(newest - d) % nk] == hot_owner).astype(np.int64)
                 for d in range(behind + 1))
    assert shared.max() == 20 and abs(shared.mean() - 7.27) < 0.01
    records = p * cfg["batch"]
    share = 1 / cfg["hot_ratio"] + (1 - 1 / cfg["hot_ratio"]) \
        * shared.max() / (behind + 1)
    need = records * share + 6 * math.sqrt(records * share * (1 - share))
    assert abs(records * share - 613.4) < 0.05 and abs(need - 707.5) < 0.05
    assert cfg["edge_capacity"] == -(-need // 128) * 128 == 768
    assert cfg["reduced"] == ["run_length"] and cfg["sharing_depth"] == -1
