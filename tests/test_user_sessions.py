"""NEXmark query 11, "User Sessions": event-time session windows that
merge as Flink's do (``SessionWindowOperator``) — step form against block
form bit for bit, own columns and dense, on traffic with splits, bridges,
the two-session race, keys a subtask does not own and rows past the
capacity, and both against a record-by-record fold of Flink's merging
windows; the ``nexmark-user-sessions`` job through ``ClusterRunner``
against its plain NumPy reference at a tiny size, fault-free and through
a kill of a ``sessions`` subtask, its totals, the exchange's peak and the
most sessions held open against the reference's, each control; the
planner's ``identity`` edge behind the vertex; the losses that have to be
loud; and the configuration file's ``edge_capacity`` arithmetic."""

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

PARSE, SESSIONS, SINK = 1, 2, 3               # vertex ids, job.py's order
EDGE = "parse->sessions"
NO_TS = -(2 ** 31) + 1


def config(**over):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           "tiny-nexmark-q11.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


# --- the operator: step form == block form == Flink, record by record --------


def _op(own=None, gap=12, bound=10, capacity=None, nk=8):
    from clonos_tpu.api.operators import SessionWindowOperator
    return SessionWindowOperator(num_keys=nk, gap=gap,
                                 out_of_orderness=bound, capacity=capacity,
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


def _blocks(seed, n_blocks, K, P, B, owner, tick, spread, nk, share=0.12,
            foreign=0.05):
    """Random blocks: a subtask's keys mostly its own (``owner``; None:
    any key), some from -1 to past the table; event time ``tick * step +
    [0, spread)``; values 0-2, so that some sessions sum to 0."""
    import jax.numpy as jnp
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    rng = np.random.RandomState(seed)
    out = []
    for blk in range(n_blocks):
        steps = blk * K + np.arange(K)
        if owner is None:
            own = rng.randint(0, nk, (K, P, B))
        else:
            own = np.stack([rng.choice(np.nonzero(owner == q)[0], (K, B))
                            for q in range(P)], axis=1)
        keys = np.where(rng.rand(K, P, B) < foreign,
                        rng.randint(-1, nk + 2, (K, P, B)), own)
        out.append(zero_invalid(RecordBatch(
            jnp.asarray(keys, jnp.int32),
            jnp.asarray(rng.randint(0, 3, (K, P, B)), jnp.int32),
            jnp.asarray(tick * steps[:, None, None]
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
        assert (jax.tree_util.tree_structure((by_block, out))
                == jax.tree_util.tree_structure((by_step, stepped)))
        assert not np.asarray(by_step["dense_blocks"]).any()
        for a, b in zip(
                jax.tree_util.tree_leaves((aside(by_block), out)),
                jax.tree_util.tree_leaves((aside(by_step), stepped))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rows.append(out)
    return by_block, rows


def _flink(blocks, P, gap, bound, held):
    """Flink's merging session windows, record by record, under the
    batched watermark: per step and subtask the sorted rows, and how
    often a record merged two sessions (a bridge), opened a session
    beside an open one (the race), or was refused. ``held(p, key)``:
    whether subtask ``p`` has a column for ``key``."""
    max_ts = [NO_TS] * P
    open_ = [dict() for _ in range(P)]          # key -> [[lo, hi, sum]]
    rows, seen = [], dict(bridges=0, races=0, late=0, sessions=0)
    for b in blocks:
        keys, vals, ts, valid = (np.asarray(x) for x in (
            b.keys, b.values, b.timestamps, b.valid))
        for k in range(keys.shape[0]):
            step = []
            for p in range(P):
                v = valid[k, p]
                if v.any():
                    max_ts[p] = max(max_ts[p], int(ts[k, p][v].max()))
                wm = NO_TS if max_ts[p] == NO_TS else max_ts[p] - bound
                fired = []
                for key, ss in open_[p].items():
                    for s in list(ss):
                        if s[1] + gap <= wm:
                            ss.remove(s)
                            seen["sessions"] += 1
                            if s[2] != 0:
                                fired.append((key, s[2], s[1] + gap))
                step.append(sorted(fired))
                for j in np.nonzero(v)[0]:
                    key, t, val = (int(keys[k, p, j]), int(ts[k, p, j]),
                                   int(vals[k, p, j]))
                    if not held(p, key) or t + gap <= wm:
                        seen["late"] += 1
                        continue
                    ss = open_[p].setdefault(key, [])
                    touch = [s for s in ss
                             if t <= s[1] + gap and t + gap >= s[0]]
                    seen["bridges"] += len(touch) == 2
                    seen["races"] += bool(ss) and not touch
                    for s in touch:
                        ss.remove(s)
                    ss.append([min([t] + [s[0] for s in touch]),
                               max([t] + [s[1] for s in touch]),
                               val + sum(s[2] for s in touch)])
                    assert len(ss) <= 2
            rows.append(step)
    return rows, seen


def _rows_by_step(rows, K, P):
    out = []
    for r in rows:
        kk, vv, tt, va = (np.asarray(x) for x in (r.keys, r.values,
                                                  r.timestamps, r.valid))
        for k in range(K):
            out.append([sorted((int(a), int(b), int(c)) for a, b, c, d in
                               zip(kk[k, p], vv[k, p], tt[k, p], va[k, p])
                               if d) for p in range(P)])
    return out


@pytest.mark.parametrize("own", [None, 5], ids=["dense", "own-columns"])
@pytest.mark.parametrize("gap, bound, tick, spread, seed, bridges", [
    (12, 10, 4, 11, 4, 1), (12, 10, 4, 11, 5, 1), (30, 10, 10, 10, 2, 0),
    (20, 15, 3, 16, 1, 2)], ids=["bridges", "bridges-2", "splits", "races"])
def test_within_the_bound_both_forms_are_flinks_sessions(
        own, gap, bound, tick, spread, seed, bridges):
    """Six blocks of 16 steps over 3 subtasks, every record within the
    bound of its subtask's newest (``spread <= bound + 1``), pauses
    around ``gap``: the step form and the block form agree bit for bit
    after every block, and their rows are, step by step, those of the
    record-by-record fold — with sessions split, two open at once for a
    key, records that bridge two, keys the subtask holds no column for
    and records out of the table among them."""
    K, P, B, nk = 16, 3, 6, 8
    owner = (None if own is None
             else np.random.RandomState(7).randint(0, P, nk))
    op = _op(own, gap=gap, bound=bound, nk=nk)
    blocks = _blocks(seed, 6, K, P, B, owner, tick, spread, nk)
    state, rows = _step_and_block(op, _bound_state(op, P, owner), blocks, K,
                                  P)
    held = (lambda p, key: 0 <= key < nk) if own is None else (
        lambda p, key: 0 <= key < nk and owner[key] == p)
    want, seen = _flink(blocks, P, gap, bound, held)
    assert _rows_by_step(rows, K, P) == want
    total = lambda k: int(np.asarray(state[k]).sum())
    assert total("fired") == sum(len(r) for s in want for r in s) > 10
    assert (total("late"), total("dropped"), total("disordered")) == (
        seen["late"], 0, 0)
    assert seen["late"] > 0 and seen["races"] > 5
    assert seen["sessions"] > total("fired")        # some summed to 0
    assert seen["bridges"] >= bridges
    assert int(np.asarray(state["open_peak"]).max()) > 2


@pytest.mark.parametrize("own", [None, 5], ids=["dense", "own-columns"])
@pytest.mark.parametrize("capacity", [None, 2], ids=["all-rows", "overflow"])
@pytest.mark.parametrize("wide", [False, True],
                         ids=["narrow", "head-and-tails"])
@pytest.mark.parametrize("gap, bound, tick, spread", [
    (30, 10, 10, 40), (30, 25, 10, 60), (50, 10, 10, 80), (30, 10, 3, 35)],
    ids=["behind-bound", "wide-bound", "late", "slow-clock"])
def test_step_form_equals_block_form_bit_for_bit(own, capacity, wide, gap,
                                                 bound, tick, spread):
    """The same with records behind the bound — arrivals that spread over
    more than ``gap`` or fall below their key's newest session (merged
    all the same, and counted), records whose own window the watermark
    has passed (late) — and a capacity of 2 rows a subtask a step: state,
    rows, their order and every total agree after each of four blocks.
    ``wide``: over 8 receive windows of 384 slots, which the block form
    looks up by head and tails — a block each of steps with up to two
    targets past the head (they change from step to step), of one step
    with three (the dense form, counted in ``dense_blocks``), of two
    every step, and of slots that are no prefix."""
    from test_head_and_tails import WIDE, widely
    K, P, B, nk = WIDE + (24,) if wide else (16, 3, 6, 8)
    owner = (None if own is None else np.arange(nk) % P if wide
             else np.random.RandomState(7).randint(0, P, nk))
    op = _op(own, gap=gap, bound=bound, capacity=capacity, nk=nk)
    blocks = _blocks(5, 4, K, P, B, owner, tick, spread, nk,
                     share=1.0 if wide else 0.3)
    if wide:
        # a third of the keys pause for the two blocks in the middle (their
        # records go to a key of the same subtask), so that sessions close
        for i in (1, 2):
            k = blocks[i].keys
            blocks[i] = blocks[i]._replace(
                keys=k - P * ((k >= P) & (k < 2 * P)))
        blocks = widely(blocks, 9)
    state, rows = _step_and_block(op, _bound_state(op, P, owner), blocks, K,
                                  P)
    total = lambda k: int(np.asarray(state[k]).sum())
    assert total("dense_blocks") == int(wide)
    assert total("fired") == sum(int(r.valid.sum()) for r in rows) > 0
    assert total("late") > 0
    if capacity is None:
        assert total("dropped") == 0
    elif own is None:               # eight keys a subtask: rows collide
        assert total("dropped") > 0
    if spread >= gap + 10:
        assert total("disordered") > 0
    assert int(np.asarray(state["open_peak"]).max()) > (
        nk // 2 if own is None else 2)


def _run_steps(op, steps, cols=None, cap=8):
    """One subtask, records ``(key, value, ts)`` a step: final state and
    the rows of each step."""
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    state = op.init_state(1)
    if cols is not None:
        state = op.bind_own_columns(state, np.asarray([cols], np.int32))
    ctx = ops.OpContext(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                        jnp.int32(0), jnp.arange(1, dtype=jnp.int32))
    fired = []
    for recs in steps:
        lanes = np.zeros((4, 1, cap), np.int32)
        for j, rec in enumerate(recs):
            lanes[:3, 0, j], lanes[3, 0, j] = rec, 1
        state, out = op.process(state, zero_invalid(RecordBatch(
            *(jnp.asarray(x) for x in lanes[:3]),
            jnp.asarray(lanes[3].astype(bool)))), ctx)
        m = np.asarray(out.valid[0])
        fired.append([tuple(int(x[0, i]) for x in (
            out.keys, out.values, out.timestamps)) for i in np.nonzero(m)[0]])
    return state, fired


def test_a_record_opens_a_session_beside_the_one_that_still_waits():
    """The race the single-session form lost: key 1's session ends at 20
    and waits for the watermark (bound 5) when a bid at 26 arrives — more
    than ``gap`` past it, so a second session, not a late record; both
    fire, the older first."""
    state, fired = _run_steps(_op(gap=10, bound=5), [
        [(1, 1, 8), (1, 1, 10)],            # [8, 10], ends at 20
        [(1, 2, 24)],                       # wm 19: the first still open
        [(2, 1, 26)],                       # wm 21: the first fires
        [(2, 1, 60)]])                      # wm 55: the rest
    assert fired == [[], [], [(1, 2, 20)], [(1, 2, 34), (2, 1, 36)]]
    assert int(state["late"][0]) == 0 and int(state["open_peak"][0]) == 2


def test_a_record_that_touches_both_open_sessions_merges_them():
    """Sessions [0, 2] and [14, 14] of key 3 (12 apart: two), then a bid
    at 9 within the bound: within ``gap`` of both, one session of three
    bids that ends at 24. A bid exactly ``gap`` past a session touches
    it."""
    state, fired = _run_steps(_op(gap=10, bound=8), [
        [(3, 1, 0), (3, 1, 2)],
        [(3, 1, 14)],                       # wm 6: [0, 2] still open
        [(3, 1, 9)],                        # reaches both
        [(3, 1, 24)],                       # 14 + gap: touches
        [(5, 1, 70)]])
    assert fired[:4] == [[], [], [], []]
    assert fired[4] == [(3, 5, 34)]
    assert int(state["open_peak"][0]) == 2 and int(state["late"][0]) == 0


def test_what_the_watermark_has_passed_is_late_and_a_key_without_a_column():
    """Own columns 2, 5 and 9: a bid on key 4 counts as late; so does one
    whose own window ends behind the watermark; one within ``gap`` of a
    session that has fired opens a new session."""
    from clonos_tpu.api.operators import NO_KEY
    state, fired = _run_steps(_op(own=4, gap=10, bound=0, nk=16), [
        [(5, 1, 0), (4, 1, 0)],             # key 4: another subtask's
        [(9, 1, 50)],                       # wm 50: [0, 0] fires
        [(5, 7, 2)],                        # [2, 12) ends behind 50
        [(5, 1, 55)],                       # a new session
        [(9, 1, 200)]], cols=[2, 5, 9, NO_KEY])
    assert fired == [[], [(5, 1, 10)], [], [], [(5, 1, 65), (9, 1, 60)]]
    assert int(state["late"][0]) == 2 and int(state["disordered"][0]) == 0


def test_rows_past_the_capacity_are_dropped_and_counted():
    """Six keys' sessions fire in one step: a capacity of 4 emits the
    first four columns and counts two."""
    op = _op(gap=10, bound=0, capacity=4, nk=8)
    state, fired = _run_steps(op, [
        [(k, k + 1, 5) for k in range(6)], [(7, 1, 100)]])
    assert fired[1] == [(k, k + 1, 15) for k in range(4)]
    assert {k: int(state[k][0]) for k, _ in op.fence_totals} == {
        "late": 0, "fired": 4, "dropped": 2, "disordered": 0,
        "dense_blocks": 0}
    assert op.fence_losses == ("late", "dropped", "disordered")
    assert op.fence_peaks == (("open_peak", "window.open_sessions"),)


def test_what_the_operator_refuses():
    from clonos_tpu.api.environment import StreamEnvironment
    with pytest.raises(ValueError, match="third"):
        _op(gap=10, bound=10)
    op = _op(own=4)
    with pytest.raises(NotImplementedError, match="bound to the keys"):
        op.rescale_keyed_state(op.init_state(2), 4, 32)
    with pytest.raises(ValueError, match="key_by"):
        StreamEnvironment().synthetic_source(8, 8).window_session(8, 10)
    assert op.static_out_keys() is None and op.emits_received_keys
    assert _op().out_capacity == 16
    assert _op(own=4, capacity=3).out_capacity == 3


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


def totals_of(runner, vid=SESSIONS):
    state = runner.executor.vertex_state(vid)
    return {k: int(np.asarray(state[k]).sum())
            for k in ("late", "fired", "dropped", "disordered")}


@pytest.mark.parametrize("own", [640, None], ids=["own-columns", "dense"])
@pytest.mark.parametrize("victim", [None, (SESSIONS, 1)],
                         ids=["fault-free", "sessions"])
def test_committed_stream_equals_the_reference(ref, tmp_path, victim, own):
    """Limit 0 over the whole committed stream, with a session gap of
    1,000 ms under pauses of 443 ms on average, so that sessions split;
    the program's totals, the exchange's fullest step and the most
    sessions a subtask held open are the reference's; every bid is in a
    row or in a session still open; the fence read the totals into the
    tracer's counters."""
    cfg = config(own_columns=own)
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 11, 20, tmp_path, kill=victim)
    assert runner.executor.check_overflow() == []
    epochs = runner.executor.epoch_id
    assert epochs == (20 if victim is None else 22)
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 2000
    assert (want.late, want.over_capacity, want.dropped) == (0, 0, 0)
    assert totals_of(runner) == {"late": 0, "fired": want.fired,
                                 "dropped": 0, "disordered": 0}
    rows = np.concatenate(want.rows)
    # the hot bidders' sessions, the cold ones', and sessions that split:
    # more rows than bidders
    assert rows[:, 1].max() > 3000 and np.median(rows[:, 1]) < 10
    assert len(rows) > 1.2 * len(np.unique(rows[:, 0]))
    state = runner.executor.vertex_state(SESSIONS)
    still_open = int(np.asarray(state["a_sum"]).sum()
                     + np.asarray(state["b_sum"]).sum())
    assert int(rows[:, 1].sum()) <= want.bids
    fired_bids = want.bids - still_open
    assert fired_bids > 0.5 * want.bids
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["peak"].tolist() == [want.peak] and want.peak > 48
    assert parts["marks"].tolist() == [want.open_peak]
    assert want.open_peak == int(np.asarray(state["open_peak"]).max()) > 200
    assert not parts["dropped"].any()
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("exchange.peak_records." + EDGE) == want.peak
    assert "exchange.dropped_records." + EDGE not in after
    assert grew("window.fired_rows.sessions") == want.fired
    assert grew("window.open_sessions.sessions") == want.open_peak
    for name in ("late_records", "dropped_rows", "disordered_arrivals"):
        assert grew(f"window.{name}.sessions") == 0


@pytest.mark.parametrize("control", ["at-least-once", "fixed-window",
                                     "arrival-time"])
def test_each_control_differs_from_the_reference(ref, control):
    cfg = config()
    epochs = 24
    stream = job.make_stream(cfg, {"table_epochs": 2}, 3)
    table = (cfg, stream.keys, stream.vals, epochs)
    want = ref.expected(*table)
    perturbed = ref.expected(*table, control=control,
                             control_step=epochs * 32)
    bad, failed, _ = ref.check(ref.committed_of(perturbed, cfg, epochs),
                               want, cfg, epochs)
    assert bad > 0 and failed
    if control == "fixed-window":       # a life cut at every window's end
        assert perturbed.fired > 2 * want.fired
    assert ref.check(ref.committed_of(want, cfg, epochs), want, cfg,
                     epochs)[:2] == (0, [])


def test_the_references_sessions_are_a_bid_by_bid_folds(ref):
    """The reference's cut — hot bids from a per-step histogram, cold
    ones by one sort a table period, a period folded with the steps its
    youngest bidder can still bid in — loses nothing: its sessions are
    those of a fold written here bid by bid from the table, by true
    id."""
    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 17)
    n_steps = 700                        # five and a half table periods
    found = ref.fold(cfg, stream.vals, n_steps, None, 0).sessions
    period = stream.vals.shape[1] // 16
    v = stream.vals.reshape(4, period, 16).transpose(1, 0, 2).reshape(
        period, -1)[np.arange(n_steps) % period].astype(np.int64)
    ts = 7 * np.arange(n_steps)[:, None] + ((v >> 2) & 1023) % 7
    last = ts // 5
    bidder = np.where(v & 3 != 0, last // 100 * 100 + 1,
                      last - 999 + (v >> 12) % 1010)
    by_id = {}
    for b, t in zip(bidder.ravel().tolist(), ts.ravel().tolist()):
        by_id.setdefault(b, []).append(t)
    want = []
    for b, times in by_id.items():
        times.sort()
        start = 0
        for i in range(1, len(times) + 1):
            if i == len(times) or times[i] - times[i - 1] > cfg["gap_ms"]:
                want.append((b, i - start, times[i - 1]))
                start = i
    got = sorted(zip(found.bidder.tolist(), found.n.tolist(),
                     found.last.tolist()))
    assert got == sorted(want) and len(got) > 1.2 * len(by_id) > 1000


# --- the planner -------------------------------------------------------------


def test_sessions_to_sink_is_routed_in_place(tmp_path):
    """``parse -> sessions`` stays on the dynamic exchange (its keys are
    what the feed says); ``sessions -> sink`` moves nothing: the vertex
    emits the keys it received, each on its owner."""
    cfg = config()
    tracer = obs.get_tracer()
    seen = len(tracer.records())
    stream = job.make_stream(cfg, {"table_epochs": 2}, 5)
    runner = job.make_runner(cfg, stream, 5, str(tmp_path / "ck"), 1)
    compiled = runner.executor.compiled
    assert [(compiled.edge_name(e), p.route)
            for e, p in sorted(compiled.edge_plans.items())] == [
        (EDGE, "dynamic"), ("sessions->sink", "identity")]
    noted = [r["args"] for r in tracer.records()[seen:]
             if r["name"] == "exchange.route" and "edge" in r["args"]]
    assert [(n["route"], n.get("reason")) for n in noted] == [
        ("dynamic", "feed-keys"), ("identity", None)]
    cols = np.asarray(runner.executor.carry.op_states[SESSIONS]["cols"])
    assert cols.shape == (4, cfg["own_columns"])
    assert compiled.peak_edges() == [1]
    assert [(v.name, k) for v, k, _ in compiled.fence_peak_slots()] == [
        ("sessions", "open_peak")]


# --- losses are loud ---------------------------------------------------------


@pytest.mark.parametrize("cut, counter, key", [
    ({"spread_ms": 1500, "clock_ms_per_step": 1500,
      "max_out_of_order_ms": 900, "hot_bidder_every": 1000},
     "window.disordered_arrivals", "disordered"),
    ({"max_out_of_order_ms": 0, "gap_ms": 4}, "window.late_records", "late"),
    ({"session_capacity": 1}, "window.dropped_rows", "dropped")],
    ids=["arrival-wider-than-the-gap", "late-record", "row-past-capacity"])
def test_a_loss_at_the_session_vertex_is_an_overflow_message(tmp_path, cut,
                                                             counter, key):
    """A step whose events spread over 1,500 ms, under a hot bidder that
    lasts 5,000, puts bids of one bidder more than the gap of 1,000
    apart into one arrival; a gap of 4 ms
    under a spread of 7 with no bound makes a bid's own window end
    behind the watermark; one row a subtask a step drops sessions that
    fire together: each is a line of ``check_overflow()`` that names the
    vertex, in a run with no tracing."""
    cfg = config(**cut)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 31)
    runner = job.make_runner(cfg, stream, 31, str(tmp_path / "ck"), 1)
    assert runner.executor.check_overflow() == []
    for _ in range(320):
        runner.step()
        lost = totals_of(runner)[key]
        if lost:
            break
    assert lost > 0
    assert f"vertex 'sessions' lost {lost} ({counter})" in \
        runner.executor.check_overflow()


def test_a_capacity_under_the_hot_targets_load_drops_loudly(ref, tmp_path):
    """``parse -> sessions`` cut to 40 records a target a step, under the
    hot bidder's owner's ~50: the exchange's counter holds exactly the
    bids the reference says do not fit, and the fence stops the run."""
    from clonos_tpu.runtime.cluster import OverflowError_
    cfg = config(edge_capacity=40, overlap_epoch=False)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 29)
    runner = job.make_runner(cfg, stream, 29, str(tmp_path / "ck"), 1)
    with pytest.raises(OverflowError_, match="edge parse->sessions dropped"):
        runner.run_epoch()
    want = ref.expected(cfg, stream.keys, stream.vals, 1)
    assert want.peak > 40 and want.dropped > 100
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["dropped"].tolist()[1] == want.dropped
    assert parts["peak"].tolist() == [want.peak]


# --- the configuration file --------------------------------------------------


def test_the_edge_capacity_is_the_rules_arithmetic():
    """``configs/nexmark-q11.json``: the fullest target of ``parse ->
    sessions`` is the hot bidder's owner; over the whole id ring, the
    most of the 1,010 eligible ids that owner also owns gives its worst
    mean load; six binomial deviations over it, up to the next 128-lane
    tile."""
    import jax.numpy as jnp
    from clonos_tpu.parallel import routing
    with open(os.path.join(BENCH, "configs", "nexmark-q11.json")) as f:
        cfg = json.load(f)
    nk, groups, p = cfg["num_keys"], cfg["num_key_groups"], \
        cfg["parallelism"]
    every, active, lead = (cfg["hot_bidder_every"], cfg["active_people"],
                           cfg["person_id_lead"])
    owner = np.asarray(routing.subtask_for_key_group(
        routing.key_group(jnp.arange(nk, dtype=jnp.int32), groups), p,
        groups))
    assert np.bincount(owner, minlength=p).max() <= cfg["own_columns"]
    newest = np.arange(math.lcm(every, nk))    # every (newest, hot) pair
    hot_owner = owner[(newest // every * every + 1) % nk]
    # how many of the eligible ids newest - 999 .. newest + 10 the hot
    # bidder's owner holds: a running count over the ring
    mine = (owner[None, :] == np.arange(p)[:, None]).astype(np.int64)
    upto = np.concatenate([np.zeros((p, 1), np.int64),
                           np.cumsum(np.tile(mine, (1, 3)), axis=1)], axis=1)
    lo = newest % nk + nk - (active - 1)
    shared = upto[hot_owner, lo + active + lead] - upto[hot_owner, lo]
    assert shared.max() == 93 and abs(shared.mean() - 64.1) < 0.1
    records = p * cfg["batch"]
    cold = 1 / cfg["hot_ratio"]
    share = 1 - cold + cold * shared.max() / (active + lead)
    need = records * share + 6 * math.sqrt(records * share * (1 - share))
    assert abs(records * share - 791.6) < 0.05 and abs(need - 872.0) < 0.05
    assert cfg["edge_capacity"] == -(-need // 128) * 128 == 896
    assert cfg["reduced"] == ["run_length"] and cfg["sharing_depth"] == -1
    assert cfg["gap_ms"] == 10000 and cfg["max_out_of_order_ms"] == 111
