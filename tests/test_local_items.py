"""NEXmark query 3, "Local Item Suggestion": the join with no window
(``IncrementalJoinOperator``, Beam ``Query3``'s rule per key) — step form
against block form bit for bit and both against a record-by-record fold,
own columns and dense, on traffic that makes every branch (a duplicate
person, an auction before its person and the flush, a person and a
waiting auction that expire, an auction after its person expired, a hot
key that moves, a bag and a row capacity that overflow loudly), with
chunks that are quiet and chunks that are not; the ``nexmark-local-items``
job through ``ClusterRunner`` against its plain NumPy reference at a tiny
size, fault-free and through a kill of a ``join`` subtask, its totals and
peaks against the reference's, each control; what the planner plans and
refuses; the losses that have to be loud; and the configuration file's
capacities held to the rules it states."""

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

PERSONS, AUCTIONS, JOIN, SINK = 2, 3, 4, 5     # vertex ids, job.py's order
NO_TS = -(2 ** 31) + 1
TOTALS = ("rows", "flushed", "bagged", "bag_expired", "duplicates",
          "expired_probes", "bag_overflow", "dropped", "unplaced",
          "ring_too_small")


def config(**over):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           "tiny-nexmark-q3.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


# --- the operator: step form == block form == Beam's rule, record by record --


def _op(nk=40, ttl=60, bound=10, capacity=8, own=None, bag=24, chunk=8):
    from clonos_tpu.api.operators import IncrementalJoinOperator
    op = IncrementalJoinOperator(
        num_keys=nk, ttl=ttl, out_of_orderness=bound, capacity=capacity,
        own_columns=own, bag_capacity=bag)
    op._CHUNK_STEPS = chunk     # the program's is 32: small blocks here
    return op


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


def _traffic(seed, T, P, B, owner, nk, tick, share, hot=0.4, foreign=0.03):
    """Both inputs as ``[T, P, B]`` arrays: a subtask's keys mostly its
    own (``owner``; None: any key), some from -1 to past the ring; the
    auctions' keys 4 in 10 on a hot key of the subtask that moves every
    third step; event time ``tick * step + [0, tick)``."""
    rng = np.random.RandomState(seed)

    def side(density, hot_share):
        keys = np.zeros((T, P, B), np.int64)
        for q in range(P):
            mine = (np.arange(nk) if owner is None
                    else np.nonzero(owner == q)[0])
            keys[:, q] = rng.choice(mine, (T, B))
            moving = mine[(np.arange(T) // 3) % len(mine)]
            keys[:, q] = np.where(rng.rand(T, B) < hot_share,
                                  moving[:, None], keys[:, q])
        keys = np.where(rng.rand(T, P, B) < foreign,
                        rng.randint(-1, nk + 2, (T, P, B)), keys)
        return dict(
            k=keys.astype(np.int32),
            v=rng.randint(1, 1000, (T, P, B)).astype(np.int32),
            t=(tick * np.arange(T)[:, None, None]
               + rng.randint(0, tick, (T, P, B))).astype(np.int32),
            m=rng.rand(T, P, B) < density)
    return side(share[0], 0.0), side(share[1], hot)


def _batch(side, at):
    import jax.numpy as jnp
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    return zero_invalid(RecordBatch(*(jnp.asarray(side[f][at])
                                      for f in "kvtm")))


def _bctx(K, P, step0):
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    return ops.BlockContext(
        times=jnp.arange(step0, step0 + K, dtype=jnp.int32),
        rng_bits=jnp.zeros((K,), jnp.int32), epoch=jnp.zeros((), jnp.int32),
        step0=jnp.asarray(step0, jnp.int32),
        subtask=jnp.arange(P, dtype=jnp.int32))


def _step_and_block(op, state, left, right, K, P):
    """Both inputs through ``process_block`` in blocks of ``K`` steps
    and, step by step, through ``process2``: assert that state (but
    ``step_chunks``, which says where the block form ran step by step)
    and rows agree bit for bit; return the state and the rows ``[T, P,
    capacity]`` as NumPy."""
    import jax
    T = left["k"].shape[0]
    step_fn = jax.jit(lambda s, l, r: op.process2(s, l, r, None))
    block_fn = jax.jit(op.process_block)
    by_step, by_block, stepped, blocked = state, state, [], []
    for t in range(T):
        by_step, out = step_fn(by_step, _batch(left, t), _batch(right, t))
        stepped.append(out)
    for t in range(0, T, K):
        at = slice(t, t + K)
        by_block, out = block_fn(
            by_block, (_batch(left, at), _batch(right, at)), _bctx(K, P, t))
        blocked.append(out)
    stepped = jax.tree_util.tree_map(
        lambda *x: np.stack([np.asarray(y) for y in x]), *stepped)
    blocked = jax.tree_util.tree_map(
        lambda *x: np.concatenate([np.asarray(y) for y in x]), *blocked)
    for a, b in zip(stepped, blocked):
        np.testing.assert_array_equal(a, b)
    assert set(by_step) == set(by_block)
    for k in by_step:
        if k != "step_chunks":
            np.testing.assert_array_equal(np.asarray(by_step[k]),
                                          np.asarray(by_block[k]), k)
    assert not np.asarray(by_step["step_chunks"]).any()
    return by_block, blocked


def _fold(op, held, P, left, right):
    """Beam ``Query3``'s rule record by record under the batched
    watermark: per step and subtask the rows in order, and the totals.
    ``held(p, key)``: whether subtask ``p`` has a column for ``key``."""
    T, B = left["k"].shape[0], left["k"].shape[2]
    person = [dict() for _ in range(P)]
    bag = [[] for _ in range(P)]
    top = [[NO_TS, NO_TS] for _ in range(P)]
    total = {k: np.zeros(P, np.int64) for k in TOTALS + ("live_peak",)}
    rows = []
    for t in range(T):
        rows.append([])
        for p in range(P):
            sides = [[(int(s["k"][t, p, i]), int(s["v"][t, p, i]),
                       int(s["t"][t, p, i])) for i in range(B)
                      if s["m"][t, p, i]] for s in (left, right)]
            for i, records in enumerate(sides):
                inside = [r for r in records if 0 <= r[0] < op.num_keys]
                total["ring_too_small"][p] += len(records) - len(inside)
                top[p][i] = max([top[p][i]] + [r[2] for r in inside])
                mine = [r for r in inside if held(p, r[0])]
                total["unplaced"][p] += len(inside) - len(mine)
                sides[i] = mine
            low = min(top[p])
            wm = NO_TS if low == NO_TS else low - op.out_of_orderness
            live = lambda k: k in person[p] and person[p][k] + op.ttl > wm
            waits = [e for e in bag[p] if e[2] + op.ttl > wm]
            total["bag_expired"][p] += len(bag[p]) - len(waits)
            registered = set()
            for k, _, ts in sorted(sides[0], key=lambda r: r[2]):
                if live(k):
                    total["duplicates"][p] += 1
                else:
                    person[p][k] = ts
                    registered.add(k)
            out = [e for e in waits if e[0] in registered]
            total["flushed"][p] += len(out)
            waits = [e for e in waits if e[0] not in registered]
            for r in sides[1]:
                if live(r[0]):
                    out.append(r)
                    continue
                total["bagged"][p] += 1
                at = person[p].get(r[0])
                total["expired_probes"][p] += (
                    at is not None and at <= r[2] < at + op.ttl)
                if len(waits) < op.bag_capacity:
                    waits.append(r)
                else:
                    total["bag_overflow"][p] += 1
            bag[p] = waits
            total["dropped"][p] += max(len(out) - op.capacity, 0)
            out = out[:op.capacity]
            total["rows"][p] += len(out)
            total["live_peak"][p] = max(
                total["live_peak"][p], sum(live(k) for k in person[p]))
            rows[-1].append(out)
    return rows, total, bag


#: (ttl, share of slots that hold a person / an auction, steps a block,
#: steps a chunk): a ttl of six steps under dense traffic makes every
#: chunk loud, one of thirty to fifty steps under sparse traffic leaves
#: most of them quiet
TRAFFIC = {"loud": (60, (0.3, 0.5), 16, 8),
           "quiet": (300, (0.08, 0.2), 32, 8),
           "quiet-16": (500, (0.05, 0.1), 32, 16)}


@pytest.mark.parametrize("own", [False, True], ids=["dense", "own-columns"])
@pytest.mark.parametrize("kind", sorted(TRAFFIC))
@pytest.mark.parametrize("seed", [0, 1])
def test_both_forms_are_the_record_by_record_fold(own, kind, seed):
    """Rows in order, every total, the live-person mark and the bag, on
    both forms; the traffic makes every branch, and (``quiet``) chunks of
    both kinds."""
    ttl, share, K, chunk = TRAFFIC[kind]
    P, B, nk, T = 3, 12, 40, 96
    owner = np.random.RandomState(seed + 99).randint(0, P, nk)
    # a bound of 3 under a spread of 10: some auctions come behind the
    # watermark, a few of them just after it passed their person's ttl
    op = _op(nk, ttl, bound=3, bag=64, chunk=chunk,
             own=(np.bincount(owner, minlength=P).max() + 2 if own
                  else None))
    left, right = _traffic(seed, T, P, B, owner, nk, 10, share)
    state, out = _step_and_block(op, _bound_state(op, P, owner), left,
                                 right, K, P)
    held = (lambda p, k: owner[k] == p) if own else (lambda p, k: True)
    rows, total, bag = _fold(op, held, P, left, right)
    for t in range(T):
        for p in range(P):
            got = [(int(out.keys[t, p, i]), int(out.values[t, p, i]),
                    int(out.timestamps[t, p, i]))
                   for i in range(op.capacity) if out.valid[t, p, i]]
            assert got == rows[t][p], (t, p)
            assert not out.valid[t, p, len(got):].any()
    for k, want in total.items():
        np.testing.assert_array_equal(np.asarray(state[k]), want, k)
    for p in range(P):
        n = int(state["bag_n"][p])
        assert [tuple(int(state[f][p, i]) for f in
                      ("bag_key", "bag_val", "bag_ts"))
                for i in range(n)] == bag[p]
    # every branch was taken (the sparse traffic leaves out the rare ones)
    for k in ("flushed", "bagged", "duplicates", "ring_too_small") + (
            ("bag_expired", "expired_probes", "dropped") if kind == "loud"
            else ()):
        assert total[k].sum() > 0, k
    assert (total["unplaced"].sum() > 0) == own
    chunks = int(np.asarray(state["step_chunks"]).sum())
    every = P * T // chunk
    assert chunks == every if kind == "loud" else 0 < chunks < every


def _run_steps(op, steps, cols=None):
    """One subtask through the step form and, as one block, the block
    form: ``steps`` is a list of (persons, auctions), each a list of
    (key, value, ts). Returns (state, rows a step)."""
    import jax.numpy as jnp
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    B, T = 4, len(steps)

    def side(i):
        arr = np.zeros((T, 1, B, 3), np.int32)
        ok = np.zeros((T, 1, B), bool)
        for t, step in enumerate(steps):
            for j, r in enumerate(step[i]):
                arr[t, 0, j], ok[t, 0, j] = r, True
        return dict(k=arr[..., 0], v=arr[..., 1], t=arr[..., 2], m=ok)
    state = op.init_state(1)
    if cols is not None:
        state = op.bind_own_columns(state, jnp.asarray(cols, jnp.int32))
    state, out = _step_and_block(op, state, side(0), side(1), T, 1)
    rows = [[(int(out.keys[t, 0, i]), int(out.values[t, 0, i]),
              int(out.timestamps[t, 0, i]))
             for i in range(op.capacity) if out.valid[t, 0, i]]
            for t in range(T)]
    return {k: np.asarray(v) for k, v in state.items()}, rows


def test_a_person_registers_once_and_its_auctions_are_rows_at_once():
    op = _op(nk=8, ttl=100, bound=0, chunk=4)
    state, rows = _run_steps(op, [
        ([(3, 0, 12), (3, 0, 10)], [(3, 71, 11), (5, 72, 11)]),
        ([(3, 0, 20)], [(3, 73, 21)]),
        ([], []), ([], [])])
    # the earlier of the step's two registers; the other one, and the
    # next step's, are duplicates; 5 has no person: its auction waits
    assert rows == [[(3, 71, 11)], [(3, 73, 21)], [], []]
    assert state["person_ts"][0, 3] == 10
    assert (state["duplicates"], state["bagged"], state["rows"]) == (2, 1, 2)
    assert state["bag_n"] == 1 and state["bag_key"][0, 0] == 5


def test_an_auction_waits_for_its_person_and_is_flushed_in_order():
    op = _op(nk=8, ttl=100, bound=0, chunk=2)
    state, rows = _run_steps(op, [
        ([(1, 0, 10)], [(4, 71, 10), (6, 72, 11), (4, 73, 12)]),
        ([(1, 0, 20)], [(6, 74, 21)]),
        ([(4, 0, 30), (6, 0, 31)], [(4, 75, 32)]),
        ([], [(1, 76, 40)])])
    # the flushed rows in the order they waited, then the step's own
    assert rows[2] == [(4, 71, 10), (6, 72, 11), (4, 73, 12), (6, 74, 21),
                       (4, 75, 32)]
    assert rows[3] == [(1, 76, 40)]
    assert (state["flushed"], state["bagged"], state["bag_n"]) == (4, 4, 0)


def test_a_person_and_a_waiting_auction_expire_by_the_watermark():
    op = _op(nk=8, ttl=25, bound=5, chunk=4)
    state, rows = _run_steps(op, [
        ([(2, 0, 10)], [(2, 71, 10), (7, 72, 10)]),
        ([(0, 0, 20)], [(2, 73, 20)]),        # wm 15: 2 lives (10 + 25)
        # wm 35: 2 and the bag expire; 2 was live at 30, not at 40
        ([(0, 0, 40)], [(2, 74, 40), (2, 77, 30)]),
        ([(2, 0, 50)], [(2, 75, 50)])])       # 2 registers again, flushes
    assert rows == [[(2, 71, 10)], [(2, 73, 20)], [],
                    [(2, 74, 40), (2, 77, 30), (2, 75, 50)]]
    assert (state["bag_expired"], state["expired_probes"]) == (1, 1)
    assert state["person_ts"][0, 2] == 50 and state["live_peak"] == 2
    assert state["duplicates"] == 1           # key 0's second


def test_nothing_expires_while_an_input_is_silent():
    op = _op(nk=8, ttl=5, bound=0, chunk=4)
    state, rows = _run_steps(op, [
        ([(2, 0, 10)], []), ([(3, 0, 500)], []), ([], [(2, 71, 490)]),
        ([], [(2, 72, 520)])])
    # no watermark until the auctions speak: 2 is still live beside 3
    # after the second step, 490 past its ttl of 5; then wm 490, 500
    assert rows == [[], [], [], []]
    assert state["live_peak"] == 2
    assert (state["bagged"], state["expired_probes"]) == (2, 0)
    assert state["bag_expired"] == 1          # the first, by the fourth step


def test_what_is_lost_is_counted():
    op = _op(nk=8, ttl=100, bound=0, capacity=2, own=3, bag=2, chunk=4)
    state, rows = _run_steps(op, [
        ([(1, 0, 10), (9, 0, 10), (-1, 0, 10)],
         [(1, 71, 10), (1, 72, 11), (1, 73, 12), (2, 74, 12)]),
        ([], [(3, 75, 20), (3, 76, 21), (3, 77, 22), (5, 78, 20)])],
        cols=[[1, 3, 2 ** 31 - 1]])
    assert rows == [[(1, 71, 10), (1, 72, 11)], []]
    # a row past the capacity; an auction past the bag; keys with no
    # column (2, 5); keys off the ring (9, -1)
    assert (state["dropped"], state["bag_overflow"]) == (1, 1)
    assert (state["unplaced"], state["ring_too_small"]) == (2, 2)
    assert state["bag_n"] == 2
    assert set(op.fence_losses) == {"dropped", "bag_overflow", "unplaced",
                                    "ring_too_small"}


def test_what_the_operator_and_the_api_refuse():
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.operators import IncrementalJoinOperator
    from clonos_tpu.ops.histogram import KERNEL_MAX_KEYS
    with pytest.raises(ValueError, match="needs own_columns"):
        IncrementalJoinOperator(num_keys=KERNEL_MAX_KEYS + 1, ttl=10)
    IncrementalJoinOperator(num_keys=KERNEL_MAX_KEYS + 1, ttl=10,
                            own_columns=128)
    with pytest.raises(ValueError, match="positive"):
        IncrementalJoinOperator(num_keys=8, ttl=0)
    with pytest.raises(NotImplementedError, match="does not support resc"):
        _op().rescale_keyed_state({}, 2, 64)
    env = StreamEnvironment()
    a = env.host_source(batch_size=4, parallelism=2)
    with pytest.raises(ValueError, match="key_by"):
        a.key_by().join_incremental(a, num_keys=8, ttl=10)


def test_own_columns_are_written_once():
    """ROADMAP D18: the four operators that hold own columns share one
    initialiser, one binding, one lookup by rank and one refusal."""
    from clonos_tpu.api import operators as ops
    for cls in (ops.EventTimeWindowTopOperator, ops.SessionWindowOperator,
                ops.IncrementalJoinOperator,
                ops.EventTimeWindowJoinOperator):
        for name in ("_columns", "_init_cols", "bind_own_columns",
                     "_column", "rescale_keyed_state"):
            assert name not in vars(cls), (cls.__name__, name)
            assert getattr(cls, name) is getattr(ops._OwnColumns, name)


# --- the job against its reference -------------------------------------------


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


def totals_of(runner):
    state = runner.executor.vertex_state(JOIN)
    return {k: int(np.asarray(state[k]).sum()) for k in TOTALS}


@pytest.mark.parametrize("own", [384, None], ids=["own-columns", "dense"])
@pytest.mark.parametrize("victim", [None, (JOIN, 1)],
                         ids=["fault-free", "join"])
def test_committed_stream_equals_the_reference(ref, tmp_path, victim, own):
    """Limit 0 over the whole committed stream, with a ttl of 50 steps
    under sellers drawn from the last 62 steps' persons, so that persons
    expire, auctions come after them (and wait) and auctions are flushed
    inside the run; the program's totals, both edges' fullest steps and the most
    live persons a subtask held are the reference's; the fence read them
    into the tracer's counters."""
    cfg = config(own_columns=own)
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 11, 20, tmp_path, kill=victim)
    assert runner.executor.check_overflow() == []
    epochs = runner.executor.epoch_id
    assert epochs == (20 if victim is None else 22)
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 3000
    assert min(want.flushed, want.bag_expired, want.duplicates) > 100
    assert want.expired_probes == 0     # no auction behind the watermark
    assert want.peak_rows <= cfg["join_capacity"]
    assert want.peak_waiting <= cfg["bag_capacity"]
    state = runner.executor.vertex_state(JOIN)
    assert int(np.asarray(state["live_peak"]).max()) == want.peak_live > 60
    if victim is not None:
        return      # a replayed subtask's totals count its replay again
    assert totals_of(runner) == {
        "rows": want.fired, "flushed": want.flushed, "bagged": want.bagged,
        "bag_expired": want.bag_expired, "duplicates": want.duplicates,
        "expired_probes": want.expired_probes, "bag_overflow": 0,
        "dropped": 0, "unplaced": 0, "ring_too_small": 0}
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["peak"].tolist() == [want.peak_persons, want.peak_auctions]
    assert parts["marks"].tolist() == [want.peak_live]
    assert not parts["dropped"].any()
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("exchange.peak_records.persons->join") == want.peak_persons
    assert grew("exchange.peak_records.auctions->join") == want.peak_auctions
    assert grew("join.rows.join") == want.fired
    assert grew("join.flushed_rows.join") == want.flushed
    assert grew("join.live_persons.join") == want.peak_live
    for name in ("bag_overflow", "dropped_rows", "unplaced_records",
                 "ring_too_small"):
        assert grew(f"join.{name}.join") == 0


@pytest.mark.parametrize("control", ["f32", "at-least-once", "no-filter"])
def test_each_control_differs_from_the_reference(ref, control):
    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 13)
    want = ref.expected(cfg, stream.keys, stream.vals, 12)
    other = ref.expected(cfg, stream.keys, stream.vals, 12, control=control,
                         control_step=6 * cfg["steps_per_epoch"])
    bad, failed, _ = ref.check(ref.committed_of(other, cfg, 12), want, cfg,
                               12)
    assert bad > 0 and failed
    assert ref.check(ref.committed_of(want, cfg, 12), want, cfg, 12)[:2] == (
        0, [])
    if control == "no-filter":
        assert other.fired > 1.5 * want.fired


def test_the_plan_and_what_the_planner_refuses(tmp_path):
    """Both inputs stay on the dynamic exchange (their keys are what the
    feed says) and say why; ``join -> sink`` moves nothing; a subtask
    that owns more ids than it has columns, and an input that is not
    keyed, refuse the plan."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.operators import IncrementalJoinOperator
    from clonos_tpu.runtime.executor import CompiledJob
    cfg = config()
    tracer = obs.get_tracer()
    seen = len(tracer.records())
    stream = job.make_stream(cfg, {"table_epochs": 2}, 5)
    runner = job.make_runner(cfg, stream, 5, str(tmp_path / "ck"), 1)
    compiled = runner.executor.compiled
    assert [(compiled.edge_name(e), p.route)
            for e, p in sorted(compiled.edge_plans.items())] == [
        ("persons->join", "dynamic"), ("auctions->join", "dynamic"),
        ("join->sink", "identity")]
    noted = [r["args"] for r in tracer.records()[seen:]
             if r["name"] == "exchange.route" and "edge" in r["args"]]
    assert [(n["route"], n.get("reason"), n.get("between")) for n in noted
            ] == [("dynamic", "feed-keys", "persons->join"),
                  ("dynamic", "feed-keys", "auctions->join"),
                  ("identity", None, None)]
    cols = np.asarray(runner.executor.carry.op_states[JOIN]["cols"])
    assert cols.shape == (4, cfg["own_columns"])
    assert [(v.name, k) for v, k, _ in compiled.fence_peak_slots()] == [
        ("join", "live_peak")]
    with pytest.raises(ValueError, match="more than the 128 own columns"):
        job.make_runner(config(own_columns=128), stream, 5,
                        str(tmp_path / "ck2"), 1)
    env = StreamEnvironment()
    a = env.host_source(batch_size=4, parallelism=2)
    b = a.filter(lambda k, v, t: v > 0)
    a._attach2(b, "join", IncrementalJoinOperator(
        num_keys=64, ttl=10, own_columns=48), None).sink()
    with pytest.raises(ValueError, match="every input must be keyed"):
        CompiledJob(env.build(), log_capacity=64, max_epochs=4,
                    inflight_ring_steps=8)


# --- losses are loud ---------------------------------------------------------


@pytest.mark.parametrize("cut, counter, key", [
    ({"bag_capacity": 8}, "join.bag_overflow", "bag_overflow"),
    ({"join_capacity": 1}, "join.dropped_rows", "dropped")],
    ids=["bag-past-capacity", "row-past-capacity"])
def test_a_loss_at_the_join_is_an_overflow_message(tmp_path, cut, counter,
                                                   key):
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
    assert f"vertex 'join' lost {lost} ({counter})" in \
        runner.executor.check_overflow()


def test_a_loss_stops_the_run_at_the_next_fence(tmp_path):
    from clonos_tpu.runtime.cluster import OverflowError_
    cfg = config(bag_capacity=8, overlap_epoch=False)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 29)
    runner = job.make_runner(cfg, stream, 29, str(tmp_path / "ck"), 1)
    with pytest.raises(OverflowError_, match="vertex 'join' lost"):
        runner.run_epoch()


# --- the configuration file --------------------------------------------------


def test_the_capacities_are_the_rules_arithmetic():
    """``configs/nexmark-q3.json``: the id ring is the next power of two
    over the ids alive in the ttl; the own columns the most ids a subtask
    owns under the planner's hash, up to the next 128-lane tile; the edge
    and the row capacity six binomial deviations over what the hot
    sellers' owner is sent in a step under its worst ownership of the
    eligible ids; the bag the level that the auctions waiting on a
    subtask — a compound of the hot sellers' bursts — pass less than once
    in 1e12 steps by Chernoff's bound."""
    import jax.numpy as jnp
    from clonos_tpu.parallel import routing
    with open(os.path.join(BENCH, "configs", "nexmark-q3.json")) as f:
        cfg = json.load(f)
    tile = lambda x: int(-(-x // 128) * 128)
    nk, groups, p = cfg["num_keys"], cfg["num_key_groups"], \
        cfg["parallelism"]
    records, tick = p * cfg["batch"], cfg["clock_ms_per_step"]
    every, active, lead = (cfg["hot_seller_every"], cfg["active_people"],
                           cfg["person_id_lead"])
    # the generator's clock: 4 of 50 events reach the job, a person every
    # 5 ms; the ring holds the ids of one ttl
    assert tick == records * 50 // 4 // 10 == 1280
    persons_a_step = tick // cfg["person_every_ms"]
    assert persons_a_step * cfg["person_every"] == records
    alive = cfg["ttl_ms"] / tick * persons_a_step
    assert abs(alive - 120000) < 1 and nk == 1 << math.ceil(math.log2(alive))
    assert nk * cfg["person_every_ms"] > cfg["ttl_ms"] + 2 * tick + \
        (active + lead) * cfg["person_every_ms"]
    owner = np.asarray(routing.subtask_for_key_group(
        routing.key_group(jnp.arange(nk, dtype=jnp.int32), groups), p,
        groups))
    most = int(np.bincount(owner, minlength=p).max())
    assert most == 8357 and cfg["own_columns"] == tile(most) == 8448
    # the hot sellers' owner: how many of the eligible ids newest - 999 ..
    # newest + 10 it also owns, over every (newest, hot seller) pair
    newest = np.arange(math.lcm(every, nk))
    hot_owner = owner[(newest // every * every) % nk]
    mine = (owner[None, :] == np.arange(p)[:, None]).astype(np.int64)
    upto = np.concatenate([np.zeros((p, 1), np.int64),
                           np.cumsum(np.tile(mine, (1, 3)), axis=1)], axis=1)
    lo = newest % nk + nk - (active - 1)
    shared = upto[hot_owner, lo + active + lead] - upto[hot_owner, lo]
    assert shared.max() == 98 and abs(shared.mean() - 64.1) < 0.1
    auction = (1 - 1 / cfg["person_every"]) / cfg["categories"]   # 0.15
    cold = 1 / cfg["hot_ratio"]
    share = auction * (1 - cold + cold * shared.max() / (active + lead))
    need = records * share + 6 * math.sqrt(records * share * (1 - share))
    assert abs(records * share - 118.9) < 0.05 and abs(need - 180.4) < 0.05
    assert cfg["edge_capacity"] == cfg["join_capacity"] == tile(need) == 256
    # the bag: an auction waits when no person of a local state fell in
    # its seller's 5 ms; it waits ttl and the step it came in
    local = len(cfg["local_states"]) / cfg["states"] / cfg["person_every"]
    absent = (1 - local / persons_a_step) ** records
    assert abs(absent - 0.6065) < 0.0005
    steps = cfg["ttl_ms"] / tick + 1
    hot_sellers = persons_a_step / every * absent / p    # a subtask a step
    burst = records * auction * (1 - cold) / (persons_a_step / every)
    lone = records * auction * cold * absent / p
    mean = steps * (hot_sellers * burst + lone)
    dev = math.sqrt(steps * (hot_sellers * (burst ** 2 + burst) + lone))
    assert abs(mean - 2735) < 1 and abs(dev - 308.3) < 0.5

    def log10_tail(level):
        """Chernoff's bound on a subtask holding ``level`` auctions after
        a step: the least of K(t) - t * level over t, K the cumulant of
        the compound Poisson (bursts of a Poisson size, and lone ones)."""
        grid = (i * 5e-5 for i in range(1, 4001))
        return min(steps * (hot_sellers * (math.exp(burst * math.expm1(t))
                                           - 1) + lone * math.expm1(t))
                   - t * level for t in grid) / math.log(10)
    # six deviations (4,585) are passed once in 2e6 (subtask, step)s — the
    # count is skewed, 45 at once — and a run has 16 x 200,000 of them:
    # the capacity is the first tile under once in 1e12
    assert -6.4 < log10_tail(tile(mean + 6 * dev)) < -6.2
    assert cfg["bag_capacity"] == 5504
    assert log10_tail(5504) < -12 < log10_tail(5504 - 128)
    assert cfg["reduced"] == ["run_length", "sharing_depth"]
    assert cfg["sharing_depth"] == 1 and cfg["ttl_ms"] == 600000
    assert (cfg["max_out_of_order_ms"], cfg["person_every"],
            cfg["categories"], cfg["hot_ratio"]) == (1280, 4, 5, 4)
