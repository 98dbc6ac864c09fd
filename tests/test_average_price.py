"""NEXmark query 4, "Average Price for a Category": the join whose
interval each key takes from its own data (``BestInIntervalJoinOperator``,
Beam ``WinningBids``' rule per key) and the exact windowed mean behind it
(``EventTimeWindowMeanOperator``) — step form against block form bit for
bit and both against a record-by-record fold, on traffic that makes every
branch (a bid before its auction, a duplicate auction, a bid on an expired
and on a never-opened id, an under-reserve bid, an auction with no valid
bid, a ring lap, a pool and a row capacity that overflow loudly), with
chunks that are quiet and chunks that are not; the mean exact where a
float32 and a wrapping int32 sum are not; the ``nexmark-average-price``
job through ``ClusterRunner`` against its plain NumPy reference at a tiny
size, fault-free and through a kill of a ``winning`` subtask, its totals
and peaks against the reference's, each control; what the planner plans
and refuses; the losses that have to be loud; and the configuration
file's capacities held to the rules it states."""

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

WINNING, MEAN = 4, 5           # vertex ids, job.py's order
NO_TS = -(2 ** 31) + 1
TOTALS = ("rows", "valid", "under", "orphans", "duplicates", "no_valid",
          "pool_overflow", "dropped", "unplaced", "ring_too_small")
PEAKS = ("open_peak", "pool_peak")

#: what an auction's value lane means in these tests
LENGTH = lambda span: (lambda v: 1 + v % span)
FLOOR = lambda v: (v // 8) % 30000
PAY = lambda v: v % 5


# --- the join: step form == block form == the rule, record by record ---------


def _op(nk=40, span=25, bound=10, capacity=4, own=16, pool=24, chunk=8,
        active=256):
    """``span``: lengths of 1 to ``span`` out of a random value lane;
    None: the value lane in decimal fields (:func:`_auction`)."""
    from clonos_tpu.api.operators import BestInIntervalJoinOperator
    by_hand = span is None
    op = BestInIntervalJoinOperator(
        num_keys=nk,
        length_of=(lambda v: 1 + v % 100) if by_hand else LENGTH(span),
        floor_of=(lambda v: v // 100 % 1000) if by_hand else FLOOR,
        emit_of=(lambda v: v // 100000) if by_hand else PAY,
        out_of_orderness=bound, capacity=capacity, own_columns=own,
        pool_capacity=pool)
    op._CHUNK_STEPS, op._ACTIVE = chunk, active   # the program's: 32, 256
    return op


def _bound_state(op, P, owner):
    """``init_state`` with the columns of ``owner`` (key -> subtask)
    bound, as the planner binds them."""
    from clonos_tpu.api.operators import NO_KEY
    cols = np.full((P, op.own_columns), NO_KEY, np.int32)
    for q in range(P):
        keys = np.nonzero(owner == q)[0]
        cols[q, :len(keys)] = keys
    return op.bind_own_columns(op.init_state(P), cols)


def _traffic(seed, T, P, B, owner, nk, tick, share, hot=0.4, foreign=0.03,
             late=0.05):
    """Both inputs as ``[T, P, B]`` arrays: a subtask's keys mostly its
    own, some from -1 to past the ring; the bids' keys 4 in 10 on a hot
    key of the subtask that moves every third step; event time ``tick *
    step + [0, tick)``, one record in twenty two ticks behind that."""
    rng = np.random.RandomState(seed)

    def side(density, hot_share):
        keys = np.zeros((T, P, B), np.int64)
        for q in range(P):
            mine = np.nonzero(owner == q)[0]
            keys[:, q] = rng.choice(mine, (T, B))
            moving = mine[(np.arange(T) // 3) % len(mine)]
            keys[:, q] = np.where(rng.rand(T, B) < hot_share,
                                  moving[:, None], keys[:, q])
        keys = np.where(rng.rand(T, P, B) < foreign,
                        rng.randint(-1, nk + 2, (T, P, B)), keys)
        ts = (tick * np.arange(T)[:, None, None]
              + rng.randint(0, tick, (T, P, B)))
        ts = np.where(rng.rand(T, P, B) < late, ts - 2 * tick, ts)
        return dict(
            k=keys.astype(np.int32),
            v=rng.randint(1, 40000, (T, P, B)).astype(np.int32),
            t=np.maximum(ts, 0).astype(np.int32),
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


def _step_and_block(op, state, sides, K, P, two=True):
    """The input(s) through ``process_block`` in blocks of ``K`` steps
    and, step by step, through ``process2`` / ``process``: assert that
    state (but ``step_chunks``, which says where the block form ran step
    by step) and rows agree bit for bit; return the state and the rows
    ``[T, P, capacity]`` as NumPy."""
    import jax
    T = sides[0]["k"].shape[0]
    step_fn = jax.jit(lambda s, *b: (op.process2(s, *b, None) if two
                                     else op.process(s, *b, None)))
    block_fn = jax.jit(op.process_block)
    by_step, by_block, stepped, blocked = state, state, [], []
    for t in range(T):
        by_step, out = step_fn(by_step, *(_batch(s, t) for s in sides))
        stepped.append(out)
    for t in range(0, T, K):
        at = slice(t, t + K)
        batches = tuple(_batch(s, at) for s in sides)
        by_block, out = block_fn(by_block, batches if two else batches[0],
                                 _bctx(K, P, t))
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
    assert not np.asarray(by_step.get("step_chunks", 0)).any()
    return by_block, blocked


def _fold(op, owner, P, left, right):
    """``WinningBids``' rule record by record under the batched
    watermark (the operator's docstring: resolve, close, open, wait):
    per step and subtask the rows in order, the totals and peaks, and
    the bids left waiting."""
    T, B = left["k"].shape[0], left["k"].shape[2]
    length, floor, pay = (
        (lambda v, f=f: int(f(np.int64(v))))
        for f in (op.length_of, op.floor_of, op.emit_of))
    auction = [dict() for _ in range(P)]    # key -> [start, end, floor,
    pool = [[] for _ in range(P)]           #         pay, best, hits]
    top = [[NO_TS, NO_TS] for _ in range(P)]
    total = {k: np.zeros(P, np.int64) for k in TOTALS + PEAKS}
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
                sides[i] = inside
            low = min(top[p])
            wm = NO_TS if low == NO_TS else low - op.out_of_orderness
            waits = []
            for k, price, ts in pool[p] + sides[1]:
                if ts > wm:
                    waits.append((k, price, ts))
                    continue
                a = auction[p].get(k)
                if a is None or not a[0] <= ts < a[1]:
                    total["orphans"][p] += 1
                elif price < a[2]:
                    total["under"][p] += 1
                else:
                    total["valid"][p] += 1
                    a[4], a[5] = max(a[4], price), a[5] + 1
            out = []
            for k in sorted(auction[p]):
                a = auction[p][k]
                if a[1] <= wm:
                    del auction[p][k]
                    if a[5]:
                        out.append((a[3], a[4], a[1] - 1))
                    else:
                        total["no_valid"][p] += 1
            for k in sorted({r[0] for r in sides[0]}):
                mine = sorted((ts, v) for kk, v, ts in sides[0] if kk == k)
                if owner[k] != p:
                    total["unplaced"][p] += len(mine)
                    continue
                if k not in auction[p]:
                    ts, v = mine[0]
                    auction[p][k] = [ts, ts + length(v), floor(v), pay(v),
                                     NO_TS, 0]
                    mine = mine[1:]
                total["duplicates"][p] += len(mine)
            total["pool_overflow"][p] += max(len(waits) - op.pool_capacity, 0)
            pool[p] = waits[:op.pool_capacity]
            total["dropped"][p] += max(len(out) - op.capacity, 0)
            out = out[:op.capacity]
            total["rows"][p] += len(out)
            total["open_peak"][p] = max(total["open_peak"][p],
                                        len(auction[p]))
            total["pool_peak"][p] = max(total["pool_peak"][p], len(pool[p]))
            rows[-1].append(out)
    return rows, total, pool


#: (length span, share of slots that hold an auction / a bid, keys, steps a
#: block, steps a chunk, intervals a chunk compares, pool, rows a step):
#: few keys with short intervals under dense traffic open a key twice in
#: most chunks; many keys under sparse traffic leave most chunks quiet;
#: ``narrow`` has fewer active intervals than a chunk opens; ``tight``
#: overflows the pool and the row capacity
TRAFFIC = {
    "loud": (12, (0.3, 0.6), 24, 16, 8, 256, 64, 6),
    "quiet": (25, (0.06, 0.5), 90, 32, 8, 256, 64, 6),
    "quiet-16": (40, (0.04, 0.4), 150, 32, 16, 256, 96, 6),
    "narrow": (25, (0.06, 0.5), 90, 32, 8, 6, 64, 6),
    "tight": (25, (0.2, 0.7), 40, 16, 8, 256, 10, 1),
}


@pytest.mark.parametrize("kind", sorted(TRAFFIC))
@pytest.mark.parametrize("seed", [0, 1])
def test_both_forms_are_the_record_by_record_fold(kind, seed):
    """Rows in order, every total, both peaks and the waiting bids, on
    both forms; the traffic makes every branch, and chunks of both
    kinds."""
    span, share, nk, K, chunk, active, pool, cap = TRAFFIC[kind]
    P, B, T = 3, 12, 96
    owner = np.random.RandomState(seed + 99).randint(0, P, nk)
    # a bound of 10 under a spread of 10, and one record in twenty two
    # ticks late: some bids come behind the watermark
    op = _op(nk, span, bound=10, capacity=cap, pool=pool, chunk=chunk,
             active=active, own=np.bincount(owner, minlength=P).max() + 2)
    left, right = _traffic(seed, T, P, B, owner, nk, 10, share)
    state, out = _step_and_block(op, _bound_state(op, P, owner),
                                 (left, right), K, P)
    rows, total, pool_left = _fold(op, owner, P, left, right)
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
        n = int(state["pool_n"][p])
        assert [tuple(int(state[f][p, i]) for f in
                      ("pool_key", "pool_val", "pool_ts"))
                for i in range(n)] == pool_left[p]
    # every branch was taken (the sparse traffic leaves out the rare ones)
    for k in ("rows", "valid", "orphans", "no_valid") + (
            ("under", "duplicates", "unplaced", "ring_too_small")
            if kind in ("loud", "tight") else ()) + (
            ("pool_overflow", "dropped") if kind == "tight" else ()):
        assert total[k].sum() > 0, k
    if kind != "tight":
        assert total["pool_overflow"].sum() == total["dropped"].sum() == 0
    chunks = int(np.asarray(state["step_chunks"]).sum())
    every = P * T // chunk
    assert (chunks == every if kind in ("loud", "tight")
            else 0 < chunks < every if kind == "narrow"
            else 0 <= chunks < every // 2), (chunks, every)


def _run_steps(op, steps, cols):
    """One subtask through the step form and, as one block, the block
    form: ``steps`` is a list of (auctions, bids), each a list of (key,
    value, ts). Returns (state, rows a step)."""
    import jax.numpy as jnp
    B, T = 4, len(steps)

    def side(i):
        arr = np.zeros((T, 1, B, 3), np.int32)
        ok = np.zeros((T, 1, B), bool)
        for t, step in enumerate(steps):
            for j, r in enumerate(step[i]):
                arr[t, 0, j], ok[t, 0, j] = r, True
        return dict(k=arr[..., 0], v=arr[..., 1], t=arr[..., 2], m=ok)
    state = op.bind_own_columns(op.init_state(1),
                                jnp.asarray(cols, jnp.int32))
    state, out = _step_and_block(op, state, (side(0), side(1)), T, 1)
    rows = [[(int(out.keys[t, 0, i]), int(out.values[t, 0, i]),
              int(out.timestamps[t, 0, i]))
             for i in range(op.capacity) if out.valid[t, 0, i]]
            for t in range(T)]
    owner = np.full(op.num_keys, -1)
    owner[[k for k in cols[0] if k < op.num_keys]] = 0
    want, total, _ = _fold(op, owner, 1, side(0), side(1))
    assert rows == [step[0] for step in want]
    for k, n in total.items():
        assert int(state[k][0]) == n[0], k
    return {k: np.asarray(v) for k, v in state.items()}, rows


def _auction(length, floor, pay):
    """The value lane ``_op(span=None)`` reads so."""
    return pay * 100000 + floor * 100 + length - 1


COLS = [[0, 1, 2, 3, 4, 5, 6, 7]]


def test_the_best_bid_inside_the_interval_at_or_over_the_floor_wins():
    op = _op(nk=8, span=None, bound=0, own=8, chunk=4)
    a = _auction(length=30, floor=100, pay=3)
    state, rows = _run_steps(op, [
        ([(2, a, 10)], [(2, 150, 12)]),             # counts
        ([(5, a, 20)], [(2, 99, 21), (2, 400, 25),  # under; the best;
                        (2, 500, 45)]),             # outside: end is 40
        ([(6, a, 50)], [(7, 1, 50)]),               # the watermark passes 40
        ([(6, a, 60)], [(7, 1, 60)])], COLS)
    assert rows == [[], [], [(3, 400, 39)], []]
    assert (state["valid"], state["under"]) == (2, 1)
    # the bid at 45 is resolved once the watermark has reached 45: no
    # auction of key 2 holds it (key 7's two have none at all), and key
    # 5's auction closed with no bid
    assert state["orphans"] == 3 and state["no_valid"] == 1


def test_a_bid_waits_for_an_auction_that_comes_after_it():
    op = _op(nk=8, span=None, bound=10, own=8, chunk=4)
    a = _auction(length=30, floor=0, pay=1)
    state, rows = _run_steps(op, [
        ([(1, a, 5)], [(3, 77, 8), (1, 5, 9)]),     # key 3: no auction yet
        ([(3, a, 7)], [(1, 6, 19)]),                # it comes a step later
        ([(1, a, 29)], [(7, 1, 29)]),               # a duplicate of key 1
        ([(6, a, 60)], [(7, 1, 60)]),               # wm 50: both close
        ([(6, a, 70)], [(7, 1, 70)])], COLS)
    assert rows == [[], [], [], [(1, 6, 34), (1, 77, 36)], []]
    assert (state["valid"], state["duplicates"], state["orphans"]) == (3, 2,
                                                                       2)
    assert state["pool_peak"] == 3 and state["pool_n"] == 1


def test_an_auction_no_bid_counted_for_is_no_row_and_an_id_reopens():
    """Key 4 opens, closes and opens again inside one chunk of three
    steps: that chunk is not quiet and runs step by step."""
    op = _op(nk=8, span=None, bound=0, own=8, chunk=4)
    a = _auction(length=5, floor=500, pay=2)
    b = _auction(length=5, floor=0, pay=4)
    state, rows = _run_steps(op, [
        ([(4, a, 0)], [(4, 499, 1)]),               # under its reserve
        ([(0, b, 10)], [(7, 1, 10)]),               # key 4 closes: no row
        ([(4, b, 20)], [(4, 9, 22), (7, 1, 20)]),   # key 4 again: a lap
        ([(0, b, 30)], [(7, 1, 30)]),
        ([(0, b, 40)], [(7, 1, 40)]),
        ([(0, b, 50)], [(7, 1, 50)])], COLS)
    assert rows == [[], [], [], [(4, 9, 24)], [], []]
    assert state["under"] == 1 and state["valid"] == 1
    assert state["no_valid"] == 4           # key 4's first, key 0's three
    assert state["step_chunks"] == 2         # the next three steps too: key 0


def test_nothing_resolves_and_nothing_closes_while_an_input_is_silent():
    op = _op(nk=8, span=None, bound=0, own=8, chunk=4)
    a = _auction(length=5, floor=0, pay=0)
    state, rows = _run_steps(op, [
        ([(1, a, 0)], []), ([(2, a, 100)], []), ([(3, a, 200)], []),
        ([], [(1, 8, 2), (3, 1, 300)]), ([], [(3, 1, 400)])], COLS)
    assert rows == [[], [], [], [(0, 8, 4)], []]
    assert state["open_peak"] == 3 and state["no_valid"] == 1
    assert state["pool_n"] == 2             # the auctions' side stands at 200


def test_what_is_lost_is_counted():
    op = _op(nk=8, span=None, bound=10, capacity=1, own=4, pool=2, chunk=4)
    a = _auction(length=5, floor=0, pay=0)
    cols = [[0, 1, 2, 2 ** 31 - 1]]
    state, rows = _run_steps(op, [
        ([(0, a, 0), (1, a, 0), (5, a, 1), (9, a, 1)],
         [(0, 3, 1), (1, 3, 1), (0, 4, 2), (-1, 1, 2)]),
        ([(2, a, 50)], [(2, 1, 50)]),
        ([(2, a, 60)], [(2, 1, 60)])], cols)
    assert state["unplaced"] == 1           # the auction of key 5
    assert state["ring_too_small"] == 2     # keys 9 and -1
    assert state["pool_overflow"] == 1      # three bids wait, two fit
    assert state["dropped"] == 1            # two rows in one step, one fits
    assert [len(r) for r in rows] == [0, 1, 0]
    from clonos_tpu.api.operators import BestInIntervalJoinOperator as Op
    assert set(Op.fence_losses) == {"pool_overflow", "dropped", "unplaced",
                                    "ring_too_small"}


def test_what_the_operator_and_the_api_refuse():
    from clonos_tpu.api.environment import StreamEnvironment
    with pytest.raises(ValueError, match="no dense form"):
        _op(own=None)
    with pytest.raises(ValueError, match="positive"):
        _op(pool=0)
    env = StreamEnvironment()
    src = env.host_source(batch_size=4, parallelism=2)
    with pytest.raises(ValueError, match="key_by"):
        src.join_best_in_interval(src.key_by(), 8, LENGTH(9), FLOOR, PAY,
                                  own_columns=8)
    with pytest.raises(ValueError, match="key_by"):
        src.window_mean(4, 10)


# --- the mean: exact where float32 and a wrapping int32 are not ----------------


def _mean_op(nk=5, size=40, slide=20, bound=10):
    from clonos_tpu.api.operators import EventTimeWindowMeanOperator
    return EventTimeWindowMeanOperator(
        num_keys=nk, window_size=size, slide=slide, out_of_orderness=bound)


def _mean_fold(op, side, P):
    """Per (subtask, window, key) the exact sum and count in Python
    ints; a window fires at the first step whose watermark reaches its
    end. Returns {(step, subtask): sorted rows}, late and refused."""
    T, B = side["k"].shape[0], side["k"].shape[2]
    size, slide = op.window_size, op.slide
    rows, late, refused, peak = {}, np.zeros(P, int), np.zeros(P, int), 0
    for p in range(P):
        acc, top = {}, NO_TS
        for t in range(T):
            recs = [(int(side["k"][t, p, i]), int(side["v"][t, p, i]),
                     int(side["t"][t, p, i])) for i in range(B)
                    if side["m"][t, p, i]]
            top = max([top] + [r[2] for r in recs])
            wm = top - op.out_of_orderness
            out = []
            for w in sorted({w for w, _ in acc}):
                if w * slide + size <= wm:
                    for k in range(op.num_keys):
                        if (w, k) in acc:
                            s, n = acc.pop((w, k))
                            out.append((k, (2 * s + n) // (2 * n),
                                        w * slide + size - 1))
            rows[t, p] = sorted(out)
            for k, v, ts in recs:
                if not 0 <= v < 2 ** 30:
                    refused[p] += 1
                    continue
                took = True
                for j in range(size // slide):
                    w = ts // slide - j
                    took &= w * slide + size > wm
                    if w * slide + size > wm:
                        s, n = acc.get((w, min(max(k, 0), op.num_keys - 1)),
                                       (0, 0))
                        acc[w, min(max(k, 0), op.num_keys - 1)] = (s + v,
                                                                   n + 1)
                        peak = max(peak, s + v)
                late[p] += not took
    return rows, late, refused, peak


@pytest.mark.parametrize("high, past", [
    (10 ** 8, "2^31: a wrapping int32 sum is wrong"),
    (2 ** 21, "2^24: a float32 sum is wrong"),
    (2 ** 30 - 1, "the limbs' own range")])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_mean_is_exact_in_both_forms(high, past, seed):
    rng = np.random.RandomState(seed)
    P, B, T, K = 2, 48, 64, 16
    op = _mean_op(size=80, slide=40)
    ts = 10 * np.arange(T)[:, None, None] + rng.randint(0, 10, (T, P, B))
    ts = np.where(rng.rand(T, P, B) < 0.03, ts - 120, ts)
    vals = rng.randint(high // 2, high, (T, P, B)).astype(np.int64)
    vals = np.where(rng.rand(T, P, B) < 0.02, -vals, vals)
    side = dict(k=rng.randint(0, 5, (T, P, B)).astype(np.int32),
                v=vals.astype(np.int32),
                t=np.maximum(ts, 0).astype(np.int32),
                m=rng.rand(T, P, B) < 0.9)
    state, out = _step_and_block(op, op.init_state(P), (side,), K, P,
                                 two=False)
    rows, late, refused, peak = _mean_fold(op, side, P)
    for t in range(T):
        for p in range(P):
            ok = out.valid[t, p]
            got = sorted(zip(out.keys[t, p][ok].tolist(),
                             out.values[t, p][ok].tolist(),
                             out.timestamps[t, p][ok].tolist()))
            assert got == rows[t, p], (t, p)
    np.testing.assert_array_equal(np.asarray(state["late"]), late)
    np.testing.assert_array_equal(np.asarray(state["refused"]), refused)
    assert late.sum() > 0 and refused.sum() > 0
    # the sums this traffic makes are past what the other two hold
    assert peak > (2 ** 31 if high >= 10 ** 8 else 2 ** 24)


def test_the_mean_rounds_half_up_and_where_a_float32_sum_would_not():
    op = _mean_op(nk=2, size=10, slide=10, bound=0)
    import jax.numpy as jnp
    # 2^24 + 1 is the first integer float32 cannot hold; two of them and
    # a 0 have the mean 11184811.33, three of 10^8 + 1 pass int32
    for vals, want in (((16777217, 16777217, 0), 11184811),
                       ((100000001,) * 30, 100000001),
                       ((1, 2), 2), ((1, 1, 2), 1), ((7,), 7)):
        n = len(vals)
        lo = jnp.asarray([sum(v & 32767 for v in vals)], jnp.int32)
        hi = jnp.asarray([sum(v >> 15 for v in vals)], jnp.int32)
        got = int(op._mean(lo, hi, jnp.asarray([n], jnp.int32))[0])
        assert got == want == (2 * sum(vals) + n) // (2 * n), vals
    assert int(np.float32(16777217)) != 16777217


# --- the job against its reference -------------------------------------------


def config(**over):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           "tiny-nexmark-q4.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


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
    state = runner.executor.vertex_state(WINNING)
    return {k: int(np.asarray(state[k]).sum()) for k in TOTALS}


@pytest.mark.parametrize("victim", [None, (WINNING, 1), (MEAN, 0)],
                         ids=["fault-free", "winning", "mean"])
def test_committed_stream_equals_the_reference(ref, tmp_path, victim):
    """Limit 0 over the whole committed stream — a winning subtask is
    sent one auction a step, so that bids wait for several steps, ids
    are never opened, auctions close with no bid and rows reach ``mean``
    out of order; through a kill of a ``winning`` subtask (its open
    auctions and waiting bids replayed) and of ``mean`` (its limbs) —;
    the join's totals, both edges' fullest steps and both marks are the
    reference's; the fence read them into the tracer's counters."""
    cfg = config()
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 11, 16, tmp_path, kill=victim)
    assert runner.executor.check_overflow() == []
    epochs = runner.executor.epoch_id
    assert epochs == (16 if victim is None else 18)
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 150
    assert min(want.valid, want.under, want.duplicates, want.no_valid) > 500
    assert want.orphans > want.valid and want.late == 0
    assert want.peak_rows <= cfg["winning_capacity"]
    assert want.peak_waiting <= cfg["pool_capacity"]
    assert want.peak_mean_lag <= cfg["mean_out_of_order_ms"]
    state = runner.executor.vertex_state(WINNING)
    assert int(np.asarray(state["open_peak"]).max()) == want.peak_open > 4
    assert int(np.asarray(state["pool_peak"]).max()) == want.peak_waiting
    assert not np.asarray(state["step_chunks"]).any()
    mean = runner.executor.vertex_state(MEAN)
    assert not (np.asarray(mean["late"]).any()
                or np.asarray(mean["refused"]).any())
    if victim is not None:
        return      # a replayed subtask's totals count its replay again
    assert totals_of(runner) == {
        "rows": want.winning_rows, "valid": want.valid, "under": want.under,
        "orphans": want.orphans, "duplicates": want.duplicates,
        "no_valid": want.no_valid, "pool_overflow": 0, "dropped": 0,
        "unplaced": 0, "ring_too_small": 0}
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["peak"].tolist() == [want.peak_auctions, want.peak_bids,
                                      want.peak_mean_rows]
    assert parts["marks"].tolist() == [want.peak_open, want.peak_waiting]
    assert not parts["dropped"].any()
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("exchange.peak_records.auctions->winning") == \
        want.peak_auctions
    assert grew("exchange.peak_records.bids->winning") == want.peak_bids
    assert grew("exchange.peak_records.winning->mean") == want.peak_mean_rows
    assert grew("winbid.rows.winning") == want.winning_rows
    assert grew("winbid.valid_bids.winning") == want.valid
    assert grew("winbid.open_auctions.winning") == want.peak_open
    assert grew("winbid.pool_fill.winning") == want.peak_waiting
    assert grew("window.fired_rows.mean") == compared
    for name in ("pool_overflow", "dropped_rows", "unplaced_records",
                 "ring_too_small"):
        assert grew(f"winbid.{name}.winning") == 0
    assert grew("window.late_records.mean") == 0


@pytest.mark.parametrize("control", ["f32", "no-interval", "lose-a-step"])
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
    if control == "no-interval":
        assert other.valid > 1.2 * want.valid
        assert other.winning_rows > want.winning_rows
    if control == "lose-a-step":
        assert 0 < want.valid - other.valid < 64


def test_a_bid_delivered_twice_moves_the_counter_and_not_the_rows(ref):
    """Why the cell has no ``at-least-once`` control: a maximum takes a
    duplicate in silence. The bids of one step delivered twice leave
    every row as it was and ``valid`` higher: the counter is the
    witness, and ``test_committed_stream_equals_the_reference`` holds
    the program's to the reference's."""
    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 13)
    want = ref.expected(cfg, stream.keys, stream.vals, 8)
    again = 5 * cfg["steps_per_epoch"] + 7
    period = stream.keys.shape[1] // cfg["batch"]
    at = slice((again % period) * cfg["batch"],
               (again % period + 1) * cfg["batch"])
    twice = ref.Stream(cfg, stream.keys, stream.vals)
    once = twice.steps

    def steps(lo, hi, skip=None):
        rec, step, ts, ident = once(lo, hi, skip)
        if not lo <= again < hi:
            return rec, step, ts, ident
        dup = np.nonzero((step == again) & ~rec.auction)[0]
        order = np.argsort(np.concatenate([np.arange(len(step)), dup]),
                           kind="stable")
        more = lambda x: np.concatenate([x, x[dup]])[order]
        return (ref.Period(*(more(x) for x in rec)), more(step), more(ts),
                more(ident))
    twice.steps = steps
    n_steps = 8 * cfg["steps_per_epoch"]
    won_once = ref.winning(ref.Stream(cfg, stream.keys, stream.vals),
                           n_steps, None, None)
    won_twice = ref.winning(twice, n_steps, None, None)
    assert won_twice.counts["valid"] > won_once.counts["valid"] == want.valid
    for a, b in zip(won_once[:5], won_twice[:5]):
        np.testing.assert_array_equal(a, b)


def test_the_plan_and_what_the_planner_refuses(tmp_path):
    """Both inputs stay on the dynamic exchange (their keys are what the
    feed says) and say why; ``winning -> mean`` stays dynamic too, and
    says ``undeclared`` (a row's key is its payload, not a key the
    subtask received: PERF.md section 7); a subtask that owns more ids
    than it has columns, and an input that is not keyed, refuse the
    plan."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.operators import BestInIntervalJoinOperator
    from clonos_tpu.runtime.executor import CompiledJob
    cfg = config()
    tracer = obs.get_tracer()
    seen = len(tracer.records())
    stream = job.make_stream(cfg, {"table_epochs": 2}, 5)
    runner = job.make_runner(cfg, stream, 5, str(tmp_path / "ck"), 1)
    compiled = runner.executor.compiled
    assert [v.name for v in runner.job.vertices] == [
        "host-source", "parse", "auctions", "bids", "winning", "mean", "sink"]
    assert [v.parallelism for v in runner.job.vertices] == [4] * 5 + [1, 1]
    assert [(compiled.edge_name(e), p.route)
            for e, p in sorted(compiled.edge_plans.items())] == [
        ("auctions->winning", "dynamic"), ("bids->winning", "dynamic"),
        ("winning->mean", "dynamic")]
    noted = [r["args"] for r in tracer.records()[seen:]
             if r["name"] == "exchange.route" and "edge" in r["args"]]
    assert [(n["route"], n.get("reason"), n.get("between")) for n in noted
            ] == [("dynamic", "feed-keys", "auctions->winning"),
                  ("dynamic", "feed-keys", "bids->winning"),
                  ("dynamic", "undeclared", "winning->mean")]
    cols = np.asarray(runner.executor.carry.op_states[WINNING]["cols"])
    assert cols.shape == (4, cfg["own_columns"])
    assert [(v.name, k) for v, k, _ in compiled.fence_peak_slots()] == [
        ("winning", "open_peak"), ("winning", "pool_peak")]
    mean = runner.job.vertices[MEAN].operator
    assert (mean.open_windows, mean.out_capacity) == (4, 60)
    assert runner.job.edges[-1].capacity == 60         # mean -> sink
    with pytest.raises(ValueError, match="more than the 128 own columns"):
        job.make_runner(config(own_columns=128), stream, 5,
                        str(tmp_path / "ck2"), 1)
    env = StreamEnvironment()
    a = env.host_source(batch_size=4, parallelism=2)
    b = a.filter(lambda k, v, t: v > 0)
    a._attach2(b, "winning", BestInIntervalJoinOperator(
        num_keys=64, length_of=LENGTH(9), floor_of=FLOOR, emit_of=PAY,
        own_columns=48), None).sink()
    with pytest.raises(ValueError, match="every input must be keyed"):
        CompiledJob(env.build(), log_capacity=64, max_epochs=4,
                    inflight_ring_steps=8)


# --- losses are loud ---------------------------------------------------------


@pytest.mark.parametrize("cut, counter, key", [
    ({"pool_capacity": 16}, "winbid.pool_overflow", "pool_overflow"),
    ({"winning_capacity": 1}, "winbid.dropped_rows", "dropped")],
    ids=["pool-past-capacity", "row-past-capacity"])
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
    assert f"vertex 'winning' lost {lost} ({counter})" in \
        runner.executor.check_overflow()


def test_a_late_row_at_the_mean_stops_the_run_at_the_next_fence(tmp_path):
    from clonos_tpu.runtime.cluster import OverflowError_
    cfg = config(mean_out_of_order_ms=0, overlap_epoch=False)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 29)
    runner = job.make_runner(cfg, stream, 29, str(tmp_path / "ck"), 1)
    with pytest.raises(OverflowError_, match="vertex 'mean' lost"):
        for _ in range(12):
            runner.run_epoch()


# --- the configuration file --------------------------------------------------


def test_the_capacities_are_the_rules_arithmetic():
    """``configs/nexmark-q4.json``: the clock is the generator's at 49
    events in 50; the id ring is ``nexmark-q5``'s and a lap of it is
    three times what an id is referred to for, a chunk of the block form
    included; the own columns the most ids a subtask owns under the
    planner's hash, up to the next 128-lane tile; the edge six binomial
    deviations over what the hot auction's owner is sent in a step under
    its worst ownership of the in-flight ids; the pool four receive
    windows; the rows, the mean's edge and its bound by the arithmetic
    the file states."""
    import jax.numpy as jnp
    from clonos_tpu.api.operators import BestInIntervalJoinOperator as Op
    from clonos_tpu.parallel import routing
    with open(os.path.join(BENCH, "configs", "nexmark-q4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "nexmark-q5.json")) as f:
        q5 = json.load(f)
    tile = lambda x: int(-(-x // 128) * 128)
    nk, groups, p = cfg["num_keys"], cfg["num_key_groups"], \
        cfg["parallelism"]
    records, tick = p * cfg["batch"], cfg["clock_ms_per_step"]
    kinds, auctions = cfg["kinds"], cfg["auctions_of_kinds"]
    every, behind = cfg["hot_auction_every"], cfg["in_flight_auctions"]
    per_ms = cfg["auctions_per_ms"]
    # the generator's clock: 49 of 50 events reach the job, 3 of them
    # auctions, 600 ids a second
    assert tick == records * 50 // (kinds * 10) == 104
    assert (kinds, auctions, per_ms) == (49, 3, [3, 5])
    assert cfg["spread_ms"] == cfg["max_out_of_order_ms"] == tick
    assert kinds * cfg["spread_ms"] <= 1 << cfg["key_bits"] == nk
    # the in-flight auctions' arrival time, twice over: nextAuctionLengthMs
    assert cfg["length_span_ms"] == 2 * behind * per_ms[1] // per_ms[0] + 1
    lap = nk * per_ms[1] / per_ms[0]
    referred = ((every + behind) * per_ms[1] / per_ms[0]
                + cfg["length_span_ms"] + 2 * tick + Op._CHUNK_STEPS * tick)
    assert nk == q5["num_keys"] == 8192 and 3 * referred < lap
    owner = np.asarray(routing.subtask_for_key_group(
        routing.key_group(jnp.arange(nk, dtype=jnp.int32), groups), p,
        groups))
    most = int(np.bincount(owner, minlength=p).max())
    assert most == 566 and cfg["own_columns"] == tile(most) == 640
    # the hot auction's owner: how many of the ids newest - 100 .. newest
    # it also owns, over every (newest, hot auction) pair
    newest = np.arange(math.lcm(every, nk))
    hot_owner = owner[newest // every * every % nk]
    shared = sum((owner[(newest - d) % nk] == hot_owner).astype(np.int64)
                 for d in range(behind + 1))
    assert shared.max() == 20
    bid = 1 - auctions / kinds
    share = bid * (1 / cfg["hot_ratio"] + (1 - 1 / cfg["hot_ratio"])
                   * shared.max() / (behind + 1))
    need = records * share + 6 * math.sqrt(records * share * (1 - share))
    assert abs(records * share - 575.8) < 0.05 and abs(need - 671.1) < 0.05
    assert cfg["edge_capacity"] == tile(need) == 768
    assert cfg["pool_capacity"] == 4 * cfg["edge_capacity"] == 3072
    # rows: the auctions a subtask closes in a step, three steps' worth
    opened = 1 - math.exp(-records * auctions / kinds
                          / (tick * per_ms[0] / per_ms[1]))
    assert abs(opened - 0.634) < 0.005      # the reference measures 0.618
    closes = tick * per_ms[0] / per_ms[1] * 0.618 / p
    assert abs(closes - 2.41) < 0.01
    assert cfg["winning_capacity"] == 32 > 3 * closes + 6 * math.sqrt(
        3 * closes) > 16
    assert cfg["mean_edge_capacity"] == tile(
        2 * 25.6 + 6 * math.sqrt(2 * 25.6)) == 128
    # an interval's share of a chunk: what a chunk opens and holds
    chunk = Op._CHUNK_STEPS * closes + 10
    assert Op._ACTIVE == 256 > chunk + 6 * math.sqrt(chunk)
    assert cfg["mean_out_of_order_ms"] == 10 * tick
    assert (cfg["window_ms"], cfg["slide_ms"]) == (10000, 5000)
    assert (cfg["categories"], cfg["first_category"]) == (5, 10)
    assert cfg["reduced"] == ["run_length", "sharing_depth"]
    assert cfg["sharing_depth"] == 1
    for k in ("steps_per_epoch", "block_steps", "log_capacity",
              "inflight_ring_steps", "recovery_block_steps", "fill_epochs",
              "max_epochs", "parallelism", "batch", "num_key_groups"):
        assert cfg[k] == q5[k], k
    # the value lane's fields do not overlap and fill its 28 bits
    lane = cfg["value_lane"]
    assert lane["cold_shift"] + lane["cold_mask"].bit_length() == \
        lane["price_shift"] == 12
    assert cfg["value_bits"] - lane["price_shift"] == 16 == \
        cfg["price_fine_bits"] + (cfg["price_knots"] - 1).bit_length()
    assert 2 * lane["reserve_mask"].bit_length() == lane["rest_shift"] == 14
    assert lane["reserve_mask"] * cfg["reserve_knot_stride"] <= \
        cfg["price_knots"]
    assert cfg["auction_flag_bit"] > cfg["value_bits"]
    # a price never passes int32 between two knots
    knots = module_at(job.topology_file(cfg, "job.py")).price_knots(cfg)
    assert (knots[0], knots[-1]) == (100, 10 ** 8)
    assert int(np.diff(knots.astype(np.int64)).max()) << \
        cfg["price_fine_bits"] < 2 ** 31
    ref = module_at(job.topology_file(cfg, "reference.py"))
    np.testing.assert_array_equal(ref.price_knots(cfg), knots)
