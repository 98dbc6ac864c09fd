"""process_block must be bit-identical to scanning process over the block
(the vectorized hot path vs the per-superstep semantic definition)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from clonos_tpu.api.operators import (
    BlockContext, HostFeedSource, IntervalJoinOperator, KeyedReduceOperator,
    MapOperator, Operator, SinkOperator, SyntheticSource,
    TumblingWindowCountOperator, UnionOperator,
)
from clonos_tpu.api.records import RecordBatch, zero_invalid


K, P, B, NK = 7, 3, 8, 13


def _bctx(times=None):
    t = jnp.asarray(times if times is not None
                    else np.arange(K) * 3, jnp.int32)
    return BlockContext(
        times=t, rng_bits=jnp.arange(K, dtype=jnp.int32) + 100,
        epoch=jnp.zeros((), jnp.int32), step0=jnp.zeros((), jnp.int32),
        subtask=jnp.arange(P, dtype=jnp.int32))


def _block_ctx(k, p):
    t = jnp.arange(k, dtype=jnp.int32)
    return BlockContext(times=t, rng_bits=t, epoch=jnp.zeros((), jnp.int32),
                        step0=jnp.zeros((), jnp.int32),
                        subtask=jnp.arange(p, dtype=jnp.int32))


def _batches(seed=0):
    rng = np.random.RandomState(seed)
    keys = rng.randint(0, NK, (K, P, B)).astype(np.int32)
    vals = rng.randint(1, 5, (K, P, B)).astype(np.int32)
    ts = rng.randint(0, 50, (K, P, B)).astype(np.int32)
    valid = rng.rand(K, P, B) < 0.7
    return zero_invalid(RecordBatch(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts),
        jnp.asarray(valid)))


def _scan_reference(op, state, batches, bctx):
    """The semantic definition: lax.scan of the per-step process."""
    return Operator.process_block(op, state, batches, bctx)


def _assert_equal(a, b):
    fa, ta = jax.tree_util.tree_flatten(a)
    fb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for xa, xb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


@pytest.mark.parametrize("op,needs_batch", [
    (SyntheticSource(vocab=11, batch_size=B), False),
    (SyntheticSource(vocab=11, batch_size=B, rate_limit=5), False),
    (MapOperator(lambda k, v, t: (k + 1, v * 2, t)), True),
    (KeyedReduceOperator(num_keys=NK), True),
    (TumblingWindowCountOperator(num_keys=NK, window_size=5), True),
    (HostFeedSource(batch_size=B), True),
    (SinkOperator(), True),
])
def test_block_equals_scan(op, needs_batch):
    state = op.init_state(P)
    batches = _batches() if needs_batch else zero_invalid(RecordBatch(
        jnp.zeros((K, P, B), jnp.int32), jnp.zeros((K, P, B), jnp.int32),
        jnp.zeros((K, P, B), jnp.int32), jnp.zeros((K, P, B), jnp.bool_)))
    bctx = _bctx()
    ref_state, ref_out = jax.jit(
        lambda s, b, c: _scan_reference(op, s, b, c))(state, batches, bctx)
    blk_state, blk_out = jax.jit(op.process_block)(state, batches, bctx)
    _assert_equal(ref_state, blk_state)
    _assert_equal(ref_out, blk_out)


def test_window_block_fires_like_stepwise():
    # Times that cross window boundaries mid-block (incl. repeated windows).
    op = TumblingWindowCountOperator(num_keys=NK, window_size=10)
    state = op.init_state(P)
    batches = _batches(3)
    bctx = _bctx(times=[0, 4, 12, 13, 25, 26, 27])
    ref = jax.jit(lambda s, b, c: _scan_reference(op, s, b, c))(
        state, batches, bctx)
    blk = jax.jit(op.process_block)(state, batches, bctx)
    _assert_equal(ref, blk)
    # Something actually fired.
    assert int(jnp.sum(blk[1].valid)) > 0


def test_reduce_static_keys_equals_dynamic():
    """The static-gather aggregation (StaticRoutePlan-fed input) must be
    bit-identical to the dynamic process_block on the same batch."""
    rng = np.random.RandomState(5)
    # Static layout: each slot is bound to a fixed key; some slots unmapped.
    slot_keys = rng.randint(-1, NK, size=(P, B)).astype(np.int32)
    keys = np.broadcast_to(np.clip(slot_keys, 0, NK - 1), (K, P, B)).copy()
    vals = rng.randint(1, 9, size=(K, P, B)).astype(np.int32)
    valid = (rng.rand(K, P, B) < 0.6) & (slot_keys >= 0)[None]
    batch = zero_invalid(RecordBatch(
        jnp.asarray(keys), jnp.asarray(vals),
        jnp.zeros((K, P, B), jnp.int32), jnp.asarray(valid)))
    op = KeyedReduceOperator(num_keys=NK)
    state = op.init_state(P)
    bctx = _bctx()
    dyn = jax.jit(op.process_block)(state, batch, bctx)
    sta = jax.jit(lambda s, b, c: op.process_block_static_keys(
        s, b, c, slot_keys))(state, batch, bctx)
    _assert_equal(dyn, sta)


def _reduce_inputs(nk, k, p, b, seed=11):
    """A ``[k, p, b]`` block for a keyed reduce over ``nk`` keys: rows
    whose slots all carry one key, rows with no record and rows all
    valid, one valid record in ten with a key past the table (the
    largest int32 among them), values over +-2**30 (sums pass 2**16 and
    wrap), and garbage in the invalid lanes."""
    rng = np.random.RandomState(seed)
    full = lambda: rng.randint(-2 ** 31, 2 ** 31, (k, p, b),
                               dtype=np.int64).astype(np.int32)
    keys = rng.randint(0, nk, (k, p, b)).astype(np.int32)
    keys[0, 0] = nk - 1                     # a row of duplicates
    keys[-1, :, : b // 2] = keys[-1, :, :1]
    past = rng.rand(k, p, b) < 0.1
    past[0, 0, 1] = past[0, 0, 2] = True
    keys[past] = nk + rng.randint(0, 1000, int(past.sum()))
    keys[0, 0, 1] = 2 ** 31 - 1
    keys[0, 0, 2] = nk                      # the first key past the table
    vals = rng.randint(-2 ** 30, 2 ** 30, (k, p, b)).astype(np.int32)
    valid = rng.rand(k, p, b) < 0.7
    valid[0] = True
    if k > 2:
        valid[1] = False
    keys, vals = np.where(valid, keys, full()), np.where(valid, vals, full())
    return RecordBatch(*(jnp.asarray(a)
                         for a in (keys, vals, full(), valid))), past & valid


@pytest.mark.parametrize("form", ["dense", "gather"])
@pytest.mark.parametrize("nk,k,p,b", [
    (5, K, P, B), (200, K, P, 130), ("widest", 3, 2, 200),
    ("widest+1", 3, 2, 200)])
def test_reduce_block_reads_back_what_the_step_form_reads(nk, k, p, b, form,
                                                          monkeypatch):
    """``KeyedReduceOperator.process_block``'s two read-backs — the dense
    compare over the key lanes (tables up to ``_DENSE_READBACK_KEYS``
    wide) and the gather (wider ones), each forced on every width by
    patching the constant — against the scan of ``process``, state and
    rows bit for bit: duplicates of a key in a row carry one value,
    invalid slots leave as zeros whatever they held, a ``B`` that is no
    multiple of 128, sums that pass 2**16 and wrap, and a valid record
    whose key is past the table reads the last key's running value (the
    block form used to say -2**31 there)."""
    from clonos_tpu.api import operators
    widest = operators._DENSE_READBACK_KEYS
    nk = {"widest": widest, "widest+1": widest + 1}.get(nk, nk)
    monkeypatch.setattr(operators, "_DENSE_READBACK_KEYS",
                        nk if form == "dense" else nk - 1)
    op = KeyedReduceOperator(num_keys=nk)
    batches, past = _reduce_inputs(nk, k, p, b)
    bctx = _block_ctx(k, p)
    state = {"acc": jnp.asarray(np.random.RandomState(3).randint(
        -2 ** 20, 2 ** 20, (p, nk)).astype(np.int32))}
    took = str(jax.make_jaxpr(op.process_block)(state, batches, bctx))
    assert (" gather[" in took) == (form == "gather")
    ref = jax.jit(lambda s, b, c: _scan_reference(op, s, b, c))(
        state, batches, bctx)
    blk = jax.jit(op.process_block)(state, batches, bctx)
    _assert_equal(ref, blk)
    # a key past the table reads the table's last key, through its step
    assert past.sum() > 2
    keys, vals, valid = (np.asarray(x) for x in (
        batches.keys, batches.values, batches.valid))
    last = np.asarray(state["acc"])[:, -1] + np.cumsum(
        np.where(valid & (keys == nk - 1), vals, 0).sum(-1, dtype=np.int64),
        axis=0)                                            # [k, p], exact
    want = np.broadcast_to(last.astype(np.int32)[..., None], past.shape)
    np.testing.assert_array_equal(np.asarray(blk[1].values)[past],
                                  want[past])
    for f in (blk[1].keys, blk[1].values, blk[1].timestamps):
        assert not np.asarray(f)[~np.asarray(blk[1].valid)].any()


def test_two_input_union_block_equals_scan():
    op = UnionOperator(capacity=2 * B)
    left, right = _batches(1), _batches(2)
    bctx = _bctx()
    from clonos_tpu.api.operators import TwoInputOperator
    ref = jax.jit(lambda s, b, c: TwoInputOperator.process_block(
        op, s, b, c))((), (left, right), bctx)
    blk = jax.jit(op.process_block)((), (left, right), bctx)
    _assert_equal(ref[1], blk[1])


def _union_inputs(case, seed=5):
    """(capacity, left, right) of one case of the union's block form:
    ``[K, P, Bl]`` and ``[K, P, Br]`` batches whose invalid lanes hold
    garbage, as a producer that does not zero them would leave them."""
    rng = np.random.RandomState(seed)
    k, p, bl, br, cap, density = case

    def side(b):
        full = lambda: rng.randint(-2 ** 31, 2 ** 31, (k, p, b),
                                   dtype=np.int64).astype(np.int32)
        keys, vals, ts = full(), full(), full()
        vals[0, 0, : min(b, 2)] = -2 ** 31
        ts[0, 0, : min(b, 2)] = -2 ** 31
        keys[0, 0, : min(b, 2)] = 2 ** 31 - 1
        valid = rng.rand(k, p, b) < density
        valid[0, 0] = True                # the extremes are records
        if k > 2:
            valid[1] = False              # rows with no record at all
            valid[2] = True               # rows where every slot is one
        return RecordBatch(*(jnp.asarray(a)
                             for a in (keys, vals, ts, valid)))
    return cap, side(bl), side(br)


@pytest.mark.parametrize("case", [
    (K, P, B, B, 7, 0.7), (K, P, B, B, 2 * B, 0.7), (K, P, 3, 5, 4, 0.5),
    (K, P, 3, 5, 8, 1.0), (K, P, 3, 5, 1, 0.5), (4, 2, 256, 384, 256, 0.5),
    (4, 2, 256, 384, 256, 0.01), (2, 1, 256, 384, 640, 0.9)],
    ids=["overflow-17-into-7", "nothing-dropped", "3+5-into-4",
         "3+5-all-valid", "3+5-into-1", "256+384-overflow",
         "256+384-sparse", "256+384-as-wide-as-both"])
def test_union_block_packs_by_rank_what_the_step_form_sorts(case):
    """``UnionOperator.process_block`` (packed by rank: no sort, no
    gather) against the scan of ``process2`` (a stable argsort and four
    gathers), in every lane of every field: the overflow drop takes the
    last records in left-then-right slot order, a row with no record is
    zeros, values, timestamps and keys span the whole int32 range, the
    two inputs need not be as wide as each other, and invalid lanes are
    zero whatever the inputs held there."""
    from clonos_tpu.api.operators import TwoInputOperator
    cap, left, right = _union_inputs(case)
    op = UnionOperator(capacity=cap)
    k, p = left.valid.shape[:2]
    bctx = _block_ctx(k, p)
    ref = jax.jit(lambda s, b, c: TwoInputOperator.process_block(
        op, s, b, c))((), (left, right), bctx)[1]
    blk = jax.jit(op.process_block)((), (left, right), bctx)[1]
    _assert_equal(ref, blk)
    n = np.asarray(left.valid).sum(-1) + np.asarray(right.valid).sum(-1)
    np.testing.assert_array_equal(np.asarray(blk.count()),
                                  np.minimum(n, cap))
    if case[4] < case[2] + case[3] and case[5] >= 0.5:
        assert (n > cap).any(), "the case drops nothing"
    # the records that stay are the first in left-then-right slot order
    both = np.concatenate([np.asarray(left.valid), np.asarray(right.valid)],
                          axis=-1)
    vals = np.concatenate([np.asarray(left.values),
                           np.asarray(right.values)], axis=-1)
    for idx in np.ndindex(k, p):
        want = vals[idx][both[idx]][:cap]
        np.testing.assert_array_equal(
            np.asarray(blk.values)[idx][: want.size], want)
    for f in (blk.keys, blk.values, blk.timestamps):
        assert not np.asarray(f)[~np.asarray(blk.valid)].any()


def test_interval_join_grouped_block_equals_scan():
    """The grouped join block (G steps fused per scan iteration) must be
    bit-identical to the sequential per-step semantics — state (ring
    contents, cursors) AND per-step output batches, including intra-step
    overflow drops, ring-slot emission order and cross-group windows."""
    from clonos_tpu.api.operators import TwoInputOperator
    for seed, cap, w, kk in ((0, 16, 4, 8), (1, 4, 2, 8), (2, 8, 1, 6),
                             (3, 64, 3, 12)):
        op = IntervalJoinOperator(num_keys=NK, window=w, interval=20,
                                  capacity=cap)
        rng = np.random.RandomState(seed)

        def mk(b):
            return zero_invalid(RecordBatch(
                jnp.asarray(rng.randint(0, NK, (kk, P, b)), jnp.int32),
                jnp.asarray(rng.randint(1, 5, (kk, P, b)), jnp.int32),
                jnp.asarray(rng.randint(0, 60, (kk, P, b)), jnp.int32),
                jnp.asarray(rng.rand(kk, P, b) < 0.6)))
        left, right = mk(B), mk(B)
        state = op.init_state(P)
        t = jnp.asarray(np.arange(kk) * 3, jnp.int32)
        bctx = BlockContext(
            times=t, rng_bits=t + 100, epoch=jnp.zeros((), jnp.int32),
            step0=jnp.zeros((), jnp.int32),
            subtask=jnp.arange(P, dtype=jnp.int32))
        ref = jax.jit(lambda s, l, r, c: TwoInputOperator.process_block(
            op, s, (l, r), c))(state, left, right, bctx)
        blk = jax.jit(lambda s, l, r, c: op.process_block(
            s, (l, r), c))(state, left, right, bctx)
        _assert_equal(ref[0], blk[0])
        _assert_equal(ref[1], blk[1])
