"""The own-column lookup of the block forms on the slots a target can
fill (``operators._OwnColumns._by_head_and_tails``): a 128-slot head of
every target and the tails of a step's first two *over* targets, against
the comparison of every slot — the helper alone (``_column_block``,
``SessionWindowOperator._arrivals_block``) on blocks whose steps have 0,
1, 2 and 3 over targets, over targets that move, slots that are no
prefix, columns bound to no key and keys no column holds; which branch a
block took (``crowded``, counted in ``lookup.dense_blocks``); and the
shapes the split is not built for, whose programs hold no ``cond``.
``receive_windows`` / ``widely`` also lay out the wide cases of
``test_user_sessions.py`` and ``test_hot_items.py``."""

import numpy as np
import pytest

HEAD, TAILS = 128, 2


def receive_windows(seed, K, P, B, over, packed=True):
    """bool ``[K, P, B]``: the slots of a block's receive windows that
    hold a record. ``over[k]`` targets of step ``k``, drawn anew every
    step, hold one at or past slot 128 (``packed``: a prefix of 129 to
    ``B`` slots, as the dynamic exchange fills a window; else slots
    anywhere, as a static plan does); the others hold 0 to 128 in the
    head, a window of exactly 128 and an empty one among them."""
    rng = np.random.RandomState(seed)
    mask = np.zeros((K, P, B), bool)
    for k in range(K):
        hot = rng.permutation(P)[:over[k % len(over)]]
        for p in range(P):
            if p in hot:
                n = rng.randint(HEAD + 1, B + 1)
                at = np.arange(n) if packed else rng.permutation(B)[:n]
                if not packed and at.max() < HEAD:
                    at[0] = rng.randint(HEAD, B)
            else:
                n = (HEAD, 0, rng.randint(0, HEAD + 1))[min((k + p) % 5, 2)]
                at = np.arange(n) if packed else rng.permutation(HEAD)[:n]
            mask[k, p, at] = True
    return mask


#: per block, how many targets each step sends past the head: the second
#: block has a step with three and takes the dense form; the last block's
#: slots lie as a static plan's
WIDE_BLOCKS = [(0, 1, 2, 1), (2, 1, 3, 0, 2), (2,), (1, 2, 0)]
#: steps, subtasks and slots of the wide cases: a window that head and
#: tails exactly halve
WIDE = (8, 8, 384)


def widely(blocks, seed):
    """``blocks`` (``WIDE``-shaped, every slot a record) with only
    ``WIDE_BLOCKS``' slots valid."""
    import jax.numpy as jnp
    from clonos_tpu.api.records import zero_invalid
    return [zero_invalid(b._replace(valid=jnp.asarray(receive_windows(
        seed + i, *WIDE, over, packed=i < 3))))
        for i, (b, over) in enumerate(zip(blocks, WIDE_BLOCKS))]


def _cols(P, C, nk, rng, bound):
    """Own columns as the planner binds them: ascending keys, then
    ``NO_KEY``; ``bound`` of ``C`` hold a key."""
    from clonos_tpu.api.operators import NO_KEY
    cols = np.full((P, C), NO_KEY, np.int32)
    for p in range(P):
        cols[p, :bound] = np.sort(rng.choice(nk, bound, replace=False))
    return cols


CASES = [
    ("none-over", (0,), True, False),
    ("one-over", (1,), True, False),
    ("two-over", (2,), True, False),
    ("over-targets-move", (0, 1, 2, 1, 2), True, False),
    ("three-over-once", (1, 2, 1, 3, 0, 2), True, True),
    ("all-over", (8,), True, True),
    ("static-plan-slots", (0, 2, 1), False, False),
    ("static-plan-slots-crowded", (2, 3), False, True),
]


@pytest.mark.parametrize("name, over, packed, crowded", CASES,
                         ids=[c[0] for c in CASES])
def test_column_block_is_the_dense_lookup_at_every_record(name, over, packed,
                                                          crowded):
    """``(column, held)`` of every valid slot, split against dense: own
    keys, keys another subtask holds, keys below, between and past every
    bound key, and columns bound to no key."""
    import jax
    import jax.numpy as jnp
    from clonos_tpu.api.operators import EventTimeWindowTopOperator
    K, P, B, C, nk = 12, 8, 384, 10, 64
    rng = np.random.RandomState(11)
    op = EventTimeWindowTopOperator(num_keys=nk, window_size=100, slide=20,
                                    own_columns=C)
    cols = _cols(P, C, nk, rng, bound=7)
    valid = receive_windows(3, K, P, B, over, packed)
    keys = np.where(valid, rng.randint(-2, nk + 3, (K, P, B)), 0)
    (col, held), went_dense = jax.jit(op._column_block)(
        jnp.asarray(cols), jnp.asarray(keys, jnp.int32), jnp.asarray(valid))
    want_col, want_held = op._column(jnp.asarray(cols),
                                     jnp.asarray(keys, jnp.int32))
    assert bool(went_dense) == crowded
    np.testing.assert_array_equal(np.asarray(held)[valid],
                                  np.asarray(want_held)[valid])
    np.testing.assert_array_equal(np.asarray(col)[valid],
                                  np.asarray(want_col)[valid])
    assert np.asarray(held)[valid].any() and not np.asarray(held)[valid].all()
    # and by hand: a held key's column is where its subtask binds it
    k, p, b = (x[:50] for x in np.nonzero(valid & np.asarray(held)))
    assert (cols[p, np.asarray(col)[k, p, b]] == keys[k, p, b]).all()


@pytest.mark.parametrize("name, over, packed, crowded", CASES,
                         ids=[c[0] for c in CASES])
def test_arrivals_block_is_the_dense_fold_to_the_bit(name, over, packed,
                                                     crowded):
    """Sum, earliest, latest per column and the late count of every
    step, split against dense, records behind the watermark among them;
    values near the int32 edge, so that the sums wrap in both."""
    import jax
    import jax.numpy as jnp
    from clonos_tpu.api.operators import SessionWindowOperator
    from clonos_tpu.api.records import RecordBatch, zero_invalid
    K, P, B, C, nk = 12, 8, 384, 10, 64
    rng = np.random.RandomState(12)
    op = SessionWindowOperator(num_keys=nk, gap=30, out_of_orderness=10,
                               own_columns=C)
    cols = jnp.asarray(_cols(P, C, nk, rng, bound=7))
    valid = receive_windows(4, K, P, B, over, packed)
    b = zero_invalid(RecordBatch(
        jnp.asarray(rng.randint(-2, nk + 3, (K, P, B)), jnp.int32),
        jnp.asarray(rng.randint(-2 ** 31, 2 ** 31 - 1, (K, P, B),
                                dtype=np.int64), jnp.int32),
        jnp.asarray(rng.randint(0, 100, (K, P, B)), jnp.int32),
        jnp.asarray(valid)))
    wm = jnp.asarray(rng.randint(20, 60, (K, P)), jnp.int32)
    got, went_dense = jax.jit(op._arrivals_block)(cols, b, wm)
    want = op._arrivals(cols, b, wm)
    assert bool(went_dense) == crowded
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(np.asarray(want[3]).sum()) > 0           # some were late
    assert (np.asarray(want[2]) > -(2 ** 31) + 1).any()


def _block(op, P, B, K=4):
    import jax.numpy as jnp
    from clonos_tpu.api import operators as ops
    from clonos_tpu.api.records import RecordBatch
    z = jnp.zeros((K, P, B), jnp.int32)
    bctx = ops.BlockContext(
        times=jnp.arange(K, dtype=jnp.int32),
        rng_bits=jnp.zeros((K,), jnp.int32), epoch=jnp.zeros((), jnp.int32),
        step0=jnp.zeros((), jnp.int32),
        subtask=jnp.arange(P, dtype=jnp.int32))
    return op.init_state(P), RecordBatch(z, z, z, z.astype(bool)), bctx


def _ops():
    from clonos_tpu.api.operators import (EventTimeWindowTopOperator,
                                          SessionWindowOperator)
    return {
        "top": EventTimeWindowTopOperator(num_keys=64, window_size=100,
                                          slide=20, own_columns=8),
        "sessions": SessionWindowOperator(num_keys=64, gap=30,
                                          out_of_orderness=10,
                                          own_columns=8)}


@pytest.mark.parametrize("P, B, splits", [
    (16, 192, False), (1, 896, False), (16, 128, False), (1, 128, False),
    (4, 768, False), (16, 768, True), (16, 896, True), (8, 384, True)],
    ids=lambda x: str(x))
@pytest.mark.parametrize("which", ["top", "sessions"])
def test_a_shape_decides_whether_the_split_is_built(which, P, B, splits):
    """Only a window that head and tails at least halve gets them:
    ``nexmark-q8``'s 16 x 192, one lane of a replay, a window of one
    head and a narrow vertex stay on the dense form alone — no ``cond``
    in the block's jaxpr — and 16 x 768 / 16 x 896 hold one."""
    import jax
    from clonos_tpu.api.operators import _OwnColumns
    assert _OwnColumns._splits(P, B) == splits
    assert _OwnColumns._splits(P, B) == (
        B > HEAD and P * HEAD + TAILS * (B - HEAD) <= P * B / 2)
    op = _ops()[which]
    text = str(jax.make_jaxpr(op.process_block)(*_block(op, P, B)))
    assert (" cond[" in text) == splits
    assert text.count(" cond[") == int(splits)


@pytest.mark.parametrize("which", ["top", "sessions"])
def test_a_crowded_block_counts_once_whatever_the_parallelism(which):
    """``dense_blocks`` is a ``fence_totals`` leaf the fence sums over
    the subtasks: a crowded block adds one in all, a block that is not
    and a shape without the split add none."""
    import jax
    import jax.numpy as jnp
    op = _ops()[which]
    assert ("dense_blocks", "lookup.dense_blocks") in op.fence_totals
    assert "dense_blocks" not in op.fence_losses
    P, B, K = 8, 384, 4
    state, b, bctx = _block(op, P, B, K)
    fn = jax.jit(op.process_block)
    for over, want in (((3,), 1), ((2,), 1), ((8,), 2), ((0, 1), 2)):
        valid = jnp.asarray(receive_windows(5, K, P, B, over))
        state, _ = fn(state, b._replace(valid=valid), bctx)
        assert int(np.asarray(state["dense_blocks"]).sum()) == want
    narrow_state, nb, nbctx = _block(op, P, HEAD, K)
    out, _ = op.process_block(
        narrow_state, nb._replace(valid=~nb.valid), nbctx)
    assert int(np.asarray(out["dense_blocks"]).sum()) == 0


def test_a_total_that_falls_back_feeds_its_counter_nothing():
    """``dense_blocks`` is the one ``fence_totals`` leaf a one-lane
    replay does not count again, so after a recovery of the first
    subtask the fence can read a total below its last reading: the
    counter is fed no negative growth, and grows again from there."""
    import jax.numpy as jnp
    from clonos_tpu import obs
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.feeds import ListFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner

    p, batch, spe, nk = 8, 64, 32, 2048
    feed = np.random.RandomState(3).randint(
        1, 1 << 28, (p, 3 * spe * batch, 2)).astype(np.int32)

    def parse(keys, vals, step):
        # three hot bidders (1, 3 and 4: three owners), a quarter of a
        # step's bids each: most blocks have a step with three targets
        # past the head
        ts, hot = 7 * step + vals % 7, vals & 3
        return (jnp.where(hot != 0, hot + (hot > 1), 5 + (vals >> 12) % 900),
                jnp.ones_like(vals), ts)

    env = StreamEnvironment(name="falls-back", num_key_groups=64,
                            default_edge_capacity=batch)
    (env.host_source(batch_size=batch, parallelism=p)
        .map(parse, name="parse", capacity=batch)
        .key_by().window_session(
            num_keys=nk, gap=1000, out_of_orderness=7, capacity=16,
            own_columns=640, edge_capacity=p * batch, name="sessions")
        .key_by().sink(parallelism=p, transactional=True, capacity=16))
    runner = ClusterRunner(
        env.build(), steps_per_epoch=spe, block_steps=16, log_capacity=256,
        max_epochs=8, inflight_ring_steps=2 * spe, seed=3,
        logical_time=True, audit=False)
    runner.executor.register_feed(0, ListFeedReader(list(feed)))
    name, tr = "lookup.dense_blocks.sessions", obs.get_tracer()

    def fed():
        runner.run_epoch(complete_checkpoint=True)
        runner.drain_fence()
        return tr.counters().get(name, 0)

    start = tr.counters().get(name, 0)
    first = fed() - start
    assert first > 0
    # what the next fence sees after a recovery took the leaf back
    runner._fence_counter_totals[name] += 1000
    assert fed() - start == first
    third = fed() - start
    assert first < third <= first + spe // 16
    assert runner._fence_counter_totals[name] == int(np.asarray(
        runner.executor.vertex_state(2)["dense_blocks"]).sum())
