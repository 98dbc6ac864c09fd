"""Event-time windows + watermarks: semantics against a python oracle,
block == scan equivalence, and bit-identical recovery under failure
(reference WindowOperator event-time/sliding/session breadth with
watermarks; here the watermark is a pure fold over record timestamps so
replay needs no watermark determinant)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from clonos_tpu.api.environment import StreamEnvironment
from clonos_tpu.api.operators import (
    BlockContext, EventTimeTumblingWindowOperator, Operator,
    SessionWindowOperator, SlidingEventTimeWindowOperator)
from clonos_tpu.api.records import RecordBatch, zero_invalid
from clonos_tpu.runtime.cluster import ClusterRunner


def _step_batch(recs, cap=8, p=1):
    keys = np.zeros((p, cap), np.int32)
    vals = np.zeros((p, cap), np.int32)
    ts = np.zeros((p, cap), np.int32)
    valid = np.zeros((p, cap), bool)
    for j, (k, v, t) in enumerate(recs):
        keys[0, j], vals[0, j], ts[0, j], valid[0, j] = k, v, t, True
    return zero_invalid(RecordBatch(jnp.asarray(keys), jnp.asarray(vals),
                                    jnp.asarray(ts), jnp.asarray(valid)))


def _ctx(p=1):
    return BlockContext(
        times=jnp.zeros((1,), jnp.int32), rng_bits=jnp.zeros((1,), jnp.int32),
        epoch=jnp.zeros((), jnp.int32), step0=jnp.zeros((), jnp.int32),
        subtask=jnp.arange(p, dtype=jnp.int32)).at_step(0)


def _run_steps(op, steps):
    state = op.init_state(1)
    fired = []
    for recs in steps:
        state, out = op.process(state, _step_batch(recs), _ctx())
        m = np.asarray(out.valid[0])
        for k, v, t in zip(np.asarray(out.keys[0])[m],
                           np.asarray(out.values[0])[m],
                           np.asarray(out.timestamps[0])[m]):
            fired.append((int(k), int(v), int(t)))
    return state, fired


def test_tumbling_event_time_fires_on_watermark():
    op = EventTimeTumblingWindowOperator(num_keys=4, window_size=10,
                                         out_of_orderness=5)
    state, fired = _run_steps(op, [
        [(1, 2, 3), (2, 1, 7)],          # window 0
        [(1, 1, 12)],                    # window 1; wm=7: nothing closes
        [(1, 1, 9)],                     # late-ish but wm=7 allows w0
        [(2, 5, 21)],                    # wm=16 -> window 0 fires
        [(3, 1, 40)],                    # wm=35 -> windows 1,2 fire
    ])
    assert (1, 3, 10) in fired and (2, 1, 10) in fired   # window 0 sums
    assert (1, 1, 20) in fired                           # window 1
    assert (2, 5, 30) in fired                           # window 2
    assert int(state["late"][0]) == 0


def test_tumbling_late_records_dropped_and_counted():
    op = EventTimeTumblingWindowOperator(num_keys=4, window_size=10,
                                         out_of_orderness=0)
    state, fired = _run_steps(op, [
        [(1, 1, 5)],
        [(1, 1, 25)],                    # wm=25 -> window 0,1 closed
        [(1, 9, 3)],                     # late: window 0 already closed
    ])
    assert int(state["late"][0]) == 1
    assert (1, 1, 10) in fired
    assert all(v != 9 for _, v, _ in fired)


def test_sliding_event_time_oracle():
    op = SlidingEventTimeWindowOperator(num_keys=4, window_size=20,
                                        slide=10, out_of_orderness=0)
    state, fired = _run_steps(op, [
        [(1, 1, 5)],                     # windows starting at -10, 0
        [(1, 2, 15)],                    # windows 0, 10
        [(1, 4, 42)],                    # wm=42: windows [-10,10],[0,20],
                                         # [10,30] close
    ])
    # window [0, 20) = 1+2 = 3; window [-10, 10) = 1; window [10, 30) = 2
    assert (1, 1, 10) in fired
    assert (1, 3, 20) in fired
    assert (1, 2, 30) in fired


def test_session_window_gap_merging_and_late():
    op = SessionWindowOperator(num_keys=4, gap=10, out_of_orderness=0)
    state, fired = _run_steps(op, [
        [(1, 1, 0), (1, 2, 5)],          # one session [0, 5]
        [(1, 3, 12)],                    # extends (12 - 5 < gap... 7<10)
        [(2, 1, 40)],                    # wm=40 -> key1 session fires
    ])
    assert (1, 6, 22) in fired           # sum 6, end 12+gap
    s2, fired2 = _run_steps(op, [
        [(1, 1, 0)],
        [(2, 1, 50)],                    # closes key1's session
        [(1, 5, 2)],                     # late for the closed frontier
    ])
    assert (1, 1, 10) in fired2
    assert int(s2["late"][0]) == 1
    # The race the single-open-session form lost as a late drop: key 1's
    # session [0, 3] still waits for the watermark (bound 5) when a
    # record 14 past it arrives. It opens a second session beside the
    # first (Flink's merging windows), and a record between the two,
    # within gap of both, merges them into one.
    op = SessionWindowOperator(num_keys=4, gap=10, out_of_orderness=5)
    s3, fired3 = _run_steps(op, [
        [(1, 1, 0), (1, 1, 3)],          # [0, 3], ends at 13
        [(1, 2, 17)],                    # wm=12: first still open; 17-3>gap
        [(2, 1, 19)],                    # wm=14: the first fires alone
        [(2, 1, 60)],
    ])
    assert fired3[:2] == [(1, 2, 13), (1, 2, 27)]
    assert int(s3["late"][0]) == 0
    s4, fired4 = _run_steps(op, [
        [(1, 1, 0), (1, 1, 3)],
        [(1, 2, 17)],                    # two open sessions for key 1
        [(1, 4, 12)],                    # within gap of both: one session
        [(2, 1, 60)],
    ])
    assert (1, 8, 27) in fired4 and len([r for r in fired4 if r[0] == 1]) == 1
    assert int(s4["late"][0]) == 0


def _assert_block_equals_scan(op, batches, state=None):
    K, P, _ = batches.keys.shape
    bctx = BlockContext(
        times=jnp.arange(K, dtype=jnp.int32),
        rng_bits=jnp.zeros((K,), jnp.int32),
        epoch=jnp.zeros((), jnp.int32), step0=jnp.zeros((), jnp.int32),
        subtask=jnp.arange(P, dtype=jnp.int32))
    state = op.init_state(P) if state is None else state
    ref = jax.jit(lambda s, b, c: Operator.process_block(op, s, b, c))(
        state, batches, bctx)
    blk = jax.jit(op.process_block)(state, batches, bctx)
    assert (jax.tree_util.tree_structure(ref)
            == jax.tree_util.tree_structure(blk))
    for xa, xb in zip(jax.tree_util.tree_leaves(ref),
                      jax.tree_util.tree_leaves(blk)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    return blk


@pytest.mark.parametrize("op", [
    EventTimeTumblingWindowOperator(num_keys=5, window_size=8,
                                    out_of_orderness=6),
    SlidingEventTimeWindowOperator(num_keys=5, window_size=8, slide=4,
                                   out_of_orderness=6),
    SessionWindowOperator(num_keys=5, gap=6, out_of_orderness=4),
])
def test_event_windows_block_equals_scan(op):
    rng = np.random.RandomState(0)
    K, P, B = 6, 2, 8
    keys = rng.randint(0, 5, (K, P, B)).astype(np.int32)
    vals = rng.randint(1, 4, (K, P, B)).astype(np.int32)
    # Mostly-increasing event times with bounded disorder.
    base = np.sort(rng.randint(0, 60, (K, P, B)), axis=0).astype(np.int32)
    valid = rng.rand(K, P, B) < 0.8
    batches = zero_invalid(RecordBatch(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(base),
        jnp.asarray(valid)))
    _assert_block_equals_scan(op, batches)


def _disordered(seed, K, P, B, nk, tick, lag, jump_at=None, empty=()):
    """A clock that advances ``tick`` a step, each record behind it by up
    to ``lag``; ``jump_at`` skips the clock far ahead at that step;
    ``empty`` steps carry no valid record."""
    rng = np.random.RandomState(seed)
    clock = tick * np.arange(K)
    if jump_at is not None:
        clock[jump_at:] += 40 * tick
    ts = clock[:, None, None] - rng.randint(0, lag + 1, (K, P, B))
    valid = rng.rand(K, P, B) < 0.85
    valid[list(empty)] = False
    return zero_invalid(RecordBatch(
        jnp.asarray(rng.randint(0, nk, (K, P, B)), jnp.int32),
        jnp.asarray(rng.randint(-3, 1 << 18, (K, P, B)), jnp.int32),
        jnp.asarray(ts, jnp.int32), jnp.asarray(valid)))


EVENT_WINDOWS = {
    "tumbling": lambda ooo: EventTimeTumblingWindowOperator(
        num_keys=7, window_size=20, out_of_orderness=ooo),
    "tumbling-many-open": lambda ooo: EventTimeTumblingWindowOperator(
        num_keys=7, window_size=4, out_of_orderness=ooo),
    "sliding": lambda ooo: SlidingEventTimeWindowOperator(
        num_keys=7, window_size=15, slide=5, out_of_orderness=ooo),
    "sliding-wide-table": lambda ooo: SlidingEventTimeWindowOperator(
        num_keys=7, window_size=15, slide=5, out_of_orderness=ooo,
        open_windows=9),
}


@pytest.mark.parametrize("kind", sorted(EVENT_WINDOWS))
@pytest.mark.parametrize("case", ["in-bound", "late", "jump", "gaps"])
def test_event_window_block_form_is_the_scan(kind, case):
    """The vectorised ``process_block`` against ``lax.scan`` over
    ``process``: state, every output lane, ``late`` and ``fired`` —
    over blocks that span many windows, records later than the bound
    (dropped and counted), a clock that jumps (every open window fires
    at once), steps with no record, and a second block that starts from
    the first one's state."""
    ooo, lag, jump, empty = {
        "in-bound": (12, 12, None, ()),
        "late": (6, 30, None, ()),
        "jump": (12, 12, 17, ()),
        "gaps": (12, 20, 30, (0, 1, 9, 10, 11, 39)),
    }[case]
    op = EVENT_WINDOWS[kind](ooo)
    K, P, B = 40, 3, 16
    first = _disordered(1, K, P, B, 7, tick=3, lag=lag, jump_at=jump,
                        empty=empty)
    state, out = _assert_block_equals_scan(op, first)
    assert int(jnp.sum(out.valid)) > 0 and int(state["fired"].sum()) > 0
    if case != "gaps":
        assert (int(state["late"].sum()) > 0) == (case == "late")
    second = _disordered(2, K, P, B, 7, tick=3, lag=lag)
    second = second._replace(timestamps=jnp.where(
        second.valid, second.timestamps + 3 * K, 0))
    _assert_block_equals_scan(op, second, state)


def test_event_window_table_wider_than_the_kernel_takes(monkeypatch):
    """Past ``KERNEL_MAX_KEYS`` composite lanes the block form sums slot
    by slot; same result."""
    from clonos_tpu.ops import histogram
    monkeypatch.setattr(histogram, "KERNEL_MAX_KEYS", 8)
    op = EVENT_WINDOWS["sliding"](6)
    assert op.open_windows * op.num_keys > 8
    _assert_block_equals_scan(op, _disordered(4, 30, 2, 12, 7, tick=3,
                                              lag=25))


def test_event_windows_emit_statically_keyed_slots():
    for op in (EVENT_WINDOWS["tumbling"](5), EVENT_WINDOWS["sliding"](5)):
        sk = op.static_out_keys()
        assert sk.shape == (op.out_capacity,)
        state, out = op.process(op.init_state(1), _step_batch(
            [(k, 1, 1) for k in range(7)]), _ctx())
        _, out = op.process(state, _step_batch([(0, 1, 500)]), _ctx())
        m = np.asarray(out.valid[0])
        assert m.sum() >= 7
        np.testing.assert_array_equal(np.asarray(out.keys[0])[m], sk[m])


def test_event_time_job_recovers_bit_identically():
    """An event-time window job survives a window-subtask failure with
    bit-identical state — watermarks replay because they are a pure
    function of the replayed inputs (no watermark determinant)."""
    def build():
        env = StreamEnvironment(name="evt", num_key_groups=16)
        (env.synthetic_source(vocab=19, batch_size=6, parallelism=2)
            .key_by()
            .window_event_time(num_keys=19, window_size=64,
                               out_of_orderness=16)
            .sink())
        return env.build()

    def runner():
        r = ClusterRunner(build(), steps_per_epoch=3, seed=3)
        r.executor.time_source.now = \
            lambda it=iter(range(0, 4000, 20)): next(it)
        return r

    golden = runner()
    r = runner()
    for rr in (golden, r):
        rr.run_epoch()
        rr.step()
        rr.step()
    r.inject_failure([3])               # window subtask 1
    rep = r.recover()
    assert rep.steps_replayed == 2
    from clonos_tpu.runtime.executor import canonical_carry
    for xa, xb in zip(
            jax.tree_util.tree_leaves(canonical_carry(r.executor.carry)),
            jax.tree_util.tree_leaves(
                canonical_carry(golden.executor.carry))):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    golden.step()
    r.step()
    for xa, xb in zip(
            jax.tree_util.tree_leaves(canonical_carry(r.executor.carry)),
            jax.tree_util.tree_leaves(
                canonical_carry(golden.executor.carry))):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_session_far_apart_records_make_two_sessions():
    """Records separated by more than gap must NOT merge (review finding:
    the absorb rule needs the gap-distance check, not just the frontier)."""
    op = SessionWindowOperator(num_keys=4, gap=10, out_of_orderness=0)
    state, fired = _run_steps(op, [
        [(1, 1, 50)],
        [(1, 2, 95)],                    # 45 > gap: closes the first
        [(2, 1, 200)],                   # closes the second
    ])
    assert (1, 1, 60) in fired
    assert (1, 2, 105) in fired
    assert all(v != 3 for _, v, _ in fired)   # never merged


def test_tumbling_negative_timestamps_floor_correctly():
    op = EventTimeTumblingWindowOperator(num_keys=4, window_size=10,
                                         out_of_orderness=0)
    state, fired = _run_steps(op, [
        [(1, 7, -10)],                   # window [-10, 0), id -1
        [(2, 1, 50)],                    # wm=50 closes it
    ])
    assert (1, 7, 0) in fired


def test_session_zero_sum_session_closes_and_key_recovers():
    """A session whose values sum to zero must still close on watermark
    passage (no emission) and free the key for later sessions."""
    op = SessionWindowOperator(num_keys=4, gap=10, out_of_orderness=0)
    state, fired = _run_steps(op, [
        [(1, 0, 0)],                     # zero-valued session
        [(2, 1, 100)],                   # wm=100 closes it silently
        [(1, 5, 200)],                   # key 1 must accept a new session
        [(2, 1, 300)],                   # closes it
    ])
    assert (1, 5, 210) in fired
    assert int(state["late"][0]) == 0
