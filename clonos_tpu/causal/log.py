"""Thread causal log: device-resident determinant ring buffer.

Capability parity with the reference's ``ThreadCausalLogImpl``
(flink-runtime .../causal/log/thread/ThreadCausalLogImpl.java:51 —
appendDeterminant:158, processUpstreamDelta:117 (dedup overlapping deltas by
offset), hasDeltaForConsumer:196, getDeltaForConsumer:249,
getDeterminants:285, makeDeltaUnsafe:364, notifyCheckpointComplete:398
(truncation as offset rebase, no copy)) — re-designed for TPU:

- The log is one ``int32[capacity, NUM_LANES]`` ring buffer in HBM plus a
  handful of int32 scalars, bundled as the :class:`ThreadLogState` pytree.
- All offsets are *absolute* (monotonic append counts); ring position is
  ``offset % capacity``. Truncation advances ``tail`` — no copying, exactly
  the reference's index-rebase trick but free because offsets never move.
- Every operation is a pure function on the state, so XLA fuses appends into
  the surrounding step and ``jax.vmap`` batches the same operation over all
  logs on a device (the stacked-log layout — see :func:`stack_logs`).
- The JVM version guards epochs with read/write locks
  (ThreadCausalLogImpl.java:63-70); here there is nothing to lock — appends
  are data dependencies in a single traced program, ordered by XLA.

Static-shape discipline: appends take a fixed-size padded row buffer plus a
count; delta extraction returns a fixed-size buffer plus a count. Capacity
must be a power of two (cheap masking instead of modulo).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.causal.determinant import NUM_LANES
from clonos_tpu.obs.trace import get_tracer


class ThreadLogState(NamedTuple):
    """Pytree state of one thread causal log (all device-resident)."""

    rows: jnp.ndarray          # int32[capacity, NUM_LANES] ring storage
    head: jnp.ndarray          # int32 scalar: absolute append count
    tail: jnp.ndarray          # int32 scalar: absolute oldest retained offset
    epoch_starts: jnp.ndarray  # int32[max_epochs]: absolute start offset of
                               #   epoch e at index e % max_epochs
    epoch_base: jnp.ndarray    # int32 scalar: oldest retained epoch id
    latest_epoch: jnp.ndarray  # int32 scalar: newest epoch recorded via
                               #   start_epoch (for epoch-index overflow
                               #   detection)

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    @property
    def max_epochs(self) -> int:
        return self.epoch_starts.shape[0]


def create(capacity: int, max_epochs: int) -> ThreadLogState:
    if capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    z = jnp.asarray(0, jnp.int32)
    return ThreadLogState(
        rows=jnp.zeros((capacity, NUM_LANES), jnp.int32),
        head=z, tail=z,
        epoch_starts=jnp.zeros((max_epochs,), jnp.int32),
        epoch_base=z, latest_epoch=z,
    )


def size(state: ThreadLogState) -> jnp.ndarray:
    """Live determinants currently retained."""
    return state.head - state.tail


def overflowed(state: ThreadLogState) -> jnp.ndarray:
    """True if appends have clobbered un-truncated determinants (the JVM
    analog is the determinant BufferPool running dry)."""
    return size(state) > state.capacity


def epoch_index_overflowed(state: ThreadLogState) -> jnp.ndarray:
    """True if more than ``max_epochs`` epochs are un-truncated, i.e.
    ``start_epoch`` has overwritten a live epoch's index slot and a later
    ``truncate`` could advance ``tail`` past retained determinants. The
    control plane must check this (and stall epoch rolls / force a
    checkpoint) before it bites."""
    return state.latest_epoch - state.epoch_base + 1 > state.max_epochs


def near_offset_wrap(state: ThreadLogState, margin: int = 1 << 29) -> jnp.ndarray:
    """True when absolute int32 offsets approach 2^31 and the control plane
    should trigger a coordinated :func:`rebase` at the next checkpoint."""
    return state.head > jnp.asarray((1 << 31) - 1 - margin, jnp.int32)


def rebase(state: ThreadLogState, amount) -> ThreadLogState:
    """Subtract ``amount`` from every absolute offset (head/tail/epoch
    index). Safe only when all producers and replicas of this log rebase by
    the same globally-agreed amount (a multiple of capacity, so ring
    positions are unchanged) at a quiescent point — the checkpoint fence.
    This is the int32-wrap mitigation for long-running streams."""
    amount = jnp.asarray(amount, jnp.int32)
    return state._replace(
        head=state.head - amount,
        tail=state.tail - amount,
        epoch_starts=state.epoch_starts - amount,
    )


def append(state: ThreadLogState, rows: jnp.ndarray, count) -> ThreadLogState:
    """Append the first ``count`` rows of a padded ``[max_batch, NUM_LANES]``
    buffer at head (reference appendDeterminant:158, vectorized)."""
    max_batch = rows.shape[0]
    count = jnp.asarray(count, jnp.int32)
    idx = jnp.arange(max_batch, dtype=jnp.int32)
    pos = (state.head + idx) & (state.capacity - 1)
    live = idx < count
    # Masked scatter: positions past `count` write back their current value.
    current = state.rows[pos]
    vals = jnp.where(live[:, None], rows, current)
    new_rows = state.rows.at[pos].set(vals, mode="drop")
    return state._replace(rows=new_rows, head=state.head + count)


def note_append(form: str, *, logs: int, runs: int, rows: int,
                capacity: int) -> None:
    """Record of which form a block's bulk append took (``log.append``
    instant, at trace time; chip_smoke.py prints them): ``runs`` (a
    replica stack appended run by run, :func:`append_runs`) or what
    :func:`append_form` names, for ``logs`` logs of ``capacity`` taking
    ``rows`` rows each (``runs``: how many runs; 0 on the batched
    forms)."""
    get_tracer().event("log.append", form=form, logs=logs, runs=runs,
                       rows=rows, capacity=capacity)


def append_form(n: int, cap: int) -> str:
    """Which form :func:`append_full` takes for ``n`` rows into a ring of
    ``cap`` (static, from the shapes alone; the ``log.append`` instant
    reports it):

    - ``scatter`` (``64 n < cap``): a row scatter, ~0.1 us a row on the
      TPU and nothing per capacity: single steps (``n`` = 4) and short
      blocks into a long ring.
    - ``window`` (``4 w <= cap``, ``w`` the power of two >= ``2 n``): the
      chunk spans at most two ``w``-aligned windows of the ring; roll it
      within a ``[2 w]`` strip and read, merge and write those two
      windows. O(n) work. What each task's own log takes in every
      configuration but ``kafka-window-64`` (``n`` = 4,096, ``cap`` =
      65,536 or 131,072).
    - ``dense`` (otherwise): pad the chunk to the capacity, roll it into
      ring position, select. O(capacity); ``kafka-window-64``'s own logs
      (``n`` = 2,048 of 8,192).

    Batched over a stack with a head per log (``v_append_full``) the
    ``window`` form's slices lower to a loop over the logs and its roll
    to a doubled strip per log: fine for a job's 32-128 own logs
    (0.3-1.4 ms a block), 30-80x the copy's cost for 1,536-1,792 replica
    logs, which therefore go run by run (:func:`append_runs`)."""
    if n * 64 < cap:
        return "scatter"
    return "window" if 4 * _window(n) <= cap else "dense"


def _window(n: int) -> int:
    """Rows of one ring window of the ``window`` form: the power of two
    >= ``2 n``."""
    return 1 << (2 * n - 1).bit_length()


def append_full(state: ThreadLogState, rows: jnp.ndarray) -> ThreadLogState:
    """Append ALL rows of ``[n, NUM_LANES]`` at head — the block-fence bulk
    path (n is static and <= capacity, so ring positions are unique), in
    the form :func:`append_form` names."""
    n = rows.shape[0]
    cap = state.capacity
    if n > cap:
        raise ValueError(f"bulk append of {n} rows > capacity {cap}")
    form = append_form(n, cap)
    if form == "window":
        w = _window(n)
        o = state.head & (cap - 1)
        r = o & (w - 1)
        base = o - r                        # W-aligned, traced
        strip = jnp.pad(rows, ((0, 2 * w - n), (0, 0)))
        strip = jnp.roll(strip, r, axis=0)
        idx2 = jnp.arange(2 * w, dtype=jnp.int32)
        mask = (idx2 >= r) & (idx2 < r + n)
        out = state.rows
        for half in (0, 1):
            start = (base + half * w) & (cap - 1)
            seg = jax.lax.dynamic_slice_in_dim(strip, half * w, w)
            m = jax.lax.dynamic_slice_in_dim(mask, half * w, w)
            win = jax.lax.dynamic_slice(
                out, (start, jnp.zeros((), jnp.int32)),
                (w, NUM_LANES))
            merged = jnp.where(m[:, None], seg, win)
            out = jax.lax.dynamic_update_slice(
                out, merged, (start, jnp.zeros((), jnp.int32)))
        return state._replace(rows=out, head=state.head + n)
    if form == "dense":
        o = state.head & (cap - 1)
        padded = jnp.pad(rows, ((0, cap - n), (0, 0)))
        rolled = jnp.roll(padded, o, axis=0)
        idx = jnp.arange(cap, dtype=jnp.int32)
        in_win = ((idx - o) & (cap - 1)) < n
        return state._replace(
            rows=jnp.where(in_win[:, None], rolled, state.rows),
            head=state.head + n)
    pos = (state.head + jnp.arange(n, dtype=jnp.int32)) & (cap - 1)
    return state._replace(rows=state.rows.at[pos].set(rows,
                                                      unique_indices=True),
                          head=state.head + n)


def runs_appendable(n: int, cap: int) -> bool:
    """:func:`append_runs` needs ring slots of ``n`` rows: ``n`` a power
    of two that divides the capacity."""
    return n & (n - 1) == 0 and cap % n == 0


def append_runs(stack: ThreadLogState, rows: jnp.ndarray,
                heads: jnp.ndarray, runs) -> ThreadLogState:
    """Append, to every log of a stack ``[R, cap, NUM_LANES]``, the block
    of the log it copies: ``runs`` are static ``(start, k, owner)``
    triples, the stack's rows ``[start, start + k)`` all copies of
    ``rows[owner]`` (``[L, n, NUM_LANES]``) appended at ``heads[owner]``
    (``[L]``, the owners' heads BEFORE this block) — what
    ``v_append_full(stack, rows[owner_of_row])`` computes when every
    copy's head equals its owner's, which is how replicas are kept
    (``ReplicationPlan.runs``).

    One offset per run, so nothing is batched over a head per log: with
    ``o = head & (cap - 1)``, ``s = o % n``, ``q = o // n`` the chunk
    covers ring slot ``q`` from ``s`` on and slot ``q + 1`` below ``s``.
    The owner's ``[n, lanes]`` block is rolled by ``s`` once, and the two
    slots of the run's ``[k, n, lanes]`` rows are read, merged and
    written at their aligned starts, in place on the (donated) stack: a
    loop over the runs of each length, its body the same for all of
    them. O(n) rows written per log whatever the capacity."""
    _, cap, lanes = stack.rows.shape
    n = rows.shape[1]
    if not runs_appendable(n, cap):
        raise ValueError(f"run-wise append of {n} rows into rings of {cap}")
    slots = cap // n
    idx = jnp.arange(n, dtype=jnp.int32)[None, :, None]
    zero = jnp.zeros((), jnp.int32)
    by_len = {}
    for start, k, owner in runs:
        by_len.setdefault(k, []).append((start, owner))
    out = stack.rows
    for k, group in sorted(by_len.items()):
        starts = jnp.asarray([s for s, _ in group], jnp.int32)
        owners = jnp.asarray([o for _, o in group], jnp.int32)

        def body(i, out):
            start, owner = starts[i], owners[i]
            o = heads[owner] & (cap - 1)
            s, q = o % n, o // n
            rolled = jnp.roll(
                jax.lax.dynamic_index_in_dim(rows, owner, 0), s, axis=1)
            for slot, mine in ((q, idx >= s), ((q + 1) % slots, idx < s)):
                at = (start, slot * n, zero)
                old = jax.lax.dynamic_slice(out, at, (k, n, lanes))
                out = jax.lax.dynamic_update_slice(
                    out, jnp.where(mine, rolled, old), at)
            return out

        out = jax.lax.fori_loop(0, len(group), body, out)
    return stack._replace(rows=out, head=stack.head + n)


def append_one(state: ThreadLogState, row: jnp.ndarray) -> ThreadLogState:
    """Append a single row (hot path inside a traced step)."""
    pos = state.head & (state.capacity - 1)
    return state._replace(rows=state.rows.at[pos].set(row),
                          head=state.head + 1)


def start_epoch(state: ThreadLogState, epoch_id) -> ThreadLogState:
    """Record the epoch -> offset index entry for a newly started epoch.

    If more than ``max_epochs`` epochs pile up un-truncated this overwrites
    the oldest live slot — detectable via :func:`epoch_index_overflowed`,
    which the checkpoint coordinator checks each epoch roll."""
    e = jnp.asarray(epoch_id, jnp.int32)
    slot = e % state.max_epochs
    return state._replace(
        epoch_starts=state.epoch_starts.at[slot].set(state.head),
        latest_epoch=jnp.maximum(state.latest_epoch, e))


def epoch_start_offset(state: ThreadLogState, epoch_id) -> jnp.ndarray:
    e = jnp.asarray(epoch_id, jnp.int32)
    return state.epoch_starts[e % state.max_epochs]


def truncate(state: ThreadLogState, completed_epoch) -> ThreadLogState:
    """Checkpoint ``completed_epoch`` finished: drop determinants of epochs
    <= completed_epoch (reference notifyCheckpointComplete:398). Pure offset
    rebase; storage is untouched."""
    e = jnp.asarray(completed_epoch, jnp.int32)
    new_tail = epoch_start_offset(state, e + 1)
    # Never move backwards (late / duplicate notifications are no-ops).
    new_tail = jnp.maximum(new_tail, state.tail)
    new_base = jnp.maximum(e + 1, state.epoch_base)
    return state._replace(tail=new_tail, epoch_base=new_base)


def slice_from(
    state: ThreadLogState, abs_offset, max_out: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gather rows [abs_offset, head) into a fixed-size buffer.

    Returns ``(buf[max_out, NUM_LANES], count, start_offset)`` — the delta
    triple that is this framework's wire format (reference makeDeltaUnsafe:364
    zero-copy slice; here a gather that XLA fuses into the consumer).
    """
    start = jnp.maximum(jnp.asarray(abs_offset, jnp.int32), state.tail)
    count = jnp.clip(state.head - start, 0, max_out)
    idx = jnp.arange(max_out, dtype=jnp.int32)
    pos = (start + idx) & (state.capacity - 1)
    buf = jnp.where((idx < count)[:, None], state.rows[pos], 0)
    return buf, count, start


def get_determinants(state: ThreadLogState, from_epoch, max_out: int):
    """All retained determinants from the start of ``from_epoch``
    (reference getDeterminants:285 — the replay feed)."""
    return slice_from(state, epoch_start_offset(state, from_epoch), max_out)


def merge_delta(
    state: ThreadLogState, rows: jnp.ndarray, count, abs_start
) -> Tuple[ThreadLogState, jnp.ndarray]:
    """Ingest a replicated delta of another task's log into this replica.

    Dedups by absolute offset exactly like the reference's
    ``processUpstreamDelta:117``: entries with offset < head are already
    present and skipped; only the fresh suffix is appended.

    Returns ``(new_state, gap)``. ``gap`` is True when ``abs_start > head``
    (a preceding delta was lost, e.g. across a reconnect): nothing is
    appended — absorbing the delta would record rows under wrong offsets —
    and the caller must request a full re-send from ``head``.
    """
    max_batch = rows.shape[0]
    count = jnp.asarray(count, jnp.int32)
    abs_start = jnp.asarray(abs_start, jnp.int32)
    gap = abs_start > state.head
    skip = jnp.clip(state.head - abs_start, 0, count)
    fresh = jnp.where(gap, 0, count - skip)
    idx = jnp.arange(max_batch, dtype=jnp.int32)
    shifted = jnp.where(idx + skip < max_batch, idx + skip, 0)
    fresh_rows = rows[shifted]
    return append(state, fresh_rows, fresh), gap


def sync_epoch_index(state: ThreadLogState, epoch_id) -> ThreadLogState:
    """Replica-side epoch bookkeeping: note that ``epoch_id`` starts at the
    replica's current head (called when the owner signals an epoch roll)."""
    return start_epoch(state, epoch_id)


# --- stacked-log layout -----------------------------------------------------
#
# A device holds many thread logs (its own main-thread + per-subpartition
# logs, plus replicas of upstream logs within sharing depth). Stacking them
# as one [L, capacity, NUM_LANES] pytree and vmapping the ops turns "for each
# log: append/merge/slice" into single fused XLA ops — the TPU answer to the
# reference's per-log object graph (JobCausalLogImpl's flat + hierarchical
# maps).

v_append = jax.vmap(append)
v_append_full = jax.vmap(append_full)
v_merge_delta = jax.vmap(merge_delta)
v_slice_from = jax.vmap(slice_from, in_axes=(0, 0, None))
v_truncate = jax.vmap(truncate, in_axes=(0, None))
v_start_epoch = jax.vmap(start_epoch, in_axes=(0, None))


def stack_logs(states) -> ThreadLogState:
    """Stack per-log states into one vmappable stacked state."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_logs(stacked: ThreadLogState):
    n = stacked.head.shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(n)]


def epoch_row_windows(stacked: ThreadLogState, epoch_slot,
                      max_rows: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                              jnp.ndarray]:
    """Gather one sealed epoch's determinant window from every stacked log
    in a single fused device op — the extraction half of tiered spilling
    (storage/tiered.py): called at the seal point, *before* the roll stamps
    the next epoch's start, so each log's window is ``[start, head)``.

    Returns ``(rows, counts, starts)`` where ``rows`` is
    ``int32[L, max_rows, NUM_LANES]`` (rows past a log's count are
    ring-garbage padding and must be trimmed by the caller), ``counts`` is
    ``int32[L]`` live rows per log, and ``starts`` is ``int32[L]`` absolute
    start offsets. ``max_rows`` is a static bound; the caller checks
    ``counts.max() <= max_rows`` and falls back to an exact host-side
    extraction on overflow (a mis-sized bound must degrade, not truncate).
    """
    epoch_slot = jnp.asarray(epoch_slot, jnp.int32)
    cap = stacked.rows.shape[1]
    starts = jnp.take(stacked.epoch_starts, epoch_slot, axis=1)     # [L]
    idx = starts[:, None] + jnp.arange(max_rows, dtype=jnp.int32)[None, :]
    pos = idx & (cap - 1)                                           # [L, W]
    rows = jnp.take_along_axis(stacked.rows, pos[:, :, None], axis=1)
    counts = stacked.head - starts
    return rows, counts, starts


# --- host-side convenience wrapper (tests / control plane) ------------------


class ThreadCausalLog:
    """Thin OO wrapper over the functional core, for host-side use.

    The executor never uses this in the hot path — there, log states live in
    the jitted step carry. This wrapper backs unit tests and the recovery
    control plane's host-side log manipulation.
    """

    # Jitted wrappers are class-level so every instance shares one trace/
    # compile cache (dozens of host-side wrappers exist per device).
    _append1 = staticmethod(jax.jit(append_one))
    _append = staticmethod(jax.jit(append))
    _truncate = staticmethod(jax.jit(truncate))
    _start_epoch = staticmethod(jax.jit(start_epoch))
    _merge = staticmethod(jax.jit(merge_delta))

    def __init__(self, capacity: int = 1 << 12, max_epochs: int = 64):
        self.state = create(capacity, max_epochs)

    def append_rows(self, rows: np.ndarray) -> None:
        if rows.ndim != 2 or rows.shape[1] != NUM_LANES:
            raise ValueError(f"expected [n, {NUM_LANES}] rows, got {rows.shape}")
        self.state = self._append(self.state, jnp.asarray(rows, jnp.int32),
                                  rows.shape[0])

    def start_epoch(self, epoch_id: int) -> None:
        self.state = self._start_epoch(self.state, epoch_id)

    def notify_checkpoint_complete(self, epoch_id: int) -> None:
        self.state = self._truncate(self.state, epoch_id)

    def merge_delta(self, rows: np.ndarray, abs_start: int) -> bool:
        """Returns True on success; False when a gap was detected (nothing
        merged — request a full re-send from ``self.head``)."""
        self.state, gap = self._merge(self.state, jnp.asarray(rows, jnp.int32),
                                      rows.shape[0], abs_start)
        return not bool(gap)

    def delta_for_consumer(self, consumer_offset: int, max_out: int):
        buf, count, start = slice_from(self.state, consumer_offset, max_out)
        return np.asarray(buf)[: int(count)], int(start)

    def determinants_from_epoch(self, epoch: int, max_out: int) -> np.ndarray:
        buf, count, _ = get_determinants(self.state, epoch, max_out)
        return np.asarray(buf)[: int(count)]

    @property
    def capacity(self) -> int:
        return self.state.capacity

    @property
    def head(self) -> int:
        return int(self.state.head)

    @property
    def tail(self) -> int:
        return int(self.state.tail)

    def __len__(self) -> int:
        return int(size(self.state))
