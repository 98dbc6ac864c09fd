"""Batched record routing: the TPU form of the network exchange.

The reference partitions record-at-a-time through channel selectors
(flink-streaming-java .../runtime/partitioner/{KeyGroupStreamPartitioner,
RebalancePartitioner,BroadcastPartitioner}.java) and moves bytes over netty
(io/network/partition/ResultPartition.java:86 ->
consumer/SingleInputGate.java:107). Here an exchange is one dense op on the
whole batch: compute a target subtask per record, stable-sort by target, and
scatter into a fixed-capacity per-subtask buffer. Under ``jit`` over a mesh
the scatter lowers to an all-to-all on ICI — XLA inserts the collective;
there is no hand-written transport.

Determinism note: routing is a pure function of the input batch (stable sort
keeps arrival order within a target), so exchanges need **no** determinants —
only the *selection* of which queued batch a multi-input vertex consumes is
nondeterministic (logged as ORDER, see runtime/executor.py).

Key-group discipline matches the reference: state is sharded by
``key_group = hash(key) % num_key_groups`` and key groups map to subtasks as
``kg * parallelism // num_key_groups``
(flink-runtime .../state/KeyGroupRangeAssignment.java).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.api.records import RecordBatch, zero_invalid
from clonos_tpu.obs.trace import get_tracer
from clonos_tpu.ops.histogram import (KERNEL_MAX_KEYS, keyed_hist,
                                      uses_kernel)
from clonos_tpu.ops.matops import running_count


def hash32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix32-style avalanche hash on int32 (uint32 arithmetic)."""
    u = x.astype(jnp.uint32)
    u = (u ^ (u >> 16)) * jnp.uint32(0x7FEB352D)
    u = (u ^ (u >> 15)) * jnp.uint32(0x846CA68B)
    u = u ^ (u >> 16)
    return u


def key_group(keys: jnp.ndarray, num_key_groups: int) -> jnp.ndarray:
    return (hash32(keys) % jnp.uint32(num_key_groups)).astype(jnp.int32)


def subtask_for_key_group(kg: jnp.ndarray, parallelism: int,
                          num_key_groups: int) -> jnp.ndarray:
    # Matches KeyGroupRangeAssignment.computeOperatorIndexForKeyGroup.
    return (kg * parallelism) // num_key_groups


def key_group_range(subtask: int, parallelism: int,
                    num_key_groups: int) -> Tuple[int, int]:
    """[start, end) of key groups owned by ``subtask``."""
    start = -(-subtask * num_key_groups // parallelism)  # ceil div
    end = -(-(subtask + 1) * num_key_groups // parallelism)
    return start, end


def _scatter_to_targets(
    batch: RecordBatch, target: jnp.ndarray, num_targets: int, out_capacity: int
) -> Tuple[RecordBatch, jnp.ndarray]:
    """Core exchange: flatten, stable-sort by target, scatter to
    ``[num_targets, out_capacity]``. Returns (routed, dropped_per_target)."""
    flat = jnp.reshape
    n = batch.keys.size
    keys, vals, ts, valid = (flat(batch.keys, (n,)), flat(batch.values, (n,)),
                             flat(batch.timestamps, (n,)), flat(batch.valid, (n,)))
    target = jnp.where(valid, flat(target, (n,)), num_targets)  # invalid last
    order = jnp.argsort(target, stable=True)
    st, sk, sv, sts = target[order], keys[order], vals[order], ts[order]
    # Position of each sorted record within its target's run.
    idx = jnp.arange(n, dtype=jnp.int32)
    run_start = jnp.searchsorted(st, jnp.arange(num_targets + 1, dtype=st.dtype),
                                 side="left").astype(jnp.int32)
    pos = idx - run_start[jnp.clip(st, 0, num_targets)]
    live = st < num_targets
    keep = live & (pos < out_capacity)
    dropped = jnp.zeros((num_targets,), jnp.int32).at[st].add(
        (live & ~keep).astype(jnp.int32), mode="drop")
    # Scatter; out-of-range rows (dropped/invalid) routed to a drop slot.
    row = jnp.where(keep, st, num_targets)
    col = jnp.where(keep, pos, 0)
    shape = (num_targets + 1, out_capacity)
    out = RecordBatch(
        keys=jnp.zeros(shape, jnp.int32).at[row, col].set(sk, mode="drop"),
        values=jnp.zeros(shape, jnp.int32).at[row, col].set(sv, mode="drop"),
        timestamps=jnp.zeros(shape, jnp.int32).at[row, col].set(sts, mode="drop"),
        valid=jnp.zeros(shape, jnp.bool_).at[row, col].set(keep, mode="drop"),
    )
    out = RecordBatch(out.keys[:num_targets], out.values[:num_targets],
                      out.timestamps[:num_targets], out.valid[:num_targets])
    return zero_invalid(out), dropped


#: floor (and the whole budget off-TPU, where the CPU test lane reports
#: no memory stats) of :func:`_count_route_budget`.
_COUNT_ROUTE_MIN_BYTES = 256 << 20


@functools.cache
def _count_route_budget() -> int:
    """Cap on the counting exchange's scratch, priced at 12 bytes an
    entry of ``[K, n, T+1]``; it holds 10 an entry of ``[K, T, n]`` (the
    bf16 one-hot, the f32 product with the triangle, the int32 running
    count). Routes past it count chunk by chunk of steps
    (:func:`_block_to_targets`).
    ~2% of the device's memory limit, within [256 MiB, 2 GiB]: ~336 MB
    on a 16 GB v5e, so the ~0.9 GB whole-recovery-window route at bench
    shapes goes in chunks there instead of crowding the GB-scale log
    state. A TPU that reports no ``memory_stats()`` is an error, not a
    default."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _COUNT_ROUTE_MIN_BYTES
    return max(_COUNT_ROUTE_MIN_BYTES,
               min(2 << 30, dev.memory_stats()["bytes_limit"] // 48))


def note_route(route: str, **shape) -> None:
    """Record of which form an exchange took (``exchange.route``
    instant; chip_smoke.py prints them): ``kernel`` / ``scatter`` when
    a dynamic exchange is lowered (trace time; a counting exchange also
    says into how many ``chunks`` of ``steps`` its block was cut), and once
    per HASH edge what the planner decided (``CompiledJob._plan_edges``,
    plan time): ``identity`` / ``static`` off the dynamic exchange, or
    ``dynamic`` with the ``reason`` it stays there."""
    get_tracer().event("exchange.route", route=route, **shape)


#: records in one chunk of the chunked counting route, at most: what the
#: blocks that count whole already hold (1,024 steps x 1,024 records).
#: Set when the running count was a ``cumsum`` over the records, whose
#: compile time past it was erratic (for the v5e: 6 s at [256, 4096]
#: records, 85 s at [512, 4096]).
_COUNT_CHUNK_MAX_RECORDS = 1 << 20


def _step_chunk(K: int, limit: int) -> int:
    """Largest divisor of ``K`` that is at most ``limit`` (0: none)."""
    return next((c for c in range(min(K, limit), 0, -1) if K % c == 0), 0)


def _count_to_targets(
    batch: RecordBatch, target: jnp.ndarray, num_targets: int,
    out_capacity: int, chunks: int = 1
) -> Tuple[RecordBatch, jnp.ndarray]:
    """The counting route of :func:`_block_to_targets` (its docstring):
    every step on its own, so any cut of the step axis gives the same
    result. ``chunks``: how many such cuts the caller made of its block
    (this is one of them); only the ``exchange.route`` instant reads it."""
    K, P, B = batch.keys.shape
    T = num_targets
    n = P * B
    fl = lambda x: jnp.reshape(x, (K, n))
    keys, vals, ts, valid = map(fl, batch)
    tgt = jnp.where(valid, fl(target), T)
    # [K, T, n]: an invalid record is in no row, so its rank reads -1 and
    # ``keep`` is false. No index is computed from the counts: a gather
    # on the v5e costs ~11 ns an element, whatever T is.
    with jax.named_scope("rank"):
        onehot = (tgt[:, None, :]
                  == jnp.arange(T, dtype=jnp.int32)[None, :, None])
        count = running_count(onehot)
        pos = jnp.sum(jnp.where(onehot, count, 0), axis=1) - 1
        counts = count[:, :, -1]
        keep = (tgt < T) & (pos < out_capacity)
        dropped = jnp.maximum(counts - out_capacity, 0).astype(jnp.int32)
    # Placement: (target, rank) pairs are UNIQUE per step, so a keyed
    # histogram over the flattened slot id IS the routed batch (sum
    # of one contribution = select) — the Pallas kernel folds it
    # on the MXU where an XLA element scatter ran ~50ms/field at bench
    # shapes (see _block_to_target_lane). Slot tables wider than the
    # kernel compiles for are placed by an element scatter.
    nk = T * out_capacity
    via_hist = nk <= KERNEL_MAX_KEYS
    note_route("kernel" if via_hist and uses_kernel() else "scatter",
               steps=K, chunks=chunks, records=n, targets=T,
               capacity=out_capacity, rank="tri")
    with jax.named_scope("place"):
        if via_hist:
            slot = jnp.where(keep, tgt * out_capacity + pos, -1)
            out_k, cnt = keyed_hist(slot, keys, keep, nk)
            out_v, _ = keyed_hist(slot, vals, keep, nk, want_counts=False)
            out_t, _ = keyed_hist(slot, ts, keep, nk, want_counts=False)
            sh = (K, T, out_capacity)
            out = RecordBatch(out_k.reshape(sh), out_v.reshape(sh),
                              out_t.reshape(sh), cnt.reshape(sh) > 0)
            return zero_invalid(out), dropped
        row = jnp.where(keep, tgt, T)
        col = jnp.where(keep, pos, 0)
        kidx = jnp.arange(K, dtype=jnp.int32)[:, None]
        shape = (K, T + 1, out_capacity)
        mk = lambda src, z: jnp.zeros(shape, z).at[kidx, row, col].set(
            src, mode="drop")
        out = RecordBatch(mk(keys, jnp.int32), mk(vals, jnp.int32),
                          mk(ts, jnp.int32), mk(keep, jnp.bool_))
        out = RecordBatch(out.keys[:, :T], out.values[:, :T],
                          out.timestamps[:, :T], out.valid[:, :T])
        return zero_invalid(out), dropped


def _block_to_targets(
    batch: RecordBatch, target: jnp.ndarray, num_targets: int,
    out_capacity: int
) -> Tuple[RecordBatch, jnp.ndarray]:
    """Block-form exchange: route a whole ``[K, P, B]`` stack of per-step
    batches without sorting at all.

    A record's slot within its target is its *arrival rank*: the count of
    same-target records before it in (p-major, slot) order. With T
    targets that is a running count per target over a ``[K, T, n]``
    one-hot, the records on the lanes (an invalid record is in no row) —
    products with a 128 x 128 triangle on the MXU plus the tiles'
    offsets (``matops.running_count``), no argsort — and a record reads
    its own rank as the sum over the T rows of the count under its
    one-hot: no index is computed, so no gather. Placement is a keyed
    histogram over ``target * cap + rank`` a field (one contribution a
    slot), or past ``KERNEL_MAX_KEYS`` slots one element scatter a field
    into ``[K, T+1, cap]`` (the +1 row swallows drops).
    Bit-identical to vmapping :func:`_scatter_to_targets` per step,
    including overflow accounting (first ``cap`` arrivals per target
    survive, the rest count as dropped).

    A block whose counting scratch would exceed
    :func:`_count_route_budget` (huge T or K) counts chunk after chunk
    of steps, each chunk within the budget and at most
    ``_COUNT_CHUNK_MAX_RECORDS`` long: the same route whatever the
    block's length, chosen by that one observable.
    """
    K, P, B = batch.keys.shape
    T = num_targets
    n = P * B
    # Priced above what the branch holds (_count_route_budget) — the cap
    # must actually bound peak scratch.
    per_step = n * (T + 1) * 4 * 3
    budget = _count_route_budget()
    if K * per_step <= budget:
        return _count_to_targets(batch, target, T, out_capacity)
    # Over budget: chunk after chunk of steps, each within the budget (a
    # step too wide for it even alone still counts, one step a chunk).
    kc = _step_chunk(K, max(1, min(budget // per_step,
                                   _COUNT_CHUNK_MAX_RECORDS // n)))
    cut = lambda x: x.reshape((K // kc, kc) + x.shape[1:])
    routed, dropped = jax.lax.map(
        lambda bt: _count_to_targets(RecordBatch(*bt[:4]), bt[4], T,
                                     out_capacity, chunks=K // kc),
        tuple(map(cut, batch)) + (cut(target),))
    join = lambda x: x.reshape((K,) + x.shape[2:])
    return RecordBatch(*map(join, routed)), join(dropped)


def _block_to_target_lane(batch: RecordBatch, target: jnp.ndarray,
                          lane, out_capacity: int) -> RecordBatch:
    """ONE consumer lane of :func:`_block_to_targets` — bit-identical to
    ``_block_to_targets(...)[0][:, lane]``.

    A record's slot within its target is its arrival rank; for a single
    lane that is a running count over a ``[K, n]`` membership mask — no
    ``[K, T, n]`` one-hot — so scratch and compute shrink T-fold
    and the single-failure replay exchange counts a whole recovery
    window in one piece, where the full route goes chunk by chunk."""
    K, P, B = batch.keys.shape
    n = P * B
    note_route("kernel" if uses_kernel() else "scatter", steps=K,
               records=n, targets=1, capacity=out_capacity)
    fl = lambda x: jnp.reshape(x, (K, n))
    keys, vals, ts, valid = map(fl, batch)
    with jax.named_scope("rank"):
        tgt = jnp.where(valid, fl(target), -1)
        hit = tgt == lane
        pos = jnp.cumsum(hit.astype(jnp.int32), axis=1) - 1
        keep = hit & (pos < out_capacity)
    # Placement is "field value at the record whose rank == c" — ranks
    # are UNIQUE per step, so a keyed histogram over them IS the routed
    # batch (sum of one contribution = select). The Pallas kernel
    # (ops/histogram.py) folds it as a factored one-hot product; an XLA
    # element scatter here ran ~50ms/field at bench shapes.
    with jax.named_scope("place"):
        slot = jnp.where(keep, pos, -1)
        out_k, cnt = keyed_hist(slot, keys, keep, out_capacity)
        out_v, _ = keyed_hist(slot, vals, keep, out_capacity,
                              want_counts=False)
        out_t, _ = keyed_hist(slot, ts, keep, out_capacity,
                              want_counts=False)
        return zero_invalid(RecordBatch(out_k, out_v, out_t, cnt > 0))


def route_hash_block_lane(batch: RecordBatch, lane, parallelism: int,
                          num_key_groups: int, out_capacity: int
                          ) -> RecordBatch:
    """One consumer lane of :func:`route_hash_block` (single-failure
    replay: only the failed subtask's inputs are reconstructed)."""
    kg = key_group(batch.keys, num_key_groups)
    return _block_to_target_lane(
        batch, subtask_for_key_group(kg, parallelism, num_key_groups),
        lane, out_capacity)


def route_rebalance_block_lane(batch: RecordBatch, lane, parallelism: int,
                               out_capacity: int, offsets: jnp.ndarray
                               ) -> RecordBatch:
    """One consumer lane of :func:`route_rebalance_block`."""
    K, P, B = batch.keys.shape
    idx = jnp.arange(P * B, dtype=jnp.int32)[None, :] + offsets[:, None]
    return _block_to_target_lane(
        batch, (idx % parallelism).reshape(K, P, B), lane, out_capacity)


def route_broadcast_block_lane(batch: RecordBatch, lane,
                               out_capacity: int) -> RecordBatch:
    """One consumer lane of :func:`route_broadcast_block` (every lane
    receives the same packed records; ``lane`` is ignored)."""
    del lane
    return _block_to_target_lane(
        batch, jnp.zeros(batch.keys.shape, jnp.int32), 0, out_capacity)


def route_forward_block_lane(batch: RecordBatch, lane,
                             out_capacity: int) -> RecordBatch:
    """One consumer lane of :func:`route_forward_block`."""
    one = jax.tree_util.tree_map(lambda x: x[:, lane][:, None], batch)
    routed, _ = route_forward_block(one, out_capacity)
    return jax.tree_util.tree_map(lambda x: x[:, 0], routed)


def route_hash(batch: RecordBatch, parallelism: int, num_key_groups: int,
               out_capacity: int) -> Tuple[RecordBatch, jnp.ndarray]:
    """keyBy exchange (KeyGroupStreamPartitioner equivalent)."""
    kg = key_group(batch.keys, num_key_groups)
    return _scatter_to_targets(
        batch, subtask_for_key_group(kg, parallelism, num_key_groups),
        parallelism, out_capacity)


def route_hash_block(batch: RecordBatch, parallelism: int,
                     num_key_groups: int, out_capacity: int
                     ) -> Tuple[RecordBatch, jnp.ndarray]:
    """Block form of :func:`route_hash` over ``[K, P, B]`` stacks; returns
    (routed ``[K, parallelism, out_capacity]``, dropped ``[K, parallelism]``),
    bit-identical to ``vmap(route_hash)``."""
    kg = key_group(batch.keys, num_key_groups)
    return _block_to_targets(
        batch, subtask_for_key_group(kg, parallelism, num_key_groups),
        parallelism, out_capacity)


def route_rebalance_block(batch: RecordBatch, parallelism: int,
                          out_capacity: int, offsets: jnp.ndarray
                          ) -> Tuple[RecordBatch, jnp.ndarray]:
    """Block form of :func:`route_rebalance`; ``offsets`` is the ``[K]``
    per-step exclusive round-robin cursor."""
    K, P, B = batch.keys.shape
    idx = jnp.arange(P * B, dtype=jnp.int32)[None, :] + offsets[:, None]
    return _block_to_targets(batch, (idx % parallelism).reshape(K, P, B),
                             parallelism, out_capacity)


def route_broadcast_block(batch: RecordBatch, parallelism: int,
                          out_capacity: int
                          ) -> Tuple[RecordBatch, jnp.ndarray]:
    """Block form of :func:`route_broadcast`."""
    K = batch.keys.shape[0]
    one, dropped = _block_to_targets(
        batch, jnp.zeros(batch.keys.shape, jnp.int32), 1, out_capacity)
    rep = RecordBatch(*(jnp.broadcast_to(
        x[:, :1], (K, parallelism) + x.shape[2:]) for x in one))
    return rep, jnp.broadcast_to(dropped[:, :1], (K, parallelism)
                                 ).astype(jnp.int32)


def route_forward_block(batch: RecordBatch, out_capacity: int
                        ) -> Tuple[RecordBatch, jnp.ndarray]:
    """Block form of :func:`route_forward` (no exchange; re-capacity)."""
    K, P, B = batch.keys.shape
    if out_capacity == B:
        return zero_invalid(batch), jnp.zeros((K, P), jnp.int32)
    if out_capacity > B:
        pad = ((0, 0), (0, 0), (0, out_capacity - B))
        return (RecordBatch(*(jnp.pad(x, pad) for x in batch)),
                jnp.zeros((K, P), jnp.int32))
    keep = batch.valid[:, :, :out_capacity]
    dropped = batch.count() - keep.sum(-1).astype(jnp.int32)
    return zero_invalid(RecordBatch(
        batch.keys[:, :, :out_capacity], batch.values[:, :, :out_capacity],
        batch.timestamps[:, :, :out_capacity], keep)), dropped


def hash32_np(x: np.ndarray) -> np.ndarray:
    """Host-side (numpy) twin of :func:`hash32` for compile-time planning."""
    u = np.asarray(x, np.uint64) & 0xFFFFFFFF
    u = ((u ^ (u >> 16)) * 0x7FEB352D) & 0xFFFFFFFF
    u = ((u ^ (u >> 15)) * 0x846CA68B) & 0xFFFFFFFF
    return (u ^ (u >> 16)) & 0xFFFFFFFF


@dataclasses.dataclass
class StaticRoutePlan:
    """Compile-time hash exchange for producers whose output slots carry
    *statically known* keys (dense table emitters like the window operator:
    slot ``i`` always holds key ``i``).

    Because each slot's key — hence key group, hence target subtask — is a
    compile-time constant, routing needs **no sort and no dynamic
    placement**: the routed batch is a static gather
    ``out[k, t, c] = producer_out[k, src_p[t, c], src_slot[t, c]]``. This
    turns the hottest exchange of keyed pipelines into a few fast vector
    loads (the dynamic block sort costs hundreds of ms per block at bench
    shapes; this costs ~nothing).

    Semantics note: slots are *not* compacted — output slot (t, c) is bound
    to one (producer, slot) pair, and a step's invalid slots stay invalid
    holes. The per-step multiset of valid records equals the dynamic
    exchange's; only the slot layout differs. Capacity overflow drops whole
    *static slots* (deterministically), recorded in ``drop_p/drop_slot``
    for per-step drop accounting. Arrival order within a target (p-major,
    slot ascending) matches the dynamic exchange's stable sort.
    """

    src_p: np.ndarray      # int32 [T, cap]: producer subtask per out slot
    src_slot: np.ndarray   # int32 [T, cap]: producer slot per out slot
    ok: np.ndarray         # bool  [T, cap]: out slot is mapped
    slot_keys: np.ndarray  # int32 [T, cap]: static key (-1 = unmapped)
    drop_p: np.ndarray     # int32 [D]: overflow slots (producer subtask)
    drop_slot: np.ndarray  # int32 [D]
    drop_t: np.ndarray     # int32 [D]: target the overflow belonged to

    @property
    def width(self) -> int:
        """Mapped out slots of the fullest target (a target's mapped
        slots are its first ones)."""
        return int(self.ok.sum(axis=1).max(initial=0))

    def apply(self, out: RecordBatch) -> Tuple[RecordBatch, jnp.ndarray]:
        """Route a producer block ``[K, P, B]`` -> ``[K, T, cap]``."""
        K = out.keys.shape[0]
        T, cap = self.src_p.shape
        # Gather the mapped columns only (a gather costs per index); the
        # rest of the receive window is never written.
        w = self.width
        g = lambda x: x[:, self.src_p[:, :w], self.src_slot[:, :w]]
        with jax.named_scope("plan"):
            valid = g(out.valid) & self.ok[None, :, :w]
            routed = zero_invalid(RecordBatch(
                g(out.keys), g(out.values), g(out.timestamps), valid))
            routed = RecordBatch(*(jnp.pad(
                x, ((0, 0), (0, 0), (0, cap - w))) for x in routed))
            if len(self.drop_p):
                dv = out.valid[:, self.drop_p, self.drop_slot]  # [K, D]
                dropped = jnp.zeros((K, T), jnp.int32).at[
                    :, self.drop_t].add(dv.astype(jnp.int32))
            else:
                dropped = jnp.zeros((K, T), jnp.int32)
            return routed, dropped


def _static_targets(slot_keys: np.ndarray, parallelism: int,
                    num_key_groups: int) -> np.ndarray:
    """Target subtask of each static slot key — THE key->key-group->
    subtask map; every compile-time consumer must share this one copy."""
    kg = (hash32_np(slot_keys) % num_key_groups).astype(np.int64)
    return (kg * parallelism) // num_key_groups


def own_slots(slot_keys: np.ndarray, parallelism: int,
              num_key_groups: int, clamp_keys=()) -> np.ndarray:
    """``live[p, slot]`` of a dense-table emitter whose every subtask
    has received only its own keys (``CompiledJob._plan_edges`` says
    when): slot ``i`` can hold a record on subtask ``p`` only if ``p``
    owns key ``slot_keys[i]``, or the key is one of ``clamp_keys``, the
    columns the operator folds out-of-range keys into — on whichever
    subtask received them, so those columns are live everywhere."""
    slot_keys = np.asarray(slot_keys, np.int64)
    own = (_static_targets(slot_keys, parallelism, num_key_groups)[None, :]
           == np.arange(parallelism)[:, None])
    return own | np.isin(slot_keys, np.asarray(clamp_keys, np.int64))[None, :]


def own_columns_width(num_keys: int, parallelism: int,
                      num_key_groups: int) -> Optional[int]:
    """The columns a subtask needs to hold a table over ``[0,
    num_keys)`` for its own keys alone (``Operator.own_columns``): the
    most ids any subtask owns (:func:`own_slots`), up to the next tile
    of 128 lanes — or None where that is no narrower than a column a
    key."""
    most = int(own_slots(np.arange(num_keys), parallelism,
                         num_key_groups).sum(axis=1).max())
    width = -(-most // 128) * 128
    return width if width < num_keys else None


def note_own_columns(vertex: str, columns: int, bound: int,
                     num_keys: int) -> None:
    """Record of a vertex whose tables hold own columns (``plan.
    own-columns`` instant, once per such vertex and job planned, beside
    :func:`note_route`'s; chip_smoke.py prints them): the ``columns`` a
    subtask has, the most ids ``bound`` to one, of ``num_keys``."""
    get_tracer().event("plan.own-columns", vertex=vertex, columns=columns,
                       bound=bound, num_keys=num_keys)


def static_hash_capacity(slot_keys: np.ndarray, src_parallelism: int,
                         parallelism: int, num_key_groups: int,
                         live: Optional[np.ndarray] = None) -> int:
    """Smallest per-target receive capacity for which
    :func:`plan_static_hash` has no overflow (drop) slots: the most
    live (producer, slot) pairs any one target is sent — without
    ``live`` (bool ``[src_parallelism, slots]``, :func:`own_slots`)
    the densest target's key count times the producer parallelism."""
    slot_keys = np.asarray(slot_keys, np.int64)
    tgt = _static_targets(slot_keys, parallelism, num_key_groups)
    pairs = (np.full(slot_keys.shape, src_parallelism) if live is None
             else np.asarray(live, bool).sum(axis=0))
    return int(np.bincount(tgt, weights=pairs, minlength=parallelism).max())


def plan_static_hash(slot_keys: np.ndarray, src_parallelism: int,
                     parallelism: int, num_key_groups: int,
                     out_capacity: int,
                     live: Optional[np.ndarray] = None) -> StaticRoutePlan:
    """Build a :class:`StaticRoutePlan` for a HASH edge whose producer
    emits key ``slot_keys[i]`` in slot ``i`` on every subtask. With
    ``live`` (:func:`own_slots`) only the (producer, slot) pairs it
    marks get a slot: the others never hold a record."""
    slot_keys = np.asarray(slot_keys, np.int64)
    if live is None:
        live = np.ones((src_parallelism, slot_keys.shape[0]), bool)
    tgt = _static_targets(slot_keys, parallelism, num_key_groups)
    T, cap = parallelism, out_capacity
    src_p = np.zeros((T, cap), np.int32)
    src_slot = np.zeros((T, cap), np.int32)
    ok = np.zeros((T, cap), bool)
    keys_out = np.full((T, cap), -1, np.int32)
    drops = []
    for t in range(T):
        # p-major, slot ascending = the dynamic exchange's arrival order
        ps, ss = np.nonzero(live & (tgt == t)[None, :])
        n = min(len(ps), cap)
        src_p[t, :n], src_slot[t, :n] = ps[:n], ss[:n]
        ok[t, :n] = True
        keys_out[t, :n] = slot_keys[ss[:n]]
        drops.append(np.stack([ps[n:], ss[n:], np.full(len(ps) - n, t)]))
    drop_p, drop_slot, drop_t = np.concatenate(drops, axis=1).astype(np.int32)
    return StaticRoutePlan(
        src_p=src_p, src_slot=src_slot, ok=ok, slot_keys=keys_out,
        drop_p=drop_p, drop_slot=drop_slot, drop_t=drop_t)


def route_rebalance(batch: RecordBatch, parallelism: int, out_capacity: int,
                    offset=0) -> Tuple[RecordBatch, jnp.ndarray]:
    """Deterministic round-robin by global record index (the reference's
    RebalancePartitioner starts at a *random* channel — randomness it must
    log via RandomService, RecordWriter.java:131-137; a deterministic cycle
    with a carried ``offset`` needs no determinant)."""
    n = batch.keys.size
    idx = jnp.arange(n, dtype=jnp.int32) + jnp.asarray(offset, jnp.int32)
    return _scatter_to_targets(batch, (idx % parallelism).reshape(batch.keys.shape),
                               parallelism, out_capacity)


def route_forward(batch: RecordBatch, out_capacity: int
                  ) -> Tuple[RecordBatch, jnp.ndarray]:
    """1:1 edge: same subtask index downstream, re-capacitied."""
    p, b = batch.keys.shape
    if out_capacity == b:
        return zero_invalid(batch), jnp.zeros((p,), jnp.int32)
    if out_capacity > b:
        pad = ((0, 0), (0, out_capacity - b))
        return RecordBatch(*(jnp.pad(x, pad) for x in batch)), jnp.zeros((p,), jnp.int32)
    keep = batch.valid[:, :out_capacity]
    dropped = batch.count() - keep.sum(-1).astype(jnp.int32)
    return zero_invalid(RecordBatch(
        batch.keys[:, :out_capacity], batch.values[:, :out_capacity],
        batch.timestamps[:, :out_capacity], keep)), dropped


def route_broadcast(batch: RecordBatch, parallelism: int, out_capacity: int
                    ) -> Tuple[RecordBatch, jnp.ndarray]:
    """Every downstream subtask receives every record (compacted)."""
    target = jnp.zeros(batch.keys.shape, jnp.int32)
    one, dropped = _scatter_to_targets(batch, target, 1, out_capacity)
    rep = RecordBatch(*(jnp.broadcast_to(x[0], (parallelism,) + x.shape[1:])
                        for x in one))
    return rep, jnp.broadcast_to(dropped[0], (parallelism,)).astype(jnp.int32)
