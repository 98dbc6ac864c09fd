"""The transactional sink's tap: a block's sink rows, not its capacity.

A sink vertex's block output is ``[K, P, capacity]`` (keys, values,
timestamps, ``valid``) of which a sliver is rows. The tap compacts the
valid rows **on the device**, per subtask, in flattened ``(step, slot)``
order — the order a boolean mask over the flattened lane gives — and the
host reads back the per-subtask counts and the packed rows only.

A packed buffer needs a static row budget, and below a thirty-second of
the lane the compaction's time on the device goes with that budget
(``pack_lanes``: by rank there, by shifts above). It comes from what the
tap observes: a ladder of budgets per block shape, a factor of two apart
up to ``K x capacity`` (every slot valid), the rung of a block chosen
from the previous block's largest per-subtask count with half as much
again of headroom: at most three slots a row (``sink.rows /
sink.pack_slots`` is the fill that comes of it). The counts come back
with the rows; a block whose count exceeds its rung is
read again through the first rung that holds it (counted:
``sink.rung_misses``). Every rung of a block shape is built when that
shape is first seen, so nothing is built later, whichever rung a count
lands on.

**The tap trails the block program by one block.** The device queue of an
epoch is ``run_block(k)``, ``sink_pack(k)``, ``run_block(k+1)``,
``sink_pack(k+1)``, ... with no gap: ``dispatch`` only launches, and
``ClusterRunner._absorb_sink_outputs`` — called once a block, right after
block ``k+1`` is dispatched — first waits out and reads ``pack(k)``
(``block.sink.wait`` with ``trailing=1``, counted in
``sink.taps_trailing``), shards it under block ``k``'s epoch, and only
then launches ``pack(k+1)``, so the rung of a block still comes from the
count of the block before it. The host therefore draws, pulls, puts and
dispatches block ``k+1`` while the chip runs block ``k``. The dense
``PackedBlock.batch`` kept for a read-again lives across that one
dispatch and no longer.

The tap never trails out of a block loop. It is drained (the same three
spans, ``trailing=0``: nothing is queued behind the block waited for)

- by ``LocalExecutor.run_epoch`` after an epoch's last block, before the
  roll and so before the fence of either mode: ``fence.txn-seal``, the
  lineage plane's ``TransactionLog.pending_shards`` and the checkpoint
  see every block of the closed epoch, sharded under *its* id;
- by ``LocalExecutor.step`` after its one-step block: whoever steps may
  read the sink next;
- at entry to ``ClusterRunner.inject_failure``: a no-op unless an
  exception abandoned a block loop (both loops above drain themselves),
  and then the block is read before the kill decides which pending
  shards are lost.

``recover`` and ``failover_drill`` start from a kill, and ``rescale_live``
from a completed fence: nothing is in flight where they begin.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from clonos_tpu.api.records import RecordBatch

#: smallest budget worth a program of its own
_MIN_RUNG = 256
#: a budget of at least the lane over this is packed by shifts (their time
#: goes with the lane, ~1.4 ns a slot of it on a v5e), a smaller one by
#: rank (~50 ns a slot of the budget)
_SHIFTS_FROM = 32
#: most output slots searched at a time
_RANK_CHUNK = 2048


def ladder(lane_len: int) -> Tuple[int, ...]:
    """The budgets for lanes of ``lane_len`` slots, ascending and a
    factor of two apart; the last is ``lane_len`` itself. With the
    headroom ``SinkTap.dispatch`` asks for, a block is packed through at
    most three slots a row it holds."""
    shifts = range(max(lane_len // _MIN_RUNG, 1).bit_length())
    return tuple(lane_len >> s for s in reversed(shifts))


def packs_by_shifts(budget: int, lane_len: int) -> bool:
    """Which of ``pack_lanes``' two forms a budget takes: a fact of the
    two lengths."""
    return budget * _SHIFTS_FROM >= lane_len


def pack_lanes(batch: RecordBatch, budget: int):
    """``[K, P, capacity]`` batch -> ``(counts [P], rows [P, 3, budget])``
    int32: per subtask the number of valid rows, and the first ``budget``
    of them as (key, value, timestamp) planes in ``(step, slot)`` order.
    Slots past a subtask's count are zero. Lanes are independent: nothing
    crosses the subtask axis. A stable compaction with no scatter and no
    sort, in the form that is cheaper for the budget (``packs_by_shifts``):
    a sparse block pays for the rows it may hold, a dense one for its
    lane, and no budget for more than the lane."""
    k, _, cap = batch.valid.shape
    if packs_by_shifts(budget, k * cap):
        return _pack_by_shifts(batch, budget)
    return _pack_by_rank(batch, budget)


def _pack_by_shifts(batch: RecordBatch, budget: int):
    """No gather at all: a row at lane position ``i`` has to move left by
    the empty slots before it, ``i - rank``, and moves by the bits of
    that distance, lowest first — one pass over the lane a bit, each a
    shift by a power of two and a select. Rows keep their order and the
    distance never falls from one row to the next, so no two ever meet.
    The budget is a slice of the packed lane."""
    k, p, cap = batch.valid.shape
    n = k * cap

    def lanes(a):
        return a.transpose(1, 0, 2).reshape(p, n)

    valid = lanes(batch.valid)
    rank = jnp.cumsum(valid, axis=1, dtype=jnp.int32) - 1
    left = jnp.where(valid, jnp.arange(n, dtype=jnp.int32) - rank, 0)
    planes = [left] + [jnp.where(valid, lanes(a), 0) for a in
                       (batch.keys, batch.values, batch.timestamps)]
    for bit in range((n - 1).bit_length()):
        by = 1 << bit
        goes = (planes[0] & by) != 0

        def pulled(a):
            return jnp.pad(a[:, by:], ((0, 0), (0, by)))

        comes = pulled(goes)
        # a slot takes the row that arrives, is emptied by one that
        # leaves, or keeps what it holds
        planes = [jnp.where(comes, pulled(a), jnp.where(goes, 0, a))
                  for a in planes]
    return rank[:, -1] + 1, jnp.stack([a[:, :budget] for a in planes[1:]],
                                      axis=1)


def _pack_by_rank(batch: RecordBatch, budget: int):
    """Four gathers a budget slot: every valid slot is marked with its
    rank in the lane plus one (a cumulative count inside each step and
    over the steps); then for each output slot the step that holds its
    rank (a dense compare against the ``K`` per-step totals), that
    step's row of marks (one row gather: on the TPU a gather costs per
    index first, per byte second), the slot inside it (a dense compare
    again) and the three values there."""
    k, p, cap = batch.valid.shape
    within = jnp.cumsum(batch.valid, axis=2, dtype=jnp.int32)   # [K, P, cap]
    upto = jnp.cumsum(within[:, :, -1], axis=0)                 # [K, P]
    counts = upto[-1]
    before = jnp.concatenate([jnp.zeros_like(upto[:1]), upto[:-1]])
    mark = jnp.where(batch.valid, before[:, :, None] + within, 0)
    lane = jnp.arange(p, dtype=jnp.int32)[:, None]
    slots = jnp.arange(cap, dtype=jnp.int32)

    def pack(rank):                                   # [chunk] ranks
        step = jnp.sum(upto.T[:, None, :] <= rank[None, :, None], axis=2,
                       dtype=jnp.int32)               # [P, chunk]
        step = jnp.minimum(step, k - 1)
        hit = mark[step, lane] == rank[None, :, None] + 1
        slot = jnp.sum(jnp.where(hit, slots, 0), axis=2)
        held = rank[None, :] < counts[:, None]
        return jnp.stack([jnp.where(held, a[step, lane, slot], 0) for a in
                          (batch.keys, batch.values, batch.timestamps)],
                         axis=1)                      # [P, 3, chunk]

    # a chunk of ranks at a time, so that the gathered rows ([P, chunk,
    # capacity]) stay small whatever the budget
    chunks = -(-budget // _RANK_CHUNK)
    ranks = jnp.arange(chunks * -(-budget // chunks), dtype=jnp.int32)
    rows = jax.lax.map(pack, ranks.reshape(chunks, -1))
    rows = rows.transpose(1, 2, 0, 3).reshape(p, 3, -1)[:, :, :budget]
    return counts, rows


@dataclasses.dataclass
class _Ladder:
    """The programs of one block shape, and what its last block held."""
    programs: Dict[int, Any]          # budget -> compiled pack_lanes
    #: largest per-subtask count of the previous block of this shape
    seen: int = 0

    def rung_for(self, count: int) -> int:
        """The first rung that holds ``count`` rows a subtask (the top
        one holds whatever a lane can)."""
        return next((r for r in self.programs if r >= count),
                    max(self.programs))


@dataclasses.dataclass
class PackedBlock:
    """One block's compaction in flight, from its launch behind the
    block program to its read one block later: the device arrays of the
    rung it was speculated at; once read, the rung it was read through,
    the bytes copied and whether it had to be read again."""
    ladder: _Ladder
    batch: RecordBatch
    rung: int
    counts: jax.Array
    rows: jax.Array
    nbytes: int = 0
    missed: int = 0
    #: budget slots compacted for it, over all lanes, and how many of
    #: its compactions packed by shifts (a read-again's too)
    slots: int = 0
    shifted: int = 0

    def launched(self, rung: int) -> None:
        k, p, cap = self.batch.valid.shape
        self.slots += p * rung
        self.shifted += packs_by_shifts(rung, k * cap)


class SinkTap:
    """Device-side compaction and read-back for one sink vertex.
    ``mesh`` / ``task_axis``: the executor's task mesh (None without
    one); a block whose subtask axis divides over it is compacted per
    shard, each chip handing back its own subtasks' rows."""

    def __init__(self, mesh: Optional[jax.sharding.Mesh], task_axis: str):
        self.mesh, self.task_axis = mesh, task_axis
        self._ladders: Dict[Any, _Ladder] = {}

    def _program(self, batch: RecordBatch, budget: int):
        """``pack_lanes`` at one budget, compiled for ``batch``'s shape
        and placement."""
        def sink_pack(b):                       # the program's name
            return pack_lanes(b, budget)
        fn = sink_pack
        if self.mesh is not None and \
                batch.valid.shape[1] % self.mesh.shape[self.task_axis] == 0:
            lanes = PartitionSpec(None, self.task_axis, None)
            fn = jax.shard_map(
                fn, mesh=self.mesh, in_specs=(RecordBatch(*[lanes] * 4),),
                out_specs=(PartitionSpec(self.task_axis),
                           PartitionSpec(self.task_axis, None, None)),
                check_vma=False)
        return jax.jit(fn).lower(batch).compile()

    def _build(self, batch: RecordBatch) -> _Ladder:
        k, _, cap = batch.valid.shape
        return _Ladder({budget: self._program(batch, budget)
                        for budget in ladder(k * cap)})

    def dispatch(self, batch: RecordBatch) -> PackedBlock:
        """Launch the compaction of ``batch`` at the rung the previous
        block of its shape suggests (it queues behind the program that
        makes ``batch``). The first block of a shape builds the shape's
        programs."""
        key = (batch.valid.shape, batch.valid.sharding)
        lad = self._ladders.get(key)
        if lad is None:
            lad = self._ladders[key] = self._build(batch)
        rung = lad.rung_for(lad.seen + (lad.seen >> 1))
        counts, rows = lad.programs[rung](batch)
        # the copy starts when the compaction ends, not a host round
        # trip later
        counts.copy_to_host_async()
        rows.copy_to_host_async()
        packed = PackedBlock(lad, batch, rung, counts, rows)
        packed.launched(rung)
        return packed

    def read(self, packed: PackedBlock) -> Tuple[np.ndarray, np.ndarray]:
        """Copy a dispatched block back: ``(counts [P], rows [P, 3, R])``.
        A count over the speculated rung is read again through the first
        rung that holds it; no row is lost either way."""
        counts, rows = np.asarray(packed.counts), np.asarray(packed.rows)
        packed.nbytes = counts.nbytes + rows.nbytes
        lad = packed.ladder
        lad.seen = int(counts.max(initial=0))
        if lad.seen > packed.rung:
            packed.missed = 1
            packed.rung = lad.rung_for(lad.seen)
            rows = np.asarray(lad.programs[packed.rung](packed.batch)[1])
            packed.nbytes += rows.nbytes
            packed.launched(packed.rung)
        return counts, rows
