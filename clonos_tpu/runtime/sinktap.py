"""The transactional sink's tap: a block's sink rows, not its capacity.

A sink vertex's block output is ``[K, P, capacity]`` (keys, values,
timestamps, ``valid``) of which a sliver is rows. The tap compacts the
valid rows **on the device**, per subtask, in flattened ``(step, slot)``
order — the order a boolean mask over the flattened lane gives — and the
host reads back the per-subtask counts and the packed rows only.

A packed buffer needs a static row budget. It comes from what the tap
observes: a short ladder of budgets per block shape, top rung ``K x
capacity`` (every slot valid), the rung of a block chosen from the
previous block's largest per-subtask count with half as much again of
headroom. The counts come back with the rows; a block whose count
exceeds its rung is read again through the first rung that holds it
(counted: ``sink.rung_misses``). Every rung of a block shape is built
when that shape is first seen, so nothing is built later, whichever rung
a count lands on.

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

#: budgets below the top rung, as right shifts of ``K x capacity``
_RUNG_SHIFTS = (10, 8, 6, 3)
#: smallest budget worth a program of its own
_MIN_RUNG = 256
#: most output slots searched at a time
_RANK_CHUNK = 2048


def ladder(lane_len: int) -> Tuple[int, ...]:
    """The budgets for lanes of ``lane_len`` slots, ascending; the last
    is ``lane_len`` itself."""
    return tuple(sorted({lane_len} | {lane_len >> s for s in _RUNG_SHIFTS
                                      if lane_len >> s >= _MIN_RUNG}))


def pack_lanes(batch: RecordBatch, budget: int):
    """``[K, P, capacity]`` batch -> ``(counts [P], rows [P, 3, budget])``
    int32: per subtask the number of valid rows, and the first ``budget``
    of them as (key, value, timestamp) planes in ``(step, slot)`` order.
    Slots past a subtask's count hold no row (whatever the clamped search
    lands on). Lanes are independent: nothing crosses the subtask axis.

    A stable compaction with no scatter and no sort: a cumulative count
    of ``valid`` inside each step and over the steps of a lane; then for
    each output slot the step that holds its rank (a dense compare
    against the ``K`` per-step totals), that step's row of counts (one
    row gather: on the TPU a gather costs per index, not per byte), the
    slot inside it (a dense compare again) and the three values there.
    Time goes with the budget, about 1 us a slot on a v5e."""
    k, p, cap = batch.valid.shape
    within = jnp.cumsum(batch.valid, axis=2, dtype=jnp.int32)   # [K, P, cap]
    per_step = within[:, :, -1]
    upto = jnp.cumsum(per_step, axis=0)                         # [K, P]
    counts = upto[-1]
    before = upto - per_step
    lane = jnp.arange(p, dtype=jnp.int32)[:, None]

    def pack(rank):                                   # [chunk] ranks
        step = jnp.sum(upto.T[:, None, :] <= rank[None, :, None], axis=2,
                       dtype=jnp.int32)               # [P, chunk]
        step = jnp.minimum(step, k - 1)
        local = rank[None, :] - before[step, lane]    # rank inside the step
        slot = jnp.sum(within[step, lane] <= local[:, :, None], axis=2,
                       dtype=jnp.int32)
        slot = jnp.minimum(slot, cap - 1)
        return jnp.stack([batch.keys[step, lane, slot],
                          batch.values[step, lane, slot],
                          batch.timestamps[step, lane, slot]], axis=1)

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


class SinkTap:
    """Device-side compaction and read-back for one sink vertex.
    ``mesh`` / ``task_axis``: the executor's task mesh (None without
    one); a block whose subtask axis divides over it is compacted per
    shard, each chip handing back its own subtasks' rows."""

    def __init__(self, mesh: Optional[jax.sharding.Mesh], task_axis: str):
        self.mesh, self.task_axis = mesh, task_axis
        self._ladders: Dict[Any, _Ladder] = {}

    def _build(self, batch: RecordBatch) -> _Ladder:
        k, p, cap = batch.valid.shape
        programs = {}
        for budget in ladder(k * cap):
            def sink_pack(b, budget=budget):    # the program's name
                return pack_lanes(b, budget)
            fn = sink_pack
            if self.mesh is not None and \
                    p % self.mesh.shape[self.task_axis] == 0:
                lanes = PartitionSpec(None, self.task_axis, None)
                fn = jax.shard_map(
                    fn, mesh=self.mesh, in_specs=(RecordBatch(*[lanes] * 4),),
                    out_specs=(PartitionSpec(self.task_axis),
                               PartitionSpec(self.task_axis, None, None)),
                    check_vma=False)
            programs[budget] = jax.jit(fn).lower(batch).compile()
        return _Ladder(programs)

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
        return PackedBlock(lad, batch, rung, counts, rows)

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
        return counts, rows
