"""Determinant replication: the piggyback channel, TPU-style.

The reference appends causal-log deltas to every outgoing netty
``BufferResponse`` and merges them on receive
(io/network/netty/NettyMessage.java:156-242, serde in
causal/log/job/serde/AbstractDeltaSerializerDeserializer.java:50, offset
dedup in ThreadCausalLogImpl.processUpstreamDelta:117, sharing-depth cut in
JobCausalLogImpl.respondToDeterminantRequest:192 and the serde's
insertNewUpstreamLog:165-193).

TPU-native re-design: replication is a **block-boundary collective**, not a
per-message payload. Every (owner subtask -> holder subtask) pair within the
sharing-depth cut is one row of a stacked replica log
``int32[R, capacity, lanes]``. The executor's block program appends the
same determinant tensor to owners and to their replicas
(:func:`append_block`: run by run at the owner's ring offset, in place) —
replica heads therefore equal owner heads *by construction* at every
block fence, and the determinants describing a block's outputs are on
their holders before those outputs become externally visible (the
piggyback guarantee, NettyMessage.java:156-242). Under pjit over a device
mesh the stack is sharded on its leading axis and appended in the batched
form, whose owner-indexed gather of the rows lowers to the ICI all-gather
this design targets (SURVEY.md §2.6).

:func:`replicate_step` (pull + offset-dedup merge) remains the
*resynchronization* path — recovery catch-up and reconnect-after-gap —
mirroring the reference's processUpstreamDelta dedup semantics.

Transitive sharing: the reference relays a remote log's delta hop-by-hop;
here the sharing mask already contains every (owner, holder) pair within
depth (multi-hop distances via CausalGraphUtils-equivalent BFS), so delivery
is direct — same reachable-replica semantics, one hop.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.causal import log as clog
from clonos_tpu.graph.job_graph import JobGraph


@dataclasses.dataclass(frozen=True)
class ReplicationPlan:
    """Static description of who replicates whose log.

    ``pairs[r] = (owner_flat, holder_flat)`` over flat subtask indices
    (JobGraph.subtask_base layout).

    ``replication_factor`` bounds how many holder *subtasks* per
    (owner subtask, holder vertex) pair carry a replica: holder subtask
    ``(owner_sub + j) % P_holder`` for ``j < factor``. ``-1`` = every
    holder subtask (the reference's behavior, where every downstream TM
    within sharing depth accumulates the log via piggybacking —
    JobCausalLogImpl.java:71 keyed by CausalLogID with one copy per TM).
    A bounded factor is the memory-scalable default: the full bipartite
    product is O(V^2·P^2) log copies, structurally impossible at the
    128-task BASELINE configs; factor k survives any k-1 failures among
    an owner's chosen holders (plus arbitrary other failures), and k=P
    restores reference-equivalent redundancy.
    """

    pairs: Tuple[Tuple[int, int], ...]
    num_subtasks: int
    replication_factor: int = -1

    @classmethod
    def from_job(cls, job: JobGraph, sharing_depth: int = -1,
                 replication_factor: int = -1) -> "ReplicationPlan":
        info = job.graph_info(0)
        mask = info.sharing_mask(sharing_depth)
        pairs: List[Tuple[int, int]] = []
        for owner_v in range(len(job.vertices)):
            for holder_v in range(len(job.vertices)):
                if owner_v == holder_v or not mask[owner_v, holder_v]:
                    continue
                ob = job.subtask_base(owner_v)
                hb = job.subtask_base(holder_v)
                hp = job.vertices[holder_v].parallelism
                k = hp if replication_factor < 0 else min(replication_factor,
                                                          hp)
                for os_ in range(job.vertices[owner_v].parallelism):
                    for j in range(k):
                        pairs.append((ob + os_, hb + (os_ + j) % hp))
        return cls(tuple(pairs), job.total_subtasks(), replication_factor)

    @property
    def num_replicas(self) -> int:
        return len(self.pairs)

    def owner_index(self) -> jnp.ndarray:
        return jnp.asarray([o for o, _ in self.pairs], jnp.int32)

    @property
    def runs(self) -> Tuple[Tuple[int, int, int], ...]:
        """``(start, k, owner)``: the maximal stretches of consecutive
        replica rows that copy one owner's log. ``from_job`` lays
        ``pairs`` out owner vertex, holder vertex, owner subtask, ``j``,
        so the ``k`` replicas of one owner towards one holder vertex are
        adjacent rows of the stack (recovery indexes replicas by row:
        nothing is reordered here)."""
        runs: List[Tuple[int, int, int]] = []
        for r, (owner, _) in enumerate(self.pairs):
            if runs and runs[-1][2] == owner:
                start, k, _ = runs[-1]
                runs[-1] = (start, k + 1, owner)
            else:
                runs.append((r, 1, owner))
        return tuple(runs)

    def replicas_held_by(self, holder_flat: int) -> List[int]:
        """Replica row indices held by one subtask (its share of the stacked
        replica log — what it answers determinant requests from)."""
        return [r for r, (_, h) in enumerate(self.pairs) if h == holder_flat]

    def replicas_of(self, owner_flat: int) -> List[int]:
        return [r for r, (o, _) in enumerate(self.pairs) if o == owner_flat]


def create_replicas(plan: ReplicationPlan, capacity: int,
                    max_epochs: int) -> clog.ThreadLogState:
    """Stacked replica logs [R, capacity, lanes]."""
    return jax.vmap(lambda _: clog.create(capacity, max_epochs))(
        jnp.arange(max(plan.num_replicas, 1)))


def append_block(replicas: clog.ThreadLogState, rows: jnp.ndarray,
                 owner_heads: jnp.ndarray, plan: ReplicationPlan,
                 sharded: bool = False) -> clog.ThreadLogState:
    """Piggyback replication at the block fence: the block's determinant
    rows ``[L, n, lanes]`` land on every replica of their owner, at the
    replica's head, in the block program (before any of the block's
    outputs is visible).

    A replica's head equals its owner's (``owner_heads``: the owners'
    heads before this block; both are appended from the same tensor,
    recovery copies an owner's log whole onto its replicas, and
    ``tests/test_replica_append.py`` holds it through blocks, single
    rows, fences, kills and recoveries), and the replicas of one owner
    lie in runs of adjacent rows (``plan.runs``). So where the ring has
    whole slots of ``n`` rows and the append is long enough to be bulk
    (``clog.append_form`` not ``scatter``), the stack is appended run by
    run at the owner's offset, in place (``clog.append_runs``). A stack
    sharded over a mesh keeps the batched form, a head per log and the
    owner-indexed gather of the rows (the run-wise loop slices the
    sharded axis at traced starts, which the partitioner answers with
    collectives), as does a block whose ``n`` leaves no whole slots."""
    if plan.num_replicas == 0:
        return replicas
    n, cap = rows.shape[1], replicas.rows.shape[1]
    form = clog.append_form(n, cap)
    runs = plan.runs
    if form != "scatter" and not sharded and clog.runs_appendable(n, cap):
        clog.note_append("runs", logs=plan.num_replicas, runs=len(runs),
                         rows=n, capacity=cap)
        return clog.append_runs(replicas, rows, owner_heads, runs)
    clog.note_append(form, logs=plan.num_replicas, runs=0, rows=n,
                     capacity=cap)
    return clog.v_append_full(replicas, rows[plan.owner_index()])


def replicate_step(replicas: clog.ThreadLogState,
                   owner_logs: clog.ThreadLogState,
                   owner_idx: jnp.ndarray,
                   max_delta: int) -> Tuple[clog.ThreadLogState, jnp.ndarray]:
    """One replication round: pull each owner's fresh suffix into every
    replica. Pure function — runs inside the jitted superstep.

    Returns (replicas, lag) where ``lag[r]`` is how many rows replica r is
    still behind after this round (nonzero when the owner produced more than
    ``max_delta`` since last round; the next round catches up — determinant
    durability lags by that many rows, the analog of netty frames in
    flight)."""
    owners = jax.tree_util.tree_map(lambda x: x[owner_idx], owner_logs)
    buf, count, start = clog.v_slice_from(owners, replicas.head, max_delta)
    new_replicas, gaps = clog.v_merge_delta(replicas, buf, count, start)
    lag = owners.head - new_replicas.head
    return new_replicas, lag


def sync_replica_epochs(replicas: clog.ThreadLogState, epoch_id
                        ) -> clog.ThreadLogState:
    """Record the epoch index on replicas at the epoch fence. Run *after* a
    catch-up replication round so replica heads equal owner heads and the
    epoch->offset entries agree with the owners'."""
    return clog.v_start_epoch(replicas, epoch_id)


# --- recovery-side: determinant requests (host control plane) ---------------


def collect_determinant_response(
    replicas_host: clog.ThreadLogState, replica_rows: Sequence[int],
    from_epoch: int, max_out: int,
) -> Dict[int, Tuple[np.ndarray, int]]:
    """Serve a DeterminantRequest from this holder's replica rows
    (reference JobCausalLogImpl.respondToDeterminantRequest:188): for each
    replica row, all retained rows from ``from_epoch``'s start. Returns
    {replica_row: (rows ndarray, abs_start)}."""
    out: Dict[int, Tuple[np.ndarray, int]] = {}
    for r in replica_rows:
        one = jax.tree_util.tree_map(lambda x: x[r], replicas_host)
        buf, count, start = clog.get_determinants(one, from_epoch, max_out)
        out[r] = (np.asarray(buf)[: int(count)], int(start))
    return out


def merge_determinant_responses(
    responses: Sequence[Tuple[np.ndarray, int]],
) -> Tuple[np.ndarray, int]:
    """Merge responses from multiple holders (reference
    DeterminantResponseEvent.merge / AbstractState.java:106-143): every
    response is a prefix-consistent slice of the same owner log, so the
    merged view is the one reaching furthest, extended left to the earliest
    start. Verifies overlap consistency (bit-equality on shared offsets)."""
    if not responses:
        # Lane-shaped empty, not (0, 0): a zero-step replay (kill right
        # after a completed fence — the pipelined fence's joined tail
        # lands exactly there) still column-indexes the merged rows.
        from clonos_tpu.causal import determinant as det
        return np.zeros((0, det.NUM_LANES), np.int32), 0
    best_rows, best_start = None, 0
    for rows, start in responses:
        if best_rows is None:
            best_rows, best_start = rows.copy(), start
            continue
        # Consistency on the overlap:
        lo = max(start, best_start)
        hi = min(start + len(rows), best_start + len(best_rows))
        if hi > lo:
            a = best_rows[lo - best_start: hi - best_start]
            b = rows[lo - start: hi - start]
            if not np.array_equal(a, b):
                raise ValueError(
                    "divergent determinant responses: replicas disagree on "
                    f"offsets [{lo},{hi}) — protocol violation")
        # Extend right.
        if start + len(rows) > best_start + len(best_rows):
            tail_from = best_start + len(best_rows) - start
            if tail_from < 0:
                best_rows, best_start = rows.copy(), start
            else:
                best_rows = np.concatenate([best_rows, rows[tail_from:]])
        # Extend left.
        if start < best_start:
            head_upto = best_start - start
            best_rows = np.concatenate([rows[:head_upto], best_rows])
            best_start = start
    return best_rows, best_start
