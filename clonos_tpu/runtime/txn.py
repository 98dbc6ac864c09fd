"""Transactional (exactly-once) sink egress — the 2-phase-commit analog.

Reference: TwoPhaseCommitSinkFunction.java (flink-streaming-java
.../functions/sink/): outputs accumulate in a per-checkpoint transaction,
pre-commit on snapshot, commit on notifyCheckpointComplete — so the
external world only ever observes output backed by a completed
checkpoint, and a replayed epoch can never double-emit.

TPU mapping: the sink operator stays a device op; the host-side
TransactionLog buffers each epoch's emitted records as the *pending
transaction* (sharded per sink subtask, matching the reference's
one-transaction-per-sink-instance ownership), seals it at the epoch
fence, and commits when the checkpoint coordinator reports the epoch's
checkpoint complete. A failed sink subtask loses ITS pending shards
(they lived with the task); recovery replays the lost epochs and
rebuilds those shards from the replayed outputs before any commit — so
the committed stream is bit-identical to a never-failed run's.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from clonos_tpu.obs.trace import get_tracer


@dataclasses.dataclass
class _Txn:
    epoch: int
    #: per-subtask accumulated [n, 3] (key, value, timestamp) records
    shards: Dict[int, List[np.ndarray]] = dataclasses.field(
        default_factory=dict)
    sealed: bool = False


class TransactionLog:
    """Per-sink-vertex 2PC state machine with per-subtask transaction
    shards."""

    def __init__(self, vertex_id: int,
                 committer: Optional[Callable[[int, np.ndarray], None]]
                 = None):
        self.vertex_id = vertex_id
        self.committer = committer
        #: pre-commit hook, called at seal with the epoch's per-subtask
        #: shards — durable sinks persist pending parts here BEFORE the
        #: checkpoint can complete (runtime/filesink.py; the reference's
        #: preCommit-on-snapshot durability promise).
        self.pre_committer: Optional[
            Callable[[int, Dict[int, np.ndarray]], None]] = None
        self._pending: Dict[int, _Txn] = {}
        self.committed: List[Tuple[int, np.ndarray]] = []

    # --- pre-commit side -----------------------------------------------------

    def absorb(self, epoch: int, counts: np.ndarray,
               rows: np.ndarray) -> None:
        """Append one block's sink emissions to the epoch's pending
        transaction, sharded per subtask: ``counts [P]`` valid rows a
        subtask and ``rows [P, 3, R]`` their (key, value, timestamp)
        planes in (step, slot) order, as the tap packed them on the
        device (runtime/sinktap.py)."""
        txn = self._pending.setdefault(epoch, _Txn(epoch))
        if txn.sealed:
            raise RuntimeError(f"epoch {epoch} transaction already sealed")
        tr = get_tracer()
        with tr.span("block.sink.shard") as sp:
            for sub, n in enumerate(counts):
                # a copy, so that a shard of a few rows does not keep
                # the whole read-back alive until its commit
                txn.shards.setdefault(sub, []).append(
                    rows[sub, :, :n].T.copy())
            total = int(counts.sum())
            sp.set(rows=total)
        tr.count("sink.rows", total)

    def seal(self, epoch: int) -> None:
        """Epoch fence: the transaction stops accepting records
        (pre-commit; reference preCommit on snapshot)."""
        txn = self._pending.setdefault(epoch, _Txn(epoch))
        txn.sealed = True
        if self.pre_committer is not None:
            self.pre_committer(epoch, self._merged_shards(txn))

    @staticmethod
    def _merged_shards(txn: _Txn) -> Dict[int, np.ndarray]:
        return {s: (np.concatenate(txn.shards[s], axis=0)
                    if txn.shards[s] else np.zeros((0, 3), np.int32))
                for s in sorted(txn.shards)}

    # --- commit / abort ------------------------------------------------------

    def commit(self, epoch: int) -> None:
        """Checkpoint complete: externalize every sealed transaction up to
        ``epoch``, subtask-major within an epoch (commits are ordered;
        reference commit on notifyCheckpointComplete)."""
        tr = get_tracer()
        for e in sorted(self._pending):
            if e > epoch:
                break
            # The span's end is the commit instant, seen from inside.
            with tr.span("txn.commit", epoch=e) as sp:
                txn = self._pending.pop(e)
                parts = [np.concatenate(txn.shards[s], axis=0)
                         for s in sorted(txn.shards) if txn.shards[s]]
                recs = (np.concatenate(parts, axis=0) if parts
                        else np.zeros((0, 3), np.int32))
                self.committed.append((e, recs))
                if self.committer is not None:
                    self.committer(e, recs)
                sp.set(rows=int(recs.shape[0]))
            tr.count("txn.rows_committed", int(recs.shape[0]))

    def drop_uncommitted_shards(self, sub: int) -> List[int]:
        """Sink-subtask failure: its pending shards lived with the task
        and are lost; recovery rebuilds them from replayed outputs."""
        lost = []
        for e, txn in self._pending.items():
            if sub in txn.shards:
                del txn.shards[sub]
                lost.append(e)
        return sorted(lost)

    def rebuild_shard(self, epoch: int, sub: int,
                      records: np.ndarray) -> None:
        """Install a replay-reconstructed shard for (epoch, subtask) —
        and re-persist its pending part if the epoch already sealed (the
        replayed bytes are bit-identical; the overwrite is the abort +
        regenerate of the reference's recoverAndAbort)."""
        txn = self._pending.setdefault(epoch, _Txn(epoch))
        txn.shards[sub] = [records]
        if txn.sealed and self.pre_committer is not None:
            self.pre_committer(epoch, {sub: np.asarray(records, np.int32)})

    # --- introspection -------------------------------------------------------

    def pending_shards(self, epoch: int) -> Dict[int, np.ndarray]:
        """One epoch's accumulated per-subtask ``[n, 3]`` records (the
        merged view :meth:`seal` pre-commits) — empty when the epoch has
        no pending transaction. Read-only: the lineage plane scans this
        at the fence for dyed sink termini."""
        txn = self._pending.get(epoch)
        return self._merged_shards(txn) if txn is not None else {}

    def committed_stream(self) -> np.ndarray:
        """All committed records in commit order — what the external
        consumer has observed."""
        if not self.committed:
            return np.zeros((0, 3), np.int32)
        return np.concatenate([r for _, r in self.committed], axis=0)

    def pending_epochs(self) -> List[int]:
        return sorted(self._pending)
