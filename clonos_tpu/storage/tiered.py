"""TieredEpochStore: host staging buffer + immutable disk segments.

One store owns the spilled sealed epochs of ONE log (an in-flight ring
vertex or the stacked determinant logs). Tiers and movement:

- **host tier**: the staging buffer. ``put`` accepts device arrays and
  returns immediately — the device→host copy (``np.asarray``) runs on
  the background writer thread, overlapped with the next epoch's
  compute. Sealed epochs are immutable, so the staged copy is final.
- **disk tier**: one checksummed segment file per epoch (storage/
  segment.py) plus a JSONL segment index under the shared torn-tail
  convention (utils/jsonl.py) — a SIGKILLed writer leaves at most one
  torn index line, which the reader drops; the segment it described is
  simply re-spilled or already covered by the host tier. Once a
  segment is durable, host copies beyond ``host_budget_epochs`` demote
  to disk-only (the budget bounds host DRAM like the ring bounds HBM).
- **refill**: ``load_epoch`` serves host hits without I/O; disk hits
  re-hash the segment against the indexed checksum and refuse torn
  bytes loudly (:class:`SegmentCorruptError` → recovery surfaces a
  labeled error instead of replaying garbage).

The writer is double-buffered by construction: the bounded queue lets
the fence stage epoch N+1 while the thread is still flushing epoch N;
``drain`` joins the queue for tests/shutdown. Spill and refill time is
attributed to the profiler's ``ft`` sections (``spill-write``,
``refill``) so an ablation run prices the tiers, and the bandwidth
counters feed the ``spill.*`` gauges ``clonos_tpu top`` renders.

Audit composition: sealed epochs are already digest-chained into the
audit ledger at the fence (obs/audit.py). ``attach_digest`` records the
ledger digest in the segment index, so a spilled epoch carries the same
fingerprint the ledger pinned — ``diff_ledgers`` verifies spill/refill
round-trips for free.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from clonos_tpu.storage.segment import read_segment, write_segment
from clonos_tpu.utils.jsonl import read_jsonl


class StorageError(RuntimeError):
    """Tiered-store failure a caller must not paper over (missing
    epoch, corrupt segment, unusable index)."""


class _Epoch:
    """One sealed epoch's residency record across the tiers."""

    __slots__ = ("start", "arrays", "path", "nbytes", "checksum",
                 "digest", "host_bytes")

    def __init__(self, start: int, arrays: Optional[Dict[str, Any]]):
        self.start = int(start)
        self.arrays = arrays          # host/device copy (None = disk-only)
        self.path: Optional[str] = None
        self.nbytes = 0               # serialized segment payload bytes
        self.checksum: Optional[str] = None
        self.digest: Optional[str] = None   # audit-ledger digest
        self.host_bytes = 0


def _arrays_nbytes(arrays: Mapping[str, Any]) -> int:
    total = 0
    for v in arrays.values():
        nb = getattr(v, "nbytes", None)
        if nb is None:
            v = np.asarray(v)
            nb = v.nbytes
        total += int(nb)
    return total


class TieredEpochStore:
    """Host-buffer + disk-segment owner of one log's spilled epochs."""

    def __init__(self, spool_dir: Optional[str], name: str,
                 durable: bool = True,
                 host_budget_epochs: Optional[int] = 2):
        self.name = name
        self.spool_dir = spool_dir
        self.durable = durable and spool_dir is not None
        self.host_budget_epochs = host_budget_epochs
        #: chaos hook (soak `stall` fault): per-segment-write sleep
        self.write_delay_s = 0.0
        if spool_dir:
            os.makedirs(spool_dir, exist_ok=True)
        self._epochs: Dict[int, _Epoch] = {}
        self._lock = threading.Lock()
        # Bandwidth/occupancy counters (spill.* gauges).
        self.bytes_spilled = 0
        self.bytes_refilled = 0
        self.spill_seconds = 0.0
        self.refill_seconds = 0.0
        self.segments_written = 0
        self.host_hits = 0
        self.disk_hits = 0
        self._writer_queue: "queue.Queue" = queue.Queue()
        self._writer = threading.Thread(target=self._writer_loop,
                                        daemon=True)
        self._writer.start()

    # --- paths ---------------------------------------------------------------

    def segment_path(self, epoch: int) -> str:
        return os.path.join(self.spool_dir, f"{self.name}_epoch{epoch}.seg")

    def index_path(self) -> str:
        return os.path.join(self.spool_dir, f"{self.name}.index.jsonl")

    def _label(self, epoch: int) -> str:
        return f"{self.name}:epoch{epoch}"

    # --- hot-path API --------------------------------------------------------

    def put(self, epoch: int, start: int,
            arrays: Mapping[str, Any]) -> None:
        """Accept one sealed epoch into the host tier and schedule its
        segment write. ``arrays`` may be device arrays — the d2h copy
        happens on the writer thread, off the critical path."""
        ep = _Epoch(start, dict(arrays))
        ep.host_bytes = _arrays_nbytes(ep.arrays)
        with self._lock:
            self._epochs[epoch] = ep
        if self.durable:
            self._writer_queue.put(("write", epoch))

    def attach_digest(self, epoch: int, digest: str) -> None:
        """Record the audit ledger's digest for a spilled epoch; the
        index entry lands via the writer thread (no fence-path I/O)."""
        with self._lock:
            ep = self._epochs.get(epoch)
            if ep is None:
                return
            ep.digest = digest
        if self.durable:
            self._writer_queue.put(("digest", epoch))

    def truncate(self, through_epoch: int) -> None:
        """Checkpoint complete: drop epochs <= ``through_epoch`` from
        every tier. Already-durable segments unlink synchronously (the
        checkpoint owns the data now; callers observe the files gone);
        epochs whose writes are still queued are handled by the queued
        truncate command — the writer re-checks residency before
        writing, and the command, ordered after every pending write,
        sweeps any segment that slipped through the check."""
        with self._lock:
            dead = [e for e in self._epochs if e <= through_epoch]
            paths = [self._epochs[e].path for e in dead
                     if self._epochs[e].path is not None]
            for e in dead:
                del self._epochs[e]
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass
        if self.durable and dead:
            self._writer_queue.put(("truncate", through_epoch))

    def retained_epochs(self) -> List[int]:
        with self._lock:
            return sorted(self._epochs)

    def epoch_digest(self, epoch: int) -> Optional[str]:
        with self._lock:
            ep = self._epochs.get(epoch)
            return ep.digest if ep is not None else None

    # --- refill --------------------------------------------------------------

    def load_epoch(self, epoch: int) -> Tuple[int, Dict[str, np.ndarray]]:
        """One epoch back from whichever tier holds it: host tier is a
        lock-held dict read; disk tier re-hashes the segment against
        the indexed checksum before trusting a byte."""
        with self._lock:
            ep = self._epochs.get(epoch)
            if ep is None:
                raise StorageError(
                    f"{self._label(epoch)}: epoch not retained by any "
                    f"tier (truncated or never spilled)")
            if ep.arrays is not None:
                self.host_hits += 1
                return ep.start, {k: np.asarray(v)
                                  for k, v in ep.arrays.items()}
            path, checksum = ep.path, ep.checksum
        if path is None:
            raise StorageError(
                f"{self._label(epoch)}: epoch resident in no tier "
                f"(host copy dropped before its segment was durable)")
        t0 = time.monotonic()
        start, arrays = read_segment(path, checksum, self._label(epoch))
        dur = time.monotonic() - t0
        with self._lock:
            self.disk_hits += 1
            self.refill_seconds += dur
            self.bytes_refilled += sum(a.nbytes for a in arrays.values())
        from clonos_tpu.obs import get_profiler
        get_profiler().observe("refill", dur)
        return start, arrays

    # --- background writer ---------------------------------------------------

    def _writer_loop(self) -> None:
        while True:
            item = self._writer_queue.get()
            try:
                if item is None:
                    return
                kind, arg = item
                if kind == "write":
                    self._write_one(arg)
                elif kind == "digest":
                    self._index_digest(arg)
                elif kind == "truncate":
                    self._truncate_disk(arg)
            except Exception:
                # The thread must survive a poisoned command: its death
                # would deadlock every future drain() and silently stop
                # all spilling. The epoch keeps its host copy (put()
                # staged it), so replay still works; durability for
                # THIS epoch is lost, which load_epoch reports if the
                # host copy is ever dropped.
                pass
            finally:
                self._writer_queue.task_done()

    def _write_one(self, epoch: int) -> None:
        with self._lock:
            ep = self._epochs.get(epoch)
            if ep is None or ep.arrays is None:
                return                 # truncated while queued
            staged = ep.arrays
            digest = ep.digest
        # d2h materialization + serialization off the critical path.
        arrays = {k: np.asarray(v) for k, v in staged.items()}
        if self.write_delay_s:
            time.sleep(self.write_delay_s)      # chaos `stall` fault
        t0 = time.monotonic()
        try:
            nbytes, checksum = write_segment(
                self.segment_path(epoch), ep.start, arrays)
            self._index_append({
                "kind": "segment", "epoch": epoch, "start": ep.start,
                "file": os.path.basename(self.segment_path(epoch)),
                "blake2b": checksum, "bytes": nbytes,
                "digest": digest,
            })
        except OSError:
            # Flush failure: keep the host copy so replay still works
            # (the reference keeps the buffer on flush failure) — but
            # materialized, so the device buffer is released either way.
            with self._lock:
                if epoch in self._epochs:
                    self._epochs[epoch].arrays = arrays
            return
        dur = time.monotonic() - t0
        with self._lock:
            cur = self._epochs.get(epoch)
            if cur is not None:
                cur.arrays = arrays     # host tier now holds np copies
                cur.path = self.segment_path(epoch)
                cur.nbytes = nbytes
                cur.checksum = checksum
            self.segments_written += 1
            self.bytes_spilled += nbytes
            self.spill_seconds += dur
            self._enforce_host_budget_locked()
        from clonos_tpu.obs import get_profiler
        get_profiler().observe("spill-write", dur)

    def _enforce_host_budget_locked(self) -> None:
        """Demote durable host copies beyond the budget to disk-only
        (oldest epochs first — refill wants the newest near)."""
        if self.host_budget_epochs is None:
            return
        resident = sorted(e for e, ep in self._epochs.items()
                          if ep.arrays is not None and ep.path is not None)
        excess = len(resident) - self.host_budget_epochs
        for e in resident[:max(excess, 0)]:
            self._epochs[e].arrays = None

    def _index_digest(self, epoch: int) -> None:
        with self._lock:
            ep = self._epochs.get(epoch)
            if ep is None or ep.path is None:
                return                 # write pending: digest rides it
            digest = ep.digest
        try:
            self._index_append({"kind": "digest", "epoch": epoch,
                                "digest": digest})
        except OSError:
            pass

    def _truncate_disk(self, through_epoch: int) -> None:
        for fn in list(os.listdir(self.spool_dir)):
            if not (fn.startswith(f"{self.name}_epoch")
                    and fn.endswith(".seg")):
                continue
            try:
                e = int(fn[len(f"{self.name}_epoch"):-len(".seg")])
            except ValueError:
                continue
            if e <= through_epoch:
                try:
                    os.remove(os.path.join(self.spool_dir, fn))
                except OSError:
                    pass
        # Record the truncation unconditionally: some segments were
        # already unlinked synchronously by truncate(), and open_index
        # must not resurrect their index entries.
        try:
            self._index_append({"kind": "truncate",
                                "through": through_epoch})
        except OSError:
            pass

    def _index_append(self, record: dict) -> None:
        import json
        line = json.dumps(record, sort_keys=True,
                          separators=(",", ":")) + "\n"
        with open(self.index_path(), "a") as f:
            f.write(line)
            f.flush()

    # --- occupancy / lifecycle -----------------------------------------------

    def occupancy(self) -> Dict[str, int]:
        """Tier residency right now (the spill.* occupancy gauges)."""
        with self._lock:
            host_e = sum(1 for ep in self._epochs.values()
                         if ep.arrays is not None)
            host_b = sum(ep.host_bytes for ep in self._epochs.values()
                         if ep.arrays is not None)
            disk_e = sum(1 for ep in self._epochs.values()
                         if ep.path is not None)
            disk_b = sum(ep.nbytes for ep in self._epochs.values()
                         if ep.path is not None)
        return {"host_epochs": host_e, "host_bytes": host_b,
                "disk_epochs": disk_e, "disk_bytes": disk_b}

    def stats(self) -> Dict[str, Any]:
        """Cumulative movement counters."""
        with self._lock:
            return {
                "bytes_spilled": self.bytes_spilled,
                "bytes_refilled": self.bytes_refilled,
                "spill_seconds": round(self.spill_seconds, 6),
                "refill_seconds": round(self.refill_seconds, 6),
                "segments_written": self.segments_written,
                "host_hits": self.host_hits,
                "disk_hits": self.disk_hits,
            }

    def drain(self) -> None:
        """Block until every queued spill/index write is durable."""
        self._writer_queue.join()

    def close(self) -> None:
        self._writer_queue.put(None)

    # --- fresh-process refill ------------------------------------------------

    @classmethod
    def open_index(cls, spool_dir: str, name: str) -> "TieredEpochStore":
        """Rebuild a store's disk tier from its segment index in a fresh
        process (standby-host refill): replay the index records in
        order — tail-tolerantly, so a SIGKILLed writer's torn final line
        drops silently while earlier corruption raises the labeled
        error (utils/jsonl.py convention)."""
        store = cls(spool_dir, name)
        label = f"{name}-index"
        records = read_jsonl(store.index_path(), label=label)
        with store._lock:
            for rec in records:
                kind = rec.get("kind")
                if kind == "segment":
                    e = int(rec["epoch"])
                    ep = _Epoch(int(rec["start"]), None)
                    ep.path = os.path.join(spool_dir, rec["file"])
                    ep.checksum = rec.get("blake2b")
                    ep.nbytes = int(rec.get("bytes", 0))
                    ep.digest = rec.get("digest")
                    store._epochs[e] = ep
                elif kind == "digest":
                    ep = store._epochs.get(int(rec["epoch"]))
                    if ep is not None:
                        ep.digest = rec.get("digest")
                elif kind == "truncate":
                    thr = int(rec["through"])
                    for e in [e for e in store._epochs if e <= thr]:
                        del store._epochs[e]
        return store
