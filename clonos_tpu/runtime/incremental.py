"""Incremental checkpoints: device-diffed, chunk-granular snapshot storage.

Capability analog of the reference's incremental state backend
(flink-state-backends RocksDBKeyedStateBackend.java:145 — only SST files
new since the last checkpoint upload). The TPU-first form diffs on the
*device*: the snapshotter keeps the previous completed snapshot's leaves
as a device-side shadow (jax arrays are immutable, so holding references
is free), and one jitted program per leaf shape

- chunks the flat leaf,
- flags chunks that changed since the shadow,
- compacts the changed chunk ids + payloads into a fixed budget
  (``jnp.nonzero(..., size=M)`` keeps shapes static for XLA),

so only the changed chunks ever cross the host link. Leaves
whose change count exceeds the budget ship whole (per-leaf, not
all-or-nothing); a chain of deltas is anchored by periodic full
snapshots, and deletion keeps a base alive until nothing retained
depends on it (the reference's shared-state registry, subsumed-
checkpoint disposal).
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.runtime.checkpoint import (CheckpointStorage,
                                           CompletedCheckpoint,
                                           carry_to_host)


@dataclasses.dataclass
class LeafDelta:
    """Changed chunks of one flattened leaf since the previous snapshot."""

    chunk_ids: np.ndarray      # int32 [m] (m <= budget), ids < num_chunks
    chunks: np.ndarray         # [m, chunk_elems] in the leaf's dtype


#: per-leaf entry in a delta snapshot: LeafDelta, or the full leaf array
#: (budget exceeded / shape changed), or None (bit-identical leaf).
LeafEntry = Any


class DeviceDiffSnapshotter:
    """Computes per-leaf chunk deltas against a device-side shadow."""

    def __init__(self, chunk_elems: int = 1024, budget_frac: float = 0.5):
        self.chunk_elems = chunk_elems
        self.budget_frac = budget_frac
        self._shadow: Optional[List[jax.Array]] = None
        self._treedef = None
        self._jit: Dict[Tuple, Any] = {}

    def _diff_fn(self, n: int, dtype, chunk: int, m: int):
        key = (n, np.dtype(dtype).str, chunk, m)
        fn = self._jit.get(key)
        if fn is None:
            c = -(-n // chunk)
            pad = c * chunk - n

            def f(new, old):
                a = jnp.pad(new.reshape(-1), (0, pad)).reshape(c, chunk)
                b = jnp.pad(old.reshape(-1), (0, pad)).reshape(c, chunk)
                changed = jnp.any(a != b, axis=1)
                ids = jnp.nonzero(changed, size=m, fill_value=c)[0]
                data = a[jnp.clip(ids, 0, c - 1)]
                return (ids.astype(jnp.int32), data,
                        changed.sum().astype(jnp.int32))
            fn = self._jit[key] = jax.jit(f)
        return fn

    def advance_shadow(self, snap) -> None:
        """Adopt ``snap`` as the diff base without computing a delta
        (used when the caller decided on a full snapshot anyway — the
        diff programs and their d2h would be wasted work)."""
        self._shadow, self._treedef = jax.tree_util.tree_flatten(snap)

    def snapshot(self, snap) -> Tuple[str, Any]:
        """Returns ("full", host_pytree) or ("delta", [LeafEntry...]).
        Updates the shadow to ``snap`` either way."""
        leaves, treedef = jax.tree_util.tree_flatten(snap)
        prev, self._shadow, ptd = self._shadow, leaves, self._treedef
        self._treedef = treedef
        if prev is None or ptd != treedef or len(prev) != len(leaves):
            return "full", carry_to_host(snap)
        entries: List[LeafEntry] = []
        for new, old in zip(leaves, prev):
            new = jnp.asarray(new)
            if new.shape != old.shape or new.dtype != old.dtype:
                entries.append(np.asarray(new))
                continue
            n = int(new.size)
            if n == 0:
                entries.append(None)
                continue
            chunk = min(self.chunk_elems, n)
            c = -(-n // chunk)
            m = max(1, int(c * self.budget_frac))
            ids, data, nch = self._diff_fn(n, new.dtype, chunk, m)(new, old)
            nch = int(nch)
            if nch == 0:
                entries.append(None)
            elif nch > m:
                entries.append(np.asarray(new))       # whole leaf ships
            else:
                # Slice on DEVICE first: only the nch changed chunks
                # cross the host link, not the whole budget.
                entries.append(LeafDelta(
                    chunk_ids=np.asarray(ids[:nch]),
                    chunks=np.asarray(data[:nch])))
        return "delta", entries

    @staticmethod
    def apply(base_host, entries: List[LeafEntry], chunk_elems: int):
        """Apply one delta's entries over a host snapshot (new pytree)."""
        leaves, treedef = jax.tree_util.tree_flatten(base_host)
        out = []
        for leaf, e in zip(leaves, entries):
            if e is None:
                out.append(leaf)
            elif isinstance(e, LeafDelta):
                n = leaf.size
                chunk = min(chunk_elems, max(n, 1))
                c = -(-n // chunk)
                flat = np.zeros((c * chunk,), leaf.dtype)
                flat[:n] = np.asarray(leaf).reshape(-1)
                ch = flat.reshape(c, chunk)
                ch[e.chunk_ids] = e.chunks
                out.append(ch.reshape(-1)[:n].reshape(leaf.shape))
            else:
                out.append(e)                         # whole-leaf payload
        return jax.tree_util.tree_unflatten(treedef, out)


class IncrementalCheckpointStorage(CheckpointStorage):
    """File-backed delta-chain storage: every ``base_every``-th write is a
    full snapshot; the rest persist only the device-diffed changed
    chunks. Reads reconstruct base + delta chain; deleting a checkpoint
    that later retained deltas still depend on defers the physical
    removal until the chain no longer needs it."""

    #: the snapshotter diffs device arrays itself — the coordinator must
    #: NOT pre-materialize the carry to host (that transfer is the cost
    #: this backend exists to avoid).
    wants_host = False

    def __init__(self, root: str, base_every: int = 8,
                 chunk_elems: int = 1024, budget_frac: float = 0.5):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.base_every = base_every
        self.chunk_elems = chunk_elems
        self._snap = DeviceDiffSnapshotter(chunk_elems, budget_frac)
        self._since_base = 0
        #: cid -> ("full", None) | ("delta", base_cid)
        self._index: Dict[int, Tuple[str, Optional[int]]] = {}
        #: cids logically deleted but physically retained for a chain
        self._zombie: set = set()
        self._order: List[int] = []     # write order (chain order)
        self._recover_index()

    def _recover_index(self) -> None:
        """Rebuild the chain index from disk (process restart over the
        same directory — FileCheckpointStorage scans the same way). Only
        each file's small meta header is read (the payload is a second
        pickle object, skipped), so startup I/O scales with the index,
        not total checkpoint bytes. Persisted tombstones re-mark logical
        deletions; files whose chain is broken (their base was removed)
        are unreadable and deleted so the directory can't grow
        unboundedly across runs."""
        found: Dict[int, Tuple[str, Optional[int]]] = {}
        for fn in os.listdir(self.root):
            if not (fn.startswith("inc_") and fn.endswith(".pkl")):
                continue
            try:
                meta = self._load_meta(int(fn[4:-4]))
                found[meta["checkpoint_id"]] = (meta["kind"], meta["base"])
            except Exception:
                continue

        def chain_ok(cid: int) -> bool:
            seen = set()
            while found[cid][0] == "delta":
                base = found[cid][1]
                if base not in found or base in seen:
                    return False
                seen.add(base)
                cid = base
            return True
        for cid in sorted(found):
            if chain_ok(cid):
                self._index[cid] = found[cid]
                self._order.append(cid)
            else:
                try:
                    os.remove(self._path(cid))
                except OSError:
                    pass
        try:
            with open(self._tomb_path()) as f:
                import json
                self._zombie = {c for c in json.load(f)
                                if c in self._index}
        except (OSError, ValueError):
            self._zombie = set()
        self._gc()

    def _path(self, cid: int) -> str:
        return os.path.join(self.root, f"inc_{cid}.pkl")

    def _tomb_path(self) -> str:
        return os.path.join(self.root, "tombstones.json")

    def _write_tombstones(self) -> None:
        import json
        tmp = self._tomb_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump(sorted(self._zombie), f)
        os.replace(tmp, self._tomb_path())

    def write(self, ckpt: CompletedCheckpoint) -> None:
        # A full snapshot every base_every-th write (deltas in between).
        force_full = (self._since_base + 1 >= self.base_every
                      or not self._order)
        # The diff shadow must only advance when the write is durable: a
        # failed write would otherwise leave the next delta diffed
        # against a checkpoint that was never persisted — silently
        # missing chunks from its chain.
        prev_shadow = self._snap._shadow
        prev_td = self._snap._treedef
        # Everything from shadow advance through the durable rename sits
        # under one rollback guard: an exception ANYWHERE (diff program,
        # d2h, disk full, interrupt) must leave the shadow at the last
        # PERSISTED checkpoint, or the next delta silently misses chunks.
        try:
            if force_full:
                # Don't pay the diff programs + budgeted d2h only to
                # discard them — advance the shadow, materialize once.
                self._snap.advance_shadow(ckpt.carry)
                kind, payload = "full", carry_to_host(ckpt.carry)
            else:
                kind, payload = self._snap.snapshot(ckpt.carry)
            base = self._order[-1] if kind == "delta" else None
            meta = {"checkpoint_id": ckpt.checkpoint_id, "kind": kind,
                    "base": base, "wall_time": ckpt.wall_time,
                    "chunk_elems": self.chunk_elems}
            tmp = self._path(ckpt.checkpoint_id) + ".tmp"
            with open(tmp, "wb") as f:
                # Object 1: small meta header (index recovery reads only
                # this). Object 2: the payload.
                pickle.dump(meta, f, protocol=pickle.HIGHEST_PROTOCOL)
                pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, self._path(ckpt.checkpoint_id))
        except BaseException:
            self._snap._shadow = prev_shadow
            self._snap._treedef = prev_td
            raise
        self._index[ckpt.checkpoint_id] = (kind, base)
        self._order.append(ckpt.checkpoint_id)
        self._since_base = 0 if kind == "full" else self._since_base + 1

    def _load_meta(self, cid: int) -> dict:
        with open(self._path(cid), "rb") as f:
            return pickle.load(f)

    def _load(self, cid: int) -> dict:
        with open(self._path(cid), "rb") as f:
            meta = pickle.load(f)
            meta["payload"] = pickle.load(f)
            return meta

    def _chain(self, cid: int) -> List[int]:
        """cids from the anchoring full snapshot to ``cid`` inclusive."""
        chain = [cid]
        while self._index[chain[0]][0] == "delta":
            chain.insert(0, self._index[chain[0]][1])
        return chain

    def read(self, checkpoint_id: int) -> CompletedCheckpoint:
        if checkpoint_id not in self._index or \
                checkpoint_id in self._zombie:
            raise KeyError(checkpoint_id)
        carry = None
        rec = None
        for cid in self._chain(checkpoint_id):
            rec = self._load(cid)
            if rec["kind"] == "full":
                carry = rec["payload"]
            else:
                carry = DeviceDiffSnapshotter.apply(
                    carry, rec["payload"], rec["chunk_elems"])
        host = carry
        size = int(sum(np.asarray(x).nbytes for x in
                       jax.tree_util.tree_leaves(host)))
        return CompletedCheckpoint(
            checkpoint_id=checkpoint_id, carry=host,
            wall_time=rec["wall_time"], size_bytes=size)

    def delete(self, checkpoint_id: int) -> None:
        if checkpoint_id not in self._index:
            return
        self._zombie.add(checkpoint_id)
        # Tombstones persist so a restart can't resurrect a logically
        # deleted checkpoint (and its file eventually GCs).
        self._write_tombstones()
        self._gc()

    def _gc(self) -> None:
        # A zombie is removable once no retained (non-zombie) checkpoint's
        # chain passes through it.
        needed: set = set()
        for cid in self._index:
            if cid not in self._zombie:
                needed.update(self._chain(cid))
        removed = False
        for cid in [z for z in self._zombie if z not in needed]:
            for p in (self._path(cid), self._path(cid) + ".done"):
                try:
                    os.remove(p)
                except OSError:
                    pass
            self._zombie.discard(cid)
            self._index.pop(cid, None)
            if cid in self._order:
                self._order.remove(cid)
            removed = True
        if removed:
            self._write_tombstones()

    def list_ids(self) -> List[int]:
        return sorted(c for c in self._index if c not in self._zombie)

    def delta_bytes_on_disk(self) -> Dict[int, int]:
        """Observability: per-checkpoint file size (full vs delta)."""
        return {cid: os.path.getsize(self._path(cid))
                for cid in self._index}
