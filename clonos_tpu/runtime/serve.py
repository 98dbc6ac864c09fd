"""Read-path scale-out: queryable state served from standby replicas.

The standby pool already restores every completed checkpoint
(StandbyPool, reference Execution.java:373 re-dispatching state to
STANDBY executions) and the audit plane already extracts each sealed
epoch's causal surface at the fence (``epoch_window``). This module
composes the two into a read tier — the fault-tolerance mechanism
itself becomes the scale-out mechanism, the same move Clonos makes for
recovery:

- :class:`ReadReplica` keeps a restored checkpoint **fence-fresh** by
  tailing sealed-epoch deltas off the runner's serve feed
  (``ClusterRunner.serve_feeds``): for operators that emit their
  updated running value per record (``emits_running_value``, the
  KeyedReduceOperator contract), the LAST emitted value per key in the
  epoch's deterministic (step, lane, slot) order IS the fence value of
  that key — so scattering the epoch's output-ring window into the
  dense table reconstructs the owner's fence state **bit-identically by
  construction**. Operators without that property fall back to
  checkpoint-only freshness (larger but still honest staleness).

- :class:`ReplicaServeEndpoint` coalesces concurrent point lookups into
  ONE jitted gather per device dispatch (the ``epoch_row_windows``
  idiom applied to serving): transport threads enqueue keys, a single
  dispatch thread drains the queue and issues one fused
  ``acc[owner_subtask(keys), keys]`` read for the whole batch instead
  of N host round-trips into the carry. The dispatch region is wrapped
  in serve-window markers and lint-enforced dispatch-only
  (lint/overlapwindow.py) — a stray host sync there re-serializes the
  exact batching win.

- :class:`ServeRouter` routes lookups by key-group across owner +
  replicas with per-replica staleness bounds; a replica past its bound,
  dead, or mid-revival is skipped in favor of the owner (a counted
  REROUTE, never a client-visible error). Every response carries
  ``(epoch, staleness_epochs)`` — reads are never torn mid-epoch
  because replicas only ever publish whole sealed-epoch states.

Consistency model: a replica at epoch ``e`` serves exactly the state
the owner had at fence ``e`` — same key-group assignment, same values
(asserted bit-for-bit in tests/test_serve_replica.py). Staleness is
``last_sealed_epoch - replica_epoch``; the router's bound is the
per-replica freshness SLO.
"""

from __future__ import annotations

import threading
import time as _time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from clonos_tpu.parallel import transport as tp
from clonos_tpu.runtime.query import (QueryRejectedError,
                                      QueryTimeoutError, _call_with_retry,
                                      owner_subtask_np)

#: padded gather bucket sizes — one compiled program per bucket, so a
#: mixed read load compiles O(log max_batch) programs, not one per
#: batch shape.
_MIN_BUCKET = 64


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


_gather_cache: Dict[Tuple[int, int], object] = {}


def _gather_fn(parallelism: int, num_key_groups: int):
    """ONE fused device read for a whole key batch: key -> key group ->
    owning subtask -> table entry, all inside a single jitted program
    (the device twin of :func:`owner_subtask_np` — same hash, same
    assignment, so replica reads agree with the exchange's routing
    byte-for-byte)."""
    key = (parallelism, num_key_groups)
    fn = _gather_cache.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        from clonos_tpu.parallel.routing import hash32

        def f(acc, keys):
            kg = (hash32(keys) % jnp.uint32(num_key_groups)
                  ).astype(jnp.int32)
            sub = (kg * parallelism) // num_key_groups
            return acc[sub, keys], sub, kg

        fn = jax.jit(f)
        _gather_cache[key] = fn
    return fn


class ReadReplica:
    """A standby's live read view of one vertex's dense keyed state.

    Restores from the standby pool's completed checkpoints and advances
    one sealed epoch at a time off the runner's serve feed. All feed
    callbacks are host-only (numpy) and lock-guarded — they run on the
    fence worker when the fence is pipelined."""

    def __init__(self, runner, vertex_id: int, state: str = "acc",
                 name: str = "replica-0"):
        self.runner = runner
        self.vertex_id = int(vertex_id)
        self.state_name = state
        self.name = name
        v = runner.job.vertices[self.vertex_id]
        self.parallelism = v.parallelism
        self.num_key_groups = runner.job.num_key_groups
        #: the operator's running-value contract is what makes
        #: output-ring tailing bit-exact; without it the replica is
        #: checkpoint-fresh only (honest, larger staleness).
        self.tailable = bool(getattr(v.operator, "emits_running_value",
                                     False))
        self._lock = threading.Lock()
        self._arr: Optional[np.ndarray] = None     # host [P, K]
        self._epoch = -1                           # fence the view is at
        self._owner_of: Optional[np.ndarray] = None
        self.alive = True
        self.applied_epochs = 0
        self.restores = 0
        self.revivals = 0
        #: device-side cache for the serve endpoint's fused gather —
        #: touched ONLY by the endpoint's single dispatch thread.
        self._dev = None
        self._dev_epoch = -1
        runner.serve_feeds.append(self._on_seal)
        runner.coordinator.subscribe_completed_state(self._on_checkpoint)
        ck = runner.standbys.latest
        if ck is not None:
            self._on_checkpoint(ck)

    # --- state plane (runner-side callbacks) -----------------------------

    def _table_from(self, ckpt) -> Optional[np.ndarray]:
        st = ckpt.carry.op_states[self.vertex_id]
        if not isinstance(st, dict) or self.state_name not in st:
            return None
        arr = np.array(st[self.state_name])
        if arr.ndim < 2 or arr.shape[0] != self.parallelism:
            return None
        return arr

    def _on_checkpoint(self, ckpt) -> None:
        """Standby restore path: adopt any completed checkpoint that is
        FRESHER than the current view (checkpoint id == the epoch it
        fences). For tailable operators the delta feed usually got
        there first and this is a no-op."""
        with self._lock:
            if not self.alive or ckpt.checkpoint_id <= self._epoch:
                return
            arr = self._table_from(ckpt)
            if arr is None:
                return
            self._adopt(arr, int(ckpt.checkpoint_id))
            self.restores += 1

    def _adopt(self, arr: np.ndarray, epoch: int) -> None:
        self._arr = arr
        self._epoch = epoch
        if self._owner_of is None or len(self._owner_of) != arr.shape[-1]:
            _, self._owner_of = owner_subtask_np(
                np.arange(arr.shape[-1]), self.parallelism,
                self.num_key_groups)

    def _on_seal(self, epoch: int, window) -> None:
        """Serve-feed tail: apply one sealed epoch's output-ring window.
        Contiguity rule: deltas only ever advance ``e-1 -> e``; any gap
        (late attach, revival) waits for the checkpoint path to close
        it — staleness stays OBSERVABLE rather than silently wrong."""
        with self._lock:
            if not self.alive:
                # Revival within one fence of the kill: re-adopt the
                # standby pool's restore point; the staleness spike is
                # (sealed - checkpoint) until completions catch up.
                ck = self.runner.standbys.latest
                if ck is None:
                    return
                arr = self._table_from(ck)
                if arr is None:
                    return
                self.alive = True
                self.revivals += 1
                self._epoch = -1
                self._adopt(arr, int(ck.checkpoint_id))
                self.restores += 1
            if (not self.tailable or self._arr is None
                    or self._epoch != epoch - 1 or window is None):
                return
            steps = window.get("rings", {}).get(self.vertex_id)
            if steps is None:
                return
            self._apply_running_values(steps)
            self._epoch = epoch
            self.applied_epochs += 1

    def _apply_running_values(self, steps) -> None:
        """Last-write-wins scatter of one epoch's emitted running values
        into the dense table: each valid record carries its key's value
        AFTER that record folded in, and the window's steps are in
        deterministic order — so the last record per key is exactly the
        owner's fence value for that key."""
        ks = [np.asarray(k, np.int64) for k, _, _ in steps if len(k)]
        vs = [np.asarray(v) for k, v, _ in steps if len(k)]
        if not ks:
            return
        keys = np.concatenate(ks)
        vals = np.concatenate(vs)
        # np.unique returns FIRST occurrences; reverse so "first in
        # reversed" == "last overall".
        rk, rv = keys[::-1], vals[::-1]
        uk, first = np.unique(rk, return_index=True)
        self._arr[self._owner_of[uk], uk] = rv[first]

    # --- serve plane -----------------------------------------------------

    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    def staleness_epochs(self) -> int:
        """How many fences behind the owner's last seal this view is
        (0 = fence-fresh; grows while dead or gapped)."""
        with self._lock:
            sealed = int(self.runner.last_sealed_epoch)
            if self._epoch < 0:
                return sealed + 1
            return max(0, sealed - self._epoch)

    def status(self) -> dict:
        with self._lock:
            sealed = int(self.runner.last_sealed_epoch)
            stal = (sealed + 1 if self._epoch < 0
                    else max(0, sealed - self._epoch))
            return {"epoch": self._epoch, "staleness_epochs": stal,
                    "alive": self.alive, "role": "replica",
                    "name": self.name, "tailable": self.tailable,
                    "applied_epochs": self.applied_epochs,
                    "restores": self.restores}

    def host_view(self) -> Tuple[Optional[np.ndarray], int]:
        """(table copy reference, epoch) under the lock — the table is
        mutated in place by the tail, so the device cache keys on the
        epoch stamp and re-uploads only when it moved."""
        with self._lock:
            if not self.alive or self._arr is None:
                return None, self._epoch
            return self._arr.copy(), self._epoch

    def device_view(self):
        """Device-resident table for the fused gather, cached per epoch
        stamp. Called only from the endpoint's single dispatch thread —
        the one thread allowed to touch the device on the serve path."""
        import jax.numpy as jnp
        arr, epoch = self.host_view()
        if arr is None:
            return None, epoch
        if epoch != self._dev_epoch or self._dev is None:
            dev = jnp.asarray(arr)
            with self._lock:
                self._dev = dev
                self._dev_epoch = epoch
            return dev, epoch
        return self._dev, epoch

    def kill(self) -> None:
        """Chaos surface (``replica-kill``): the replica stops serving
        and drops its view; the router must re-route to the owner with
        zero client-visible errors. Revives at the next seal. The epoch
        stamp resets too — a dead replica has NO view, so its staleness
        is ``sealed + 1`` (behind every fence), the spike the soak's
        degradation witness measures until revival recovers it."""
        with self._lock:
            self.alive = False
            self._arr = None
            self._epoch = -1
            self._dev = None
            self._dev_epoch = -1

    def rehome(self, new_runner) -> None:
        """Live re-cut (``ClusterRunner.rescale_live``): re-attach this
        replica to the NEW incarnation. The key-group->subtask owner map
        changes with the vertex's parallelism, so the old view's
        table SHAPE is wrong — drop it and re-adopt from the new
        runner's restore point (rescale_live re-fences the handoff
        checkpoint in the new shape, so one is always there). During
        the window between re-home and re-adopt the replica reads as
        dead: the router REROUTES to the owner, clients see staleness,
        never errors."""
        with self._lock:
            try:
                self.runner.serve_feeds.remove(self._on_seal)
            except ValueError:
                pass
            self.runner = new_runner
            v = new_runner.job.vertices[self.vertex_id]
            self.parallelism = v.parallelism
            self.num_key_groups = new_runner.job.num_key_groups
            self.tailable = bool(getattr(v.operator,
                                         "emits_running_value", False))
            self.alive = True
            self._arr = None
            self._epoch = -1
            self._owner_of = None
            self._dev = None
            self._dev_epoch = -1
        new_runner.serve_feeds.append(self._on_seal)
        new_runner.coordinator.subscribe_completed_state(
            self._on_checkpoint)
        ck = new_runner.standbys.latest
        if ck is not None:
            self._on_checkpoint(ck)

    def close(self) -> None:
        with self._lock:
            try:
                self.runner.serve_feeds.remove(self._on_seal)
            except ValueError:
                pass


class ReplicaServeEndpoint:
    """Serves a :class:`ReadReplica` over the control transport with
    request coalescing: transport threads enqueue keys and block on a
    ticket; a single dispatch thread drains the whole queue into ONE
    padded, jitted gather per device dispatch."""

    def __init__(self, replica: ReadReplica, host: str = "127.0.0.1",
                 port: int = 0, max_batch: int = 4096):
        self.replica = replica
        self.max_batch = int(max_batch)
        self._cv = threading.Condition()
        self._pending: deque = deque()
        self._closed = False
        #: observability: device dispatches vs keys served — the
        #: coalescing ratio the batching win is made of.
        self.dispatches = 0
        self.keys_served = 0
        self.requests = 0
        self._thread = threading.Thread(
            target=self._serve_loop, name=f"serve-{replica.name}",
            daemon=True)
        self._thread.start()
        self.server = tp.ControlServer(self._handle, host, port)
        self.address = self.server.address

    # --- transport side --------------------------------------------------

    def _handle(self, mtype: int, payload: bytes) -> Tuple[int, bytes]:
        if mtype == tp.SERVE_STATUS:
            st = self.replica.status()
            st["dispatches"] = self.dispatches
            st["keys_served"] = self.keys_served
            return tp.QUERY_RESPONSE, tp.pack_json(st)
        if mtype not in (tp.QUERY_STATE, tp.QUERY_BATCH):
            return tp.ERROR, tp.pack_json({"error": f"bad mtype {mtype}"})
        req = tp.unpack_json(payload)
        tp.adopt_hlc(req, verb="QUERY_STATE" if mtype == tp.QUERY_STATE
                     else "QUERY_BATCH")
        if req["vertex"] != self.replica.vertex_id or \
                req.get("state", "acc") != self.replica.state_name:
            return tp.ERROR, tp.pack_json(
                {"error": f"replica serves (vertex "
                          f"{self.replica.vertex_id}, "
                          f"{self.replica.state_name!r}) only"})
        single = mtype == tp.QUERY_STATE
        keys = np.asarray([req["key"]] if single else req["keys"],
                          np.int64)
        ticket = {"keys": keys, "event": threading.Event(),
                  "out": None, "err": None}
        with self._cv:
            if self._closed:
                return tp.ERROR, tp.pack_json(
                    {"error": "endpoint closed", "rejected": True})
            self._pending.append(ticket)
            self.requests += 1
            self._cv.notify()
        ticket["event"].wait()
        if ticket["err"] is not None:
            return tp.ERROR, tp.pack_json(ticket["err"])
        vals, subs, kgs, epoch, stal = ticket["out"]
        # ``replica`` + ``epoch`` are the provenance stamp a lineage
        # path terminates on (the router adds ``rerouted``).
        if single:
            return tp.QUERY_RESPONSE, tp.pack_json(
                {"value": vals[0], "subtask": subs[0],
                 "key_group": kgs[0], "epoch": epoch,
                 "staleness_epochs": stal, "served_by":
                 self.replica.name, "replica": self.replica.name})
        return tp.QUERY_BATCH_RESPONSE, tp.pack_json(
            {"values": vals, "subtasks": subs, "key_groups": kgs,
             "epoch": epoch, "staleness_epochs": stal,
             "served_by": self.replica.name,
             "replica": self.replica.name})

    # --- the single dispatch thread --------------------------------------

    def _serve_loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    self._cv.wait()
                if self._closed and not self._pending:
                    return
                batch: List[dict] = []
                n = 0
                while self._pending and n < self.max_batch:
                    t = self._pending.popleft()
                    batch.append(t)
                    n += len(t["keys"])
            try:
                self._dispatch(batch)
            except BaseException as e:   # keep the loop alive; fail the batch
                for t in batch:
                    if not t["event"].is_set():
                        t["err"] = {"error": f"serve dispatch failed: {e}",
                                    "rejected": True}
                        t["event"].set()

    def _dispatch(self, batch: List[dict]) -> None:
        import jax.numpy as jnp
        r = self.replica
        arr_dev, epoch = r.device_view()
        if arr_dev is None:
            why = ("replica dead" if not r.alive
                   else "replica has no restored state yet")
            for t in batch:
                t["err"] = {"error": why, "rejected": True}
                t["event"].set()
            return
        num_keys = arr_dev.shape[-1]
        all_keys = np.concatenate([t["keys"] for t in batch])
        if all_keys.min() < 0 or all_keys.max() >= num_keys:
            for t in batch:
                bad = (t["keys"].min() < 0
                       or t["keys"].max() >= num_keys)
                if bad:
                    t["err"] = {"error": f"key out of range "
                                         f"[0, {num_keys})"}
                    t["event"].set()
            batch = [t for t in batch if not t["event"].is_set()]
            if not batch:
                return
            all_keys = np.concatenate([t["keys"] for t in batch])
        n = len(all_keys)
        b = _bucket(n)
        padded = np.zeros(b, np.int32)
        padded[:n] = all_keys
        fn = _gather_fn(r.parallelism, r.num_key_groups)
        keys_dev = jnp.asarray(padded)
        # clonos: serve-window-begin
        vals_d, subs_d, kgs_d = fn(arr_dev, keys_dev)
        # clonos: serve-window-end
        # The drain happens OUTSIDE the marked window: the window is the
        # dispatch-only region (one fused gather for the whole coalesced
        # batch); blocking host reads belong here, after it.
        vals = np.asarray(vals_d)[:n].tolist()
        subs = np.asarray(subs_d)[:n].tolist()
        kgs = np.asarray(kgs_d)[:n].tolist()
        stal = r.staleness_epochs()
        self.dispatches += 1
        self.keys_served += n
        off = 0
        for t in batch:
            m = len(t["keys"])
            t["out"] = (vals[off:off + m], subs[off:off + m],
                        kgs[off:off + m], epoch, stal)
            off += m
            t["event"].set()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self.server.close()
        self._thread.join(timeout=5.0)


class ReplicaStateClient:
    """Client for a :class:`ReplicaServeEndpoint` (same wire protocol
    as QueryableStateClient, same timeout/backoff discipline). One
    connection, NOT thread-safe: concurrent readers hold one client
    each — the endpoint coalesces across connections, the socket does
    not."""

    def __init__(self, address, timeout_s: float = 5.0,
                 retries: int = 2, backoff_s: float = 0.05):
        self.address = tuple(address)
        self.timeout_s = float(timeout_s)
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self._client = tp.ControlClient(self.address,
                                        timeout_s=self.timeout_s)

    def _call(self, mtype: int, payload: dict) -> dict:
        if mtype in (tp.QUERY_STATE, tp.QUERY_BATCH):
            tp.attach_hlc(payload,
                          verb="QUERY_STATE" if mtype == tp.QUERY_STATE
                          else "QUERY_BATCH")
        rt, resp = _call_with_retry(
            self._client, mtype, tp.pack_json(payload), self.address,
            self.timeout_s, self.retries, self.backoff_s)
        out = tp.unpack_json(resp)
        if rt == tp.ERROR:
            if out.get("rejected"):
                raise QueryRejectedError(out["error"])
            raise KeyError(out["error"])
        return out

    def query(self, vertex: int, key: int, state: str = "acc") -> dict:
        return self._call(tp.QUERY_STATE,
                          {"vertex": vertex, "state": state, "key": key})

    def query_batch(self, vertex: int, keys: Sequence[int],
                    state: str = "acc") -> dict:
        return self._call(tp.QUERY_BATCH,
                          {"vertex": vertex, "state": state,
                           "keys": [int(k) for k in keys]})

    def status(self) -> dict:
        return self._call(tp.SERVE_STATUS, {})

    def close(self) -> None:
        self._client.close()


class ServeRouter:
    """Routes keyed lookups across owner + replicas by key group.

    Endpoints are duck-typed (``query`` / ``query_batch`` / ``status``)
    so the routing policy is unit-testable with fakes (no cluster).
    Policy: key -> key group -> replica ``kg % R``; the replica is used
    iff its last-known status is alive and within ``staleness_bound``
    sealed epochs of the owner; otherwise the read REROUTES to the
    owner (counted, never an error). Liveness failures against a
    replica (timeout / rejection / transport) also reroute — clients
    see degradation as staleness and latency, not exceptions."""

    def __init__(self, owner, replicas: Sequence,
                 num_key_groups: int, staleness_bound: int = 2,
                 status_ttl_s: float = 0.05, lineage=None):
        self.owner = owner
        #: lineage plane for serve-read termini (obs/lineage.py);
        #: None resolves to the process-global plane per read, so a
        #: router built before arming still records. Dyed keys only —
        #: the Null plane records nothing.
        self.lineage = lineage
        self.replicas = list(replicas)
        self.num_key_groups = int(num_key_groups)
        self.staleness_bound = int(staleness_bound)
        self.status_ttl_s = float(status_ttl_s)
        self.reads = 0
        self.reroutes = 0
        self.replica_reads = 0
        self.owner_reads = 0
        self.errors = 0
        #: recent end-to-end read latencies (ms) for the p99 gauge
        self.recent_ms: deque = deque(maxlen=8192)
        self._status: List[Optional[dict]] = [None] * len(self.replicas)
        self._status_at = [0.0] * len(self.replicas)
        self._lock = threading.Lock()

    # --- policy ----------------------------------------------------------

    def key_group(self, key: int) -> int:
        kg, _ = owner_subtask_np(np.asarray(key), 1, self.num_key_groups)
        return int(kg)

    def replica_for_group(self, kg: int) -> Optional[int]:
        if not self.replicas:
            return None
        return int(kg) % len(self.replicas)

    def replica_staleness(self, i: int) -> Optional[int]:
        st = self._probe(i)
        if st is None:
            return None
        return int(st.get("staleness_epochs", 0))

    def _probe(self, i: int) -> Optional[dict]:
        """Cached freshness probe (one STATUS call per TTL per replica
        — the routing decision must not double every read's round
        trips)."""
        now = _time.monotonic()
        with self._lock:
            if (self._status[i] is not None
                    and now - self._status_at[i] < self.status_ttl_s):
                return self._status[i]
        try:
            st = self.replicas[i].status()
        except (QueryTimeoutError, QueryRejectedError, OSError,
                KeyError):
            st = None
        with self._lock:
            self._status[i] = st
            self._status_at[i] = _time.monotonic()
        return st

    def _usable(self, i: Optional[int]) -> bool:
        if i is None:
            return False
        st = self._probe(i)
        return (st is not None and st.get("alive", True)
                and int(st.get("staleness_epochs", 0))
                <= self.staleness_bound)

    def _invalidate(self, i: int) -> None:
        with self._lock:
            if i < len(self._status):
                self._status[i] = None

    # --- reads -----------------------------------------------------------

    def _lineage(self):
        if self.lineage is not None:
            return self.lineage
        from clonos_tpu.obs.lineage import get_lineage
        return get_lineage()

    def query(self, vertex: int, key: int, state: str = "acc") -> dict:
        t0 = _time.monotonic()
        kg = self.key_group(key)
        i = self.replica_for_group(kg)
        out = None
        rerouted = False
        if self._usable(i):
            try:
                out = self.replicas[i].query(vertex, key, state=state)
                self.replica_reads += 1
            except (QueryTimeoutError, QueryRejectedError, OSError,
                    IndexError):
                # IndexError: the tier shrank between routing and the
                # call (drop_replica) — reroute like any replica loss
                self._invalidate(i)
                out = None
        if out is None:
            if i is not None:
                self.reroutes += 1
                rerouted = True
            out = self.owner.query(vertex, key, state=state)
            self.owner_reads += 1
        # Provenance stamp: which endpoint actually answered, at which
        # sealed epoch, and whether the read fell back to the owner —
        # enough for a lineage path to terminate at this read.
        out = dict(out)
        out["replica"] = str(out.get("served_by", "owner"))
        out["rerouted"] = rerouted
        lin = self._lineage()
        if lin.enabled:
            lin.observe_serve(key, epoch=int(out.get("epoch", -1)),
                              replica=out["replica"],
                              rerouted=rerouted)
        self.reads += 1
        self.recent_ms.append((_time.monotonic() - t0) * 1e3)
        return out

    def query_batch(self, vertex: int, keys: Sequence[int],
                    state: str = "acc") -> dict:
        """Batched routed read: keys are grouped per endpoint choice and
        each group goes out as ONE wire request (the replica end fuses
        it further into one device gather). Results return in input
        order with per-key provenance."""
        t0 = _time.monotonic()
        keys = [int(k) for k in keys]
        groups: Dict[object, List[int]] = {}
        routed_away: List[int] = []
        for pos, k in enumerate(keys):
            i = self.replica_for_group(self.key_group(k))
            dest = i if self._usable(i) else None
            if dest is None and i is not None:
                self.reroutes += 1
                routed_away.append(pos)
            groups.setdefault(dest, []).append(pos)
        n = len(keys)
        values = [None] * n
        epochs = [None] * n
        stals = [None] * n
        served = [None] * n
        rerouted = [False] * n
        for dest, positions in groups.items():
            sub_keys = [keys[p] for p in positions]
            out = None
            if dest is not None:
                try:
                    out = self.replicas[dest].query_batch(
                        vertex, sub_keys, state=state)
                    self.replica_reads += len(positions)
                except (QueryTimeoutError, QueryRejectedError, OSError,
                        IndexError):
                    self._invalidate(dest)
                    self.reroutes += len(positions)
                    for p in positions:
                        rerouted[p] = True
                    out = None
            if out is None:
                out = self.owner.query_batch(vertex, sub_keys,
                                             state=state)
                self.owner_reads += len(positions)
            who = out.get("served_by", "owner")
            for j, p in enumerate(positions):
                values[p] = out["values"][j]
                epochs[p] = out["epoch"]
                stals[p] = out.get("staleness_epochs", 0)
                served[p] = who
        for p in routed_away:
            rerouted[p] = True
        lin = self._lineage()
        if lin.enabled:
            for p, k in enumerate(keys):
                lin.observe_serve(k, epoch=int(epochs[p] or -1),
                                  replica=str(served[p]),
                                  rerouted=rerouted[p])
        self.reads += n
        self.recent_ms.append((_time.monotonic() - t0) * 1e3)
        return {"values": values, "epochs": epochs,
                "staleness_epochs": stals, "served_by": served,
                "rerouted": rerouted}


class ServeTier:
    """One runner's assembled read tier: replicas + their endpoints +
    clients + the router, plus the ``serve.*`` gauges riding the
    heartbeat piggyback into ``cluster_metrics()``."""

    def __init__(self, runner, vertex_id: int, n_replicas: int = 2,
                 staleness_bound: int = 2, state: str = "acc",
                 timeout_s: float = 5.0):
        self.runner = runner
        self.vertex_id = int(vertex_id)
        self.state_name = state
        self.timeout_s = float(timeout_s)
        #: monotone name counter — replica NAMES are never reused even
        #: when an index is (drop then add), so logs stay unambiguous
        self._n_created = 0
        self.owner_endpoint = None
        from clonos_tpu.runtime.query import (QueryableStateClient,
                                              QueryableStateEndpoint)
        self.owner_endpoint = QueryableStateEndpoint(runner)
        self.owner_client = QueryableStateClient(
            self.owner_endpoint.address, timeout_s=timeout_s)
        self.replicas: List[ReadReplica] = []
        self.endpoints: List[ReplicaServeEndpoint] = []
        self.clients: List[ReplicaStateClient] = []
        for _ in range(n_replicas):
            self._build_replica()
        self.router = ServeRouter(
            self.owner_client, self.clients,
            num_key_groups=runner.job.num_key_groups,
            staleness_bound=staleness_bound,
            lineage=getattr(runner, "lineage", None))
        # Owner endpoint snapshots refresh at every fence (fence hooks
        # run before truncation, after the seal stamped
        # last_sealed_epoch on the sequential path).
        runner.fence_hooks.append(self._on_fence)
        self._register_gauges()

    def _build_replica(self):
        """One replica + endpoint + client, appended to the tier's
        parallel lists (NOT yet visible to the router)."""
        rep = ReadReplica(self.runner, self.vertex_id,
                          state=self.state_name,
                          name=f"replica-{self._n_created}")
        self._n_created += 1
        ep = ReplicaServeEndpoint(rep)
        self.replicas.append(rep)
        self.endpoints.append(ep)
        self.clients.append(ReplicaStateClient(
            ep.address, timeout_s=self.timeout_s))
        return rep

    # --- runtime-adjustable replica count (the autoscaler's read-path
    # --- scale knob; ROADMAP "replica count fixed at tier build")

    def add_replica(self) -> int:
        """Grow the read tier by one replica at runtime. The new
        replica adopts ``standbys.latest`` immediately if one exists
        and (re)fills at the next seal — the PR 14 revival path — so
        it serves with honest staleness from the first read. The
        router's ``kg % R`` assignment picks up the new count the
        moment the replica is published under the router lock."""
        i = len(self.replicas)
        self._build_replica()
        with self.router._lock:
            self.router.replicas.append(self.clients[i])
            self.router._status.append(None)
            self.router._status_at.append(0.0)
        g = self.runner.metrics.group("serve")
        g.gauge(f"replica.{i}.staleness-epochs",
                lambda i=i: self.replicas[i].staleness_epochs())
        return i

    def drop_replica(self) -> int:
        """Shrink the read tier by one replica (the last index, so the
        ``kg % R`` map and the dense gauge indexing both contract
        cleanly). The router stops routing to it under the lock BEFORE
        the endpoint closes — an in-flight read that already picked it
        reroutes to the owner like any replica failure (staleness,
        never an error). Its status cache entries drop with it and its
        staleness gauge is unregistered (the registry would otherwise
        pin the dead closure forever)."""
        if len(self.replicas) <= 1:
            raise ValueError("cannot drop the last read replica")
        i = len(self.replicas) - 1
        with self.router._lock:
            self.router.replicas.pop()
            self.router._status = [None] * len(self.router.replicas)
            self.router._status_at = [0.0] * len(self.router.replicas)
        client = self.clients.pop()
        ep = self.endpoints.pop()
        rep = self.replicas.pop()
        client.close()
        ep.close()
        rep.close()
        self.runner.metrics.unregister(
            f"serve.replica.{i}.staleness-epochs")
        return i

    def _on_fence(self, closed: int) -> None:
        # Fence hooks fire before the (possibly pipelined) seal lands;
        # the executor state IS this fence's state, so stamp `closed`
        # explicitly rather than reading the trailing seal counter.
        self.owner_endpoint.refresh(epoch=closed)

    def _register_gauges(self) -> None:
        from clonos_tpu.soak.slo import quantile
        g = self.runner.metrics.group("serve")
        router = self.router
        g.gauge("reads", lambda: router.reads)
        g.gauge("reroutes", lambda: router.reroutes)
        g.gauge("replica-reads", lambda: router.replica_reads)
        g.gauge("owner-reads", lambda: router.owner_reads)
        g.gauge("read-errors", lambda: router.errors)
        g.gauge("p99-read-ms", lambda: round(
            quantile(list(router.recent_ms), 0.99), 3))
        g.gauge("replicas-alive",
                lambda: sum(1 for r in self.replicas if r.alive))
        self._meter = g.meter("reads-per-sec")
        # index-based closures (not per-object): the gauge for slot i
        # always reads the CURRENT occupant, so a drop-then-add cycle
        # that reuses the index never serves a dead replica's numbers.
        for i in range(len(self.replicas)):
            g.gauge(f"replica.{i}.staleness-epochs",
                    lambda i=i: self.replicas[i].staleness_epochs())

    def mark_reads(self, n: int) -> None:
        self._meter.mark(n)

    def rehome(self, new_runner) -> None:
        """Re-home the whole read tier after a live re-cut: the owner
        endpoint snapshots the NEW runner, every replica re-adopts in
        the new shape (key-group->replica assignment ``kg % R`` is
        recomputed per read from the new parallelism), fence hooks and
        gauges move over. Reads issued during the handoff window
        reroute to the owner — degradation shows as staleness, never as
        a client-visible error."""
        try:
            self.runner.fence_hooks.remove(self._on_fence)
        except ValueError:
            pass
        self.runner = new_runner
        self.owner_endpoint.runner = new_runner
        self.owner_endpoint.refresh()
        for rep in self.replicas:
            rep.rehome(new_runner)
        # Freshness probes cached against the old incarnation would
        # keep routing on stale staleness for a TTL — drop them.
        with self.router._lock:
            self.router._status = [None] * len(self.router.replicas)
        new_runner.fence_hooks.append(self._on_fence)
        self._register_gauges()

    def kill_replica(self, i: int) -> None:
        self.replicas[i % len(self.replicas)].kill()

    def staleness(self) -> List[int]:
        return [r.staleness_epochs() for r in self.replicas]

    def close(self) -> None:
        for c in self.clients:
            c.close()
        for ep in self.endpoints:
            ep.close()
        for r in self.replicas:
            r.close()
        self.owner_client.close()
        self.owner_endpoint.close()
        try:
            self.runner.fence_hooks.remove(self._on_fence)
        except ValueError:
            pass


def build_serve_tier(runner, vertex_id: int, n_replicas: int = 2,
                     staleness_bound: int = 2,
                     state: str = "acc") -> ServeTier:
    """Convenience assembly used by the soak serve load and tests."""
    return ServeTier(runner, vertex_id, n_replicas=n_replicas,
                     staleness_bound=staleness_bound, state=state)
