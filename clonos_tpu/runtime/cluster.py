"""Cluster runner: epochs and their fences, failure detection, standbys.

This is the control-plane layer tying the executor, checkpoint coordinator
and replication plan together — capability parity with the reference's
JobMaster-side machinery:

- ``HeartbeatMonitor``   <-  runtime/heartbeat (JobMaster.java:258-266)
- ``StandbyPool``        <-  ExecutionVertex.addStandbyExecution /
                             CheckpointCoordinator state dispatch (:1226)
- ``ClusterRunner``      <-  the JobMaster's epoch loop; what it does
                             on a task failure is runtime/failover.py
"""

from __future__ import annotations

import contextlib
import os
import threading
import time as _time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.causal import determinant as det
from clonos_tpu.causal import recovery as rec
from clonos_tpu.graph.job_graph import JobGraph, PartitionType
from clonos_tpu.parallel import routing
from clonos_tpu.runtime import checkpoint as cp
from clonos_tpu.obs import get_tracer
from clonos_tpu.runtime.executor import (DETS_PER_STEP, LocalExecutor,
                                         LogicalTimeSource)
from clonos_tpu.runtime.failover import (Failover, RecoveryReport,
                                         _exposed_ms)


@contextlib.contextmanager
def _fence_phase(phases: Dict[str, float], key: str, prof=None,
                 section: Optional[str] = None):
    """One fence phase under one pair of stamps: the ``<key>`` span,
    whose milliseconds become ``phases[key]`` and, where the phase is a
    profiler section, its ``overhead.<section>-ms`` sample."""
    with get_tracer().span(key) as sp:
        yield sp
    phases[key] = sp.ms
    if section is not None:
        prof.observe(section, sp.dur)


class HeartbeatMonitor:
    """Deadline-based liveness tracking (reference runtime/heartbeat)."""

    def __init__(self, subtasks: Sequence[int], timeout_s: float = 5.0,
                 clock=_time.monotonic):
        self._clock = clock
        self.timeout_s = timeout_s
        self._last: Dict[int, float] = {s: clock() for s in subtasks}
        self._dead: Set[int] = set()
        #: injected per-subtask heartbeat delay (seconds): a gray-failed
        #: worker's beats ARRIVE this much late — the worker is alive and
        #: making (slow) progress, so the monitor must classify it as
        #: degraded, not dead. Written by the chaos injector
        #: (soak/driver.py); empty in production.
        self.lag: Dict[int, float] = {}

    def beat(self, subtask: int) -> None:
        if subtask not in self._dead:
            self._last[subtask] = (self._clock()
                                   - self.lag.get(subtask, 0.0))

    def beat_all_except(self, dead: Set[int]) -> None:
        now = self._clock()
        for s in self._last:
            if s not in dead and s not in self._dead:
                self._last[s] = now - self.lag.get(s, 0.0)

    def mark_dead(self, subtask: int) -> None:
        self._dead.add(subtask)

    def expired(self) -> List[int]:
        now = self._clock()
        out = [s for s, t in self._last.items()
               if s not in self._dead and now - t > self.timeout_s]
        return sorted(out)

    def degraded(self, grace_s: float = 0.0) -> List[int]:
        """Subtasks whose beats arrive late but inside the death
        timeout: gray failures. Lateness is measured against the
        FRESHEST live beat, not wall time — between beat rounds every
        worker's last beat ages identically, and only a worker lagging
        its peers by more than ``grace_s`` is actually degraded.
        Disjoint from :meth:`expired` by construction — a worker is
        degraded OR dead, never both."""
        alive = {s: t for s, t in self._last.items()
                 if s not in self._dead}
        if not alive:
            return []
        freshest = max(alive.values())
        now = self._clock()
        out = [s for s, t in alive.items()
               if freshest - t > grace_s and now - t <= self.timeout_s]
        return sorted(out)

    def ages_ms(self) -> Dict[int, float]:
        """Per-subtask beat age behind the FRESHEST live beat, in ms —
        the peer-relative evidence the gray-failure detector scores
        (obs/detect.py). 0.0 for the freshest worker; empty when no one
        is alive."""
        alive = {s: t for s, t in self._last.items()
                 if s not in self._dead}
        if not alive:
            return {}
        freshest = max(alive.values())
        return {s: (freshest - t) * 1e3 for s, t in alive.items()}

    def revive(self, subtask: int) -> None:
        self._dead.discard(subtask)
        self.lag.pop(subtask, None)
        self._last[subtask] = self._clock()


class StandbyPool:
    """Holds the state standbys restore from: the latest completed
    checkpoint, refreshed on every completion (the reference re-dispatches
    state to STANDBY executions on each checkpoint, Execution.java:373)."""

    def __init__(self, num_standby_per_vertex: int = 1):
        self.num_standby_per_vertex = num_standby_per_vertex
        self.latest: Optional[cp.CompletedCheckpoint] = None
        self.dispatch_count = 0

    def on_completed_checkpoint(self, ckpt: cp.CompletedCheckpoint) -> None:
        # Monotonic: async writes can complete out of order, and a
        # stale completion must never regress the restore point behind
        # state (ring truncation) that has already moved past it.
        if self.latest is None \
                or ckpt.checkpoint_id >= self.latest.checkpoint_id:
            self.latest = ckpt
        self.dispatch_count += 1

    def has_state(self) -> bool:
        return self.latest is not None


class LatencyMarkers:
    """Latency markers, TPU-first (reference RecordWriter.randomEmit
    routing markers through RandomService so replay reproduces them,
    RecordWriter.java:131-137 + LatencyMarker):

    Marker STEPS are chosen by the per-step causal RNG draw
    (``rng % every == 0``). Those draws are recorded determinants, so a
    recovered task re-derives the SAME marker schedule — replay-stable
    by construction. A record emitted at source step ``s`` reaches the
    sink at step ``s + depth`` (the depth-1 superstep pipeline), so the
    marker's latency is the causal-time delta between those two steps'
    inputs — pipeline transit time as experienced by the data, reacting
    to stalls exactly like the reference's markers. Feeds the
    ``latency-ms`` registry histogram."""

    def __init__(self, runner: "ClusterRunner", every: int):
        self.runner = runner
        self.every = every
        job = runner.job
        # Pipeline depth: longest source->sink path in edges.
        depth = {v.vertex_id: 0 for v in job.vertices}
        for vid in job.topo_order():
            for e in job.in_edges(vid):
                depth[vid] = max(depth[vid],
                                 depth[job.edges[e].src] + 1)
        self.depth = max(depth.values()) if depth else 0
        self.hist = runner.metrics.group(
            f"job.{job.name}").histogram("latency-ms")
        self._seen = 0
        #: recent ``(source step, latency)`` pairs behind the histogram —
        #: the raw series coordinated-omission correction needs (the
        #: histogram forgets WHEN a sample happened, so queueing delay
        #: can't be re-attributed from it). Bounded: keeps the newest
        #: ``max_samples``.
        self.samples: List[Tuple[int, float]] = []
        self.max_samples = 8192

    @staticmethod
    def schedule(rngs, every: int):
        """Marker steps for a given rng-draw stream (pure — recovery
        tests re-derive it from recovered determinant rows)."""
        return [i for i, r in enumerate(rngs) if r % every == 0]

    def observe(self) -> None:
        hist = self.runner.executor.step_input_history
        upto = len(hist) - self.depth
        for s in range(self._seen, max(upto, 0)):
            t, r = hist[s]
            if r % self.every == 0:
                lat = hist[s + self.depth][0] - t
                self.hist.update(lat)
                self.samples.append((s, float(lat)))
        if len(self.samples) > self.max_samples:
            del self.samples[:len(self.samples) - self.max_samples]
        self._seen = max(self._seen, upto, 0)


class OverflowError_(RuntimeError):
    """An un-checkpointed log/ring overflow was detected — the state is no
    longer recoverable and the control plane must not keep running."""


class ClusterRunner:
    """Single-process cluster (MiniCluster analog) with failure injection.

    Drives epochs; at every epoch fence triggers a checkpoint, collects
    acks from healthy subtasks, and on completion truncates logs and
    refreshes standbys."""

    def __init__(self, job: JobGraph, steps_per_epoch: int = 8,
                 num_standby: int = 1, heartbeat_timeout_s: float = 5.0,
                 checkpoint_dir: Optional[str] = None,
                 incremental_checkpoints: bool = False,
                 incremental_base_every: int = 8,
                 recovery_block_steps: Optional[int] = None,
                 latency_marker_every: Optional[int] = None,
                 audit: Optional[bool] = None,
                 audit_on_divergence: Optional[str] = None,
                 lineage=None,
                 overlap_epoch: bool = False,
                 **executor_kw):
        self.job = job
        self.executor = LocalExecutor(job, steps_per_epoch=steps_per_epoch,
                                      **executor_kw)
        #: fence mode of run_epoch(), fixed for the runner's life: True
        #: hands the fence tail (health drain, audit seal, ledger append,
        #: checkpoint write) to a worker thread that overlaps the next
        #: epoch's compute, joining before the next fence — at most one
        #: tail in flight. Defaults to False (today's strict order):
        #: overlap defers checkpoint completion/truncation and ledger
        #: visibility by one fence, which callers must opt into. The
        #: inline fence never writes the fence.overlap-saved key.
        self.overlap_epoch = overlap_epoch
        #: in-flight fence tail (pipelined fence): None, or a dict with
        #: the worker thread + its captured handles/results. Joined at
        #: the next fence, before any failure injection, and before
        #: recover() — never survives past one epoch.
        self._fence_tail: Optional[dict] = None
        #: fence attribution of the last joined/sequential fence:
        #: fence.* sub-spans (true walls), "fence-tail" (critical-path
        #: wall the epoch actually waited), and — overlapped only —
        #: "fence.overlap-saved", preserving
        #: sum(fence.*) - overlap-saved == fence-tail.
        self.last_fence_phases: Dict[str, float] = {}
        #: cumulative fence.overlap-saved milliseconds
        self.fence_overlap_saved_total_ms = 0.0
        self._fence_headroom_checked = False
        if incremental_checkpoints:
            if checkpoint_dir is None:
                raise ValueError(
                    "incremental_checkpoints requires checkpoint_dir")
            from clonos_tpu.runtime.incremental import (
                IncrementalCheckpointStorage)
            storage: cp.CheckpointStorage = IncrementalCheckpointStorage(
                checkpoint_dir, base_every=incremental_base_every)
        elif checkpoint_dir:
            storage = cp.FileCheckpointStorage(checkpoint_dir)
        else:
            storage = cp.InMemoryCheckpointStorage()
        self.coordinator = cp.CheckpointCoordinator(
            storage, num_subtasks=job.total_subtasks(),
            base_interval_steps=steps_per_epoch)
        self.standbys = StandbyPool(num_standby)
        self.coordinator.subscribe_completed_state(
            self.standbys.on_completed_checkpoint)
        self.coordinator.subscribe_completion(
            self.executor.notify_checkpoint_complete)
        # Durable-connector contract: a completed checkpoint commits the
        # feed offsets it captured (FlinkKafkaConsumerBase
        # .notifyCheckpointComplete), letting bounded-retention readers
        # release history below them — recovery only ever re-reads from
        # the latest completed checkpoint's offsets.
        self.coordinator.subscribe_completed_state(self._commit_feed_offsets)
        self.heartbeats = HeartbeatMonitor(
            range(job.total_subtasks()), timeout_s=heartbeat_timeout_s)
        self.failed: Set[int] = set()
        # Fence hooks run at every epoch fence BEFORE checkpoint
        # completion truncates the logs and rings — the window where an
        # edge export (runtime/scheduler.py) must snapshot the producer
        # rings' fresh steps or lose them to the truncation.
        self.fence_hooks: List = []
        #: read-replica delta feeds (runtime/serve.py): ``fn(epoch,
        #: window)`` fires when an epoch seals, with the SAME extracted
        #: causal-surface window the audit digests — standbys tail it to
        #: keep their restored checkpoint fence-fresh. Runs on the fence
        #: worker when the fence is pipelined: subscribers must be
        #: host-only and thread-safe, like the auditor.
        self.serve_feeds: List = []
        #: the last epoch whose fence tail SEALED (digest when audit is
        #: on, fence persistence either way) — the freshness stamp every
        #: queryable-state snapshot carries. -1 until the first seal:
        #: endpoints reject reads rather than serve an unstamped view.
        self.last_sealed_epoch = -1
        self.global_step = 0
        self._fence_step: Dict[int, int] = {}   # epoch -> global step at start
        self._fence_step[0] = 0
        self.plan = self.executor.compiled.plan
        self.reports: List[RecoveryReport] = []
        # Observability (reference MetricRegistryImpl + Clonos determinant
        # watchdog; see utils/metrics.py).
        from clonos_tpu.utils import metrics as met
        self.metrics = met.MetricRegistry()
        g = self.metrics.group(f"job.{job.name}")
        self._m_steps = g.counter("supersteps")
        self._m_records = g.meter("records-per-sec")
        self._m_epochs = g.counter("epochs")
        self._m_ckpt_bytes = g.gauge(
            "checkpoint.latest-bytes",
            lambda: (self.standbys.latest.size_bytes
                     if self.standbys.latest else 0))
        self._m_epoch_steps_ms = g.histogram("epoch.steps-ms")
        self._m_epoch_fence_ms = g.histogram("epoch.fence-ms")
        self._m_ckpt_latency_ms = g.histogram(
            "checkpoint.trigger-to-complete-ms")
        self.coordinator.subscribe_completion(
            lambda cid: self._m_ckpt_latency_ms.update(
                self.coordinator.completion_latency_s.get(cid, 0.0) * 1e3))
        self._mgroup = g
        # Exactly-once audit plane (obs/audit.py): ``audit=None``
        # inherits the process-global stance (set by config/CLI or
        # adopted from the JobMaster's DEPLOY via transport.adopt_audit);
        # the default is the zero-overhead NullAuditor — no digest reads,
        # no ledger writes, no wire fields.
        from clonos_tpu.obs import audit as _audit_mod
        #: partition shape stamped into every sealed digest so ledger
        #: diffs across a live re-cut know which epochs need the
        #: layout-invariant comparison (obs/audit.diff_ledgers_cross).
        self._audit_layout = tuple(
            (v.vertex_id, v.parallelism) for v in job.vertices)
        if audit is None:
            audit = _audit_mod.get_auditor().enabled
        if audit:
            self.auditor: _audit_mod.NullAuditor = _audit_mod.Auditor(
                on_divergence=(audit_on_divergence
                               or _audit_mod.get_auditor().on_divergence))
        else:
            self.auditor = _audit_mod.NullAuditor()
        self._m_audit_sealed = g.counter("audit.epochs-sealed")
        # Overhead attribution (obs/profile.py): the runner inherits the
        # process-global profiler (set by config/CLI). Binding routes
        # the overhead.<section>-ms histograms and overhead.ft-fraction
        # gauge into this registry so they ride the heartbeat piggyback;
        # the default NullProfiler binds to nothing and fences nothing.
        from clonos_tpu.obs import profile as _prof_mod
        self.profiler = _prof_mod.get_profiler()
        if self.profiler.enabled:
            self.profiler.bind(g)
        g.gauge("audit.enabled", lambda: int(self.auditor.enabled))
        g.gauge("audit.last-sealed-epoch", lambda: self.auditor.last_epoch)
        # Incident forensics plane (obs/incident.py): when the process
        # has a live IncidentManager its capture counters ride the same
        # heartbeat piggyback; the NullIncidentManager default registers
        # nothing — zero wire fields.
        from clonos_tpu.obs import incident as _inc_mod
        _inc = _inc_mod.get_incidents()
        if _inc.enabled:
            _inc.register_gauges(self.metrics)
        # Record-level lineage plane (obs/lineage.py): per-runner
        # binding like the auditor — ``lineage=None`` inherits the
        # process-global plane (set by CLI/soak arming or adopted from
        # a DEPLOY header via transport.adopt_lineage); callers that
        # run twins in one process (the soak control) pass distinct
        # planes so each runner's observations land in its own file.
        # The NullLineage default scans nothing and registers nothing.
        from clonos_tpu.obs import lineage as _lin_mod
        self.lineage = (lineage if lineage is not None
                        else _lin_mod.get_lineage())
        if self.lineage.enabled:
            self.lineage.register_gauges(self.metrics)
        #: vertex id -> parallelism, for the lineage plane's
        #: key-group/subtask attribution at the seal scan.
        self._lineage_topology = {v.vertex_id: v.parallelism
                                  for v in job.vertices}
        # Live exactly-once health: how hard the in-flight rings are
        # holding un-truncated history (backpressure proxy — rings only
        # grow when checkpoints lag), and how many supersteps a failure
        # RIGHT NOW would replay (the recovery-cost exposure).
        g.gauge("backpressure.inflight-occupancy", self._inflight_occupancy)
        g.gauge("recovery.replay-lag-steps", self._replay_lag_steps)
        # Tiered-storage residency + movement (storage/tiered.py), summed
        # over every spill owner (in-flight rings + determinant tier).
        # Zero when spilling is disabled; `clonos_tpu top` renders the
        # spill.* suffix as its SPILL column.
        if self.executor.spill_logs is not None:
            g.gauge("spill.host-epochs",
                    lambda: self.executor.spill_occupancy()["host_epochs"])
            g.gauge("spill.disk-epochs",
                    lambda: self.executor.spill_occupancy()["disk_epochs"])
            g.gauge("spill.host-bytes",
                    lambda: self.executor.spill_occupancy()["host_bytes"])
            g.gauge("spill.disk-bytes",
                    lambda: self.executor.spill_occupancy()["disk_bytes"])
            g.gauge("spill.bytes-spilled",
                    lambda: self.executor.spill_stats()
                    .get("bytes_spilled", 0))
            g.gauge("spill.bytes-refilled",
                    lambda: self.executor.spill_stats()
                    .get("bytes_refilled", 0))
        self.watchdog = met.LogOccupancyWatchdog(self.executor, g)
        # Per-mesh-shard health (mesh-sharded fused blocks): one gauge
        # triple per task-axis shard, fed from the executor's packed
        # [n, 3] per-shard read, cached per epoch so a metrics scrape
        # costs at most one device round-trip per fence.
        self._shard_health: Optional[np.ndarray] = None
        self._shard_health_epoch = -1
        mesh_ = self.executor.compiled.mesh
        if mesh_ is not None:
            n_sh = mesh_.shape[self.executor.compiled.task_axis]
            g.gauge("mesh.shards", lambda n_sh=n_sh: n_sh)
            for i in range(n_sh):
                g.gauge(f"shard.{i}.records",
                        lambda i=i: int(self.per_shard_health()[i, 0]))
                g.gauge(f"shard.{i}.log-rows",
                        lambda i=i: int(self.per_shard_health()[i, 1]))
                g.gauge(f"shard.{i}.ring-slots",
                        lambda i=i: int(self.per_shard_health()[i, 2]))
        #: counter fed from the fence's health read (an event-time
        #: window's ``fence_totals``, ``exchange.*``) -> its total at the
        #: last fence
        self._fence_counter_totals: Dict[str, int] = {}
        self._last_records_total = 0
        #: checkpoint id -> np [L] log heads at that fence, harvested from
        #: the per-epoch health read (recovery's patch phase reads them
        #: here instead of round-tripping the device on the failure path).
        #: Inserted by the fence tail (worker thread when pipelined),
        #: pruned by the completion hook (async writer thread), read by
        #: recovery — every touch holds _ck_heads_lock.
        self._ck_log_heads: Dict[int, np.ndarray] = {}
        self._ck_heads_lock = threading.Lock()
        #: host mirror of the in-flight ring offsets: heads advance one
        #: per superstep (== global_step), tails move only at checkpoint
        #: completion (ifl.truncate to the completed epoch's end fence).
        #: Lets recover() make its routing coverage decisions without a
        #: device read; the device bounds are still compared against the
        #: mirror in recovery's final packed read (fail-loud, not trust).
        self._ring_tail_mirror = 0
        self._ring_mirror_valid = True
        self.coordinator.subscribe_completion(self._update_ring_mirror)
        # Host epoch control plane (reference EpochTrackerImpl): the
        # listener bus + record counting driven from the fused per-epoch
        # health read; checkpoint completions fan out through it.
        from clonos_tpu.causal.epoch import EpochTracker
        self.epoch_tracker = EpochTracker()
        self.coordinator.subscribe_completion(
            self.epoch_tracker.notify_checkpoint_complete)
        #: flat subtask -> ProcessingTimeService; timers fire at block
        #: boundaries on causal time and log TIMER_TRIGGER determinants
        #: (reference SystemProcessingTimeService.java:50,79-114).
        self.timer_services: Dict[int, Any] = {}
        self.executor.block_listeners.append(self._advance_timers)
        #: latency markers through the causal RNG path (RecordWriter
        #: .randomEmit analog); None = off.
        self.latency = (LatencyMarkers(self, latency_marker_every)
                        if latency_marker_every else None)
        #: source subtasks (no input edges): their logs record
        #: SOURCE_CHECKPOINT determinants at every trigger
        #: (StreamTask.performCheckpoint:833-840).
        self._source_flats = [
            self.job.subtask_base(v.vertex_id) + s
            for v in self.job.vertices if not self.job.in_edges(v.vertex_id)
            for s in range(v.parallelism)]
        # Transactional sinks: 2PC egress (runtime/txn.py). Emissions tap
        # the per-block outputs; transactions seal at fences and commit on
        # checkpoint completion.
        from clonos_tpu.api.operators import TransactionalSinkOperator
        from clonos_tpu.runtime.txn import TransactionLog
        self.txn_logs: Dict[int, TransactionLog] = {
            v.vertex_id: TransactionLog(v.vertex_id)
            for v in job.vertices
            if isinstance(v.operator, TransactionalSinkOperator)}
        #: the newest block's compaction in flight, with the epoch the
        #: block ran in: ``(epoch, {sink vertex: PackedBlock})``, None
        #: once read (runtime/sinktap.py: the tap trails by one block)
        self._tap_pending: Optional[Tuple[int, Dict[int, Any]]] = None
        if self.txn_logs:
            from clonos_tpu.runtime.sinktap import SinkTap
            compiled = self.executor.compiled
            self._sink_taps = {
                vid: SinkTap(compiled.mesh, compiled.task_axis)
                for vid in self.txn_logs}
            self.executor.on_block_outputs = self._absorb_sink_outputs
            self.executor.drain_block_outputs = self._read_sink_tap
            self.coordinator.subscribe_completion(
                lambda e: [tl.commit(e) for tl in self.txn_logs.values()])
        #: the failure path (runtime/failover.py): kill, recovery, the
        #: warm standby's programs, at the recovery chunk size
        self.failover = Failover(self, g, min(
            recovery_block_steps or self.executor.block_steps,
            self.executor.compiled.inflight_ring_steps,
            self.executor.compiled.log_capacity // DETS_PER_STEP))

    def _commit_feed_offsets(self, ckpt) -> None:
        for vid, reader in self.executor.feed_readers.items():
            off = np.asarray(ckpt.carry.op_states[vid]["offset"])
            reader.notify_checkpoint_complete([int(x) for x in off])

    def _absorb_sink_outputs(self, outs, epoch: int) -> None:
        """The sink tap, once a block and one block behind the block
        program (runtime/sinktap.py). The program that produced ``outs``
        has just been dispatched: first read the *previous* block's
        rows, whose compaction was queued ahead of it, then launch this
        block's compaction behind it, at the rung the count just read
        suggests. Nothing here waits for ``outs``."""
        self._read_sink_tap(trailing=1)
        packed = {vid: self._sink_taps[vid].dispatch(outs.sinks[vid])
                  for vid in self.txn_logs if vid in outs.sinks}
        if packed:
            self._tap_pending = (epoch, packed)

    def _read_sink_tap(self, trailing: int = 0) -> None:
        """Read the block whose compaction is in flight, if any, into
        the transaction log of the epoch it ran in, in three spans:
        waiting the compaction out (device busy, not idle), what is left
        of the device-to-host copy of the counts and the packed rows,
        the per-subtask sharding (``TransactionLog.absorb``).
        ``trailing``: 1 when the block's successor was dispatched before
        this wait began, 0 for a drain (nothing queued behind it)."""
        if self._tap_pending is None:
            return
        (epoch, packed), self._tap_pending = self._tap_pending, None
        tr = get_tracer()
        with tr.span("block.sink.wait", trailing=trailing):
            jax.block_until_ready([(pk.counts, pk.rows)
                                   for pk in packed.values()])
        with tr.span("block.sink.d2h") as sp:
            host = {vid: self._sink_taps[vid].read(pk)
                    for vid, pk in packed.items()}
            nbytes = sum(pk.nbytes for pk in packed.values())
            sp.set(bytes=nbytes,
                   rung=max(pk.rung for pk in packed.values()))
        misses = sum(pk.missed for pk in packed.values())
        tr.count("sink.d2h_bytes", nbytes)
        tr.count("sink.rung_reads", len(packed))
        tr.count("sink.pack_slots", sum(pk.slots for pk in packed.values()))
        if trailing:
            tr.count("sink.taps_trailing", len(packed))
        if misses:
            tr.count("sink.rung_misses", misses)
        launches = len(packed) + misses
        tr.count("block.dispatches.sink_pack", launches)
        shifted = sum(pk.shifted for pk in packed.values())
        if shifted:
            tr.count("sink.packs_by_shifts", shifted)
        if launches > shifted:
            tr.count("sink.packs_by_rank", launches - shifted)
        for vid, (counts, rows) in host.items():
            self.txn_logs[vid].absorb(epoch, counts, rows)

    # --- live health gauges (heartbeat-piggybacked; runtime/remote.py) -------

    def _inflight_occupancy(self) -> float:
        """Fraction of the in-flight rings' capacity holding
        un-truncated steps — the host-mirror backpressure proxy (rings
        retain exactly the steps a failure would need to re-route; a
        rising value means checkpoint completion is lagging the fences)."""
        if not self.executor.carry.out_rings:
            return 0.0
        cap = self.executor.compiled.inflight_ring_steps
        held = self.global_step - self._ring_tail_mirror
        return min(max(held, 0) / cap, 1.0)

    def _replay_lag_steps(self) -> int:
        """Supersteps a failure occurring NOW would replay (distance from
        the latest completed checkpoint's fence) — the live recovery-cost
        exposure."""
        ck = self.standbys.latest
        if ck is None:
            return self.global_step
        f = self._fence_step.get(ck.checkpoint_id + 1)
        return self.global_step - f if f is not None else 0

    def per_shard_health(self) -> Optional[np.ndarray]:
        """int32 [n_shards, 3] (records, live log rows, live ring slots)
        per task-axis mesh shard, cached per epoch (the shard.<i>.*
        gauges all read through this, so a full metrics scrape costs one
        device round-trip, not 3n). None without a mesh."""
        if self.executor.compiled.mesh is None:
            return None
        if self._shard_health_epoch != self.executor.epoch_id \
                or self._shard_health is None:
            self._shard_health = self.executor.per_shard_health()
            self._shard_health_epoch = self.executor.epoch_id
        return self._shard_health

    def _update_ring_mirror(self, completed_epoch: int) -> None:
        """Checkpoint-completion hook: advance the host ring-tail mirror
        to the completed epoch's end fence (matches ifl.truncate). A
        completion whose fence the runner never saw (executor driven
        directly, e.g. by a test) invalidates the mirror — recover()
        then reads the device bounds instead of trusting stale ones."""
        f = self._fence_step.get(completed_epoch + 1)
        if f is None:
            self._ring_mirror_valid = False
        else:
            self._ring_tail_mirror = max(self._ring_tail_mirror, f)
        # Recovery only ever restores from the latest completed
        # checkpoint — drop older fence-head entries (bounded ledger).
        # Under the lock: this hook runs on the async writer thread
        # while the fence tail inserts the next epoch's heads.
        with self._ck_heads_lock:
            self._ck_log_heads = {
                k: v for k, v in self._ck_log_heads.items()
                if k >= completed_epoch}

    # --- timers / epoch services ---------------------------------------------

    def timer_service(self, flat_subtask: int):
        """The per-task processing-time timer service (lazily created);
        registered callbacks fire at block boundaries on causal time and
        their TIMER_TRIGGER determinants replay after a failure."""
        svc = self.timer_services.get(flat_subtask)
        if svc is None:
            from clonos_tpu.runtime.timers import ProcessingTimeService
            svc = ProcessingTimeService(
                append=lambda d, f=flat_subtask:
                    self.executor.append_async_determinant(f, d))
            self.timer_services[flat_subtask] = svc
        return svc

    def _advance_timers(self, now: int, stamp: int) -> None:
        if self.profiler.enabled and self.timer_services:
            with self.profiler.section("timer-advance"):
                for flat, svc in self.timer_services.items():
                    if flat not in self.failed:
                        svc.advance(now, stamp)
            return
        for flat, svc in self.timer_services.items():
            if flat not in self.failed:
                svc.advance(now, stamp)

    @classmethod
    def from_config(cls, job: JobGraph, config=None, **overrides
                    ) -> "ClusterRunner":
        """Build a runner from the typed Configuration surface
        (config/defaults.py — the reference's flink-conf.yaml /
        ExecutionConfig path). Explicit ``overrides`` win."""
        from clonos_tpu.config import defaults as D
        from clonos_tpu.config.options import Configuration
        cfg = config or Configuration()
        job.sharing_depth = cfg.get(D.DETERMINANT_SHARING_DEPTH)
        kw: Dict[str, Any] = dict(
            steps_per_epoch=cfg.get(D.CHECKPOINT_INTERVAL_STEPS),
            num_standby=(cfg.get(D.NUM_STANDBY_TASKS)
                         if cfg.get(D.FAILOVER_STRATEGY) == "standbytask"
                         else 0),
            heartbeat_timeout_s=cfg.get(D.HEARTBEAT_TIMEOUT_MS) / 1e3,
            log_capacity=cfg.get(D.DETERMINANT_LOG_CAPACITY),
            max_epochs=cfg.get(D.DETERMINANT_MAX_EPOCHS),
            inflight_ring_steps=cfg.get(D.INFLIGHT_CAPACITY_BATCHES),
        )
        if cfg.get(D.INFLIGHT_TYPE) == "spillable":
            kw["spool_dir"] = os.path.join(cfg.get(D.CHECKPOINT_DIR),
                                           "spill")
            kw["spill_policy"] = cfg.get(D.INFLIGHT_SPILL_POLICY)
            kw["spill_host_budget_epochs"] = cfg.get(
                D.INFLIGHT_HOST_BUDGET_EPOCHS)
        if cfg.contains(D.CHECKPOINT_DIR):
            kw["checkpoint_dir"] = cfg.get(D.CHECKPOINT_DIR)
        if cfg.get(D.AUDIT_ENABLED):
            kw["audit"] = True
            kw["audit_on_divergence"] = cfg.get(D.AUDIT_ON_DIVERGENCE)
        if cfg.get(D.PROFILE_ENABLED):
            from clonos_tpu.obs import profile as _prof
            if not _prof.get_profiler().enabled:
                _prof.configure_profile()
        kw.update(overrides)
        runner = cls(job, **kw)
        runner.coordinator.backoff_multiplier = cfg.get(
            D.CHECKPOINT_BACKOFF_MULTIPLIER)
        return runner

    @classmethod
    def bootstrap_standby(cls, job: JobGraph, checkpoint_dir: str,
                          mirror_rows: Dict[int, Tuple[np.ndarray, int]],
                          ignored_checkpoints: Sequence[int] = (),
                          feed_readers: Optional[Dict[int, object]] = None,
                          **runner_kw
                          ) -> Tuple["ClusterRunner", RecoveryReport]:
        """Standby-HOST failover: rebuild the ENTIRE job in a fresh
        process after a whole-host loss, from (a) the durable checkpoint
        and (b) a RemoteReplicaMirror's determinant rows — the mirrors
        are the determinant source intra-chip replicas cannot be when
        the chip died with the host (reference: standby TaskManagers +
        DeterminantResponseEvent over the wire;
        RunStandbyTaskStrategy.java:186-227, Task.java:1290).

        Every subtask is recovered through the normal causal protocol in
        topological order — sources replay from their recorded rng/time
        streams, their rebuilt in-flight rings feed downstream routing —
        so the rebuilt cluster's state is bit-identical to the dead
        worker's at its last mirrored fence, verified by the replay's
        output-cut asserts against the mirrored BUFFER_BUILT rows.

        Requirements: ``mirror_rows`` must cover every flat subtask and
        end at an epoch fence (mirrors refresh at fences); rebalance
        edges are not yet reconstructible (their round-robin cursors are
        not in the lean snapshot's fence state).

        ``feed_readers`` maps HostFeedSource vertex ids to rewindable
        readers (api/feeds.py contract); they are registered BEFORE the
        replay so the feed re-read path (`_reread_feed`) can serve the
        recorded offset windows — required when the rebuilt job has
        host-boundary sources (e.g. a scheduler slice whose cut in-edges
        arrive over the wire)."""
        for e in job.edges:
            if e.partition == PartitionType.REBALANCE:
                raise rec.RecoveryError(
                    "bootstrap_standby: rebalance edges not supported "
                    "(post-replay round-robin cursors are not "
                    "reconstructible from the fence snapshot)")
        # Rebuild-stage sub-attribution: the stages around recover() are
        # the standby-host analog of the finalize phase (everything that
        # must happen besides replay before the job resumes). Each stage
        # is a recovery.finalize.<stage> span under the adopted recovery
        # trace id whose stamps also fold into the report's phase_ms.
        sub_ms: Dict[str, float] = {}
        stages = get_tracer().chain("recovery.", into=sub_ms)
        stages.switch("finalize.state-rehydrate")

        runner = cls(job, checkpoint_dir=checkpoint_dir, **runner_kw)
        for vid, reader in (feed_readers or {}).items():
            runner.executor.register_feed(vid, reader)
        storage = runner.coordinator.storage
        ignored = set(ignored_checkpoints)
        # Only fully-ACKED checkpoints are restore points; triggered-but-
        # unacked snapshots also sit in storage (written at the fence).
        ids = [i for i in storage.completed_ids() if i not in ignored]
        if not ids:
            raise rec.RecoveryError(
                "bootstrap_standby: no durable completed non-ignored "
                f"checkpoint in {checkpoint_dir}")
        ckpt = storage.read(max(ids))
        runner.standbys.on_completed_checkpoint(ckpt)
        runner.coordinator.mark_ignored(ignored)
        spe = runner.executor.steps_per_epoch
        from_epoch = ckpt.checkpoint_id + 1
        L = job.total_subtasks()
        missing = [f for f in range(L) if f not in mirror_rows]
        if missing:
            raise rec.RecoveryError(
                f"bootstrap_standby: mirror rows missing for subtasks "
                f"{missing}")

        # The absolute superstep at the fence: the lean snapshot's ring
        # heads ARE step counts (one append per superstep). A job with
        # no rings (single vertex, no edges) carries no such counter,
        # but checkpoint cadence pins it anyway: checkpoint id e seals
        # epochs 0..e, so its fence sits at exactly (e + 1) *
        # steps_per_epoch supersteps — the same invariant `ring_heads[0]`
        # encodes when rings exist (one append per superstep from step
        # 0). Deriving it makes edge-less jobs bootstrappable past epoch
        # 0 instead of refusing (ADVICE round 5: the old silent
        # `global_step = 0` default replayed from the wrong offset).
        if ckpt.carry.ring_heads:
            fence = int(np.asarray(ckpt.carry.ring_heads[0]))
        else:
            fence = (ckpt.checkpoint_id + 1) * spe

        # Steps replayed = sync-anchor count of the mirrored streams
        # (lockstep supersteps: every log advances together, and the
        # mirror snapshot is prefix-consistent across flats).
        anchors_by_flat: Dict[int, np.ndarray] = {
            flat: det.sync_anchors(rows)
            for flat, (rows, _start) in mirror_rows.items()}
        ns = {len(a) for a in anchors_by_flat.values()}
        if len(ns) != 1:
            raise rec.RecoveryError(
                f"bootstrap_standby: mirror streams disagree on the "
                f"replayed step count: {sorted(ns)}")
        n_steps = ns.pop()
        if n_steps % spe != 0:
            raise rec.RecoveryError(
                f"bootstrap_standby: mirrored {n_steps} steps is not a "
                f"whole number of {spe}-step epochs (mirrors refresh at "
                f"fences)")
        k = n_steps // spe

        # Control-plane bookkeeping the dead worker would have had.
        runner.global_step = fence + n_steps
        # Step-input ledger: per-step (time, rng) inputs are global
        # across the lockstep supersteps, so any subtask's recorded
        # stream reproduces them; pre-fence entries are placeholders
        # (nothing replays below a completed fence).
        a0 = anchors_by_flat[0]
        rows0 = np.asarray(mirror_rows[0][0], np.int32)
        hist = [(0, 0)] * fence
        for j in range(n_steps):
            hist.append((int(rows0[a0[j], det.LANE_P + 1]),
                         int(rows0[a0[j] + 1, det.LANE_P])))
        runner.executor.step_input_history = hist
        if runner.latency is not None:
            # Placeholder entries (rng=0) would all read as markers and
            # flood the histogram with zero samples — markers resume at
            # the first post-rebuild step.
            runner.latency._seen = len(hist)
        runner.executor.epoch_id = from_epoch + k
        runner.executor.step_in_epoch = 0
        for j in range(k + 1):
            runner._fence_step[from_epoch + j] = fence + j * spe
        runner._ring_tail_mirror = fence
        with runner._ck_heads_lock:
            runner._ck_log_heads[ckpt.checkpoint_id] = np.asarray(
                ckpt.carry.log_heads).astype(np.int64)
        stages.switch("finalize.ring-reregister")

        # Overlapped finalize (the tentpole restructure): the roll-gap /
        # async ledger derivation (listener-reattach) is a pure function
        # of the mirrored streams, and the host-RNG fast-forward +
        # first-step AOT warm (first-step-recompile) touch nothing the
        # device replay mutates — all of it runs on ONE worker thread
        # concurrently with recover()'s replay instead of serially
        # around it. Join points are explicit: the ledgers install at
        # recover()'s pre-patch join (the earliest read site — _patch
        # rebuilds epoch offsets from roll_gap_async), the warm work
        # joins before bootstrap returns (= before the first live
        # step). Ring-reregister CANNOT move: recover() captures the
        # carry and dispatches its ring-bounds read at entry, and the
        # final packed read asserts those device bounds — the offsets
        # must already be in place.
        # Both pieces of work stamp their (start, end), and the two
        # joins when they began to wait, on one clock: the report's
        # blocked remainders come from these stamps alone.
        ov: Dict[str, Any] = {"derive": (0.0, 0.0), "warm": (0.0, 0.0),
                              "derive_wait": 0.0,
                              "rg": {}, "ac": {}, "err": None}
        derived = threading.Event()

        def _overlap_work() -> None:
            # Roll-gap / async ledgers, re-derived from the mirrored
            # streams: rows between one epoch's last sync block and the
            # next epoch's first anchor are that next epoch's roll-gap
            # appends (exact when between-epoch appends happen only at
            # rolls — fence SOURCE_CHECKPOINTs, ignore broadcasts; see
            # executor.roll_gap_async).
            t_d = _time.monotonic()
            try:
                rg: Dict[Tuple[int, int], int] = {}
                ac: Dict[Tuple[int, int], int] = {}
                for flat, (rows, _start) in mirror_rows.items():
                    rows = np.asarray(rows, np.int32)
                    a = anchors_by_flat[flat]
                    for j in range(k + 1):
                        if j == 0:
                            gap = int(a[0]) if len(a) else rows.shape[0]
                        else:
                            prev_end = int(a[j * spe - 1]) + DETS_PER_STEP
                            nxt = (int(a[j * spe]) if j < k
                                   else rows.shape[0])
                            gap = nxt - prev_end
                        if gap > 0:
                            rg[(flat, from_epoch + j)] = gap
                    # async totals per epoch (cleanness ledger for
                    # FUTURE failures of the rebuilt cluster).
                    for j in range(k):
                        lo = int(a[j * spe])
                        hi = (int(a[(j + 1) * spe]) if j + 1 < k
                              else rows.shape[0])
                        async_n = (hi - lo) - spe * DETS_PER_STEP
                        lead_gap = rg.get((flat, from_epoch + j), 0)
                        total_async = async_n + (lead_gap if j == 0
                                                 else 0)
                        if total_async > 0:
                            ac[(flat, from_epoch + j)] = total_async
                ov["rg"], ov["ac"] = rg, ac
            except Exception as err:          # re-raised at the join
                ov["err"] = err
            finally:
                ov["derive"] = (t_d, _time.monotonic())
                derived.set()
            if ov["err"] is not None:
                return
            # Off the join path: the host RNG is a seeded per-run
            # stream, one draw per executed superstep; replay reproduces
            # the prefix from RECORDED rng determinants without
            # consuming it, so fast-forward a fresh stream past the
            # prefix (replay never draws, so the thread owns the RNG).
            # Then warm the first-step executable — with the persistent
            # compile cache (utils/compile_cache.py) this is a cache
            # HIT from the pre-failure prewarm, not a full XLA compile.
            t_w = _time.monotonic()
            try:
                runner.executor.fast_forward_host_rng(fence + n_steps)
                from clonos_tpu.utils.compile_cache import (
                    aot_lower_first_step)
                aot_lower_first_step(runner.executor, runner._mgroup)
            except Exception as err:
                ov["err"] = err
            ov["warm"] = (t_w, _time.monotonic())

        worker = threading.Thread(target=_overlap_work,
                                  name="bootstrap-finalize-overlap")
        worker.start()

        def _join_ledgers() -> None:
            ov["derive_wait"] = _time.monotonic()
            derived.wait()
            if ov["err"] is not None:
                raise ov["err"]
            runner.executor.install_replay_ledgers(ov["rg"], ov["ac"])

        # In-flight ring offsets/epoch index as the dead worker had them:
        # content is rebuilt by the per-vertex ring write-backs during
        # recover(); offsets must already read (tail=fence, head=fence+n)
        # for the topological routing to see its coverage.
        c = runner.executor.carry
        new_rings = []
        for el in c.out_rings:
            starts = np.asarray(el.epoch_starts)
            me = starts.shape[0]
            starts = starts.copy()
            for j in range(k + 1):
                starts[(from_epoch + j) % me] = fence + j * spe
            new_rings.append(el._replace(
                head=jnp.asarray(fence + n_steps, jnp.int32),
                tail=jnp.asarray(fence, jnp.int32),
                epoch_starts=jnp.asarray(starts, jnp.int32),
                latest_epoch=jnp.asarray(from_epoch + k, jnp.int32),
                epoch_base=jnp.asarray(from_epoch, jnp.int32)))
        runner.executor.carry = c._replace(out_rings=tuple(new_rings))
        stages.close()

        # Everything is failed; recover() rebuilds it all from the
        # checkpoint + mirror rows, in topological order. The ledger
        # derivation rides inside the replay window; recover() joins it
        # at the pre-patch point and bills only the blocked remainder.
        runner.failed = set(range(L))
        for f in range(L):
            runner.heartbeats.mark_dead(f)
        report = runner.recover(host_rows=mirror_rows,
                                pre_patch_join=_join_ledgers)
        stages.switch("finalize.edge-rehydrate")  # recover() timed itself

        # The depth-1 edge buffers (the in-flight batch produced at step
        # fence+n-1, consumed by the NEXT live step) are not part of
        # replay's input range — route that one step from the rebuilt
        # rings now.
        if n_steps > 0:
            c = runner.executor.carry
            progs = runner.failover.programs
            bufs = list(c.edge_bufs)
            for eidx, e in enumerate(job.edges):
                ri = runner.executor.compiled.ring_index[e.src]
                z = jnp.asarray(0, jnp.int32)
                routed, *_ = progs.route_chunk(eidx, progs.chunk, True)(
                    c.out_rings[ri],
                    jnp.asarray(fence + n_steps - 1, jnp.int32),
                    z, jnp.asarray(1, jnp.int32), z)
                bufs[eidx] = jax.tree_util.tree_map(
                    lambda x: x[0], routed)
            runner.executor.carry = c._replace(edge_bufs=tuple(bufs))
        else:
            # Nothing replayed: the completed fence IS the rebuild point,
            # and the lean snapshot's depth-1 edge buffers (produced at
            # step fence-1, consumed by the next live step) are the only
            # copy of that in-flight batch — the rings below the fence
            # were truncated on completion and are not rebuilt.
            c = runner.executor.carry
            bufs = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x).copy(), ckpt.carry.edge_bufs)
            runner.executor.carry = c._replace(edge_bufs=tuple(bufs))
        stages.close()

        # Join the overlap worker (host-RNG fast-forward + first-step
        # AOT warm) — the guarantee the first live step needs: the RNG
        # stream sits exactly past the replayed prefix and the block
        # executable is compiled. Only the blocked remainder extends
        # the critical path; the rest overlapped replay.
        t_j2 = _time.monotonic()
        worker.join()
        if ov["err"] is not None:
            raise ov["err"]

        # Fold the rebuild stages into the report: they extend the
        # finalize phase (everything-after-replay). Overlap is
        # attributed, never hidden — each finalize.* sub-span keeps its
        # TRUE wall (the derivation/warm thread time), only the blocked
        # remainders extend the finalize total, and the difference is
        # credited to finalize.overlap-saved, preserving the invariant
        # sum(finalize.* sub-spans) - overlap-saved == finalize.
        for name, ms in sub_ms.items():
            report.phase_ms[name] = report.phase_ms.get(name, 0.0) + ms
            report.phase_ms["finalize"] = (
                report.phase_ms.get("finalize", 0.0) + ms)
            runner._mgroup.histogram(f"recovery.{name}-ms").update(ms)
        # (recover()'s own listener-reattach entry, the wall of its
        # join, is replaced by the derivation's true wall.)
        derive_ms = (ov["derive"][1] - ov["derive"][0]) * 1e3
        warm_ms = (ov["warm"][1] - ov["warm"][0]) * 1e3
        blocked_ms = (_exposed_ms(*ov["derive"], ov["derive_wait"])
                      + _exposed_ms(*ov["warm"], t_j2))
        report.phase_ms["finalize.listener-reattach"] = derive_ms
        report.phase_ms["finalize.first-step-recompile"] = (
            report.phase_ms.get("finalize.first-step-recompile", 0.0)
            + warm_ms)
        report.phase_ms["finalize"] = (
            report.phase_ms.get("finalize", 0.0) + blocked_ms)
        report.phase_ms["finalize.overlap-saved"] = (
            report.phase_ms.get("finalize.overlap-saved", 0.0)
            + derive_ms + warm_ms - blocked_ms)
        for name in ("finalize.listener-reattach",
                     "finalize.first-step-recompile",
                     "finalize.overlap-saved"):
            runner._mgroup.histogram(f"recovery.{name}-ms").update(
                report.phase_ms[name])
        return runner, report

    @classmethod
    def restore_rescaled(cls, job_new: JobGraph, job_old: JobGraph,
                         ckpt: cp.CompletedCheckpoint,
                         **runner_kw) -> "ClusterRunner":
        """Restore a completed checkpoint into a job whose keyed vertices
        run at a DIFFERENT parallelism (the planned-rescale restart;
        reference CheckpointCoordinator.restoreSavepoint ->
        StateAssignmentOperation with KeyGroupRangeAssignment). Dense
        keyed state splits/merges by key-group ownership
        (Operator.rescale_keyed_state); checkpointed depth-1 edge
        buffers re-route through the hash exchange at the new
        parallelism. The restored incarnation starts a fresh causal-log
        epoch 0 — a rescale is a planned restart at a completed fence,
        so there is nothing to replay.

        Constraints: topology (vertex count, operator types, edge
        partition kinds) must match; rescaled vertices' input edges must
        be HASH (key ownership defines the split); vertices without a
        keyed rescaling story must keep their parallelism."""
        if len(job_new.vertices) != len(job_old.vertices) or \
                len(job_new.edges) != len(job_old.edges):
            raise rec.RecoveryError(
                "restore_rescaled: topology mismatch between jobs")
        runner = cls(job_new, **runner_kw)
        cpy = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).copy(), t)
        snap = ckpt.carry
        carry = runner.executor.carry
        ops = list(carry.op_states)
        for v_new, v_old in zip(job_new.vertices, job_old.vertices):
            if type(v_new.operator) is not type(v_old.operator):
                raise rec.RecoveryError(
                    f"restore_rescaled: vertex {v_new.vertex_id} operator "
                    f"type changed")
            vid = v_new.vertex_id
            st = cpy(snap.op_states[vid])
            if v_new.parallelism == v_old.parallelism:
                ops[vid] = st
            else:
                for eidx in job_new.in_edges(vid):
                    if job_new.edges[eidx].partition != PartitionType.HASH:
                        raise rec.RecoveryError(
                            f"restore_rescaled: vertex {vid} rescaled but "
                            f"input edge {eidx} is not HASH-partitioned")
                ops[vid] = v_new.operator.rescale_keyed_state(
                    st, v_new.parallelism, job_new.num_key_groups)
        bufs = list(carry.edge_bufs)
        for eidx, (e_new, e_old) in enumerate(zip(job_new.edges,
                                                  job_old.edges)):
            if e_new.partition != e_old.partition:
                raise rec.RecoveryError(
                    f"restore_rescaled: edge {eidx} partition changed")
            old_buf = cpy(snap.edge_bufs[eidx])
            dst_p = job_new.vertices[e_new.dst].parallelism
            if e_new.partition == PartitionType.HASH:
                raw = jax.tree_util.tree_map(lambda x: x[None], old_buf)
                routed, dropped = routing.route_hash_block(
                    raw, dst_p, job_new.num_key_groups, e_new.capacity)
                # Rescaling DOWN concentrates old lanes' records; an
                # overflow here would silently lose in-flight records
                # and break the identical-output contract — fail loud.
                if int(np.asarray(dropped).sum()) > 0:
                    raise rec.RecoveryError(
                        f"restore_rescaled: edge {eidx} buffer overflows "
                        f"capacity {e_new.capacity} at parallelism "
                        f"{dst_p} — widen the edge capacity of the "
                        f"rescaled job")
                bufs[eidx] = jax.tree_util.tree_map(
                    lambda x: x[0], routed)
            else:
                want = bufs[eidx].keys.shape
                if old_buf.keys.shape != want:
                    raise rec.RecoveryError(
                        f"restore_rescaled: edge {eidx} buffer shape "
                        f"{old_buf.keys.shape} != {want} and the edge is "
                        f"not HASH-rescalable")
                bufs[eidx] = old_buf
        runner.executor.carry = carry._replace(
            op_states=tuple(ops), edge_bufs=tuple(bufs))
        return runner

    def rescale_live(self, job_new: JobGraph,
                     observers: Sequence = (),
                     feed_readers: Optional[Dict[int, object]] = None,
                     **runner_kw
                     ) -> Tuple["ClusterRunner", Dict[str, Any]]:
        """Elastic re-cut under live traffic: at THIS runner's completed
        checkpoint fence, stand up a new incarnation of the job at a
        different keyed parallelism and hand off exactly once — no
        record lost, none duplicated. The verified protocol
        (verify/models.RepartitionModel) is fence → drain → migrate →
        redirect, driven through a
        :class:`~clonos_tpu.runtime.scheduler.RescaleCoordinator` whose
        ``transition_observers`` conformance hooks fire at every step.

        fence    — the latest COMPLETED checkpoint is the handoff point
                   (the caller just ran ``run_epoch``, so the fence
                   seals every epoch up to ``epoch_id - 1``; the ledger
                   certifies them).
        drain    — the old lanes' in-flight edge buffers were captured
                   IN that checkpoint; counting them into the migration
                   payload is the drain (nothing is dropped on the
                   floor: route_hash_block re-cuts them below).
        migrate  — keyed state splits/merges by key-group ownership and
                   the drained buffers re-route at the new parallelism
                   (``restore_rescaled``); the old↔new group directory
                   comes from the audit layer
                   (obs/audit.key_group_directory) — the same mapping
                   ``audit A --diff B`` uses, built once and reused.
        redirect — the new incarnation adopts the epoch cursor, ledger
                   and RNG stream mid-run (the ``bootstrap_standby``
                   zero-replay surgery) and the OLD incarnation is
                   fenced off: its subtasks are marked failed so a
                   stale ``run_epoch``/``step`` raises instead of
                   double-applying records.

        Returns ``(new_runner, stats)``; the caller rebinds its handle
        (and re-homes any read tier: ``ServeTier.rehome``). ``stats``
        reports the fence checkpoint, drained record count, moved key
        groups per rescaled vertex, and the observed protocol
        transitions."""
        from clonos_tpu.obs import audit as _audit_mod
        from clonos_tpu.runtime.scheduler import RescaleCoordinator
        if self.failed:
            raise rec.RecoveryError(
                f"rescale_live: failed subtasks {sorted(self.failed)} — "
                f"recover() first; a re-cut needs a healthy fence")
        self.drain_fence()
        if self.executor.step_in_epoch != 0:
            raise rec.RecoveryError(
                f"rescale_live: mid-epoch (step {self.executor.step_in_epoch}"
                f"/{self.executor.steps_per_epoch}) — a re-cut happens at "
                f"an epoch fence; finish the epoch first")
        ids = self.coordinator.storage.completed_ids()
        if not ids:
            raise rec.RecoveryError(
                "rescale_live: no completed checkpoint — the fence the "
                "re-cut hands off at does not exist yet")
        ckpt = self.coordinator.storage.read(max(ids))
        if ckpt.checkpoint_id != self.executor.epoch_id - 1:
            raise rec.RecoveryError(
                f"rescale_live: latest completed checkpoint "
                f"{ckpt.checkpoint_id} is not the current fence "
                f"(epoch {self.executor.epoch_id - 1}) — run the epoch "
                f"to completion (complete_checkpoint=True) first")
        tr = get_tracer()
        job_old = self.job

        # The re-cut's control plane: one group per OLD lane of each
        # rescaled vertex. Guards on the coordinator refuse exactly the
        # orderings the model's seeded bugs inject.
        rescaled = [(v_new, v_old)
                    for v_new, v_old in zip(job_new.vertices,
                                            job_old.vertices)
                    if v_new.parallelism != v_old.parallelism]
        lanes: List[Tuple[int, int]] = []   # (vertex_id, old lane)
        for v_new, v_old in rescaled:
            lanes += [(v_old.vertex_id, s)
                      for s in range(v_old.parallelism)]
        coord = RescaleCoordinator(len(lanes))
        events: List[tuple] = []
        coord.transition_observers.append(
            lambda kind, **f: events.append((kind, tuple(sorted(f.items())))))
        coord.transition_observers.extend(observers)

        # Per-old-lane in-flight counts: the depth-1 edge buffers the
        # fence checkpoint captured (the records "in the pipe" at the
        # handoff point).
        inflight = [0] * len(lanes)
        for g, (vid, lane) in enumerate(lanes):
            for eidx in job_old.in_edges(vid):
                buf = ckpt.carry.edge_bufs[eidx]
                inflight[g] += int(np.asarray(buf.valid)[lane].sum())
            if inflight[g]:
                coord.note_inflight(g, inflight[g])
        coord.fence(ckpt.checkpoint_id)

        # Migration: keyed-state surgery + edge-buffer re-route at the
        # new parallelism, from the SAME fence checkpoint.
        t_mig = _time.monotonic()
        runner = type(self).restore_rescaled(job_new, job_old, ckpt,
                                             **runner_kw)
        for vid, reader in (feed_readers or {}).items():
            runner.executor.register_feed(vid, reader)
        directories = {
            v_old.vertex_id: _audit_mod.key_group_directory(
                v_old.parallelism, v_new.parallelism,
                job_new.num_key_groups)
            for v_new, v_old in rescaled}
        for g, (vid, lane) in enumerate(lanes):
            if inflight[g]:
                coord.drain(g, inflight[g])
            coord.migrate(g)
        migrate_ms = (_time.monotonic() - t_mig) * 1e3

        # Epoch-continuity surgery (bootstrap_standby's zero-replay
        # recipe): the new incarnation resumes at the fence — same
        # epoch cursor, same global step, same host-RNG position — so
        # its next sealed epoch continues the adopted ledger.
        spe = runner.executor.steps_per_epoch
        from_epoch = ckpt.checkpoint_id + 1
        if ckpt.carry.ring_heads:
            fence = int(np.asarray(ckpt.carry.ring_heads[0]))
        else:
            fence = from_epoch * spe
        runner.global_step = fence
        runner.executor.step_input_history = [(0, 0)] * fence
        if runner.latency is not None:
            runner.latency._seen = fence
        runner.executor.epoch_id = from_epoch
        runner.executor.step_in_epoch = 0
        runner._fence_step[from_epoch] = fence
        runner._ring_tail_mirror = fence
        with runner._ck_heads_lock:
            runner._ck_log_heads[ckpt.checkpoint_id] = np.asarray(
                runner.executor.carry.logs.head).astype(np.int64)
        c = runner.executor.carry
        new_rings = []
        for el in c.out_rings:
            starts = np.asarray(el.epoch_starts).copy()
            starts[from_epoch % starts.shape[0]] = fence
            new_rings.append(el._replace(
                head=jnp.asarray(fence, jnp.int32),
                tail=jnp.asarray(fence, jnp.int32),
                epoch_starts=jnp.asarray(starts, jnp.int32),
                latest_epoch=jnp.asarray(from_epoch, jnp.int32),
                epoch_base=jnp.asarray(from_epoch, jnp.int32)))
        runner.executor.carry = c._replace(out_rings=tuple(new_rings))
        runner.executor.fast_forward_host_rng(fence)
        # The causal-time source is a live host object: the new
        # incarnation keeps ticking the OLD one's stream (a fresh
        # source would replay timestamps from zero and shift every
        # window fire). EXCEPT logical time, which is bound to its
        # executor's step_input_history — the new incarnation's own
        # (history rebuilt to the fence above) already resumes at the
        # right step, while the old one's is frozen at the fence.
        if not isinstance(self.executor.time_source, LogicalTimeSource):
            runner.executor.time_source = self.executor.time_source

        # Ledger adoption: the new incarnation carries the pre-re-cut
        # seals forward, so one continuous audit chain spans the
        # re-cut — post-re-cut epochs diff against pre-re-cut ones via
        # the group directory (diff_ledgers_cross), which is what makes
        # "no record lost or duplicated" checkable after the fact.
        if runner.auditor.enabled and self.auditor.enabled:
            runner.auditor.adopt(self.auditor.ledger())
        runner.last_sealed_epoch = max(runner.last_sealed_epoch,
                                       self.last_sealed_epoch)

        # Durable restore point in the NEW shape: re-fence the handoff
        # checkpoint over the re-cut carry, so a failure in the first
        # post-re-cut epoch recovers at the new parallelism instead of
        # finding an old-shaped snapshot.
        runner.coordinator.trigger(ckpt.checkpoint_id,
                                   runner.executor.lean_snapshot(),
                                   async_write=False, owned=True)
        runner.coordinator.ack_all(ckpt.checkpoint_id)

        # Redirect: every group is migrated (the coordinator verifies),
        # traffic belongs to the new incarnation, and the old one is
        # fenced off — a stale writer raises instead of double-applying.
        coord.redirect()
        self.failed = set(range(job_old.total_subtasks()))
        for f in self.failed:
            self.heartbeats.mark_dead(f)

        stats = {
            "fence_checkpoint": ckpt.checkpoint_id,
            "from_epoch": from_epoch,
            "groups": len(lanes),
            "drained_records": int(sum(inflight)),
            "moved_key_groups": {
                vid: len(_audit_mod.moved_key_groups(d))
                for vid, d in directories.items()},
            "migrate_ms": migrate_ms,
            "transitions": events,
        }
        tr.event("rescale.redirect", **{k: v for k, v in stats.items()
                                        if k != "transitions"})
        return runner, stats

    def attach_file_sink(self, vertex_id: int, root: str, election=None,
                         token: int = 0):
        """Back a transactional sink with durable part files
        (runtime/filesink.py — the StreamingFileSink analog): pendings
        persist at every epoch seal, commits are atomic renames, and
        stale pendings of a dead incarnation are swept now.

        ``election`` (a ``runtime.leader.FileLeaderElection`` or any
        object with ``is_leader()``) fences every mutating sink
        operation on leadership: when two incarnations share ``root``
        (the standby-takeover deployment this sink exists for), a
        fenced-off incarnation attaching here must NOT run the startup
        sweep — it would delete the healthy writer's in-progress
        pendings.

        ``token`` is the writer's fencing token (monotone incarnation
        number — e.g. bump it on each live re-cut); the startup sweep
        only ever deletes parts at or below it, so a stale incarnation
        attaching to a shared root cannot destroy a newer writer's
        in-progress parts even without a leadership handle."""
        from clonos_tpu.runtime.filesink import FileSystemSink
        if vertex_id not in self.txn_logs:
            raise ValueError(
                f"vertex {vertex_id} is not a transactional sink")
        fs = FileSystemSink(root, fencing=election, token=token)
        tl = self.txn_logs[vertex_id]
        tl.pre_committer = fs.write_pending
        tl.committer = fs.commit
        fs.sweep_pending(keep_epochs=tl.pending_epochs())
        return fs

    def state_digest(self) -> str:
        """Canonical digest of the recoverable job state: operator
        states, record counts, log heads and each log's live row window.
        A standby-host rebuild (bootstrap_standby) must reproduce the
        dead worker's digest at its last mirrored fence EXACTLY — the
        cross-process bit-identity check (reference: state handle
        equality on restore)."""
        import hashlib
        h = hashlib.sha1()
        for vid in range(len(self.job.vertices)):
            st = self.executor.vertex_state(vid)
            for k in sorted(st):
                h.update(np.asarray(st[k]).tobytes())
        c = self.executor.carry
        heads = np.asarray(c.logs.head)
        tails = np.asarray(c.logs.tail)
        rows = np.asarray(c.logs.rows)
        cap = rows.shape[1]
        h.update(heads.tobytes())
        for flat in range(rows.shape[0]):
            pos = np.arange(int(tails[flat]), int(heads[flat])) & (cap - 1)
            h.update(rows[flat][pos].tobytes())
        h.update(np.asarray(c.record_counts).tobytes())
        return h.hexdigest()

    # --- steady state --------------------------------------------------------

    def run_epoch(self, complete_checkpoint: bool = True) -> None:
        """Run to the next epoch fence and trigger its checkpoint.

        ``complete_checkpoint=False`` leaves the checkpoint pending (no
        acks): logs keep accumulating across epochs — the large-checkpoint-
        interval regime the spillable in-flight log exists for, and the
        setup for multi-epoch recovery gaps.

        A runner built with ``overlap_epoch=True`` runs the pipelined
        fence: the closed epoch's fence state is
        captured as device-side handles (async health d2h, epoch-window
        copies, lean snapshot) and the tail — health drain, audit seal,
        group-committed ledger append, async checkpoint write, spill
        digests — drains on a single fence-worker thread while the NEXT
        epoch's compute runs; the worker joins at the next fence, so at
        most one tail is ever in flight. Deferred with it, by at most
        one epoch, are the overflow check (re-run from the async health
        read before the ring can wrap twice; one epoch of ring headroom
        is asserted once), checkpoint completion/truncation, and ledger
        visibility — ``drain_fence()`` settles all of it on demand.
        ``overlap_epoch=False`` keeps the strict order, runs the tail
        inline and never writes the ``fence.overlap-saved``
        attribution key."""
        if self.failed:
            raise rec.RecoveryError(
                f"cannot run with failed subtasks {sorted(self.failed)}; "
                f"call recover() first")
        overlap = self.overlap_epoch
        if overlap and not self._fence_headroom_checked:
            self._check_fence_headroom()
        # Spill settles strictly: the in-flight worker
        # (attach_spill_digests) and this epoch's spill hook would
        # otherwise race on the host store, so join BEFORE dispatching
        # this epoch's compute.
        if (self._fence_tail is not None
                and self.executor.spill_logs is not None):
            self._join_fence_tail()
        closed = self.executor.epoch_id
        n = self.executor.steps_per_epoch - self.executor.step_in_epoch
        tr = get_tracer()
        prof = self.profiler
        with tr.span("epoch", epoch=closed, steps=n):
            with tr.span("epoch.steps") as steps:
                self.executor.run_epoch()
                if not overlap:
                    # Enabled profiler: fence the carry so "compute"
                    # measures execution, not dispatch (the fused block
                    # program = user compute + in-program causal/ring
                    # appends). Never on the overlapped path — this block
                    # would serialize exactly the window the pipeline
                    # hides, so overlapped "compute" is dispatch wall only.
                    prof.fence(self.executor.carry)
            self._m_epoch_steps_ms.update(steps.ms)
            prof.observe("compute", steps.dur, kind="compute")
            # The PREVIOUS epoch's tail joins here: after this epoch's
            # compute is dispatched (the tail overlapped it), before any
            # of this fence's state is touched. The join re-raises
            # worker errors, runs the deferred overflow check, and
            # acks/truncates its checkpoint on this (the main) thread.
            self._join_fence_tail()
            self.global_step += n
            self._fence_step[self.executor.epoch_id] = self.global_step
            self.heartbeats.beat_all_except(self.failed)
            self._m_steps.inc(n)
            self._m_epochs.inc()
            if self.latency is not None:
                self.latency.observe()
            if overlap:
                self._begin_fence_tail(closed, complete_checkpoint, prof)
            else:
                self._run_fence_tail_inline(closed, complete_checkpoint,
                                            prof)
            # Close the attribution window: FT seconds / (FT + compute)
            # since the previous fence -> the overhead.ft-fraction
            # gauge (a no-op returning 0.0 on the NullProfiler).
            prof.rollup()

    def _absorb_fence_health(self, closed: int, vec: np.ndarray) -> int:
        """Fold one fence's drained health vector into the host mirrors
        (runs inline on the sequential path, on the fence worker when
        pipelined). Returns the epoch's record delta."""
        parts = self.executor.health_parts(vec)
        total_records = int(parts["records"][0])
        # The heads at this fence ARE checkpoint ``closed``'s log
        # heads (the SOURCE_CHECKPOINT appends come after and belong
        # to the new epoch) — recovery's patch phase reads them from
        # here instead of paying a device round-trip on the failure
        # path.
        # Bounded even when checkpoints never complete (the completion
        # hook prunes harder). Epochs arrive in monotonic order, so
        # evicting in insertion order is oldest-first and O(1) — a
        # pruned-but-needed entry only costs the patch fallback's one
        # device read.
        with self._ck_heads_lock:
            self._ck_log_heads[closed] = parts["heads"].astype(np.int64)
            while len(self._ck_log_heads) > 128:
                self._ck_log_heads.pop(
                    next(iter(self._ck_log_heads)))
        delta_records = total_records - self._last_records_total
        self._m_records.mark(delta_records)
        self._last_records_total = total_records
        # Counters the same read feeds, each its growth since the last
        # fence: what every event-time window (and the window join)
        # dropped as late, fired, accepted a side (the operator's
        # ``fence_totals``), then the exchange — records an edge has
        # dropped, and the most a target of a dynamic edge has been sent
        # in one step; both only grow, and stay absent while 0; last the
        # operators' high-water marks (``fence_peaks``), fed alike. A
        # total that reads BELOW its last reading feeds nothing: a
        # recovery put a replayed lane's state in a victim's place, and
        # a diagnostic the one-lane replay does not count again
        # (``lookup.dense_blocks``) then stands at its checkpoint's.
        compiled = self.executor.compiled
        seen = [(f"{counter}.{v.name}", n, True) for (v, _, counter), n
                in zip(compiled.fence_total_slots(), parts["totals"])]
        seen += [(f"exchange.dropped_records.{compiled.edge_name(e)}", n,
                  False) for e, n in enumerate(parts["dropped"])]
        seen += [(f"exchange.peak_records.{compiled.edge_name(e)}", n, False)
                 for e, n in zip(compiled.peak_edges(), parts["peak"])]
        seen += [(f"{counter}.{v.name}", n, False) for (v, _, counter), n
                 in zip(compiled.fence_peak_slots(), parts["marks"])]
        tr = get_tracer()
        for counter, n, even_zero in seen:
            grown = max(
                int(n) - self._fence_counter_totals.get(counter, 0), 0)
            self._fence_counter_totals[counter] = int(n)
            if grown or even_zero:
                tr.count(counter, grown)
        return delta_records

    def _seal_and_trigger(self, closed: int, window_fn, snap_fn,
                          phases: Dict[str, float], prof,
                          async_write: bool) -> None:
        """The fence tail's persistence half, shared verbatim by both
        modes: audit seal over the closed epoch's causal surface,
        ledger append, spill digests, seal fan-out, checkpoint trigger.
        ``window_fn``/``snap_fn`` abstract WHERE the state comes from —
        the live carry (sequential) or captured device handles
        (pipelined) — so the digests are byte-identical either way."""
        # One window extraction feeds BOTH planes: the audit digest and
        # the read-replica delta feeds (runtime/serve.py) read the same
        # causal surface, so a serving-only run (audit off) still pays
        # exactly one extraction and a dual run pays no second one.
        win = (window_fn()
               if self.auditor.enabled or self.serve_feeds
               or self.lineage.enabled else None)
        if self.auditor.enabled:
            from clonos_tpu.obs import audit as _audit_mod
            with _fence_phase(phases, "fence.digest-seal", prof,
                                   "digest-seal"):
                dg = _audit_mod.digest_epoch_window(
                    closed, win, layout=self._audit_layout)
                self.auditor.seal(dg)
            with _fence_phase(phases, "fence.ledger-write", prof,
                                   "ledger-write"):
                self.coordinator.record_ledger(dg.to_entry())
            if self.executor.spill_logs is not None:
                # Segment index entries inherit the ledger's channel
                # fingerprints — spill/refill round-trips become
                # audit-verifiable (storage/tiered.py docstring).
                self.executor.attach_spill_digests(closed, dg)
            self.epoch_tracker.notify_epoch_sealed(closed, dg)
            self._m_audit_sealed.inc()
        # The seal stamp advances in both modes — the fence tail IS the
        # seal event queryable-state freshness is measured against.
        # max(): the pipelined fence may run this on the worker while a
        # drain-ordering edge case replays an older epoch's tail.
        self.last_sealed_epoch = max(self.last_sealed_epoch, closed)
        from clonos_tpu.obs import get_timeline
        tl = get_timeline()
        if tl.enabled:
            tl.record("epoch.seal", epoch=int(closed),
                      audited=bool(self.auditor.enabled))
        if self.serve_feeds:
            with _fence_phase(phases, "fence.serve-feed"):
                for fn in list(self.serve_feeds):
                    fn(closed, win)
        # Lineage capture at the seal (obs/lineage.py): scan the same
        # extracted window for dyed keys — plus the epoch's sink
        # transaction shards for termini (complete at the fence in
        # both modes; the pipelined path seals them on the main thread
        # before this worker starts). Null plane: no scan, no file.
        if self.lineage.enabled and win is not None:
            with _fence_phase(phases, "fence.lineage-observe"):
                self.lineage.observe_epoch(
                    closed, win,
                    num_key_groups=self.job.num_key_groups,
                    topology=self._lineage_topology,
                    parts={vid: tl.pending_shards(closed)
                           for vid, tl in self.txn_logs.items()})
        # Checkpoint at the fence: the lean fence snapshot (op state
        # + offsets; logs/rings are truncated on completion, not
        # persisted).
        with _fence_phase(phases, "fence.snapshot", prof, "snapshot"):
            self.coordinator.trigger(closed, snap_fn(),
                                     async_write=async_write, owned=True)
            if async_write:
                self.coordinator.drain()

    def _append_source_fence_determinant(self, closed: int,
                                         phases: Dict[str, float],
                                         prof) -> None:
        """The checkpoint-trigger RPC arrival is nondeterministic in
        the reference and logged by every source
        (StreamTask.performCheckpoint:833-840); fence-aligned here, but
        the determinant is still recorded for replay/wire parity — one
        fused device append for all sources, AFTER the fence capture /
        lean snapshot so the checkpointed log heads stay aligned with
        the fence offsets (the rows belong to the new epoch)."""
        if not self._source_flats:
            return
        t_ms = (self.executor.step_input_history[-1][0]
                if self.executor.step_input_history else 0)
        with _fence_phase(phases, "fence.source-append", prof,
                               "source-append"):
            self.executor.append_async_many(
                self._source_flats,
                det.SourceCheckpointDeterminant(
                    record_count=self.executor.global_record_stamp(),
                    checkpoint_id=closed, timestamp=t_ms))
            prof.fence(self.executor.carry.logs)

    def _run_fence_tail_inline(self, closed: int,
                               complete_checkpoint: bool, prof) -> None:
        """Today's strict fence order, inline on the calling thread —
        the sequential control. Phases land in ``last_fence_phases``
        under the same ``fence.*`` keys as the pipelined path, minus
        the overlap key (its absence marks the control run). Each key
        is the duration of the span of the same name; ``fence-tail`` is
        the ``fence`` span's."""
        phases: Dict[str, float] = {}
        tr = get_tracer()
        with tr.span("fence", epoch=closed, mode="inline") as fence:
            # One fused device read per epoch: overflow flags + record
            # total + fence log heads (one device→host sync per fence).
            with _fence_phase(phases, "fence.health-read", prof,
                                   "health-read"):
                vec = self.executor.health_vector()
            delta_records = self._absorb_fence_health(closed, vec)
            # Overflow guards at every roll: an un-truncated ring that
            # wrapped has silently clobbered recovery state — fail
            # loudly, never limp.
            violations = self.executor.overflow_messages(vec)
            if violations:
                raise OverflowError_("; ".join(violations))
            # Host epoch control plane mirrors the fence.
            self.epoch_tracker.inc_record_count(delta_records)
            self.epoch_tracker.start_new_epoch(self.executor.epoch_id)
            # Audit seal at the fence (obs/audit.py): digest the closed
            # epoch's causal surface while its log/ring windows are
            # still resident (completion below truncates them), persist
            # the ledger entry next to the checkpoint, and fan out on
            # the epoch tracker's seal bus. The SOURCE_CHECKPOINT
            # appends after the snapshot land past this epoch's window
            # end, so the seal is fence-exact.
            self._seal_and_trigger(
                closed, lambda: self.executor.epoch_window(closed),
                self.executor.lean_snapshot, phases, prof,
                async_write=False)
            self._append_source_fence_determinant(closed, phases, prof)
            self._seal_txns_and_run_hooks(closed, phases)
            if complete_checkpoint:
                # completion -> TransactionLog.commit -> log/ring
                # truncation -> feed-offset commit: what a consumer of
                # the sink waits for
                with _fence_phase(phases, "fence.ack"):
                    self.coordinator.ack_all(closed)
        phases["fence-tail"] = fence.ms
        self.last_fence_phases = phases
        self._m_epoch_fence_ms.update(fence.ms)

    def _seal_txns_and_run_hooks(self, closed: int,
                                 phases: Dict[str, float]) -> None:
        if self.txn_logs:
            with _fence_phase(phases, "fence.txn-seal"):
                for tl in self.txn_logs.values():
                    tl.seal(closed)
        # Before completion: ack_all truncates rings up to this fence,
        # so anything reading their fresh steps (edge exports) goes now.
        if self.fence_hooks:
            with _fence_phase(phases, "fence.hooks"):
                for hook in self.fence_hooks:
                    hook(closed)

    def _check_fence_headroom(self) -> None:
        """One epoch of ring headroom, asserted once: the pipelined
        fence defers the overflow check to the NEXT fence, so the
        in-flight rings must absorb one extra epoch of steps before
        wrapping — otherwise a wrap inside the deferral window silently
        clobbers the recovery state the check exists to protect.
        Spill-enabled runs are exempt (ring overflow is the spill
        tiers' concern, not the check's)."""
        self._fence_headroom_checked = True
        if self.executor.spill_logs is not None:
            return
        rings = self.executor.carry.out_rings
        if not rings:
            return
        min_steps = min(r.ring_steps for r in rings)
        spe = self.executor.steps_per_epoch
        if min_steps < 2 * spe:
            raise ValueError(
                f"overlap_epoch needs one epoch of ring headroom: "
                f"inflight_ring_steps={min_steps} < 2*steps_per_epoch="
                f"{2 * spe} — raise inflight_ring_steps or use the "
                f"sequential fence (overlap_epoch=False)")

    def _begin_fence_tail(self, closed: int, complete_checkpoint: bool,
                          prof) -> None:
        """Capture this fence's state as device-side handles and hand
        the tail to the single fence worker. Everything inside the
        overlap window stays dispatch-only — no host synchronization
        (lint rule overlap-window enforces it), so the next epoch's
        compute can be dispatched immediately behind it."""
        phases: Dict[str, float] = {}
        tr = get_tracer()
        with tr.span("fence", epoch=closed, mode="pipelined",
                     part="begin") as fence:
            # clonos: overlap-window-begin
            with _fence_phase(phases, "fence.capture"):
                handles = self.executor.capture_fence(
                    with_window=self.auditor.enabled
                    or bool(self.serve_feeds) or self.lineage.enabled)
                snap = self.executor.lean_snapshot()
            self._append_source_fence_determinant(closed, phases, prof)
            # clonos: overlap-window-end
            self._seal_txns_and_run_hooks(closed, phases)
            parent = tr.current_span()    # the worker's spans hang here
        tail = {"epoch": closed, "complete": complete_checkpoint,
                "handles": handles, "snap": snap, "phases": phases,
                "pre_ms": fence.ms, "vec": None, "err": None,
                "parent": parent}
        th = threading.Thread(target=self._fence_worker, args=(tail, prof),
                              name="fence-tail", daemon=True)
        tail["thread"] = th
        self._fence_tail = tail
        th.start()

    def _fence_worker(self, tail: dict, prof) -> None:
        """Fence-tail drain, off the critical path: drain the async
        health d2h, fold the host mirrors, advance the epoch control
        plane, then seal + ledger + checkpoint from the captured
        handles and make the snapshot durable (coordinator.drain before
        exit). Errors are held and re-raised at the join; the overflow
        check on the drained health vector is ALSO deferred to the join
        — it must run on the main thread, like the checkpoint ack whose
        completion listeners mutate executor state. Its spans are
        children of the ``fence`` span that started it."""
        closed = tail["epoch"]
        phases = tail["phases"]
        try:
            with get_tracer().attach(tail["parent"]):
                with _fence_phase(phases, "fence.health-read", prof,
                                       "health-read"):
                    vec = tail["handles"].health()
                tail["vec"] = vec
                delta_records = self._absorb_fence_health(closed, vec)
                self.epoch_tracker.inc_record_count(delta_records)
                # By value, not executor.epoch_id: the main thread may
                # have dispatched further epochs by the time this runs.
                self.epoch_tracker.start_new_epoch(closed + 1)
                self._seal_and_trigger(
                    closed, tail["handles"].window, lambda: tail["snap"],
                    phases, prof, async_write=True)
        except BaseException as e:      # re-raised at the join
            tail["err"] = e

    def _join_fence_tail(self) -> None:
        """Join the in-flight fence tail. Main thread only: the
        deferred overflow check and the checkpoint ack — whose
        completion listeners truncate logs/rings by replacing
        ``executor.carry`` — must interleave with steps, never with
        them. Also closes the tail's attribution: sub-spans keep their
        true walls, ``fence-tail`` is the critical-path wall actually
        paid (the two ``fence`` spans: capture, then join with its
        ack), and the difference is credited to
        ``fence.overlap-saved``, preserving
        sum(fence.*) - overlap-saved == fence-tail."""
        tail = self._fence_tail
        if tail is None:
            return
        self._fence_tail = None
        tr = get_tracer()
        phases = tail["phases"]
        violations: List[str] = []
        try:
            with tr.span("fence", epoch=tail["epoch"], mode="pipelined",
                         part="join") as fence:
                with tr.span("fence.join"):
                    tail["thread"].join()
                if tail["err"] is None:
                    violations = self.executor.overflow_messages(
                        tail["vec"])
                    if not violations and tail["complete"]:
                        with _fence_phase(phases, "fence.ack"):
                            self.coordinator.ack_all(tail["epoch"])
        finally:
            tail_ms = tail["pre_ms"] + fence.ms
            spans = sum(v for k, v in phases.items()
                        if k.startswith("fence."))
            saved = max(0.0, spans - tail_ms)
            phases["fence-tail"] = tail_ms
            phases["fence.overlap-saved"] = saved
            self.fence_overlap_saved_total_ms += saved
            self.last_fence_phases = phases
            self._m_epoch_fence_ms.update(tail_ms)
        if tail["err"] is not None:
            raise tail["err"]
        if violations:
            raise OverflowError_(
                f"deferred fence check (pipelined fence, epoch "
                f"{tail['epoch']}): " + "; ".join(violations))

    def fence_tail_in_flight(self) -> bool:
        """True while a pipelined fence tail is still unjoined."""
        return self._fence_tail is not None

    def drain_fence(self) -> None:
        """Settle the pipelined fence completely: join the in-flight
        tail (running its deferred overflow check and checkpoint ack)
        and wait out async checkpoint writes — after this, ledger,
        completion, and truncation state match what a sequential run
        would show at the same fence."""
        self._join_fence_tail()
        self.coordinator.drain()

    def step(self) -> None:
        if self.failed:
            raise rec.RecoveryError("failed subtasks present; recover() first")
        self.executor.step()
        self.global_step += 1
        self._m_steps.inc()
        self.heartbeats.beat_all_except(self.failed)

    # --- the failure path (runtime/failover.py) ------------------------------

    def inject_failure(self, flat_subtasks: Sequence[int]) -> None:
        """Kill subtasks (:meth:`Failover.inject_failure`)."""
        self.failover.inject_failure(flat_subtasks)

    def detect_failures(self) -> List[int]:
        return self.heartbeats.expired()

    def recover(self, drill: bool = False, host_rows=None,
                pre_patch_join=None) -> RecoveryReport:
        """Recover every failed subtask (:meth:`Failover.recover`)."""
        return self.failover.recover(drill, host_rows, pre_patch_join)

    def prewarm_recovery(self) -> float:
        """Compile the recovery programs (:meth:`Failover.prewarm`)."""
        return self.failover.prewarm()

    def failover_drill(self, flats: Optional[Sequence[int]] = None) -> float:
        """Rehearse a failover end to end (:meth:`Failover.drill`)."""
        return self.failover.drill(flats)
