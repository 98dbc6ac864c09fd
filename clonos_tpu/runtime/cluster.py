"""Cluster runner: failure detection, standby management, causal recovery.

This is the control-plane layer tying the executor, checkpoint coordinator,
replication plan, and recovery FSM together — capability parity with the
reference's JobMaster-side machinery:

- ``HeartbeatMonitor``   <-  runtime/heartbeat (JobMaster.java:258-266)
- ``StandbyPool``        <-  ExecutionVertex.addStandbyExecution /
                             CheckpointCoordinator state dispatch (:1226)
- ``ClusterRunner``      <-  RunStandbyTaskStrategy.onTaskFailure
                             (failover/RunStandbyTaskStrategy.java:85):
                             remove failed, ignore unacked checkpoints,
                             back off the checkpoint interval, run the
                             standby through the recovery FSM (§3.4)

Failure model (TPU deployment semantics): the unit of loss is a subtask's
device-resident state — its operator-state slice, its thread causal log
row, the replica rows it holds for others, AND its shard of its vertex's
in-flight output ring (the producer's subpartition log dies with the
producer, exactly the reference's PipelinedSubpartition ownership).
Recovery rebuilds the lost ring shard from the replayed operator's
re-emitted batches — reconstruction, not just verification (reference
buildAndLogBuffer, PipelinedSubpartition.java:536-599).

"Local recovery instead of global rollback" (README.md:13-20): healthy
subtasks are never rolled back — the failed subtask alone is rebuilt from
the last checkpoint plus determinant replay, then patched into the live
carry. The proof obligation (and the test): the patched carry is
bit-identical to a never-failed run on the canonical (logically-live)
state — executor.canonical_carry.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time as _time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.causal import determinant as det
from clonos_tpu.causal import log as clog
from clonos_tpu.causal import recovery as rec
from clonos_tpu.causal import replication as rep
from clonos_tpu.graph.job_graph import JobGraph, PartitionType
from clonos_tpu.inflight import log as ifl
from clonos_tpu.ops.histogram import over_mesh
from clonos_tpu.parallel import routing
from clonos_tpu.runtime import checkpoint as cp
from clonos_tpu.obs import get_tracer
from clonos_tpu.obs.scopes import scoped
from clonos_tpu.storage import SegmentCorruptError, StorageError
from clonos_tpu.runtime.executor import (DETS_PER_STEP, JobCarry,
                                         LeanSnapshot, LocalExecutor,
                                         LogicalTimeSource)


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


@dataclasses.dataclass
class RecoveryReport:
    """What one failure's recovery did (metrics + test surface)."""

    failed_subtasks: Tuple[int, ...]
    from_epoch: int
    steps_replayed: int
    determinants_replayed: int
    records_replayed: int
    ignored_checkpoints: Tuple[int, ...]
    recovery_ms: float
    managers: Tuple[rec.RecoveryManager, ...]
    #: wall-clock per recovery phase (fetch_determinants / inputs / replay /
    #: patch / replica_rebuild) — the cold-recovery cost breakdown.
    phase_ms: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: True for failover rehearsals (failover_drill): excluded from the
    #: recovery metrics and the reports ledger.
    drill: bool = False
    #: bytes the shard-local restore actually moved: the failed subtasks'
    #: checkpoint slices + fetched determinant rows + replayed input
    #: windows. The paper's local-recovery claim in one number —
    #: ``restore_bytes < checkpoint_bytes`` says healthy shards kept
    #: their live buffers instead of rolling back.
    restore_bytes: int = 0
    #: bytes of the FULL checkpointed carry a global rollback would have
    #: re-loaded (the denominator for restore_bytes).
    checkpoint_bytes: int = 0


class OverflowError_(RuntimeError):
    """An un-checkpointed log/ring overflow was detected — the state is no
    longer recoverable and the control plane must not keep running."""


def _exposed_ms(start: float, end: float, wait_from: float) -> float:
    """Milliseconds of a worker's interval ``[start, end]`` that ran
    after the thread it works for began waiting on it at ``wait_from``:
    the part on the critical path (the rest ran under other work)."""
    return max(0.0, end - max(start, wait_from)) * 1e3


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
                 prewarm: bool = False,
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
        self._m_recovery_ms = g.histogram("recovery.duration-ms")
        self._m_recovered_records = g.counter("recovery.records-replayed")
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
        self._m_audit_matches = g.counter("audit.epochs-validated")
        self._m_audit_div = g.counter("audit.divergences")
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
        #: compiled recovery programs, keyed by (kind, params) — populated
        #: lazily and by prewarm_recovery() (warm standby: no XLA compile
        #: in the failure path).
        self._rjit: Dict[Any, Any] = {}
        import threading as _threading
        self._rjit_lock = _threading.Lock()
        #: routed edge-window cache, scoped to one vertex's failed
        #: subtasks within one recover() call (the exchange output is
        #: consumer-independent; see _replay_inputs). Populated only
        #: when the current vertex has >= 2 failed subtasks — the
        #: all-lane blocks are P-times a lane's size, so caching buys
        #: nothing for the common single-subtask failure.
        self._route_cache: Dict[Any, Any] = {}
        self._route_cache_enabled = False
        #: observability/test hook: cache hits in the last recover()
        self._route_cache_hits = 0
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
        #: recovery chunk size: larger than the live block trades a bigger
        #: prewarm compile for fewer per-chunk dispatches on the failure
        #: path.
        self._recovery_ch = min(
            recovery_block_steps or self.executor.block_steps,
            self.executor.compiled.inflight_ring_steps,
            self.executor.compiled.log_capacity // DETS_PER_STEP)
        if prewarm:
            self.prewarm_recovery()

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

    # --- compiled recovery programs ------------------------------------------

    def _jitted(self, key, make, donate=()):
        f = self._rjit.get(key)
        if f is None:
            with self._rjit_lock:
                f = self._rjit.get(key)
                if f is None:
                    compiled = self.executor.compiled
                    f = jax.jit(over_mesh(make(), compiled.mesh,
                                          compiled.task_axis),
                                donate_argnums=donate)
                    self._rjit[key] = f
        return f

    def _chunk(self) -> int:
        return self._recovery_ch

    def _fetch_fn(self):
        cap = self.executor.compiled.log_capacity
        return self._jitted(("fetch",), lambda: (
            lambda replicas, r, from_epoch: clog.get_determinants(
                jax.tree_util.tree_map(lambda x: x[r], replicas),
                from_epoch, cap)))

    def _fetch_meta_fn(self, h: int):
        """(count, start) of every holder's response in one device call —
        holders are bit-identical replicas by construction, so the host
        merge reduces to verifying the counts agree and pulling ONE body."""
        cap = self.executor.compiled.log_capacity

        def make():
            def f(replicas, rs, from_epoch):
                def one(r):
                    rep_one = jax.tree_util.tree_map(
                        lambda x: x[r], replicas)
                    off = clog.epoch_start_offset(rep_one, from_epoch)
                    cnt = jnp.clip(rep_one.head - off, 0, cap)
                    return jnp.stack([cnt, off])
                return jax.vmap(one)(rs)          # [h, 2]
            return f
        return self._jitted(("fetch_meta", h), make)

    def _pad_steps(self) -> int:
        ch = self._recovery_ch
        return -(-self.executor.compiled.inflight_ring_steps // ch) * ch

    def _device_parse_fn(self):
        """Parse a consistent replica's determinant stream ON DEVICE:
        locate the per-step sync anchors, extract the time/rng/expected
        lanes (padded to the replayer's fixed stream length), and report
        whether the stream is 'clean' (pure sync rows, exact layout).
        Only ~16 bytes of metadata cross the host link — the multi-MB
        log body stays on device (it IS the replica; the restore path
        copies it device-side too). Reference contrast: the JVM replayer
        walks the byte log on-heap (LogReplayerImpl.java:36-157)."""
        cap = self.executor.compiled.log_capacity
        maxn = self._pad_steps()
        k = DETS_PER_STEP

        def make():
            def f(replicas, r, from_epoch):
                buf, count, start = clog.get_determinants(
                    jax.tree_util.tree_map(lambda x: x[r], replicas),
                    from_epoch, cap)
                tags = buf[:, det.LANE_TAG]
                rowmask = jnp.arange(cap) < count
                cond = (rowmask & (tags == det.TIMESTAMP)
                        & (buf[:, det.LANE_RC] == 0))
                n_anchors = cond.sum().astype(jnp.int32)
                ids = jnp.nonzero(cond, size=maxn,
                                  fill_value=cap - k)[0].astype(jnp.int32)
                amask = jnp.arange(maxn) < n_anchors
                layout = jnp.all(
                    ~amask
                    | ((tags[ids + 1] == det.RNG)
                       & (tags[ids + 2] == det.ORDER)
                       & (tags[ids + 3] == det.BUFFER_BUILT)))
                clean = layout & (count == n_anchors * k)
                last = jnp.maximum(n_anchors - 1, 0)
                t_raw = buf[ids, det.LANE_P + 1]
                r_raw = buf[ids + 1, det.LANE_P]
                times = jnp.where(amask, t_raw, t_raw[last])
                rngs = jnp.where(amask, r_raw, r_raw[last])
                expected = jnp.where(amask, buf[ids + 3, det.LANE_P], 0)
                small = jnp.stack([count, start, n_anchors,
                                   clean.astype(jnp.int32)])
                return times, rngs, expected, small
            return f
        return self._jitted(("device_parse",), make)

    def _ring_bounds_dev(self):
        """Device [R, 2] (tail, head) of every in-flight ring — dispatch
        only; recover() folds the transfer into its packed reads."""
        if not self.executor.carry.out_rings:
            return None
        fn = self._jitted(("ring_bounds",), lambda: (
            lambda rings: jnp.stack(
                [jnp.stack([el.tail, el.head]) for el in rings])))
        return fn(self.executor.carry.out_rings)

    def _ring_bounds(self) -> Dict[int, Tuple[int, int]]:
        """(tail, head) of every in-flight ring in ONE device read — ring
        offsets don't move during recovery (write-backs change contents
        only), so recover() reads them once instead of twice per chunk."""
        dev = self._ring_bounds_dev()
        if dev is None:
            return {}
        arr = np.asarray(dev)
        return {ri: (int(arr[ri, 0]), int(arr[ri, 1]))
                for ri in range(arr.shape[0])}

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

    def _ring_chunk_fn(self, ri: int, m: int):
        return self._jitted(("ring_chunk", ri, m), lambda: (
            lambda el, start: ifl.slice_steps(el, start, m)))

    def _route_chunk_fn(self, eidx: int, m: int, all_lanes: bool = False):
        """Read + route one [m]-step window of edge ``eidx``'s producer
        ring — one program with the loop state (window start, leading
        skip, rebalance offset, remaining needed steps) carried ON
        DEVICE, so a chunk costs no host→device put of its own.

        Two variants, both prewarmed:
        - fused (default): the consumer's lane is selected INSIDE the
          program. Crucial for the single-failure case: XLA then scatters
          only that lane's rows (a general scatter runs ~row-at-a-time
          on TPU, so materializing all P lanes costs ~P times more).
        - ``all_lanes``: the full [m, P, cap] routed block — the routing
          is consumer-independent, so a connected multi-subtask failure
          routes each window ONCE and lane-selects per consumer (the
          reference re-serves the in-flight log per requesting channel;
          here the exchange is the expensive part and it is shared).

        Replay windows are UNIFORM: every window is m steps, the first
        starting one slot before the fence (that dead slot is masked by
        ``lead`` and later replaced by the checkpointed edge buffer) —
        one compiled program serves every chunk instead of a first-chunk
        (m-1) shape variant doubling the prewarm. ``need_left`` masks
        steps past the replay range invalid (the replay-padding
        contract); ``lead`` masks the leading dead slot of window 0."""
        def make():
            body = self._route_body(eidx, m)
            if all_lanes:
                def f(el, start, rr0, need_left, lead):
                    raw = ifl.slice_steps_at(el, start, m)
                    routed, cnt = body(raw, None, rr0, need_left, lead)
                    return (routed, start + m, rr0 + cnt, need_left - m,
                            jnp.zeros_like(lead))
            else:
                def f(el, start, sub, rr0, need_left, lead):
                    raw = ifl.slice_steps_at(el, start, m)
                    lane, cnt = body(raw, sub, rr0, need_left, lead)
                    return (lane, start + m, rr0 + cnt, need_left - m,
                            jnp.zeros_like(lead))
            return f
        return self._jitted(("route_chunk", eidx, m, all_lanes), make)

    def _lane_select_fn(self, eidx: int, m: int):
        """Select one consumer lane of a routed [m, P, cap] block."""
        return self._jitted(("lane_select", eidx, m), lambda: (
            lambda routed, sub: jax.tree_util.tree_map(
                lambda x: x[:, sub], routed)))

    def _route_body(self, eidx: int, m: int):
        """The shared exchange-replay body: mask the ``lead`` leading
        slots and steps past ``need_left`` invalid, then take the
        edge's route (``CompiledJob.route_edge``, the block program's
        own) — to all destination lanes (``sub`` None), or to the
        single consumer lane ``sub`` DIRECTLY, bit-identical to the full
        route's lane: a dynamic exchange then counts a [m, n] membership
        mask (routing._block_to_target_lane) instead of the [m, T, n]
        one-hot, a whole window of m steps in one piece where the full
        exchange goes chunk by chunk."""
        compiled = self.executor.compiled

        def body(raw, sub, rr0, need_left, lead):
            need = jnp.clip(need_left, 0, m)
            idx = jnp.arange(m, dtype=jnp.int32)
            live = (idx >= lead) & (idx < need)
            raw = raw._replace(valid=raw.valid & live[:, None, None])
            r, _ = compiled.route_edge(eidx, raw, rr0, lane=sub)
            return r, raw.count().sum()
        return scoped("exchange")(body)

    def _route_raw_fn(self, eidx: int, m: int, all_lanes: bool = False):
        """Spill-path twin of :meth:`_route_chunk_fn`: routes a
        host-assembled raw chunk instead of reading the device ring,
        advancing the same device-carried loop state."""
        def make():
            body = self._route_body(eidx, m)
            if all_lanes:
                def f(raw, start, rr0, need_left, lead):
                    routed, cnt = body(raw, None, rr0, need_left, lead)
                    return (routed, start + m, rr0 + cnt, need_left - m,
                            jnp.zeros_like(lead))
            else:
                def f(raw, start, sub, rr0, need_left, lead):
                    lane, cnt = body(raw, sub, rr0, need_left, lead)
                    return (lane, start + m, rr0 + cnt, need_left - m,
                            jnp.zeros_like(lead))
            return f
        return self._jitted(("route_raw", eidx, m, all_lanes), make)

    #: replica rows one call of the rebuild program copies: its scratch
    #: is this many log rows, not the whole replica set (which, gathered
    #: beside the carry, does not fit the chip once a job is deep)
    REPLICA_COPY_ROWS = 64

    def _replica_copy_fn(self):
        """``replicas[ri] = logs[oi]`` for ``REPLICA_COPY_ROWS`` pairs
        (``ri`` past the end: no row), in place on the donated replicas."""
        return self._jitted(("replica_copy",), lambda: (
            lambda replicas, logs, ri, oi: jax.tree_util.tree_map(
                lambda s, l: s.at[ri].set(l[oi], mode="drop"),
                replicas, logs)), donate=(0,))

    def _first_chunk_fn(self, eidx: int):
        """Replace the first window's dead leading slot with the
        checkpointed depth-1 edge buffer (replay step 0 consumes it)."""
        return self._jitted(("first_chunk", eidx), lambda: (
            lambda buf_sub, routed: jax.tree_util.tree_map(
                lambda a, b: b.at[0].set(a[0]), buf_sub, routed)))

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
            ch = runner._chunk()
            bufs = list(c.edge_bufs)
            for eidx, e in enumerate(job.edges):
                ri = runner.executor.compiled.ring_index[e.src]
                z = jnp.asarray(0, jnp.int32)
                routed, *_ = runner._route_chunk_fn(
                    eidx, ch, all_lanes=True)(
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
        # operators' high-water marks (``fence_peaks``), fed alike.
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
            grown = int(n) - self._fence_counter_totals.get(counter, 0)
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

    # --- failure injection ---------------------------------------------------

    def _inject_fn(self, vid: int):
        """One fused kill program per vertex class (eager per-array
        zeroing would copy the carry once per touched leaf)."""
        compiled = self.executor.compiled
        nr = compiled.plan.num_replicas

        def make():
            def f(carry, sub, flat, held_idx):
                fresh = clog.create(compiled.log_capacity,
                                    compiled.max_epochs)
                ops = list(carry.op_states)
                ops[vid] = jax.tree_util.tree_map(
                    lambda x: x.at[sub].set(jnp.zeros_like(x[sub])),
                    ops[vid])
                logs = jax.tree_util.tree_map(
                    lambda s, fr: s.at[flat].set(fr), carry.logs, fresh)
                replicas = carry.replicas
                if nr > 0:
                    replicas = jax.tree_util.tree_map(
                        lambda s, fr: s.at[held_idx].set(
                            jnp.broadcast_to(
                                fr, held_idx.shape + fr.shape),
                            mode="drop"),
                        replicas, fresh)
                rings = list(carry.out_rings)
                if vid in compiled.ring_index:
                    ri = compiled.ring_index[vid]
                    el = rings[ri]
                    rings[ri] = el._replace(
                        keys=el.keys.at[:, sub].set(0),
                        values=el.values.at[:, sub].set(0),
                        timestamps=el.timestamps.at[:, sub].set(0),
                        valid=el.valid.at[:, sub].set(False))
                return carry._replace(
                    op_states=tuple(ops), logs=logs, replicas=replicas,
                    out_rings=tuple(rings),
                    record_counts=carry.record_counts.at[flat].set(0))
            return f
        return self._jitted(("inject", vid), make, donate=(0,))

    def inject_failure(self, flat_subtasks: Sequence[int]) -> None:
        """Kill subtasks: zero their device state — operator slice, causal
        log row, held replica rows, and their shard of the vertex's
        in-flight output ring (the producer's subpartition log dies with
        the producer). (Fault-injection API the reference delegates to
        Jepsen, flink-jepsen/.)"""
        # A kill landing mid-pipelined-fence DRAINS the in-flight seal
        # deterministically: the tail belongs to an epoch every victim
        # completed healthy, so joining it first (seal + ledger +
        # checkpoint ack all land) makes the post-kill storage state a
        # pure function of the kill point — recovery then sees either a
        # completed fence or a cleanly pending one, never a half-sealed
        # epoch. So does the sink tap: a block still in flight (only
        # where an exception abandoned a block loop) is read before the
        # kill decides which pending shards are lost.
        self._join_fence_tail()
        self._read_sink_tap()
        carry = self.executor.carry
        nr = self.executor.compiled.plan.num_replicas
        for flat in flat_subtasks:
            self.failed.add(flat)
            self.heartbeats.mark_dead(flat)
            vid, sub = self._vertex_of(flat)
            held = np.full((max(nr, 1),), max(nr, 1), np.int32)
            hl = self.plan.replicas_held_by(flat)
            held[:len(hl)] = hl
            carry = self._inject_fn(vid)(
                carry, jnp.asarray(sub, jnp.int32),
                jnp.asarray(flat, jnp.int32), jnp.asarray(held))
        self.executor.carry = carry

    def _vertex_of(self, flat: int) -> Tuple[int, int]:
        for v in self.job.vertices:
            base = self.job.subtask_base(v.vertex_id)
            if base <= flat < base + v.parallelism:
                return v.vertex_id, flat - base
        raise ValueError(f"no subtask {flat}")

    # --- recovery (reference §3.4 signature path) ----------------------------

    def detect_failures(self) -> List[int]:
        return self.heartbeats.expired()

    def recover(self, drill: bool = False,
                host_rows: Optional[Dict[int, Tuple[np.ndarray, int]]]
                = None,
                pre_patch_join: Optional[Callable[[], None]] = None
                ) -> RecoveryReport:
        """Public entry for :meth:`_recover_impl` that additionally
        lands an incident bundle (obs/incident.py) when the protocol
        itself fails — a recovery that cannot complete is exactly the
        moment the forensic state (ledgers, determinant windows, HLC
        timeline) is about to become unreachable. No-op passthrough
        when the incident plane is disabled."""
        tr = get_tracer()
        # The phases run through one body, so they are a chain of
        # consecutive spans (children of ``recovery``) whose stamps also
        # fill ``RecoveryReport.phase_ms``.
        phases: Dict[str, float] = {}
        try:
            with tr.span("recovery", drill=bool(drill),
                         victims=sorted(self.failed)) as span, \
                    tr.chain("recovery.", into=phases,
                             drill=bool(drill)) as chain:
                report = self._recover_impl(
                    phases, chain, drill=drill, host_rows=host_rows,
                    pre_patch_join=pre_patch_join)
                span.set(from_epoch=report.from_epoch,
                         steps_replayed=report.steps_replayed,
                         records_replayed=report.records_replayed,
                         recovery_ms=report.recovery_ms)
                return report
        except Exception as e:
            from clonos_tpu.obs.incident import get_incidents
            get_incidents().signal(
                "recovery.failure",
                epoch=int(getattr(self.auditor, "last_epoch", -1)),
                error=f"{type(e).__name__}: {str(e)[:200]}",
                drill=bool(drill),
                failed=sorted(self.failed))
            raise

    def _recover_impl(self, phases: Dict[str, float], chain,
                      drill: bool = False,
                      host_rows: Optional[Dict[int, Tuple[np.ndarray, int]]]
                      = None,
                      pre_patch_join: Optional[Callable[[], None]] = None
                      ) -> RecoveryReport:
        """Run the full causal-recovery protocol for all failed subtasks,
        in topological order (an upstream's reconstructed ring shard feeds
        its downstream's replay — the reference's staged
        WaitingConnections/in-flight-request ordering).

        ``drill=True`` (failover rehearsal) runs the identical replay
        protocol but makes none of the failure-handling *decisions* —
        pending checkpoints are not ignored (they may yet complete),
        no IGNORE_CHECKPOINT determinants are logged, the checkpoint
        interval is not backed off, and recovered timer effects are not
        re-fired — so the job state is bit-identical afterwards.

        ``host_rows`` maps flat subtask -> (rows, abs_start): an external
        determinant source that replaces the on-device replica fetch for
        those subtasks — the standby-HOST path, where the rows come from
        a RemoteReplicaMirror after a whole-host loss (reference
        DeterminantResponseEvent arriving over the wire instead of the
        local piggyback channel).

        The finalize drains the final packed barrier-read on a worker
        thread while the main thread runs the audit validator, with an
        explicit join + deferred-assert check before returning; revive
        bookkeeping runs only after the join and state-verify pass (a
        failed verify leaves the subtasks marked dead, and an audit
        divergence is re-raised after verify and revive).

        ``pre_patch_join`` is the bootstrap-overlap hook: a callable
        joined (once) immediately before the FIRST ``_patch`` call —
        the earliest point recovery reads the roll-gap/async ledgers a
        bootstrap derives on a worker thread concurrently with this
        replay. Its blocked wall is attributed to
        ``finalize.listener-reattach``, not to the patch phase."""
        if not self.failed:
            raise rec.RecoveryError("no failed subtasks")
        # Defensive: inject_failure already drains the pipelined fence,
        # but recovery must never run against a half-sealed tail.
        self._join_fence_tail()
        if not self.standbys.has_state():
            raise rec.RecoveryError(
                "no completed checkpoint to restore standbys from")
        t0 = _time.monotonic()
        chain.switch("restore")
        topo_pos = {vid: i for i, vid in
                    enumerate(self.executor.compiled.topo)}
        failed = tuple(sorted(
            self.failed, key=lambda f: (topo_pos[self._vertex_of(f)[0]], f)))

        # (1) RunStandbyTaskStrategy.onTaskFailure: ignore checkpoints the
        # dead tasks never acked; back off the checkpoint interval.
        ignored: Tuple[int, ...] = ()
        if not drill:
            ignored = tuple(self.coordinator.ignore_unacked_for(set(failed)))
            self.coordinator.backoff()
            # Healthy tasks log the ignore decision (reference
            # StreamTask.ignoreCheckpoint:891-915 — the RPC arrival is a
            # determinant so their own later recoveries replay it).
            healthy = [f for f in range(self.job.total_subtasks())
                       if f not in self.failed]
            for cid in ignored:
                self.executor.append_async_many(
                    healthy, det.IgnoreCheckpointDeterminant(
                        record_count=self.executor.global_record_stamp(),
                        checkpoint_id=cid))

        ckpt = self.standbys.latest
        from_epoch = ckpt.checkpoint_id + 1
        fence = self._fence_step[from_epoch]
        n_steps = self.global_step - fence
        snap: LeanSnapshot = jax.tree_util.tree_map(jnp.asarray, ckpt.carry)
        managers: List[rec.RecoveryManager] = []
        total_dets = 0
        total_records = 0
        # Shard-local restore accounting: bytes each failed subtask's
        # rehydration actually moves vs the full snapshot a global
        # rollback would re-load (the paper's local-recovery claim as a
        # measurable ratio; surfaces on the RecoveryReport).
        restore_bytes = 0
        checkpoint_bytes = (int(getattr(ckpt, "size_bytes", 0) or 0)
                            or cp.carry_nbytes(ckpt.carry))
        tr = get_tracer()
        patched = self.executor.carry
        # Ring bounds for routing coverage decisions: the host mirror
        # (tails move only at checkpoint completion, heads advance one
        # per superstep == global_step) when valid, else one device read.
        # The device values recovery actually used are re-checked in the
        # final packed read either way (fail-loud, not trust).
        bounds_dev = self._ring_bounds_dev()
        nrings = len(patched.out_rings)
        if self._ring_mirror_valid:
            # Heads advance once per superstep wherever the executor is
            # driven from; its own step ledger is the authoritative one.
            head_m = len(self.executor.step_input_history)
            self._bounds_cache = {
                ri: (self._ring_tail_mirror, head_m)
                for ri in range(nrings)}
        else:
            barr = (np.asarray(bounds_dev) if nrings
                    else np.zeros((0, 2), np.int32))
            self._bounds_cache = {ri: (int(barr[ri, 0]), int(barr[ri, 1]))
                                  for ri in range(nrings)}
        self._route_cache = {}
        self._route_cache_hits = 0
        vid_failed_counts: Dict[int, int] = {}
        for flat in failed:
            v_of = self._vertex_of(flat)[0]
            vid_failed_counts[v_of] = vid_failed_counts.get(v_of, 0) + 1
        prev_vid = None
        chain.switch("fetch_determinants")

        # ---- phase A: determinant metadata for ALL failed subtasks ----
        # Dispatch every per-subtask parse/meta program up front, then pay
        # at most ONE host read for the whole failure set. Subtasks whose
        # cleanness the host can derive itself (no async rows since the
        # fence — executor.async_counts ledger — and fence log heads in
        # hand) skip even that: their metadata becomes deferred asserts
        # in the final packed read, and their replay defers its sync too:
        # every host read stalls the dispatch queue behind it.
        with self._ck_heads_lock:
            ck_heads = self._ck_log_heads.get(ckpt.checkpoint_id)
        from clonos_tpu.api.operators import HostFeedSource
        prep: Dict[int, Dict[str, Any]] = {}
        slow_reads: List[Tuple[int, str, Any]] = []
        for flat in failed:
            vid_a, _sub_a = self._vertex_of(flat)
            v_a = self.job.vertices[vid_a]
            if host_rows is not None and flat in host_rows:
                # External determinant source (standby-host mirror):
                # no device fetch/parse to dispatch at all.
                prep[flat] = {"holders": [], "fast": False, "host": True}
                continue
            holders_a = [
                (r, h) for r, (o, h) in enumerate(self.plan.pairs)
                if o == flat and h not in self.failed]
            p: Dict[str, Any] = {"holders": holders_a}
            eligible = (bool(holders_a) and n_steps > 0
                        and v_a.operator.replay_pad_safe
                        and not isinstance(v_a.operator, HostFeedSource)
                        and n_steps <= self._pad_steps())
            if eligible:
                t_d, r_d, e_d, small_d = self._device_parse_fn()(
                    patched.replicas,
                    jnp.asarray(holders_a[0][0], jnp.int32),
                    jnp.asarray(from_epoch, jnp.int32))
                p["det_device"] = (t_d, r_d, e_d)
                p["small_d"] = small_d
            if holders_a:
                hidx_a = jnp.asarray([r for r, _ in holders_a], jnp.int32)
                p["meta_d"] = self._fetch_meta_fn(len(holders_a))(
                    patched.replicas, hidx_a,
                    jnp.asarray(from_epoch, jnp.int32))
            p["fast"] = (eligible and ck_heads is not None
                         and vid_a not in self.txn_logs
                         and self.executor.async_rows_since(
                             flat, from_epoch) == 0)
            if not p["fast"]:
                if "small_d" in p:
                    slow_reads.append((flat, "small", p["small_d"]))
                if "meta_d" in p:
                    slow_reads.append((flat, "meta", p["meta_d"]))
            prep[flat] = p
        slow_vals: Dict[Tuple[int, str], np.ndarray] = {}
        if slow_reads:
            packed_a = np.asarray(jnp.concatenate(
                [d.reshape(-1).astype(jnp.int32)
                 for _f, _k, d in slow_reads]))
            off_a = 0
            for flat, kind, d in slow_reads:
                nsz = int(np.prod(d.shape))
                slow_vals[(flat, kind)] = packed_a[
                    off_a: off_a + nsz].reshape(d.shape)
                off_a += nsz

        for flat in failed:
            chain.switch("fetch_determinants")
            vid, sub = self._vertex_of(flat)
            if vid != prev_vid:
                # Routed windows are valid only while the upstream rings
                # they read are final — scope the share to one vertex's
                # consumers (upstream vertices were patched earlier in
                # topological order). The cache holds full [m, P, cap]
                # blocks, so bound its bytes: past the budget every
                # consumer takes the fused per-lane path instead of an
                # OOM mid-recovery.
                self._route_cache = {}
                share = vid_failed_counts[vid] >= 2
                if share and n_steps > 0:
                    ch_ = self._chunk()
                    nblocks_ = -(-n_steps // ch_)
                    est = sum(
                        nblocks_ * ch_
                        * self.job.vertices[self.job.edges[e2].dst
                                            ].parallelism
                        * self.job.edges[e2].capacity * 4 * 4
                        for e2 in self.job.in_edges(vid))
                    share = est <= (1 << 30)
                self._route_cache_enabled = share
                prev_vid = vid
            v = self.job.vertices[vid]
            mgr = rec.RecoveryManager(vid, sub, flat,
                                      self._make_replayer(vid, sub))
            managers.append(mgr)
            in_edges = self.job.in_edges(vid)
            out_edges = self.job.out_edges(vid)

            # FSM: standby -> connections re-established + state restored.
            mgr.notify_start_recovery(in_edges, out_edges)
            mgr.notify_state_restoration_complete()
            for e in in_edges:
                mgr.notify_new_input_channel(e)
            for e in out_edges:
                mgr.notify_new_output_channel(e)

            # DeterminantRequest flood to surviving holders of this log
            # (programs were dispatched in phase A; values arrive either
            # from the phase-A packed read or — fast path — as deferred
            # asserts in the final one).
            p = prep[flat]
            holders = p["holders"]
            fast = p["fast"]
            synthesized = False
            if p.get("host"):
                # Mirror-sourced determinants (whole-host loss): the rows
                # arrived over the wire; everything downstream of the
                # fetch (merge, replay, verify, patch) is identical.
                rows_h, start_h = host_rows[flat]
                mgr.expect_determinant_responses(1)
                mgr.notify_determinant_response(
                    np.asarray(rows_h, np.int32), int(start_h))
            elif not holders and n_steps > 0:
                if out_edges:
                    raise rec.RecoveryError(
                        f"subtask {flat}: no surviving replica holds its "
                        f"determinant log (sharing depth / replication "
                        f"factor too shallow for this failure pattern)")
                # Pure sink: nobody downstream replicates its log. Its
                # inputs replay exactly from the upstream ring; its own
                # nondeterminism (time/rng step inputs) is re-synthesized
                # from the coordinator's input ledger. (The reference has
                # the same boundary: sink exactly-once needs transactional
                # sinks, TwoPhaseCommitSinkFunction.)
                synthesized = True
            r_best = None
            det_device = None
            clean_n = None
            if p.get("host"):
                pass          # responses already delivered above
            elif fast:
                # Host-derived cleanness: zero async rows since the fence
                # means the log holds exactly n_steps k-row sync blocks
                # starting at the checkpointed head. Everything the old
                # metadata read returned is therefore known here; the
                # device parse/meta values become deferred asserts.
                ck_head_f = int(ck_heads[flat])
                det_device = p["det_device"]
                clean_n, clean_start = DETS_PER_STEP * n_steps, ck_head_f
                r_best = holders[0][0]
                mgr.expect_determinant_responses(1)
                mgr.notify_determinant_response(
                    np.zeros((0, det.NUM_LANES), np.int32), clean_start)
            elif holders:
                # Holders are bit-identical replicas by construction, so
                # when their metadata agrees the merge is "pull one body"
                # (saves H-1 multi-MB transfers + 2(H-1) round-trips).
                meta = slow_vals[(flat, "meta")]
                consistent = (len(np.unique(meta[:, 0])) == 1
                              and len(np.unique(meta[:, 1])) == 1)
                # Clean path off the ledger fast lane: the device parse
                # (phase A) says whether the stream is pure sync rows; if
                # so the multi-MB body never crosses the host link.
                if consistent and (flat, "small") in slow_vals:
                    cnt_s, start_s, nanch, cleanflag = (
                        int(x) for x in slow_vals[(flat, "small")])
                    if cleanflag and nanch == n_steps:
                        det_device = p["det_device"]
                        clean_n, clean_start = cnt_s, start_s
                        mgr.expect_determinant_responses(1)
                        mgr.notify_determinant_response(
                            np.zeros((0, det.NUM_LANES), np.int32),
                            start_s)
                if det_device is None:
                    use = ([holders[0]] if consistent else holders)
                    mgr.expect_determinant_responses(len(use))
                    fetch = self._fetch_fn()
                    for j, (r, _h) in enumerate(use):
                        buf, count, start = fetch(
                            patched.replicas, jnp.asarray(r, jnp.int32),
                            jnp.asarray(from_epoch, jnp.int32))
                        mgr.notify_determinant_response(
                            np.asarray(buf)[: int(meta[j, 0])],
                            int(meta[j, 1]))
                # A single consistent replica's device bytes can restore
                # the log directly; disagreeing holders must go through
                # the host merge (r_best None -> chunked upload path).
                r_best = holders[0][0] if consistent else None
            else:
                mgr.expect_determinant_responses(0)
            if synthesized:
                rows = self._synthesize_det_rows(fence, n_steps)
                start = (int(ck_heads[flat]) if ck_heads is not None
                         else int(np.asarray(snap.log_heads[flat])))
            elif det_device is not None:
                rows = np.zeros((0, det.NUM_LANES), np.int32)
                start = clean_start
            else:
                rows, start = mgr.merged_determinants()
            total_dets += clean_n if clean_n is not None else len(rows)
            chain.switch("inputs")

            # Lost inputs: the checkpointed edge buffer (the depth-1 batch
            # spanning the fence) + the upstream rings' raw outputs,
            # re-routed through the deterministic exchange. Upstream ring
            # shards zeroed by a connected failure were rebuilt earlier in
            # this loop (topological order).
            from clonos_tpu.api.operators import (HostFeedSource,
                                                  TwoInputOperator)
            input_steps = None
            if isinstance(v.operator, TwoInputOperator):
                input_steps = list(zip(
                    self._replay_inputs(patched, snap, in_edges[0], sub,
                                        fence, n_steps),
                    self._replay_inputs(patched, snap, in_edges[1], sub,
                                        fence, n_steps)))
            elif in_edges:
                input_steps = self._replay_inputs(patched, snap, in_edges[0],
                                                  sub, fence, n_steps)
            elif isinstance(v.operator, HostFeedSource) and n_steps > 0:
                input_steps = self._reread_feed(vid, sub, snap, rows, n_steps)
            chain.switch("replay")

            plan = rec.ReplayPlan(
                vertex_id=vid, subtask=sub, flat_subtask=flat,
                from_epoch=from_epoch, input_steps=input_steps,
                det_rows=rows, det_start=start,
                checkpoint_op_state=snap.op_states[vid],
                n_steps=n_steps, verify_outputs=not synthesized,
                det_device=det_device)
            restore_bytes += rec.plan_restore_nbytes(plan)
            # Fast path: replay dispatches only — output-cut verification
            # and the consumed total ride the final packed read.
            result = mgr.run_replay(plan, defer_sync=fast)
            if not result.deferred:
                total_records += result.records_replayed
            # Re-fire recovered timer effects (rows are already spliced
            # into the rebuilt log; only the callback side-effects re-run —
            # reference LogReplayerImpl.triggerAsyncEvent:102).
            svc = self.timer_services.get(flat)
            if svc is not None and not drill:
                for _step_i, ad in result.async_events:
                    if isinstance(ad, det.TimerTriggerDeterminant):
                        svc.refire(ad)
            # Transactional sink: its pending transaction shards died with
            # the task — rebuild them from the replayed outputs BEFORE any
            # commit can run (2PC abort+regenerate; TwoPhaseCommitSink
            # recoverAndAbort analog).
            if vid in self.txn_logs and n_steps > 0:
                self.txn_logs[vid].drop_uncommitted_shards(sub)
                self._rebuild_txn_shards(vid, sub, result, from_epoch,
                                         fence, n_steps)
            chain.switch("patch")

            rebuilt = np.asarray(result.rebuilt_log_rows)
            # The regenerated determinant rows must equal the recovered ones
            # (bit-identical replay; reference post-replay log asserts).
            # Skipped when rebuilt IS the recovered buffer (clean path):
            # verify() already established the only re-derived lane
            # (BUFFER_BUILT) matches, and comparing a view against itself
            # would be dead work masquerading as a check.
            if not synthesized and not result.rebuilt_is_view \
                    and not np.array_equal(
                        rebuilt, rows[: rebuilt.shape[0]]):
                raise rec.RecoveryError(
                    f"subtask {flat}: replayed determinant stream diverges "
                    f"from the recovered log")

            if pre_patch_join is not None:
                # Bootstrap's ledger-derivation thread must land before
                # _patch reads roll_gap_async; the blocked remainder is
                # the non-overlapped listener-reattach cost (the rest
                # rode inside the replay window above).
                chain.switch("finalize.listener-reattach")
                pre_patch_join()
                chain.switch("patch")    # the wait is not the patch's
                pre_patch_join = None
            patched = self._patch(patched, snap, vid, sub, flat,
                                  result, rebuilt, from_epoch, fence,
                                  n_steps, replica_src=r_best,
                                  det_n=clean_n,
                                  clean_sync=det_device is not None,
                                  ck_head=(int(ck_heads[flat])
                                           if ck_heads is not None
                                           else None))
        chain.switch("replica_rebuild")

        # Replica rows held by revived subtasks: replicas are identical to
        # their owner's log by construction (same bulk appends), so rebuild
        # by copying the owner's (possibly just-restored) log row — one
        # batched scatter for the whole failure set.
        rs, os_ = [], []
        for flat in failed:
            for r in self.plan.replicas_held_by(flat):
                rs.append(r)
                os_.append(self.plan.pairs[r][0])
        # Fixed-size scatters (padded with out-of-range rows, mode=drop)
        # so one prewarmed program serves every failure-set size.
        n = self.REPLICA_COPY_ROWS
        for lo in range(0, len(rs), n):
            rs_p = np.full((n,), self.plan.num_replicas, np.int32)
            os_p = np.zeros((n,), np.int32)
            rs_p[:len(rs[lo:lo + n])] = rs[lo:lo + n]
            os_p[:len(os_[lo:lo + n])] = os_[lo:lo + n]
            patched = patched._replace(replicas=self._replica_copy_fn()(
                patched.replicas, patched.logs,
                jnp.asarray(rs_p), jnp.asarray(os_p)))

        self.executor.carry = patched
        self._bounds_cache = None
        self._route_cache = {}     # free the held routed device buffers

        # ---- final packed read: completion barrier + deferred asserts ----
        # ONE device->host transfer closes the protocol: the restored log
        # heads (graft landed), the ring bounds recovery routed against,
        # and for every fast-path subtask its parse/meta metadata, its
        # on-device output-cut verification flag, and its consumed total.
        # TPU programs execute in dispatch order, so this read — dispatched
        # last — is also the barrier the old device_sync(patched) was.
        # Sub-attribution: ``finalize.barrier-read`` = the packed
        # concatenate + d2h transfer (dispatch-order barrier: it pays
        # for every program still in flight), ``finalize.state-verify``
        # = the host-side deferred asserts. The transfer drains on a
        # worker thread while the main thread runs the audit validator
        # inside the same window; the sub-spans keep their true walls
        # and ``finalize.overlap-saved`` carries the credit, so
        # sum(finalize.*) - overlap-saved == finalize (overlap
        # attributed, never hidden). The join + deferred asserts run
        # before recover() returns — a mis-speculated fast-path replay
        # raises here, before any live step, with the audit validator
        # as an independent gate on the replayed state. Revive
        # bookkeeping runs after verify: a failed barrier/verify leaves
        # the subtasks marked dead so the failure is retryable, never
        # silently "healthy".
        # ``finalize`` is the chain's last span; its children below use
        # their own spans (the barrier's on whichever thread drains it).
        chain.switch("finalize")
        fin_span = tr.current_span()
        fin_before = phases.get("finalize", 0.0)
        fast_mgrs = [m for m in managers if prep[m.flat_subtask]["fast"]]
        with tr.span("recovery.finalize.barrier-dispatch",
                     drill=drill) as disp:
            fl_d = jnp.asarray(list(failed), jnp.int32)
            pieces = [patched.logs.head[fl_d].astype(jnp.int32)]
            if nrings:
                pieces.append(bounds_dev.reshape(-1).astype(jnp.int32))
            for m in fast_mgrs:
                pf = prep[m.flat_subtask]
                pieces += [
                    pf["small_d"].astype(jnp.int32),
                    pf["meta_d"].reshape(-1).astype(jnp.int32),
                    m.result.verify_ok_d.astype(jnp.int32).reshape(1),
                    m.result.consumed_d.astype(jnp.int32).reshape(1)]
            packed_f = jnp.concatenate(pieces)        # dispatch only
        phases["finalize.barrier-dispatch"] = (
            phases.get("finalize.barrier-dispatch", 0.0) + disp.ms)
        barrier: Dict[str, Any] = {"arr": None, "err": None, "span": None}

        def _drain_barrier(parent) -> None:
            with tr.attach(parent):
                with tr.span("recovery.finalize.barrier-read",
                             drill=drill) as sp:
                    try:
                        barrier["arr"] = np.asarray(packed_f)
                    except Exception as err:  # surfaces at the join below
                        barrier["err"] = err
            barrier["span"] = sp

        def _verify(arr_f: np.ndarray) -> int:
            verified_records = 0
            off_f = len(failed)
            heads_after = arr_f[:off_f]
            if nrings:
                bounds_np = arr_f[off_f: off_f + nrings * 2].reshape(
                    nrings, 2)
                off_f += nrings * 2
                if self._ring_mirror_valid:
                    for ri in range(nrings):
                        want = (self._ring_tail_mirror,
                                len(self.executor.step_input_history))
                        got = (int(bounds_np[ri, 0]),
                               int(bounds_np[ri, 1]))
                        if got != want:
                            raise rec.RecoveryError(
                                f"ring {ri}: host bound mirror {want} "
                                f"diverges from device bounds {got} — "
                                f"recovery routed against wrong "
                                f"coverage; state suspect")
            want_n = DETS_PER_STEP * n_steps
            for m in fast_mgrs:
                flat_m = m.flat_subtask
                pf = prep[flat_m]
                ck_head_m = int(ck_heads[flat_m])
                small_np = arr_f[off_f: off_f + 4]
                off_f += 4
                nh = len(pf["holders"])
                meta_np = arr_f[off_f: off_f + 2 * nh].reshape(nh, 2)
                off_f += 2 * nh
                ok_f = int(arr_f[off_f])
                consumed_f = int(arr_f[off_f + 1])
                off_f += 2
                if (tuple(int(x) for x in small_np)
                        != (want_n, ck_head_m, n_steps, 1)):
                    raise rec.RecoveryError(
                        f"subtask {flat_m}: host-derived clean stream "
                        f"(n={want_n}, start={ck_head_m}, "
                        f"anchors={n_steps}) contradicted by device "
                        f"parse {[int(x) for x in small_np]} — "
                        f"async-row ledger or fence-head cache is "
                        f"wrong; state suspect")
                for j in range(nh):
                    if (int(meta_np[j, 0]), int(meta_np[j, 1])) \
                            != (want_n, ck_head_m):
                        raise rec.RecoveryError(
                            f"subtask {flat_m}: replica holder {j} "
                            f"metadata {meta_np[j].tolist()} disagrees "
                            f"with ({want_n}, {ck_head_m}) — replicas "
                            f"inconsistent")
                if int(heads_after[list(failed).index(flat_m)]) \
                        != ck_head_m + want_n:
                    raise rec.RecoveryError(
                        f"subtask {flat_m}: restored log head "
                        f"{int(heads_after[list(failed).index(flat_m)])}"
                        f" != fence head {ck_head_m} + {want_n} rows")
                if not ok_f:
                    # Resolve the device arrays and let verify() build
                    # the detailed divergence message (failure path: the
                    # extra transfer is fine).
                    m.result.emit_counts = np.asarray(m.result.emit_counts)
                    m.result.expected_emits = np.asarray(
                        m.result.expected_emits)
                    try:
                        m.result.verify()
                    except rec.RecoveryError as err:
                        raise rec.RecoveryError(
                            f"subtask {flat_m}: {err}") from None
                    raise rec.RecoveryError(
                        f"subtask {flat_m}: device verify flag tripped "
                        f"but host recheck passed — flag/stream mismatch")
                m.result.records_replayed = consumed_f
                verified_records += consumed_f
            return verified_records

        def _revive() -> None:
            for flat in failed:
                self.heartbeats.revive(flat)
            self.failed.clear()
            if not drill:
                self.coordinator.reset_interval()

        def _audit():
            # Audit validation (obs/audit.py): recompute every replayed
            # closed epoch's digest from the patched carry and compare
            # against the sealed ledger — one match/divergence instant
            # per epoch lands under this recovery's trace id. Abort
            # policy raises AuditDivergenceError here: fail loudly
            # before the job resumes on state that did not reproduce
            # the original execution.
            # Returns its span (None with the audit off).
            if not self.auditor.enabled:
                return None
            with tr.span("recovery.audit", drill=drill) as sp:
                validator = rec.AuditValidator(
                    self.executor, self.coordinator.read_ledger(),
                    on_divergence=self.auditor.on_divergence)
                try:
                    validator.validate(
                        range(from_epoch, self.executor.epoch_id))
                finally:
                    # evidence reaches the metrics plane even when the
                    # abort policy throws mid-validation
                    self._m_audit_matches.inc(validator.stats["match"])
                    self._m_audit_div.inc(validator.stats["divergence"])
            phases["audit"] = phases.get("audit", 0.0) + sp.ms
            return sp

        audit_span = None
        audit_err: Optional[Exception] = None
        th = threading.Thread(target=_drain_barrier, args=(fin_span,),
                              name="recovery-finalize-barrier")
        th.start()
        # Host-side finalize work folded into the barrier window: the
        # audit validator's digest recompute reads the same patched
        # carry the packed read waits on (its transfers interleave with
        # the barrier d2h instead of queuing after it). Revive
        # bookkeeping does NOT fold in: it must stay after the join +
        # state-verify below — if the packed read or a deferred assert
        # raises, self.failed and the heartbeat table must still mark
        # the subtasks dead so a retry of recover() sees them. An audit
        # divergence is held and re-raised after verify (a verify
        # failure wins), and the join runs unconditionally so the
        # barrier thread never outlives this call.
        try:
            audit_span = _audit()
        except Exception as err:
            audit_err = err
        finally:
            # KeyboardInterrupt/SystemExit skip the deferral but
            # still land here: the thread never leaks.
            th.join()
        if barrier["err"] is not None:
            raise barrier["err"]
        read = barrier["span"]
        phases["finalize.barrier-read"] = (
            phases.get("finalize.barrier-read", 0.0) + read.ms)
        with tr.span("recovery.finalize.state-verify", drill=drill) as sp:
            total_records += _verify(barrier["arr"])
        verify_ms = sp.ms
        phases["finalize.state-verify"] = (
            phases.get("finalize.state-verify", 0.0) + verify_ms)
        chain.close()
        # ``finalize`` and ``finalize.overlap-saved`` are derived from
        # the sub-spans' own stamps, not from the window's wall, so
        # sum(finalize.*) - overlap-saved == finalize holds exactly: the
        # barrier read is on the critical path only for the part that
        # ran after the audit (the main thread's work in the window)
        # had ended; what ran under the audit is the saving. The audit
        # has its own key.
        exposed_ms = _exposed_ms(
            read.mono, read.mono + read.dur,
            read.mono if audit_span is None
            else audit_span.mono + audit_span.dur)
        phases["finalize"] = fin_before + disp.ms + exposed_ms + verify_ms
        # Verify passed, NOW the subtasks may be marked healthy; a held
        # audit divergence propagates after revive.
        _revive()
        if audit_err is not None:
            raise audit_err
        phases["finalize.overlap-saved"] = (
            phases.get("finalize.overlap-saved", 0.0)
            + read.ms - exposed_ms)
        report = RecoveryReport(
            failed_subtasks=failed, from_epoch=from_epoch,
            steps_replayed=n_steps, determinants_replayed=total_dets,
            records_replayed=total_records,
            ignored_checkpoints=ignored,
            recovery_ms=(_time.monotonic() - t0) * 1e3,
            managers=tuple(managers), phase_ms=phases, drill=drill,
            restore_bytes=restore_bytes, checkpoint_bytes=checkpoint_bytes)
        if not drill:
            # Rehearsals must not inflate the recovery count/latency
            # series operators alert on.
            self.reports.append(report)
            self._m_recovery_ms.update(report.recovery_ms)
            self._m_recovered_records.inc(report.records_replayed)
            # Per-phase latency distributions (recovery.replay-ms p50/p99
            # etc.) — the tuning surface for the paper's headline claim.
            for pname, ms in phases.items():
                self._mgroup.histogram(f"recovery.{pname}-ms").update(ms)
        return report

    def prewarm_recovery(self, vertex_ids: Optional[Sequence[int]] = None,
                         spill_paths: bool = False) -> float:
        """Compile every recovery program a standby will need, at job
        start — the reference keeps standby tasks *deployed* so failover
        only switches them to RUNNING (Task.java:300-302, :1040,
        Execution.java:373-377 state re-dispatch); the TPU analog of
        "deployed" is "XLA-compiled": after this, the failure path runs
        entirely on cached executables (recovery-time-to-resume drops from
        minutes of compile to milliseconds of replay).

        Requires ``num_standby >= 1`` (the knob that buys warm failover).
        Returns wall-clock seconds spent compiling. For vertices whose
        input edge is statically routed the replay program is specialized
        per subtask; all subtasks are prewarmed.
        """
        if self.standbys.num_standby_per_vertex < 1:
            raise rec.RecoveryError(
                "prewarm_recovery needs num_standby >= 1 (no standby "
                "programs requested)")
        t0 = _time.monotonic()
        from clonos_tpu.api.operators import TwoInputOperator
        from clonos_tpu.api.records import RecordBatch as RB
        ch = self._chunk()
        carry = self.executor.carry
        compiled = self.executor.compiled
        zero = lambda shape, dt=jnp.int32: jnp.zeros(shape, dt)

        def zero_batch(lead):
            return RB(zero(lead), zero(lead), zero(lead),
                      zero(lead, jnp.bool_))

        # Fetch + replica copy + ring bounds + replica-sourced log restore.
        if compiled.plan.num_replicas > 0:
            self._fetch_fn()(carry.replicas, jnp.asarray(0, jnp.int32),
                             jnp.asarray(0, jnp.int32))
            self._device_parse_fn()(carry.replicas,
                                    jnp.asarray(0, jnp.int32),
                                    jnp.asarray(0, jnp.int32))
            holders_per_owner = {}
            for (o, _h) in compiled.plan.pairs:
                holders_per_owner[o] = holders_per_owner.get(o, 0) + 1
            for h in sorted(set(holders_per_owner.values())):
                self._fetch_meta_fn(h)(carry.replicas, zero((h,)),
                                       jnp.asarray(0, jnp.int32))
            self._log_restore_from_replica_fn()(
                carry.replicas, jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32), zero((compiled.max_epochs,)),
                zero((compiled.max_epochs,), jnp.bool_),
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
            # Donated arg: compiled against the live carry, not run (see
            # the whole-carry programs below).
            n = self.REPLICA_COPY_ROWS
            self._replica_copy_fn().lower(
                carry.replicas, carry.logs, zero((n,)), zero((n,))
            ).compile()
        if carry.out_rings:
            self._ring_bounds()
        # Shared log-restore programs.
        st = clog.create(compiled.log_capacity, compiled.max_epochs)
        st = self._log_restore_fn()(
            zero((ch * DETS_PER_STEP, det.NUM_LANES)),
            jnp.asarray(0, jnp.int32), st)
        self._log_finalize_fn()(
            st, zero((compiled.max_epochs,)),
            zero((compiled.max_epochs,), jnp.bool_),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))

        vids = (list(vertex_ids) if vertex_ids is not None
                else [v.vertex_id for v in self.job.vertices])
        # Independent compiles run CONCURRENTLY: each job below first-calls
        # one jit program; XLA compilations of distinct programs proceed in
        # parallel across threads (the executions they also trigger are
        # tiny and serialize on the device queue). This roughly divides
        # prewarm wall-clock by min(#workers, #independent programs).
        jobs: List[Any] = []
        z = jnp.asarray(0, jnp.int32)
        nrp = max(compiled.plan.num_replicas, 1)

        def _edge_jobs(vid: int) -> None:
            v = self.job.vertices[vid]
            in_edges = self.job.in_edges(vid)
            # Ring/route/concat programs for each input edge.
            for eidx in in_edges:
                e = self.job.edges[eidx]
                src_p = self.job.vertices[e.src].parallelism
                src_cap = compiled.vertex_out_capacity(e.src)
                ri = compiled.ring_index[e.src]
                el = carry.out_rings[ri]
                z = jnp.asarray(0, jnp.int32)
                # Uniform [ch] replay windows: ONE shape per edge (the
                # old first-chunk ch-1 variants doubled these compiles).
                # Both routing variants: fused lane (single failure) and
                # all-lane + select (connected-failure sharing).
                jobs.append(lambda eidx=eidx, el=el, z=z:
                            self._route_chunk_fn(eidx, ch)(
                                el, z, z, z, z, z))

                def _all_lane(eidx=eidx, el=el, z=z):
                    routed, *_ = self._route_chunk_fn(
                        eidx, ch, all_lanes=True)(el, z, z, z, z)
                    self._lane_select_fn(eidx, ch)(routed, z)
                jobs.append(_all_lane)
                if spill_paths:
                    # Spill-path twin (AVAILABILITY wrap recovery):
                    # doubles the exchange compiles, so opt-in — a
                    # ring-covered recovery (the common case) never
                    # takes this path.
                    jobs.append(lambda ri=ri, el=el, z=z:
                                self._ring_chunk_fn(ri, ch)(el, z))
                    jobs.append(lambda eidx=eidx, src_p=src_p,
                                src_cap=src_cap, z=z:
                                self._route_raw_fn(eidx, ch)(
                                    zero_batch((ch, src_p, src_cap)),
                                    z, z, z, z, z))
                    jobs.append(lambda eidx=eidx, src_p=src_p,
                                src_cap=src_cap, z=z:
                                self._route_raw_fn(
                                    eidx, ch, all_lanes=True)(
                                    zero_batch((ch, src_p, src_cap)),
                                    z, z, z, z))
                jobs.append(lambda eidx=eidx, e=e:
                            self._first_chunk_fn(eidx)(
                                zero_batch((1, e.capacity)),
                                zero_batch((ch, e.capacity))))

        def _vertex_jobs(vid: int) -> None:
            v = self.job.vertices[vid]
            in_edges = self.job.in_edges(vid)
            _edge_jobs(vid)
            # Replay block program(s).
            slot_keys = compiled.consumer_slot_keys(vid)
            subs = range(v.parallelism) if slot_keys is not None else [0]
            in_cap = (self.job.edges[in_edges[0]].capacity if in_edges
                      else compiled.vertex_out_capacity(vid))
            state0 = jax.tree_util.tree_map(
                lambda x: x[0][None], carry.op_states[vid])
            if isinstance(v.operator, TwoInputOperator):
                cap2 = self.job.edges[in_edges[1]].capacity
                chunk0 = (zero_batch((ch, in_cap)), zero_batch((ch, cap2)))
            else:
                chunk0 = zero_batch((ch, in_cap))

            def _replay_job(sub, state0=state0, chunk0=chunk0):
                rp = self._make_replayer(vid, sub)
                rp._jit_block(state0, chunk0, zero((ch,)), zero((ch,)),
                              jnp.asarray(sub, jnp.int32),
                              jnp.zeros((), jnp.int32))
                # tslice serves the pad-fixed stream length (the shape
                # every failure uses; see LogReplayer.pad_steps).
                rp._jit_tslice(zero((rp.pad_steps or ch,)),
                               jnp.asarray(0, jnp.int32))
            for sub in subs:
                jobs.append(lambda sub=sub: _replay_job(sub))
            # Whole-carry programs (graft / kill / ring write) take the
            # carry DONATED, so executing them here would need a second,
            # disposable carry — at the headline deployment that is
            # 5.24 GiB next to the live 5.24 GiB, the all-lane route
            # programs' ~2 GB each and the kill program's 1.5 GB of
            # scratch, on a 16 GB chip. Lowering + compiling against the
            # LIVE carry allocates nothing and donates nothing, and the
            # executable it leaves in the jit's cache is the one the
            # failure path dispatches.
            jobs.append(lambda: self._graft_fn(vid).lower(
                carry, state0, st, z, z, z).compile())
            jobs.append(lambda: self._inject_fn(vid).lower(
                carry, z, z, jnp.full((nrp,), nrp, jnp.int32)).compile())
            if vid in compiled.ring_index:
                ri = compiled.ring_index[vid]
                jobs.append(lambda: self._ring_write_fn(ri, ch).lower(
                    carry.out_rings[ri],
                    zero_batch((ch, compiled.vertex_out_capacity(vid))),
                    z, z, z, z).compile())

        for vid in vids:
            _vertex_jobs(vid)

        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=4) as pool:
            for res in pool.map(lambda j: j(), jobs):
                pass
        # AOT-lower the standby's first-step (block) program into the
        # persistent compile cache too. A rehydrated standby's first
        # dispatch after restore is then a cache hit, not a recompile
        # in the finalize tail; a program that does not compile fails
        # the prewarm here, not the failover later.
        from clonos_tpu.utils.compile_cache import aot_lower_first_step
        aot_lower_first_step(self.executor, self._mgroup)
        return _time.monotonic() - t0

    def failover_drill(self, flats: Optional[Sequence[int]] = None
                       ) -> float:
        """Rehearse a failover end-to-end and return its wall-clock
        seconds: inject a failure, run the full recovery protocol, and
        rely on bit-identical recovery to leave the job state canonically
        unchanged (executor.canonical_carry: live log/ring content equal;
        physically-dead pre-fence slots may differ — nothing ever reads
        them). The reference's RunStandbyTaskStrategy keeps standby
        executions *running* (Task.java:300-302, Execution.java:373-377),
        so their whole failure path is hot; compiling programs
        (prewarm_recovery) is necessary but not sufficient for that — the
        first execution still pays allocator growth, transfer-path and
        host-pool warmup. One drill moves all of it off the real failure
        path.

        Default drill set: one subtask of every vertex class, failed
        together (a connected multi-class failure exercises every class's
        replay program and the staged topological recovery)."""
        if self.failed:
            raise rec.RecoveryError("cannot drill with real failures "
                                    "pending")
        if not self.standbys.has_state():
            raise rec.RecoveryError(
                "failover_drill needs a completed checkpoint")
        t0 = _time.monotonic()
        fence = self._fence_step[self.standbys.latest.checkpoint_id + 1]
        if self.global_step == fence:
            import warnings
            warnings.warn(
                "failover_drill at an epoch fence replays zero steps; "
                "run it mid-epoch so the chunked replay path executes")
        if flats is None:
            flats = [self.job.subtask_base(v.vertex_id)
                     for v in self.job.vertices]
        flats = list(flats)
        # The drill must NEVER corrupt a healthy job: verify every drilled
        # log has a surviving replica holder BEFORE zeroing any device
        # state (recover() makes the same check, but only after the
        # injection has already destroyed the state it needs).
        if self.global_step > fence:
            fset = set(flats)
            for flat in flats:
                vid, _ = self._vertex_of(flat)
                if not self.job.out_edges(vid):
                    continue       # sinks synthesize; no holder needed
                if not any(o == flat and h not in fset
                           for (o, h) in self.plan.pairs):
                    raise rec.RecoveryError(
                        f"failover_drill: subtask {flat} would have no "
                        f"surviving determinant replica under drill set "
                        f"{sorted(fset)} — drill fewer subtasks at once "
                        f"or deepen sharing/replication")
            # Input reconstruction needs the whole replay window in the
            # upstream rings (or spill): check BEFORE zeroing state too.
            n_steps = self.global_step - fence
            if (n_steps > self.executor.compiled.inflight_ring_steps
                    and self.executor.spill_logs is None):
                raise rec.RecoveryError(
                    f"failover_drill: {n_steps} steps since the last "
                    f"completed checkpoint exceed the in-flight ring "
                    f"({self.executor.compiled.inflight_ring_steps} "
                    f"steps) and spill is disabled — drill earlier or "
                    f"enable spill")
        self.inject_failure(flats)
        self.recover(drill=True)
        return _time.monotonic() - t0

    def _rebuild_txn_shards(self, vid: int, sub: int,
                            result: rec.ReplayResult, from_epoch: int,
                            fence: int, n_steps: int) -> None:
        """Reconstruct the failed sink subtask's pending transaction
        shards from its replayed output chunks, epoch by epoch."""
        tl = self.txn_logs[vid]
        chunks = [jax.tree_util.tree_map(np.asarray, c)
                  for c in (result.out_chunks or [])]

        def steps_slice(lo: int, hi: int) -> np.ndarray:
            rows = []
            for i, c in enumerate(chunks):
                ch_n = c.keys.shape[0]
                base = i * self._chunk()
                a = max(lo, base)
                b = min(hi, base + ch_n)
                for s in range(a, b):
                    m = c.valid[s - base]
                    if m.any():
                        rows.append(np.stack(
                            [c.keys[s - base][m], c.values[s - base][m],
                             c.timestamps[s - base][m]], axis=1))
            return (np.concatenate(rows, axis=0) if rows
                    else np.zeros((0, 3), np.int32))

        cur = self.executor.epoch_id
        for e in range(from_epoch, cur + 1):
            if e not in self._fence_step:
                continue
            lo = self._fence_step[e] - fence
            hi = (self._fence_step.get(e + 1, fence + n_steps) - fence
                  if e < cur else n_steps)
            tl.rebuild_shard(e, sub, steps_slice(lo, min(hi, n_steps)))

    # --- input reconstruction ------------------------------------------------

    def _ring_steps(self, patched: JobCarry, src_vid: int, start: int,
                    n: int, need: Optional[int] = None):
        """Raw output steps [start, start+n) of a producer vertex, from the
        device ring — falling back to the host spill for steps the ring no
        longer retains (reference SpilledReplayIterator.java:61).

        ``need``: how many leading steps must actually be present
        (default n). With need < n the returned [n]-shaped batch may hold
        dead entries past ``need`` — chunked replay reads fixed-size
        [CH] windows whose tail can extend past the ring head."""
        if need is None:
            need = n
        compiled = self.executor.compiled
        ri = compiled.ring_index[src_vid]
        el = patched.out_rings[ri]
        # Coverage math from the bounds cache (one read per recover();
        # ring offsets are stable across recovery — write-backs replace
        # contents only), so the fast path costs zero host round-trips.
        if getattr(self, "_bounds_cache", None) and ri in self._bounds_cache:
            tail, head = self._bounds_cache[ri]
        else:
            tail, head = int(el.tail), int(el.head)
        got_start = max(start, tail)
        cnt = max(min(head - got_start, n), 0)
        # Steps physically retained by the ring: slice_steps only clamps to
        # ``tail``, but when checkpoints stall past ring capacity newer
        # appends have clobbered positions of steps < head - ring_steps —
        # those must come from the spill even though tail hasn't advanced.
        ring_lo = max(tail, head - el.ring_steps)
        batch, _, _ = self._ring_chunk_fn(ri, n)(
            el, jnp.asarray(start, jnp.int32))
        if got_start == start and start >= ring_lo and cnt >= need:
            return batch
        # Ring shortfall: pull the missing leading steps from the spill.
        if self.executor.spill_logs is None:
            raise rec.RecoveryError(
                f"in-flight log of vertex {src_vid} lost steps "
                f"[{start}, {max(got_start, ring_lo)}) and spill is disabled")
        spill = self.executor.spill_logs[ri]
        boundary = min(start + n, max(got_start, ring_lo))
        required_end = min(start + need, boundary)
        parts = []
        have = start
        # Prefetching epoch reads (reference SpilledReplayIterator.java:61
        # — async reads run ahead of consumption).
        eps = spill.retained_epochs()
        if eps:
            it = ifl.ReplayIterator(spill, eps[0], eps[-1])
            try:
                for ep_start, ep_batch in it.epochs():
                    ep_n = ep_batch.keys.shape[0]
                    lo = max(have, ep_start)
                    hi = min(ep_start + ep_n, boundary)
                    if hi > lo:
                        parts.append(jax.tree_util.tree_map(
                            lambda x: x[lo - ep_start: hi - ep_start],
                            ep_batch))
                        have = hi
                    if have >= boundary:
                        break
            except (SegmentCorruptError, StorageError) as e:
                # Torn/corrupt/missing segment on refill: surface as a
                # labeled recovery failure, never as garbage replay bytes
                # (satellite: spill-file durability).
                raise rec.RecoveryError(
                    f"vertex {src_vid}: tiered refill failed — {e}") from e
            finally:
                it.close()
        if have < required_end:
            raise rec.RecoveryError(
                f"vertex {src_vid}: spill does not cover steps "
                f"[{have}, {required_end})")
        if have < boundary:
            # Dead filler past the needed range (fixed-shape chunk reads).
            ref = parts[0] if parts else batch
            parts.append(jax.tree_util.tree_map(
                lambda x: jnp.zeros((boundary - have,) + x.shape[1:],
                                    x.dtype), ref))
        if boundary < start + n:
            parts.append(jax.tree_util.tree_map(
                lambda x: x[boundary - got_start: start + n - got_start],
                batch))
        out = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *parts)
        if out.keys.shape[0] != n:
            raise rec.RecoveryError(
                f"vertex {src_vid}: reconstructed {out.keys.shape[0]} of "
                f"{n} in-flight steps")
        return out

    def _replay_inputs(self, patched: JobCarry, snap: LeanSnapshot,
                       eidx: int, sub: int, fence: int, n_steps: int):
        """The failed consumer's lost inputs on edge ``eidx``: the
        checkpointed depth-1 edge buffer (its input at the first lost step)
        followed by the upstream's ring outputs [fence, fence+n-1), routed
        through the deterministic exchange.

        Returns a LIST of block-sized chunks ([CH, cap] each; the last
        covers the tail) so every device program here is fixed-shape and
        prewarm-compiled — recovery pays no XLA compile (warm standby)."""
        e = self.job.edges[eidx]
        ch = self._chunk()
        compiled = self.executor.compiled
        ri = compiled.ring_index[e.src]
        first = jax.tree_util.tree_map(
            lambda x: x[sub][None], snap.edge_bufs[eidx])
        if n_steps <= 0:
            return []
        el = patched.out_rings[ri]
        if self._bounds_cache and ri in self._bounds_cache:
            tail, head = self._bounds_cache[ri]
        else:
            tail, head = int(el.tail), int(el.head)
        ring_lo = max(tail, head - el.ring_steps)
        # Uniform [ch] windows: window i covers absolute steps
        # [fence-1+i*ch, fence-1+(i+1)*ch). Window slot j (global) holds
        # step fence-1+j; slot 0 is dead (pre-fence) — masked by ``lead``
        # and replaced with the checkpointed edge buffer. One compiled
        # program per edge serves every chunk (prewarm halved vs the old
        # first-chunk (ch-1) shape variants). Loop state lives ON DEVICE
        # (no host scalar put per chunk); coverage decisions use the host
        # bounds.
        start_d = jnp.asarray(fence - 1, jnp.int32)
        sub_d = jnp.asarray(sub, jnp.int32)
        rr_d = jnp.asarray(snap.rr_offsets[eidx][0], jnp.int32)
        need_d = jnp.asarray(n_steps, jnp.int32)
        lead_d = jnp.asarray(1, jnp.int32)
        chunks = []
        nblocks = -(-n_steps // ch)
        for i in range(nblocks):
            h_start = fence - 1 + i * ch
            # Real ring steps this window must provide (its live slots).
            lo_real = max(h_start, fence)
            hi_real = min(h_start + ch, fence - 1 + n_steps)
            h_need = max(hi_real - lo_real, 0)
            covered = (lo_real >= ring_lo and lo_real >= tail
                       and head - lo_real >= h_need)
            share = self._route_cache_enabled

            def raw_window():
                # Spill-backed window, shaped like the ring window: pull
                # the real steps from ring+spill and shift window 0 down
                # one slot (its dead leading slot carries no step).
                raw = self._ring_steps(patched, e.src, lo_real, ch,
                                       need=h_need)
                if i == 0:
                    raw = jax.tree_util.tree_map(
                        lambda x: jnp.roll(x, 1, axis=0).at[0].set(
                            jnp.zeros_like(x[0])), raw)
                return raw

            if not share:
                # Single failed consumer: the fused variant scatters only
                # this lane's rows (~P times cheaper than materializing
                # the whole routed block).
                if covered:
                    lane, start_d, rr_d, need_d, lead_d = \
                        self._route_chunk_fn(eidx, ch)(
                            el, start_d, sub_d, rr_d, need_d, lead_d)
                else:
                    lane, start_d, rr_d, need_d, lead_d = \
                        self._route_raw_fn(eidx, ch)(
                            raw_window(), start_d, sub_d, rr_d, need_d,
                            lead_d)
            else:
                # Multiple failed consumers: route the window once to all
                # lanes, cache it, and lane-select per consumer
                # (recover() scopes the cache to one vertex's group).
                key = (eidx, i)
                cached = self._route_cache.get(key)
                if cached is None:
                    if covered:
                        routed, start_d, rr_d, need_d, lead_d = \
                            self._route_chunk_fn(eidx, ch, all_lanes=True)(
                                el, start_d, rr_d, need_d, lead_d)
                    else:
                        routed, start_d, rr_d, need_d, lead_d = \
                            self._route_raw_fn(eidx, ch, all_lanes=True)(
                                raw_window(), start_d, rr_d, need_d,
                                lead_d)
                    self._route_cache[key] = routed
                else:
                    routed = cached
                    self._route_cache_hits += 1
                lane = self._lane_select_fn(eidx, ch)(routed, sub_d)
            if i == 0:
                chunks.append(self._first_chunk_fn(eidx)(first, lane))
            else:
                chunks.append(lane)
        return chunks

    def _reread_feed(self, vid: int, sub: int, snap: LeanSnapshot,
                     rows: np.ndarray, n_steps: int):
        """Rebuild a HostFeedSource's lost input batches: offset from the
        checkpointed operator state, per-step pull counts from the recorded
        BUFFER_BUILT determinants, records from the rewindable reader.
        Returns block-sized chunks (zero-padded tail) like
        :meth:`_replay_inputs`."""
        reader = self.executor.feed_readers.get(vid)
        if reader is None:
            raise rec.RecoveryError(
                f"vertex {vid}: HostFeedSource has no registered feed "
                f"reader to re-read from")
        v = self.job.vertices[vid]
        b = v.operator.batch_size
        anchors = det.sync_anchors(rows)[:n_steps]
        counts = rows[anchors + 3, det.LANE_P].astype(np.int64)
        offset = int(np.asarray(snap.op_states[vid]["offset"][sub]))
        ch = self._chunk()
        padded = -(-n_steps // ch) * ch
        keys = np.zeros((padded, b), np.int32)
        vals = np.zeros((padded, b), np.int32)
        valid = np.zeros((padded, b), bool)
        for i, c in enumerate(counts):
            ks, vs = reader.read_at(sub, offset, int(c))
            keys[i, :int(c)], vals[i, :int(c)] = ks, vs
            valid[i, :int(c)] = True
            offset += int(c)
        from clonos_tpu.api.records import RecordBatch as RB
        zts = np.zeros((padded, b), np.int32)
        return [RB(jnp.asarray(keys[lo:lo + ch]),
                   jnp.asarray(vals[lo:lo + ch]),
                   jnp.asarray(zts[lo:lo + ch]),
                   jnp.asarray(valid[lo:lo + ch]))
                for lo in range(0, padded, ch)]

    def _synthesize_det_rows(self, fence_global: int,
                             n_steps: int) -> np.ndarray:
        """Rebuild a sink's per-step determinant rows from the executor's
        step-input ledger (times/rng draws for the lost steps). BUFFER_BUILT
        payloads are placeholders — the replayer fills real emit counts into
        the rebuilt rows."""
        hist = self.executor.step_input_history[fence_global:
                                                fence_global + n_steps]
        if len(hist) < n_steps:
            raise rec.RecoveryError("step-input ledger shorter than the "
                                    "lost step range")
        rows = np.zeros((n_steps * DETS_PER_STEP, det.NUM_LANES), np.int32)
        for i, (t, r) in enumerate(hist):
            base = i * DETS_PER_STEP
            rows[base, det.LANE_TAG] = det.TIMESTAMP
            rows[base, det.LANE_P] = -1 if t < 0 else 0
            rows[base, det.LANE_P + 1] = t
            rows[base + 1, det.LANE_TAG] = det.RNG
            rows[base + 1, det.LANE_P] = r
            rows[base + 2, det.LANE_TAG] = det.ORDER
            rows[base + 3, det.LANE_TAG] = det.BUFFER_BUILT
        return rows

    def _make_replayer(self, vid: int, sub: int) -> rec.LogReplayer:
        """Standby replay program for (vertex, subtask); compiled programs
        are cached on the operator so repeated failures (and prewarm)
        share them."""
        v = self.job.vertices[vid]
        slot_keys = self.executor.compiled.consumer_slot_keys(vid)
        compiled = self.executor.compiled
        return rec.LogReplayer(
            v.operator, v.parallelism, vertex_name=v.name,
            block_steps=self._recovery_ch,
            in_slot_keys=(slot_keys[sub:sub + 1]
                          if slot_keys is not None else None),
            pad_steps=compiled.inflight_ring_steps,
            mesh=compiled.mesh, task_axis=compiled.task_axis)

    def _log_restore_fn(self):
        cap = self.executor.compiled.log_capacity

        def make():
            def f(rows_chunk, count, state):
                return clog.append(state, rows_chunk, count)
            return f
        return self._jitted(("log_append",), make)

    def _log_restore_from_replica_fn(self):
        """Rebuild a failed task's log row ON DEVICE from a surviving
        replica: the replayed determinant stream was verified equal to the
        recovered one, so the replica's bytes ARE the restored log — no
        host round-trip of the rows."""
        cap = self.executor.compiled.log_capacity
        me = self.executor.compiled.max_epochs

        def make():
            def f(replicas, r, from_epoch, used, ck_head,
                  epoch_offs, epoch_mask, latest, base):
                rep_one = jax.tree_util.tree_map(lambda x: x[r], replicas)
                buf, _cnt, _start = clog.get_determinants(
                    rep_one, from_epoch, cap)
                st = clog.create(cap, me)
                st = st._replace(head=ck_head, tail=ck_head)
                st = clog.append(st, buf, used)
                return st._replace(
                    epoch_starts=jnp.where(epoch_mask, epoch_offs,
                                           st.epoch_starts),
                    latest_epoch=jnp.maximum(st.latest_epoch, latest),
                    epoch_base=jnp.maximum(st.epoch_base, base))
            return f
        return self._jitted(("log_restore_replica",), make)

    def _log_finalize_fn(self):
        def make():
            def f(state, epoch_offs, epoch_mask, latest, base):
                starts = jnp.where(epoch_mask, epoch_offs,
                                   state.epoch_starts)
                return state._replace(
                    epoch_starts=starts,
                    latest_epoch=jnp.maximum(state.latest_epoch, latest),
                    epoch_base=jnp.maximum(state.epoch_base, base))
            return f
        return self._jitted(("log_finalize",), make)

    def _graft_fn(self, vid: int):
        def make():
            def f(carry, new_state, restored_log, sub, flat, rc):
                ops = list(carry.op_states)
                ops[vid] = jax.tree_util.tree_map(
                    lambda live_x, new_x: live_x.at[sub].set(new_x[0]),
                    ops[vid], new_state)
                logs = jax.tree_util.tree_map(
                    lambda s, r: s.at[flat].set(r), carry.logs,
                    restored_log)
                return carry._replace(
                    op_states=tuple(ops), logs=logs,
                    record_counts=carry.record_counts.at[flat].set(rc))
            return f
        # Donated: an un-donated graft copies the whole multi-GB carry
        # (rings included) per failed subtask, thrashing the allocator.
        return self._jitted(("graft", vid), make, donate=(0,))

    def _ring_write_fn(self, ri: int, m: int):
        """Write an [m, cap] replayed output chunk into ring ``ri`` at
        steps [base, base+m), keeping only steps in [keep_from, hi);
        returns (ring, base + m) so the loop cursor stays on device."""
        def make():
            def f(el, chunk, base, sub, keep_from, hi):
                steps = base + jnp.arange(m, dtype=jnp.int32)
                keep = (steps >= keep_from) & (steps < hi)
                pos = jnp.where(keep, steps & (el.ring_steps - 1),
                                el.ring_steps)        # OOB row -> dropped
                return el._replace(
                    keys=el.keys.at[pos, sub].set(chunk.keys, mode="drop"),
                    values=el.values.at[pos, sub].set(chunk.values,
                                                      mode="drop"),
                    timestamps=el.timestamps.at[pos, sub].set(
                        chunk.timestamps, mode="drop"),
                    valid=el.valid.at[pos, sub].set(chunk.valid,
                                                    mode="drop")), base + m
            return f
        return self._jitted(("ring_write", ri, m), make, donate=(0,))

    def _patch(self, carry: JobCarry, snap: LeanSnapshot, vid: int,
               sub: int, flat: int, result: rec.ReplayResult,
               det_rows: np.ndarray, from_epoch: int, fence: int,
               n_steps: int, replica_src: Optional[int] = None,
               det_n: Optional[int] = None, clean_sync: bool = False,
               ck_head: Optional[int] = None) -> JobCarry:
        """Graft the rebuilt subtask back into the live carry. Every
        device program here is fixed-shape (chunked appends/writes) so a
        prewarmed standby pays zero XLA compile on the failure path.

        ``clean_sync`` (device-resident determinant stream): the rows
        never came to the host, but the stream is pure k-row sync blocks
        so the anchors are exactly ``i * DETS_PER_STEP``; ``det_n`` is
        its device-verified row count."""
        compiled = self.executor.compiled
        ch4 = self._chunk() * DETS_PER_STEP
        if ck_head is None:
            ck_head = int(np.asarray(snap.log_heads[flat]))
        n = det_rows.shape[0] if det_n is None else det_n
        # Epoch->offset index entries died with the task; rebuild them from
        # the fence-step ledger. Sync blocks anchor at TIMESTAMP rows.
        if clean_sync:
            ts_pos = np.arange(n // DETS_PER_STEP,
                               dtype=np.int64) * DETS_PER_STEP
        elif n > 0:
            ts_pos = det.sync_anchors(det_rows)
        else:
            ts_pos = np.zeros((0,), np.int64)
        me = compiled.max_epochs
        epoch_offs = np.zeros((me,), np.int32)
        epoch_mask = np.zeros((me,), bool)
        latest = 0
        for e in range(from_epoch, self.executor.epoch_id + 1):
            if e in self._fence_step:
                step_i = self._fence_step[e] - fence
                # from_epoch starts exactly at the checkpointed head (async
                # rows appended in the roll gap come after the fence);
                # later fences anchor at their first step's TIMESTAMP row
                # minus the roll-gap ledger — async rows appended after
                # the roll but before the epoch's first step (fence
                # SOURCE_CHECKPOINTs, ignore broadcasts, between-epoch
                # service calls) precede that anchor yet belong to the
                # NEW epoch (executor.roll_gap_async).
                gap = self.executor.roll_gap_async.get((flat, e), 0)
                if step_i == 0:
                    off = ck_head
                elif step_i < len(ts_pos):
                    off = ck_head + int(ts_pos[step_i]) - gap
                else:
                    off = ck_head + n - gap
                epoch_offs[e % me] = off
                epoch_mask[e % me] = True
                latest = max(latest, e)
        if replica_src is not None:
            # The replayed stream was verified equal to the recovered one,
            # so the replica's device bytes ARE the restored log (no h2d).
            restored = self._log_restore_from_replica_fn()(
                carry.replicas, jnp.asarray(replica_src, jnp.int32),
                jnp.asarray(from_epoch, jnp.int32),
                jnp.asarray(n, jnp.int32), jnp.asarray(ck_head, jnp.int32),
                jnp.asarray(epoch_offs), jnp.asarray(epoch_mask),
                jnp.asarray(latest, jnp.int32),
                jnp.asarray(from_epoch, jnp.int32))
        else:
            # Synthesized streams (sink recovery) upload in fixed chunks.
            restored = clog.create(compiled.log_capacity,
                                   compiled.max_epochs)
            base = jnp.asarray(ck_head, jnp.int32)
            restored = restored._replace(head=base, tail=base)
            app = self._log_restore_fn()
            for lo in range(0, n, ch4):
                cnt = min(ch4, n - lo)
                chunk = np.zeros((ch4, det.NUM_LANES), np.int32)
                chunk[:cnt] = det_rows[lo:lo + cnt]
                restored = app(jnp.asarray(chunk),
                               jnp.asarray(cnt, jnp.int32), restored)
            restored = self._log_finalize_fn()(
                restored, jnp.asarray(epoch_offs), jnp.asarray(epoch_mask),
                jnp.asarray(latest, jnp.int32),
                jnp.asarray(from_epoch, jnp.int32))
        # Operator state slice + log row + record count in one program.
        # Deferred replays keep the consumed total on device — the add
        # happens there and the host never waits for it.
        rc = snap.record_counts[flat] + (
            result.consumed_d if result.deferred
            else result.records_replayed)
        carry = self._graft_fn(vid)(
            carry, result.op_state, restored,
            jnp.asarray(sub, jnp.int32), jnp.asarray(flat, jnp.int32), rc)
        # In-flight ring shard reconstruction: write the replayed outputs
        # back into the producer's ring at their original step offsets
        # (reference buildAndLogBuffer — the standby re-cuts identical
        # buffers and re-logs them so downstream recoveries can be
        # served). Only the last ring_steps replayed steps fit; earlier
        # chunks are masked out (spill-backed replays longer than the
        # ring must not wrap into newer steps).
        rings = list(carry.out_rings)
        if vid in compiled.ring_index and result.out_chunks is not None \
                and n_steps > 0:
            ri = compiled.ring_index[vid]
            el = rings[ri]
            keep_from = jnp.asarray(fence + n_steps
                                    - min(n_steps, el.ring_steps),
                                    jnp.int32)
            hi = jnp.asarray(fence + n_steps, jnp.int32)
            sub_j = jnp.asarray(sub, jnp.int32)
            ch = self._chunk()
            base_d = None
            for i, chunk in enumerate(result.out_chunks):
                m = chunk.keys.shape[0]
                base_i = fence + i * ch
                if base_i + m <= fence + n_steps - min(n_steps,
                                                       el.ring_steps):
                    continue      # wholly before the retained window
                if base_d is None:
                    base_d = jnp.asarray(base_i, jnp.int32)
                el, base_d = self._ring_write_fn(ri, m)(
                    el, chunk, base_d, sub_j, keep_from, hi)
            rings[ri] = el
        return carry._replace(out_rings=tuple(rings))
