"""The failover path: kill, causal recovery, the warm standby's set-up.

The runner's side of the per-subtask protocol in causal/recovery.py
(reference RunStandbyTaskStrategy.onTaskFailure, failover/
RunStandbyTaskStrategy.java:85): remove the failed, ignore the
checkpoints they never acked, back off the checkpoint interval, run
each standby through the recovery FSM, graft it into the live carry.
One :class:`Failover` a runner; one :class:`_Recovery` a ``recover()``,
handed from one phase to the next, a phase a method and a span of the
``recovery.`` chain. The device programs are runtime/recovery_programs.py's.

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

import dataclasses
import threading
import time as _time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.api.operators import HostFeedSource, TwoInputOperator
from clonos_tpu.api.records import RecordBatch
from clonos_tpu.causal import determinant as det
from clonos_tpu.causal import log as clog
from clonos_tpu.causal import recovery as rec
from clonos_tpu.inflight import log as ifl
from clonos_tpu.obs import get_tracer
from clonos_tpu.runtime import checkpoint as cp
from clonos_tpu.runtime.executor import DETS_PER_STEP, LeanSnapshot
from clonos_tpu.runtime.recovery_programs import RecoveryPrograms
from clonos_tpu.storage import SegmentCorruptError, StorageError


def _add_ms(phases: Dict[str, float], key: str, ms: float) -> None:
    phases[key] = phases.get(key, 0.0) + ms


def _exposed_ms(start: float, end: float, wait_from: float) -> float:
    """Milliseconds of a worker's interval ``[start, end]`` that ran
    after the thread it works for began waiting on it at ``wait_from``:
    the part on the critical path (the rest ran under other work)."""
    return max(0.0, end - max(start, wait_from)) * 1e3


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
    #: routed edge windows a later consumer of the same vertex took from
    #: an earlier one's (observability/test hook)
    route_cache_hits: int = 0
    #: the farthest number of edges downstream from which any failed
    #: subtask fetched its determinants (the holder's vertex against the
    #: victim's; 0 where none came from a replica); with :attr:`victims`
    #: also the counters ``recovery.fetch_hops`` and ``recovery.victims``
    #: of a recovery that is no drill
    fetch_hops: int = 0

    @property
    def victims(self) -> int:
        """How many subtasks failed together."""
        return len(self.failed_subtasks)


@dataclasses.dataclass
class _Victim:
    """One failed subtask on its way through fetch, inputs, replay and
    patch."""

    flat: int
    vid: int
    sub: int
    #: surviving (replica row, holder) pairs of its log
    holders: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    #: edges from its vertex down to the holder its determinants came from
    fetch_hops: int = 0
    host: bool = False       # determinants come from ``host_rows``
    #: cleanness derived on the host: its metadata and its replay's
    #: checks are deferred asserts of the final packed read
    fast: bool = False
    #: phase A's dispatches: the on-device parse (times, rngs, expected),
    #: its 4 words of metadata, the holders' [h, 2] metadata
    parsed: Any = None
    small_d: Any = None
    meta_d: Any = None
    mgr: Optional[rec.RecoveryManager] = None
    #: the recovered stream: host rows from ``start``, or (``det_device``
    #: set) the parsed stream left on the device, ``clean_n`` rows
    rows: Optional[np.ndarray] = None
    start: int = 0
    det_device: Any = None
    clean_n: Optional[int] = None
    synthesized: bool = False      # rows re-made from the step ledger
    #: replica row whose device bytes restore the log (None: upload)
    r_best: Optional[int] = None
    input_steps: Optional[list] = None
    result: Optional[rec.ReplayResult] = None


@dataclasses.dataclass
class _Recovery:
    """One ``recover()``: what its phases hand on; dies with the call."""

    chain: Any
    phases: Dict[str, float]
    drill: bool
    host_rows: Optional[Dict[int, Tuple[np.ndarray, int]]]
    pre_patch_join: Optional[Callable[[], None]]
    t0: float = 0.0
    failed: Tuple[int, ...] = ()       # in topological order
    ignored: Tuple[int, ...] = ()      # checkpoints they never acked
    ckpt: Any = None
    from_epoch: int = 0
    fence: int = 0
    n_steps: int = 0
    snap: Optional[LeanSnapshot] = None
    #: the log heads at the checkpoint's fence, where the fence kept them
    ck_heads: Optional[np.ndarray] = None
    carry: Any = None                  # the carry being patched
    #: ring (tail, head): as dispatched at entry for the final read, and
    #: what the coverage decisions use (host mirror, else one read)
    bounds_dev: Any = None
    bounds: Dict[int, Tuple[int, int]] = dataclasses.field(
        default_factory=dict)
    #: routed edge windows of vertex ``routes_of``'s input edges, shared
    #: by its failed subtasks where it has >= 2 (_scope_route_cache)
    route_cache: Dict[Any, Any] = dataclasses.field(default_factory=dict)
    routes_of: Optional[int] = None
    share_routes: bool = False
    route_cache_hits: int = 0
    victims: List[_Victim] = dataclasses.field(default_factory=list)
    total_dets: int = 0
    total_records: int = 0
    #: the shard-local restore accounting RecoveryReport documents
    restore_bytes: int = 0
    checkpoint_bytes: int = 0


class Failover:
    """The failure path of one :class:`~clonos_tpu.runtime.cluster
    .ClusterRunner`. It reaches the runner through these names and no
    others: ``executor``, ``job``, ``plan``, ``failed``, ``coordinator``,
    ``standbys``, ``txn_logs``, ``timer_services``, ``auditor``,
    ``heartbeats``, ``global_step``, ``_fence_step``, ``_ck_log_heads``,
    ``_ck_heads_lock``, ``_ring_tail_mirror``, ``_ring_mirror_valid``,
    ``_join_fence_tail``, ``_read_sink_tap``; ``reports`` is the
    runner's list."""

    def __init__(self, runner, group, chunk_steps: int):
        self.runner = runner
        self.programs = RecoveryPrograms(runner.executor.compiled,
                                         runner.job, chunk_steps)
        self.reports: List[RecoveryReport] = runner.reports
        self._mgroup = group
        self._m_recovery_ms = group.histogram("recovery.duration-ms")
        self._m_recovered_records = group.counter("recovery.records-replayed")
        self._m_audit_matches = group.counter("audit.epochs-validated")
        self._m_audit_div = group.counter("audit.divergences")
        #: edges from an owner's vertex down to a holder's
        self._hops = runner.job.graph_info(0).distances()

    def _vertex_of(self, flat: int) -> Tuple[int, int]:
        job = self.runner.job
        for v in job.vertices:
            base = job.subtask_base(v.vertex_id)
            if base <= flat < base + v.parallelism:
                return v.vertex_id, flat - base
        raise ValueError(f"no subtask {flat}")

    # --- failure injection ---------------------------------------------------

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
        rt = self.runner
        rt._join_fence_tail()
        rt._read_sink_tap()
        carry = rt.executor.carry
        nr = rt.plan.num_replicas
        for flat in flat_subtasks:
            rt.failed.add(flat)
            rt.heartbeats.mark_dead(flat)
            vid, sub = self._vertex_of(flat)
            held = np.full((max(nr, 1),), max(nr, 1), np.int32)
            hl = rt.plan.replicas_held_by(flat)
            held[:len(hl)] = hl
            carry = self.programs.inject(vid)(
                carry, jnp.asarray(sub, jnp.int32),
                jnp.asarray(flat, jnp.int32), jnp.asarray(held))
        rt.executor.carry = carry

    # --- the warm standby ----------------------------------------------------

    def prewarm(self) -> float:
        """Compile every recovery program a standby will need, at job
        start — the reference keeps standby tasks *deployed* so failover
        only switches them to RUNNING (Task.java:300-302, :1040,
        Execution.java:373-377 state re-dispatch); the TPU analog of
        "deployed" is "XLA-compiled": after this, the failure path runs
        entirely on cached executables (recovery-time-to-resume drops from
        minutes of compile to milliseconds of replay).

        Requires ``num_standby >= 1`` (the knob that buys warm failover).
        Returns wall-clock seconds spent compiling
        (:meth:`RecoveryPrograms.warm` says what and at which shapes)."""
        rt = self.runner
        if rt.standbys.num_standby_per_vertex < 1:
            raise rec.RecoveryError(
                "prewarm_recovery needs num_standby >= 1 (no standby "
                "programs requested)")
        t0 = _time.monotonic()
        self.programs.warm(rt.executor.carry)
        # AOT-lower the standby's first-step (block) program into the
        # persistent compile cache too. A rehydrated standby's first
        # dispatch after restore is then a cache hit, not a recompile
        # in the finalize tail; a program that does not compile fails
        # the prewarm here, not the failover later.
        from clonos_tpu.utils.compile_cache import aot_lower_first_step
        aot_lower_first_step(rt.executor, self._mgroup)
        return _time.monotonic() - t0

    def drill(self, flats: Optional[Sequence[int]] = None) -> float:
        """Rehearse a failover end-to-end and return its wall-clock
        seconds: inject a failure, run the full recovery protocol, and
        rely on bit-identical recovery to leave the job state canonically
        unchanged (executor.canonical_carry: live log/ring content equal;
        physically-dead pre-fence slots may differ — nothing ever reads
        them). The reference's RunStandbyTaskStrategy keeps standby
        executions *running* (Task.java:300-302, Execution.java:373-377),
        so their whole failure path is hot; compiling programs
        (prewarm) is necessary but not sufficient for that — the
        first execution still pays allocator growth, transfer-path and
        host-pool warmup. One drill moves all of it off the real failure
        path.

        Default drill set: one subtask of every vertex class, failed
        together (a connected multi-class failure exercises every class's
        replay program and the staged topological recovery)."""
        rt = self.runner
        if rt.failed:
            raise rec.RecoveryError("cannot drill with real failures "
                                    "pending")
        if not rt.standbys.has_state():
            raise rec.RecoveryError(
                "failover_drill needs a completed checkpoint")
        t0 = _time.monotonic()
        fence = rt._fence_step[rt.standbys.latest.checkpoint_id + 1]
        if rt.global_step == fence:
            import warnings
            warnings.warn(
                "failover_drill at an epoch fence replays zero steps; "
                "run it mid-epoch so the chunked replay path executes")
        if flats is None:
            flats = [rt.job.subtask_base(v.vertex_id)
                     for v in rt.job.vertices]
        flats = list(flats)
        # The drill must NEVER corrupt a healthy job: verify every drilled
        # log has a surviving replica holder BEFORE zeroing any device
        # state (recover() makes the same check, but only after the
        # injection has already destroyed the state it needs).
        if rt.global_step > fence:
            fset = set(flats)
            for flat in flats:
                vid, _ = self._vertex_of(flat)
                if not rt.job.out_edges(vid):
                    continue       # sinks synthesize; no holder needed
                if not any(o == flat and h not in fset
                           for (o, h) in rt.plan.pairs):
                    raise rec.RecoveryError(
                        f"failover_drill: subtask {flat} would have no "
                        f"surviving determinant replica under drill set "
                        f"{sorted(fset)} — drill fewer subtasks at once "
                        f"or deepen sharing/replication")
            # Input reconstruction needs the whole replay window in the
            # upstream rings (or spill): check BEFORE zeroing state too.
            n_steps = rt.global_step - fence
            ring_steps = rt.executor.compiled.inflight_ring_steps
            if n_steps > ring_steps and rt.executor.spill_logs is None:
                raise rec.RecoveryError(
                    f"failover_drill: {n_steps} steps since the last "
                    f"completed checkpoint exceed the in-flight ring "
                    f"({ring_steps} steps) and spill is disabled — drill "
                    f"earlier or enable spill")
        self.inject_failure(flats)
        self.recover(drill=True)
        return _time.monotonic() - t0

    # --- recovery (reference §3.4 signature path) ----------------------------

    def recover(self, drill: bool = False,
                host_rows: Optional[Dict[int, Tuple[np.ndarray, int]]]
                = None,
                pre_patch_join: Optional[Callable[[], None]] = None
                ) -> RecoveryReport:
        """Public entry for :meth:`_run` that additionally lands an
        incident bundle (obs/incident.py) when the protocol itself
        fails — a recovery that cannot complete is exactly the moment
        the forensic state (ledgers, determinant windows, HLC timeline)
        is about to become unreachable. No-op passthrough when the
        incident plane is disabled."""
        rt = self.runner
        tr = get_tracer()
        # The phases run one after another on this thread, so they are a
        # chain of consecutive spans (children of ``recovery``) whose
        # stamps also fill ``RecoveryReport.phase_ms``.
        phases: Dict[str, float] = {}
        try:
            with tr.span("recovery", drill=bool(drill),
                         victims=sorted(rt.failed)) as span, \
                    tr.chain("recovery.", into=phases,
                             drill=bool(drill)) as chain:
                report = self._run(_Recovery(
                    chain, phases, drill, host_rows, pre_patch_join))
                span.set(from_epoch=report.from_epoch,
                         steps_replayed=report.steps_replayed,
                         records_replayed=report.records_replayed,
                         recovery_ms=report.recovery_ms)
                return report
        except Exception as e:
            from clonos_tpu.obs.incident import get_incidents
            get_incidents().signal(
                "recovery.failure",
                epoch=int(getattr(rt.auditor, "last_epoch", -1)),
                error=f"{type(e).__name__}: {str(e)[:200]}",
                drill=bool(drill),
                failed=sorted(rt.failed))
            raise

    def _run(self, r: _Recovery) -> RecoveryReport:
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
        joined (once) immediately before the FIRST graft —
        the earliest point recovery reads the roll-gap/async ledgers a
        bootstrap derives on a worker thread concurrently with this
        replay. Its blocked wall is attributed to
        ``finalize.listener-reattach``, not to the patch phase."""
        rt = self.runner
        if not rt.failed:
            raise rec.RecoveryError("no failed subtasks")
        # Defensive: inject_failure already drains the pipelined fence,
        # but recovery must never run against a half-sealed tail.
        rt._join_fence_tail()
        if not rt.standbys.has_state():
            raise rec.RecoveryError(
                "no completed checkpoint to restore standbys from")
        r.t0 = _time.monotonic()
        self._restore(r)
        self._fetch_determinants(r)
        for v in r.victims:
            self._fetch(r, v)
            self._inputs(r, v)
            self._replay(r, v)
            self._patch(r, v)
        self._replica_rebuild(r)
        self._finalize(r)
        return self._report(r)

    def _restore(self, r: _Recovery) -> None:
        """The restore point and what is restored from it: the failure
        decisions, the checkpoint, the carry to patch, the ring bounds."""
        rt = self.runner
        r.chain.switch("restore")
        topo_pos = {vid: i for i, vid in
                    enumerate(rt.executor.compiled.topo)}
        r.victims = sorted(
            (_Victim(f, *self._vertex_of(f)) for f in rt.failed),
            key=lambda v: (topo_pos[v.vid], v.flat))
        r.failed = tuple(v.flat for v in r.victims)

        # (1) RunStandbyTaskStrategy.onTaskFailure: ignore checkpoints the
        # dead tasks never acked; back off the checkpoint interval.
        if not r.drill:
            r.ignored = tuple(
                rt.coordinator.ignore_unacked_for(set(r.failed)))
            rt.coordinator.backoff()
            # Healthy tasks log the ignore decision (reference
            # StreamTask.ignoreCheckpoint:891-915 — the RPC arrival is a
            # determinant so their own later recoveries replay it).
            healthy = [f for f in range(rt.job.total_subtasks())
                       if f not in rt.failed]
            for cid in r.ignored:
                rt.executor.append_async_many(
                    healthy, det.IgnoreCheckpointDeterminant(
                        record_count=rt.executor.global_record_stamp(),
                        checkpoint_id=cid))

        ckpt = r.ckpt = rt.standbys.latest
        r.from_epoch = ckpt.checkpoint_id + 1
        r.fence = rt._fence_step[r.from_epoch]
        r.n_steps = rt.global_step - r.fence
        r.snap = jax.tree_util.tree_map(jnp.asarray, ckpt.carry)
        r.checkpoint_bytes = (int(getattr(ckpt, "size_bytes", 0) or 0)
                              or cp.carry_nbytes(ckpt.carry))
        r.carry = rt.executor.carry
        # Ring bounds for routing coverage decisions: the host mirror
        # (tails move only at checkpoint completion, heads advance one
        # per superstep == global_step) when valid, else one device read.
        # The device values recovery actually used are re-checked in the
        # final packed read either way (fail-loud, not trust).
        nrings = len(r.carry.out_rings)
        if nrings:
            r.bounds_dev = self.programs.ring_bounds()(r.carry.out_rings)
        if rt._ring_mirror_valid:
            # Heads advance once per superstep wherever the executor is
            # driven from; its own step ledger is the authoritative one.
            head_m = len(rt.executor.step_input_history)
            r.bounds = {ri: (rt._ring_tail_mirror, head_m)
                        for ri in range(nrings)}
        elif nrings:
            r.bounds = {ri: (int(tail), int(head)) for ri, (tail, head)
                        in enumerate(np.asarray(r.bounds_dev))}

    def _fetch_determinants(self, r: _Recovery) -> None:
        """Phase A: determinant metadata for ALL failed subtasks.
        Dispatch every per-subtask parse/meta program up front, then pay
        at most ONE host read for the whole failure set. Subtasks whose
        cleanness the host can derive itself (no async rows since the
        fence — executor.async_counts ledger — and fence log heads in
        hand) skip even that: their metadata becomes deferred asserts
        in the final packed read, and their replay defers its sync too:
        every host read stalls the dispatch queue behind it."""
        rt = self.runner
        r.chain.switch("fetch_determinants")
        with rt._ck_heads_lock:
            r.ck_heads = rt._ck_log_heads.get(r.ckpt.checkpoint_id)
        from_epoch_d = jnp.asarray(r.from_epoch, jnp.int32)
        slow_reads: List[Tuple[_Victim, str, Any]] = []
        for v in r.victims:
            if r.host_rows is not None and v.flat in r.host_rows:
                # External determinant source (standby-host mirror):
                # no device fetch/parse to dispatch at all.
                v.host = True
                continue
            whole = [(i, h) for i, (o, h) in enumerate(rt.plan.pairs)
                     if o == v.flat]
            v.holders = [(i, h) for i, h in whole if h not in rt.failed]
            if not v.holders and r.n_steps > 0 and rt.job.out_edges(v.vid):
                raise rec.RecoveryError(self._no_holder_message(v, whole))
            op = rt.job.vertices[v.vid].operator
            eligible = (bool(v.holders) and r.n_steps > 0
                        and op.replay_pad_safe
                        and not isinstance(op, HostFeedSource)
                        and r.n_steps <= self.programs.pad_steps())
            if eligible:
                *parsed, v.small_d = self.programs.device_parse()(
                    r.carry.replicas,
                    jnp.asarray(v.holders[0][0], jnp.int32), from_epoch_d)
                v.parsed = tuple(parsed)
            if v.holders:
                # At the log's whole holder count, the shape the warm-up
                # compiled: a connected failure leaves fewer, and the
                # rows past them ask the first survivor again.
                rows = [i for i, _ in v.holders]
                rows += rows[:1] * (len(whole) - len(rows))
                v.meta_d = self.programs.fetch_meta(len(whole))(
                    r.carry.replicas, jnp.asarray(rows, jnp.int32),
                    from_epoch_d)
                v.fetch_hops = int(self._hops[
                    v.vid, self._vertex_of(v.holders[0][1])[0]])
            v.fast = (eligible and r.ck_heads is not None
                      and v.vid not in rt.txn_logs
                      and rt.executor.async_rows_since(
                          v.flat, r.from_epoch) == 0)
            if not v.fast:
                slow_reads += [(v, name, getattr(v, name))
                               for name in ("small_d", "meta_d")
                               if getattr(v, name) is not None]
        if slow_reads:
            packed = np.asarray(jnp.concatenate(
                [d.reshape(-1).astype(jnp.int32) for _v, _k, d in slow_reads]))
            off = 0
            for v, name, d in slow_reads:
                n = int(np.prod(d.shape))
                setattr(v, name, packed[off: off + n].reshape(d.shape))
                off += n

    def _no_holder_message(self, v: _Victim,
                           whole: List[Tuple[int, int]]) -> str:
        """Names the log no survivor holds, and who held it."""
        job = self.runner.job

        def name(flat: int) -> str:
            vid, sub = self._vertex_of(flat)
            return f"{job.vertices[vid].name}[{sub}]"

        held = ", ".join(name(h) for _, h in whole) or "nobody"
        return (f"subtask {v.flat} ({name(v.flat)}): no surviving replica "
                f"holds its determinant log — it was kept by {held}, all "
                f"failed with it (sharing depth {job.sharing_depth} / "
                f"replication factor {self.runner.plan.replication_factor} "
                f"too shallow for this failure pattern)")

    def _scope_route_cache(self, r: _Recovery, vid: int) -> None:
        """Routed windows are valid only while the upstream rings they
        read are final — scope the share to one vertex's consumers
        (upstream vertices were patched earlier in topological order).
        The cache holds full [m, P, cap] blocks, so bound its bytes: past
        the budget every consumer takes the fused per-lane path instead
        of an OOM mid-recovery."""
        job = self.runner.job
        r.route_cache, r.routes_of = {}, vid
        # The exchange output is consumer-independent, but the all-lane
        # blocks are P-times a lane's size: sharing buys nothing for the
        # common single-subtask failure.
        share = sum(v.vid == vid for v in r.victims) >= 2
        if share and r.n_steps > 0:
            ch = self.programs.chunk
            est = sum(
                -(-r.n_steps // ch) * ch
                * job.vertices[job.edges[e].dst].parallelism
                * job.edges[e].capacity * 4 * 4
                for e in job.in_edges(vid))
            share = est <= (1 << 30)
        r.share_routes = share

    def _fetch(self, r: _Recovery, v: _Victim) -> None:
        """One subtask's determinants: the FSM up to REPLAYING, and the
        recovered stream (``v.rows`` from ``v.start``, or left on the
        device)."""
        rt = self.runner
        r.chain.switch("fetch_determinants")
        if v.vid != r.routes_of:
            self._scope_route_cache(r, v.vid)
        mgr = v.mgr = rec.RecoveryManager(
            v.vid, v.sub, v.flat, self.programs.replayer(v.vid, v.sub))
        in_edges = rt.job.in_edges(v.vid)
        out_edges = rt.job.out_edges(v.vid)

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
        no_rows = np.zeros((0, det.NUM_LANES), np.int32)
        if v.host:
            # Mirror-sourced determinants (whole-host loss): the rows
            # arrived over the wire; everything downstream of the
            # fetch (merge, replay, verify, patch) is identical.
            rows_h, start_h = r.host_rows[v.flat]
            mgr.expect_determinant_responses(1)
            mgr.notify_determinant_response(
                np.asarray(rows_h, np.int32), int(start_h))
        elif v.fast:
            # Host-derived cleanness: zero async rows since the fence
            # means the log holds exactly n_steps k-row sync blocks
            # starting at the checkpointed head. Everything the old
            # metadata read returned is therefore known here; the
            # device parse/meta values become deferred asserts.
            v.det_device = v.parsed
            v.clean_n = DETS_PER_STEP * r.n_steps
            v.start = int(r.ck_heads[v.flat])
            v.r_best = v.holders[0][0]
        elif v.holders:
            # Holders are bit-identical replicas by construction, so
            # when their metadata agrees the merge is "pull one body"
            # (saves H-1 multi-MB transfers + 2(H-1) round-trips).
            meta = v.meta_d
            consistent = (len(np.unique(meta[:, 0])) == 1
                          and len(np.unique(meta[:, 1])) == 1)
            # Clean path off the ledger fast lane: the device parse
            # (phase A) says whether the stream is pure sync rows; if
            # so the multi-MB body never crosses the host link.
            if consistent and v.small_d is not None:
                cnt_s, start_s, nanch, cleanflag = (
                    int(x) for x in v.small_d)
                if cleanflag and nanch == r.n_steps:
                    v.det_device = v.parsed
                    v.clean_n, v.start = cnt_s, start_s
            if v.det_device is None:
                use = ([v.holders[0]] if consistent else v.holders)
                mgr.expect_determinant_responses(len(use))
                for j, (i_rep, _h) in enumerate(use):
                    buf, _count, _start = self.programs.fetch()(
                        r.carry.replicas, jnp.asarray(i_rep, jnp.int32),
                        jnp.asarray(r.from_epoch, jnp.int32))
                    mgr.notify_determinant_response(
                        np.asarray(buf)[: int(meta[j, 0])],
                        int(meta[j, 1]))
            # A single consistent replica's device bytes can restore
            # the log directly; disagreeing holders must go through
            # the host merge (r_best None -> chunked upload path).
            v.r_best = v.holders[0][0] if consistent else None
        else:
            if r.n_steps > 0:
                # Pure sink: nobody downstream replicates its log. Its
                # inputs replay exactly from the upstream ring; its own
                # nondeterminism (time/rng step inputs) is re-synthesized
                # from the coordinator's input ledger. (The reference has
                # the same boundary: sink exactly-once needs transactional
                # sinks, TwoPhaseCommitSinkFunction.) Any other task
                # without a holder was refused in phase A.
                v.synthesized = True
            mgr.expect_determinant_responses(0)
        if v.det_device is not None:
            # The stream stays on the device: an empty response at its
            # start is all the FSM is told.
            v.rows = no_rows
            mgr.expect_determinant_responses(1)
            mgr.notify_determinant_response(no_rows, v.start)
        elif v.synthesized:
            v.rows = self._synthesize_det_rows(r.fence, r.n_steps)
            v.start = (int(r.ck_heads[v.flat]) if r.ck_heads is not None
                       else int(np.asarray(r.snap.log_heads[v.flat])))
        else:
            v.rows, v.start = mgr.merged_determinants()
        r.total_dets += (v.clean_n if v.clean_n is not None
                         else len(v.rows))

    def _inputs(self, r: _Recovery, v: _Victim) -> None:
        """Lost inputs: the checkpointed edge buffer (the depth-1 batch
        spanning the fence) + the upstream rings' raw outputs, re-routed
        through the deterministic exchange. Upstream ring shards zeroed
        by a connected failure were rebuilt earlier in the loop
        (topological order)."""
        rt = self.runner
        r.chain.switch("inputs")
        op = rt.job.vertices[v.vid].operator
        in_edges = rt.job.in_edges(v.vid)
        if isinstance(op, TwoInputOperator):
            v.input_steps = list(zip(
                self._replay_inputs(r, in_edges[0], v.sub),
                self._replay_inputs(r, in_edges[1], v.sub)))
        elif in_edges:
            v.input_steps = self._replay_inputs(r, in_edges[0], v.sub)
        elif isinstance(op, HostFeedSource) and r.n_steps > 0:
            v.input_steps = self._reread_feed(v.vid, v.sub, r.snap, v.rows,
                                              r.n_steps)

    def _replay(self, r: _Recovery, v: _Victim) -> None:
        """The replay proper, and what of the task's host side it
        rebuilds: re-fired timers, a sink's pending shards."""
        rt = self.runner
        r.chain.switch("replay")
        plan = rec.ReplayPlan(
            vertex_id=v.vid, subtask=v.sub, flat_subtask=v.flat,
            from_epoch=r.from_epoch, input_steps=v.input_steps,
            det_rows=v.rows, det_start=v.start,
            checkpoint_op_state=r.snap.op_states[v.vid],
            n_steps=r.n_steps, verify_outputs=not v.synthesized,
            det_device=v.det_device)
        r.restore_bytes += rec.plan_restore_nbytes(plan)
        # Fast path: replay dispatches only — output-cut verification
        # and the consumed total ride the final packed read.
        result = v.result = v.mgr.run_replay(plan, defer_sync=v.fast)
        if not result.deferred:
            r.total_records += result.records_replayed
        # Re-fire recovered timer effects (rows are already spliced
        # into the rebuilt log; only the callback side-effects re-run —
        # reference LogReplayerImpl.triggerAsyncEvent:102).
        svc = rt.timer_services.get(v.flat)
        if svc is not None and not r.drill:
            for _step_i, ad in result.async_events:
                if isinstance(ad, det.TimerTriggerDeterminant):
                    svc.refire(ad)
        # Transactional sink: its pending transaction shards died with
        # the task — rebuild them from the replayed outputs BEFORE any
        # commit can run (2PC abort+regenerate; TwoPhaseCommitSink
        # recoverAndAbort analog).
        if v.vid in rt.txn_logs and r.n_steps > 0:
            rt.txn_logs[v.vid].drop_uncommitted_shards(v.sub)
            self._rebuild_txn_shards(r, v)

    def _patch(self, r: _Recovery, v: _Victim) -> None:
        """Hold the regenerated determinant rows to the recovered ones,
        then graft the subtask into the carry."""
        r.chain.switch("patch")
        rebuilt = np.asarray(v.result.rebuilt_log_rows)
        # The regenerated determinant rows must equal the recovered ones
        # (bit-identical replay; reference post-replay log asserts).
        # Skipped when rebuilt IS the recovered buffer (clean path):
        # verify() already established the only re-derived lane
        # (BUFFER_BUILT) matches, and comparing a view against itself
        # would be dead work masquerading as a check.
        if not v.synthesized and not v.result.rebuilt_is_view \
                and not np.array_equal(
                    rebuilt, v.rows[: rebuilt.shape[0]]):
            raise rec.RecoveryError(
                f"subtask {v.flat}: replayed determinant stream diverges "
                f"from the recovered log")
        if r.pre_patch_join is not None:
            # Bootstrap's ledger-derivation thread must land before
            # _graft reads roll_gap_async; the blocked remainder is
            # the non-overlapped listener-reattach cost (the rest
            # rode inside the replay window above).
            r.chain.switch("finalize.listener-reattach")
            r.pre_patch_join()
            r.chain.switch("patch")    # the wait is not the patch's
            r.pre_patch_join = None
        self._graft(r, v, rebuilt)

    def _replica_rebuild(self, r: _Recovery) -> None:
        """Replica rows held by revived subtasks: replicas are identical
        to their owner's log by construction (same bulk appends), so
        rebuild by copying the owner's (possibly just-restored) log row —
        one batched scatter for the whole failure set. The carry is then
        whole: the executor takes it."""
        rt = self.runner
        r.chain.switch("replica_rebuild")
        rs, os_ = [], []
        for flat in r.failed:
            for i in rt.plan.replicas_held_by(flat):
                rs.append(i)
                os_.append(rt.plan.pairs[i][0])
        # Fixed-size scatters (padded with out-of-range rows, mode=drop)
        # so one prewarmed program serves every failure-set size.
        n = self.programs.REPLICA_COPY_ROWS
        for lo in range(0, len(rs), n):
            rs_p = np.full((n,), rt.plan.num_replicas, np.int32)
            os_p = np.zeros((n,), np.int32)
            rs_p[:len(rs[lo:lo + n])] = rs[lo:lo + n]
            os_p[:len(os_[lo:lo + n])] = os_[lo:lo + n]
            r.carry = r.carry._replace(
                replicas=self.programs.replica_copy()(
                    r.carry.replicas, r.carry.logs,
                    jnp.asarray(rs_p), jnp.asarray(os_p)))
        rt.executor.carry = r.carry
        r.route_cache = {}     # free the held routed device buffers

    def _finalize(self, r: _Recovery) -> None:
        """The final packed read: completion barrier + deferred asserts.
        ONE device->host transfer closes the protocol: the restored log
        heads (graft landed), the ring bounds recovery routed against,
        and for every fast-path subtask its parse/meta metadata, its
        on-device output-cut verification flag, and its consumed total.
        TPU programs execute in dispatch order, so this read — dispatched
        last — is also the barrier the old device_sync(patched) was.

        Sub-attribution: ``finalize.barrier-read`` = the packed
        concatenate + d2h transfer (dispatch-order barrier: it pays
        for every program still in flight), ``finalize.state-verify``
        = the host-side deferred asserts. The transfer drains on a
        worker thread while the main thread runs the audit validator
        inside the same window; the sub-spans keep their true walls
        and ``finalize.overlap-saved`` carries the credit, so
        sum(finalize.*) - overlap-saved == finalize (overlap
        attributed, never hidden). The join + deferred asserts run
        before recover() returns — a mis-speculated fast-path replay
        raises here, before any live step, with the audit validator
        as an independent gate on the replayed state. Revive
        bookkeeping runs after verify: a failed barrier/verify leaves
        the subtasks marked dead so the failure is retryable, never
        silently "healthy"."""
        # ``finalize`` is the chain's last span; its children below use
        # their own spans (the barrier's on whichever thread drains it).
        tr = get_tracer()
        phases, drill = r.phases, r.drill
        r.chain.switch("finalize")
        fin_span = tr.current_span()
        fin_before = phases.get("finalize", 0.0)
        with tr.span("recovery.finalize.barrier-dispatch",
                     drill=drill) as disp:
            fl_d = jnp.asarray(list(r.failed), jnp.int32)
            pieces = [r.carry.logs.head[fl_d].astype(jnp.int32)]
            if r.bounds_dev is not None:
                pieces.append(r.bounds_dev.reshape(-1).astype(jnp.int32))
            for v in r.victims:
                if v.fast:
                    pieces += [
                        v.small_d.astype(jnp.int32),
                        v.meta_d.reshape(-1).astype(jnp.int32),
                        v.result.verify_ok_d.astype(jnp.int32).reshape(1),
                        v.result.consumed_d.astype(jnp.int32).reshape(1)]
            packed_f = jnp.concatenate(pieces)        # dispatch only
        _add_ms(phases, "finalize.barrier-dispatch", disp.ms)
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
        # raises, runner.failed and the heartbeat table must still mark
        # the subtasks dead so a retry of recover() sees them. An audit
        # divergence is held and re-raised after verify (a verify
        # failure wins), and the join runs unconditionally so the
        # barrier thread never outlives this call.
        try:
            audit_span = self._audit(r)
        except Exception as err:
            audit_err = err
        finally:
            # KeyboardInterrupt/SystemExit skip the deferral but
            # still land here: the thread never leaks.
            th.join()
        if barrier["err"] is not None:
            raise barrier["err"]
        read = barrier["span"]
        _add_ms(phases, "finalize.barrier-read", read.ms)
        with tr.span("recovery.finalize.state-verify", drill=drill) as sp:
            r.total_records += self._verify(r, barrier["arr"])
        verify_ms = sp.ms
        _add_ms(phases, "finalize.state-verify", verify_ms)
        r.chain.close()
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
        rt = self.runner
        for flat in r.failed:
            rt.heartbeats.revive(flat)
        rt.failed.clear()
        if not drill:
            rt.coordinator.reset_interval()
        if audit_err is not None:
            raise audit_err
        _add_ms(phases, "finalize.overlap-saved", read.ms - exposed_ms)

    def _verify(self, r: _Recovery, arr_f: np.ndarray) -> int:
        """The deferred asserts over the final packed read; returns the
        fast-path subtasks' consumed records."""
        rt = self.runner
        verified_records = 0
        off_f = len(r.failed)
        heads_after = arr_f[:off_f]
        nrings = len(r.bounds)
        if nrings:
            bounds_np = arr_f[off_f: off_f + nrings * 2].reshape(nrings, 2)
            off_f += nrings * 2
            if rt._ring_mirror_valid:
                for ri in range(nrings):
                    want = (rt._ring_tail_mirror,
                            len(rt.executor.step_input_history))
                    got = (int(bounds_np[ri, 0]), int(bounds_np[ri, 1]))
                    if got != want:
                        raise rec.RecoveryError(
                            f"ring {ri}: host bound mirror {want} "
                            f"diverges from device bounds {got} — "
                            f"recovery routed against wrong "
                            f"coverage; state suspect")
        want_n = DETS_PER_STEP * r.n_steps
        for v in r.victims:
            if not v.fast:
                continue
            flat_m, res = v.flat, v.result
            ck_head_m = int(r.ck_heads[flat_m])
            small_np = arr_f[off_f: off_f + 4]
            off_f += 4
            nh = v.meta_d.shape[0]
            meta_np = arr_f[off_f: off_f + 2 * nh].reshape(nh, 2)
            off_f += 2 * nh
            ok_f = int(arr_f[off_f])
            consumed_f = int(arr_f[off_f + 1])
            off_f += 2
            if (tuple(int(x) for x in small_np)
                    != (want_n, ck_head_m, r.n_steps, 1)):
                raise rec.RecoveryError(
                    f"subtask {flat_m}: host-derived clean stream "
                    f"(n={want_n}, start={ck_head_m}, "
                    f"anchors={r.n_steps}) contradicted by device "
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
            head_after = int(heads_after[r.failed.index(flat_m)])
            if head_after != ck_head_m + want_n:
                raise rec.RecoveryError(
                    f"subtask {flat_m}: restored log head {head_after}"
                    f" != fence head {ck_head_m} + {want_n} rows")
            if not ok_f:
                # Resolve the device arrays and let verify() build
                # the detailed divergence message (failure path: the
                # extra transfer is fine).
                res.emit_counts = np.asarray(res.emit_counts)
                res.expected_emits = np.asarray(res.expected_emits)
                try:
                    res.verify()
                except rec.RecoveryError as err:
                    raise rec.RecoveryError(
                        f"subtask {flat_m}: {err}") from None
                raise rec.RecoveryError(
                    f"subtask {flat_m}: device verify flag tripped "
                    f"but host recheck passed — flag/stream mismatch")
            res.records_replayed = consumed_f
            verified_records += consumed_f
        return verified_records

    def _audit(self, r: _Recovery):
        """Audit validation (obs/audit.py): recompute every replayed
        closed epoch's digest from the patched carry and compare
        against the sealed ledger — one match/divergence instant
        per epoch lands under this recovery's trace id. Abort
        policy raises AuditDivergenceError here: fail loudly
        before the job resumes on state that did not reproduce
        the original execution.
        Returns its span (None with the audit off)."""
        rt = self.runner
        if not rt.auditor.enabled:
            return None
        with get_tracer().span("recovery.audit", drill=r.drill) as sp:
            validator = rec.AuditValidator(
                rt.executor, rt.coordinator.read_ledger(),
                on_divergence=rt.auditor.on_divergence)
            try:
                validator.validate(
                    range(r.from_epoch, rt.executor.epoch_id))
            finally:
                # evidence reaches the metrics plane even when the
                # abort policy throws mid-validation
                self._m_audit_matches.inc(validator.stats["match"])
                self._m_audit_div.inc(validator.stats["divergence"])
        _add_ms(r.phases, "audit", sp.ms)
        return sp

    def _report(self, r: _Recovery) -> RecoveryReport:
        report = RecoveryReport(
            failed_subtasks=r.failed, from_epoch=r.from_epoch,
            steps_replayed=r.n_steps, determinants_replayed=r.total_dets,
            records_replayed=r.total_records,
            ignored_checkpoints=r.ignored,
            recovery_ms=(_time.monotonic() - r.t0) * 1e3,
            managers=tuple(v.mgr for v in r.victims), phase_ms=r.phases,
            drill=r.drill, restore_bytes=r.restore_bytes,
            checkpoint_bytes=r.checkpoint_bytes,
            route_cache_hits=r.route_cache_hits,
            fetch_hops=max(v.fetch_hops for v in r.victims))
        if not r.drill:
            tr = get_tracer()
            tr.count("recovery.victims", report.victims)
            tr.count("recovery.fetch_hops", report.fetch_hops)
            # Rehearsals must not inflate the recovery count/latency
            # series operators alert on.
            self.reports.append(report)
            self._m_recovery_ms.update(report.recovery_ms)
            self._m_recovered_records.inc(report.records_replayed)
            # Per-phase latency distributions (recovery.replay-ms p50/p99
            # etc.) — the tuning surface for the paper's headline claim.
            for pname, ms in r.phases.items():
                self._mgroup.histogram(f"recovery.{pname}-ms").update(ms)
        return report

    def _rebuild_txn_shards(self, r: _Recovery, v: _Victim) -> None:
        """Reconstruct the failed sink subtask's pending transaction
        shards from its replayed output chunks, epoch by epoch."""
        rt = self.runner
        tl = rt.txn_logs[v.vid]
        chunks = [jax.tree_util.tree_map(np.asarray, c)
                  for c in (v.result.out_chunks or [])]

        def steps_slice(lo: int, hi: int) -> np.ndarray:
            rows = []
            for i, c in enumerate(chunks):
                ch_n = c.keys.shape[0]
                base = i * self.programs.chunk
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

        cur = rt.executor.epoch_id
        fence, n_steps = r.fence, r.n_steps
        for e in range(r.from_epoch, cur + 1):
            if e not in rt._fence_step:
                continue
            lo = rt._fence_step[e] - fence
            hi = (rt._fence_step.get(e + 1, fence + n_steps) - fence
                  if e < cur else n_steps)
            tl.rebuild_shard(e, v.sub, steps_slice(lo, min(hi, n_steps)))

    # --- input reconstruction ------------------------------------------------

    def _ring_steps(self, r: _Recovery, src_vid: int, start: int,
                    n: int, need: int):
        """Raw output steps [start, start+n) of a producer vertex, from the
        device ring — falling back to the host spill for steps the ring no
        longer retains (reference SpilledReplayIterator.java:61).

        ``need``: how many leading steps must actually be present. With
        need < n the returned [n]-shaped batch may hold dead entries past
        ``need`` — chunked replay reads fixed-size [CH] windows whose
        tail can extend past the ring head."""
        executor = self.runner.executor
        ri = executor.compiled.ring_index[src_vid]
        el = r.carry.out_rings[ri]
        # Coverage math from the recovery's bounds (one read per
        # recover(); ring offsets are stable across recovery — write-backs
        # replace contents only), so this costs zero host round-trips.
        tail, head = r.bounds[ri]
        got_start = max(start, tail)
        cnt = max(min(head - got_start, n), 0)
        # Steps physically retained by the ring: slice_steps only clamps to
        # ``tail``, but when checkpoints stall past ring capacity newer
        # appends have clobbered positions of steps < head - ring_steps —
        # those must come from the spill even though tail hasn't advanced.
        ring_lo = max(tail, head - el.ring_steps)
        batch, _, _ = self.programs.ring_chunk(ri, n)(
            el, jnp.asarray(start, jnp.int32))
        if got_start == start and start >= ring_lo and cnt >= need:
            return batch
        # Ring shortfall: pull the missing leading steps from the spill.
        if executor.spill_logs is None:
            raise rec.RecoveryError(
                f"in-flight log of vertex {src_vid} lost steps "
                f"[{start}, {max(got_start, ring_lo)}) and spill is disabled")
        spill = executor.spill_logs[ri]
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

    def _replay_inputs(self, r: _Recovery, eidx: int, sub: int):
        """The failed consumer's lost inputs on edge ``eidx``: the
        checkpointed depth-1 edge buffer (its input at the first lost step)
        followed by the upstream's ring outputs [fence, fence+n-1), routed
        through the deterministic exchange.

        Returns a LIST of block-sized chunks ([CH, cap] each; the last
        covers the tail) so every device program here is fixed-shape and
        prewarm-compiled — recovery pays no XLA compile (warm standby)."""
        progs, fence, n_steps = self.programs, r.fence, r.n_steps
        e = self.runner.job.edges[eidx]
        ch = progs.chunk
        ri = progs.compiled.ring_index[e.src]
        first = jax.tree_util.tree_map(
            lambda x: x[sub][None], r.snap.edge_bufs[eidx])
        if n_steps <= 0:
            return []
        el = r.carry.out_rings[ri]
        tail, head = r.bounds[ri]
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
        rr_d = jnp.asarray(r.snap.rr_offsets[eidx][0], jnp.int32)
        need_d = jnp.asarray(n_steps, jnp.int32)
        lead_d = jnp.asarray(1, jnp.int32)
        chunks = []
        for i in range(-(-n_steps // ch)):
            h_start = fence - 1 + i * ch
            # Real ring steps this window must provide (its live slots).
            lo_real = max(h_start, fence)
            hi_real = min(h_start + ch, fence - 1 + n_steps)
            h_need = max(hi_real - lo_real, 0)
            covered = (lo_real >= ring_lo and lo_real >= tail
                       and head - lo_real >= h_need)

            def raw_window():
                # Spill-backed window, shaped like the ring window: pull
                # the real steps from ring+spill and shift window 0 down
                # one slot (its dead leading slot carries no step).
                raw = self._ring_steps(r, e.src, lo_real, ch, need=h_need)
                if i == 0:
                    raw = jax.tree_util.tree_map(
                        lambda x: jnp.roll(x, 1, axis=0).at[0].set(
                            jnp.zeros_like(x[0])), raw)
                return raw

            # The ring's own window where it covers the steps, else a
            # host-assembled one through the spill-path twin.
            route = progs.route_chunk if covered else progs.route_raw
            if not r.share_routes:
                # Single failed consumer: the fused variant scatters only
                # this lane's rows (~P times cheaper than materializing
                # the whole routed block).
                lane, start_d, rr_d, need_d, lead_d = \
                    route(eidx, ch, False)(
                        el if covered else raw_window(), start_d, sub_d,
                        rr_d, need_d, lead_d)
            else:
                # Multiple failed consumers: route the window once to all
                # lanes, cache it, and lane-select per consumer
                # (_scope_route_cache scopes the cache to one vertex's
                # group).
                key = (eidx, i)
                routed = r.route_cache.get(key)
                if routed is not None:
                    r.route_cache_hits += 1
                else:
                    routed, start_d, rr_d, need_d, lead_d = \
                        route(eidx, ch, True)(
                            el if covered else raw_window(), start_d,
                            rr_d, need_d, lead_d)
                    r.route_cache[key] = routed
                lane = progs.lane_select(eidx, ch)(routed, sub_d)
            chunks.append(progs.first_chunk(eidx)(first, lane) if i == 0
                          else lane)
        return chunks

    def _reread_feed(self, vid: int, sub: int, snap: LeanSnapshot,
                     rows: np.ndarray, n_steps: int):
        """Rebuild a HostFeedSource's lost input batches: offset from the
        checkpointed operator state, per-step pull counts from the recorded
        BUFFER_BUILT determinants, records from the rewindable reader.
        Returns block-sized chunks (zero-padded tail) like
        :meth:`_replay_inputs`."""
        reader = self.runner.executor.feed_readers.get(vid)
        if reader is None:
            raise rec.RecoveryError(
                f"vertex {vid}: HostFeedSource has no registered feed "
                f"reader to re-read from")
        b = self.runner.job.vertices[vid].operator.batch_size
        anchors = det.sync_anchors(rows)[:n_steps]
        counts = rows[anchors + 3, det.LANE_P].astype(np.int64)
        offset = int(np.asarray(snap.op_states[vid]["offset"][sub]))
        ch = self.programs.chunk
        padded = -(-n_steps // ch) * ch
        keys = np.zeros((padded, b), np.int32)
        vals = np.zeros((padded, b), np.int32)
        valid = np.zeros((padded, b), bool)
        for i, c in enumerate(counts):
            ks, vs = reader.read_at(sub, offset, int(c))
            keys[i, :int(c)], vals[i, :int(c)] = ks, vs
            valid[i, :int(c)] = True
            offset += int(c)
        zts = np.zeros((padded, b), np.int32)
        return [RecordBatch(jnp.asarray(keys[lo:lo + ch]),
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
        hist = self.runner.executor.step_input_history[
            fence_global: fence_global + n_steps]
        if len(hist) < n_steps:
            raise rec.RecoveryError("step-input ledger shorter than the "
                                    "lost step range")
        rows = np.zeros((n_steps * DETS_PER_STEP, det.NUM_LANES), np.int32)
        for i, (t, rng) in enumerate(hist):
            base = i * DETS_PER_STEP
            rows[base, det.LANE_TAG] = det.TIMESTAMP
            rows[base, det.LANE_P] = -1 if t < 0 else 0
            rows[base, det.LANE_P + 1] = t
            rows[base + 1, det.LANE_TAG] = det.RNG
            rows[base + 1, det.LANE_P] = rng
            rows[base + 2, det.LANE_TAG] = det.ORDER
            rows[base + 3, det.LANE_TAG] = det.BUFFER_BUILT
        return rows

    # --- the graft -----------------------------------------------------------

    def _epoch_index(self, r: _Recovery, v: _Victim, det_rows: np.ndarray,
                     n: int, ck_head: int):
        """Epoch->offset index entries died with the task; rebuild them
        from the fence-step ledger. Sync blocks anchor at TIMESTAMP
        rows. Returns (offsets, mask, latest epoch) over the log's
        ``max_epochs`` slots."""
        rt = self.runner
        if v.det_device is not None:
            # Device-resident stream: the rows never came to the host,
            # but the stream is pure k-row sync blocks so the anchors are
            # exactly ``i * DETS_PER_STEP``.
            ts_pos = np.arange(n // DETS_PER_STEP,
                               dtype=np.int64) * DETS_PER_STEP
        elif n > 0:
            ts_pos = det.sync_anchors(det_rows)
        else:
            ts_pos = np.zeros((0,), np.int64)
        me = rt.executor.compiled.max_epochs
        epoch_offs = np.zeros((me,), np.int32)
        epoch_mask = np.zeros((me,), bool)
        latest = 0
        for e in range(r.from_epoch, rt.executor.epoch_id + 1):
            if e in rt._fence_step:
                step_i = rt._fence_step[e] - r.fence
                # from_epoch starts exactly at the checkpointed head (async
                # rows appended in the roll gap come after the fence);
                # later fences anchor at their first step's TIMESTAMP row
                # minus the roll-gap ledger — async rows appended after
                # the roll but before the epoch's first step (fence
                # SOURCE_CHECKPOINTs, ignore broadcasts, between-epoch
                # service calls) precede that anchor yet belong to the
                # NEW epoch (executor.roll_gap_async).
                gap = rt.executor.roll_gap_async.get((v.flat, e), 0)
                if step_i == 0:
                    off = ck_head
                elif step_i < len(ts_pos):
                    off = ck_head + int(ts_pos[step_i]) - gap
                else:
                    off = ck_head + n - gap
                epoch_offs[e % me] = off
                epoch_mask[e % me] = True
                latest = max(latest, e)
        return epoch_offs, epoch_mask, latest

    def _graft(self, r: _Recovery, v: _Victim, det_rows: np.ndarray
               ) -> None:
        """Graft the rebuilt subtask back into the live carry. Every
        device program here is fixed-shape (chunked appends/writes) so a
        prewarmed standby pays zero XLA compile on the failure path.
        ``v.clean_n`` is a device-resident stream's device-verified row
        count."""
        progs, compiled = self.programs, self.programs.compiled
        carry, result, flat = r.carry, v.result, v.flat
        fence, n_steps, from_epoch = r.fence, r.n_steps, r.from_epoch
        ck_head = (int(r.ck_heads[flat]) if r.ck_heads is not None
                   else int(np.asarray(r.snap.log_heads[flat])))
        n = det_rows.shape[0] if v.clean_n is None else v.clean_n
        epoch_offs, epoch_mask, latest = self._epoch_index(
            r, v, det_rows, n, ck_head)
        index = (jnp.asarray(epoch_offs), jnp.asarray(epoch_mask),
                 jnp.asarray(latest, jnp.int32),
                 jnp.asarray(from_epoch, jnp.int32))
        if v.r_best is not None:
            # The replayed stream was verified equal to the recovered one,
            # so the replica's device bytes ARE the restored log (no h2d).
            restored = progs.log_restore_from_replica()(
                carry.replicas, jnp.asarray(v.r_best, jnp.int32),
                jnp.asarray(from_epoch, jnp.int32),
                jnp.asarray(n, jnp.int32), jnp.asarray(ck_head, jnp.int32),
                *index)
        else:
            # Synthesized streams (sink recovery) upload in fixed chunks.
            ch4 = progs.chunk * DETS_PER_STEP
            restored = clog.create(compiled.log_capacity,
                                   compiled.max_epochs)
            base = jnp.asarray(ck_head, jnp.int32)
            restored = restored._replace(head=base, tail=base)
            for lo in range(0, n, ch4):
                cnt = min(ch4, n - lo)
                chunk = np.zeros((ch4, det.NUM_LANES), np.int32)
                chunk[:cnt] = det_rows[lo:lo + cnt]
                restored = progs.log_restore()(
                    jnp.asarray(chunk), jnp.asarray(cnt, jnp.int32),
                    restored)
            restored = progs.log_finalize()(restored, *index)
        # Operator state slice + log row + record count in one program.
        # Deferred replays keep the consumed total on device — the add
        # happens there and the host never waits for it.
        rc = r.snap.record_counts[flat] + (
            result.consumed_d if result.deferred
            else result.records_replayed)
        sub_j = jnp.asarray(v.sub, jnp.int32)
        carry = progs.graft(v.vid)(
            carry, result.op_state, restored, sub_j,
            jnp.asarray(flat, jnp.int32), rc)
        # In-flight ring shard reconstruction: write the replayed outputs
        # back into the producer's ring at their original step offsets
        # (reference buildAndLogBuffer — the standby re-cuts identical
        # buffers and re-logs them so downstream recoveries can be
        # served). Only the last ring_steps replayed steps fit; earlier
        # chunks are masked out (spill-backed replays longer than the
        # ring must not wrap into newer steps).
        if v.vid in compiled.ring_index and result.out_chunks is not None \
                and n_steps > 0:
            rings = list(carry.out_rings)
            ri = compiled.ring_index[v.vid]
            el = rings[ri]
            kept_from = fence + n_steps - min(n_steps, el.ring_steps)
            keep_from = jnp.asarray(kept_from, jnp.int32)
            hi = jnp.asarray(fence + n_steps, jnp.int32)
            base_d = None
            for i, chunk in enumerate(result.out_chunks):
                m = chunk.keys.shape[0]
                base_i = fence + i * progs.chunk
                if base_i + m <= kept_from:
                    continue      # wholly before the retained window
                if base_d is None:
                    base_d = jnp.asarray(base_i, jnp.int32)
                el, base_d = progs.ring_write(ri, m)(
                    el, chunk, base_d, sub_j, keep_from, hi)
            rings[ri] = el
            carry = carry._replace(out_rings=tuple(rings))
        r.carry = carry
