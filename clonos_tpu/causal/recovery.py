"""Causal recovery: the standby-replay protocol.

Capability parity with the reference's recovery core
(flink-runtime .../causal/recovery/ — RecoveryManager.java:37-60 state
machine Standby -> WaitingConnections -> WaitingDeterminants -> Replaying ->
Running, with synchronized event dispatch :66-108; WaitingDeterminantsState
sends InFlightLogRequest + DeterminantRequest events :126-155 and merges
responses; ReplayingState rebuilds output buffers from BufferBuilt
determinants :136-215; LogReplayerImpl serves recorded values back and
asserts post-replay log-length equality :121-133) — re-designed TPU-first:

- The FSM stays on the **host** (it runs once per failure, not per record),
  but replay itself is **one ``lax.scan`` on device**: the lost epochs'
  input batches (from the upstream in-flight rings) and the failed task's
  determinant tensor (merged from downstream replicas) are stacked along a
  steps axis and the vertex's operator is scanned over them. The JVM's
  record-at-a-time replay loop becomes a single compiled program — this is
  where the >=10x replay-rate target lands (BASELINE.md).
- Determinants arrive as the packed ``int32[n, 8]`` rows the log already
  stores; because the executor's per-step layout is fixed (TIMESTAMP, RNG,
  ORDER, BUFFER_BUILT — executor.DETS_PER_STEP = 4), the replayer locates
  the ``[steps, 4, lanes]`` sync blocks and reads payload lanes directly.
- Output reconstruction: the replayed operator re-emits its output batches;
  the replayer verifies each batch's record count against the recorded
  BUFFER_BUILT determinant (the bit-identical buffer-cut check,
  PipelinedSubpartition.buildAndLogBuffer:536-571) and *discards* the
  batches — downstream already consumed them (the dedup the reference gets
  from numBuffersToSkip).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.api.operators import OpContext, Operator, TwoInputOperator
from clonos_tpu.api.records import RecordBatch
from clonos_tpu.causal import determinant as det
from clonos_tpu.causal import log as clog
from clonos_tpu.obs import get_tracer as _get_tracer
from clonos_tpu.ops.histogram import over_mesh


class RecoveryState(enum.Enum):
    STANDBY = "standby"
    WAITING_CONNECTIONS = "waiting_connections"
    WAITING_DETERMINANTS = "waiting_determinants"
    REPLAYING = "replaying"
    RUNNING = "running"


class RecoveryError(RuntimeError):
    pass


class AuditDivergenceError(RecoveryError):
    """A replayed epoch's recomputed audit digest does not match the
    sealed ledger entry — the exactly-once replay contract is violated
    (raised only under ``observability.audit.on-divergence = abort``)."""


class AuditValidator:
    """Recovery-time half of the epoch audit ledger (obs/audit.py).

    After the causal replay has patched the failed subtasks back into the
    live carry, the validator recomputes each replayed epoch's digest
    from the SAME extraction path the live seal used
    (``LocalExecutor.epoch_window`` + ``digest_epoch_window``) and
    compares it against the persisted ledger — turning "replay is
    bit-identical" from a test-time hope into a runtime invariant. Every
    epoch emits a ``recovery.audit.match`` / ``recovery.audit.divergence``
    / ``recovery.audit.missing`` instant into the active recovery trace;
    the first divergence names the epoch and channel (which subtask's
    determinant log or which vertex's output ring went off-script) and,
    under the ``abort`` policy, raises :class:`AuditDivergenceError`.
    """

    def __init__(self, executor, ledger_entries: Sequence[dict],
                 on_divergence: str = "warn"):
        self.executor = executor
        # last-wins per epoch: a rebuilt runner appends fresh seals for
        # post-recovery epochs to the same durable ledger
        self.ledger: Dict[int, dict] = {
            int(e["epoch"]): e for e in ledger_entries}
        self.on_divergence = on_divergence
        #: running totals — still accurate when the abort policy throws
        #: mid-validation (the caller's metrics read these, not the
        #: return value)
        self.stats: Dict[str, int] = {"match": 0, "divergence": 0,
                                      "missing": 0}

    def validate(self, epochs: Sequence[int]) -> Dict[str, int]:
        """Validate the given replayed (closed) epochs against the
        ledger. Returns ``{"match": n, "divergence": n, "missing": n}``;
        raises under the abort policy after emitting the divergence
        instant (the flight recorder keeps the evidence either way)."""
        from clonos_tpu.obs import audit as _audit
        from clonos_tpu.obs.digest import EpochDigest, diff as _diff
        tr = _get_tracer()
        stats = self.stats
        for e in epochs:
            e = int(e)
            recomputed = _audit.digest_epoch_window(
                e, self.executor.epoch_window(e))
            entry = self.ledger.get(e)
            if entry is None:
                stats["missing"] += 1
                tr.event("recovery.audit.missing", epoch=e)
                continue
            d = _diff(EpochDigest.from_entry(entry), recomputed)
            if d is None:
                stats["match"] += 1
                tr.event("recovery.audit.match", epoch=e,
                         channels=len(recomputed.channels),
                         records=recomputed.record_count())
            else:
                stats["divergence"] += 1
                channel, reason = d
                tr.event("recovery.audit.divergence", epoch=e,
                         channel=channel, reason=reason)
                # Flight-recorder trigger: the replayed epoch went
                # off-script — bundle the evidence before the abort
                # policy (possibly) tears recovery down. No-op when
                # the incident plane is disabled.
                from clonos_tpu.obs.incident import get_incidents
                get_incidents().signal("audit.divergence", epoch=e,
                                       channel=channel, reason=reason,
                                       source="recovery-validator")
                if self.on_divergence == "abort":
                    raise AuditDivergenceError(
                        f"epoch {e} channel {channel}: {reason} — replay "
                        f"did not reproduce the original execution")
        return stats

    def recompute_entries(self, epochs: Sequence[int]) -> List[dict]:
        """Recompute the given epochs' digests from the CURRENT carry
        and return them as ledger entries (``EpochDigest.to_entry``
        dicts) WITHOUT validating against the persisted ledger — the
        raw material for a ``diff_ledgers`` comparison between two
        runs (``tests/test_pipelined_fence.py`` shows the pipelined
        fence bit-identical to the inline one this way)."""
        from clonos_tpu.obs import audit as _audit
        return [_audit.digest_epoch_window(
                    int(e), self.executor.epoch_window(int(e))).to_entry()
                for e in epochs]


@dataclasses.dataclass
class ReplayPlan:
    """Everything a standby needs to replay one failed subtask."""

    vertex_id: int
    subtask: int                    # subtask index within the vertex
    flat_subtask: int               # global flat id (log row)
    from_epoch: int                 # first lost epoch (checkpoint + 1 ...)
    #: the lost input batches: a LIST of block_steps-sized chunks (each a
    #: RecordBatch [CH, cap] for single-input vertices, a (left, right)
    #: pair for TwoInputOperator vertices), or None for self-generating
    #: sources. Chunks keep every device program shape-static so the
    #: whole replay runs on programs compiled at job start (warm standby
    #: — no XLA in the failure path).
    input_steps: Optional[List[Any]]
    det_rows: np.ndarray            # int32[m, lanes] merged determinant rows
    det_start: int                  # absolute offset of det_rows[0]
    checkpoint_op_state: Any        # failed vertex's op state [P, ...] slice
    n_steps: int                    # lost supersteps to replay
    #: False when the determinant rows were synthesized rather than
    #: recovered from replicas (pure-sink recovery: no downstream holds the
    #: sink's log; its inputs replay exactly but its own output cuts have
    #: no recorded value to check against).
    verify_outputs: bool = True
    #: Device-resident determinant stream for the clean fast path
    #: (consistent replica, pure sync rows): (times, rngs, expected)
    #: int32 device arrays padded to the replayer's ``pad_steps``. When
    #: set, ``det_rows`` stays empty — the multi-MB log body never
    #: crosses the host link (it was parsed ON DEVICE;
    #: runtime/recovery_programs.py ``device_parse``).
    det_device: Optional[Any] = None


@dataclasses.dataclass
class ReplayResult:
    op_state: Any                   # rebuilt [1, ...] subtask state slice
    rebuilt_log_rows: np.ndarray    # regenerated determinant rows (sync
                                    # blocks re-derived, async rows spliced
                                    # back at their recorded positions)
    emit_counts: np.ndarray         # [n] replayed output batch cuts (host)
    expected_emits: np.ndarray      # [n] recorded BUFFER_BUILT values
    #: the replayed operator's rebuilt output batches as a list of
    #: block-sized chunks [CH, out_cap] (last chunk may be shorter) — the
    #: reconstruction of the failed producer's in-flight log shard
    #: (reference PipelinedSubpartition.buildAndLogBuffer:536-599: the
    #: standby re-cuts bit-identical buffers and re-logs them). Chunked so
    #: the ring write-back reuses prewarmed fixed-shape programs.
    out_chunks: Optional[List[RecordBatch]]
    records_replayed: int
    #: async determinants recovered from the log: (step_index, determinant)
    #: fired before superstep ``step_index`` of the replay range (reference
    #: LogReplayerImpl.triggerAsyncEvent:102 — the control plane re-fires
    #: their effects; services replay their values).
    async_events: List[Tuple[int, det.Determinant]] = dataclasses.field(
        default_factory=list)
    #: True when rebuilt_log_rows is a view of the recovered rows (the
    #: clean fast path, where verify() already establishes equality) —
    #: callers must not "re-verify" it against the same buffer.
    rebuilt_is_view: bool = False
    #: Deferred-sync replay (``replay(plan, defer_sync=True)``): nothing
    #: crossed the host link — ``emit_counts``/``expected_emits`` are
    #: device arrays, ``records_replayed`` is -1 until the cluster's
    #: final packed read resolves it, and verification is the device
    #: flag ``verify_ok_d`` (folded into that same read). Every host
    #: sync stalls the dispatch queue, so the warm failure path defers
    #: them all into one.
    deferred: bool = False
    verify_ok_d: Optional[Any] = None
    consumed_d: Optional[Any] = None

    def verify(self) -> None:
        """Post-replay equality asserts (reference LogReplayerImpl:127,
        ReplayingState:196): every replayed output cut must equal the
        recorded one."""
        got = np.asarray(self.emit_counts)
        want = np.asarray(self.expected_emits)
        if not np.array_equal(got, want):
            bad = np.nonzero(got != want)[0]
            raise RecoveryError(
                f"replay diverged: output batch cuts differ at replayed "
                f"steps {bad.tolist()} (got {got[bad].tolist()}, recorded "
                f"{want[bad].tolist()})")


def plan_restore_nbytes(plan: ReplayPlan) -> int:
    """Bytes this plan's shard-local restore moves: the failed subtask's
    slice of the checkpointed vertex state (one row of the [P, ...]
    pytree — healthy subtasks' rows stay in their live buffers), the
    recovered determinant stream, and the replayed input windows. The
    per-shard numerator of RecoveryReport.restore_bytes; compare against
    checkpoint.carry_nbytes of the full snapshot to see what a global
    rollback would have moved instead."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(plan.checkpoint_op_state):
        n0 = getattr(leaf, "shape", (1,))[0] if getattr(
            leaf, "ndim", 0) > 0 else 1
        total += int(getattr(leaf, "nbytes", 0)) // max(1, n0)
    if plan.det_rows is not None and getattr(plan.det_rows, "size", 0):
        total += int(plan.det_rows.nbytes)
    elif plan.det_device is not None:
        total += sum(int(np.prod(x.shape)) * 4
                     for x in plan.det_device if hasattr(x, "shape"))
    if plan.input_steps is not None:
        for leaf in jax.tree_util.tree_leaves(plan.input_steps):
            total += int(getattr(leaf, "nbytes", 0))
    return total


class LogReplayer:
    """Serves recorded determinants back and drives the on-device replay
    (reference LogReplayer/LogReplayerImpl.java:36-157). Replay runs the
    operator's **block form** over the lost step range — the same
    step-batched kernels as the live path, so a multi-thousand-step replay
    is a handful of fused programs, not a per-step loop (this is where the
    >=10x replay-rate target lands, BASELINE.md)."""

    def __init__(self, operator: Operator, parallelism: int,
                 block_steps: int = 512, in_slot_keys=None,
                 pad_steps: Optional[int] = None, mesh=None,
                 task_axis: str = "tasks",
                 vertex_name: Optional[str] = None):
        self.operator = operator
        #: the ``vertex/<name>`` named scope of the replay program's ops
        self.vertex_name = vertex_name or type(operator).__name__
        self.parallelism = parallelism
        self.block_steps = block_steps
        #: fixed upper bound to pad the uploaded time/rng streams to (the
        #: recoverable window, e.g. the in-flight ring depth): keeps the
        #: tslice program's input shape INDEPENDENT of n_steps, so the
        #: prewarmed executable serves every failure instead of
        #: recompiling on the failure path when n differs from the drill.
        self.pad_steps = (-(-pad_steps // block_steps) * block_steps
                          if pad_steps else None)
        #: static [1, cap] input-slot keys when the failed subtask's input
        #: edge is statically routed (routing.StaticRoutePlan) — replay
        #: then uses the same fast static-gather aggregation as the live
        #: block program.
        self.in_slot_keys = in_slot_keys
        # Share compiled replay programs across LogReplayer instances for
        # the same (operator, block shape, slot keys, mesh): a later
        # failure of the same vertex must not pay a retrace (the jit cache
        # is per-wrapper, and RecoveryManagers are built per failure).
        cache = operator.__dict__.setdefault("_replay_jit_cache", {})
        key = (parallelism, block_steps,
               None if in_slot_keys is None
               else np.asarray(in_slot_keys).tobytes(), mesh, task_axis)
        if key not in cache:
            cache[key] = jax.jit(
                over_mesh(self._replay_block, mesh, task_axis))
        self._jit_block = cache[key]
        skey = ("tslice", block_steps)
        if skey not in cache:
            cache[skey] = jax.jit(lambda v, lo: jax.lax.dynamic_slice(
                v, (lo,), (block_steps,)))
        self._jit_tslice = cache[skey]

    def _replay_block(self, op_state, batches, times, rngs, subtask,
                      consumed_in):
        """One block of replay: state has leading dim 1 (the failed subtask
        alone); operators are written over an arbitrary leading P dim, so
        the same block code replays one subtask that ran as one lane of P.
        ``consumed_in`` is the running consumed-record total — accumulated
        INSIDE the program so the loop's end needs no extra eager
        stack/sum dispatches."""
        from clonos_tpu.api.operators import BlockContext
        lift = lambda b: jax.tree_util.tree_map(lambda x: x[:, None], b)
        bctx = BlockContext(
            times=times, rng_bits=rngs, epoch=jnp.zeros((), jnp.int32),
            step0=jnp.zeros((), jnp.int32), subtask=subtask[None])
        with jax.named_scope(f"vertex/{self.vertex_name}"):
            if isinstance(self.operator, TwoInputOperator):
                left, right = batches
                new_state, out = self.operator.process_block(
                    op_state, (lift(left), lift(right)), bctx)
                consumed = left.count().sum() + right.count().sum()
            elif self.in_slot_keys is not None and hasattr(
                    self.operator, "process_block_static_keys"):
                new_state, out = self.operator.process_block_static_keys(
                    op_state, lift(batches), bctx, self.in_slot_keys)
                consumed = batches.count().sum()
            else:
                new_state, out = self.operator.process_block(
                    op_state, lift(batches), bctx)
                consumed = batches.count().sum()
        # Drop the singleton P dim: out [k, 1, cap] -> [k, cap].
        out = jax.tree_util.tree_map(lambda x: x[:, 0], out)
        return (new_state, out, out.count(),
                consumed_in + consumed.astype(jnp.int32))

    #: per-step sync row layout (must match executor.DETS_PER_STEP appends)
    LAYOUT = (det.TIMESTAMP, det.RNG, det.ORDER, det.BUFFER_BUILT)

    def _parse(self, rows: np.ndarray, n: int):
        """Tag-aware parse: locate the n per-step sync blocks (anchored at
        TIMESTAMP rows) and classify everything between them as async
        determinant rows (host-appended between supersteps)."""
        k = len(self.LAYOUT)
        tags = rows[:, det.LANE_TAG]
        ts_idx = det.sync_anchors(rows)
        if len(ts_idx) < n:
            raise RecoveryError(
                f"determinant log too short: need {n} superstep blocks, "
                f"have {len(ts_idx)}")
        ts_idx = ts_idx[:n]
        for i, tag in enumerate(self.LAYOUT[1:], start=1):
            pos = ts_idx + i
            if (pos >= rows.shape[0]).any() or not (tags[pos] == tag).all():
                raise RecoveryError(
                    "determinant stream has unexpected layout (corrupt or "
                    f"misaligned response at sync lane {i})")
        sync_pos = (ts_idx[:, None] + np.arange(k)[None, :]).ravel()
        used = int(sync_pos.max()) + 1 if n > 0 else 0
        # Trailing async rows (appended after the last replayed step).
        while used < rows.shape[0] and not (
                tags[used] == det.TIMESTAMP
                and rows[used, det.LANE_RC] == 0):
            used += 1
        mask = np.ones(used, bool)
        mask[sync_pos] = False
        async_pos = np.nonzero(mask)[0]
        async_step = np.searchsorted(ts_idx, async_pos)
        async_events = [(int(async_step[j]),
                         det.Determinant.unpack(rows[async_pos[j]]))
                        for j in range(len(async_pos))]
        return ts_idx, int(used), async_events

    def replay(self, plan: ReplayPlan,
               defer_sync: bool = False) -> ReplayResult:
        """Drive the replay off either determinant-stream source:
        host rows (``plan.det_rows``, parsed/spliced here) or the
        device-resident stream (``plan.det_device`` — clean path: no log
        body on the host, no parse, no splice; only emit counts and
        expected cuts, a few KB, ever transfer).

        ``defer_sync`` (device stream only): dispatch everything and
        transfer NOTHING — the output-cut verification becomes a device
        flag and the consumed total stays a device scalar, both folded
        into the cluster's single end-of-recovery read (ReplayResult
        fields ``verify_ok_d`` / ``consumed_d``)."""
        n = plan.n_steps
        k = len(self.LAYOUT)
        dev = plan.det_device is not None
        if dev:
            if not plan.verify_outputs:    # pragma: no cover
                raise RecoveryError(
                    "device stream requires verifiable (non-synthesized) "
                    "recovery")
            t_dev, r_dev, expected_d = plan.det_device
            rows = np.zeros((0, det.NUM_LANES), np.int32)
            ts_idx = np.zeros((0,), np.int64)
            used = 0
            async_events: List[Tuple[int, Any]] = []
            times_np = rngs_np = expected = None
        else:
            rows = np.asarray(plan.det_rows)
            ts_idx, used, async_events = self._parse(rows, n)
            times_np = rows[ts_idx, det.LANE_P + 1].astype(np.int32)
            rngs_np = rows[ts_idx + 1, det.LANE_P].astype(np.int32)
            expected = rows[ts_idx + 3, det.LANE_P].astype(np.int32)

        if plan.input_steps is None:
            # Source vertex: regenerates its records; inputs are empty.
            cap = self.operator.out_capacity or 1
            zc = jnp.zeros((self.block_steps, cap), jnp.int32)
            self._zero_chunk = RecordBatch(
                zc, zc, zc, jnp.zeros((self.block_steps, cap), jnp.bool_))
        elif not isinstance(plan.input_steps, list):
            raise RecoveryError(
                f"ReplayPlan.input_steps is a list of {self.block_steps}"
                f"-step chunks, one per replay block, or None; a stacked "
                f"{type(plan.input_steps).__name__} is not replayed")

        state = jax.tree_util.tree_map(
            lambda x: x[plan.subtask][None], plan.checkpoint_op_state)
        subtask = jnp.asarray(plan.subtask, jnp.int32)
        out_chunks: List[Any] = []
        emit_chunks: List[jnp.ndarray] = []
        consumed_acc = jnp.zeros((), jnp.int32)
        ch = self.block_steps
        if not dev:
            # One h2d of the whole (pad-extended) time/rng streams;
            # per-chunk views are prewarmed dynamic slices, not per-chunk
            # uploads. (The device stream arrives already padded to
            # pad_steps.)
            npad = -(-max(n, 1) // ch) * ch
            if self.pad_steps is not None and npad <= self.pad_steps:
                npad = self.pad_steps
            t_all = np.full((npad,), times_np[n - 1] if n else 0, np.int32)
            r_all = np.full((npad,), rngs_np[n - 1] if n else 0, np.int32)
            t_all[:n] = times_np[:n]
            r_all[:n] = rngs_np[:n]
            t_dev = jnp.asarray(t_all)
            r_dev = jnp.asarray(r_all)
        lo = 0
        ci = 0
        while lo < n:
            hi = min(lo + ch, n)
            kk = hi - lo
            # Tail blocks: pad-safe operators run the full fixed block
            # shape with repeated time/rng and (already all-invalid) pad
            # inputs, so the warm standby's prewarmed program serves every
            # n; pad-unsafe operators (pure generators) run the exact tail
            # and pay one small compile. The device stream is pad-safe by
            # construction (the clean-path guard requires it).
            pad = dev or (kk < ch and self.operator.replay_pad_safe)
            chunk = (self._zero_chunk if plan.input_steps is None
                     else plan.input_steps[ci])
            if kk < ch and not pad:
                chunk = jax.tree_util.tree_map(lambda x: x[:kk], chunk)
            if pad or kk == ch:
                lo_j = jnp.asarray(lo, jnp.int32)
                t_in = self._jit_tslice(t_dev, lo_j)
                r_in = self._jit_tslice(r_dev, lo_j)
            else:
                t_in = jnp.asarray(times_np[lo:hi])
                r_in = jnp.asarray(rngs_np[lo:hi])
            state, out, counts, consumed_acc = self._jit_block(
                state, chunk, t_in, r_in, subtask, consumed_acc)
            out_chunks.append(out)
            emit_chunks.append(counts)
            lo = hi
            ci += 1
        final_state = state
        if defer_sync:
            if not dev:    # pragma: no cover - cluster guards eligibility
                raise RecoveryError(
                    "defer_sync requires the device-resident determinant "
                    "stream (host-row plans must parse on the host)")
            emit_d = jnp.concatenate(emit_chunks, axis=0)[:n]
            exp_d = expected_d[:n]
            ok_d = jnp.all(emit_d == exp_d)
            return ReplayResult(
                op_state=final_state,
                rebuilt_log_rows=rows[:0], emit_counts=emit_d,
                expected_emits=exp_d,
                out_chunks=out_chunks if out_chunks else None,
                records_replayed=-1, async_events=[],
                rebuilt_is_view=True,
                deferred=True, verify_ok_d=ok_d, consumed_d=consumed_acc)
        # ONE concat dispatch + ONE d2h for the emit counts, the
        # in-program consumed total, and (device path) the expected cuts.
        tail = [consumed_acc.reshape(1)]
        if dev:
            tail.append(expected_d[:max(n, 1)])
        packed = jnp.concatenate(emit_chunks + tail, axis=0)
        packed_np = np.asarray(packed)             # d2h sync point
        n_emit = sum(int(c.shape[0]) for c in emit_chunks)
        emit_np = packed_np[:n_emit][:n]
        consumed_total = int(packed_np[n_emit])
        if dev:
            expected = packed_np[n_emit + 1:][:n]

        # Regenerate the determinant rows the replayed run would log — the
        # rebuilt log must extend the recovered one bit-for-bit. Sync blocks
        # are re-derived from the replay; async rows are spliced back at
        # their recorded positions (append-even-during-replay invariant).
        # Clean case (no async rows, real recovered determinants): the
        # re-derived sync values differ from the recorded rows only in the
        # BUFFER_BUILT payload, and verify() checks exactly that equality —
        # so the rebuilt stream IS the recovered prefix, no copy needed.
        rebuilt_is_view = not async_events and plan.verify_outputs
        if rebuilt_is_view:
            rebuilt = rows[:used]
        else:
            blocks = np.zeros((n, k, det.NUM_LANES), np.int32)
            blocks[:, 0, det.LANE_TAG] = det.TIMESTAMP
            blocks[:, 0, det.LANE_P] = np.where(times_np < 0, -1, 0)
            blocks[:, 0, det.LANE_P + 1] = times_np
            blocks[:, 1, det.LANE_TAG] = det.RNG
            blocks[:, 1, det.LANE_P] = rngs_np
            blocks[:, 2, det.LANE_TAG] = det.ORDER
            blocks[:, 3, det.LANE_TAG] = det.BUFFER_BUILT
            blocks[:, 3, det.LANE_P] = emit_np
            rebuilt = rows[:used].copy()
            sync_pos = (ts_idx[:, None] + np.arange(k)[None, :])  # [n, k]
            rebuilt[sync_pos.ravel()] = blocks.reshape(
                n * k, det.NUM_LANES)

        consumed = (consumed_total if plan.input_steps is not None
                    else int(emit_np.sum()))
        return ReplayResult(
            op_state=final_state, rebuilt_log_rows=rebuilt,
            emit_counts=emit_np, expected_emits=expected,
            out_chunks=out_chunks if out_chunks else None,
            records_replayed=consumed, async_events=async_events,
            rebuilt_is_view=rebuilt_is_view)


class RecoveryManager:
    """Host-side per-failed-subtask recovery FSM (reference
    RecoveryManager.java). Event methods mirror the reference's
    notifications; the cluster runner drives them in order and observers
    (tests, metrics) can watch ``state`` transitions."""

    def __init__(self, vertex_id: int, subtask: int, flat_subtask: int,
                 replayer: LogReplayer):
        self.vertex_id = vertex_id
        self.subtask = subtask
        self.flat_subtask = flat_subtask
        self.replayer = replayer
        self.state = RecoveryState.STANDBY
        self._pending_inputs: Dict[int, bool] = {}
        self._pending_outputs: Dict[int, bool] = {}
        self._state_restored = False
        self._responses: List[Tuple[np.ndarray, int]] = []
        self._expected_responses = 0
        self._expected_set = False
        self.plan: Optional[ReplayPlan] = None
        self.result: Optional[ReplayResult] = None
        self.transitions: List[RecoveryState] = [self.state]
        #: transition observers: ``fn(kind, **fields)`` on every FSM
        #: state change — the verify conformance layer's observation
        #: surface (kind is the entered state's name).
        self.transition_observers: List = []

    def _goto(self, s: RecoveryState) -> None:
        self.state = s
        self.transitions.append(s)
        for fn in self.transition_observers:
            fn(s.name, flat=self.flat_subtask)
        tr = _get_tracer()
        if tr.enabled:
            # FSM transitions as instants (reference RecoveryManager
            # logs each state change) — the fine-grained layer under
            # the recovery.* phase spans the cluster runner emits.
            tr.event("recovery.fsm", state=s.name, flat=self.flat_subtask,
                     vertex=self.vertex_id, subtask=self.subtask)
        from clonos_tpu.obs import get_timeline
        tl = get_timeline()
        if tl.enabled:
            tl.record("recovery.fsm", state=s.name,
                      flat=self.flat_subtask, vertex=self.vertex_id,
                      subtask=self.subtask)

    # --- events (reference notify* methods) ---------------------------------

    def notify_start_recovery(self, in_edges: Sequence[int],
                              out_edges: Sequence[int]) -> None:
        if self.state != RecoveryState.STANDBY:
            raise RecoveryError(f"start_recovery in state {self.state}")
        self._pending_inputs = {e: False for e in in_edges}
        self._pending_outputs = {e: False for e in out_edges}
        self._goto(RecoveryState.WAITING_CONNECTIONS)
        if self._connections_ready():
            self._enter_waiting_determinants()

    def notify_state_restoration_complete(self) -> None:
        self._state_restored = True
        self._maybe_advance_connections()

    def notify_new_input_channel(self, edge: int) -> None:
        if edge in self._pending_inputs:
            self._pending_inputs[edge] = True
        self._maybe_advance_connections()

    def notify_new_output_channel(self, edge: int) -> None:
        if edge in self._pending_outputs:
            self._pending_outputs[edge] = True
        self._maybe_advance_connections()

    def _connections_ready(self) -> bool:
        # Advances only when every input AND output channel is established
        # and state restoration finished (WaitingConnectionsState.java:96).
        return (self._state_restored
                and all(self._pending_inputs.values())
                and all(self._pending_outputs.values()))

    def _maybe_advance_connections(self) -> None:
        if (self.state == RecoveryState.WAITING_CONNECTIONS
                and self._connections_ready()):
            self._enter_waiting_determinants()

    def _enter_waiting_determinants(self) -> None:
        self._goto(RecoveryState.WAITING_DETERMINANTS)

    def expect_determinant_responses(self, n: int) -> None:
        self._expected_responses = n
        self._expected_set = True
        self._maybe_have_determinants()

    def notify_determinant_response(self, rows: np.ndarray,
                                    abs_start: int) -> None:
        if self.state != RecoveryState.WAITING_DETERMINANTS:
            raise RecoveryError(f"determinant response in state {self.state}")
        self._responses.append((rows, abs_start))
        self._maybe_have_determinants()

    def _maybe_have_determinants(self) -> None:
        if (self.state == RecoveryState.WAITING_DETERMINANTS
                and self._expected_set
                and len(self._responses) >= self._expected_responses):
            self._goto(RecoveryState.REPLAYING)

    def merged_determinants(self) -> Tuple[np.ndarray, int]:
        from clonos_tpu.causal.replication import merge_determinant_responses
        return merge_determinant_responses(self._responses)

    def run_replay(self, plan: ReplayPlan,
                   defer_sync: bool = False) -> ReplayResult:
        if self.state != RecoveryState.REPLAYING:
            raise RecoveryError(f"replay in state {self.state}")
        self.plan = plan
        self.result = self.replayer.replay(plan, defer_sync=defer_sync)
        if plan.verify_outputs and not self.result.deferred:
            self.result.verify()
        self._goto(RecoveryState.RUNNING)
        return self.result
