"""Block executor: the whole job as a few large fused programs per epoch.

This replaces the reference's task plane + stream runtime
(taskexecutor/TaskExecutor.java:422, taskmanager/Task.java:124,
runtime/tasks/StreamTask.java and the OneInputStreamTask.run hot loop,
OneInputStreamTask.java:106) with the TPU-native execution model:

- Every vertex's subtasks are a ``[P]`` dim of its state/batches, shardable
  over a ``jax.sharding.Mesh`` axis — the analog of deploying subtasks to
  TaskManagers.
- A **superstep** advances every vertex by one batch concurrently: vertex v
  consumes the batch its upstream routed in the *previous* superstep
  (depth-1 edge buffers). That is pipeline parallelism — all stages busy
  every step — without queues/threads/backpressure machinery.
- The executor runs supersteps in **blocks of K**: each vertex processes a
  whole ``[K, P, B]`` stack per program (``Operator.process_block``), each
  exchange routes the whole stack, and the causal/in-flight logs take one
  bulk append per block. Per-step semantics are preserved exactly (the
  depth-1 shift is a concatenate of the carried edge buffer with the first
  K-1 routed outputs; ``tests/test_executor.py::test_scan_epoch_equals_
  stepwise`` proves block == stepwise bit-for-bit) — but the kernel count
  per epoch is O(vertices + edges), not O(steps · ops). On hardware where
  each non-fused kernel in a sequential loop costs hundreds of
  microseconds, this is the difference between 10^4 and 10^7 records/sec.
- The per-superstep causal determinants (TIMESTAMP of the causal time
  input, RNG draw, ORDER of the consumed channel, BUFFER_BUILT with the
  emitted record count — reference CausalBufferOrderService.java:112,
  PipelinedSubpartition buffer cuts) are materialized for the whole block
  as one ``[L, K·4, lanes]`` tensor and appended to the stacked device log
  and its replicas in two scatters.
- **Determinant durability boundary == output visibility boundary**: sink
  outputs and routed batches leave the device only when a block program
  returns, and the same program has already appended + replicated every
  determinant describing them. This is the step-fused form of the
  reference's piggybacking (deltas ride the data they describe,
  NettyMessage.java:156-242).

Host Python never touches records: it stages each block's causal
time/RNG arrays in one transfer and reads sink batches out.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time as _time
from functools import partial
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.api.operators import (BlockContext, HostFeedSource,
                                      OpContext, TwoInputOperator)
from clonos_tpu.api.records import RecordBatch, empty, zero_invalid
from clonos_tpu.causal import log as clog
from clonos_tpu.causal import determinant as det
from clonos_tpu.causal import replication as rep
from clonos_tpu.graph.job_graph import JobGraph, PartitionType
from clonos_tpu.inflight import log as ifl
from clonos_tpu.obs.trace import get_tracer, install_compile_listener
from clonos_tpu.ops.histogram import kernel_mesh
from clonos_tpu.parallel import routing

# Determinants appended per subtask per superstep on the sync path, in this
# fixed order: TIMESTAMP (causal time read), RNG (causal host-RNG draw),
# ORDER (consumed channel), BUFFER_BUILT (emitted batch cut). The fixed
# layout is what lets the replayer parse the log as a [steps, 4, lanes]
# tensor on device.
DETS_PER_STEP = 4


class StepInputs(NamedTuple):
    """Host-fed inputs for one superstep (single-step API; the block path
    uses :class:`BlockInputs`). ``time``/``rng_bits`` are the causal-service
    scalars (recorded as determinants; replayed from the log). ``feeds``
    carries one RecordBatch per HostFeedSource vertex (in vertex-id order) —
    the external-system boundary (Kafka/socket analog)."""

    time: jnp.ndarray
    rng_bits: jnp.ndarray
    feeds: Tuple[RecordBatch, ...] = ()


class BlockInputs(NamedTuple):
    """Host-fed inputs for a block of K supersteps, staged in one transfer."""

    times: jnp.ndarray                    # int32[K]
    rng_bits: jnp.ndarray                 # int32[K]
    epoch: jnp.ndarray                    # int32 scalar
    step0: jnp.ndarray                    # int32 scalar (global step index)
    feeds: Tuple[RecordBatch, ...] = ()   # per feed vertex, [K, P, B]


class JobCarry(NamedTuple):
    """The complete device-resident job state (the block program's carry)."""

    op_states: Tuple[Any, ...]          # per-vertex operator state pytrees
    edge_bufs: Tuple[RecordBatch, ...]  # per-edge routed batch [P_dst, cap]
    rr_offsets: Tuple[jnp.ndarray, ...] # per-edge [1] round-robin cursors
    record_counts: jnp.ndarray          # int32[L] records consumed per subtask
    logs: clog.ThreadLogState           # stacked [L, cap, lanes]
    out_rings: Tuple[ifl.EdgeLogState, ...]  # per producing vertex: its raw
                                        # output batches [S, P, out_cap] — the
                                        # PipelinedSubpartition in-flight log,
                                        # owned by (and dying with) the
                                        # producer's subtask shards
    replicas: clog.ThreadLogState       # stacked [R, cap, lanes] downstream
                                        # determinant replicas
    exchange: Tuple[Dict[str, jnp.ndarray], ...] = ()
                                        # per edge, int32[P_dst] each and
                                        # sharded like the edge's buffer:
                                        # "dropped", the records the edge
                                        # has dropped past its capacity,
                                        # and on a dynamic HASH edge
                                        # "peak", the most a target has
                                        # been sent in one step; monotone,
                                        # reduced only in the fence's
                                        # health read


class LeanSnapshot(NamedTuple):
    """What a checkpoint actually persists (reference: async snapshots of
    *operator state* only, StreamTask.java:854; RocksDB incremental
    backends). Causal logs, replicas, and in-flight rings are NOT
    snapshotted: a completed checkpoint *truncates* them, so their
    post-fence content is exactly what recovery regenerates — persisting
    them would be GB-scale dead weight (round-1 VERDICT weakness #12).
    Only their fence offsets ride along."""

    op_states: Tuple[Any, ...]
    edge_bufs: Tuple[RecordBatch, ...]   # the depth-1 in-flight batch per
                                         # edge — the aligned-barrier channel
                                         # state spanning the fence
    rr_offsets: Tuple[jnp.ndarray, ...]
    record_counts: jnp.ndarray
    log_heads: jnp.ndarray               # int32[L] log heads at the fence
    ring_heads: Tuple[jnp.ndarray, ...]  # per-ring heads at the fence


class StepOutputs(NamedTuple):
    sinks: Dict[int, RecordBatch]       # vertex_id -> emitted batch [P, cap]
    dropped: Dict[int, jnp.ndarray]     # edge index -> [P_dst] drops
    consumed: jnp.ndarray               # int32[L] records consumed this step


class BlockOutputs(NamedTuple):
    sinks: Dict[int, RecordBatch]       # vertex_id -> [K, P, cap]
    dropped: Dict[int, jnp.ndarray]     # edge index -> [K, P_dst]
    consumed: jnp.ndarray               # int32[K, L]


class EdgePlan(NamedTuple):
    """What ``CompiledJob._plan_edges`` decided for one HASH edge."""

    route: str          # "identity" | "static" | "dynamic"
    width: int          # receive capacity per target subtask
    pairs_kept: int     # (producer, slot) pairs the plan holds a slot
    pairs_total: int    # for, of those "any key anywhere" would need;
                        # identity/dynamic: (producer, target) lanes


@dataclasses.dataclass
class CompiledJob:
    """A job graph lowered to (init_carry, run_block) pure functions."""

    job: JobGraph
    log_capacity: int = 1 << 14
    max_epochs: int = 64
    inflight_ring_steps: int = 64
    mesh: Optional[jax.sharding.Mesh] = None
    task_axis: str = "tasks"
    replication_factor: int = -1   # holder subtasks per (owner, holder
                                   # vertex); -1 = all (see replication.py)

    def __post_init__(self):
        self.job.validate()
        self.topo = self.job.topo_order()
        self.L = self.job.total_subtasks()
        #: vertex ids of host-fed sources, in id order (feeds positions
        #: align with this list).
        self.feed_vertices = [v.vertex_id for v in self.job.vertices
                              if isinstance(v.operator, HostFeedSource)]
        self.plan = rep.ReplicationPlan.from_job(
            self.job, self.job.sharing_depth,
            replication_factor=self.replication_factor)
        self._owner_idx = self.plan.owner_index()
        #: vertices owning an in-flight output ring (everything that feeds
        #: a downstream consumer).
        self.ring_vertices = [v.vertex_id for v in self.job.vertices
                              if self.job.out_edges(v.vertex_id)]
        self.ring_index = {vid: i for i, vid in enumerate(self.ring_vertices)}
        #: vertices whose state keeps totals the fence's health read
        #: carries (``fence_totals``: the event-time windows' late-dropped
        #: records and fired rows, the window join's records a side too;
        #: ``fence_peaks``: the most sessions a subtask has held open)
        self.event_window_vertices = [
            v.vertex_id for v in self.job.vertices
            if v.operator.fence_totals or v.operator.fence_peaks]
        self._plan_edges()

    def _plan_edges(self) -> None:
        """Decide every HASH edge's route, and the widths that follow.

        **Own keys** (defined here, and nowhere else): a vertex *holds
        own keys* when every valid record subtask ``p`` receives has a
        key that ``routing._static_targets`` sends to ``p`` (at the
        vertex's parallelism and the job's ``num_key_groups``). That is
        so when each input edge is a HASH edge — however it is routed —
        or a FORWARD edge from a vertex that *emits* own keys: one that
        holds them and declares ``Operator.emits_received_keys``. A
        dense-table emitter (``static_out_keys``) that holds own keys
        and declares ``static_clamp_keys`` has ``routing.own_slots`` for
        its live (subtask, slot) pairs, and emits own keys where no
        pair lies outside its owner. The planner reads those two
        declarations and the graph; never an operator's class, never a
        job's name. Two consequences, one per kind of producer:

        - a static gather plan (``routing.StaticRoutePlan``) holds a
          slot only for the live pairs: one producer a key, plus the
          clamp columns from every producer;
        - a HASH edge between equal parallelisms whose producer emits
          own keys moves nothing — every record is on its target — and
          is routed in place (``identity``: ``route_forward_block``).
          Such a record stays in its slot, so the edge is as wide as
          its producer at least.

        ``edge_plans`` keeps the decision per HASH edge (``route`` is
        ``identity``, ``static`` or ``dynamic``) and :meth:`route_edge`
        acts on it; each decision is noted once as an ``exchange.route``
        instant, and one that stays ``dynamic`` says why (``reason``):
        ``feed-keys`` — the producer does not hold own keys, its
        records' keys are whatever the data says (behind a source, a
        map, a FORWARD chain from one) and no declaration would help;
        ``undeclared`` — the producer holds own keys and states nothing
        the planner could use (``IntervalJoinOperator``, a map behind
        a keyBy: ROADMAP D16);
        ``rescale`` — it emits own keys into another parallelism;
        ``too-wide`` — a gather plan would multiply the edge."""
        job = self.job
        G = job.num_key_groups
        #: HASH edges whose producer emits statically-keyed slots get a
        #: compile-time gather plan instead of the sort exchange.
        self.static_route: Dict[int, routing.StaticRoutePlan] = {}
        self.edge_plans: Dict[int, EdgePlan] = {}
        #: vertex -> the ids each subtask owns, for an operator that
        #: holds a table column only for those (``Operator.own_columns``):
        #: int32 [P, own_columns], ascending, then ``NO_KEY``
        self.own_columns: Dict[int, np.ndarray] = {}
        emits_own = set()     # vertices that emit own keys
        # A static route leaves holes (slots are bound to (producer, key)
        # pairs, not compacted), an identity route keeps its producer's,
        # and operators without a width of their own pass slots through
        # in place. A FORWARD edge narrower than such a producer would
        # cut live records off by POSITION, so it inherits the widening.
        sparse = set()
        for vid in self.topo:
            v = job.vertices[vid]
            op, p = v.operator, v.parallelism
            ins = job.in_edges(vid)
            holds_own = bool(ins) and all(
                job.edges[i].partition == PartitionType.HASH
                or (job.edges[i].partition == PartitionType.FORWARD
                    and job.edges[i].src in emits_own) for i in ins)
            if len(ins) == 1 and op.out_capacity is None:
                e_in = job.edges[ins[0]]
                planned = self.edge_plans.get(ins[0])
                if (planned and planned.route != "dynamic") or (
                        e_in.partition == PartitionType.FORWARD
                        and e_in.src in sparse):
                    sparse.add(vid)
            if op.own_columns is not None:
                if not holds_own:
                    raise ValueError(
                        f"vertex {v.name!r} holds table columns only for "
                        f"the keys its subtasks own, so every input must "
                        f"be keyed: key_by() before it")
                self.own_columns[vid] = self._bind_own_columns(vid)
            sk, clamp = op.static_out_keys(), op.static_clamp_keys()
            live = None
            if sk is None:
                if holds_own and op.emits_received_keys:
                    emits_own.add(vid)
            elif holds_own and clamp is not None:
                live = routing.own_slots(sk, p, G, clamp)
                if live.sum() == len(sk):     # every slot on its owner only
                    emits_own.add(vid)
            width = self.vertex_out_capacity(vid)
            for eidx in job.out_edges(vid):
                e = job.edges[eidx]
                if e.partition == PartitionType.FORWARD and vid in sparse:
                    e.capacity = max(e.capacity, width)
                if e.partition != PartitionType.HASH:
                    continue
                dst_p = job.vertices[e.dst].parallelism
                plan = None
                if sk is not None:
                    plan = self._plan_static(eidx, sk, p, dst_p, live)
                elif vid in emits_own and dst_p == p:
                    e.capacity = max(e.capacity, width)
                    plan = EdgePlan("identity", e.capacity, p, p * p)
                why = {}
                if plan is None:
                    plan = EdgePlan("dynamic", e.capacity, p * dst_p,
                                    p * dst_p)
                    why["reason"] = (
                        "too-wide" if sk is not None
                        else "feed-keys" if not holds_own
                        else "undeclared" if vid not in emits_own
                        else "rescale")
                    why["capacity"] = e.capacity
                    why["between"] = self.edge_name(eidx)
                self.edge_plans[eidx] = plan
                routing.note_route(
                    plan.route, edge=eidx, width=plan.width,
                    pairs_kept=plan.pairs_kept,
                    pairs_total=plan.pairs_total, **why)

    def _bind_own_columns(self, vid: int) -> np.ndarray:
        """The columns of a vertex that holds own keys: per subtask the
        ids ``routing.own_slots`` gives it, ascending; a subtask that
        owns more than the operator has columns refuses the plan."""
        from clonos_tpu.api.operators import NO_KEY
        v = self.job.vertices[vid]
        op, p = v.operator, v.parallelism
        own = routing.own_slots(np.arange(op.num_keys), p,
                                self.job.num_key_groups)
        most = int(own.sum(axis=1).max())
        if most > op.own_columns:
            raise ValueError(
                f"vertex {v.name!r}: subtask {int(own.sum(axis=1).argmax())}"
                f" owns {most} of the {op.num_keys} keys, more than the "
                f"{op.own_columns} own columns a subtask has")
        cols = np.full((p, op.own_columns), NO_KEY, np.int32)
        for q in range(p):
            keys = np.nonzero(own[q])[0]
            cols[q, :len(keys)] = keys
        routing.note_own_columns(v.name, op.own_columns, most, op.num_keys)
        return cols

    def edge_name(self, eidx: int) -> str:
        """``<producer>-><consumer>``: how counters and overflow
        messages name an edge."""
        e = self.job.edges[eidx]
        return (f"{self.job.vertices[e.src].name}->"
                f"{self.job.vertices[e.dst].name}")

    def _fence_slots(self, declared: str) -> List[Tuple[Any, str, str]]:
        vertices = [self.job.vertices[vid]
                    for vid in self.event_window_vertices]
        return [(v, key, counter) for v in vertices
                for key, counter in getattr(v.operator, declared)]

    def fence_total_slots(self) -> List[Tuple[Any, str, str]]:
        """``(vertex, state key, counter)`` of each total the fence's
        health read carries, in the vector's order (the operators'
        ``fence_totals``)."""
        return self._fence_slots("fence_totals")

    def fence_peak_slots(self) -> List[Tuple[Any, str, str]]:
        """The same for the operators' ``fence_peaks``: high-water marks
        the read reduces by their maximum over the subtasks."""
        return self._fence_slots("fence_peaks")

    def peak_edges(self) -> List[int]:
        """The edges whose fill the carry follows (``exchange[e]
        ["peak"]``): the HASH edges that stay on the dynamic exchange,
        the only ones on which the data decides how full a target is."""
        return [e for e, plan in sorted(self.edge_plans.items())
                if plan.route == "dynamic"]

    def _plan_static(self, eidx: int, sk: np.ndarray, src_p: int,
                     dst_p: int, live: Optional[np.ndarray]
                     ) -> Optional["EdgePlan"]:
        """The gather plan of a HASH edge behind a dense-table emitter
        (None: it would be too wide, the edge stays dynamic)."""
        e = self.job.edges[eidx]
        G = self.job.num_key_groups
        # The plan reserves a slot for every live (producer, slot) pair,
        # so a hash-skewed target can need more than the requested
        # receive window even though the dynamic exchange never drops
        # (it only sees per-step live arrivals). The edge capacity is a
        # lower-bound request — widen it to fit the fullest target
        # (rounded to the 128 TPU lane width): it buys the gather plan
        # (~50x cheaper than the sort exchange at bench shapes).
        need = routing.static_hash_capacity(sk, src_p, dst_p, G, live)
        if need > max(4 * e.capacity, 1024):
            # The static plan would need far more receive memory than
            # the user asked for (very dense key table or extreme hash
            # skew into a narrow edge): keep the dynamic exchange rather
            # than silently multiplying the edge and downstream buffers.
            return None
        if need > e.capacity:
            e.capacity = -(-need // 128) * 128
        plan = routing.plan_static_hash(sk, src_p, dst_p, G, e.capacity,
                                        live)
        if len(plan.drop_p):                           # pragma: no cover
            raise RuntimeError(
                f"static plan for edge {eidx} still has "
                f"{len(plan.drop_p)} overflow slots at capacity "
                f"{e.capacity} — static_hash_capacity disagrees "
                f"with plan_static_hash")
        self.static_route[eidx] = plan
        return EdgePlan("static", e.capacity, int(plan.ok.sum()),
                        src_p * len(sk))

    def consumer_slot_keys(self, vid: int) -> Optional[np.ndarray]:
        """Static per-slot input keys of vertex ``vid`` ([P, cap], -1 =
        unmapped), when its (single) input edge is statically routed."""
        ins = self.job.in_edges(vid)
        if len(ins) == 1 and ins[0] in self.static_route:
            return self.static_route[ins[0]].slot_keys
        return None

    def route_edge(self, eidx: int, out: RecordBatch, rr0,
                   lane=None) -> Tuple[RecordBatch, Optional[jnp.ndarray]]:
        """THE route of edge ``eidx`` over a producer block ``[K, P, B]``
        — the block program and both of recovery's re-routes ask here.
        ``rr0`` is the edge's round-robin cursor at the block's first
        step (REBALANCE reads it). All consumer lanes by default:
        (``[K, T, cap]``, dropped ``[K, T]``); with ``lane`` that one
        consumer's ``[K, cap]`` (bit-identical to the full route's
        lane) and no drop count."""
        e = self.job.edges[eidx]
        T, cap = self.job.vertices[e.dst].parallelism, e.capacity
        G = self.job.num_key_groups
        route = (self.edge_plans[eidx].route if eidx in self.edge_plans
                 else e.partition.value)

        def offsets():                  # [K] exclusive round-robin cursor
            counts = out.count().sum(axis=1)
            return rr0 + jnp.cumsum(counts) - counts

        in_place = lambda: routing.route_forward_block(out, cap)
        in_place_lane = lambda: routing.route_forward_block_lane(
            out, lane, cap)
        if lane is None:
            return {
                "static": lambda: self.static_route[eidx].apply(out),
                "identity": in_place, "forward": in_place,
                "dynamic": lambda: routing.route_hash_block(out, T, G, cap),
                "rebalance": lambda: routing.route_rebalance_block(
                    out, T, cap, offsets()),
                "broadcast": lambda: routing.route_broadcast_block(
                    out, T, cap)}[route]()
        return {
            "static": lambda: jax.tree_util.tree_map(
                lambda x: x[:, lane], self.static_route[eidx].apply(out)[0]),
            "identity": in_place_lane, "forward": in_place_lane,
            "dynamic": lambda: routing.route_hash_block_lane(
                out, lane, T, G, cap),
            "rebalance": lambda: routing.route_rebalance_block_lane(
                out, lane, T, cap, offsets()),
            "broadcast": lambda: routing.route_broadcast_block_lane(
                out, lane, cap)}[route](), None

    # --- shapes -------------------------------------------------------------

    def vertex_out_capacity(self, vid: int) -> int:
        v = self.job.vertices[vid]
        if v.operator.out_capacity is not None:
            return v.operator.out_capacity
        ins = self.job.in_edges(vid)
        if ins:
            return self.job.edges[ins[0]].capacity
        return 1

    # --- sharding -----------------------------------------------------------

    def _shard_axis(self, x: jnp.ndarray, axis: int) -> jnp.ndarray:
        """Constrain ``x`` to be sharded over the task mesh axis along
        ``axis`` when divisible (the subtask->device deployment)."""
        if self.mesh is None:
            return x
        n = self.mesh.shape[self.task_axis]
        if x.ndim <= axis or x.shape[axis] % n != 0:
            return x
        spec = [None] * x.ndim
        spec[axis] = self.task_axis
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(*spec)))

    def _shard_leading(self, x: jnp.ndarray) -> jnp.ndarray:
        if getattr(x, "ndim", 0) == 0:
            return x
        return self._shard_axis(x, 0)

    def _shard_tree(self, tree):
        return jax.tree_util.tree_map(self._shard_leading, tree)

    def _shard_block(self, tree):
        """Block tensors are [K, P, ...]: shard the subtask axis (1)."""
        return jax.tree_util.tree_map(
            lambda x: self._shard_axis(x, 1) if getattr(x, "ndim", 0) > 1
            else x, tree)

    def carry_shardings(self, carry: JobCarry):
        """NamedSharding pytree over the task mesh for the full carry
        (the form jit in/out_shardings take), or None without a mesh."""
        if self.mesh is None:
            return None
        from clonos_tpu.parallel import distributed as dist
        return dist.named_shardings(carry, self.mesh, axis=self.task_axis)

    def constrain_carry(self, carry: JobCarry) -> JobCarry:
        """Constrain EVERY carry leaf to its rule-assigned sharding —
        logs/replicas on their leading task axis, ring payloads on their
        subtask axis (1), control scalars replicated. Applied at carry
        construction and at the end of every block so the traced
        program's layout always matches the explicit jit shardings."""
        if self.mesh is None:
            return carry
        shardings = self.carry_shardings(carry)
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, carry, shardings)

    # --- initialization -----------------------------------------------------

    def build_carry(self) -> JobCarry:
        """The initial carry on the devices, for a live executor. Under a
        mesh one jitted program builds it with ``carry_shardings()`` as
        its ``out_shardings``: every device makes its own shard of every
        leaf and none ever holds a leaf whole (the default sharing
        depth's replica logs alone are 14 GiB on ``nexmark-q3-x4``).
        The span ``setup.init-carry`` times the build; ``carry.bytes``
        and ``carry.max_device_bytes`` (the fullest device's share) count
        what it left."""
        tr = get_tracer()
        with tr.span("setup.init-carry") as span:
            if self.mesh is None:
                carry = self.init_carry()
            else:
                shardings = self.carry_shardings(
                    jax.eval_shape(self.init_carry))
                carry = jax.jit(self.init_carry, out_shardings=shardings)()
            carry = distinct_buffers(carry)
            jax.block_until_ready(carry)
            total, fullest = carry_device_bytes(carry)
            span.set(bytes=total, max_device_bytes=fullest)
        tr.count("carry.bytes", total)
        tr.count("carry.max_device_bytes", fullest)
        tr.count("carry.build_us", int(span.ms * 1e3))
        return carry

    def init_carry(self) -> JobCarry:
        """The initial carry as a pure function (traceable: ``jit``,
        ``eval_shape``); :meth:`build_carry` is what an executor calls."""
        def init_state(v):
            state = v.operator.init_state(v.parallelism)
            cols = self.own_columns.get(v.vertex_id)
            return (state if cols is None
                    else v.operator.bind_own_columns(state, cols))

        op_states = tuple(init_state(v) for v in self.job.vertices)
        edge_bufs = tuple(
            empty((self.job.vertices[e.dst].parallelism, e.capacity))
            for e in self.job.edges)
        rr = tuple(jnp.zeros((1,), jnp.int32) for _ in self.job.edges)
        logs = jax.vmap(lambda _: clog.create(self.log_capacity, self.max_epochs)
                        )(jnp.arange(self.L))
        out_rings = tuple(
            ifl.create(self.inflight_ring_steps,
                       self.job.vertices[vid].parallelism,
                       self.vertex_out_capacity(vid), self.max_epochs)
            for vid in self.ring_vertices)
        replicas = rep.create_replicas(self.plan, self.log_capacity,
                                       self.max_epochs)
        peak = set(self.peak_edges())
        exchange = tuple(
            {k: jnp.zeros((self.job.vertices[e.dst].parallelism,), jnp.int32)
             for k in (("dropped", "peak") if i in peak else ("dropped",))}
            for i, e in enumerate(self.job.edges))
        carry = JobCarry(op_states, edge_bufs, rr,
                         jnp.zeros((self.L,), jnp.int32), logs, out_rings,
                         replicas, exchange)
        return self.constrain_carry(carry)

    # --- the block program --------------------------------------------------

    def run_block(self, carry: JobCarry, binputs: BlockInputs
                  ) -> Tuple[JobCarry, BlockOutputs]:
        """Advance K supersteps as one traced program (its Pallas
        kernels per mesh shard when a mesh is attached)."""
        with kernel_mesh(self.mesh, self.task_axis):
            return self._run_block(carry, binputs)

    def _run_block(self, carry: JobCarry, binputs: BlockInputs
                   ) -> Tuple[JobCarry, BlockOutputs]:
        job = self.job
        K = binputs.times.shape[0]
        if DETS_PER_STEP * K > self.log_capacity:
            raise ValueError(
                f"block of {K} steps appends {DETS_PER_STEP * K} determinant"
                f" rows > log capacity {self.log_capacity}")
        if K > self.inflight_ring_steps:
            raise ValueError(
                f"block of {K} steps exceeds in-flight ring "
                f"({self.inflight_ring_steps} steps)")
        op_states = list(carry.op_states)
        rr_offsets = list(carry.rr_offsets)
        out_rings = list(carry.out_rings)
        new_edge_bufs = list(carry.edge_bufs)
        exchange = list(carry.exchange)
        routed: Dict[int, RecordBatch] = {}
        sinks: Dict[int, RecordBatch] = {}
        dropped: Dict[int, jnp.ndarray] = {}
        consumed_parts: Dict[int, jnp.ndarray] = {}
        emit_parts: Dict[int, jnp.ndarray] = {}

        def shifted(eidx: int) -> RecordBatch:
            # Depth-1 pipeline: the batch consumed at block step k is the
            # upstream's routed output of step k-1; step 0 consumes the
            # carried edge buffer (the previous block's last routed batch).
            return jax.tree_util.tree_map(
                lambda r, b: jnp.concatenate([b[None], r[:-1]], axis=0),
                routed[eidx], carry.edge_bufs[eidx])

        for vid in self.topo:
            v = job.vertices[vid]
            p = v.parallelism
            in_edges = job.in_edges(vid)
            bctx = BlockContext(
                times=binputs.times, rng_bits=binputs.rng_bits,
                epoch=binputs.epoch, step0=binputs.step0,
                subtask=jnp.arange(p, dtype=jnp.int32))
            if isinstance(v.operator, TwoInputOperator):
                ins = (shifted(in_edges[0]), shifted(in_edges[1]))
                consumed = ins[0].count() + ins[1].count()       # [K, P]
            elif in_edges:
                ins = shifted(in_edges[0])
                consumed = ins.count()
            elif vid in self.feed_vertices and binputs.feeds:
                ins = binputs.feeds[self.feed_vertices.index(vid)]
                consumed = ins.count()
            else:
                ins = empty((K, p, self.vertex_out_capacity(vid)))
                consumed = None
            slot_keys = self.consumer_slot_keys(vid)
            # Named scopes are metadata on the lowered ops: an xprof
            # view, and the benchmark's per-layer device times, group
            # ``%fusion.N`` by them. ``obs/scopes.py`` is the vocabulary;
            # the program is the same with or without them.
            with jax.named_scope(f"vertex/{v.name}"):
                if slot_keys is not None and hasattr(
                        v.operator, "process_block_static_keys"):
                    state, out = v.operator.process_block_static_keys(
                        op_states[vid], ins, bctx, slot_keys)
                else:
                    state, out = v.operator.process_block(
                        op_states[vid], ins, bctx)
            if consumed is None:
                # Pure generators "consume" what they emit (their record
                # count advances with generated records, like the
                # reference's source loop).
                consumed = out.count()
            op_states[vid] = self._shard_tree(state)
            out = self._shard_block(out)
            if in_edges and not job.out_edges(vid):
                sinks[vid] = out
            consumed_parts[vid] = consumed
            emit_parts[vid] = out.count()                        # [K, P]

            for eidx in job.out_edges(vid):
                e = job.edges[eidx]
                with jax.named_scope("exchange"):
                    r, d = self.route_edge(eidx, out, rr_offsets[eidx][0])
                if e.partition == PartitionType.REBALANCE:
                    rr_offsets[eidx] = (
                        (rr_offsets[eidx] + out.count().sum())
                        % jnp.asarray(job.vertices[e.dst].parallelism,
                                      jnp.int32))
                routed[eidx] = self._shard_block(r)
                dropped[eidx] = d
                # per target, so nothing crosses a shard here: the sums
                # over targets are the fence's (``_health_vector``)
                stats = {"dropped": exchange[eidx]["dropped"] + d.sum(axis=0)}
                if "peak" in exchange[eidx]:
                    stats["peak"] = jnp.maximum(
                        exchange[eidx]["peak"],
                        (routed[eidx].count() + d).max(axis=0))
                exchange[eidx] = self._shard_tree(stats)
                new_edge_bufs[eidx] = jax.tree_util.tree_map(
                    lambda x: x[-1], routed[eidx])

            if vid in self.ring_index:
                # In-flight logging: retain the producer's raw output block
                # (reference PipelinedSubpartition.add -> InFlightLog.log);
                # consumers re-derive their input by re-running the
                # deterministic exchange during replay.
                ri = self.ring_index[vid]
                with jax.named_scope("inflight-ring"):
                    el = ifl.append_block(out_rings[ri], out)
                # Re-pin the ring payload to its subtask axis (axis 1):
                # append_block's scatter would otherwise let the
                # partitioner re-layout the [S, P, cap] tensors along the
                # ring-step axis, splitting every step's batch across
                # chips instead of keeping each subtask's lane local.
                out_rings[ri] = el._replace(
                    keys=self._shard_axis(el.keys, 1),
                    values=self._shard_axis(el.values, 1),
                    timestamps=self._shard_axis(el.timestamps, 1),
                    valid=self._shard_axis(el.valid, 1))

        # Determinant block: one [L, K*4, lanes] tensor, two bulk appends.
        emits_all = jnp.concatenate(
            [emit_parts[v.vertex_id] for v in job.vertices], axis=1)  # [K, L]
        consumed_all = jnp.concatenate(
            [consumed_parts[v.vertex_id] for v in job.vertices], axis=1)
        with jax.named_scope("causal-log"):
            with jax.named_scope("rows"):
                rows = self._det_rows(binputs, emits_all)         # [L, 4K, 8]
            with jax.named_scope("own"):
                n, cap = rows.shape[1], self.log_capacity
                clog.note_append(clog.append_form(n, cap), logs=self.L,
                                 runs=0, rows=n, capacity=cap)
                logs = clog.v_append_full(carry.logs, rows)
                logs = self._shard_tree(logs)
            # Piggyback replication: the same block of determinants
            # lands in every downstream replica before any of this
            # block's outputs become externally visible (the per-message
            # netty delta becomes one bulk append per run of an owner's
            # replicas at the block fence).
            with jax.named_scope("replicas"):
                replicas = self._shard_tree(rep.append_block(
                    carry.replicas, rows, carry.logs.head, self.plan,
                    sharded=self.mesh is not None))

        new_carry = JobCarry(
            tuple(op_states), tuple(new_edge_bufs), tuple(rr_offsets),
            carry.record_counts + consumed_all.sum(axis=0), logs,
            tuple(out_rings), replicas, tuple(exchange))
        new_carry = self.constrain_carry(new_carry)
        return new_carry, BlockOutputs(sinks, dropped, consumed_all)

    def _det_rows(self, binputs: BlockInputs, emits_all: jnp.ndarray
                  ) -> jnp.ndarray:
        """Build the block's packed determinant rows [L, K*4, lanes]."""
        K = binputs.times.shape[0]
        t_hi = jnp.where(binputs.times < 0, -1, 0)
        base = jnp.zeros((K, DETS_PER_STEP, det.NUM_LANES), jnp.int32)
        base = base.at[:, 0, det.LANE_TAG].set(det.TIMESTAMP)
        base = base.at[:, 0, det.LANE_P].set(t_hi)
        base = base.at[:, 0, det.LANE_P + 1].set(binputs.times)
        base = base.at[:, 1, det.LANE_TAG].set(det.RNG)
        base = base.at[:, 1, det.LANE_P].set(binputs.rng_bits)
        base = base.at[:, 2, det.LANE_TAG].set(det.ORDER)
        base = base.at[:, 3, det.LANE_TAG].set(det.BUFFER_BUILT)
        rows = jnp.broadcast_to(base[None],
                                (self.L, K, DETS_PER_STEP, det.NUM_LANES))
        rows = rows.at[:, :, 3, det.LANE_P].set(
            emits_all.T)                                          # [L, K]
        return rows.reshape(self.L, K * DETS_PER_STEP, det.NUM_LANES)

    # --- single-step compatibility API --------------------------------------

    def superstep(self, carry: JobCarry, inputs: StepInputs
                  ) -> Tuple[JobCarry, StepOutputs]:
        """One superstep (a K=1 block): the dryrun/test surface."""
        binputs = BlockInputs(
            times=inputs.time[None], rng_bits=inputs.rng_bits[None],
            epoch=jnp.zeros((), jnp.int32), step0=jnp.zeros((), jnp.int32),
            feeds=tuple(jax.tree_util.tree_map(lambda x: x[None], f)
                        for f in inputs.feeds))
        carry, outs = self.run_block(carry, binputs)
        return carry, StepOutputs(
            sinks={vid: jax.tree_util.tree_map(lambda x: x[0], b)
                   for vid, b in outs.sinks.items()},
            dropped={e: d[0] for e, d in outs.dropped.items()},
            consumed=outs.consumed[0])


def _shard_buffers(leaf) -> List[Tuple[Any, int, int]]:
    """``(device, buffer address, bytes)`` of every shard of a leaf this
    process can address."""
    return [(s.device, s.data.unsafe_buffer_pointer(), s.data.nbytes)
            for s in leaf.addressable_shards]


def distinct_buffers(carry: JobCarry) -> JobCarry:
    """The carry with no device buffer under two leaves. Constructors
    hand several leaves one buffer (a ring's head, tail and epoch marks
    are one zero), and the donated block program rejects that ("donate
    the same buffer twice"): a leaf that shares a buffer with an earlier
    one is copied, every other leaf stays where it was built — a copy of
    every leaf would hold the largest one twice, which a leaf past half a
    chip's free memory cannot afford. Later programs keep the buffers
    distinct (outputs alias donated inputs one to one)."""
    leaves, treedef = jax.tree_util.tree_flatten(carry)
    seen = set()
    for i, leaf in enumerate(leaves):
        leaf = jnp.asarray(leaf)
        here = {(dev, ptr) for dev, ptr, _ in _shard_buffers(leaf)}
        if here & seen:
            leaf = leaf.copy()
            here = {(dev, ptr) for dev, ptr, _ in _shard_buffers(leaf)}
        seen |= here
        leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)


def carry_device_bytes(carry: JobCarry) -> Tuple[int, int]:
    """``(bytes of the whole carry, bytes on the fullest device)``, from
    the shards as they lie: a replicated leaf counts once on every
    device that holds it, and once in the total."""
    total, per_device = 0, {}
    for leaf in jax.tree_util.tree_leaves(carry):
        total += leaf.nbytes
        for dev, _ptr, nbytes in _shard_buffers(leaf):
            per_device[dev] = per_device.get(dev, 0) + nbytes
    return total, max(per_device.values(), default=0)


def _canon_log(state: clog.ThreadLogState) -> clog.ThreadLogState:
    """Zero ring rows outside [tail, head) and epoch-index slots outside
    [epoch_base, latest_epoch] — the physically-present-but-logically-dead
    storage. Two runs are equivalent iff their canonical carries are
    bit-identical (truncated slots may hold different garbage: a recovered
    log never re-materializes rows a completed checkpoint already dropped)."""
    cap = state.capacity
    pos = (state.tail + jnp.arange(cap, dtype=jnp.int32)) & (cap - 1)
    live = jnp.zeros((cap,), jnp.bool_).at[pos].set(
        jnp.arange(cap, dtype=jnp.int32) < (state.head - state.tail))
    m = state.max_epochs
    eidx = jnp.arange(m, dtype=jnp.int32)
    base = state.epoch_base
    # Live epochs: [max(base, latest-m+1), latest]; slot e % m.
    lo = jnp.maximum(base, state.latest_epoch - m + 1)
    live_e = jnp.zeros((m,), jnp.bool_).at[
        (lo + eidx) % m].set(lo + eidx <= state.latest_epoch)
    return state._replace(
        rows=jnp.where(live[:, None], state.rows, 0),
        epoch_starts=jnp.where(live_e, state.epoch_starts, 0))


def _canon_ring(state: ifl.EdgeLogState) -> ifl.EdgeLogState:
    S = state.ring_steps
    pos = (state.tail + jnp.arange(S, dtype=jnp.int32)) & (S - 1)
    live = jnp.zeros((S,), jnp.bool_).at[pos].set(
        jnp.arange(S, dtype=jnp.int32) < (state.head - state.tail))
    lv = live[:, None, None]
    m = state.max_epochs
    eidx = jnp.arange(m, dtype=jnp.int32)
    lo = jnp.maximum(state.epoch_base, state.latest_epoch - m + 1)
    live_e = jnp.zeros((m,), jnp.bool_).at[
        (lo + eidx) % m].set(lo + eidx <= state.latest_epoch)
    return state._replace(
        keys=jnp.where(lv, state.keys, 0),
        values=jnp.where(lv, state.values, 0),
        timestamps=jnp.where(lv, state.timestamps, 0),
        valid=jnp.where(lv, state.valid, False),
        epoch_starts=jnp.where(live_e, state.epoch_starts, 0))


@jax.jit
def canonical_carry(carry: JobCarry) -> JobCarry:
    """The carry with all logically-dead storage zeroed — the equality
    domain for the bit-identical-recovery property (tests compare
    ``canonical_carry(recovered) == canonical_carry(never_failed)``)."""
    return carry._replace(
        logs=jax.vmap(_canon_log)(carry.logs),
        replicas=(jax.vmap(_canon_log)(carry.replicas)
                  if carry.replicas.head.shape[0] > 0 else carry.replicas),
        out_rings=tuple(_canon_ring(r) for r in carry.out_rings))


def _slice_log_window(epoch: int, rows: np.ndarray, heads: np.ndarray,
                      starts: np.ndarray) -> Dict[int, np.ndarray]:
    """Slice one closed epoch's determinant rows out of the stacked
    causal-log arrays (host side). Shared by the live fence path
    (``epoch_window`` reading the resident carry) and the pipelined
    fence's deferred drain (``FenceHandles`` reading captured device
    copies), so both produce byte-identical audit-digest input."""
    cap = rows.shape[1]
    me = starts.shape[1]
    logs: Dict[int, np.ndarray] = {}
    for flat in range(rows.shape[0]):
        s = int(starts[flat, epoch % me])
        t = int(starts[flat, (epoch + 1) % me])
        if t < s:               # next epoch's start not stamped yet
            t = int(heads[flat])
        pos = np.arange(s, t) & (cap - 1)
        logs[flat] = np.ascontiguousarray(rows[flat][pos])
    return logs


def _slice_ring_window(epoch: int, keys: np.ndarray, values: np.ndarray,
                       stamps: np.ndarray, valid: np.ndarray,
                       estarts: np.ndarray, head: int) -> list:
    """Per-step valid records of one output ring for one closed epoch,
    in the deterministic (lane, slot) order — the ring half of
    :func:`_slice_log_window`'s shared-extraction contract."""
    rme = estarts.shape[0]
    s = int(estarts[epoch % rme])
    t = int(estarts[(epoch + 1) % rme])
    if t < s:
        t = int(head)
    rcap = keys.shape[0]
    steps = []
    for step in range(s, t):
        p = step & (rcap - 1)
        m = valid[p]
        steps.append((keys[p][m], values[p][m], stamps[p][m]))
    return steps


def iter_ring_steps(window: Dict[str, Any]):
    """Deterministic scan order over one ``epoch_window`` dict's ring
    section: ``(vertex_id, step_seq, keys, values, timestamps)`` tuples,
    vertices ascending, steps in epoch-relative order, records already
    in the (lane, slot) order :func:`_slice_ring_window` fixed. The
    lineage plane's dye scan (obs/lineage.py) consumes this so the
    window shape stays owned here."""
    rings = window.get("rings", {}) or {}
    for vid in sorted(rings, key=int):
        for seq, (keys, values, stamps) in enumerate(rings[vid]):
            yield int(vid), seq, keys, values, stamps


class FenceHandles:
    """Device-side capture of one closed epoch's fence surface — the
    health vector plus (optionally) the causal-log / in-flight-ring
    window arrays the audit seal digests. Produced by
    :meth:`LocalExecutor.capture_fence` as deep device copies with d2h
    started asynchronously, so the pipelined fence can dispatch the
    next epoch's compute immediately and let a worker thread drain the
    handles off the critical path. The handles never alias the live
    carry (whose buffers are donated into later block programs)."""

    def __init__(self, epoch: int, health, window, ring_index):
        self.epoch = epoch
        self._health = health
        self._window = window
        self._ring_index = ring_index

    def health(self) -> np.ndarray:
        """Drain the fused health vector (blocks until the capture
        program and its async d2h complete)."""
        return np.asarray(self._health)

    def window(self) -> Optional[Dict[str, Any]]:
        """Drain the captured causal surface into the exact
        ``epoch_window`` dict shape (None when captured without one)."""
        if self._window is None:
            return None
        rows, heads, starts, rings_t = self._window
        logs = _slice_log_window(self.epoch, np.asarray(rows),
                                 np.asarray(heads), np.asarray(starts))
        rings: Dict[int, list] = {}
        for vid, ri in self._ring_index.items():
            keys, values, stamps, valid, estarts, head = rings_t[ri]
            rings[vid] = _slice_ring_window(
                self.epoch, np.asarray(keys), np.asarray(values),
                np.asarray(stamps), np.asarray(valid),
                np.asarray(estarts), int(np.asarray(head)))
        return {"logs": logs, "rings": rings}


class CausalTimeSource:
    """Host clock for the live path (reference CausalTimeService /
    PeriodicCausalTimeService.java — one amortized read per superstep).
    Produces int32 millis since executor start; values are recorded in every
    task's log as TIMESTAMP determinants by the block program itself."""

    def __init__(self):
        self._t0 = _time.monotonic()

    def now(self) -> int:
        return int((_time.monotonic() - self._t0) * 1000) & 0x7FFFFFFF


class LogicalTimeSource:
    """Deterministic causal time: 1 ms per superstep, read as the
    absolute step index about to be stamped. Wall-clock TIMESTAMP
    determinants are the one live-path input that replay reproduces but
    two INDEPENDENT runs never share; with logical time the whole step
    input stream is a pure function of (job, seed, feed records), so a
    spanned job's slices can be digest-compared against a no-failure
    control run. After a standby rebuild the restored
    ``step_input_history`` makes the clock resume exactly at the fence
    step — bit-identical with the run that never failed."""

    def __init__(self, executor: "LocalExecutor"):
        self._ex = executor

    def now(self) -> int:
        # Called exactly once per superstep, just before the (t, rng)
        # append — history length IS the global index of that step.
        return len(self._ex.step_input_history) & 0x7FFFFFFF


class LocalExecutor:
    """Single-process job driver (MiniCluster analog): owns the compiled
    job, the carry, the causal time/RNG sources, and the epoch loop."""

    def __init__(self, job: JobGraph, steps_per_epoch: int = 16,
                 log_capacity: int = 1 << 14, max_epochs: int = 64,
                 inflight_ring_steps: int = 64,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 spool_dir: Optional[str] = None,
                 spill_policy: str = ifl.SpillPolicy.EAGER,
                 spill_host_budget_epochs: int = 2,
                 block_steps: Optional[int] = None,
                 replication_factor: int = -1,
                 seed: int = 0, logical_time: bool = False):
        self.compiled = CompiledJob(job, log_capacity=log_capacity,
                                    max_epochs=max_epochs,
                                    inflight_ring_steps=inflight_ring_steps,
                                    mesh=mesh,
                                    replication_factor=replication_factor)
        self.job = job
        self.steps_per_epoch = steps_per_epoch
        self.block_steps = min(block_steps or 512, steps_per_epoch,
                               inflight_ring_steps)
        self.carry = self.compiled.build_carry()
        self.time_source = (LogicalTimeSource(self) if logical_time
                            else CausalTimeSource())
        self._seed = seed
        self._rng = np.random.RandomState(seed)
        self.epoch_id = 0
        self.step_in_epoch = 0
        #: (flat, epoch) -> async rows appended in that epoch's roll gap
        #: (after the roll, before its first step) — recovery subtracts
        #: this when re-deriving epoch start offsets from TIMESTAMP
        #: anchors (the rows belong to the NEW epoch).
        self.roll_gap_async: Dict[Tuple[int, int], int] = {}
        #: (flat, epoch) -> ALL async rows appended to that task's log
        #: during that epoch. A host-side mirror of log cleanness: a task
        #: with zero async rows since an epoch fence has a pure k-row
        #: sync-block stream there (only the block program appended), so
        #: recovery can take the device-resident clean path WITHOUT a
        #: metadata round-trip — the device parse still validates it, but
        #: as a deferred assert folded into recovery's final read.
        self.async_counts: Dict[Tuple[int, int], int] = {}
        # Explicit shardings for every jitted entry point when a mesh is
        # attached: the carry rides its rule-driven NamedSharding tree
        # (parallel/distributed.py rules — the SAME table the in-trace
        # constraints use, so entry layout and traced layout can never
        # disagree), host-fed step inputs replicate. Donation stays on:
        # input and output carry shardings match leaf-for-leaf, so XLA
        # aliases the GB-scale buffers shard-locally (no cross-chip copy
        # at the donate boundary).
        self._carry_ns = self.compiled.carry_shardings(self.carry)
        self._repl_ns = (jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
            if mesh is not None else None)

        def _mesh_kw(in_shardings, out_shardings=None):
            if mesh is None:
                return {}
            kw = {"in_shardings": in_shardings}
            if out_shardings is not None:
                kw["out_shardings"] = out_shardings
            return kw

        def _ns0(x):
            # Leading-axis task sharding for a plain array arg (the
            # stacked-log storage the async-append program touches),
            # with the same divisibility guard the rule table applies.
            if mesh is None:
                return None
            n = mesh.shape[self.compiled.task_axis]
            shp = getattr(x, "shape", ())
            if len(shp) >= 1 and shp[0] % n == 0 and shp[0] > 0:
                return jax.sharding.NamedSharding(
                    mesh,
                    jax.sharding.PartitionSpec(self.compiled.task_axis))
            return self._repl_ns

        install_compile_listener()
        # The carry is donated: the block program updates GB-scale log /
        # ring storage in place instead of copying it every call (the
        # carry's buffers are only ever referenced by the live executor;
        # checkpoints deep-copy what they keep — lean_snapshot).
        self._jit_block = jax.jit(
            self.compiled.run_block, donate_argnums=0,
            **_mesh_kw((self._carry_ns, self._repl_ns)))

        plan = self.compiled.plan

        def _roll(carry: JobCarry, e) -> JobCarry:
            # Epoch fence: record the new epoch's start offset on every
            # log, replica, and in-flight ring coherently. Replica heads
            # equal owner heads by construction (the block program appends
            # both from the same tensor).
            replicas = carry.replicas
            with jax.named_scope("causal-log"):
                if plan.num_replicas > 0:
                    with jax.named_scope("replicas"):
                        replicas = rep.sync_replica_epochs(replicas, e)
                with jax.named_scope("own"):
                    logs = clog.v_start_epoch(carry.logs, e)
            with jax.named_scope("inflight-ring"):
                out_rings = tuple(ifl.start_epoch(el, e)
                                  for el in carry.out_rings)
            return carry._replace(
                logs=logs,
                # Ring markers sit exactly at the fence. The batch appended
                # at the fence's last step is still in flight (its consumer
                # reads it one step after the fence), but that batch rides
                # the checkpoint as the depth-1 edge buffer of the
                # LeanSnapshot — the ring copy is redundant, so truncation
                # may drop it (and recovery never rebuilds it).
                out_rings=out_rings, replicas=replicas)

        def _trunc(carry: JobCarry, e) -> JobCarry:
            replicas = carry.replicas
            with jax.named_scope("causal-log"):
                if plan.num_replicas > 0:
                    with jax.named_scope("replicas"):
                        replicas = clog.v_truncate(replicas, e)
                with jax.named_scope("own"):
                    logs = clog.v_truncate(carry.logs, e)
            with jax.named_scope("inflight-ring"):
                out_rings = tuple(ifl.truncate(el, e)
                                  for el in carry.out_rings)
            return carry._replace(logs=logs, out_rings=out_rings,
                                  replicas=replicas)

        self._jit_roll = jax.jit(
            _roll, donate_argnums=0,
            **_mesh_kw((self._carry_ns, self._repl_ns), self._carry_ns))
        self._jit_trunc = jax.jit(
            _trunc, donate_argnums=0,
            **_mesh_kw((self._carry_ns, self._repl_ns), self._carry_ns))
        # Host-side spill owners, one per ring vertex (None = disabled).
        self.spill_policy = spill_policy
        self.spill_logs: Optional[List[ifl.SpillingInFlightLog]] = None
        #: determinant-log tier (storage/tiered.py): sealed epochs of every
        #: stacked causal log spill through the same host→disk tiers as the
        #: in-flight rings, so replication depth is no longer HBM-bounded.
        self.det_store = None
        #: per-ring epochs deferred by the AVAILABILITY policy, awaiting
        #: either a later spill (before a wrap) or truncation.
        self._pending_spill: List[List[Tuple[int, int, int]]] = [
            [] for _ in self.compiled.ring_vertices]
        if spool_dir is not None:
            from clonos_tpu.storage import TieredEpochStore
            self.spill_logs = [
                ifl.SpillingInFlightLog(
                    spool_dir, edge_id=vid, policy=spill_policy,
                    host_budget_epochs=spill_host_budget_epochs)
                for vid in self.compiled.ring_vertices]
            self.det_store = TieredEpochStore(
                spool_dir, "dets",
                durable=spill_policy != ifl.SpillPolicy.DISABLED,
                host_budget_epochs=spill_host_budget_epochs)
            # Static bound for the fused epoch-window gather: the sync
            # block stream is DETS_PER_STEP rows/step; async appends
            # (timers, sources) ride on top, so leave headroom and fall
            # back to the exact host extraction when a hot epoch blows
            # past it (_spill_epoch checks counts against this).
            self._det_window_rows = min(
                self.compiled.log_capacity,
                steps_per_epoch * DETS_PER_STEP * 2 + 64)
            self._jit_det_window = jax.jit(
                partial(clog.epoch_row_windows,
                        max_rows=self._det_window_rows))
        # Epoch 0 starts at log offset 0 for every log.
        self.carry = self._jit_roll(self.carry, 0)
        self.step_input_history: List[Tuple[int, int]] = []
        #: vid -> FeedReader for HostFeedSource vertices
        self.feed_readers: Dict[int, Any] = {}
        #: called after every block with (last_causal_time, record_stamp) —
        #: the superstep-boundary hook timer services advance on.
        self.block_listeners: List[Any] = []
        #: optional hook fed (BlockOutputs, epoch_id) after every block —
        #: the transactional-sink egress tap (runtime/txn.py). It may
        #: trail the block program (runtime/sinktap.py): what it still
        #: holds when a block loop ends, ``drain_block_outputs`` reads.
        self.on_block_outputs: Optional[Any] = None
        #: optional hook, no arguments, called where a block loop ends:
        #: after an epoch's last block (before the roll) and after a
        #: ``step()``.
        self.drain_block_outputs: Optional[Any] = None
        #: the newest block's outputs, until ``step``/``run_epoch`` hand
        #: them to the caller
        self._block_outs: Optional[BlockOutputs] = None

        owner_idx = self.compiled._owner_idx
        nrep = self.compiled.plan.num_replicas

        def _append_many(log_rows, log_heads, rep_rows, rep_heads,
                         rows1, counts):
            # Masked single-row append per selected log + its replicas,
            # donated in-place (rows storage is referenced only by the
            # live carry; heads are returned fresh because lean snapshots
            # alias them).
            L = log_heads.shape[0]
            capm = self.compiled.log_capacity - 1
            pos = log_heads & capm
            cur = log_rows[jnp.arange(L), pos]
            sel = counts[:, None] > 0
            log_rows = log_rows.at[jnp.arange(L), pos].set(
                jnp.where(sel, rows1, cur))
            log_heads = log_heads + counts
            if nrep > 0:
                rrows1 = rows1[owner_idx]
                rcounts = counts[owner_idx]
                rpos = rep_heads & capm
                rcur = rep_rows[jnp.arange(nrep), rpos]
                rsel = rcounts[:, None] > 0
                rep_rows = rep_rows.at[jnp.arange(nrep), rpos].set(
                    jnp.where(rsel, rrows1, rcur))
                rep_heads = rep_heads + rcounts
            return log_rows, log_heads, rep_rows, rep_heads

        c0 = self.carry
        self._jit_append_many = jax.jit(
            _append_many, donate_argnums=(0, 2),
            **_mesh_kw(
                (_ns0(c0.logs.rows), _ns0(c0.logs.head),
                 _ns0(c0.replicas.rows), _ns0(c0.replicas.head),
                 _ns0(c0.logs.rows), _ns0(c0.logs.head)),
                (_ns0(c0.logs.rows), _ns0(c0.logs.head),
                 _ns0(c0.replicas.rows), _ns0(c0.replicas.head))))

    def register_feed(self, vertex_id: int, reader) -> None:
        """Attach a rewindable reader (api/feeds.py) to a HostFeedSource
        vertex — the external-system ingestion boundary."""
        if vertex_id not in self.compiled.feed_vertices:
            raise ValueError(f"vertex {vertex_id} is not a HostFeedSource")
        self.feed_readers[vertex_id] = reader

    def _pull_feeds(self, k: int) -> Tuple[RecordBatch, ...]:
        """Pull k steps' worth of records from every feed reader into
        stacked [k, P, B] batches (one device put per feed)."""
        from clonos_tpu.api.records import empty as empty_batch
        if not self.compiled.feed_vertices:
            return ()
        tr = get_tracer()
        pulled = []
        with tr.span("block.feed.pull"):
            for vid in self.compiled.feed_vertices:
                v = self.job.vertices[vid]
                b = v.operator.batch_size
                reader = self.feed_readers.get(vid)
                if reader is None:
                    pulled.append(((k, v.parallelism, b), None))
                    continue
                rows_k = np.zeros((k, v.parallelism, b), np.int32)
                rows_v = np.zeros((k, v.parallelism, b), np.int32)
                counts = np.zeros((k, v.parallelism), np.int32)
                for s in range(v.parallelism):
                    ks, vs, cnt = reader.pull_block(s, b, k)
                    rows_k[:, s, :], rows_v[:, s, :] = ks, vs
                    counts[:, s] = cnt
                valid = np.arange(b)[None, None, :] < counts[:, :, None]
                tr.count("feed.records", int(counts.sum()))
                pulled.append((rows_k.shape, (rows_k, rows_v, valid)))
        feeds = []
        with tr.span("block.feed.put") as put:
            nbytes = 0
            for shape, host in pulled:
                if host is None:
                    feeds.append(empty_batch(shape))
                    continue
                rows_k, rows_v, valid = host
                nbytes += rows_k.nbytes + rows_v.nbytes + valid.nbytes
                feeds.append(RecordBatch(
                    jnp.asarray(rows_k), jnp.asarray(rows_v),
                    jnp.zeros(shape, jnp.int32), jnp.asarray(valid)))
            put.set(bytes=nbytes)
        tr.count("feed.h2d_bytes", nbytes)
        return tuple(feeds)

    def _draw_causal_inputs(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """The next ``n`` steps' causal time and RNG draws, recorded in
        ``step_input_history``."""
        times = np.empty((n,), np.int32)
        rngs = np.empty((n,), np.int32)
        for i in range(n):
            t = self.time_source.now()
            r = int(self._rng.randint(0, 2 ** 31, dtype=np.int64))
            times[i], rngs[i] = t, r
            self.step_input_history.append((t, r))
        return times, rngs

    def _next_block_inputs(self, k: int) -> BlockInputs:
        with get_tracer().span("block.causal-inputs"):
            times, rngs = self._draw_causal_inputs(k)
            times_d, rngs_d = jnp.asarray(times), jnp.asarray(rngs)
            epoch = jnp.asarray(self.epoch_id, jnp.int32)
            step0 = jnp.asarray(len(self.step_input_history) - k,
                                jnp.int32)
        return BlockInputs(times=times_d, rng_bits=rngs_d, epoch=epoch,
                           step0=step0, feeds=self._pull_feeds(k))

    def _notify_block(self) -> None:
        # The time/stamp of the last executed step.
        if self.block_listeners and self.step_input_history:
            with get_tracer().span("block.notify"):
                t = self.step_input_history[-1][0]
                stamp = self.global_record_stamp()
                for fn in self.block_listeners:
                    fn(t, stamp)

    def _host_block(self, k: int) -> None:
        """One host-staged block of ``k`` supersteps: causal inputs and
        feeds up, the block program, the sink tap, the listeners — each
        under its own span inside ``block``. Nothing here waits for the
        program it dispatched: the tap (``on_block_outputs``) reads the
        *previous* block's rows and launches this one's compaction, so
        the next call prepares and dispatches its block while this one
        runs. Its outputs replace ``_block_outs`` at the dispatch, so
        the previous block's are released there; only the dense sink
        batch the tap kept for a read-again lives on, until that tap
        reads it a few lines further down."""
        tr = get_tracer()
        with tr.span("block", epoch=self.epoch_id, k=k, program="run_block"):
            inputs = self._next_block_inputs(k)
            with tr.span("block.dispatch"):
                self.carry, self._block_outs = self._jit_block(self.carry,
                                                               inputs)
            del inputs
            tr.count("block.dispatches.run_block")
            self._block_done(k)

    def _block_done(self, k: int) -> None:
        self.step_in_epoch += k
        if self.on_block_outputs is not None:
            self.on_block_outputs(self._block_outs, self.epoch_id)
        self._notify_block()

    def _take_block_outs(self) -> Optional[BlockOutputs]:
        outs, self._block_outs = self._block_outs, None
        return outs

    def _drain_block_outputs(self) -> None:
        if self.drain_block_outputs is not None:
            self.drain_block_outputs()

    def step(self) -> StepOutputs:
        """Run one superstep on the live path (a K=1 block)."""
        self._host_block(1)
        self._drain_block_outputs()
        outs = self._take_block_outs()
        return StepOutputs(
            sinks={vid: jax.tree_util.tree_map(lambda x: x[0], b)
                   for vid, b in outs.sinks.items()},
            dropped={e: d[0] for e, d in outs.dropped.items()},
            consumed=outs.consumed[0])

    def run_epoch(self) -> Optional[BlockOutputs]:
        """Run the remainder of the current epoch in block programs, then
        roll the epoch (the checkpoint fence lands here)."""
        tr = get_tracer()
        while self.step_in_epoch < self.steps_per_epoch:
            self._host_block(min(
                self.block_steps, self.steps_per_epoch - self.step_in_epoch))
        # the tap never trails across an epoch boundary: the fence seals
        # the transaction with the epoch's last block in it
        self._drain_block_outputs()
        closed = self.epoch_id
        self.epoch_id += 1
        self.step_in_epoch = 0
        # One pair of stamps per interval: the span's, which also feed
        # the overhead.<section>-ms histograms of an enabled profiler.
        from clonos_tpu.obs import get_profiler
        prof = get_profiler()
        if self.spill_logs is not None:
            with tr.span("epoch.spill") as sp:
                self._spill_epoch(closed)
            prof.observe("spill", sp.dur)
        with tr.span("epoch.roll") as sp:
            self.carry = self._jit_roll(self.carry, self.epoch_id)
            prof.fence(self.carry.logs)
        tr.count("block.dispatches.roll")
        prof.observe("roll", sp.dur)
        return self._take_block_outs()

    def _spill_epoch(self, epoch: int) -> None:
        """Move the just-closed epoch's in-flight batches to the host spill
        owner (reference SpillableSubpartitionInFlightLogger writes one file
        per epoch as it closes). Policy AVAILABILITY skips epochs while the
        ring has headroom (reference spill.policy availability) — but a
        skipped epoch is only DEFERRED: before a future ring wrap could
        clobber its steps, it is retroactively spilled (the round-2/3
        advice hole: 'skip forever' silently destroys the only copy and
        recovery fails only at recovery time)."""
        for i, el in enumerate(self.carry.out_rings):
            start = int(ifl.epoch_start_step(el, epoch))
            head = int(el.head)
            n = head - start
            skip = False
            if self.spill_policy == ifl.SpillPolicy.AVAILABILITY:
                occupancy = float(jnp.asarray(ifl.size(el))) / el.ring_steps
                if occupancy < self.spill_logs[i].availability_trigger:
                    skip = True
            if skip:
                if n > 0:
                    self._pending_spill[i].append((epoch, start, n))
            elif n > 0:
                # Device arrays go straight to the spill owner: the
                # device→host copy happens on its writer thread, overlapped
                # with the next epoch's compute (the slice result is a
                # fresh buffer, so the roll's donation cannot alias it).
                batch, count, s0 = ifl.slice_steps(el, start, n)
                self.spill_logs[i].spill_epoch(epoch, int(s0), batch)
            # Retroactive flush: anything a wrap could reach within the
            # next epoch's appends must leave the ring now.
            danger = head + self.steps_per_epoch - el.ring_steps
            keep = []
            for (e, s, m) in self._pending_spill[i]:
                if s < head - el.ring_steps:
                    raise RuntimeError(
                        f"in-flight ring {i}: epoch {e} steps "
                        f"[{s}, {s + m}) were clobbered before spilling "
                        f"(AVAILABILITY policy deferred too long)")
                if s < danger:
                    batch, count, s0 = ifl.slice_steps(el, s, m)
                    self.spill_logs[i].spill_epoch(e, int(s0), batch)
                else:
                    keep.append((e, s, m))
            self._pending_spill[i] = keep
        if self.det_store is not None:
            self._spill_det_epoch(epoch)

    def _spill_det_epoch(self, epoch: int) -> None:
        """Evict the just-closed epoch's determinant windows (every stacked
        log, one fused gather) into the tiered store — called before the
        roll stamps the next epoch's start, so each window is
        ``[epoch_start, head)``, exactly :meth:`epoch_window`'s slice."""
        me = self.compiled.max_epochs
        rows, counts, starts = self._jit_det_window(
            self.carry.logs, epoch % me)
        counts_h = np.asarray(counts)
        starts_h = np.asarray(starts)
        n = int(counts_h.max()) if counts_h.size else 0
        if n > self._det_window_rows:
            # Async-heavy epoch blew past the static gather bound: degrade
            # to the exact host extraction rather than truncate rows.
            win = self.epoch_window(epoch)["logs"]
            padded = np.zeros((len(win), max(n, 1), det.NUM_LANES),
                              np.int32)
            for flat, r in win.items():
                padded[flat, :r.shape[0]] = r
            rows = padded
        elif n < self._det_window_rows:
            rows = rows[:, :max(n, 1)]   # trim ring-garbage padding
        self.det_store.put(
            epoch, int(starts_h.min()) if starts_h.size else 0,
            {"rows": rows, "counts": counts_h, "starts": starts_h})

    def notify_checkpoint_complete(self, epoch: int) -> None:
        """Truncate determinant + in-flight logs for epochs <= ``epoch``."""
        from clonos_tpu.obs import get_profiler
        tr = get_tracer()
        prof = get_profiler()
        # checkpoint-cadence, not per-step: the epoch fence -> truncation
        # leg of the epoch lifecycle
        with tr.span("ckpt.truncate", epoch=epoch) as sp:
            self.carry = self._jit_trunc(self.carry, epoch)
            prof.fence(self.carry.logs)
            if self.spill_logs is not None:
                for sl in self.spill_logs:
                    sl.truncate(epoch)
            if self.det_store is not None:
                self.det_store.truncate(epoch)
        tr.count("block.dispatches.trunc")
        prof.observe("truncate", sp.dur)
        for i, pend in enumerate(self._pending_spill):
            self._pending_spill[i] = [(e, s, m) for (e, s, m) in pend
                                      if e > epoch]
        self.roll_gap_async = {k: v for k, v in self.roll_gap_async.items()
                               if k[1] > epoch}
        self.async_counts = {k: v for k, v in self.async_counts.items()
                             if k[1] > epoch}

    # --- tiered-storage surface (storage/tiered.py) --------------------------

    def _tier_stores(self):
        out = []
        if self.spill_logs is not None:
            out.extend(sl.store for sl in self.spill_logs)
        if self.det_store is not None:
            out.append(self.det_store)
        return out

    def attach_spill_digests(self, epoch: int, dg) -> None:
        """Stamp the sealed epoch's audit fingerprints onto its spilled
        tiers: each ring segment carries its ``ring/v<vid>`` channel
        chain, the determinant segment one fold over the ``log/<flat>``
        chains — the SAME digests the ledger entry pins, so a
        spill/refill round-trip is audit-verifiable for free."""
        if self.spill_logs is not None:
            for i, vid in enumerate(self.compiled.ring_vertices):
                ch = dg.channels.get(f"ring/v{vid}")
                if ch is not None:
                    self.spill_logs[i].attach_digest(epoch, ch[1].hex())
        # clonos: allow(join-discipline): det_store is attached during
        # setup, before any worker thread starts, and never rebound;
        # the tiered store's mutating methods serialize on its own
        # internal lock (the race pass models collaborator method calls
        # as mutations of the holder attribute).
        if self.det_store is not None:
            h = hashlib.blake2b(digest_size=8)
            for name in sorted(dg.channels):
                if name.startswith("log/"):
                    _, state = dg.channels[name]
                    h.update(name.encode() + b"\x00" + state)
            self.det_store.attach_digest(epoch, h.hexdigest())

    def det_rows_for_epoch(self, flat: int, epoch: int) -> np.ndarray:
        """Refill one subtask's determinant-row window for a spilled
        epoch from whichever tier holds it — bit-identical to the
        ``epoch_window(epoch)["logs"][flat]`` slice taken at the seal
        (the spilled-determinant acceptance test pins this)."""
        if self.det_store is None:
            raise RuntimeError("determinant tier disabled (no spool_dir)")
        _, arrs = self.det_store.load_epoch(epoch)
        c = int(np.asarray(arrs["counts"])[flat])
        return np.ascontiguousarray(np.asarray(arrs["rows"])[flat, :c])

    def spill_occupancy(self) -> Dict[str, int]:
        """Tier residency summed across every spill owner (rings + dets)
        — the ``spill.*`` occupancy gauges."""
        agg = {"host_epochs": 0, "host_bytes": 0,
               "disk_epochs": 0, "disk_bytes": 0}
        for st in self._tier_stores():
            for k, v in st.occupancy().items():
                agg[k] += v
        return agg

    def spill_stats(self) -> Dict[str, Any]:
        """Cumulative spill/refill movement counters summed across
        stores."""
        agg: Dict[str, Any] = {}
        for st in self._tier_stores():
            for k, v in st.stats().items():
                agg[k] = agg.get(k, 0) + v
        return agg

    def drain_spill(self) -> None:
        """Block until every queued segment write is durable (tests,
        pre-kill quiesce in soak)."""
        for st in self._tier_stores():
            st.drain()

    def epoch_window(self, epoch: int) -> Dict[str, Any]:
        """Host snapshot of one CLOSED epoch's causal surface — the single
        extraction path behind the audit digests (obs/audit.py): the live
        seal at the epoch fence and the recovery-time recompute both read
        through here, so their chain chunk boundaries always agree.

        Returns ``{"logs": {flat: rows[n, NUM_LANES]},
        "rings": {vid: [(keys, values, timestamps) per step]}}`` — the
        determinant-row window of every subtask's causal log and, per
        output ring, each step's valid records flattened in the
        deterministic (lane, slot) order. Requires the epoch's rows to
        still be retained (not truncated past) — true at the fence that
        closes it and for every epoch at/after the latest completed
        checkpoint during recovery."""
        c = self.carry
        logs = _slice_log_window(
            epoch, np.asarray(c.logs.rows), np.asarray(c.logs.head),
            np.asarray(c.logs.epoch_starts))
        rings: Dict[int, list] = {}
        for vid, ri in self.compiled.ring_index.items():
            el = c.out_rings[ri]
            rings[vid] = _slice_ring_window(
                epoch, np.asarray(el.keys), np.asarray(el.values),
                np.asarray(el.timestamps), np.asarray(el.valid),
                np.asarray(el.epoch_starts), int(el.head))
        return {"logs": logs, "rings": rings}

    def _health_vector(self, carry: JobCarry) -> jnp.ndarray:
        """Pure: packed int32 [3 + num_rings + 1 + 1] health flags + total
        record count — ONE device value so the per-epoch control-plane
        read is one device→host sync, not six."""
        logs = carry.logs
        cap = self.compiled.log_capacity
        flags = [
            jnp.any(logs.head - logs.tail > cap),
            jnp.any(logs.latest_epoch - logs.epoch_base + 1
                    > self.compiled.max_epochs),
            jnp.any(clog.near_offset_wrap(logs)),
        ]
        for el in carry.out_rings:
            flags.append(jnp.asarray(ifl.overflowed(el)))
        if self.compiled.plan.num_replicas > 0:
            flags.append(jnp.any(carry.replicas.head - carry.replicas.tail
                                 > cap))
        else:
            flags.append(jnp.zeros((), jnp.bool_))
        vec = jnp.stack([f.astype(jnp.int32) for f in flags])
        # Trailing: total record count, then the per-task log heads — at
        # an epoch fence these ARE the checkpoint's log heads, so the
        # control plane learns them inside the one read it already pays
        # (recovery's patch phase then needs no head round-trip) — then
        # ``fence_totals`` of every event-time window vertex, then every
        # edge's dropped records and the dynamic edges' fullest step
        # (the one place the exchange's per-target counters are reduced).
        tail = [carry.op_states[v.vertex_id][k].sum()
                for v, k, _ in self.compiled.fence_total_slots()]
        tail += [x["dropped"].sum() for x in carry.exchange]
        tail += [carry.exchange[e]["peak"].max()
                 for e in self.compiled.peak_edges()]
        tail += [carry.op_states[v.vertex_id][k].max()
                 for v, k, _ in self.compiled.fence_peak_slots()]
        return jnp.concatenate(
            [vec, carry.record_counts.sum()[None], carry.logs.head]
            + ([jnp.stack(tail)] if tail else []))

    def health_parts(self, vec: np.ndarray) -> Dict[str, np.ndarray]:
        """A health vector by what its entries are: ``flags``,
        ``records`` (one total), ``heads`` (a log head a task),
        ``totals`` (the ``fence_totals`` of each vertex that has any, in
        ``event_window_vertices`` order), ``dropped`` (an entry an edge),
        ``peak`` (one a ``peak_edges()`` edge) and ``marks`` (the
        operators' ``fence_peaks``, in the same vertex order)."""
        c = self.compiled
        sizes = (("flags", 4 + len(self.carry.out_rings)), ("records", 1),
                 ("heads", c.L),
                 ("totals", len(c.fence_total_slots())),
                 ("dropped", len(self.job.edges)),
                 ("peak", len(c.peak_edges())),
                 ("marks", len(c.fence_peak_slots())))
        parts, at = {}, 0
        for name, n in sizes:
            parts[name] = vec[at:at + n]
            at += n
        return parts

    def health_vector(self) -> np.ndarray:
        if not hasattr(self, "_jit_health"):
            self._jit_health = jax.jit(self._health_vector)
        return np.asarray(self._jit_health(self.carry))

    def _per_shard_health(self, carry: JobCarry) -> jnp.ndarray:
        """Pure: int32 [n_shards, 3] — records processed, live causal-log
        rows, live in-flight ring slots — summed over the task-axis block
        each mesh shard owns. One packed device value, same rationale as
        :meth:`_health_vector`: the control plane pays one read to learn
        which chip is hot, lagging, or about to overflow."""
        n = self.compiled.mesh.shape[self.compiled.task_axis]
        L = self.compiled.L
        g = -(-L // n)                       # block size (ceil for pad)
        pad = g * n - L

        def blocks(x):                       # [L] -> [n] block sums
            return jnp.pad(x, (0, pad)).reshape(n, g).sum(axis=1)

        rec = blocks(carry.record_counts)
        rows = blocks(carry.logs.head - carry.logs.tail)
        ring = jnp.zeros((n,), jnp.int32)
        for el in carry.out_rings:
            p = el.valid.shape[1]
            gp = -(-p // n)
            padp = gp * n - p
            v = jnp.pad(el.valid.astype(jnp.int32),
                        ((0, 0), (0, padp), (0, 0)))
            ring = ring + v.reshape(v.shape[0], n, gp,
                                    v.shape[2]).sum(axis=(0, 2, 3))
        return jnp.stack([rec, rows, ring], axis=1)

    def per_shard_health(self) -> Optional[np.ndarray]:
        """int32 [n_shards, 3] (records, log rows, ring occupancy) per
        mesh shard along the task axis; None without a mesh (the job is
        one implicit shard). Shards are the contiguous task-axis blocks
        the rule-driven PartitionSpec deals to each device."""
        if self.compiled.mesh is None:
            return None
        if not hasattr(self, "_jit_shard_health"):
            self._jit_shard_health = jax.jit(self._per_shard_health)
        return np.asarray(self._jit_shard_health(self.carry))

    def overflow_messages(self, vec: np.ndarray) -> List[str]:
        """Decode :meth:`health_vector` flags into violation strings."""
        out = []
        if vec[0]:
            out.append("causal log ring overflow (appends clobbered "
                       "un-truncated determinants)")
        if vec[1]:
            out.append("causal log epoch index overflow (> max_epochs "
                       "un-truncated epochs)")
        if vec[2]:
            out.append("causal log absolute offsets near int32 wrap "
                       "(rebase required)")
        spilled = self.spill_logs is not None
        for i in range(len(self.carry.out_rings)):
            if not spilled and vec[3 + i]:
                out.append(f"in-flight ring of vertex "
                           f"{self.compiled.ring_vertices[i]} overflowed "
                           f"with spill disabled")
        if vec[3 + len(self.carry.out_rings)]:
            out.append("replica log ring overflow")
        # Losses, loud in every run: records an edge dropped past its
        # capacity, and what an operator counts as lost (``fence_losses``).
        parts = self.health_parts(vec)
        for eidx, n in enumerate(parts["dropped"]):
            if n:
                out.append(
                    f"edge {self.compiled.edge_name(eidx)} dropped {int(n)} "
                    f"records past its capacity "
                    f"{self.job.edges[eidx].capacity}")
        for (v, key, counter), n in zip(self.compiled.fence_total_slots(),
                                        parts["totals"]):
            if key in v.operator.fence_losses and n:
                out.append(f"vertex {v.name!r} lost {int(n)} ({counter})")
        return out

    def check_overflow(self) -> List[str]:
        """Overflow guards the control plane must heed at every epoch roll
        (VERDICT round-1: these existed but had no caller). Returns a list
        of violation descriptions; empty = healthy."""
        return self.overflow_messages(self.health_vector())

    def plan_replicas_overflowed(self) -> bool:
        if self.compiled.plan.num_replicas == 0:
            return False
        reps = self.carry.replicas
        return bool(jnp.any(reps.head - reps.tail
                            > self.compiled.log_capacity))

    @property
    def plan(self):
        return self.compiled.plan

    def append_async_determinant(self, flat_subtask: int,
                                 d: "det.Determinant") -> None:
        """Host path for causal services: append one determinant row to a
        task's device log — and to every replica of that log, preserving
        the replicate-before-visible invariant — between blocks.
        TIMESTAMP/RNG rows get a nonzero record-count stamp so the replayer
        can tell them apart from the per-step sync anchors."""
        self.append_async_many([flat_subtask], d)

    def append_async_many(self, flat_subtasks: Sequence[int],
                          d: "det.Determinant") -> None:
        """Append one determinant row to several subtask logs (and every
        replica of each) in ONE fused device program — the control plane's
        batch path for SOURCE_CHECKPOINT / IGNORE_CHECKPOINT broadcasts
        (reference StreamTask.performCheckpoint:833-840 / :891-915)."""
        row = d.pack().copy()
        if row[det.LANE_RC] == 0 and row[det.LANE_TAG] in (det.TIMESTAMP,
                                                           det.RNG):
            row[det.LANE_RC] = self.global_record_stamp()
        if self.step_in_epoch == 0:
            # Roll-gap append: the epoch already rolled but none of its
            # steps ran, so this row belongs to the NEW epoch even though
            # it precedes the epoch's first TIMESTAMP anchor in the log.
            # Recovery rebuilds the epoch->offset index from those anchors
            # and subtracts this ledger to place the boundary exactly
            # (failover._epoch_index; SOURCE_CHECKPOINT / IGNORE_CHECKPOINT /
            # service calls between epochs all land here).
            for f in flat_subtasks:
                k = (f, self.epoch_id)
                self.roll_gap_async[k] = self.roll_gap_async.get(k, 0) + 1
        for f in flat_subtasks:
            k = (f, self.epoch_id)
            self.async_counts[k] = self.async_counts.get(k, 0) + 1
        rows1 = np.zeros((self.compiled.L, det.NUM_LANES), np.int32)
        counts = np.zeros((self.compiled.L,), np.int32)
        rows1[list(flat_subtasks)] = row
        counts[list(flat_subtasks)] = 1
        c = self.carry
        from clonos_tpu.obs import get_profiler
        with get_profiler().section("async-append"):
            lr, lh, rr, rh = self._jit_append_many(
                c.logs.rows, c.logs.head, c.replicas.rows, c.replicas.head,
                jnp.asarray(rows1), jnp.asarray(counts))
            self.carry = c._replace(
                logs=c.logs._replace(rows=lr, head=lh),
                replicas=c.replicas._replace(rows=rr, head=rh))

    def global_record_stamp(self) -> int:
        """Monotone nonzero stamp for async rows (1 + supersteps run)."""
        return len(self.step_input_history) + 1

    def async_rows_since(self, flat_subtask: int, from_epoch: int) -> int:
        """How many async determinant rows this task's log holds in epochs
        >= ``from_epoch`` (host ledger — no device read)."""
        return sum(v for (f, e), v in self.async_counts.items()
                   if f == flat_subtask and e >= from_epoch)

    def install_replay_ledgers(self,
                               roll_gap: Dict[Tuple[int, int], int],
                               async_counts: Dict[Tuple[int, int], int]
                               ) -> None:
        """Merge externally re-derived roll-gap / async-row ledgers (the
        standby-host bootstrap derives them from mirrored determinant
        streams, possibly on a worker thread overlapped with replay).
        One atomic-enough install point: callers must invoke this BEFORE
        anything reads the ledgers — ``Failover._epoch_index`` reads
        ``roll_gap_async`` when rebuilding epoch start offsets, so the
        bootstrap joins its derivation thread at recovery's pre-patch
        join point, not after replay."""
        self.roll_gap_async.update(roll_gap)
        self.async_counts.update(async_counts)

    def first_step_inputs(self) -> BlockInputs:
        """Zeroed host-fed inputs with the FIRST-STEP block program's
        exact avals — what :func:`utils.compile_cache.
        aot_lower_first_step` lowers against (shape/dtype is all
        lowering reads; values never execute)."""
        k = self.block_steps
        return BlockInputs(times=jnp.zeros((k,), jnp.int32),
                           rng_bits=jnp.zeros((k,), jnp.int32),
                           epoch=jnp.zeros((), jnp.int32),
                           step0=jnp.zeros((), jnp.int32), feeds=())

    def fast_forward_host_rng(self, steps: int) -> None:
        """Reset the host RNG to a fresh seeded stream and consume
        exactly one per-step draw for ``steps`` supersteps — the rebuilt
        standby's stream position then matches the never-failed run's,
        so its continuation draws precisely what the original would
        have. Replay reproduces the prefix from RECORDED rng
        determinants without consuming the stream, hence the explicit
        fast-forward. Thread-safe only while nothing else draws (true
        during recovery: the replayer never touches the host RNG)."""
        self._rng = np.random.RandomState(self._seed)
        for _ in range(steps):
            self._rng.randint(0, 2 ** 31, dtype=np.int64)

    def service_factory(self, flat_subtask: int,
                        sidecar: "det.SidecarStore",
                        replay_feed=None, seed: int = 0, clock=None):
        """Per-task causal-service bundle (StreamingRuntimeContext analog:
        user host code gets time/random/external-call wrappers whose values
        record into this task's log and replay after failure)."""
        from clonos_tpu.causal.services import CausalServiceFactory
        return CausalServiceFactory(
            append=lambda d: self.append_async_determinant(flat_subtask, d),
            sidecar=sidecar, epoch_of=lambda: self.epoch_id,
            replay_feed=replay_feed, seed=seed, clock=clock)

    def lean_snapshot(self) -> LeanSnapshot:
        """The fence snapshot handed to the checkpoint coordinator. The
        pieces are DEEP-COPIED on device (one jitted program): the live
        carry's buffers are donated into subsequent block programs, so a
        reference-holding snapshot would be invalidated by the next
        block."""
        if not hasattr(self, "_jit_snap"):
            def _snap(c: JobCarry) -> LeanSnapshot:
                cp = lambda t: jax.tree_util.tree_map(
                    lambda x: jnp.asarray(x).copy(), t)
                return LeanSnapshot(
                    op_states=cp(c.op_states), edge_bufs=cp(c.edge_bufs),
                    rr_offsets=cp(c.rr_offsets),
                    record_counts=cp(c.record_counts),
                    log_heads=cp(c.logs.head),
                    ring_heads=tuple(cp(r.head) for r in c.out_rings))
            self._jit_snap = jax.jit(_snap)
        return self._jit_snap(self.carry)

    def capture_fence(self, with_window: bool = True) -> FenceHandles:
        """Capture the fence surface of the epoch that just closed as
        cheap device-side handles: ONE jitted deep-copy program (the
        fused health vector plus, when the audit seal needs it, the
        causal-log and ring window arrays), then a non-blocking
        ``copy_to_host_async`` on every output. The pipelined fence
        calls this before dispatching the next epoch's compute; the
        fence worker drains the handles into host arrays later without
        touching the live carry. Must run at the fence (right after
        ``run_epoch`` rolls), while ``epoch_starts[closed+1]`` is
        stamped and no new-epoch rows have landed."""
        key = bool(with_window)
        if not hasattr(self, "_jit_capture"):
            self._jit_capture = {}
        if key not in self._jit_capture:
            def _cap(c: JobCarry):
                cp = lambda t: jax.tree_util.tree_map(
                    lambda x: jnp.asarray(x).copy(), t)
                health = self._health_vector(c)
                if not key:
                    return health, None
                window = (
                    cp(c.logs.rows), cp(c.logs.head),
                    cp(c.logs.epoch_starts),
                    tuple((cp(el.keys), cp(el.values), cp(el.timestamps),
                           cp(el.valid), cp(el.epoch_starts), cp(el.head))
                          for el in c.out_rings))
                return health, window
            self._jit_capture[key] = jax.jit(_cap)
        health, window = self._jit_capture[key](self.carry)
        for leaf in jax.tree_util.tree_leaves((health, window)):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        return FenceHandles(self.epoch_id - 1, health, window,
                            dict(self.compiled.ring_index))

    def restore(self, carry_host, epoch_id: int) -> None:
        """Adopt a checkpointed carry (standby restore path; reference
        Task.dispatchStateToStandbyTask -> initializeState). The carry must
        be an epoch-``epoch_id``-boundary snapshot; the next step continues
        epoch ``epoch_id``. Leaves are deep-copied: the live carry is
        donated into later programs, and aliasing the stored checkpoint's
        buffers would delete it out of storage on the first step."""
        self.carry = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).copy(), carry_host)
        self.epoch_id = epoch_id
        self.step_in_epoch = 0

    # --- introspection -------------------------------------------------------

    def log_sizes(self) -> np.ndarray:
        return np.asarray(clog.size(self.carry.logs))

    def vertex_state(self, vertex_id: int):
        return jax.device_get(self.carry.op_states[vertex_id])
