"""Multi-host distributed backend.

Capability parity with the reference's communication stack (SURVEY §2.6:
netty TCP data plane + akka control RPC + in-band task events), mapped to
the TPU fabric the way the design intends:

- **Data plane**: XLA collectives over ICI within a slice, DCN across
  slices — inserted by the SPMD partitioner from sharding annotations (the
  executor's ``with_sharding_constraint`` over the task axis), never
  hand-written sends. The exchange scatter (parallel/routing.py) lowers to
  all-to-alls; determinant replication's gather-by-owner lowers to
  all-gathers (causal/replication.py).
- **Control plane**: jax.distributed (gRPC) for process bootstrap +
  barriers; the ClusterRunner stays the single logical control plane
  (process 0), matching the reference's single JobMaster.
- **In-band events** (determinant/in-flight requests): host-level gRPC in
  the reference; here they are host-side array reads against the sharded
  carry — jax.device_get on an addressable shard — so the "request" rides
  the same runtime channel as everything else.

Under multi-host, every process runs the SAME jitted superstep over one
global mesh (SPMD); per-host Python only feeds host-local step inputs.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


@dataclasses.dataclass
class DistributedContext:
    process_id: int
    num_processes: int
    coordinator: Optional[str]

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> DistributedContext:
    """Bootstrap multi-host JAX (gRPC barrier at coordinator_address).
    No-op single-process context when no coordinator is given."""
    if coordinator_address is None:
        return DistributedContext(0, 1, None)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return DistributedContext(jax.process_index(), jax.process_count(),
                              coordinator_address)


def task_mesh(max_devices: Optional[int] = None,
              axis: str = "tasks") -> jax.sharding.Mesh:
    """One-axis mesh over all (global) devices: the subtask-deployment
    axis. Device order is JAX's global enumeration, so intra-host
    neighbors are ICI-adjacent and cross-host hops ride DCN — exchanges
    between adjacent subtasks stay on the faster links."""
    devs = jax.devices()
    if max_devices is not None:
        devs = devs[:max_devices]
    return jax.sharding.Mesh(np.asarray(devs), (axis,))


def hierarchical_mesh(axis_tasks: str = "tasks",
                      axis_hosts: str = "hosts") -> jax.sharding.Mesh:
    """Two-axis mesh [hosts, tasks-per-host] for layouts that want
    replication across hosts (e.g. standby redundancy on a different
    failure domain) while sharding subtasks within a host."""
    n_hosts = jax.process_count()
    devs = jax.devices()
    per_host = len(devs) // n_hosts
    grid = np.asarray(devs).reshape(n_hosts, per_host)
    return jax.sharding.Mesh(grid, (axis_hosts, axis_tasks))


# --- rule-driven carry partitioning -----------------------------------------
#
# The executor's JobCarry is a deep pytree whose leaves disagree about
# WHICH axis is the subtask axis: stacked causal logs / replicas lead
# with it ([L, cap, lanes]), in-flight ring tensors carry it second
# ([S, P, cap] — the leading axis is the ring step), round-robin cursors
# and ring scalars are control state that every shard must see. A single
# "shard the leading axis" heuristic therefore cannot express the
# deployment; these RULES can: ordered (regex over the '/'-joined leaf
# path, shard dim | None) pairs, first match wins, unmatched leaves
# replicate. The same table drives with_sharding_constraint inside the
# traced block program AND the explicit in/out shardings on the jitted
# entry points, so the two can never disagree.

#: (path regex, dim to shard along the task axis; None = replicate).
CARRY_PARTITION_RULES: Tuple[Tuple[str, Optional[int]], ...] = (
    # In-flight ring payload tensors are [ring_step, subtask, cap].
    (r"out_rings/\d+/(keys|values|timestamps|valid)$", 1),
    # Ring bookkeeping (head/tail/epoch index) is scalar control state.
    (r"out_rings/", None),
    # Stacked causal logs + determinant replicas lead with the task axis.
    (r"(^|/)(logs|replicas)/", 0),
    # Rebalance cursors are [1] scalars shared by the whole edge.
    (r"rr_offsets/", None),
    # Operator state / depth-1 edge buffers / record counts lead with
    # the (destination) subtask axis.
    (r"(^|/)(op_states|edge_bufs|record_counts)($|/)", 0),
    # The exchange's counters are per destination subtask, like the
    # edge's buffer.
    (r"(^|/)exchange/", 0),
)


def _path_str(path: Tuple[Any, ...]) -> str:
    """Render a tree_flatten_with_path key path as 'a/0/b' — attribute
    names for NamedTuple/dataclass fields, indices for sequences, keys
    for dicts — the namespace the partition-rule regexes match against."""
    parts = []
    for k in path:
        if hasattr(k, "name"):                 # GetAttrKey / DictKey-like
            parts.append(str(k.name))
        elif hasattr(k, "idx"):                # SequenceKey
            parts.append(str(k.idx))
        elif hasattr(k, "key"):                # DictKey / FlattenedIndexKey
            parts.append(str(k.key))
        else:                                  # pragma: no cover
            parts.append(str(k))
    return "/".join(parts)


def _spec_for_leaf(path_s: str, leaf: Any, n: int, axis: str,
                   rules: Sequence[Tuple[str, Optional[int]]]
                   ) -> PartitionSpec:
    """First matching rule decides the shard dim; a dim the leaf lacks or
    cannot split evenly over the ``n`` mesh devices degrades to
    replication (same guard the in-trace constraint applies, so explicit
    jit shardings and with_sharding_constraint always agree)."""
    ndim = getattr(leaf, "ndim", None)
    if ndim is None:
        ndim = np.ndim(leaf)
    shape = getattr(leaf, "shape", ())
    for pat, dim in rules:
        if re.search(pat, path_s):
            if dim is None or ndim <= dim or shape[dim] == 0 \
                    or shape[dim] % n != 0:
                return PartitionSpec()
            return PartitionSpec(*([None] * dim + [axis]))
    return PartitionSpec()


def infer_partition_spec(tree: Any, mesh: jax.sharding.Mesh,
                         axis: str = "tasks",
                         rules: Sequence[Tuple[str, Optional[int]]]
                         = CARRY_PARTITION_RULES) -> Any:
    """PartitionSpec pytree for ``tree`` (same structure), derived from
    the rule table over flattened leaf names. Scalars and indivisible
    leaves replicate."""
    n = mesh.shape[axis]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = [_spec_for_leaf(_path_str(p), x, n, axis, rules)
             for p, x in leaves]
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(tree: Any, mesh: jax.sharding.Mesh,
                    axis: str = "tasks",
                    rules: Sequence[Tuple[str, Optional[int]]]
                    = CARRY_PARTITION_RULES) -> Any:
    """NamedSharding pytree over ``mesh`` for ``tree`` — the form
    ``jax.jit``'s in/out_shardings and ``device_put`` take."""
    specs = infer_partition_spec(tree, mesh, axis=axis, rules=rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec))


def standby_device_order(mesh: jax.sharding.Mesh,
                         axis: str = "tasks") -> Sequence[int]:
    """Placement hint: standby replicas should restore onto devices
    *rotated by one host* relative to their primary, so a host loss never
    takes a primary and its standby together (the reference schedules
    standbys on different TaskManagers, RunStandbyTaskStrategy.java:186)."""
    n = mesh.shape[axis]
    per_host = max(1, n // max(jax.process_count(), 1))
    return [(i + per_host) % n for i in range(n)]


def standby_worker_order(num_workers: int) -> Sequence[int]:
    """Worker-process-level form of :func:`standby_device_order`, used by
    the slot-pool scheduler's anti-affinity rule: task group ``i``'s
    standby (the redeploy target when its primary worker dies) is the
    NEXT worker in registration order — a vertex's standby never shares a
    worker process with its primary, so one process loss cannot take
    both (RunStandbyTaskStrategy.java:186 placement)."""
    if num_workers < 1:
        raise ValueError("standby_worker_order: need at least one worker")
    return [(i + 1) % num_workers for i in range(num_workers)]
