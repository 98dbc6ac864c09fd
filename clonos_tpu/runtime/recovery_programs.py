"""The compiled programs of the failure path, each declared once.

Every device program a recovery (runtime/failover.py) dispatches is
built from a declaration that holds, beside its body, the arguments it
is warmed with: :meth:`RecoveryPrograms.warm` is a walk over the
declarations, after which the failure path meets only cached
executables. Built from the compiled job, the job graph and the recovery
chunk size: it never sees the runner.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from clonos_tpu.api.operators import TwoInputOperator
from clonos_tpu.api.records import empty as _zero_batch
from clonos_tpu.causal import determinant as det
from clonos_tpu.causal import log as clog
from clonos_tpu.causal import recovery as rec
from clonos_tpu.graph.job_graph import JobGraph
from clonos_tpu.inflight import log as ifl
from clonos_tpu.obs.scopes import scoped
from clonos_tpu.ops.histogram import over_mesh
from clonos_tpu.runtime.executor import DETS_PER_STEP, JobCarry


def _zeros(shape, dt=jnp.int32):
    return jnp.zeros(shape, dt)


def _z():
    return jnp.asarray(0, jnp.int32)


def _whole_shards(stack, mesh, axis: str) -> bool:
    """Whether every leaf of a stacked log leads with an axis the mesh
    divides: the rule table (parallel/distributed.py) shards it then."""
    return mesh is not None and all(
        x.ndim >= 1 and x.shape[0] > 0 and x.shape[0] % mesh.shape[axis] == 0
        for x in jax.tree_util.tree_leaves(stack))


def take_row(stack, r, mesh, axis: str):
    """Row ``r`` of every leaf of a stacked log (``replicas``: one leaf of
    it is 14 GiB at a deep sharing depth). On one device a dynamic index.
    Over a mesh the stack is sharded on that axis, and a dynamic index
    into it makes the partitioner gather the whole stack onto every
    chip: there each chip reads the row out of its own shard, or zeros
    where the row is another chip's, and one all-reduce of the row
    (4 MiB) hands it to all."""
    if not _whole_shards(stack, mesh, axis):
        return jax.tree_util.tree_map(lambda x: x[r], stack)
    from jax.sharding import PartitionSpec as P

    def local(stack, r):
        shard = jax.lax.axis_index(axis)

        def one(x):
            at = r - shard * x.shape[0]
            mine = (at >= 0) & (at < x.shape[0])
            row = x[jnp.clip(at, 0, x.shape[0] - 1)]
            return jax.lax.psum(jnp.where(mine, row, jnp.zeros_like(row)),
                                axis)
        return jax.tree_util.tree_map(one, stack)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)(stack, r)


@dataclasses.dataclass(frozen=True)
class Program:
    """One program of the failure path: the jitted body and
    ``warm_args``, the arguments it is warmed with as a function of the
    live carry (zeros of the shapes the failure path passes, pieces of
    the carry, another program's warmed result), None for a program
    that compiles on first use (the spill path's, which a ring-covered
    recovery never takes). ``lower_only``: warming lowers and compiles
    it against those arguments and does not run it — the programs that
    take the carry, or a piece of it, DONATED, where running would need
    a second, disposable carry (at the headline deployment 5.24 GiB
    next to the live 5.24 GiB, the all-lane route programs' ~2 GB each
    and the kill program's 1.5 GB of scratch, on a 16 GB chip).
    Lowering against the LIVE carry allocates nothing and donates
    nothing, and the executable it leaves in the jit's cache is the one
    the failure path dispatches."""

    fn: Any
    warm_args: Optional[Callable[[JobCarry], tuple]]
    lower_only: bool

    def __call__(self, *args):
        return self.fn(*args)

    def warm(self, carry: JobCarry):
        args = self.warm_args(carry)
        return (self.fn.lower(*args).compile() if self.lower_only
                else self.fn(*args))


def _program(kind: str, donate=()):
    """A method that declares the program ``kind``: called with the
    parameters that tell one instance of it from another (an edge, a
    vertex, a window length) it returns ``(body, warm_args)``; the
    decorated method returns the :class:`Program`, built once per
    ``(kind, *parameters)``."""
    def deco(declare):
        @functools.wraps(declare)
        def get(self, *params) -> Program:
            key = (kind,) + params
            p = self._rjit.get(key)
            if p is None:
                with self._rjit_lock:
                    p = self._rjit.get(key)
                    if p is None:
                        body, warm_args = declare(self, *params)
                        c = self.compiled
                        p = self._rjit[key] = Program(
                            jax.jit(over_mesh(body, c.mesh, c.task_axis),
                                    donate_argnums=donate),
                            warm_args, bool(donate))
            return p
        return get
    return deco


class RecoveryPrograms:
    """The recovery programs of one compiled job and their cache."""

    #: replica rows one call of the rebuild program copies: its scratch
    #: is this many log rows, not the whole replica set (which, gathered
    #: beside the carry, does not fit the chip once a job is deep)
    REPLICA_COPY_ROWS = 64

    def __init__(self, compiled, job: JobGraph, chunk_steps: int):
        self.compiled = compiled
        self.job = job
        #: recovery chunk size: larger than the live block trades a bigger
        #: prewarm compile for fewer per-chunk dispatches on the failure
        #: path.
        self.chunk = chunk_steps
        #: compiled recovery programs, keyed by (kind, params) — populated
        #: lazily and by warm() (warm standby: no XLA compile in the
        #: failure path).
        self._rjit: Dict[Any, Program] = {}
        self._rjit_lock = threading.Lock()

    def pad_steps(self) -> int:
        ch = self.chunk
        return -(-self.compiled.inflight_ring_steps // ch) * ch

    def _replica(self, replicas, r):
        """One replica log of the stack (:func:`take_row`)."""
        return take_row(replicas, r, self.compiled.mesh,
                        self.compiled.task_axis)

    # --- determinant fetch ---------------------------------------------------

    @_program("fetch")
    def fetch(self):
        cap = self.compiled.log_capacity
        return (lambda replicas, r, from_epoch: clog.get_determinants(
                    self._replica(replicas, r), from_epoch, cap),
                lambda c: (c.replicas, _z(), _z()))

    @_program("fetch_meta")
    def fetch_meta(self, h: int):
        """(count, start) of every holder's response in one device call —
        holders are bit-identical replicas by construction, so the host
        merge reduces to verifying the counts agree and pulling ONE body."""
        cap = self.compiled.log_capacity

        def f(replicas, rs, from_epoch):
            def one(r):
                rep_one = jax.tree_util.tree_map(lambda x: x[r], replicas)
                off = clog.epoch_start_offset(rep_one, from_epoch)
                cnt = jnp.clip(rep_one.head - off, 0, cap)
                return jnp.stack([cnt, off])
            return jax.vmap(one)(rs)          # [h, 2]
        return f, lambda c: (c.replicas, _zeros((h,)), _z())

    @_program("device_parse")
    def device_parse(self):
        """Parse a consistent replica's determinant stream ON DEVICE:
        locate the per-step sync anchors, extract the time/rng/expected
        lanes (padded to the replayer's fixed stream length), and report
        whether the stream is 'clean' (pure sync rows, exact layout).
        Only ~16 bytes of metadata cross the host link — the multi-MB
        log body stays on device (it IS the replica; the restore path
        copies it device-side too). Reference contrast: the JVM replayer
        walks the byte log on-heap (LogReplayerImpl.java:36-157)."""
        cap = self.compiled.log_capacity
        maxn = self.pad_steps()
        k = DETS_PER_STEP

        def f(replicas, r, from_epoch):
            buf, count, start = clog.get_determinants(
                self._replica(replicas, r), from_epoch, cap)
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
        return f, lambda c: (c.replicas, _z(), _z())

    # --- input reconstruction ------------------------------------------------

    @_program("ring_bounds")
    def ring_bounds(self):
        """Device [R, 2] (tail, head) of every in-flight ring — dispatch
        only; a recovery folds the transfer into its packed reads."""
        return (lambda rings: jnp.stack(
                    [jnp.stack([el.tail, el.head]) for el in rings]),
                lambda c: (c.out_rings,))

    @_program("ring_chunk")
    def ring_chunk(self, ri: int, m: int):
        return (lambda el, start: ifl.slice_steps(el, start, m)), None

    def _ring_of(self, eidx: int, c: JobCarry):
        return c.out_rings[self.compiled.ring_index[self.job.edges[eidx].src]]

    @_program("route_chunk")
    def route_chunk(self, eidx: int, m: int, all_lanes: bool):
        """Read + route one [m]-step window of edge ``eidx``'s producer
        ring — one program with the loop state (window start, leading
        skip, rebalance offset, remaining needed steps) carried ON
        DEVICE, so a chunk costs no host→device put of its own.

        Two variants, both warmed:
        - fused (``all_lanes`` False): the consumer's lane is selected
          INSIDE the program. Crucial for the single-failure case: XLA
          then scatters only that lane's rows (a general scatter runs
          ~row-at-a-time on TPU, so materializing all P lanes costs ~P
          times more).
        - ``all_lanes``: the full [m, P, cap] routed block — the routing
          is consumer-independent, so a connected multi-subtask failure
          routes each window ONCE and lane-selects per consumer (the
          reference re-serves the in-flight log per requesting channel;
          here the exchange is the expensive part and it is shared). It
          is warmed as :meth:`lane_select`'s argument.

        Replay windows are UNIFORM: every window is m steps, the first
        starting one slot before the fence (that dead slot is masked by
        ``lead`` and later replaced by the checkpointed edge buffer) —
        one compiled program serves every chunk instead of a first-chunk
        (m-1) shape variant doubling the prewarm. ``need_left`` masks
        steps past the replay range invalid (the replay-padding
        contract); ``lead`` masks the leading dead slot of window 0."""
        f = self._route_program(eidx, m, all_lanes, lambda el, start:
                                ifl.slice_steps_at(el, start, m))
        return f, lambda c: ((self._ring_of(eidx, c),)
                             + (_z(),) * (4 if all_lanes else 5))

    def _route_program(self, eidx: int, m: int, all_lanes: bool, read):
        """The window ``read(source, start)`` yields, routed, with the
        loop state advanced past it."""
        body = self._route_body(eidx, m)
        if all_lanes:
            def f(src, start, rr0, need_left, lead):
                routed, cnt = body(read(src, start), None, rr0, need_left,
                                   lead)
                return (routed, start + m, rr0 + cnt, need_left - m,
                        jnp.zeros_like(lead))
        else:
            def f(src, start, sub, rr0, need_left, lead):
                lane, cnt = body(read(src, start), sub, rr0, need_left,
                                 lead)
                return (lane, start + m, rr0 + cnt, need_left - m,
                        jnp.zeros_like(lead))
        return f

    @_program("lane_select")
    def lane_select(self, eidx: int, m: int):
        """Select one consumer lane of a routed [m, P, cap] block."""
        return (lambda routed, sub: jax.tree_util.tree_map(
                    lambda x: x[:, sub], routed),
                lambda c: (self.route_chunk(eidx, m, True).warm(c)[0], _z()))

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
        compiled = self.compiled

        def body(raw, sub, rr0, need_left, lead):
            need = jnp.clip(need_left, 0, m)
            idx = jnp.arange(m, dtype=jnp.int32)
            live = (idx >= lead) & (idx < need)
            raw = raw._replace(valid=raw.valid & live[:, None, None])
            r, _ = compiled.route_edge(eidx, raw, rr0, lane=sub)
            return r, raw.count().sum()
        return scoped("exchange")(body)

    @_program("route_raw")
    def route_raw(self, eidx: int, m: int, all_lanes: bool):
        """Spill-path twin of :meth:`route_chunk`: routes a
        host-assembled raw chunk instead of reading the device ring,
        advancing the same device-carried loop state."""
        return self._route_program(eidx, m, all_lanes,
                                   lambda raw, start: raw), None

    @_program("first_chunk")
    def first_chunk(self, eidx: int):
        """Replace the first window's dead leading slot with the
        checkpointed depth-1 edge buffer (replay step 0 consumes it)."""
        cap = self.job.edges[eidx].capacity
        return (lambda buf_sub, routed: jax.tree_util.tree_map(
                    lambda a, b: b.at[0].set(a[0]), buf_sub, routed),
                lambda c: (_zero_batch((1, cap)),
                           _zero_batch((self.chunk, cap))))

    # --- replay --------------------------------------------------------------

    def replayer(self, vid: int, sub: int) -> rec.LogReplayer:
        """Standby replay program for (vertex, subtask); compiled programs
        are cached on the operator so repeated failures (and the warm-up)
        share them."""
        v = self.job.vertices[vid]
        compiled = self.compiled
        slot_keys = compiled.consumer_slot_keys(vid)
        return rec.LogReplayer(
            v.operator, v.parallelism, vertex_name=v.name,
            block_steps=self.chunk,
            in_slot_keys=(slot_keys[sub:sub + 1]
                          if slot_keys is not None else None),
            pad_steps=compiled.inflight_ring_steps,
            mesh=compiled.mesh, task_axis=compiled.task_axis)

    def _state0(self, vid: int, c: JobCarry):
        return jax.tree_util.tree_map(lambda x: x[0][None], c.op_states[vid])

    def _warm_replayer(self, vid: int, sub: int, c: JobCarry) -> None:
        """The replayer's block program at the chunk's shape, and its
        time/rng slice at the pad-fixed stream length (the shape every
        failure uses; see LogReplayer.pad_steps)."""
        ch, job = self.chunk, self.job
        in_edges = job.in_edges(vid)
        chunk0 = _zero_batch((ch, job.edges[in_edges[0]].capacity if in_edges
                              else self.compiled.vertex_out_capacity(vid)))
        if isinstance(job.vertices[vid].operator, TwoInputOperator):
            chunk0 = (chunk0,
                      _zero_batch((ch, job.edges[in_edges[1]].capacity)))
        rp = self.replayer(vid, sub)
        rp._jit_block(self._state0(vid, c), chunk0, _zeros((ch,)),
                      _zeros((ch,)), jnp.asarray(sub, jnp.int32),
                      jnp.zeros((), jnp.int32))
        rp._jit_tslice(_zeros((rp.pad_steps or ch,)), _z())

    # --- kill, log restore, graft --------------------------------------------

    @_program("inject", donate=(0,))
    def inject(self, vid: int):
        """One fused kill program per vertex class (eager per-array
        zeroing would copy the carry once per touched leaf)."""
        compiled = self.compiled
        nr = compiled.plan.num_replicas

        def f(carry, sub, flat, held_idx):
            fresh = clog.create(compiled.log_capacity, compiled.max_epochs)
            ops = list(carry.op_states)
            ops[vid] = jax.tree_util.tree_map(
                lambda x: x.at[sub].set(jnp.zeros_like(x[sub])), ops[vid])
            logs = jax.tree_util.tree_map(
                lambda s, fr: s.at[flat].set(fr), carry.logs, fresh)
            replicas = carry.replicas
            if nr > 0 and _whole_shards(replicas, compiled.mesh,
                                        compiled.task_axis):
                # A scatter into the sharded stack would gather it whole
                # onto every chip (take_row); a select touches a chip's
                # own shard only.
                held = jnp.zeros((nr,), jnp.bool_).at[held_idx].set(
                    True, mode="drop")
                replicas = jax.tree_util.tree_map(
                    lambda s, fr: jnp.where(
                        held.reshape((nr,) + (1,) * fr.ndim), fr, s),
                    replicas, fresh)
            elif nr > 0:
                replicas = jax.tree_util.tree_map(
                    lambda s, fr: s.at[held_idx].set(
                        jnp.broadcast_to(fr, held_idx.shape + fr.shape),
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
        nrp = max(nr, 1)
        return f, lambda c: (c, _z(), _z(), jnp.full((nrp,), nrp, jnp.int32))

    @_program("replica_copy", donate=(0,))
    def replica_copy(self):
        """``replicas[ri] = logs[oi]`` for ``REPLICA_COPY_ROWS`` pairs
        (``ri`` past the end: no row), in place on the donated replicas."""
        n = self.REPLICA_COPY_ROWS
        return (lambda replicas, logs, ri, oi: jax.tree_util.tree_map(
                    lambda s, l: s.at[ri].set(l[oi], mode="drop"),
                    replicas, logs),
                lambda c: (c.replicas, c.logs, _zeros((n,)), _zeros((n,))))

    def _epoch_index0(self):
        me = self.compiled.max_epochs
        return _zeros((me,)), _zeros((me,), jnp.bool_), _z(), _z()

    @_program("log_append")
    def log_restore(self):
        cap, me = self.compiled.log_capacity, self.compiled.max_epochs

        def f(rows_chunk, count, state):
            return clog.append(state, rows_chunk, count)
        return f, lambda c: (
            _zeros((self.chunk * DETS_PER_STEP, det.NUM_LANES)), _z(),
            clog.create(cap, me))

    @_program("log_restore_replica")
    def log_restore_from_replica(self):
        """Rebuild a failed task's log row ON DEVICE from a surviving
        replica: the replayed determinant stream was verified equal to the
        recovered one, so the replica's bytes ARE the restored log — no
        host round-trip of the rows."""
        cap = self.compiled.log_capacity
        me = self.compiled.max_epochs

        def f(replicas, r, from_epoch, used, ck_head,
              epoch_offs, epoch_mask, latest, base):
            buf, _cnt, _start = clog.get_determinants(
                self._replica(replicas, r), from_epoch, cap)
            st = clog.create(cap, me)
            st = st._replace(head=ck_head, tail=ck_head)
            st = clog.append(st, buf, used)
            return st._replace(
                epoch_starts=jnp.where(epoch_mask, epoch_offs,
                                       st.epoch_starts),
                latest_epoch=jnp.maximum(st.latest_epoch, latest),
                epoch_base=jnp.maximum(st.epoch_base, base))
        return f, lambda c: ((c.replicas,) + (_z(),) * 4
                             + self._epoch_index0())

    @_program("log_finalize")
    def log_finalize(self):
        def f(state, epoch_offs, epoch_mask, latest, base):
            starts = jnp.where(epoch_mask, epoch_offs, state.epoch_starts)
            return state._replace(
                epoch_starts=starts,
                latest_epoch=jnp.maximum(state.latest_epoch, latest),
                epoch_base=jnp.maximum(state.epoch_base, base))
        return f, lambda c: ((self.log_restore().warm(c),)
                             + self._epoch_index0())

    # Donated: an un-donated graft copies the whole multi-GB carry
    # (rings included) per failed subtask, thrashing the allocator.
    @_program("graft", donate=(0,))
    def graft(self, vid: int):
        def f(carry, new_state, restored_log, sub, flat, rc):
            ops = list(carry.op_states)
            ops[vid] = jax.tree_util.tree_map(
                lambda live_x, new_x: live_x.at[sub].set(new_x[0]),
                ops[vid], new_state)
            logs = jax.tree_util.tree_map(
                lambda s, r: s.at[flat].set(r), carry.logs, restored_log)
            return carry._replace(
                op_states=tuple(ops), logs=logs,
                record_counts=carry.record_counts.at[flat].set(rc))
        return f, lambda c: (c, self._state0(vid, c),
                             self.log_restore().warm(c), _z(), _z(), _z())

    @_program("ring_write", donate=(0,))
    def ring_write(self, ri: int, m: int):
        """Write an [m, cap] replayed output chunk into ring ``ri`` at
        steps [base, base+m), keeping only steps in [keep_from, hi);
        returns (ring, base + m) so the loop cursor stays on device."""
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
        cap = self.compiled.vertex_out_capacity(
            self.compiled.ring_vertices[ri])
        return f, lambda c: ((c.out_rings[ri], _zero_batch((m, cap)))
                             + (_z(),) * 4)

    # --- the warm-up ---------------------------------------------------------

    def warm(self, carry: JobCarry) -> None:
        """Compile every program a recovery of this job dispatches, at
        the shapes it dispatches them with: the job-wide ones one after
        another, then each vertex's (its replay program per subtask
        where slot keys specialize it) through four threads — XLA
        compilations of distinct programs proceed in parallel, and the
        executions they also trigger are tiny and serialize on the
        device queue."""
        c, job, ch = self.compiled, self.job, self.chunk
        shared = []
        if c.plan.num_replicas > 0:
            holders = collections.Counter(o for o, _h in c.plan.pairs)
            shared += [self.fetch(), self.device_parse()]
            shared += [self.fetch_meta(h)
                       for h in sorted(set(holders.values()))]
            shared += [self.log_restore_from_replica(), self.replica_copy()]
        if carry.out_rings:
            shared.append(self.ring_bounds())
        for p in shared + [self.log_restore(), self.log_finalize()]:
            p.warm(carry)
        jobs = []
        for v in job.vertices:
            vid = v.vertex_id
            for eidx in job.in_edges(vid):
                jobs += [self.route_chunk(eidx, ch, False).warm,
                         self.lane_select(eidx, ch).warm,
                         self.first_chunk(eidx).warm]
            for sub in (range(v.parallelism)
                        if c.consumer_slot_keys(vid) is not None else [0]):
                jobs.append(functools.partial(self._warm_replayer, vid, sub))
            jobs += [self.graft(vid).warm, self.inject(vid).warm]
            if vid in c.ring_index:
                jobs.append(self.ring_write(c.ring_index[vid], ch).warm)
        with ThreadPoolExecutor(max_workers=4) as pool:
            for _ in pool.map(lambda j: j(carry), jobs):
                pass
