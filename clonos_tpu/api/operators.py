"""Operator library: pure per-superstep batch transforms.

Capability analog of the reference's operator layer
(flink-streaming-java .../api/operators/AbstractStreamOperator.java,
StreamMap/StreamFilter, windowing/WindowOperator.java, StreamSource) —
re-imagined for TPU: an operator is a pair of pure functions

    init_state(parallelism)            -> state pytree, leading dim P
    process(state, batch, ctx)         -> (state, out_batch)

applied to a whole ``RecordBatch[P, B]`` per superstep. No per-record user
code: transforms are jnp expressions, keyed aggregation is scatter-add into
dense key tables, and windows fire on causal time carried in the step
context. Everything traces into one XLA program.

Time discipline (TPU-first): operators never read a clock. The current
processing time is a step *input* (``OpContext.time``) produced by the
causal time service — recorded as a TIMESTAMP determinant on the live path
and replayed from the log during recovery (reference
CausalTimeService.java:48-67). This makes every operator deterministic given
(state, batch, ctx).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from clonos_tpu.api.records import RecordBatch, empty, zero_invalid
from clonos_tpu.obs.scopes import scoped
from clonos_tpu.parallel import routing


class OpContext(NamedTuple):
    """Per-superstep inputs an operator may consume. All values are device
    scalars (or [P] vectors) fed by the executor — never host reads."""

    time: jnp.ndarray        # int32 scalar: causal processing time
    epoch: jnp.ndarray       # int32 scalar: current epoch id
    step: jnp.ndarray        # int32 scalar: superstep index within epoch
    rng_bits: jnp.ndarray    # int32 scalar: causal host-RNG draw for this step
    subtask: jnp.ndarray     # int32[P]: subtask indices (for vmapped ops)


class BlockContext(NamedTuple):
    """Step-batched context for :meth:`Operator.process_block`: the executor
    hands operators a whole block of K supersteps at once so their work
    compiles to a handful of large fused kernels instead of K small ones
    (the decisive TPU cost model — per-kernel launch dwarfs per-element
    work at stream batch sizes)."""

    times: jnp.ndarray       # int32[K]: causal time per superstep
    rng_bits: jnp.ndarray    # int32[K]: causal host-RNG draw per superstep
    epoch: jnp.ndarray       # int32 scalar: epoch id of the block
    step0: jnp.ndarray       # int32 scalar: global step index of block start
    subtask: jnp.ndarray     # int32[P]

    def at_step(self, k) -> OpContext:
        return OpContext(time=self.times[k], epoch=self.epoch,
                         step=self.step0 + jnp.asarray(k, jnp.int32),
                         rng_bits=self.rng_bits[k], subtask=self.subtask)


class Operator:
    """Base operator. Subclasses override ``init_state``/``process`` (the
    per-superstep semantics) and, for the hot path, ``process_block`` (the
    step-batched form, which must be bit-identical to scanning ``process``
    — tests/test_operators_block.py enforces this for the stock library)."""

    #: output batch capacity per subtask per superstep; None = same as input.
    out_capacity: Optional[int] = None

    #: Replay-padding contract: True iff running extra steps with
    #: all-invalid input batches and the last step's time/rng repeated
    #: leaves the operator state unchanged and emits only invalid records.
    #: Lets the replayer pad a partial tail block to the fixed block size
    #: (so warm standbys never compile on the failure path). Pure
    #: generators that advance state unconditionally (SyntheticSource)
    #: must set this False and accept one tail-shape compile instead.
    replay_pad_safe: bool = True

    #: Running-value contract: True iff every VALID output record carries
    #: the operator's updated keyed state for that record's key (Flink
    #: reduce semantics). Read replicas (runtime/serve.py) tail such
    #: operators to fence freshness by last-write-wins scatter of each
    #: sealed epoch's output ring — bit-identical to the owner's fence
    #: state by construction. Operators without the property fall back
    #: to checkpoint-only freshness on the read path.
    emits_running_value: bool = False

    #: Own-keys contract, first fact (what the planner may assume of an
    #: operator; ``CompiledJob._plan_edges`` defines "own keys" and is
    #: its only reader): True iff every VALID record a subtask emits
    #: from ``process_block`` carries the key of a valid record that
    #: subtask received, unchanged — for EVERY input. False where that
    #: cannot be shown (a map's function may rewrite keys; a source
    #: receives nothing); the vertex's out-edges are then planned from
    #: "any key anywhere".
    emits_received_keys: bool = False

    #: per-subtask int32 totals in operator state that the fence's one
    #: health read brings back, and the counter each feeds
    #: (``<counter>.<vertex name>``): ``((state key, counter), ...)``.
    #: A total is what a replay of the lane counts again, with one
    #: exception: ``lookup.dense_blocks`` says which branch a block of
    #: ALL subtasks took, which one replayed lane cannot know, so a
    #: recovered subtask's stands at its checkpoint's and the counter is
    #: fed no negative growth (``ClusterRunner._absorb_fence_health``)
    fence_totals: Tuple[Tuple[str, str], ...] = ()

    #: the state keys among ``fence_totals`` that count LOSSES the job's
    #: guarantees do not allow (a record refused, a row past a
    #: capacity): a non-zero one is an overflow message at the next
    #: fence (``LocalExecutor.overflow_messages``), in every run.
    fence_losses: Tuple[str, ...] = ()

    #: per-subtask int32 high-water marks in operator state that ride
    #: the same read, reduced by their maximum over the subtasks, and
    #: the counter each feeds (it only grows; the counter is fed its
    #: growth and reads the mark): ``((state key, counter), ...)``
    fence_peaks: Tuple[Tuple[str, str], ...] = ()

    #: columns a subtask holds of a table keyed over ``num_keys`` ids,
    #: where it holds a column only for the ids it owns (None: no such
    #: table). The planner binds them: ``CompiledJob._plan_edges`` works
    #: out which ids each subtask owns and ``init_carry`` hands them to
    #: :meth:`bind_own_columns`, so the binding is a leaf of the
    #: vertex's state and the operator object knows nothing of a plan.
    own_columns: Optional[int] = None

    def init_state(self, parallelism: int) -> Any:
        return ()

    def bind_own_columns(self, state: Any, cols: np.ndarray) -> Any:
        """``state`` with ``cols`` (int32 ``[P, own_columns]``: the ids
        subtask ``p`` owns, ascending, then :data:`NO_KEY`) bound."""
        raise NotImplementedError(
            f"{type(self).__name__} holds no own columns")

    def process(self, state: Any, batch: RecordBatch,
                ctx: OpContext) -> Tuple[Any, RecordBatch]:
        raise NotImplementedError

    def process_block(self, state: Any, batches: RecordBatch,
                      bctx: BlockContext) -> Tuple[Any, RecordBatch]:
        """Advance K supersteps at once. ``batches`` has leading dims
        ``[K, P, B]``; returns stacked outputs ``[K, P, out_cap]``.

        Default: ``lax.scan`` over :meth:`process` — always correct, pays
        per-step kernel costs; stock operators override with vectorized
        forms (prefix sums over the step axis)."""
        K = bctx.times.shape[0]

        def step(st, xs):
            b, k = xs
            return self.process(st, b, bctx.at_step(k))

        return jax.lax.scan(step, state,
                            (batches, jnp.arange(K, dtype=jnp.int32)))

    def static_out_keys(self) -> Optional[np.ndarray]:
        """The statically-known key of each output slot, or None when
        emission keys are dynamic. Dense-table emitters (window) return
        their key enumeration; the executor then replaces the downstream
        hash exchange with a compile-time gather plan
        (routing.StaticRoutePlan) — no sort, no scatter."""
        return None

    def static_clamp_keys(self) -> Optional[np.ndarray]:
        """Own-keys contract, second fact, for emitters that have
        :meth:`static_out_keys`: the keys whose columns a key outside
        the table is folded into, such that a subtask emits a valid
        record in slot ``i`` only if it received key
        ``static_out_keys()[i]`` or that key is listed here — for EVERY
        input (``process_block`` is what runs). Empty where out-of-range
        keys are dropped; None (the default) declares nothing, and every
        slot counts as live on every subtask."""
        return None

    def rescale_keyed_state(self, state: Any, new_parallelism: int,
                            num_key_groups: int) -> Any:
        """Remap checkpointed state to a DIFFERENT parallelism by key
        ownership (reference StateAssignmentOperation +
        KeyGroupRangeAssignment: state is split/merged along key-group
        ranges). Dense-table operators implement it as sum-then-remask:
        per-key rows are disjoint across old subtasks (each holds only
        its own keys: ``CompiledJob._plan_edges``), so the global table
        is the subtask sum and each new subtask keeps the keys the new
        assignment routes to it.
        Operators without a keyed rescaling story raise."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support rescaling")


def rescale_dense_table(table: jnp.ndarray, new_parallelism: int,
                        num_key_groups: int,
                        fill: int = 0) -> jnp.ndarray:
    """Remap a dense keyed table ``[P, ..., K]`` to ``new_parallelism``:
    sum over the old subtask axis (rows are disjoint by key ownership,
    "own keys" as ``CompiledJob._plan_edges`` defines it; a column that
    out-of-range keys were clamped into sums over the subtasks that
    received them) and keep, per new subtask, only the keys the new key-group
    assignment routes to it (``fill`` elsewhere — the operator's init
    value, what an untouched key holds)."""
    from clonos_tpu.parallel.routing import (key_group,
                                             subtask_for_key_group)
    nk = table.shape[-1]
    total = (table - fill).sum(axis=0) + fill
    kg = key_group(jnp.arange(nk, dtype=jnp.int32), num_key_groups)
    owner = subtask_for_key_group(kg, new_parallelism, num_key_groups)
    sub = jnp.arange(new_parallelism, dtype=jnp.int32)
    mask = (owner[None, :] == sub[:, None]).reshape(
        (new_parallelism,) + (1,) * (total.ndim - 1) + (nk,))
    return jnp.where(mask, total[None], fill)


class TwoInputOperator(Operator):
    """Base for vertices with two input edges (ConnectedStreams /
    TwoInputStreamOperator analog, flink-streaming-java
    .../api/operators/TwoInputStreamOperator.java).

    TPU-first note on ORDER determinants: the reference logs which channel
    each consumed buffer came from because its task threads race on input
    queues (CausalBufferOrderService.java:48). The lockstep superstep
    consumes BOTH inputs' pending batch every step, so the interleaving
    nondeterminism is structurally eliminated — ``process2`` receives both
    batches and any merge it performs is a pure function. The ORDER
    determinant still records the (degenerate) selection for wire/protocol
    parity."""

    def process2(self, state: Any, left: RecordBatch, right: RecordBatch,
                 ctx: OpContext) -> Tuple[Any, RecordBatch]:
        raise NotImplementedError

    def process(self, state, batch, ctx):
        raise TypeError("TwoInputOperator requires process2 with two inputs")

    def process_block(self, state: Any, batches: Tuple[RecordBatch,
                                                       RecordBatch],
                      bctx: BlockContext) -> Tuple[Any, RecordBatch]:
        """``batches`` is a (left, right) pair of ``[K, P, B]`` stacks."""
        K = bctx.times.shape[0]

        def step(st, xs):
            (l, r), k = xs
            return self.process2(st, l, r, bctx.at_step(k))

        return jax.lax.scan(step, state,
                            (batches, jnp.arange(K, dtype=jnp.int32)))


@dataclasses.dataclass
class MapOperator(Operator):
    """Elementwise transform: fn(keys, values, timestamps) -> same triple.
    (StreamMap equivalent; fn is a traced jnp expression, not per-record.)"""

    fn: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                 Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]

    def process(self, state, batch, ctx):
        k, v, t = self.fn(batch.keys, batch.values, batch.timestamps)
        return state, zero_invalid(RecordBatch(k, v, t, batch.valid))

    def process_block(self, state, batches, bctx):
        # Stateless elementwise fn: applies to the whole [K, P, B] stack.
        return self.process(state, batches, None)


@dataclasses.dataclass
class FilterOperator(Operator):
    """Keep records where pred(keys, values, timestamps) — mask update only;
    compaction happens at the next exchange (StreamFilter equivalent)."""

    pred: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]

    # a mask update: what stays valid keeps its key
    emits_received_keys = True

    def process(self, state, batch, ctx):
        keep = batch.valid & self.pred(batch.keys, batch.values, batch.timestamps)
        return state, zero_invalid(batch._replace(valid=keep))

    def process_block(self, state, batches, bctx):
        return self.process(state, batches, None)


@dataclasses.dataclass
class SyntheticSource(Operator):
    """On-device record generator (benchmark source; StreamSource analog).

    Emits ``batch_size`` records per superstep per subtask with keys drawn
    from ``[0, vocab)`` by a counter hash — deterministic given the carried
    sequence counter, so replay regenerates identical data without logging
    the payloads (the in-flight log covers the *downstream* loss case).
    """

    vocab: int
    batch_size: int
    rate_limit: Optional[int] = None  # records/superstep cap (None = full)

    #: generates unconditionally per step — padding would advance ``seq``.
    replay_pad_safe = False

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.batch_size

    def init_state(self, parallelism: int):
        return {"seq": jnp.zeros((parallelism,), jnp.int32)}

    #: key-mix stride; must exceed any parallelism so (seq, subtask) pairs
    #: stay unique — and must NOT depend on the state's leading dim, which
    #: is 1 when a lone subtask is being replayed after a failure.
    SUBTASK_STRIDE = 1 << 10

    def process(self, state, batch, ctx):
        p = state["seq"].shape[0]
        b = self.batch_size
        lane = jnp.arange(b, dtype=jnp.int32)
        seq = state["seq"][:, None] + lane[None, :]              # [P, B]
        mix = seq * self.SUBTASK_STRIDE + ctx.subtask[:, None]   # global unique
        keys = (routing.hash32(mix) % jnp.uint32(self.vocab)).astype(jnp.int32)
        n = b if self.rate_limit is None else min(b, self.rate_limit)
        valid = jnp.broadcast_to(lane < n, (p, b))
        ts = jnp.broadcast_to(ctx.time, (p, b)).astype(jnp.int32)
        out = zero_invalid(RecordBatch(keys, jnp.ones((p, b), jnp.int32), ts, valid))
        return {"seq": state["seq"] + n}, out

    def process_block(self, state, batches, bctx):
        # The sequence counter advances by exactly n per step, so the whole
        # block's keys are a closed form of (seq0, step index) — one kernel.
        p = state["seq"].shape[0]
        b = self.batch_size
        K = bctx.times.shape[0]
        n = b if self.rate_limit is None else min(b, self.rate_limit)
        lane = jnp.arange(b, dtype=jnp.int32)
        step = jnp.arange(K, dtype=jnp.int32)
        seq = (state["seq"][None, :, None] + step[:, None, None] * n
               + lane[None, None, :])                            # [K, P, B]
        mix = seq * self.SUBTASK_STRIDE + bctx.subtask[None, :, None]
        keys = (routing.hash32(mix) % jnp.uint32(self.vocab)).astype(jnp.int32)
        valid = jnp.broadcast_to(lane[None, None, :] < n, (K, p, b))
        ts = jnp.broadcast_to(bctx.times[:, None, None], (K, p, b)
                              ).astype(jnp.int32)
        out = zero_invalid(RecordBatch(keys, jnp.ones((K, p, b), jnp.int32),
                                       ts, valid))
        return {"seq": state["seq"] + n * K}, out


#: widest ``num_keys`` whose running values ``KeyedReduceOperator
#: .process_block`` reads back by a compare over the key lanes; a wider
#: table is read by a gather. The gather costs ~10-13 ns a slot whatever
#: the table, the compare ~0.9-2.2 ps a slot a key lane: alone on a v5e at
#: ``keys s32[1024, 8, 512]``, ms a call at 200 / 1,024 / 4,096 / 8,192 /
#: 16,384 keys, the gather 43.6 / 43.8 / 54.2 / 54.4 / 54.7, the compare
#: 1.85 / 4.13 / 15.0 / 32.7 / 67.2 — they cross near 13,400 keys
_DENSE_READBACK_KEYS = 8192


def _read_running(acc_end: jnp.ndarray, keys: jnp.ndarray) -> jnp.ndarray:
    """``out[k, p, b] = acc_end[k, p, clip(keys[k, p, b], 0, nk - 1)]``:
    every slot's running value out of its (step, subtask) row's table, a
    key past the table reading the last key's as the step form's
    ``new_acc[b.keys]`` does. Up to ``_DENSE_READBACK_KEYS`` key lanes
    with no gather: one compare, one select and one sum over the key
    lanes, which fuse (no ``[K, P, B, nk]`` array exists, and no scratch
    at any width); one lane matches, so the sum is that lane's value bit
    for bit."""
    K, p, nk = acc_end.shape
    if nk > _DENSE_READBACK_KEYS:
        return jnp.take_along_axis(
            acc_end.reshape(K * p, nk), keys.reshape(K * p, -1), axis=1,
            mode="clip").reshape(keys.shape)
    hit = (jnp.clip(keys, 0, nk - 1)[..., None]
           == jnp.arange(nk, dtype=jnp.int32))
    return jnp.sum(jnp.where(hit, acc_end[:, :, None, :], 0), axis=-1)


@dataclasses.dataclass
class KeyedReduceOperator(Operator):
    """Running keyed reduce over a dense key table (keyed-state analog of the
    reference's HeapKeyedStateBackend ValueState + ReduceFunction).

    State is ``acc[P, num_keys]``; behind a HASH exchange each subtask holds
    only its own keys (``CompiledJob._plan_edges`` defines the property and
    plans from it), so tables never conflict. Emits the updated running
    value for every input record (Flink reduce semantics).

    A key is in ``[0, num_keys)``. A valid record with a key past the
    table adds to no sum and leaves with the LAST key's running value, in
    the step form (``new_acc[b.keys]`` clamps) and in both read-backs of
    the block form (:func:`_read_running`) alike.
    """

    num_keys: int
    reduce_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] = jnp.add
    init_value: int = 0
    # out_vals = new_acc[b.keys] for valid records below — the running
    # value — so read replicas can tail this operator's output rings.
    emits_running_value = True
    # The output is the input batch with its values replaced: keys, stamps
    # and validity pass through (a key past ``num_keys`` too; only its sum
    # is not kept).
    emits_received_keys = True

    def init_state(self, parallelism: int):
        return {"acc": jnp.full((parallelism, self.num_keys), self.init_value,
                                jnp.int32)}

    def rescale_keyed_state(self, state, new_parallelism, num_key_groups):
        return {"acc": rescale_dense_table(
            state["acc"], new_parallelism, num_key_groups,
            fill=self.init_value)}

    def process(self, state, batch, ctx):
        def one(acc, b: RecordBatch):
            # Sequential fold per slot is wrong for non-commutative fns under
            # scatter; restrict to associative+commutative reduce_fn (doc'd).
            contrib = jnp.zeros_like(acc).at[b.keys].add(
                jnp.where(b.valid, b.values, 0), mode="drop")
            # A key is touched iff any VALID record carries it; scatter-add
            # of the mask (scatter-set with duplicate keys is unordered —
            # an invalid record zeroed to key 0 must not untouch key 0).
            touched = jnp.zeros(acc.shape, jnp.int32).at[b.keys].add(
                b.valid.astype(jnp.int32), mode="drop") > 0
            new_acc = jnp.where(touched, self.reduce_fn(acc, contrib), acc)
            out_vals = jnp.where(b.valid, new_acc[b.keys], 0)
            return new_acc, zero_invalid(b._replace(values=out_vals))
        new_acc, out = jax.vmap(one)(state["acc"], batch)
        return {"acc": new_acc}, out

    def process_block(self, state, batches, bctx):
        # Vectorized form is exact only for the additive default (the prefix
        # over steps must distribute); other reduce_fns take the scan path.
        if self.reduce_fn is not jnp.add:
            return super().process_block(state, batches, bctx)
        from clonos_tpu.ops.histogram import keyed_hist
        acc0 = state["acc"]                               # [P, nk]
        contrib, _ = keyed_hist(batches.keys, batches.values,
                                batches.valid, self.num_keys,
                                want_counts=False)        # [K, P, nk]
        with jax.named_scope("segsum"):
            cum = jnp.cumsum(contrib, axis=0)             # inclusive prefix
            acc_end = acc0[None] + cum                    # [K, P, nk]
        with jax.named_scope("readback"):
            out_vals = jnp.where(batches.valid,
                                 _read_running(acc_end, batches.keys), 0)
        return ({"acc": acc0 + cum[-1]},
                zero_invalid(batches._replace(values=out_vals)))

    def process_block_static_keys(self, state, batches, bctx,
                                  slot_keys: np.ndarray):
        """Fast path when the input arrives over a StaticRoutePlan edge:
        ``slot_keys[p, b]`` is the compile-time key of input slot (p, b)
        (-1 = never mapped). The per-step histogram then needs no dynamic
        scatter — each key's contributions sit at statically-known slots,
        so ``contrib`` is a handful of static gathers (one per producer
        occurrence), and emission is a static gather back. Bit-identical
        to :meth:`process_block` (integer adds in the same association).
        """
        if self.reduce_fn is not jnp.add:
            return self.process_block(state, batches, bctx)
        K, p, B = batches.keys.shape
        nk = self.num_keys
        sk = np.asarray(slot_keys)
        if sk.shape != (p, B):
            raise ValueError(f"slot_keys shape {sk.shape} != {(p, B)}")
        # Static inverted index: slots carrying key n on subtask q.
        occ = [[[] for _ in range(nk)] for _ in range(p)]
        for q in range(p):
            for b in range(B):
                k = int(sk[q, b])
                if 0 <= k < nk:
                    occ[q][k].append(b)
        S = max((len(o) for row in occ for o in row), default=0)
        S = max(S, 1)
        idx = np.full((p, nk, S), B, np.int32)        # B = zero-pad column
        for q in range(p):
            for n in range(nk):
                for s, b in enumerate(occ[q][n]):
                    idx[q, n, s] = b
        vals = jnp.where(batches.valid, batches.values, 0)
        vpad = jnp.pad(vals, ((0, 0), (0, 0), (0, 1)))    # [K, P, B+1]
        pp = np.arange(p)[:, None]
        contrib = vpad[:, pp, idx[:, :, 0]]
        for s in range(1, S):
            contrib = contrib + vpad[:, pp, idx[:, :, s]]  # [K, P, nk]
        acc0 = state["acc"]
        with jax.named_scope("segsum"):
            cum = jnp.cumsum(contrib, axis=0)
            acc_end = acc0[None] + cum
        key_of_slot = np.clip(sk, 0, nk - 1)
        with jax.named_scope("readback"):
            out_vals = jnp.where(
                batches.valid,
                acc_end[:, pp, key_of_slot], 0)
        return ({"acc": acc0 + cum[-1]},
                zero_invalid(batches._replace(values=out_vals)))


@dataclasses.dataclass
class TumblingWindowCountOperator(Operator):
    """Tumbling processing-time windowed count/sum per key
    (WindowOperator + aggregate equivalent; the SocketWindowWordCount shape).

    ``window_size`` is in causal-time units. State: dense ``acc[P, K]`` and
    the current window id per subtask. When ``ctx.time`` crosses a window
    boundary, emits one record per key with a nonzero accumulator
    (key, aggregate, window_end_time) and resets. Emission capacity is
    ``num_keys`` (dense scan of the table — static shape).
    """

    num_keys: int
    window_size: int

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.num_keys

    def init_state(self, parallelism: int):
        return {
            "acc": jnp.zeros((parallelism, self.num_keys), jnp.int32),
            "window": jnp.zeros((parallelism,), jnp.int32),
        }

    def process(self, state, batch, ctx):
        w_now = (ctx.time // self.window_size).astype(jnp.int32)

        def one(acc, window, b: RecordBatch):
            fire = w_now > window
            window_end = (window + 1) * self.window_size
            keys = jnp.arange(self.num_keys, dtype=jnp.int32)
            out = RecordBatch(
                keys=keys,
                values=acc,
                timestamps=jnp.full((self.num_keys,), 1, jnp.int32) * window_end,
                valid=fire & (acc != 0),
            )
            acc = jnp.where(fire, 0, acc)
            # Accumulate this superstep's records into the (possibly fresh)
            # window.
            acc = acc.at[b.keys].add(jnp.where(b.valid, b.values, 0), mode="drop")
            window = jnp.where(fire, w_now, window)
            return acc, window, zero_invalid(out)

        acc, window, out = jax.vmap(one)(state["acc"], state["window"], batch)
        return {"acc": acc, "window": window}, out

    def process_block(self, state, batches, bctx):
        # Step-batched form: window-id evolution is a running max of the
        # per-step window ids; accumulator segments between fires are
        # differences of an inclusive prefix sum; the emission at a fire
        # step is the segment ending at the previous step. All exact int32.
        K, p, _ = batches.keys.shape
        nk = self.num_keys
        size = self.window_size
        w_now = (bctx.times // size).astype(jnp.int32)            # [K]
        w0 = state["window"]                                      # [P]
        acc0 = state["acc"]                                       # [P, nk]
        rm = jax.lax.associative_scan(jnp.maximum, w_now)         # incl [K]
        neg_inf = jnp.asarray(-(2 ** 31) + 1, jnp.int32)
        rm_excl = jnp.concatenate([neg_inf[None], rm[:-1]])
        window_pre = jnp.maximum(w0[None, :], rm_excl[:, None])   # [K, P]
        fire = w_now[:, None] > window_pre                        # [K, P]

        from clonos_tpu.ops.histogram import keyed_hist
        contrib, _ = keyed_hist(batches.keys, batches.values,
                                batches.valid, nk,
                                want_counts=False)                # [K, P, nk]
        from clonos_tpu.ops.matops import onehot_gather_rows
        with jax.named_scope("segsum"):
            cum = jnp.cumsum(contrib, axis=0)                     # [K, P, nk]
            cum_excl = cum - contrib
            kidx = jnp.arange(K, dtype=jnp.int32)[:, None]
            lf = jax.lax.associative_scan(                        # [K, P]
                jnp.maximum, jnp.where(fire, kidx, -1), axis=0)
            seg_base = onehot_gather_rows(cum_excl, jnp.clip(lf, 0, K - 1))
            acc_end = jnp.where(lf[:, :, None] >= 0, cum - seg_base,
                                acc0[None] + cum)                 # [K, P, nk]
            emit = jnp.concatenate([acc0[None], acc_end[:-1]], axis=0)

        keys = jnp.broadcast_to(jnp.arange(nk, dtype=jnp.int32)[None, None, :],
                                (K, p, nk))
        window_end = (window_pre + 1) * size                      # [K, P]
        out = zero_invalid(RecordBatch(
            keys=keys, values=emit,
            timestamps=jnp.broadcast_to(window_end[:, :, None], (K, p, nk)
                                        ).astype(jnp.int32),
            valid=fire[:, :, None] & (emit != 0)))
        return ({"acc": acc_end[-1],
                 "window": jnp.maximum(w0, rm[-1])}, out)

    def rescale_keyed_state(self, state, new_parallelism, num_key_groups):
        # Window ids are lockstep across subtasks (driven by shared
        # causal time): carry the max forward.
        return {"acc": rescale_dense_table(state["acc"], new_parallelism,
                                           num_key_groups),
                "window": jnp.broadcast_to(state["window"].max(),
                                           (new_parallelism,))}

    def static_out_keys(self) -> Optional[np.ndarray]:
        # Dense table emission: slot i always carries key i.
        return np.arange(self.num_keys, dtype=np.int32)

    def static_clamp_keys(self) -> Optional[np.ndarray]:
        # process_block sums through keyed_hist, which drops a key
        # outside [0, num_keys): column i holds key i's records alone.
        return np.zeros((0,), np.int32)


#: free-slot sentinel for open-window tables; far below any reachable
#: window id (ids are event_ts // size), and safe in guarded arithmetic.
_NO_WINDOW = -(2 ** 30)


#: "no valid record yet": the fold's identity for ``max_ts``
_NO_TS = -(2 ** 31) + 1
#: an own column bound to no key (``Operator.own_columns``): past every
#: key, so that the bound ones stay an ascending prefix
NO_KEY = 2 ** 31 - 1


def _segmented_cumsum(values: jnp.ndarray, reset: jnp.ndarray
                      ) -> jnp.ndarray:
    """Inclusive running sum along axis 0 that restarts at every step
    whose ``reset`` is set (that step's value opens the new segment).
    One associative scan: int32 adds wrap and associate, so any
    bracketing equals the step-by-step fold bit for bit."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, va + vb)
    return jax.lax.associative_scan(combine, (reset, values), axis=0)[1]


class _EventTimeSlots:
    """The arithmetic every event-time window operator shares — the
    single-input windows (:class:`EventTimeWindow`) and the two-input
    window join (:class:`EventTimeWindowJoinOperator`) — in a step form
    and a block form each: the running maximum behind a watermark, the
    placement of records in ``open_windows`` slots by window id, the
    fire of the slots the watermark has passed, and the accumulators'
    running sums that restart at a fire. One implementation: an operator
    differs in what it folds and what a fire emits, not in this.

    Why a slot never holds the wrong window: the ids still open after
    the fire lie in ``[wm_floor, max_ts // slide]``, at most
    ``(out_of_orderness + size) // slide + 1`` consecutive values, fewer
    than ``open_windows``; so of the ids congruent to a slot exactly one
    can be open at a time, and whatever the slot holds is that one.
    Every state reached from ``init_state`` keeps this, and the block
    form leans on it: a valid record is accepted iff its window is not
    closed. An operator whose watermark can trail its newest record by
    more than that (two inputs, the slower one holding the watermark)
    asks for ``bounded`` placement: a record ``open_windows`` or more
    windows ahead of the first open one has no slot and is refused like
    a late one, which keeps the same fact by construction.
    """

    num_keys: int
    window_size: int
    out_of_orderness: int
    open_windows: int

    @property
    def _slide(self) -> int:
        raise NotImplementedError

    def _window_end(self, win: jnp.ndarray) -> jnp.ndarray:
        """End of window ``win``; a free slot reads as window 0 so that
        the sentinel never enters the multiply."""
        return (jnp.where(win != _NO_WINDOW, win, 0) * self._slide
                + self.window_size)

    def _accepts(self, valid, rw, wm, bounded: bool):
        """Valid records whose window ``rw`` the watermark ``wm`` has
        not passed (and, ``bounded``, that has a slot: class docstring)."""
        size, slide = self.window_size, self._slide
        ok = valid & ~(rw * slide + size <= wm)
        if bounded:
            ok = ok & (rw < (wm - size) // slide + 1 + self.open_windows)
        return ok

    # --- step form (one subtask, under ``jax.vmap``) -------------------------

    def _fire_step(self, win, wm):
        """``(win_end, fire)`` of the slots ``win [W]``: every open
        window with end <= wm closes, before the step's records."""
        win_end = self._window_end(win)
        return win_end, (win != _NO_WINDOW) & (win_end <= wm)

    def _place_step(self, win, wm, valid, ts, bounded: bool = False):
        """Place one step's records: ``(win, [(slot, ok), ...], ok_any)``
        — per window a record falls in (``size // slide`` of them) its
        slot and whether it was accepted there; ``win`` with the ids
        accepted. The newest window containing ts starts at
        floor(ts / slide) * slide (jnp // floors); the record is also in
        the ``size // slide - 1`` windows before it."""
        w = self.open_windows
        base = ts // self._slide
        ok_any = jnp.zeros_like(valid)
        placed = []
        for j in range(self.window_size // self._slide):
            rw = base - j                              # window id
            slot = rw % w
            slot_win = win[slot]
            ok = self._accepts(valid, rw, wm, bounded) & (
                (slot_win == rw) | (slot_win == _NO_WINDOW))
            ok_any = ok_any | ok
            win = win.at[slot].max(jnp.where(ok, rw, _NO_WINDOW),
                                   mode="drop")
            placed.append((slot, ok))
        return win, placed, ok_any

    # --- block form ([K, P, ...], no scan over the steps, no scatter) --------

    def _block_max_ts(self, max_ts0, valid, ts):
        """``[K, P]``: the largest valid timestamp seen through each
        step, this step's included (a running maximum over the steps)."""
        step_max = jnp.max(jnp.where(valid, ts, _NO_TS), axis=2)  # [K, P]
        return jnp.maximum(max_ts0[None], jax.lax.cummax(step_max, axis=0))

    @scoped("place")
    def _block_place(self, valid, key, values, ts, wm,
                     want_counts: bool = False, bounded: bool = False,
                     nk: Optional[int] = None):
        """Place a block's records by the watermark ``wm [K, P]`` alone
        (class docstring): ``(sums, counts, taken, ok_any)`` — per-step
        sums of ``values`` (and record counts, ``want_counts``) per
        (slot, key) through the keyed histogram over the composite lane
        ``slot * nk + key`` (``[K, P, W * nk]``), the largest window id
        accepted into each slot at each step (``[K, P, W]``), and which
        records were accepted at all. ``nk``: the key lanes a slot has,
        where that is not ``num_keys`` (own columns; ``key`` is then the
        column)."""
        from clonos_tpu.ops.histogram import KERNEL_MAX_KEYS, keyed_hist
        K, p, _ = valid.shape
        nk, w = self.num_keys if nk is None else nk, self.open_windows
        slots = jnp.arange(w, dtype=jnp.int32)
        base = ts // self._slide
        ok_any = jnp.zeros_like(valid)
        contrib = jnp.zeros((K, p, w * nk), jnp.int32)
        counts = contrib if want_counts else None
        taken = jnp.full((K, p, w), _NO_WINDOW, jnp.int32)
        for j in range(self.window_size // self._slide):
            rw = base - j
            ok = self._accepts(valid, rw, wm[:, :, None], bounded)
            ok_any = ok_any | ok
            slot = rw % w
            if w * nk <= KERNEL_MAX_KEYS:
                part, cnt = keyed_hist(slot * nk + key, values, ok,
                                       w * nk, want_counts=want_counts)
            else:       # a table wider than the kernel takes: slot by slot
                parts = [keyed_hist(key, values, ok & (slot == s), nk,
                                    want_counts=want_counts)
                         for s in range(w)]
                part = jnp.concatenate([x[0] for x in parts], axis=2)
                cnt = (jnp.concatenate([x[1] for x in parts], axis=2)
                       if want_counts else None)
            contrib = contrib + part
            if want_counts:
                counts = counts + cnt
            hit = ok[..., None] & (slot[..., None] == slots)   # [K,P,B,W]
            taken = jnp.maximum(taken, jnp.max(
                jnp.where(hit, rw[..., None], _NO_WINDOW), axis=2))
        return contrib, counts, taken, ok_any

    def _block_slots(self, win0, taken, wm):
        """``(held, win_end, fire)``, each ``[K, P, W]``: the window a
        slot holds after each step (the largest id ever accepted into
        it, if that is still open), and what each step's fire sees — the
        end of the window the step before left there, and whether the
        watermark has passed it."""
        seen = jnp.maximum(win0[None], jax.lax.cummax(taken, axis=0))
        held = jnp.where(self._window_end(seen) <= wm[:, :, None],
                         _NO_WINDOW, seen)
        before = jnp.concatenate([win0[None], held[:-1]], axis=0)
        win_end = self._window_end(before)
        fire = (before != _NO_WINDOW) & (win_end <= wm[:, :, None])
        return held, win_end, fire

    def _block_lanes(self, x, nk: Optional[int] = None):
        """``[K, P, W]`` -> ``[K, P, W * nk]``: a slot's value on each of
        its key lanes."""
        return jnp.repeat(x, self.num_keys if nk is None else nk, axis=2)

    @scoped("segsum")
    def _block_accumulate(self, acc0, contrib, fire_l):
        """``(acc, emit)``, ``[K, P, W * nk]``: the accumulators after
        each step — ``contrib`` in a running sum that restarts where the
        lane's slot fires (``fire_l``), from ``acc0 [P, W * nk]`` — and
        as the step before left them, which is what a fire emits."""
        first = jnp.where(fire_l[0], 0, acc0) + contrib[0]
        acc = _segmented_cumsum(
            jnp.concatenate([first[None], contrib[1:]], axis=0),
            jnp.concatenate([jnp.ones_like(fire_l[:1]), fire_l[1:]],
                            axis=0))
        return acc, jnp.concatenate([acc0[None], acc[:-1]], axis=0)


class EventTimeWindow(_EventTimeSlots, Operator):
    """Event-time windowed sum per key with watermark-driven firing: what
    the tumbling and the sliding operator share (a tumbling window is a
    sliding one whose slide is its size). Window id = start // slide.

    TPU-first watermark discipline: the watermark is a PURE FOLD over the
    record timestamps flowing through this operator —
    ``wm = max(event_ts seen) - out_of_orderness`` — not a timer race.
    That makes it deterministic given the inputs, so recovery replays it
    bit-identically with **no watermark determinant at all** (the
    reference must route watermark generation through causal time because
    its per-channel arrival interleaving races; the lockstep superstep
    eliminates the race structurally).

    Batched-watermark discipline: the watermark advances once per
    superstep, BEFORE the step's records are assigned — so records of one
    superstep whose timestamps trail the step's own maximum by more than
    ``out_of_orderness`` are late-dropped. Set ``out_of_orderness`` to at
    least the expected intra-superstep timestamp spread (the reference's
    per-record watermark interleaving has the same knob, just at record
    granularity).

    State per subtask: ``open_windows`` accumulator slots ``acc[W, nk]``
    with absolute window ids ``win[W]`` (``_NO_WINDOW`` = free), plus
    ``max_ts``. A record whose window the watermark has passed is a LATE
    DROP — counted in ``late`` like the reference's lateness side-output
    (once per record, not per (record, window) pair). Windows whose
    end <= watermark fire FIRST: one record per key with a nonzero sum,
    timestamped with the window end and counted in ``fired`` (a window
    completed by this step's records emits next step — deterministic
    one-step emission latency). The watermark, slot and fire arithmetic
    is :class:`_EventTimeSlots`'s, which also says why a slot never
    holds the wrong window.
    """

    fence_totals = (("late", "window.late_records"),
                    ("fired", "window.fired_rows"))

    @property
    def out_capacity(self):  # type: ignore[override]
        # All open windows may fire in one step.
        return self.num_keys * self.open_windows

    def static_out_keys(self) -> Optional[np.ndarray]:
        # Dense table emission: slot (w, i) always carries key i.
        return np.tile(np.arange(self.num_keys, dtype=np.int32),
                       self.open_windows)

    def static_clamp_keys(self) -> Optional[np.ndarray]:
        # ``jnp.clip(keys, 0, nk - 1)``: a negative key is summed under
        # key 0 and a key at or past num_keys under the last key, by
        # the subtask that received it.
        return np.asarray([0, self.num_keys - 1], np.int32)

    def init_state(self, parallelism: int):
        w = self.open_windows
        return {
            "acc": jnp.zeros((parallelism, w, self.num_keys), jnp.int32),
            "win": jnp.full((parallelism, w), _NO_WINDOW, jnp.int32),
            "max_ts": jnp.full((parallelism,), _NO_TS, jnp.int32),
            "late": jnp.zeros((parallelism,), jnp.int32),
            "fired": jnp.zeros((parallelism,), jnp.int32),
        }

    def process(self, state, batch, ctx):
        nk = self.num_keys

        def one(acc, win, max_ts, late, fired, b: RecordBatch):
            # Advance the watermark from this step's data (pure fold).
            step_max = jnp.max(jnp.where(b.valid, b.timestamps, _NO_TS))
            max_ts = jnp.maximum(max_ts, step_max)
            wm = max_ts - self.out_of_orderness
            # FIRE FIRST: every open window with end <= wm closes, freeing
            # its slot before this step's records are assigned.
            win_end, fire = self._fire_step(win, wm)           # [W]
            out = RecordBatch(
                keys=jnp.asarray(self.static_out_keys()),
                values=acc.reshape(-1),
                timestamps=jnp.repeat(win_end, nk),
                valid=(fire[:, None] & (acc != 0)).reshape(-1))
            fired = fired + jnp.sum(out.valid.astype(jnp.int32))
            acc = jnp.where(fire[:, None], 0, acc)
            win = jnp.where(fire, _NO_WINDOW, win)
            win, placed, ok_any = self._place_step(win, wm, b.valid,
                                                   b.timestamps)
            for slot, ok in placed:
                acc = acc.at[slot, jnp.clip(b.keys, 0, nk - 1)].add(
                    jnp.where(ok, b.values, 0), mode="drop")
            late = late + jnp.sum((b.valid & ~ok_any).astype(jnp.int32))
            return acc, win, max_ts, late, fired, zero_invalid(out)

        acc, win, max_ts, late, fired, out = jax.vmap(one)(
            state["acc"], state["win"], state["max_ts"], state["late"],
            state["fired"], batch)
        return ({"acc": acc, "win": win, "max_ts": max_ts, "late": late,
                 "fired": fired}, out)

    def process_block(self, state, batches, bctx):
        # Step-batched form, no scan over the steps and no scatter
        # (:class:`_EventTimeSlots`): the watermark is a running maximum
        # over the step axis; a record is accepted iff its window is not
        # behind it; per-step sums per (slot, key) come from the keyed
        # histogram; accumulators are those sums in a running sum that
        # restarts where the slot fires; a fire emits the accumulator as
        # the step before left it.
        K, p, _ = batches.keys.shape
        nk, w = self.num_keys, self.open_windows
        valid = batches.valid
        max_ts = self._block_max_ts(state["max_ts"], valid,
                                    batches.timestamps)
        wm = max_ts - self.out_of_orderness                       # [K, P]
        contrib, _, taken, ok_any = self._block_place(
            valid, jnp.clip(batches.keys, 0, nk - 1), batches.values,
            batches.timestamps, wm)
        late = state["late"] + jnp.sum(
            (valid & ~ok_any).astype(jnp.int32), axis=(0, 2))
        held, win_end, fire = self._block_slots(state["win"], taken, wm)
        fire_l = self._block_lanes(fire)                       # [K,P,W*nk]
        acc, emit = self._block_accumulate(
            state["acc"].reshape(p, w * nk), contrib, fire_l)
        out = zero_invalid(RecordBatch(
            keys=jnp.broadcast_to(jnp.asarray(self.static_out_keys()),
                                  (K, p, w * nk)),
            values=emit, timestamps=self._block_lanes(win_end),
            valid=fire_l & (emit != 0)))
        fired = state["fired"] + jnp.sum(
            out.valid.astype(jnp.int32), axis=(0, 2))
        return ({"acc": acc[-1].reshape(p, w, nk), "win": held[-1],
                 "max_ts": max_ts[-1], "late": late, "fired": fired}, out)


@dataclasses.dataclass
class EventTimeTumblingWindowOperator(EventTimeWindow):
    """Event-time tumbling windowed sum per key (WindowOperator +
    EventTimeTrigger analog; reference flink-streaming-java
    .../windowing/WindowOperator.java with watermarks from
    StreamSourceContexts.java:180-187). See :class:`EventTimeWindow`."""

    num_keys: int
    window_size: int
    out_of_orderness: int = 0
    open_windows: int = 2

    def __post_init__(self):
        # After fire-first, open window ids span at most
        # out_of_orderness // window_size + 1 consecutive values; one
        # spare slot keeps the (rw % W) placement collision-free.
        need = self.out_of_orderness // self.window_size + 2
        self.open_windows = max(self.open_windows, need)

    @property
    def _slide(self) -> int:
        return self.window_size


@dataclasses.dataclass
class SlidingEventTimeWindowOperator(EventTimeWindow):
    """Event-time SLIDING windowed sum per key: each record contributes to
    ``size // slide`` consecutive windows (WindowOperator +
    SlidingEventTimeWindows analog). See :class:`EventTimeWindow`."""

    num_keys: int
    window_size: int
    slide: int
    out_of_orderness: int = 0
    open_windows: int = 4

    def __post_init__(self):
        if self.window_size % self.slide:
            raise ValueError("window_size must be a multiple of slide")
        need = (self.out_of_orderness + self.window_size) // self.slide + 2
        self.open_windows = max(self.open_windows, need)

    @property
    def _slide(self) -> int:
        return self.slide


@dataclasses.dataclass
class EventTimeWindowMeanOperator(SlidingEventTimeWindowOperator):
    """Event-time windowed MEAN per key, sliding or tumbling, exact: a
    window that fires emits one row ``(key, round-half-up(sum / count),
    window end - 1)`` for every key it holds a record of (Beam's
    ``Mean.perKey`` behind ``Math.round``; NEXmark query 4, "Average
    Price for a Category"). Watermark, slots, fire, the batched-watermark
    rule and the dense ``slot x key`` lanes are :class:`EventTimeWindow`'s;
    a row is stamped with its window's last millisecond, as
    :class:`EventTimeWindowTopOperator` stamps its.

    **Exact.** A value in ``[0, 2**30)`` goes into two sums as two limbs
    of 15 bits beside the count, so that neither sum passes int32 while
    a (window, key) holds fewer than 65,536 records, whatever they add up
    to (prices reach 10**8: four hundred of them pass int32 and a
    float32's 24 bits both); the mean is their long division by the
    count, rounded half up. A value outside that range is refused and
    counted (``refused``); ``late`` counts the records that missed ANY
    of the windows that hold them (a sliding window's older ones fire
    first, and a mean that lacks a row is a wrong mean, where
    :class:`EventTimeWindow` counts only the records no window took);
    both are ``fence_losses``."""

    fence_totals = EventTimeWindow.fence_totals + (
        ("refused", "window.refused_values"),)
    fence_losses = ("late", "refused")

    _LIMB = 15

    def init_state(self, parallelism: int):
        state = super().init_state(parallelism)
        state.update(acc_hi=jnp.zeros_like(state["acc"]),
                     count=jnp.zeros_like(state["acc"]),
                     refused=jnp.zeros_like(state["late"]))
        return state

    def _limbs(self, b: RecordBatch):
        """``(taken, low limb, high limb, refused [..., P])`` of a
        receive window's records."""
        fits = (b.values >= 0) & (b.values < 1 << 2 * self._LIMB)
        return (b.valid & fits, b.values & ((1 << self._LIMB) - 1),
                b.values >> self._LIMB,
                jnp.sum((b.valid & ~fits).astype(jnp.int32), axis=-1))

    def _mean(self, lo, hi, count):
        """``round-half-up((hi * 2**15 + lo) / count)`` by long division
        (0 where ``count`` is): the one step that passes int32 is taken
        in uint32, which ``remainder * 2**15 + lo < 2**32`` fits."""
        u = lambda x: x.astype(jnp.uint32)
        n = jnp.maximum(count, 1)
        rest = u(hi % n) * (1 << self._LIMB) + u(lo)
        return ((hi // n << self._LIMB) + (rest // u(n)).astype(jnp.int32)
                + (2 * (rest % u(n)) >= u(n)).astype(jnp.int32))

    def _rows(self, lo, hi, count, fire_l, win_end_l):
        """The rows of one or many steps over the dense lanes ``[..., W *
        nk]``, and how many ``[...]``."""
        out = zero_invalid(RecordBatch(
            keys=jnp.broadcast_to(jnp.asarray(self.static_out_keys()),
                                  lo.shape),
            values=self._mean(lo, hi, count), timestamps=win_end_l - 1,
            valid=fire_l & (count != 0)))
        return out, jnp.sum(out.valid.astype(jnp.int32), axis=-1)

    def process(self, state, batch, ctx):
        nk = self.num_keys
        took, lo, hi, refused = self._limbs(batch)

        def one(acc, acc_hi, count, win, max_ts, b: RecordBatch, took, lo,
                hi):
            max_ts = jnp.maximum(max_ts, jnp.max(
                jnp.where(b.valid, b.timestamps, _NO_TS)))
            wm = max_ts - self.out_of_orderness
            win_end, fire = self._fire_step(win, wm)               # [W]
            out, fired = self._rows(
                acc.reshape(-1), acc_hi.reshape(-1), count.reshape(-1),
                jnp.repeat(fire, nk), jnp.repeat(win_end, nk))
            acc, acc_hi, count = (jnp.where(fire[:, None], 0, x)
                                  for x in (acc, acc_hi, count))
            win = jnp.where(fire, _NO_WINDOW, win)
            win, placed, ok_any = self._place_step(win, wm, took,
                                                   b.timestamps)
            key = jnp.clip(b.keys, 0, nk - 1)
            for slot, ok in placed:
                add = lambda x, v: x.at[slot, key].add(
                    jnp.where(ok, v, 0), mode="drop")
                acc, acc_hi, count = (add(acc, lo), add(acc_hi, hi),
                                      add(count, jnp.ones_like(lo)))
            ok_all = functools.reduce(jnp.logical_and,
                                      (ok for _, ok in placed))
            late = jnp.sum((took & ~ok_all).astype(jnp.int32))
            return acc, acc_hi, count, win, max_ts, late, fired, out

        acc, acc_hi, count, win, max_ts, late, fired, out = jax.vmap(one)(
            state["acc"], state["acc_hi"], state["count"], state["win"],
            state["max_ts"], batch, took, lo, hi)
        return dict(
            acc=acc, acc_hi=acc_hi, count=count, win=win, max_ts=max_ts,
            late=state["late"] + late, fired=state["fired"] + fired,
            refused=state["refused"] + refused), out

    def process_block(self, state, batches, bctx):
        # :class:`EventTimeWindow`'s block form over three accumulators
        p = batches.keys.shape[1]
        nk, w = self.num_keys, self.open_windows
        took, lo, hi, refused = self._limbs(batches)
        max_ts = self._block_max_ts(state["max_ts"], batches.valid,
                                    batches.timestamps)
        wm = max_ts - self.out_of_orderness                       # [K, P]
        key = jnp.clip(batches.keys, 0, nk - 1)
        add_lo, add_n, taken, _ = self._block_place(
            took, key, lo, batches.timestamps, wm, want_counts=True)
        ok_all = functools.reduce(jnp.logical_and, (
            self._accepts(took, batches.timestamps // self.slide - j,
                          wm[:, :, None], False)
            for j in range(self.window_size // self.slide)))
        add_hi = self._block_place(took, key, hi, batches.timestamps, wm)[0]
        held, win_end, fire = self._block_slots(state["win"], taken, wm)
        fire_l = self._block_lanes(fire)                       # [K,P,W*nk]
        (acc, lo), (acc_hi, hi), (count, n) = (
            self._block_accumulate(state[k].reshape(p, w * nk), add, fire_l)
            for k, add in (("acc", add_lo), ("acc_hi", add_hi),
                           ("count", add_n)))
        out, fired = self._rows(lo, hi, n, fire_l,
                                self._block_lanes(win_end))
        return dict(
            acc=acc[-1].reshape(p, w, nk),
            acc_hi=acc_hi[-1].reshape(p, w, nk),
            count=count[-1].reshape(p, w, nk), win=held[-1],
            max_ts=max_ts[-1],
            late=state["late"] + jnp.sum(
                (took & ~ok_all).astype(jnp.int32), axis=(0, 2)),
            fired=state["fired"] + fired.sum(axis=0),
            refused=state["refused"] + refused.sum(axis=0)), out


@dataclasses.dataclass
class UnionOperator(TwoInputOperator):
    """Merge two streams: left records first, then right, packed by rank
    into a fixed output capacity (the union / ConnectedStreams.map-same-
    type shape). Deterministic concatenation order replaces the
    reference's arrival-order race; a record whose rank among the valid
    ones is at or past ``capacity`` is a (deterministic) overflow drop.

    The step form sorts and gathers, one row at a time; the block form
    packs a whole block by rank (:func:`_pack_by_rank`: a running count
    and a keyed histogram a field — no sort, no gather). The two agree
    bit for bit (``tests/test_operators_block.py``)."""

    capacity: int

    # a compaction of both inputs' records, as they came
    emits_received_keys = True

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.capacity

    def process2(self, state, left, right, ctx):
        def one(l: RecordBatch, r: RecordBatch):
            keys = jnp.concatenate([l.keys, r.keys])
            vals = jnp.concatenate([l.values, r.values])
            ts = jnp.concatenate([l.timestamps, r.timestamps])
            valid = jnp.concatenate([l.valid, r.valid])
            # Compact valid records to the front (stable); anything past
            # ``capacity`` live records is a (deterministic) overflow drop.
            order = jnp.argsort(~valid, stable=True)
            take = order[: self.capacity]
            return zero_invalid(RecordBatch(
                keys[take], vals[take], ts[take], valid[take]))
        return state, jax.vmap(one)(left, right)

    def process_block(self, state, batches, bctx):
        # Stateless. A valid slot's place is its rank among the valid
        # ones in slot order (the order of the step form's stable sort);
        # a rank past the capacity is out of the histogram's range and
        # dropped, a place no record took holds its 0 in every field.
        with jax.named_scope("compact"):
            keys, vals, ts, valid = (
                jnp.concatenate(both, axis=-1) for both in zip(*batches))
            packed, count = _pack_by_rank(valid, (keys, vals, ts),
                                          self.capacity)
            taken = (jnp.arange(self.capacity, dtype=jnp.int32)
                     < count[..., None])
        return state, RecordBatch(*packed, taken)


@dataclasses.dataclass
class IntervalJoinOperator(TwoInputOperator):
    """Keyed stream-stream join (the NEXMark-style join shape,
    BASELINE config #5; reference analog: IntervalJoinOperator /
    flink-libraries join machinery re-imagined dense).

    State per subtask: for each key, a ring of the last ``window`` left
    records (value, timestamp). Each right record joins against all
    retained left records of its key with |ts_l - ts_r| <= interval,
    emitting (key, combine(vl, vr), ts_r). Dense tables: ``[P, K, W]``.
    Emission capacity bounds matches per step (static shape; overflow
    drops are deterministic)."""

    num_keys: int
    window: int               # retained left records per key
    interval: int             # max |ts_left - ts_right|
    capacity: int             # output capacity per subtask per step

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.capacity

    def init_state(self, parallelism: int):
        k, w = self.num_keys, self.window
        return {
            "lv": jnp.zeros((parallelism, k, w), jnp.int32),   # left values
            "lt": jnp.zeros((parallelism, k, w), jnp.int32),   # left ts
            "lm": jnp.zeros((parallelism, k, w), jnp.bool_),   # live mask
            "cursor": jnp.zeros((parallelism, k), jnp.int32),  # ring cursor
        }

    def process2(self, state, left, right, ctx):
        k, w, cap = self.num_keys, self.window, self.capacity

        def one(lv, lt, lm, cursor, l: RecordBatch, r: RecordBatch):
            # Insert the whole left batch at once. A record's ring slot is
            # cursor[key] + its per-key arrival rank (a running bucket
            # count — same counting trick as the routing exchange, no
            # per-record scan); only the last ``w`` records of a key
            # survive a single batch (earlier ones would be overwritten
            # by the sequential semantics anyway), which also makes every
            # scatter destination unique.
            lk = jnp.clip(l.keys, 0, k - 1)
            onehot = (l.valid[:, None]
                      & (lk[:, None] == jnp.arange(k, dtype=jnp.int32)))
            cum = jnp.cumsum(onehot.astype(jnp.int32), axis=0)  # [B, K]
            rank = jnp.take_along_axis(cum, lk[:, None], 1)[:, 0] - 1
            total = cum[-1]                                     # [K]
            keep = l.valid & (total[lk] - 1 - rank < w)
            slot = (cursor[lk] + rank) % w
            row = jnp.where(keep, lk, k)          # k = drop row
            lv = lv.at[row, slot].set(l.values, mode="drop")
            lt = lt.at[row, slot].set(l.timestamps, mode="drop")
            lm = lm.at[row, slot].set(True, mode="drop")
            cursor = cursor + total

            # Join each right record against its key's ring: [B_r, W] pairs.
            rk = jnp.clip(r.keys, 0, k - 1)
            cand_v = lv[rk]                       # [B_r, W]
            cand_t = lt[rk]
            cand_m = lm[rk] & r.valid[:, None]
            match = cand_m & (jnp.abs(cand_t - r.timestamps[:, None])
                              <= self.interval)
            out_keys = jnp.broadcast_to(r.keys[:, None], match.shape)
            out_vals = cand_v + r.values[:, None]
            out_ts = jnp.broadcast_to(r.timestamps[:, None], match.shape)
            flat_n = match.size
            fk = out_keys.reshape(flat_n)
            fv = out_vals.reshape(flat_n)
            ft = out_ts.reshape(flat_n)
            fm = match.reshape(flat_n)
            # Compact matches to the front by arrival rank (cumsum, not
            # argsort); first ``cap`` survive, deterministically.
            pos = jnp.cumsum(fm.astype(jnp.int32)) - 1
            keep2 = fm & (pos < cap)
            dst = jnp.where(keep2, pos, cap)
            g = lambda src, z: jnp.zeros((cap + 1,), z).at[dst].set(
                src, mode="drop")[:cap]
            return lv, lt, lm, cursor, zero_invalid(RecordBatch(
                g(fk, jnp.int32), g(fv, jnp.int32), g(ft, jnp.int32),
                g(fm, jnp.bool_)))

        lv, lt, lm, cursor, out = jax.vmap(one)(
            state["lv"], state["lt"], state["lm"], state["cursor"],
            left, right)
        return {"lv": lv, "lt": lt, "lm": lm, "cursor": cursor}, out

    def process_block(self, state, batches, bctx):
        """Grouped step-batched form: G supersteps are fused per scan
        iteration (the per-step scan cost ~3ms/step at 128-task bench
        shapes — a 20k-step replay took a minute of pure scan overhead).

        Within a group everything is rank arithmetic, bit-identical to
        the sequential semantics: a left record's ring slot is its
        GLOBAL arrival index mod w (cursor carries the global count), so
        a right record's slot-j candidate is the latest group-local left
        with rank ≡ (j - cursor) mod w below its through-count — gathered
        from a group-local time-indexed table — falling back to the
        carried ring slot j for pre-group arrivals. Join outputs keep
        process2's exact (right-slot, ring-slot) emission order and
        per-step compaction."""
        left, right = batches
        K, P, B = left.keys.shape
        B2 = right.keys.shape[2]
        nk, w, cap = self.num_keys, self.window, self.capacity
        # Group size bounded by the [P, nk, G*B] table scratch.
        budget = 128 << 20
        per = P * nk * B * 4 * 3
        gmax = max(1, min(64, budget // max(per, 1)))
        G = 1
        for d in range(int(gmax), 0, -1):
            if K % d == 0:
                G = d
                break
        if G == 1:
            return TwoInputOperator.process_block(self, state, batches,
                                                  bctx)
        n = G * B
        ks = jnp.arange(nk, dtype=jnp.int32)

        def one(lv, lt, lm, cur, l, r):
            # l fields [G, B] (one lane); flatten in (step, slot) order —
            # the sequential insert order.
            lk = jnp.clip(l.keys, 0, nk - 1).reshape(n)
            lvalid = l.valid.reshape(n)
            oh = lvalid[:, None] & (lk[:, None] == ks[None, :])
            cum = jnp.cumsum(oh.astype(jnp.int32), axis=0)   # [n, nk] incl
            rank = jnp.take_along_axis(cum, lk[:, None], 1)[:, 0] - 1
            rank = jnp.where(lvalid, rank, n)                # n = drop row
            total = cum[-1]                                  # [nk]
            # Time-indexed group table: left record with (key, rank).
            Tv = jnp.zeros((nk, n), jnp.int32).at[lk, rank].set(
                l.values.reshape(n), mode="drop")
            Tt = jnp.zeros((nk, n), jnp.int32).at[lk, rank].set(
                l.timestamps.reshape(n), mode="drop")
            # Lefts of key k seen through step g (inclusive).
            through = cum.reshape(G, B, nk)[:, -1]           # [G, nk]

            rk = jnp.clip(r.keys, 0, nk - 1)                 # [G, B2]
            hi = jnp.take_along_axis(through, rk, 1)         # [G, B2]
            c0 = cur[rk]                                     # [G, B2]
            js = jnp.arange(w, dtype=jnp.int32)
            hib = hi[..., None]
            tmod = (js[None, None, :] - c0[..., None]) % w   # [G, B2, w]
            rc = hib - 1 - ((hib - 1 - tmod) % w)
            use_g = (hib > 0) & (rc >= 0)
            rc_s = jnp.clip(rc, 0, n - 1)
            rkb = rk[..., None]
            cand_v = jnp.where(use_g, Tv[rkb, rc_s], lv[rk])
            cand_t = jnp.where(use_g, Tt[rkb, rc_s], lt[rk])
            cand_m = jnp.where(use_g, use_g, lm[rk]) & r.valid[..., None]
            match = cand_m & (jnp.abs(cand_t - r.timestamps[..., None])
                              <= self.interval)
            out_keys = jnp.broadcast_to(r.keys[..., None], match.shape)
            out_vals = cand_v + r.values[..., None]
            out_ts = jnp.broadcast_to(r.timestamps[..., None], match.shape)
            # Per-step compaction in (right-slot, ring-slot) order.
            fm = match.reshape(G, B2 * w)
            pos = jnp.cumsum(fm.astype(jnp.int32), axis=1) - 1
            keep2 = fm & (pos < cap)
            dst = jnp.where(keep2, pos, cap)
            gidx = jnp.arange(G, dtype=jnp.int32)[:, None]

            def comp(src, dt):
                return jnp.zeros((G, cap + 1), dt).at[gidx, dst].set(
                    jnp.where(keep2, src.reshape(G, B2 * w),
                              jnp.zeros((), dt)),
                    mode="drop")[:, :cap]
            out = zero_invalid(RecordBatch(
                comp(out_keys, jnp.int32), comp(out_vals, jnp.int32),
                comp(out_ts, jnp.int32), comp(keep2, jnp.bool_)))
            # End-of-group ring: slot j <- latest group arrival with
            # rank ≡ (j - cursor) mod w, else the carried slot.
            tmod_k = (js[None, :] - cur[:, None]) % w        # [nk, w]
            tot = total[:, None]
            rck = tot - 1 - ((tot - 1 - tmod_k) % w)
            use_k = (tot > 0) & (rck >= 0)
            rck_s = jnp.clip(rck, 0, n - 1)
            kidx = ks[:, None]
            lv2 = jnp.where(use_k, Tv[kidx, rck_s], lv)
            lt2 = jnp.where(use_k, Tt[kidx, rck_s], lt)
            lm2 = lm | use_k
            return lv2, lt2, lm2, cur + total, out

        def group_step(carry, xs):
            lv, lt, lm, cur = carry
            gl, gr = xs                  # [G, P, *]
            lv, lt, lm, cur, out = jax.vmap(
                one, in_axes=(0, 0, 0, 0, 1, 1), out_axes=(
                    0, 0, 0, 0, 1))(lv, lt, lm, cur, gl, gr)
            return (lv, lt, lm, cur), out

        regroup = lambda t: jax.tree_util.tree_map(
            lambda x: x.reshape((K // G, G) + x.shape[1:]), t)
        (lv, lt, lm, cur), outs = jax.lax.scan(
            group_step,
            (state["lv"], state["lt"], state["lm"], state["cursor"]),
            (regroup(left), regroup(right)))
        out = jax.tree_util.tree_map(
            lambda x: x.reshape((K,) + x.shape[2:]), outs)
        return {"lv": lv, "lt": lt, "lm": lm, "cursor": cur}, out


#: slots at the front of every target's receive window that a block's
#: own-column lookup compares as they lie: one lane tile
_HEAD = 128
#: targets a step whose slots past the head the lookup compares too (the
#: step's first *over* targets, in subtask order). Two, because a hot id
#: that moves at most once a step straddles at most two owners in one
#: step (``nexmark-q5``'s moves every 1.5 steps, ``nexmark-q11``'s every
#: 4.5), and every other target of a step of ``P x batch`` records holds
#: a fraction of a head
_TAILS = 2


class _HeadAndTails(NamedTuple):
    """A block's receive windows ``[K, P, B]`` as the slots a target can
    fill: the head ``[K, P, _HEAD]`` of every target, and the slots past
    it ``[_TAILS, K, B - _HEAD]`` of each step's first ``_TAILS`` over
    targets, picked out — and put back — by a one-hot select over the
    subtasks (no gather). The tails' axis leads, so that the steps and
    not the two tails lie along the sublanes of what is compared."""

    #: bool ``[_TAILS, K, P]``: the step's j-th over target, one-hot
    pick: jnp.ndarray
    #: bool ``[]``: some step has more over targets than tails
    crowded: jnp.ndarray

    def head(self, x: jnp.ndarray) -> jnp.ndarray:
        return x[..., :_HEAD]

    def tails(self, x: jnp.ndarray) -> jnp.ndarray:
        """``x [K, P, B]`` past the head, of the picked targets (a tail
        that picks none: zeros, nothing valid)."""
        pick, past = self.pick[..., None], x[None, :, :, _HEAD:]
        if x.dtype == jnp.bool_:
            return jnp.any(pick & past, axis=2)
        return jnp.sum(jnp.where(pick, past, 0), axis=2)

    def own(self, cols: jnp.ndarray) -> jnp.ndarray:
        """The picked targets' columns, ``cols [P, C]`` -> ``[_TAILS, K,
        C]``."""
        return jnp.sum(jnp.where(self.pick[..., None], cols, 0), axis=2)

    def back(self, x: jnp.ndarray, fill) -> jnp.ndarray:
        """``x [_TAILS, K, N]`` at its target, ``fill`` at every other:
        ``[K, P, N]``."""
        out = jnp.full_like(x[0, :, None], fill)
        for pick, tail in zip(self.pick, x):
            out = jnp.where(pick[:, :, None], tail[:, None], out)
        return out


class _OwnColumns:
    """A table with a column a key, on every subtask (dense) or, with
    ``own_columns``, only for the keys a subtask owns: what every such
    operator shares. ``state["cols"]`` (int32 ``[P, columns]``,
    ascending, then :data:`NO_KEY`) says which key each column holds;
    the planner binds it (``CompiledJob._bind_own_columns``), so the
    binding is state — one operator object serves any number of plans,
    a checkpoint carries it. A record finds its column by its key's
    rank among the bound keys (:meth:`_column`).

    **A block's lookup works on the slots a target can fill**
    (:meth:`_by_head_and_tails`; the windowed top's :meth:`_column_block`
    and the session window's ``_arrivals_block``). A receive window is as
    wide as the fullest target's worst step, and a step holds ``P x
    batch`` records: under a hot key one target fills its window and the
    others a few slots. So the block form compares the first
    :data:`_HEAD` slots of every target, and all further slots of the
    step's first :data:`_TAILS` targets that hold a valid record there
    (*over* targets: read from the mask, so slots that are no prefix —
    a static plan's — are as right as the exchange's packed ones). A
    block in which some step has more over targets compares every slot
    of every target as the step form does, under one ``lax.cond``, and
    counts in ``dense_blocks`` (``lookup.dense_blocks``, one a block:
    how often the split did not engage). Both branches give the dense
    form's answers bit for bit. The split is built only for a window it
    at least halves (:meth:`_splits`); any other shape — a narrow
    window, one lane of a replay — runs the dense form alone, no
    ``cond``, and counts nothing."""

    num_keys: int
    own_columns: Optional[int]

    @property
    def _columns(self) -> int:
        return self.num_keys if self.own_columns is None else self.own_columns

    def _init_cols(self, parallelism: int) -> jnp.ndarray:
        """Dense: a column a key; own columns: none bound yet, every
        record is refused until the planner binds them."""
        c = self._columns
        cols = (jnp.arange(c, dtype=jnp.int32) if self.own_columns is None
                else jnp.full((c,), NO_KEY, jnp.int32))
        return jnp.broadcast_to(cols, (parallelism, c))

    def bind_own_columns(self, state, cols):
        cols = jnp.asarray(cols, jnp.int32)
        if cols.shape != state["cols"].shape:
            raise ValueError(
                f"own columns {cols.shape} for a table of "
                f"{state['cols'].shape}")
        return dict(state, cols=cols)

    @staticmethod
    def _rank(cols, keys):
        """``(column, held)`` of each key of a row ``keys [..., B]`` among
        the row's columns ``cols [..., C]``. The bound keys are an
        ascending prefix, so a held key's column is the count of bound
        keys below it."""
        k, c = keys[..., None], cols[..., None, :]
        return (jnp.sum((k > c).astype(jnp.int32), axis=-1),
                jnp.any(k == c, axis=-1))

    @scoped("lookup")
    def _column(self, cols, keys):
        """``(column, held)`` of each record's key on its subtask:
        ``cols [P, C]`` against ``keys [..., P, B]``, every slot against
        every column."""
        return self._rank(cols, keys)

    @staticmethod
    def _splits(p: int, b: int) -> bool:
        """Whether head and tails compare at most half of a ``[P, B]``
        receive window's slots (16 x 768: 3,328 of 12,288; not 16 x 192,
        not one lane, not a window of one head)."""
        return b > _HEAD and 2 * (p * _HEAD + _TAILS * (b - _HEAD)) <= p * b

    def _by_head_and_tails(self, mask, dense, split):
        """A block's lookup over the receive windows ``mask [K, P, B]``
        marks (class docstring): ``(result, crowded)`` — ``split(parts)``
        on the :class:`_HeadAndTails` of ``mask`` or, in a block with a
        step of more over targets than tails, ``dense()``; ``crowded``
        says which (None: the shape has no split, ``dense()`` it is)."""
        _, p, b = mask.shape
        if not self._splits(p, b):
            return dense(), None
        over = jnp.any(mask[..., _HEAD:], axis=-1)                 # [K, P]
        nth = jnp.cumsum(over.astype(jnp.int32), axis=1) - 1
        parts = _HeadAndTails(
            pick=over & (
                nth == jnp.arange(_TAILS, dtype=jnp.int32)[:, None, None]),
            crowded=jnp.any(nth[:, -1] >= _TAILS))
        return (jax.lax.cond(parts.crowded, dense, lambda: split(parts)),
                parts.crowded)

    @staticmethod
    def _dense_blocks(state, crowded):
        """``state["dense_blocks"]`` after a block: one more on the
        first subtask (the fence sums the leaf) where it was crowded."""
        n = state["dense_blocks"]
        if crowded is None:
            return n
        return n + (crowded & (jnp.arange(n.shape[0]) == 0)).astype(n.dtype)

    @scoped("lookup")
    def _column_block(self, cols, keys, valid):
        """:meth:`_column` of a block's receive windows ``keys [K, P,
        B]``, on head and tails: the same ``(column, held)`` at every
        valid slot (a slot with no record reads ``(0, False)`` where the
        split left it out), and whether the block was crowded."""
        def split(parts):
            head = self._rank(cols, parts.head(keys))
            tails = self._rank(parts.own(cols), parts.tails(keys))
            return tuple(jnp.concatenate([h, parts.back(t, 0)], axis=-1)
                         for h, t in zip(head, tails))
        return self._by_head_and_tails(
            valid, lambda: self._rank(cols, keys), split)

    def _lane_words(self, cols, slots: int):
        """``slot * num_keys + key`` of every lane of a subtask's
        ``[slots, columns]`` table, ``[P, slots * C]``: what a compaction
        carries so that a row names its slot and its key (a column bound
        to no key reads key 0; no row comes from one)."""
        return (jnp.arange(slots, dtype=jnp.int32)[None, :, None]
                * self.num_keys
                + jnp.where(cols != NO_KEY, cols, 0)[:, None, :]
                ).reshape(-1, slots * self._columns)

    def rescale_keyed_state(self, state, new_parallelism, num_key_groups):
        raise NotImplementedError(
            f"{type(self).__name__} does not support rescaling: its "
            f"columns are bound to the keys each subtask owns when the job "
            f"is planned, and a live rescale would have to bind them anew")


@dataclasses.dataclass
class EventTimeWindowJoinOperator(_OwnColumns, _EventTimeSlots,
                                  TwoInputOperator):
    """Tumbling event-time window join of two keyed streams on equal key
    and equal window (the DataStream API's ``a.join(b).where(..)
    .equalTo(..).window(TumblingEventTimeWindows.of(size))``; NEXmark
    query 8 is its textbook use: persons joined with the auctions they
    opened in the window they registered in). One row per (key, window)
    in which BOTH inputs had a record: ``(key, sum of the right input's
    values in the window wrapped to int32, window end)``.

    **Tables: own columns.** Three tables a subtask — the left input's
    counts, the right input's counts, the right input's sums — each
    ``[open_windows, columns]``, and ``state["cols"]`` (int32 ``[P,
    columns]``, ascending, then :data:`NO_KEY`) says which key each
    column holds (:class:`_OwnColumns`). Behind its two ``key_by()``
    inputs a subtask only ever receives the keys it owns, so
    ``DataStream.window_join`` sets ``own_columns`` to what the fullest
    subtask owns, up to the next 128 lanes, and the planner binds each
    subtask's columns to its own ids (``CompiledJob._bind_own_columns``):
    4,096 ids at parallelism 16 are 384 columns a subtask, not 4,096.
    Without ``own_columns`` (a direct construction) the same code runs
    over a column for every key in ``[0, num_keys)``. A record's column
    is its key's rank among the subtask's bound keys, by comparison with
    all of them (no gather by a computed index).

    Semantics, per subtask (every edge one step deep, as everywhere; the
    watermark, slot and fire arithmetic is :class:`_EventTimeSlots`'s,
    shared with :class:`EventTimeWindow`). A record whose key the
    subtask holds no column for — any key outside ``[0, num_keys)``,
    and with own columns a key another subtask owns, which no keyed
    edge delivers — is no record here: no counter sees it, ``late``
    included. ``max_ts_left`` and ``max_ts_right`` are
    the running maxima of each input's valid timestamps, this step's
    included. The watermark is the smaller of the two less the bound —
    Flink's rule for a two-input operator — and advances once a step,
    before the step's records are assigned:
    ``wm = max(min(max_ts_left, max_ts_right), anchor) - out_of_orderness``.
    ``anchor`` only matters while an input is still silent: it is what
    ``min`` read at the first step that brought any record, a silent
    input not counted (so: that step's ``min`` if both inputs spoke,
    else the maximum of the one that did), and it never moves again.
    Until both inputs have delivered a record nothing fires (no window
    with a record ends at or behind ``anchor - out_of_orderness``), and
    the slots are placed from ``anchor`` on; from then on
    ``min(...) >= anchor`` unless the late starter begins behind it, in
    which case its records older than the anchor's window are late.

    Each step: windows whose end is at or behind ``wm`` fire FIRST — for
    every key whose left count and right count in that window are both
    non-zero one row, compacted in (slot, column) order into ``capacity``
    rows a subtask a step (rows past it are dropped and counted in
    ``dropped``: size ``capacity`` for the keys a subtask owns) — then
    the fired slots clear; then the step's left and right records are
    assigned by timestamp to window ``ts // window_size``. A record whose
    window is already closed is dropped and counted in ``late`` (once,
    whichever side). So is one ``open_windows`` or more windows ahead of
    the first open one: with two inputs the watermark can trail the
    faster input without bound, and the slots cannot (``bounded``
    placement). ``fired`` counts rows emitted, ``left_records`` and
    ``right_records`` the records each side accepted.

    The block form has no scan over the steps and no scatter: both
    inputs' histograms over ``slot x column`` lanes, three running sums
    that restart at a fire, and the compaction itself a keyed histogram
    over the rows' ranks (a row's place is its rank: no gather either).

    Every row carries a key this subtask received, on both inputs
    (``emits_received_keys``): behind two ``key_by()`` inputs the out
    edge is routed in place.
    """

    num_keys: int
    window_size: int
    out_of_orderness: int = 0
    capacity: int = 256
    own_columns: Optional[int] = None
    open_windows: int = 2

    emits_received_keys = True

    fence_totals = EventTimeWindow.fence_totals + (
        ("left_records", "join.left_records"),
        ("right_records", "join.right_records"),
        ("dropped", "join.dropped_rows"))

    def __post_init__(self):
        # as the tumbling window: the open ids span at most
        # out_of_orderness // window_size + 1 values, and one spare slot
        need = self.out_of_orderness // self.window_size + 2
        self.open_windows = max(self.open_windows, need)

    @property
    def _slide(self) -> int:
        return self.window_size

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.capacity

    _TABLES = ("left", "right", "sum")

    def init_state(self, parallelism: int):
        p, w = parallelism, self.open_windows
        state = {k: jnp.zeros((p, w, self._columns), jnp.int32)
                 for k in self._TABLES}       # counts, counts, right sums
        state.update({k: jnp.zeros((p,), jnp.int32)
                      for k, _ in self.fence_totals})
        state.update({k: jnp.full((p,), _NO_TS, jnp.int32)
                      for k in ("max_ts_left", "max_ts_right", "anchor")})
        state["win"] = jnp.full((p, w), _NO_WINDOW, jnp.int32)
        state["cols"] = self._init_cols(p)
        return state

    def _placed(self, cols, b: RecordBatch):
        """``(column, record)``: each record's column on its subtask,
        and whether it is a record here — valid, its key one the subtask
        holds a column for (a key of :data:`NO_KEY` names no column)."""
        col, held = self._column(cols, b.keys)
        return col, b.valid & held & (b.keys < self.num_keys)

    @staticmethod
    def _anchor_reading(max_l, max_r):
        """The smaller of both running maxima, a silent input left out
        (``_NO_TS`` while both are): what an unset anchor is set to."""
        lo = jnp.minimum(max_l, max_r)      # _NO_TS while an input is silent
        return jnp.where(lo != _NO_TS, lo, jnp.maximum(max_l, max_r))

    def _watermark(self, max_l, max_r, anchor):
        """``(wm, anchor)`` from both inputs' running maxima and the
        anchor as the step found it (class docstring); elementwise."""
        anchor = jnp.where(anchor != _NO_TS, anchor,
                           self._anchor_reading(max_l, max_r))
        base = jnp.maximum(jnp.minimum(max_l, max_r), anchor)
        return jnp.where(base != _NO_TS, base - self.out_of_orderness,
                         _NO_TS), anchor

    @scoped("emit")
    def _emit(self, match, sums, win_end, cols):
        """The rows of one or many steps: ``match [..., P, W * C]`` lanes
        compacted, in lane order, into ``[..., P, capacity]`` rows (key,
        right sum, window end), and how many did not fit ``[..., P]``. A
        row's place is its rank among the matches, so the compaction is
        a keyed histogram over ranks: one call carries the sums, one
        ``slot * num_keys + key`` (a lane is its slot and its column, a
        column its key)."""
        from clonos_tpu.ops.histogram import keyed_hist
        from clonos_tpu.ops.matops import running_count
        nk, w, cap = self.num_keys, self.open_windows, self.capacity
        rank = running_count(match) - 1
        total = rank[..., -1] + 1
        values, _ = keyed_hist(rank, sums, match, cap, want_counts=False)
        words, _ = keyed_hist(
            rank, jnp.broadcast_to(self._lane_words(cols, w), match.shape),
            match, cap, want_counts=False)
        valid = jnp.arange(cap, dtype=jnp.int32) < total[..., None]
        slot = words // nk
        ts = sum(jnp.where(slot == s, win_end[..., s:s + 1], 0)
                 for s in range(w))
        return (zero_invalid(RecordBatch(words % nk, values, ts, valid)),
                jnp.maximum(total - cap, 0))

    def process2(self, state, left, right, ctx):
        lcol, lv = self._placed(state["cols"], left)
        rcol, rv = self._placed(state["cols"], right)

        def fire_first(tables, win, max_l, max_r, anchor, l, lok, r, rok):
            max_l = jnp.maximum(max_l, jnp.max(
                jnp.where(lok, l.timestamps, _NO_TS)))
            max_r = jnp.maximum(max_r, jnp.max(
                jnp.where(rok, r.timestamps, _NO_TS)))
            wm, anchor = self._watermark(max_l, max_r, anchor)
            win_end, fire = self._fire_step(win, wm)              # [W]
            cl, cr, sr = tables
            match = (fire[:, None] & (cl > 0) & (cr > 0)).reshape(-1)
            tables = tuple(jnp.where(fire[:, None], 0, t) for t in tables)
            win = jnp.where(fire, _NO_WINDOW, win)
            return (tables, win, max_l, max_r, anchor, wm, win_end, match,
                    sr.reshape(-1))

        def assign(tables, win, wm, l, lcol, lok, r, rcol, rok):
            cl, cr, sr = tables
            win, placed, l_any = self._place_step(
                win, wm, lok, l.timestamps, bounded=True)
            for slot, ok in placed:
                cl = cl.at[slot, lcol].add(ok.astype(jnp.int32),
                                           mode="drop")
            win, placed, r_any = self._place_step(
                win, wm, rok, r.timestamps, bounded=True)
            for slot, ok in placed:
                cr = cr.at[slot, rcol].add(ok.astype(jnp.int32),
                                           mode="drop")
                sr = sr.at[slot, rcol].add(jnp.where(ok, r.values, 0),
                                           mode="drop")
            n = lambda m: jnp.sum(m.astype(jnp.int32))
            return ((cl, cr, sr), win, n(lok & ~l_any) + n(rok & ~r_any),
                    n(l_any), n(r_any))

        tables = tuple(state[k] for k in self._TABLES)
        (tables, win, max_l, max_r, anchor, wm, win_end, match,
         sums) = jax.vmap(fire_first)(
            tables, state["win"], state["max_ts_left"],
            state["max_ts_right"], state["anchor"], left, lv, right, rv)
        out, dropped = self._emit(match, sums, win_end, state["cols"])
        tables, win, late, n_left, n_right = jax.vmap(assign)(
            tables, win, wm, left, lcol, lv, right, rcol, rv)
        new = dict(state, **dict(zip(self._TABLES, tables)))
        new.update(
            win=win, max_ts_left=max_l, max_ts_right=max_r, anchor=anchor,
            late=state["late"] + late, dropped=state["dropped"] + dropped,
            fired=state["fired"] + out.count(),
            left_records=state["left_records"] + n_left,
            right_records=state["right_records"] + n_right)
        return new, out

    def process_block(self, state, batches, bctx):
        left, right = batches
        K, p, _ = left.keys.shape
        w, c = self.open_windows, self._columns
        lcol, lv = self._placed(state["cols"], left)
        rcol, rv = self._placed(state["cols"], right)
        max_l = self._block_max_ts(state["max_ts_left"], lv,
                                   left.timestamps)               # [K, P]
        max_r = self._block_max_ts(state["max_ts_right"], rv,
                                   right.timestamps)
        # the anchor a step finds: the state's, or what the first step
        # that brought a record set (``_watermark`` on it sets the same)
        first = self._anchor_reading(max_l, max_r)
        at = jnp.argmax(first != _NO_TS, axis=0)                  # [P]
        since = jnp.arange(K, dtype=jnp.int32)[:, None] > at[None]
        anchor = jnp.where(
            state["anchor"] != _NO_TS, state["anchor"], jnp.where(
                since, jnp.take_along_axis(first, at[None], axis=0),
                _NO_TS))
        wm, anchor = self._watermark(max_l, max_r, anchor)

        cl, _, taken_l, l_any = self._block_place(
            lv, lcol, jnp.ones_like(left.values), left.timestamps, wm,
            bounded=True, nk=c)
        sr, cr, taken_r, r_any = self._block_place(
            rv, rcol, right.values, right.timestamps, wm,
            want_counts=True, bounded=True, nk=c)
        held, win_end, fire = self._block_slots(
            state["win"], jnp.maximum(taken_l, taken_r), wm)
        fire_l = self._block_lanes(fire, c)                    # [K,P,W*C]
        acc, emit = {}, {}
        for k, contrib in zip(self._TABLES, (cl, cr, sr)):
            acc[k], emit[k] = self._block_accumulate(
                state[k].reshape(p, w * c), contrib, fire_l)
        out, dropped = self._emit(
            fire_l & (emit["left"] > 0) & (emit["right"] > 0), emit["sum"],
            win_end, state["cols"])
        n = lambda m: jnp.sum(m.astype(jnp.int32), axis=(0, 2))
        new = dict(state, **{k: acc[k][-1].reshape(p, w, c)
                             for k in self._TABLES})
        new.update(
            win=held[-1], max_ts_left=max_l[-1], max_ts_right=max_r[-1],
            anchor=anchor[-1],
            late=state["late"] + n(lv & ~l_any) + n(rv & ~r_any),
            dropped=state["dropped"] + dropped.sum(axis=0),
            fired=state["fired"] + out.count().sum(axis=0),
            left_records=state["left_records"] + n(l_any),
            right_records=state["right_records"] + n(r_any))
        return new, out


@dataclasses.dataclass
class EventTimeWindowTopOperator(_OwnColumns, _EventTimeSlots, Operator):
    """Event-time windowed sum per key, sliding or tumbling, of which a
    window that fires emits only its LARGEST: one row ``(key, sum,
    window end - 1)`` for every key whose non-zero sum equals the largest
    non-zero sum the subtask holds for that window, ties kept (NEXmark
    query 5, "Hot Items": ``num >= max(num)`` per window — a first stage
    behind ``key_by()`` sums per key and passes on each subtask's
    largest, a second at parallelism 1 sums those rows per key, each key
    owned by one subtask, and keeps the largest over all of them:
    Flink's ``windowAll``, Beam's ``Combine.globally``). A row is
    stamped with its window's last millisecond — Flink's
    ``maxTimestamp()`` — so that a window of the same grid downstream
    takes it for the same window, which is what lets the second stage
    be this operator again, tumbling by the first one's slide.

    Watermark, slots and fire are :class:`_EventTimeSlots`'s, the
    batched-watermark rule and the late count :class:`EventTimeWindow`'s
    (``wm = max(event_ts seen) - out_of_orderness``, advanced once a
    step before the step's records are assigned; windows with end <= wm
    fire FIRST). Each fire's rows are compacted in (slot, column) order
    into ``capacity`` rows a subtask a step; rows past it are dropped
    and counted in ``dropped``. ``late`` counts the records no window
    took — behind the watermark, or with a key this subtask holds no
    column for (below) — and ``fired`` the rows emitted. ``late`` and
    ``dropped`` are ``fence_losses``: a non-zero one stops the run at
    the next fence.

    **Own columns.** The table is ``[open_windows, columns]`` a subtask,
    and ``state["cols"]`` (int32 ``[P, columns]``, ascending, then
    :data:`NO_KEY`) says which key each column holds. Without
    ``own_columns`` there is a column for every key in ``[0, num_keys)``
    on every subtask (dense). With it a subtask holds ``own_columns``
    columns, bound by the planner to the keys that subtask owns
    (``CompiledJob._plan_edges``; the job must reach this vertex through
    ``key_by()``): a table over 8,192 ids at parallelism 16 is 640
    columns wide, not 8,192. The binding is state — one operator object
    serves any number of plans, a checkpoint carries it — and a record
    whose key has no column on its subtask is counted in ``late``, never
    read as no record. A record's column is its key's rank among the
    subtask's bound keys, by comparison with all of them (no gather by a
    computed index); the block form has no scan over the steps and no
    scatter, and agrees with the step form bit for bit. It looks up
    only the slots a target can fill — a 128-slot head of every receive
    window and the rest of a step's two fullest (:class:`_OwnColumns`)
    — where the window is wide enough for that to halve the work;
    ``dense_blocks`` counts the blocks that compared every slot all the
    same.
    """

    num_keys: int
    window_size: int
    slide: int
    out_of_orderness: int = 0
    capacity: int = 16
    own_columns: Optional[int] = None
    open_windows: int = 2

    emits_received_keys = True

    fence_totals = EventTimeWindow.fence_totals + (
        ("dropped", "window.dropped_rows"),
        ("dense_blocks", "lookup.dense_blocks"))
    fence_losses = ("late", "dropped")

    def __post_init__(self):
        if self.window_size % self.slide:
            raise ValueError("window_size must be a multiple of slide")
        need = (self.out_of_orderness + self.window_size) // self.slide + 2
        self.open_windows = max(self.open_windows, need)

    @property
    def _slide(self) -> int:
        return self.slide

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.capacity

    def init_state(self, parallelism: int):
        p, w, c = parallelism, self.open_windows, self._columns
        state = {k: jnp.zeros((p,), jnp.int32) for k, _ in self.fence_totals}
        state.update(
            acc=jnp.zeros((p, w, c), jnp.int32),
            win=jnp.full((p, w), _NO_WINDOW, jnp.int32),
            max_ts=jnp.full((p,), _NO_TS, jnp.int32),
            cols=self._init_cols(p))
        return state

    @scoped("emit")
    def _emit(self, acc, fire, win_end, cols):
        """The rows of one or many steps: of the accumulators ``acc
        [..., P, W * C]`` as the fire finds them, the lanes of a firing
        slot (``fire``, ``win_end``: ``[..., P, W]``) that hold its
        largest non-zero sum, compacted in lane order into ``[..., P,
        capacity]`` rows, and how many did not fit ``[..., P]``. A row's
        place is its rank among those lanes, so the compaction is one
        keyed histogram over ranks that carries ``slot * num_keys + key``
        (a lane is its slot and its column, a column its key); sum and
        stamp are its slot's."""
        from clonos_tpu.ops.histogram import keyed_hist
        from clonos_tpu.ops.matops import running_count
        nk, w, c, cap = (self.num_keys, self.open_windows, self._columns,
                         self.capacity)
        by_slot = acc.reshape(acc.shape[:-1] + (w, c))
        none = jnp.iinfo(jnp.int32).min           # a slot with no sum
        top = jnp.max(jnp.where(by_slot != 0, by_slot, none),
                      axis=-1)                                # [..., P, W]
        match = ((fire & (top != none))[..., None]
                 & (by_slot == top[..., None])).reshape(acc.shape)
        word = self._lane_words(cols, w)                      # [P, W * C]
        rank = running_count(match) - 1
        total = rank[..., -1] + 1
        words, _ = keyed_hist(rank, jnp.broadcast_to(word, match.shape),
                              match, cap, want_counts=False)
        valid = jnp.arange(cap, dtype=jnp.int32) < total[..., None]
        slot = words // nk
        of_slot = lambda x: sum(
            jnp.where(slot == s, x[..., s:s + 1], 0) for s in range(w))
        return (zero_invalid(RecordBatch(words % nk, of_slot(top),
                                         of_slot(win_end - 1), valid)),
                jnp.maximum(total - cap, 0))

    def process(self, state, batch, ctx):
        p, w, c = batch.keys.shape[0], self.open_windows, self._columns
        col, held = self._column(state["cols"], batch.keys)

        def fire_first(acc, win, max_ts, b: RecordBatch):
            max_ts = jnp.maximum(max_ts, jnp.max(
                jnp.where(b.valid, b.timestamps, _NO_TS)))
            wm = max_ts - self.out_of_orderness
            win_end, fire = self._fire_step(win, wm)              # [W]
            return (jnp.where(fire[:, None], 0, acc),
                    jnp.where(fire, _NO_WINDOW, win), max_ts, wm, win_end,
                    fire)

        def assign(acc, win, wm, b: RecordBatch, col, held):
            win, placed, ok_any = self._place_step(
                win, wm, b.valid & held, b.timestamps)
            for slot, ok in placed:
                acc = acc.at[slot, col].add(jnp.where(ok, b.values, 0),
                                            mode="drop")
            return acc, win, jnp.sum((b.valid & ~ok_any).astype(jnp.int32))

        acc, win, max_ts, wm, win_end, fire = jax.vmap(fire_first)(
            state["acc"], state["win"], state["max_ts"], batch)
        out, dropped = self._emit(state["acc"].reshape(p, w * c), fire,
                                  win_end, state["cols"])
        acc, win, late = jax.vmap(assign)(acc, win, wm, batch, col, held)
        return dict(
            state, acc=acc, win=win, max_ts=max_ts,
            late=state["late"] + late, fired=state["fired"] + out.count(),
            dropped=state["dropped"] + dropped), out

    def process_block(self, state, batches, bctx):
        p = batches.keys.shape[1]
        w, c = self.open_windows, self._columns
        valid = batches.valid
        (col, held), crowded = self._column_block(
            state["cols"], batches.keys, valid)
        max_ts = self._block_max_ts(state["max_ts"], valid,
                                    batches.timestamps)
        wm = max_ts - self.out_of_orderness                       # [K, P]
        contrib, _, taken, ok_any = self._block_place(
            valid & held, col, batches.values, batches.timestamps, wm,
            nk=c)
        held_win, win_end, fire = self._block_slots(state["win"], taken, wm)
        acc, found = self._block_accumulate(
            state["acc"].reshape(p, w * c), contrib,
            self._block_lanes(fire, c))
        out, dropped = self._emit(found, fire, win_end, state["cols"])
        n = lambda m: jnp.sum(m.astype(jnp.int32), axis=(0, 2))
        return dict(
            state, acc=acc[-1].reshape(p, w, c), win=held_win[-1],
            max_ts=max_ts[-1], late=state["late"] + n(valid & ~ok_any),
            fired=state["fired"] + out.count().sum(axis=0),
            dropped=state["dropped"] + dropped.sum(axis=0),
            dense_blocks=self._dense_blocks(state, crowded)), out


#: "no record yet": the fold's identity for an earliest timestamp
_NO_LO = 2 ** 31 - 1


def _in_front(x: jnp.ndarray, *first: jnp.ndarray) -> jnp.ndarray:
    """``x`` with the steps ``first`` in front of it along axis 0, as an
    array of its own: the barrier keeps the concatenation out of the
    fusions of the scan it feeds. Compiled for the v5e inside a job's
    block program — never alone — a restarting sum over ``[state's two
    steps, 1,024 steps]`` came out wrong from step 640 on (a restart
    missed) when the compiler fused the concatenation into the scan's
    first level; with the operand materialised it is right (PR 40:
    PERF.md section 6 has the chip runs that found it)."""
    return jax.lax.optimization_barrier(
        jnp.concatenate([f[None] for f in first] + [x], axis=0))


def _shifted(x: jnp.ndarray, first: jnp.ndarray) -> jnp.ndarray:
    """``x`` one step later along axis 0, ``first`` in front: what the
    step before left."""
    return _in_front(x[:-1], first)


def _running_max(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running maximum along axis 0, as an associative scan:
    ``lax.cummax`` over the 8,194 steps of a recovery block with the
    state's two in front took the v5e's compiler 96 s, this 2 (PR 40)."""
    return jax.lax.associative_scan(jnp.maximum, x, axis=0)


def _last_flagged(flag: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """Along axis 0, the value at the latest step whose ``flag`` is set,
    this step's included (0 before the first)."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, va)
    return jax.lax.associative_scan(
        combine, (flag, jnp.where(flag, values, 0)), axis=0)[1]


def _segmented_any_min(reset: jnp.ndarray, hit: jnp.ndarray,
                       low: jnp.ndarray):
    """Along axis 0, within the segment a set ``reset`` opens: whether
    ``hit`` was set so far, and the smallest ``low`` so far (both this
    step's included)."""
    def combine(a, b):
        fa, ha, la = a
        fb, hb, lb = b
        return (fa | fb, jnp.where(fb, hb, ha | hb),
                jnp.where(fb, lb, jnp.minimum(la, lb)))
    return jax.lax.associative_scan(combine, (reset, hit, low), axis=0)[1:]


@dataclasses.dataclass
class SessionWindowOperator(_OwnColumns, Operator):
    """Event-time session windows per key, Flink's merging sessions
    (``EventTimeSessionWindows.withGap``; NEXmark query 11, "User
    Sessions"): a record opens ``[ts, ts + gap)``, windows of one key
    that touch or overlap merge (``|ts2 - ts1| <= gap`` merges), and a
    session fires at the first step whose watermark reaches its end —
    one row ``(key, sum of values, last timestamp + gap)``, none for a
    sum of 0.

    Watermark: :class:`EventTimeWindow`'s batched rule — ``wm = max(
    event_ts seen) - out_of_orderness``, advanced once a step BEFORE the
    step's records are taken; sessions whose end the watermark has
    reached fire FIRST, so a record within ``gap`` of a session that
    fires this step opens a new one (Flink: a fired window merges no
    more).

    **Two open sessions a key.** A record may open a session while its
    key's older one still waits for the watermark, and a record that
    touches both merges them into one (the bridge). A third cannot
    exist: an open session's last timestamp lies above ``wm - gap``, the
    next session starts more than ``gap`` above that, so above ``wm``,
    and a third more than ``gap`` above that, so above ``wm + gap >
    max_ts`` when ``gap > out_of_orderness`` — the constructor refuses
    ``gap <= out_of_orderness``.

    **What a step takes.** A record whose own window ``[ts, ts + gap)``
    ends at or behind the watermark is late (Flink would still take it
    into an open session it touches; within the bound, ``ts >= max_ts -
    out_of_orderness``, there is no such record), as is one whose key
    this subtask holds no column for. The records of one key taken in
    one step are one *arrival* ``[earliest, latest]``. An arrival more
    than ``gap`` above everything its key has seen, or behind a newest
    session that has fired, opens a session; any other joins the key's
    newest session, and merges the older open one into it if it reaches
    that too. For records within the bound this is Flink's result
    exactly (they lie within ``out_of_orderness < gap`` of each other
    and no further below the newest session). An arrival that spreads
    over more than ``gap``, or lies more than ``gap`` below the earliest
    arrival of its newest session's current run, is merged all the same
    and counted in ``disordered``: Flink might have kept two sessions.
    ``late``, ``disordered`` and ``dropped`` (rows past ``capacity``)
    are ``fence_losses``: a non-zero one stops the run at the next
    fence.

    **Table.** Per subtask and column the older session's ``(sum,
    latest)`` and the newest one's ``(sum, earliest of its run,
    latest)``; ``state["cols"]`` says which key a column holds, dense
    (every key on every subtask) or, with ``own_columns``, the keys the
    planner binds (:class:`EventTimeWindowTopOperator` says how). Rows
    are compacted, older sessions' in column order and then newest
    ones', into ``capacity`` rows a subtask a step (default: two a
    column, which drops none). ``open_peak`` is the most sessions a
    subtask has held open after a step.

    The block form has no scan over the steps, no scatter and no gather
    by a computed index: an arrival's count, sum, earliest and latest
    come from the comparison of the step's records with the subtask's
    columns — of the slots a target can fill, a 128-slot head of every
    receive window and the rest of a step's two fullest, where the
    window is wide enough for that to halve the work
    (:class:`_OwnColumns`; ``dense_blocks`` counts the blocks that
    compared every slot all the same); the newest session's latest is
    a running maximum along the steps, a session starts where an
    arrival lies more than ``gap`` above it (a *break*) or the
    watermark has passed it, a break is
    undone when a later arrival reaches back across it while the older
    session is still open, sums are running sums that restart at the
    breaks that stand, and a session's row leaves at the one step where
    the watermark crosses its end. It agrees with the step form bit for
    bit.
    """

    num_keys: int
    gap: int
    out_of_orderness: int = 0
    capacity: Optional[int] = None
    own_columns: Optional[int] = None

    emits_received_keys = True

    fence_totals = EventTimeWindow.fence_totals + (
        ("dropped", "window.dropped_rows"),
        ("disordered", "window.disordered_arrivals"),
        ("dense_blocks", "lookup.dense_blocks"))
    fence_losses = ("late", "dropped", "disordered")
    fence_peaks = (("open_peak", "window.open_sessions"),)

    def __post_init__(self):
        if self.gap <= self.out_of_orderness:
            raise ValueError(
                f"a session gap of {self.gap} within the out-of-orderness "
                f"bound {self.out_of_orderness}: a key could hold a third "
                f"open session, and this operator keeps two")

    @property
    def out_capacity(self):  # type: ignore[override]
        return (2 * self._columns if self.capacity is None
                else self.capacity)

    def init_state(self, parallelism: int):
        p, c = parallelism, self._columns
        state = {k: jnp.zeros((p,), jnp.int32)
                 for k, _ in self.fence_totals + self.fence_peaks}
        state.update(
            a_sum=jnp.zeros((p, c), jnp.int32),
            a_hi=jnp.full((p, c), _NO_TS, jnp.int32),
            b_sum=jnp.zeros((p, c), jnp.int32),
            b_lo=jnp.full((p, c), _NO_LO, jnp.int32),
            b_hi=jnp.full((p, c), _NO_TS, jnp.int32),
            max_ts=jnp.full((p,), _NO_TS, jnp.int32),
            cols=self._init_cols(p))
        return state

    def _watermark(self, max_ts):
        return jnp.where(max_ts == _NO_TS, _NO_TS,
                         max_ts - self.out_of_orderness)

    def _took(self, b: RecordBatch, wm):
        """The records of ``b [..., P, B]`` a step takes under the
        watermark ``wm [..., P]``."""
        return b.valid & (b.timestamps + self.gap > wm[..., None])

    @staticmethod
    def _fold(cols, keys, values, ts, took):
        """``(count, sum, earliest, latest)`` per column ``[..., C]`` of
        the records ``[..., B]`` that ``took`` marks, a row of columns
        ``cols [..., C]`` a row of records (no record: 0, 0, ``_NO_LO``,
        ``_NO_TS``). One comparison of every record with every column of
        its row carries all four."""
        m = took[..., None] & (keys[..., None] == cols[..., None, :])
        ts = ts[..., None]                              # [..., B, C]
        # one pass over the pairs: a reduction with four results (four
        # reductions of their own each compare every pair again: on the
        # v5e 128 ms a block of the cell's 9.4e9 pairs, half of it the
        # count)
        return jax.lax.reduce(
            (m.astype(jnp.int32), jnp.where(m, values[..., None], 0),
             jnp.where(m, ts, _NO_LO), jnp.where(m, ts, _NO_TS)),
            (jnp.int32(0), jnp.int32(0), jnp.int32(_NO_LO),
             jnp.int32(_NO_TS)),
            lambda x, y: (x[0] + y[0], x[1] + y[1],
                          jnp.minimum(x[2], y[2]), jnp.maximum(x[3], y[3])),
            (m.ndim - 2,))

    @staticmethod
    def _bound_arrivals(cols, b: RecordBatch, n, s, lo, hi):
        """What :meth:`_arrivals` returns, of the fold of ``b``: a column
        bound to no key holds nothing, whatever key matched it, and a
        valid record no column took is late."""
        bound = cols != NO_KEY
        late = (jnp.sum(b.valid.astype(jnp.int32), axis=-1)
                - jnp.sum(jnp.where(bound, n, 0), axis=-1))
        return (jnp.where(bound, s, 0), jnp.where(bound, lo, _NO_LO),
                jnp.where(bound, hi, _NO_TS), late)

    @scoped("lookup")
    def _arrivals(self, cols, b: RecordBatch, wm):
        """``(sum, earliest, latest)`` per column ``[..., P, C]`` of the
        records of ``b [..., P, B]`` a step takes under the watermark
        ``wm [..., P]`` (no record: 0, ``_NO_LO``, ``_NO_TS``), and how
        many it does not take ``[..., P]``: every record against every
        column of its subtask."""
        return self._bound_arrivals(cols, b, *self._fold(
            cols, b.keys, b.values, b.timestamps, self._took(b, wm)))

    @scoped("lookup")
    def _arrivals_block(self, cols, b: RecordBatch, wm):
        """:meth:`_arrivals` of a block ``b [K, P, B]``, on head and
        tails (:class:`_OwnColumns`): the tails' folds go back to their
        targets and into the head's with ``+``, ``+``, ``min``, ``max``
        — the same four numbers a column, bit for bit — and whether the
        block was crowded."""
        fields = (b.keys, b.values, b.timestamps, self._took(b, wm))

        def split(parts):
            n, s, lo, hi = self._fold(cols, *map(parts.head, fields))
            tn, ts, tlo, thi = self._fold(parts.own(cols),
                                          *map(parts.tails, fields))
            return (n + parts.back(tn, 0), s + parts.back(ts, 0),
                    jnp.minimum(lo, parts.back(tlo, _NO_LO)),
                    jnp.maximum(hi, parts.back(thi, _NO_TS)))
        folded, crowded = self._by_head_and_tails(
            fields[3], lambda: self._fold(cols, *fields), split)
        return self._bound_arrivals(cols, b, *folded), crowded

    @scoped("emit")
    def _emit(self, cols, fire, sums, ends):
        """The rows of one or many steps: the lanes ``fire [..., P, 2 *
        C]`` (the older sessions' columns, then the newest ones') with
        their ``sums`` and ``ends``, compacted in lane order into
        ``[..., P, capacity]`` rows, and how many did not fit ``[...,
        P]``. A row's place is its rank among the firing lanes: three
        keyed histograms over ranks carry key, sum and end."""
        from clonos_tpu.ops.histogram import keyed_hist
        from clonos_tpu.ops.matops import running_count
        cap = self.out_capacity
        rank = running_count(fire) - 1
        total = rank[..., -1] + 1
        key = jnp.where(cols != NO_KEY, cols, 0)
        key = jnp.broadcast_to(jnp.concatenate([key, key], axis=-1),
                               fire.shape)
        keys, values, stamps = (
            keyed_hist(rank, x, fire, cap, want_counts=False)[0]
            for x in (key, sums, ends))
        valid = jnp.arange(cap, dtype=jnp.int32) < total[..., None]
        return (zero_invalid(RecordBatch(keys, values, stamps, valid)),
                jnp.maximum(total - cap, 0))

    def process(self, state, batch, ctx):
        gap = self.gap
        both = lambda a, b: jnp.concatenate([a, b], axis=-1)
        max_ts = jnp.maximum(state["max_ts"], jnp.max(
            jnp.where(batch.valid, batch.timestamps, _NO_TS), axis=-1))
        wm = self._watermark(max_ts)                              # [P]
        a_sum, a_hi = state["a_sum"], state["a_hi"]
        b_sum, b_lo, b_hi = state["b_sum"], state["b_lo"], state["b_hi"]
        # FIRE FIRST: every open session whose end the watermark has
        # reached, the older ones' rows before the newest ones'
        fire_a = (a_hi != _NO_TS) & (a_hi + gap <= wm[:, None])
        fire_b = (b_hi != _NO_TS) & (b_hi + gap <= wm[:, None])
        out, dropped = self._emit(
            state["cols"], both(fire_a & (a_sum != 0), fire_b & (b_sum != 0)),
            both(a_sum, b_sum), both(a_hi, b_hi) + gap)
        a_sum, a_hi = jnp.where(fire_a, 0, a_sum), jnp.where(fire_a, _NO_TS,
                                                             a_hi)
        b_sum, b_lo, b_hi = (jnp.where(fire_b, 0, b_sum),
                             jnp.where(fire_b, _NO_LO, b_lo),
                             jnp.where(fire_b, _NO_TS, b_hi))
        # then the step's arrivals, a column at a time
        s, lo, hi, late = self._arrivals(state["cols"], batch, wm)
        some = hi != _NO_TS
        opens = some & ((b_hi == _NO_TS) | (lo > b_hi + gap))
        joins = some & ~opens
        bridges = joins & (a_hi != _NO_TS) & (lo <= a_hi + gap)
        disordered = some & ((hi - lo > gap) | (joins & (hi + gap < b_lo)))
        # a session that opens makes the newest one the older (which, by
        # the class docstring, has fired); a bridge folds the older in
        new = dict(
            a_sum=jnp.where(opens, b_sum, jnp.where(bridges, 0, a_sum)),
            a_hi=jnp.where(opens, b_hi, jnp.where(bridges, _NO_TS, a_hi)),
            b_sum=jnp.where(opens, s, jnp.where(
                joins, b_sum + s + jnp.where(bridges, a_sum, 0), b_sum)),
            b_lo=jnp.where(opens, lo, jnp.minimum(b_lo, lo)),
            b_hi=jnp.where(opens, hi, jnp.maximum(b_hi, hi)))
        n = lambda m: jnp.sum(m.astype(jnp.int32), axis=-1)
        held = n(new["a_hi"] != _NO_TS) + n(new["b_hi"] != _NO_TS)
        return dict(
            state, **new, max_ts=max_ts, late=state["late"] + late,
            fired=state["fired"] + out.count(),
            dropped=state["dropped"] + dropped,
            disordered=state["disordered"] + n(disordered),
            open_peak=jnp.maximum(state["open_peak"], held)), out

    def process_block(self, state, batches, bctx):
        gap = self.gap
        both = lambda a, b: jnp.concatenate([a, b], axis=-1)
        n = lambda m: jnp.sum(m.astype(jnp.int32), axis=-1)
        max_ts = _EventTimeSlots._block_max_ts(
            self, state["max_ts"], batches.valid, batches.timestamps)
        wm, wm0 = self._watermark(max_ts), self._watermark(state["max_ts"])
        (s, lo, hi, late), crowded = self._arrivals_block(
            state["cols"], batches, wm)
        # Two steps that stand for the state go in front — the older
        # open session's sum and latest as an arrival, then the newest
        # one's — so every array from here on is [K + 2, P, C].
        s = _in_front(s, state["a_sum"], state["b_sum"])
        lo = _in_front(lo, state["a_hi"], state["b_lo"])
        hi = _in_front(hi, state["a_hi"], state["b_hi"])
        w = _in_front(wm, wm0, wm0)[:, :, None]
        none = jnp.full_like(hi[0], _NO_TS)
        some = hi != _NO_TS
        with jax.named_scope("place"):
            # the newest session's latest after each step: a running
            # maximum (a newer record is in it, or in a newer session)
            top = _running_max(hi)
            before = _shifted(top, none)
            # a run of arrivals starts where one lies more than ``gap``
            # above everything before it, or the watermark has passed
            # that ...
            run = some & ((before == _NO_TS) | (lo > before + gap)
                          | (w >= before + gap))
            a_top = _running_max(jnp.where(run, before, _NO_TS))
            # ... and is one session with the run before it once an
            # arrival reaches back to that while it is still open
            reach = (some & ~run & (a_top != _NO_TS) & (lo <= a_top + gap)
                     & (w < a_top + gap))
            bridged, run_lo = _segmented_any_min(run, reach, lo)
            # the starts no later arrival undoes: a pass from the
            # block's end, run by run, for whether any arrival of the
            # run reached back
            ahead = _segmented_cumsum(
                reach[::-1].astype(jnp.int32),
                _shifted(run[::-1], jnp.ones_like(run[0])))[::-1]
            starts = run & (ahead == 0)
        with jax.named_scope("segsum"):
            total = _segmented_cumsum(s, starts)
            # the session before the newest one: what the newest was
            # when the last start that stands came
            a_sum = _last_flagged(
                starts, _shifted(total, jnp.zeros_like(s[0])))
            a_hi = _running_max(jnp.where(starts, before, _NO_TS))
        # a session's row leaves at the one step at which the watermark
        # crosses its end, as the step before left the session
        crossed = lambda h: ((h[1:-1] != _NO_TS) & (w[2:] >= h[1:-1] + gap)
                             & (h[1:-1] + gap > w[1:-1]))
        fire_a, fire_b = crossed(a_hi), crossed(top)
        out, dropped = self._emit(
            state["cols"],
            both(fire_a & (a_sum[1:-1] != 0), fire_b & (total[1:-1] != 0)),
            both(a_sum[1:-1], total[1:-1]),
            both(a_hi[1:-1], top[1:-1]) + gap)
        # after each step the newest session is open while the watermark
        # has not reached its end, and the run before it while that
        # holds for it too and no arrival has merged the two
        open_b = (top != _NO_TS) & (top + gap > w)
        open_a = (a_top != _NO_TS) & (a_top + gap > w) & ~bridged
        disordered = some[2:] & (
            (hi[2:] - lo[2:] > gap)
            | (~run[2:] & (hi[2:] + gap < run_lo[1:-1])))
        held = jnp.max(n(open_a[2:]) + n(open_b[2:]), axis=0)
        return dict(
            state,
            a_sum=jnp.where(open_a[-1], a_sum[-1], 0),
            a_hi=jnp.where(open_a[-1], a_top[-1], _NO_TS),
            b_sum=jnp.where(open_b[-1], total[-1], 0),
            b_lo=jnp.where(open_b[-1], run_lo[-1], _NO_LO),
            b_hi=jnp.where(open_b[-1], top[-1], _NO_TS),
            max_ts=max_ts[-1], late=state["late"] + late.sum(axis=0),
            fired=state["fired"] + out.count().sum(axis=0),
            dropped=state["dropped"] + dropped.sum(axis=0),
            disordered=state["disordered"] + n(disordered).sum(axis=0),
            open_peak=jnp.maximum(state["open_peak"], held),
            dense_blocks=self._dense_blocks(state, crowded)), out


def _pack_by_rank(mask: jnp.ndarray, fields, width: int):
    """``fields [..., N]`` of the lanes ``mask`` marks, packed to the
    front of ``[..., width]`` in lane order, and how many were marked
    ``[...]`` (past ``width`` they do not fit). A lane's place is its
    rank among the marked, so the packing is one keyed histogram over
    ranks a field: no sort, no gather."""
    from clonos_tpu.ops.histogram import keyed_hist
    from clonos_tpu.ops.matops import running_count
    rank = running_count(mask) - 1
    return (tuple(keyed_hist(rank, x, mask, width, want_counts=False)[0]
                  for x in fields), rank[..., -1] + 1)


class _ChunkedJoin(_OwnColumns, TwoInputOperator):
    """What the two-input operators on own columns that take a block in
    chunks of steps share (:class:`IncrementalJoinOperator`,
    :class:`BestInIntervalJoinOperator`): the two-input watermark, the
    records off the id ring, a chunk's records packed to the front, its
    rows as histogram lanes ``step x capacity + rank``, the step form as
    a chunk of one step, and the block form — a loop over the chunks,
    none over the steps, in which a chunk that is not *quiet* runs step
    by step under a ``lax.cond`` and is counted (``step_chunks``), so
    that the block form is the step form bit for bit on any input.

    An operator says what a chunk does — :meth:`_chunk`, exact for one
    step always and for more while the chunk is quiet in the operator's
    own sense — which of its state a chunk works on (``_CORE``, with the
    ``ring_too_small`` and ``step_chunks`` counters), how many receive
    windows a chunk's records are packed into a side
    (``_PACKED_WINDOWS``) and how many lanes its row operands have
    (:meth:`_row_lanes`)."""

    num_keys: int
    out_of_orderness: int
    capacity: int

    #: steps of a chunk, at most (short enough that what an operator
    #: keeps for a while — a ttl, an interval — outlasts or fits it)
    _CHUNK_STEPS = 32
    _PACKED_WINDOWS: Tuple[int, int]
    _CORE: Tuple[str, ...]

    def _chunk(self, core, left, right, wm):
        """One chunk of ``S`` steps over every subtask: ``core``, the
        chunk's records of each side in (step, slot) order as ``(key,
        value, timestamp, step, valid)`` of ``[P, M]`` and ``wm [S, P]``
        -> the new ``core``, the chunk's rows as histogram operands
        ``(lane, key, value, timestamp, valid) [P, N]``, the rows a step
        emits ``[P, S]`` and which subtasks the chunk was not quiet for
        ``[P]``."""
        raise NotImplementedError

    def _row_lanes(self, widths) -> int:
        """``N`` of :meth:`_chunk`'s row operands, given the packed
        widths of the two sides."""
        raise NotImplementedError


    @property
    def out_capacity(self):  # type: ignore[override]
        return self.capacity

    def _in_range(self, b: RecordBatch):
        return b.valid & (b.keys >= 0) & (b.keys < self.num_keys)

    def _watermark(self, max_l, max_r):
        lo = jnp.minimum(max_l, max_r)       # _NO_TS while an input is silent
        return jnp.where(lo != _NO_TS, lo - self.out_of_orderness, _NO_TS)

    def _chunk_of(self, steps: int) -> int:
        """Steps a chunk of a block of ``steps`` takes: they divide the
        block, and a chunk's rows fit the histogram's lanes."""
        from clonos_tpu.ops.histogram import KERNEL_MAX_KEYS
        most = max(1, min(self._CHUNK_STEPS,
                          KERNEL_MAX_KEYS // self.capacity))
        return max(s for s in range(1, most + 1) if steps % s == 0)

    @scoped("emit")
    def _rows_of(self, rows, emitted, steps: int) -> RecordBatch:
        """The rows ``_chunk`` hands back as batches ``[..., P, steps,
        capacity]``: a row's place is its lane."""
        from clonos_tpu.ops.histogram import keyed_hist
        lane, key, val, ts, ok = rows
        cap = self.capacity
        key, val, ts = (
            keyed_hist(lane, x, ok, steps * cap, want_counts=False)[0]
            .reshape(lane.shape[:-1] + (steps, cap)) for x in (key, val, ts))
        valid = jnp.arange(cap, dtype=jnp.int32) < emitted[..., None]
        return zero_invalid(RecordBatch(key, val, ts, valid))

    def _step(self, core, left, right, wm):
        """One step, exactly: ``core``, the two receive windows ``[P,
        B]`` and ``wm [P]`` -> ``(core, rows [P, capacity])``."""
        zero = jnp.zeros_like(left.keys)
        as_chunk = lambda b: (b.keys, b.values, b.timestamps, zero,
                              self._in_range(b))
        core, rows, emitted, _ = self._chunk(
            core, as_chunk(left), as_chunk(right), wm[None])
        out = self._rows_of(rows, emitted, 1)
        return core, jax.tree_util.tree_map(lambda x: x[:, 0], out)

    def _top_ts(self, b: RecordBatch):
        """The largest timestamp among a receive window's records."""
        return jnp.max(jnp.where(self._in_range(b), b.timestamps, _NO_TS),
                       axis=-1)

    def _core(self, state, left, right, steps_axis=()):
        """The part of ``state`` a chunk works on, with the records whose
        key lies off the ring counted."""
        core = {k: state[k] for k in self._CORE}
        core["ring_too_small"] = core["ring_too_small"] + sum(
            jnp.sum((b.valid & ~self._in_range(b)).astype(jnp.int32),
                    axis=steps_axis + (-1,)) for b in (left, right))
        return core

    def process2(self, state, left, right, ctx):
        max_l = jnp.maximum(state["max_ts_left"], self._top_ts(left))
        max_r = jnp.maximum(state["max_ts_right"], self._top_ts(right))
        core, out = self._step(self._core(state, left, right), left, right,
                               self._watermark(max_l, max_r))
        return dict(state, **core, max_ts_left=max_l,
                    max_ts_right=max_r), out

    @scoped("compact")
    def _packed(self, b: RecordBatch, chunks: int, width: int):
        """A block's records chunk by chunk, packed to the front in
        (step, slot) order: ``(key, value, timestamp, step, valid)`` of
        ``[chunks, P, width]``, and how many a chunk brought ``[chunks,
        P]`` (past ``width`` they do not fit)."""
        K, p, e = b.keys.shape
        s = K // chunks
        flat = lambda x: x.reshape(chunks, s, p, e).transpose(
            0, 2, 1, 3).reshape(chunks, p, s * e)
        ok = flat(self._in_range(b))
        step = jnp.broadcast_to(
            jnp.repeat(jnp.arange(s, dtype=jnp.int32), e), ok.shape)
        fields = (flat(b.keys), flat(b.values), flat(b.timestamps), step)
        if s * e <= width:                   # as they lie: nothing to pack
            return fields + (ok,), jnp.sum(ok.astype(jnp.int32), axis=-1)
        packed, total = _pack_by_rank(ok, fields, width)
        return packed + (jnp.arange(width, dtype=jnp.int32)
                         < total[..., None],), total

    def process_block(self, state, batches, bctx):
        left, right = batches
        K, p, e = left.keys.shape
        S, cap = self._chunk_of(K), self.capacity
        chunks = K // S
        max_l = jnp.maximum(state["max_ts_left"][None],
                            _running_max(self._top_ts(left)))     # [K, P]
        max_r = jnp.maximum(state["max_ts_right"][None],
                            _running_max(self._top_ts(right)))
        wm = self._watermark(max_l, max_r).reshape(chunks, S, p)
        widths = tuple(min(S * e, w * e) for w in self._PACKED_WINDOWS)
        (l_pack, l_total), (r_pack, r_total) = (
            self._packed(b, chunks, w)
            for b, w in zip((left, right), widths))
        full = (l_total > widths[0]) | (r_total > widths[1])   # [chunks, P]
        by_chunk = lambda b: jax.tree_util.tree_map(
            lambda x: x.reshape((chunks, S) + x.shape[1:]), b)
        core = self._core(state, left, right, steps_axis=(0,))
        # a chunk's rows as histogram operands, as wide as either way of
        # running it makes them
        wide = max(self._row_lanes(widths), S * cap)

        def quiet(core, xs):
            l_pack, r_pack, _, _, wm = xs
            core, rows, emitted, loud = self._chunk(core, l_pack, r_pack, wm)
            pad = lambda x: jnp.pad(x, ((0, 0), (0, wide - x.shape[-1])))
            return core, tuple(pad(x) for x in rows), emitted, loud

        def by_steps(core, xs):
            _, _, l_raw, r_raw, wm = xs
            core, out = jax.lax.scan(
                lambda c, x: self._step(c, *x), core, (l_raw, r_raw, wm))
            lanes = lambda x: jnp.pad(
                x.transpose(1, 0, 2).reshape(p, S * cap),
                ((0, 0), (0, wide - S * cap)))
            lane = jnp.broadcast_to(jnp.arange(wide, dtype=jnp.int32),
                                    (p, wide))
            return core, (lane, lanes(out.keys), lanes(out.values),
                          lanes(out.timestamps), lanes(out.valid)), \
                out.count().T

        def chunk(core, xs):
            fast, rows, emitted, loud = quiet(core, xs[:-1])
            loud = loud | xs[-1]
            core, rows, emitted = jax.lax.cond(
                jnp.any(loud), lambda: by_steps(core, xs[:-1]),
                lambda: (fast, rows, emitted))
            core["step_chunks"] = core["step_chunks"] + loud.astype(jnp.int32)
            return core, (rows, emitted)

        core, (rows, emitted) = jax.lax.scan(
            chunk, core, (l_pack, r_pack, by_chunk(left), by_chunk(right),
                          wm, full))
        out = self._rows_of(rows, emitted, S)         # [chunks, P, S, cap]
        out = jax.tree_util.tree_map(
            lambda x: x.transpose(0, 2, 1, 3).reshape(K, p, cap), out)
        return dict(state, **core, max_ts_left=max_l[-1],
                    max_ts_right=max_r[-1]), out




@dataclasses.dataclass
class IncrementalJoinOperator(_ChunkedJoin):
    """Join of two keyed streams over their whole history, with no
    window: the left input BUILDS — a key's first record registers it —
    and the right input PROBES; a probe whose key is not registered
    waits for it. Apache Beam's NEXmark ``Query3`` ("Local Item
    Suggestion": category-10 auctions joined with the persons of three
    states on ``seller = id``) writes the rule per key as a stateful
    ``DoFn`` — the person in a value state, the auctions waiting for
    their person in a bag, the person cleared ``maxAuctionsWaitingTime``
    after it registered — and this operator is that rule, per key,
    exactly. "Person" and "auction" below are Query3's words for a left
    and a right record.

    **Watermark.** Flink's rule for a two-input operator, as
    :class:`EventTimeWindowJoinOperator` has it: the smaller of the two
    inputs' running maxima of valid timestamps less
    ``out_of_orderness``, advanced once a step BEFORE the step's
    records; while an input has delivered nothing there is none, and
    nothing expires. A person is *live* while ``its timestamp + ttl >
    watermark``; an auction waits as long, by its own timestamp.

    **A step**, per subtask, in this order: (1) the auctions that have
    waited ``ttl`` leave the bag (``bag_expired``); (2) the step's
    persons, key by key: if the key holds a live person they are
    duplicates (``duplicates``; nothing is emitted), else the one with
    the earliest timestamp becomes the key's person (the others are
    duplicates) and every auction waiting for the key leaves the bag as
    a row (``flushed``); (3) the step's auctions: one whose key holds a
    live person is a row at once, any other waits (``bagged``) — also
    one that arrives after its person expired (``expired_probes``
    counts, among those that wait, the ones whose key holds an expired
    person that was live at the auction's own timestamp: rows that
    lateness cost; an id ring's earlier lap is not one). A row is the auction: ``(key, its value,
    its timestamp)``. A step's rows are the flushed ones in the order
    they waited, then the auctions' in arrival order, cut to
    ``capacity`` a subtask a step.

    **What is lost is counted and loud** (``fence_losses``: a non-zero
    one stops the run at the next fence): a row past ``capacity``
    (``dropped``), an auction that finds ``bag_capacity`` auctions
    waiting on its subtask (``bag_overflow``; Beam's bag is unbounded
    because its run ends), a record whose key this subtask holds no
    column for (``unplaced``) and one whose key lies outside ``[0,
    num_keys)`` — an id ring smaller than the ids alive in ``ttl``
    would show there or as wrong rows (``ring_too_small``).

    **State.** Per own column (:class:`_OwnColumns`) the person's
    timestamp (it stays after it expired, until the next one
    registers); per subtask the waiting auctions ``(key, value,
    timestamp)`` in the order they came, ``bag_capacity`` of them —
    one pool a subtask and not a bag a column: Beam bounds neither, and
    a hot key may fill what a thousand quiet ones leave. No dense form
    past what the keyed histogram holds: the table exists for
    ``own_columns`` (``num_keys`` 131,072 in NEXmark's cell).
    ``live_peak`` is the most live persons a subtask has held after a
    step.

    **The block form** takes the block in chunks of 32 steps at most — a loop over the chunks, none over the steps, no scatter, no
    gather. A chunk's records are packed to the front first (a keyed
    histogram over their ranks: the receive windows of a skewed edge
    are mostly empty), then one comparison of the chunk's persons with
    the subtask's columns gives each column its first arrival, one of
    its auctions with the columns gives each auction its key's person,
    one of the bag with the persons says what a registration flushes,
    and a row's place is a histogram lane ``step x capacity + rank``.
    That is exact while a chunk is quiet in the way chunks are — no key
    whose person expires inside the chunk receives another, a person
    that registers outlives the chunk, the packed records and the bag
    fit; the chunk that is not (``step_chunks`` counts it) runs step by
    step under a ``lax.cond``, so the block form is the step form bit
    for bit on any input (``step_chunks`` aside, which depends on where
    the chunks fall).

    Every row carries a key this subtask received
    (``emits_received_keys``): behind two ``key_by()`` inputs the out
    edge is routed in place.
    """

    num_keys: int
    ttl: int
    out_of_orderness: int = 0
    capacity: int = 256
    own_columns: Optional[int] = None
    bag_capacity: int = 1024

    emits_received_keys = True

    fence_totals = (
        ("rows", "join.rows"), ("flushed", "join.flushed_rows"),
        ("bagged", "join.bagged"), ("bag_expired", "join.bag_expired"),
        ("duplicates", "join.duplicate_persons"),
        ("expired_probes", "join.expired_probes"),
        ("step_chunks", "join.step_form_chunks"),
        ("bag_overflow", "join.bag_overflow"),
        ("dropped", "join.dropped_rows"),
        ("unplaced", "join.unplaced_records"),
        ("ring_too_small", "join.ring_too_small"))
    fence_losses = ("bag_overflow", "dropped", "unplaced", "ring_too_small")
    fence_peaks = (("live_peak", "join.live_persons"),)

    #: a chunk's records are packed into this many receive windows a
    #: side (left, right); a chunk that brings more runs step by step
    _PACKED_WINDOWS = (2, 4)

    def __post_init__(self):
        from clonos_tpu.ops.histogram import KERNEL_MAX_KEYS
        if self.own_columns is None and self.num_keys > KERNEL_MAX_KEYS:
            raise ValueError(
                f"an incremental join over {self.num_keys} keys needs "
                f"own_columns: a dense table a subtask is only kept up to "
                f"{KERNEL_MAX_KEYS} keys")
        if min(self.ttl, self.capacity, self.bag_capacity) < 1:
            raise ValueError("ttl, capacity and bag_capacity must be "
                             "positive")

    _COUNTERS = tuple(k for k, _ in fence_totals)

    def init_state(self, parallelism: int):
        p, c, g = parallelism, self._columns, self.bag_capacity
        state = {k: jnp.zeros((p,), jnp.int32)
                 for k, _ in self.fence_totals + self.fence_peaks}
        state.update(
            cols=self._init_cols(p),
            person_ts=jnp.full((p, c), _NO_TS, jnp.int32),
            bag_key=jnp.zeros((p, g), jnp.int32),
            bag_val=jnp.zeros((p, g), jnp.int32),
            bag_ts=jnp.zeros((p, g), jnp.int32),
            bag_n=jnp.zeros((p,), jnp.int32),
            max_ts_left=jnp.full((p,), _NO_TS, jnp.int32),
            max_ts_right=jnp.full((p,), _NO_TS, jnp.int32))
        return state

    def _live(self, ts, wm):
        return (ts != _NO_TS) & (ts + self.ttl > wm)

    def _chunk(self, core, left, right, wm):
        """One chunk of ``S`` steps over every subtask, exact if the
        chunk is quiet (class docstring; always for ``S = 1``).

        ``core``: the state's tables and counters; ``left`` / ``right``:
        the chunk's records in (step, slot) order as ``(key, value,
        timestamp, step, valid)`` of ``[P, M]``; ``wm [S, P]``. Returns
        the new ``core``, the chunk's rows as histogram operands —
        ``(lane, key, value, timestamp, valid) [P, N]``, lane ``step x
        capacity + rank`` — with the rows a step emits ``[P, S]``, and
        which subtasks the chunk was not quiet for ``[P]``."""
        from clonos_tpu.ops.matops import running_count
        S, cap, g, ttl = wm.shape[0], self.capacity, self.bag_capacity, \
            self.ttl
        cols, pts0 = core["cols"], core["person_ts"]
        lk, _, lt, ls, lok = left
        rk, rvl, rt, rs, rok = right
        n = lambda m, axis=-1: jnp.sum(m.astype(jnp.int32), axis=axis)
        steps = jnp.arange(S, dtype=jnp.int32)
        wm_p = wm.T                                                # [P, S]
        at_step = lambda s, x: jnp.sum(                 # x[p, s[p, m]]
            jnp.where(s[..., None] == steps, x[:, None, :], 0), axis=-1)

        with jax.named_scope("lookup"):
            # each column's persons of the chunk: how many, and of the
            # earliest step's the earliest (one pass over the pairs)
            m = lok[:, :, None] & (lk[:, :, None] == cols[:, None, :])

            def first(x, y):
                early = (x[1] < y[1]) | ((x[1] == y[1]) & (x[2] <= y[2]))
                return (x[0] + y[0], jnp.where(early, x[1], y[1]),
                        jnp.where(early, x[2], y[2]))

            came, fs, ft = jax.lax.reduce(
                (m.astype(jnp.int32), jnp.where(m, ls[:, :, None], S),
                 jnp.where(m, lt[:, :, None], _NO_LO)),
                (jnp.int32(0), jnp.int32(S), jnp.int32(_NO_LO)), first, (1,))
            live0 = self._live(pts0, wm_p[:, :1])
            live1 = self._live(pts0, wm_p[:, -1:])
            reg = (came > 0) & ~live0
            # not quiet: a key's person expires inside the chunk and the
            # key receives another; a person that registers and does not
            # outlive the chunk
            loud = (jnp.any((came > 0) & live0 & ~live1, axis=-1)
                    | jnp.any(reg & ~self._live(ft, wm_p[:, -1:]), axis=-1))
            person_ts = jnp.where(reg, ft, pts0)
            reg_at = jnp.where(reg, fs, S)
            reg_ts = jnp.where(reg, ft, _NO_TS)
            # each auction's column: the person it held when the chunk
            # began, and the one that registers in it
            m2 = rok[:, :, None] & (rk[:, :, None] == cols[:, None, :])
            held, p0, r_at, r_ts = jax.lax.reduce(
                (m2.astype(jnp.int32), jnp.where(m2, pts0[:, None, :], 0),
                 jnp.where(m2, reg_at[:, None, :], 0),
                 jnp.where(m2, reg_ts[:, None, :], 0)),
                (jnp.int32(0),) * 4,
                lambda x, y: tuple(a + b for a, b in zip(x, y)), (2,))
            held = held > 0
        with jax.named_scope("place"):
            wm_r = at_step(rs, wm_p)                               # [P, M]
            after = held & (r_at < S) & (rs >= r_at)   # persons come first
            match = held & jnp.where(after, self._live(r_ts, wm_r),
                                     self._live(p0, wm_r))
            bagged = held & ~match
            # of those that wait, the ones their key's person would have
            # taken, had they come before the watermark passed its ttl
            holds = jnp.where(after, r_ts, p0)
            probes = (bagged & (holds != _NO_TS) & (holds <= rt)
                      & (rt < holds + ttl))
            # the bag, then the chunk's waiting auctions, in the order
            # they came: when each expires, and when a person flushes it
            slot = jnp.arange(g, dtype=jnp.int32)
            b_ok = slot[None, :] < core["bag_n"][:, None]
            first_person = jnp.min(jnp.where(
                b_ok[:, :, None] & lok[:, None, :]
                & (core["bag_key"][:, :, None] == lk[:, None, :]),
                ls[:, None, :], S), axis=-1)                       # [P, G]
            cat = lambda a, b: jax.lax.optimization_barrier(
                jnp.concatenate([a, b], axis=-1))
            e_key, e_val, e_ts = (cat(core["bag_key"], rk),
                                  cat(core["bag_val"], rvl),
                                  cat(core["bag_ts"], rt))
            e_ok = cat(b_ok, bagged)
            born = cat(jnp.full_like(core["bag_key"], -1), rs)
            flush_at = cat(first_person,
                           jnp.where((r_at < S) & (rs < r_at), r_at, S))
            gone_at = jnp.maximum(born + 1, n(
                wm_p[:, None, :] < (e_ts + ttl)[:, :, None]))
            flushed = e_ok & (flush_at < S) & (flush_at < gone_at)
            expired = e_ok & ~flushed & (gone_at < S)
            waits = e_ok & ~flushed & ~expired
            (bag_key, bag_val, bag_ts), w_total = _pack_by_rank(
                waits, (e_key, e_val, e_ts), g)
            loud = loud | (core["bag_n"] + n(bagged) > g)
        with jax.named_scope("emit"):
            # a step's rows: the flushed ones in the order they waited,
            # then its auctions in arrival order
            f_hot = flushed[:, None, :] & (flush_at[:, None, :]
                                           == steps[None, :, None])
            f_rank = jnp.sum(jnp.where(f_hot, running_count(f_hot) - 1, 0),
                             axis=1)                               # [P, N]
            n_flush = n(f_hot)                                     # [P, S]
            m_hot = match[:, None, :] & (rs[:, None, :]
                                         == steps[None, :, None])
            n_match = n(m_hot)
            before = jnp.cumsum(n_match, axis=-1) - n_match
            m_rank = (running_count(match) - 1
                      + at_step(rs, n_flush - before))
            emitted = jnp.minimum(n_flush + n_match, cap)
            rank = cat(m_rank, f_rank)
            rows = (cat(rs, flush_at) * cap + rank, cat(rk, e_key),
                    cat(rvl, e_val), cat(rt, e_ts),
                    cat(match, flushed) & (rank < cap))
        # the most live persons after a step of the chunk
        step_live = jnp.where(
            reg[:, None, :] & (steps[None, :, None] >= fs[:, None, :]),
            self._live(ft[:, None, :], wm_p[:, :, None]),
            self._live(pts0[:, None, :], wm_p[:, :, None]))
        new = dict(
            core, person_ts=person_ts, bag_key=bag_key, bag_val=bag_val,
            bag_ts=bag_ts, bag_n=jnp.minimum(w_total, g),
            rows=core["rows"] + n(emitted),
            flushed=core["flushed"] + n(flushed),
            bagged=core["bagged"] + n(bagged),
            bag_expired=core["bag_expired"] + n(expired),
            duplicates=core["duplicates"] + n(came) - n(reg),
            expired_probes=core["expired_probes"] + n(probes),
            bag_overflow=core["bag_overflow"] + jnp.maximum(w_total - g, 0),
            dropped=core["dropped"] + n(n_flush + n_match - emitted),
            unplaced=(core["unplaced"] + n(lok) - n(came)
                      + n(rok & ~held)),
            live_peak=jnp.maximum(core["live_peak"],
                                  jnp.max(n(step_live), axis=-1)))
        return new, rows, emitted, loud

    _CORE = ("cols", "person_ts", "bag_key", "bag_val", "bag_ts", "bag_n",
             "live_peak") + _COUNTERS

    def _row_lanes(self, widths) -> int:
        return widths[1] + self.bag_capacity + widths[1]


@dataclasses.dataclass
class BestInIntervalJoinOperator(_ChunkedJoin):
    """Join of two keyed streams in which every key takes its interval
    from its own data, and of which only the best probe of an interval
    comes out: the left input OPENS — a record ``(key, v, t)`` opens
    ``[t, t + length_of(v))`` for its key, with a floor ``floor_of(v)``
    and a payload ``emit_of(v)`` — and the right input PROBES: a record
    ``(key, price, t)`` counts for the interval of its key that holds
    ``t`` if ``price >= floor``; when the watermark passes an interval's
    end it closes, and is one row ``(payload, largest price that
    counted, end - 1)`` if any did. NEXmark query 4's first half, Beam's
    ``WinningBids`` ("the winning bid of every closed auction": bids
    inside an auction's ``[dateTime, expires)``, at or over its reserve,
    the highest), is its textbook use; "auction" and "bid" below are
    its words for an opening record and a probe. ``length_of``,
    ``floor_of`` and ``emit_of`` are traced elementwise functions of the
    value lane, as a ``map``'s ``fn`` is: the job says what an auction's
    value means.

    **Watermark.** Flink's rule for a two-input operator
    (:class:`_ChunkedJoin`): the smaller of the two inputs' running
    maxima less ``out_of_orderness``, advanced once a step BEFORE the
    step's records; none while an input is silent (nothing resolves,
    nothing closes).

    **A step**, per subtask, in this order, none of it dependent on the
    slot order inside the step: (1) the bids whose own ``t`` the
    watermark has reached are *resolved* — those that waited, then the
    step's own — against the auction their key holds open: inside its
    interval and at or over its floor a bid counts (``valid``; the
    auction keeps the largest), inside and under it counts in ``under``,
    outside any in ``orphans`` (no auction, one not yet open at ``t``,
    one expired before ``t``); (2) the auctions whose end the
    watermark has reached close, in key order: a row each if a bid
    counted, else ``no_valid``, and the column is free; (3) the step's
    auctions, key by key: if the key holds an open auction they are
    ``duplicates``, else the one with the smallest ``(t, v)`` opens and
    the others are duplicates; (4) the bids not yet resolved wait, in
    arrival order. A bid thus waits until every auction record with a
    ``t`` at or before its own has arrived (the watermark says so), and
    an interval's result is the maximum over every bid of its key with
    ``start <= t < end`` and ``price >= floor``, whenever it arrived
    within the bound.

    **What is lost is counted and loud** (``fence_losses``): a bid that
    finds ``pool_capacity`` bids waiting on its subtask
    (``pool_overflow``), a row past ``capacity`` a subtask a step
    (``dropped``), an auction whose key this subtask holds no column
    for (``unplaced``; a bid with such a key resolves as an orphan: the
    planner binds every key a subtask can be sent behind ``key_by()``)
    and a record whose key lies outside ``[0, num_keys)``
    (``ring_too_small``). ``open_peak`` and ``pool_peak`` are the most
    auctions a subtask has held open, and bids waiting, after a step.

    **State.** Per own column (:class:`_OwnColumns`; there is no dense
    form) the open auction's start, end, floor, payload, best price and
    how many bids counted; per subtask the waiting bids ``(key, price,
    timestamp)`` in arrival order.

    **The block form** (:class:`_ChunkedJoin`: chunks of 32 steps, no
    loop over the steps, no scatter, no gather, no sort). A chunk's
    auctions are packed to the front and compared with the subtask's
    columns once: per column the auction that opens in the chunk, if
    any, behind the one it held. Those two a column, the ones that
    exist, are packed into ``_ACTIVE`` intervals with the steps between
    which each is open, and ONE comparison of the waiting and the
    chunk's bids — as they lie in their receive windows — with the
    active intervals carries, per interval, the bids inside it, those
    that count and the best (a reduction with three results). A row's
    place is the histogram lane ``closing step x capacity + rank``. The
    chunk is quiet while no key opens twice in it, its intervals fit
    ``_ACTIVE``, the waiting bids fit the pool after every step and the
    packed auctions their window.

    A row's key is the payload, not a key the subtask received: the
    operator does not claim ``emits_received_keys``.
    """

    num_keys: int
    length_of: Callable[[jnp.ndarray], jnp.ndarray]
    floor_of: Callable[[jnp.ndarray], jnp.ndarray]
    emit_of: Callable[[jnp.ndarray], jnp.ndarray]
    out_of_orderness: int = 0
    capacity: int = 32
    own_columns: Optional[int] = None
    pool_capacity: int = 1024

    fence_totals = (
        ("rows", "winbid.rows"), ("valid", "winbid.valid_bids"),
        ("under", "winbid.under_reserve"), ("orphans", "winbid.orphan_bids"),
        ("duplicates", "winbid.duplicate_auctions"),
        ("no_valid", "winbid.no_valid_bids"),
        ("step_chunks", "winbid.step_form_chunks"),
        ("pool_overflow", "winbid.pool_overflow"),
        ("dropped", "winbid.dropped_rows"),
        ("unplaced", "winbid.unplaced_records"),
        ("ring_too_small", "winbid.ring_too_small"))
    fence_losses = ("pool_overflow", "dropped", "unplaced", "ring_too_small")
    fence_peaks = (("open_peak", "winbid.open_auctions"),
                   ("pool_peak", "winbid.pool_fill"))

    #: a chunk's auctions are packed into one receive window; its bids
    #: are compared as they lie (a window a step)
    _PACKED_WINDOWS = (1, _ChunkedJoin._CHUNK_STEPS)
    #: intervals a chunk of more than one step compares its bids with (a
    #: step compares them with two a column)
    _ACTIVE = 256

    _COLUMN = (("i_start", _NO_TS), ("i_end", _NO_TS), ("i_floor", 0),
               ("i_pay", 0), ("i_best", _NO_TS), ("i_hits", 0))
    _CORE = (("cols", "pool_key", "pool_val", "pool_ts", "pool_n")
             + tuple(k for k, _ in _COLUMN)
             + tuple(k for k, _ in fence_totals + fence_peaks))

    def __post_init__(self):
        if self.own_columns is None:
            raise ValueError(
                "join_best_in_interval keeps its intervals on own_columns: "
                "there is no dense form")
        if min(self.capacity, self.pool_capacity) < 1:
            raise ValueError("capacity and pool_capacity must be positive")

    def init_state(self, parallelism: int):
        p, c, g = parallelism, self._columns, self.pool_capacity
        state = {k: jnp.zeros((p,), jnp.int32)
                 for k, _ in self.fence_totals + self.fence_peaks}
        state.update({k: jnp.full((p, c), free, jnp.int32)
                      for k, free in self._COLUMN})
        state.update(
            cols=self._init_cols(p),
            pool_key=jnp.zeros((p, g), jnp.int32),
            pool_val=jnp.zeros((p, g), jnp.int32),
            pool_ts=jnp.zeros((p, g), jnp.int32),
            pool_n=jnp.zeros((p,), jnp.int32),
            max_ts_left=jnp.full((p,), _NO_TS, jnp.int32),
            max_ts_right=jnp.full((p,), _NO_TS, jnp.int32))
        return state

    def _row_lanes(self, widths) -> int:
        return 2 * self._columns

    def _chunk(self, core, left, right, wm):
        from clonos_tpu.ops.matops import running_count
        S, cap, g, c = (wm.shape[0], self.capacity, self.pool_capacity,
                        self._columns)
        cols = core["cols"]
        lk, lv, lt, ls, lok = left
        rk, rv, rt, rs, rok = right
        n = lambda m, axis=-1: jnp.sum(m.astype(jnp.int32), axis=axis)
        steps = jnp.arange(S, dtype=jnp.int32)
        wm_p = wm.T                                                # [P, S]
        # the first step of the chunk at which the watermark has reached
        # ``when [P, X]`` (S: not in this chunk)
        reached = lambda when: n(wm_p[:, None, :] < when[:, :, None])
        # a column's two intervals side by side, [P, C] x 2 -> [P, 2 C]:
        # the one it held when the chunk began, the one that opens in it
        pair = lambda a, b: jnp.stack([a, b], axis=-1).reshape(-1, 2 * c)

        with jax.named_scope("lookup"):
            open0 = core["i_end"] != _NO_TS
            close0 = jnp.where(open0, reached(core["i_end"]), 0)   # [P, C]
            # each column's auctions of the chunk: how many, and of those
            # that come once the column is free the first (one pass over
            # the pairs)
            m = lok[:, :, None] & (lk[:, :, None] == cols[:, None, :])
            may = m & (ls[:, :, None] >= close0[:, None, :])

            def first(x, y):
                early = (x[1] < y[1]) | ((x[1] == y[1]) & (
                    (x[2] < y[2]) | ((x[2] == y[2]) & (x[3] <= y[3]))))
                return (x[0] + y[0],) + tuple(
                    jnp.where(early, a, b) for a, b in zip(x[1:], y[1:]))

            came, fs, ft, fv = jax.lax.reduce(
                (m.astype(jnp.int32), jnp.where(may, ls[:, :, None], S),
                 jnp.where(may, lt[:, :, None], _NO_LO),
                 jnp.where(may, lv[:, :, None], _NO_LO)),
                (jnp.int32(0), jnp.int32(S), jnp.int32(_NO_LO),
                 jnp.int32(_NO_LO)), first, (1,))
            opens = fs < S
            fv = jnp.where(opens, fv, 0)
            end1 = jnp.where(opens, ft + self.length_of(fv), _NO_TS)
            close1 = jnp.where(opens, jnp.maximum(fs + 1, reached(end1)), S)
            # not quiet: a key that opens a second time inside the chunk
            loud = jnp.any(m & (ls[:, :, None] >= close1[:, None, :]),
                           axis=(1, 2))
            on = pair(open0, opens)
            starts = pair(core["i_start"], jnp.where(opens, ft, _NO_TS))
            ends = pair(core["i_end"], end1)
            floors = pair(core["i_floor"], self.floor_of(fv))
            since = pair(jnp.full_like(fs, -1), fs)    # open after this step
            until = pair(close0, close1)               # ... through this one
            fields = (pair(cols, cols), starts, ends, floors, since, until)
            width = 2 * c if S == 1 else min(self._ACTIVE, 2 * c)
            if width == 2 * c:                   # as they lie
                a_on = on
            else:
                fields, total = _pack_by_rank(on, fields, width)
                a_on = jnp.arange(width, dtype=jnp.int32) < total[:, None]
                loud = loud | (total > width)
            a_key, a_start, a_end, a_floor, a_since, a_until = (
                x[:, None, :] for x in fields)
        with jax.named_scope("place"):
            # the bids that wait, then the chunk's, in the order they
            # came: when each is resolved
            cat = lambda a, b: jax.lax.optimization_barrier(
                jnp.concatenate([a, b], axis=-1))
            q_ok = cat(jnp.arange(g, dtype=jnp.int32)[None, :]
                       < core["pool_n"][:, None], rok)
            q_key, q_val, q_ts = (cat(core["pool_key"], rk),
                                  cat(core["pool_val"], rv),
                                  cat(core["pool_ts"], rt))
            born = cat(jnp.zeros_like(core["pool_key"]), rs)
            at = jnp.maximum(born, reached(q_ts))                  # [P, N]
            resolved = q_ok & (at < S)
            waits = q_ok & ~resolved
            # one pass over the (bid, interval) pairs: the interval is
            # its key's, open when the bid is resolved, and holds its t
            k, v, t, r = (x[:, :, None] for x in (q_key, q_val, q_ts, at))
            inside = (resolved[:, :, None] & a_on[:, None, :] & (k == a_key)
                      & (r > a_since) & (r <= a_until)
                      & (t >= a_start) & (t < a_end))
            counts = inside & (v >= a_floor)
            n_in, n_valid, best = jax.lax.reduce(
                (inside.astype(jnp.int32), counts.astype(jnp.int32),
                 jnp.where(counts, v, _NO_TS)),
                (jnp.int32(0), jnp.int32(0), jnp.int32(_NO_TS)),
                lambda x, y: (x[0] + y[0], x[1] + y[1],
                              jnp.maximum(x[2], y[2])), (1,))      # [P, A]
            # the bids waiting after each step of the chunk
            fill = n((q_ok[:, :, None] & (born[:, :, None] <= steps)
                      & (r > steps)), axis=1)                      # [P, S]
            loud = loud | jnp.any(fill > g, axis=-1)
            (pool_key, pool_val, pool_ts), w_total = _pack_by_rank(
                waits, (q_key, q_val, q_ts), g)
        with jax.named_scope("emit"):
            if width != 2 * c:       # back onto the columns' lanes
                mine = on[:, :, None] & (
                    (running_count(on) - 1)[:, :, None]
                    == jnp.arange(width, dtype=jnp.int32))
                n_valid = jnp.sum(jnp.where(mine, n_valid[:, None, :], 0),
                                  axis=-1)
                best = jnp.max(jnp.where(mine, best[:, None, :], _NO_TS),
                               axis=-1)
            hits = pair(core["i_hits"], jnp.zeros_like(fs)) + n_valid
            top = jnp.maximum(
                pair(core["i_best"], jnp.full_like(fs, _NO_TS)), best)
            pay = pair(core["i_pay"], self.emit_of(fv))
            # an interval that closes in the chunk is a row at its
            # closing step if a bid counted, in key order
            closes = on & (until < S)
            fire = closes & (hits > 0)
            f_hot = fire[:, None, :] & (until[:, None, :]
                                        == steps[None, :, None])
            rank = jnp.sum(jnp.where(f_hot, running_count(f_hot) - 1, 0),
                           axis=1)                                 # [P, 2C]
            n_rows = n(f_hot)                                      # [P, S]
            emitted = jnp.minimum(n_rows, cap)
            rows = (until * cap + rank, pay, top, ends - 1,
                    fire & (rank < cap))
            # what a column holds when the chunk ends
            by_col = lambda x: x.reshape(-1, c, 2)
            keep1 = opens & (close1 == S)
            keep0 = ~opens & open0 & (close0 == S)
            held = lambda x, none: jnp.where(
                keep1, by_col(x)[..., 1],
                jnp.where(keep0, by_col(x)[..., 0], none))
            column = {key: held(x, none) for (key, none), x in zip(
                self._COLUMN, (starts, ends, floors, pay, top, hits))}
        at_step = steps[None, :, None]
        open_after = n(on[:, None, :] & (since[:, None, :] <= at_step)
                       & (until[:, None, :] > at_step))            # [P, S]
        new = dict(
            core, **column, pool_key=pool_key, pool_val=pool_val,
            pool_ts=pool_ts, pool_n=jnp.minimum(w_total, g),
            rows=core["rows"] + n(emitted),
            valid=core["valid"] + n(n_valid),
            under=core["under"] + n(n_in) - n(n_valid),
            orphans=core["orphans"] + n(resolved) - n(n_in),
            duplicates=core["duplicates"] + n(came) - n(opens),
            no_valid=core["no_valid"] + n(closes & ~fire),
            pool_overflow=core["pool_overflow"] + jnp.maximum(w_total - g, 0),
            dropped=core["dropped"] + n(n_rows - emitted),
            unplaced=core["unplaced"] + n(lok) - n(came),
            open_peak=jnp.maximum(core["open_peak"],
                                  jnp.max(open_after, axis=-1)),
            pool_peak=jnp.maximum(core["pool_peak"],
                                  jnp.minimum(jnp.max(fill, axis=-1), g)))
        return new, rows, emitted, loud


@dataclasses.dataclass
class OperatorStateCountOperator(Operator):
    """Pass-through with operator (non-keyed) state: each subtask counts
    the records it has seen and hands them on unchanged (the shape of a
    mapper that keeps per-subtask list state; reference
    OperatorStateBackend ListState under a RichMapFunction)."""

    emits_received_keys = True

    def init_state(self, parallelism: int):
        return {"seen": jnp.zeros((parallelism,), jnp.int32)}

    def process(self, state, batch, ctx):
        return {"seen": state["seen"] + batch.count()}, zero_invalid(batch)

    def process_block(self, state, batches, bctx):
        out = zero_invalid(batches)
        return {"seen": state["seen"] + out.count().sum(axis=0)}, out


@dataclasses.dataclass
class TransactionalSinkOperator(Operator):
    """Exactly-once sink (TwoPhaseCommitSinkFunction analog): emissions
    flow to the host-side runtime.txn.TransactionLog as per-epoch pending
    transactions, committed only when the epoch's checkpoint completes.
    Device-side it is a pass-through counter like SinkOperator."""

    def init_state(self, parallelism: int):
        return {"emitted": jnp.zeros((parallelism,), jnp.int32)}

    def process(self, state, batch, ctx):
        return ({"emitted": state["emitted"] + batch.count()},
                zero_invalid(batch))

    def process_block(self, state, batches, bctx):
        out = zero_invalid(batches)
        return ({"emitted": state["emitted"] + out.count().sum(axis=0)},
                out)


@dataclasses.dataclass
class HostFeedSource(Operator):
    """Source fed by the host boundary (the Kafka/socket-source analog).

    The executor passes the pulled batch in as this vertex's input batch;
    the operator stamps timestamps and passes it through. Offset state
    makes the checkpoint carry the feed position (the Kafka-offset-in-
    checkpoint pattern); replay re-reads the same records from the
    rewindable reader (reference: sources restore offsets and the causal
    log pins the per-buffer cut counts)."""

    batch_size: int

    @property
    def out_capacity(self):  # type: ignore[override]
        return self.batch_size

    def init_state(self, parallelism: int):
        return {"offset": jnp.zeros((parallelism,), jnp.int32)}

    def process(self, state, batch, ctx):
        out = zero_invalid(batch._replace(
            timestamps=jnp.where(batch.valid, ctx.time, 0)))
        return {"offset": state["offset"] + out.count()}, out

    def process_block(self, state, batches, bctx):
        out = zero_invalid(batches._replace(
            timestamps=jnp.where(batches.valid, bctx.times[:, None, None], 0)))
        return ({"offset": state["offset"] + out.count().sum(axis=0)}, out)


@dataclasses.dataclass
class SinkOperator(Operator):
    """Terminal operator: passes its input through as the job's visible
    output (the executor surfaces it to the host) and counts emissions
    (DiscardingSink/collect-sink analog)."""

    def init_state(self, parallelism: int):
        return {"emitted": jnp.zeros((parallelism,), jnp.int32)}

    def process(self, state, batch, ctx):
        return ({"emitted": state["emitted"] + batch.count()},
                zero_invalid(batch))

    def process_block(self, state, batches, bctx):
        out = zero_invalid(batches)
        return ({"emitted": state["emitted"] + out.count().sum(axis=0)}, out)
