"""Keyed histogram (segment-sum) kernel: the TPU replacement for the
scatter-add at the heart of every keyed aggregation.

The reference aggregates record-at-a-time into hash-keyed state
(flink-runtime .../state/heap/HeapKeyedStateBackend.java ValueState
update per record). The dense-table TPU design turns that into a per-step
histogram ``contrib[row, key] = sum(values where keys == key)`` — but
XLA's scatter-add serializes its updates on TPU (60-120ms at bench shapes
for ~4M updates). This Pallas kernel factors the one-hot instead:
``key = hi * 128 + lo``, so a row's table is the matrix product of a
``[hi, records]`` one-hot that carries the values and a
``[records, lo]`` one-hot. The VPU builds the two factors (``hi + 128``
compares a record, where a compare per key lane made the kernel's time
go with records x key lanes) and the MXU does the products, bit-exact
modulo 2**32 over the whole int32 range through byte planes
(:func:`_hist_kernel_mxu`) — no scatter anywhere.

The kernel's traced body does not grow with the table: planes and key
blocks are axes of one operand, column chunks a loop. Every program that
holds the kernel traces, lowers and loads it again, so an unrolled body
is paid in every process's set-up, compile cache or not.

Which form runs is decided by the platform the program is lowered for,
never by a failure: the kernel on a TPU, a bit-identical XLA scatter
elsewhere (the CPU test lane; ``tests/test_pallas_kernels.py`` pins
kernel == scatter == NumPy in interpret mode). :func:`keyed_hist` leaves
a ``hist.kernel`` instant per call site traced, saying which.

Mosaic kernels cannot be partitioned automatically, so a program lowered
over a device mesh has to say so: it traces inside :func:`kernel_mesh`
and the kernel then runs per shard under ``shard_map`` (rows are
independent, so no operand is gathered to one device).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from clonos_tpu.obs.trace import get_tracer

#: rows of records one kernel program folds (sublanes of an int32 tile)
_ROW_TILE = 8
#: lanes of a tile: the ``lo`` half of a key, and the MXU's width
_LANES = 128
#: record columns one matrix product contracts, at most. A step's cost
#: is mostly fixed (the MXU's fill and drain), so fewer and fatter steps
#: win: on the v5e 1,024 read 0.55 ms where 512 read 0.72 and 256 0.85
#: at ``[1024, 1024] -> 8192``; the stacked operand of the widest table
#: (``[8, 5 x 128, 1024]`` bf16, 10 MiB) still fits the default scoped
#: VMEM, at 2,048 it would not.
_COL_CHUNK = 1024
#: record columns one kernel program folds in f32 before its sums go to
#: int32 (a byte plane's partial sum stays under 255 * 2,048 < 2**24, so
#: it is exact). Longer rows walk the grid's second axis.
_COL_BLOCK = 2048
#: the ``hi`` half of the table is padded to this many rows a plane (the
#: sublanes of an f32 tile, in which the planes are stacked)
_HI_ALIGN = 8
#: largest key count callers may hand the kernel (both variants compile
#: for the v5e up to here under the compiler's default scoped-VMEM
#: limit — tests/test_tpu_aot.py).
KERNEL_MAX_KEYS = 1 << 14

#: (mesh, task axis) of the program being traced, or None.
_MESH_SCOPE: contextvars.ContextVar[
    Optional[Tuple[jax.sharding.Mesh, str]]] = contextvars.ContextVar(
        "clonos_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh: Optional[jax.sharding.Mesh], axis: str):
    """Trace-time scope for a program lowered over ``mesh``: inside it
    :func:`keyed_hist` shards its kernel over ``axis``. A ``None`` mesh
    is no scope (the single-device program)."""
    if mesh is None:
        yield
        return
    token = _MESH_SCOPE.set((mesh, axis))
    try:
        yield
    finally:
        _MESH_SCOPE.reset(token)


def over_mesh(fn, mesh: Optional[jax.sharding.Mesh], axis: str):
    """``fn`` wrapped so that tracing it happens inside
    :func:`kernel_mesh` — what a ``jax.jit`` over mesh-resident
    arguments is handed."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with kernel_mesh(mesh, axis):
            return fn(*args, **kwargs)
    return scoped


def uses_kernel() -> bool:
    """True when :func:`keyed_hist` traced here runs the Pallas kernel:
    the program targets a TPU (the scope's mesh says which devices;
    without one, the default backend)."""
    scope = _MESH_SCOPE.get()
    platform = (scope[0].devices.flat[0].platform if scope is not None
                else jax.default_backend())
    return platform == "tpu"


def _hist_kernel_mxu(keys_ref, vals_ref, *outs, hi_rows: int):
    """One ``[_ROW_TILE, cols]`` block of records against every key lane.

    ``key = hi * 128 + lo``, so a row's table is a matrix product over
    its records ``j``::

        out[hi, lo] = sum_j ([hi_j == hi] * vals_j) * [lo_j == lo]

    The VPU builds the two one-hots (``hi_rows + 128`` compares a
    record, not one per key lane) and the MXU does the ``records x key
    lanes`` products, the tile's rows as one batched product so that
    they overlap in its pipeline. ``vals`` goes in as the four unsigned
    byte planes of its two's-complement word, stacked on the ``hi`` axis
    with a fifth plane of ones for the counts: a byte is exact in bf16,
    the one-hot is 0/1, the product accumulates in f32 and a plane's sum
    over one ``_COL_BLOCK`` is under 2**24, so nothing rounds; the
    planes recombine with shifts in int32 and wrap like the scatter-add.
    A key outside ``[0, 128 * hi_rows)`` (-1 of an invalid record among
    them) has no row in the first factor, so whatever lane its ``lo``
    matches carries nothing: ``mode="drop"`` parity.

    The planes are one stacked operand and the key blocks one axis of
    it; full column chunks are a loop and the remainder one more step:
    the traced body is at most two chunk steps whatever the table's
    width (tests/test_pallas_kernels.py counts them).
    """
    want_counts = len(outs) == 2
    rt, cols = keys_ref.shape
    f32, bf16 = jnp.float32, jnp.bfloat16

    def fold(c0, width, acc):
        k = keys_ref[:, pl.ds(c0, width)][:, None, :]         # [rt, 1, W]
        v = vals_ref[:, pl.ds(c0, width)][:, None, :]
        at_hi = (k >> 7) == jax.lax.broadcasted_iota(
            jnp.int32, (rt, hi_rows, width), 1)
        planes = [jnp.where(at_hi, ((v >> s) & 255).astype(f32), 0.0)
                  for s in (0, 8, 16, 24)]
        if want_counts:
            planes.append(at_hi.astype(f32))
        a = jnp.concatenate(planes, axis=1).astype(bf16)
        at_lo = (k & 127) == jax.lax.broadcasted_iota(
            jnp.int32, (rt, _LANES, width), 1)
        return acc + jax.lax.dot_general(           # [rt, planes * hi, 128]
            a, at_lo.astype(f32).astype(bf16),
            (((2,), (2,)), ((0,), (0,))), preferred_element_type=f32)

    full, rest = divmod(cols, _COL_CHUNK)
    acc = jnp.zeros((rt, (4 + want_counts) * hi_rows, _LANES), f32)
    if full:
        acc = jax.lax.fori_loop(
            0, full,
            lambda c, acc: fold(pl.multiple_of(c * _COL_CHUNK, _COL_CHUNK),
                                _COL_CHUNK, acc), acc)
    if rest:
        acc = fold(full * _COL_CHUNK, rest, acc)
    p = acc.astype(jnp.int32).reshape(rt, -1, hi_rows, _LANES)
    tables = [p[:, 0] + (p[:, 1] << 8) + (p[:, 2] << 16) + (p[:, 3] << 24)]
    if want_counts:
        tables.append(p[:, 4])
    later = pl.program_id(1) > 0
    for out_ref, tb in zip(outs, tables):
        h = out_ref.shape[1] // _LANES
        tb = tb[:, :h, :].reshape(rt, h * _LANES)
        out_ref[...] = tb + jnp.where(later, out_ref[...], 0)


def _pad_to(x: jnp.ndarray, axis: int, mult: int,
            fill: int = 0) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _hist_pallas(keys, vals, valid, nk: int, interpret: bool,
                 want_counts: bool = True):
    r, b = keys.shape
    nkp = -(-nk // _LANES) * _LANES
    blocks = -(-b // _COL_BLOCK)
    cols = -(-b // (blocks * _LANES)) * _LANES      # a block's, lane-aligned
    # Invalid records AND pad slots get key -1 (matches nothing) — a 0-pad
    # would count phantom records of key 0.
    k = _pad_to(jnp.where(valid, keys, -1), 1, blocks * cols, fill=-1)
    k = _pad_to(k, 0, _ROW_TILE, fill=-1)
    v = _pad_to(jnp.where(valid, vals, 0), 1, blocks * cols)
    v = _pad_to(v, 0, _ROW_TILE)
    rp = k.shape[0]
    nout = 1 + want_counts
    spec_in = pl.BlockSpec((_ROW_TILE, cols), lambda i, j: (i, j),
                           memory_space=pltpu.VMEM)
    spec_out = pl.BlockSpec((_ROW_TILE, nkp), lambda i, j: (i, 0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _hist_kernel_mxu,
            hi_rows=-(-nkp // (_LANES * _HI_ALIGN)) * _HI_ALIGN),
        out_shape=(jax.ShapeDtypeStruct((rp, nkp), jnp.int32),) * nout,
        grid=(rp // _ROW_TILE, blocks),
        in_specs=[spec_in, spec_in],
        out_specs=(spec_out,) * nout,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(k, v)
    return out[0][:r, :nk], out[1][:r, :nk] if want_counts else None


def _hist_xla(keys, vals, valid, nk: int):
    """Scatter-add form (bit-identical; what runs off-TPU)."""
    r, b = keys.shape
    row = jnp.broadcast_to(jnp.arange(r, dtype=jnp.int32)[:, None],
                           keys.shape)
    sums = jnp.zeros((r, nk), jnp.int32).at[row, keys].add(
        jnp.where(valid, vals, 0), mode="drop")
    cnts = jnp.zeros((r, nk), jnp.int32).at[row, keys].add(
        valid.astype(jnp.int32), mode="drop")
    return sums, cnts


def _hist_pallas_sharded(keys, vals, valid, nk: int, interpret: bool,
                         want_counts: bool, mesh, axis: str):
    """The kernel per shard of ``mesh``'s ``axis``, over the middle dim
    of ``[L, R, B]`` operands — the LAST leading dim of the caller's:
    for an operator's ``[K, P, B]`` block that is the subtask axis the
    carry is already sharded on (no data moves); for an exchange's
    ``[K, P*B]`` it is the step axis (the reshard is the exchange).
    R is padded up to the axis size. Returns ``[L, R, nk]`` arrays."""
    r, b = keys.shape[1:]
    pad = (-r) % mesh.shape[axis]
    if pad:
        keys, vals, valid = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                             for x in (keys, vals, valid))

    def local(k, v, m):
        shp = k.shape[:2] + (nk,)
        sums, cnts = _hist_pallas(
            k.reshape(-1, b), v.reshape(-1, b), m.reshape(-1, b),
            nk, interpret, want_counts)
        return ((sums.reshape(shp), cnts.reshape(shp)) if want_counts
                else sums.reshape(shp))

    spec = PartitionSpec(None, axis, None)
    # check_vma=False: pallas_call's out_shape carries no varying-axes
    # annotation in this JAX.
    out = jax.shard_map(
        local, mesh=mesh, in_specs=(spec,) * 3,
        out_specs=(spec, spec) if want_counts else spec,
        check_vma=False)(keys, vals, valid)
    if want_counts:
        return out[0][:, :r], out[1][:, :r]
    return out[:, :r], None


def keyed_hist(keys: jnp.ndarray, vals: jnp.ndarray, valid: jnp.ndarray,
               nk: int, force: str = "", want_counts: bool = True):
    """Per-row keyed sums and counts.

    ``keys/vals/valid``: ``[..., B]`` (any leading dims, flattened to rows).
    Returns ``(sums, counts)`` of shape ``[..., nk]`` — for each row, the
    sum of ``vals`` and the count of records carrying each key in
    ``[0, nk)``. Out-of-range keys are dropped (scatter ``mode=drop``
    parity). ``force``: "pallas" | "interpret" | "xla" | "" (by target
    platform, :func:`uses_kernel`). ``want_counts=False`` skips the count
    output (returned as None) — one plane of five and one output less;
    the aggregation operators only need sums. Inside :func:`kernel_mesh`
    the kernel runs per mesh shard. Each call leaves a ``hist.kernel``
    instant (form ``mxu`` / ``scatter``, rows, cols, lanes, hi, planes)
    in the process tracer, at trace time.
    """
    lead = keys.shape[:-1]
    b = keys.shape[-1]
    mode = force or ("pallas" if uses_kernel() else "xla")
    get_tracer().event(
        "hist.kernel", form="scatter" if mode == "xla" else "mxu",
        rows=math.prod(lead), cols=b, lanes=nk, hi=-(-nk // _LANES),
        planes=4 + want_counts)
    with jax.named_scope("hist"):      # metadata: groups the kernel's ops
        if mode == "xla":
            kf, vf, mf = (x.reshape(-1, b) for x in (keys, vals, valid))
            # Out-of-range guard to mirror mode="drop" exactly.
            ok = mf & (kf >= 0) & (kf < nk)
            sums, cnts = _hist_xla(jnp.where(ok, kf, 0), vf, ok, nk)
            if not want_counts:
                cnts = None
        else:
            interpret = mode == "interpret"
            scope = _MESH_SCOPE.get()
            if scope is None:
                sums, cnts = _hist_pallas(
                    keys.reshape(-1, b), vals.reshape(-1, b),
                    valid.reshape(-1, b), nk, interpret, want_counts)
            else:
                r = keys.shape[-2] if keys.ndim > 1 else 1
                sums, cnts = _hist_pallas_sharded(
                    keys.reshape(-1, r, b), vals.reshape(-1, r, b),
                    valid.reshape(-1, r, b), nk, interpret, want_counts,
                    *scope)
    return (sums.reshape(lead + (nk,)),
            cnts.reshape(lead + (nk,)) if cnts is not None else None)
