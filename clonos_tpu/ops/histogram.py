"""Keyed histogram (segment-sum) kernel: the TPU replacement for the
scatter-add at the heart of every keyed aggregation.

The reference aggregates record-at-a-time into hash-keyed state
(flink-runtime .../state/heap/HeapKeyedStateBackend.java ValueState
update per record). The dense-table TPU design turns that into a per-step
histogram ``contrib[row, key] = sum(values where keys == key)`` — but
XLA's scatter-add serializes its updates on TPU (60-120ms at bench shapes
for ~4M updates). This Pallas kernel streams the records through the VPU
as chunked compare-accumulate instead: for each 128-record chunk, a
``[rows, chunk, key_lanes]`` one-hot compare and an axis reduce — no
scatter anywhere.

Which form runs is decided by the platform the program is lowered for,
never by a failure: the kernel on a TPU, a bit-identical XLA scatter
elsewhere (the CPU test lane; ``tests/test_pallas_kernels.py`` pins
kernel == scatter in interpret mode).

Mosaic kernels cannot be partitioned automatically, so a program lowered
over a device mesh has to say so: it traces inside :func:`kernel_mesh`
and the kernel then runs per shard under ``shard_map`` (rows are
independent, so no operand is gathered to one device).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

#: rows per kernel program (VPU sublane count)
_ROW_TILE = 8
#: record columns per in-kernel chunk (VPU lane count)
_COL_CHUNK = 128
#: largest key count callers may hand the kernel (both variants compile
#: for v5e up to here under _VMEM_LIMIT_BYTES — tests/test_tpu_aot.py).
KERNEL_MAX_KEYS = 1 << 14
#: scoped-VMEM limit requested for the kernel: the compiler's default
#: (16 MiB on v5e) refuses the sums-and-counts variant from 12288 keys
#: up; it needs 17.8 MiB at KERNEL_MAX_KEYS.
_VMEM_LIMIT_BYTES = 32 << 20

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


def _hist_kernel(keys_ref, vals_ref, sum_ref, cnt_ref):
    rt, b = keys_ref.shape
    nkp = sum_ref.shape[1]
    nchunks = b // _COL_CHUNK

    def body(i, carry):
        sums, cnts = carry
        kc = keys_ref[:, pl.ds(i * _COL_CHUNK, _COL_CHUNK)]   # [RT, C]
        vc = vals_ref[:, pl.ds(i * _COL_CHUNK, _COL_CHUNK)]
        iota = jax.lax.broadcasted_iota(jnp.int32, (rt, _COL_CHUNK, nkp), 2)
        oh = kc[:, :, None] == iota
        sums = sums + jnp.sum(jnp.where(oh, vc[:, :, None], 0), axis=1)
        cnts = cnts + jnp.sum(oh.astype(jnp.int32), axis=1)
        return sums, cnts

    sums, cnts = jax.lax.fori_loop(
        0, nchunks, body,
        (jnp.zeros((rt, nkp), jnp.int32), jnp.zeros((rt, nkp), jnp.int32)))
    sum_ref[:] = sums
    cnt_ref[:] = cnts


def _hist_kernel_sums(keys_ref, vals_ref, sum_ref):
    # Sums-only variant: half the vector work of _hist_kernel (the keyed
    # aggregation operators never use the counts).
    rt, b = keys_ref.shape
    nkp = sum_ref.shape[1]
    nchunks = b // _COL_CHUNK

    def body(i, sums):
        kc = keys_ref[:, pl.ds(i * _COL_CHUNK, _COL_CHUNK)]
        vc = vals_ref[:, pl.ds(i * _COL_CHUNK, _COL_CHUNK)]
        iota = jax.lax.broadcasted_iota(jnp.int32, (rt, _COL_CHUNK, nkp), 2)
        oh = kc[:, :, None] == iota
        return sums + jnp.sum(jnp.where(oh, vc[:, :, None], 0), axis=1)

    sum_ref[:] = jax.lax.fori_loop(
        0, nchunks, body, jnp.zeros((rt, nkp), jnp.int32))


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
    nkp = -(-nk // _COL_CHUNK) * _COL_CHUNK
    # Invalid records AND pad slots get key -1 (matches nothing) — a 0-pad
    # would count phantom records of key 0.
    k = _pad_to(jnp.where(valid, keys, -1), 1, _COL_CHUNK, fill=-1)
    k = _pad_to(k, 0, _ROW_TILE, fill=-1)
    v = _pad_to(jnp.where(valid, vals, 0), 1, _COL_CHUNK)
    v = _pad_to(v, 0, _ROW_TILE)
    rp, bp = k.shape
    grid = (rp // _ROW_TILE,)
    params = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    spec_in = pl.BlockSpec((_ROW_TILE, bp), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    spec_out = pl.BlockSpec((_ROW_TILE, nkp), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    if not want_counts:
        sums = pl.pallas_call(
            _hist_kernel_sums,
            out_shape=jax.ShapeDtypeStruct((rp, nkp), jnp.int32),
            grid=grid,
            in_specs=[spec_in, spec_in],
            out_specs=spec_out,
            compiler_params=params,
            interpret=interpret,
        )(k, v)
        return sums[:r, :nk], None
    sums, cnts = pl.pallas_call(
        _hist_kernel,
        out_shape=(jax.ShapeDtypeStruct((rp, nkp), jnp.int32),
                   jax.ShapeDtypeStruct((rp, nkp), jnp.int32)),
        grid=grid,
        in_specs=[spec_in, spec_in],
        out_specs=(spec_out, spec_out),
        compiler_params=params,
        interpret=interpret,
    )(k, v)
    return sums[:r, :nk], cnts[:r, :nk]


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
    output (returned as None) — half the kernel work; the aggregation
    operators only need sums. Inside :func:`kernel_mesh` the kernel runs
    per mesh shard.
    """
    lead = keys.shape[:-1]
    b = keys.shape[-1]
    mode = force or ("pallas" if uses_kernel() else "xla")
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
