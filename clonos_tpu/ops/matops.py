"""MXU-backed exact integer data movement.

Dynamic gathers along the step axis are slow on TPU (~40ms for a
[512, 8, 997] take_along_axis at bench shapes) while one-hot f32 matmuls
on the MXU are ~free. These helpers express int32 gathers as two-matmul
(16-bit split) one-hot contractions with ``Precision.HIGHEST`` — exact
over the full int32 range (each product is 0/1 x 16-bit value; a row has
exactly one nonzero, so f32 accumulation is exact). A running count
along the lanes is the same trade: products of 0/1 operands with a
triangle, exact, where a scan strides the minor dimension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def onehot_gather_rows(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``out[k, p, :] = table[idx[k, p], p, :]`` — exact int32 gather along
    axis 0 of a ``[J, P, N]`` table, as two MXU one-hot matmuls.

    ``idx`` must already be clipped to ``[0, J)``.
    """
    j = table.shape[0]
    oh = (idx[:, :, None]
          == jnp.arange(j, dtype=jnp.int32)[None, None, :]
          ).astype(jnp.float32)                           # [K, P, J]
    lo = (table & 0xFFFF).astype(jnp.float32)
    hi = jnp.right_shift(table, 16).astype(jnp.float32)
    glo = jnp.einsum("kpj,jpn->kpn", oh, lo, precision=_HI,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    ghi = jnp.einsum("kpj,jpn->kpn", oh, hi, precision=_HI,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    return glo + (ghi << 16)


def running_count(mask: jnp.ndarray, group: int = 128) -> jnp.ndarray:
    """Inclusive running count of ``mask`` along its last axis, int32
    (``cumsum`` bit for bit). A scan along the lanes is strided slices
    of the minor dimension; this counts inside groups of ``group`` lanes
    by a product with a triangular matrix (0/1 operands, sums of at most
    ``group``: exact) and runs the sum over the groups' totals only."""
    n = mask.shape[-1]
    pad = (-n) % group
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    m = m.reshape(m.shape[:-1] + (-1, group)).astype(jnp.bfloat16)
    upto = (jnp.arange(group)[:, None] <= jnp.arange(group)[None, :])
    within = jnp.dot(m, upto.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    totals = within[..., -1]
    before = jnp.cumsum(totals, axis=-1) - totals
    return (within + before[..., None]).reshape(
        mask.shape[:-1] + (-1,))[..., :n]
