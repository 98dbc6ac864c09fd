"""Pallas log kernels: property equivalence against the XLA scatter path
(interpret mode on the CPU mesh; the same kernel compiles via Mosaic on
a TPU — tests/test_tpu_aot.py compiles it, chip_smoke.py runs it)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from clonos_tpu.causal import log as clog
from clonos_tpu.ops.histogram import kernel_mesh, keyed_hist


@pytest.mark.parametrize("b", [100, 128, 300])
def test_keyed_hist_kernel_matches_xla(b):
    """The Pallas histogram (the keyed-aggregation scatter replacement)
    must be bit-identical to the XLA fallback — including non-128-multiple
    record axes (pad slots must not count as key-0 records) and
    out-of-range keys (mode=drop parity)."""
    rng = np.random.RandomState(1)
    nk = 13
    keys = jnp.asarray(rng.randint(-3, nk + 4, (5, 4, b)), jnp.int32)
    vals = jnp.asarray(rng.randint(-50, 50, (5, 4, b)), jnp.int32)
    valid = jnp.asarray(rng.rand(5, 4, b) < 0.7)
    s1, c1 = keyed_hist(keys, vals, valid, nk, force="interpret")
    s2, c2 = keyed_hist(keys, vals, valid, nk, force="xla")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("shape", [(5, 4, 100), (3, 6, 128), (7, 300)])
@pytest.mark.parametrize("want_counts", [True, False])
def test_keyed_hist_kernel_per_mesh_shard_matches_xla(shape, want_counts,
                                                      eight_devices):
    """Inside ``kernel_mesh`` the kernel runs per shard of the last
    leading dim (padded up to the mesh when it does not divide) and
    still equals the scatter — the form every mesh-lowered program
    takes on a TPU, where a bare Mosaic kernel cannot be partitioned."""
    rng = np.random.RandomState(2)
    nk = 13
    mesh = jax.sharding.Mesh(np.array(eight_devices[:4]), ("tasks",))
    keys = jnp.asarray(rng.randint(-3, nk + 4, shape), jnp.int32)
    vals = jnp.asarray(rng.randint(-50, 50, shape), jnp.int32)
    valid = jnp.asarray(rng.rand(*shape) < 0.7)

    def sharded(k, v, m):
        with kernel_mesh(mesh, "tasks"):
            return keyed_hist(k, v, m, nk, force="interpret",
                              want_counts=want_counts)

    s1, c1 = jax.jit(sharded)(keys, vals, valid)
    s2, c2 = keyed_hist(keys, vals, valid, nk, force="xla")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    if want_counts:
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    else:
        assert c1 is None


@pytest.mark.parametrize("cap,sizes", [
    (64, (4, 16, 28)),       # dense pad/roll branch (n * 64 >= cap)
    (512, (4, 6, 3)),        # small-append scatter branch (n * 64 < cap)
    (512, (4, 200, 3, 380)), # mixed: scatter resumes at a head the
                             # dense branch advanced, and wraps
])
def test_bulk_append_full_matches_masked_append(cap, sizes):
    """The block executor's bulk path (append_full — dense pad/roll for
    large appends, unique-index scatter for small ones) must agree with
    the general masked append, including ring wraps."""
    rng = np.random.RandomState(3)
    L = 4
    a = jax.vmap(lambda _: clog.create(cap, 8))(jnp.arange(L))
    b = jax.vmap(lambda _: clog.create(cap, 8))(jnp.arange(L))
    for n in sizes:
        rows = jnp.asarray(rng.randint(-9, 9, (L, n, 8)), jnp.int32)
        a = clog.v_append_full(a, rows)
        b = clog.v_append(b, rows, jnp.full((L,), n, jnp.int32))
    np.testing.assert_array_equal(np.asarray(a.rows), np.asarray(b.rows))
    np.testing.assert_array_equal(np.asarray(a.head), np.asarray(b.head))
