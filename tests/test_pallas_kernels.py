"""Pallas log kernels: property equivalence against the XLA scatter path
(interpret mode on the CPU mesh; the same kernel compiles via Mosaic on
a TPU — tests/test_tpu_aot.py compiles it, chip_smoke.py runs it)."""

import collections

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from clonos_tpu.causal import log as clog
from clonos_tpu.obs import trace
from clonos_tpu.ops import histogram
from clonos_tpu.ops.histogram import KERNEL_MAX_KEYS, kernel_mesh, keyed_hist


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


INT32_MIN, INT32_MAX = -(1 << 31), (1 << 31) - 1

#: the cells' calls, rows scaled down for interpret mode: leading dims,
#: record columns, keys, whether a row's keys are unique (a placement)
CELL_SHAPES = {
    "kafka-window": ((2, 8), 640, 499, False),
    "kafka-place": ((16,), 512, 8192, True),
    "allround-window": ((2, 8), 1152, 997, False),
    "allround-place": ((8,), 1024, 8192, True),
    "allroundup-sliding": ((2, 8), 1408, 1400, False),
    "allroundup-place": ((8,), 1024, 4096, True),
    "long-row": ((3,), 5000, 997, False),        # walks the column grid
    "one-key": ((5, 3), 300, 1, False),
    "widest": ((8,), 256, KERNEL_MAX_KEYS, True),
}


def _cell_inputs(lead, b, nk, unique, seed):
    """Keys a little outside ``[0, nk)`` on both sides, values over the
    whole int32 range with both edges planted (so per-key sums wrap),
    a tenth of the records invalid."""
    rng = np.random.RandomState(seed)
    shape = lead + (b,)
    if unique:
        keys = np.stack([rng.permutation(nk + 3)[:b] - 1
                         for _ in range(int(np.prod(lead)))]).reshape(shape)
    else:
        keys = rng.randint(-3, nk + 4, shape)
    vals = rng.randint(INT32_MIN, INT32_MAX + 1, shape, dtype=np.int64)
    edge = rng.rand(*shape)
    vals = np.where(edge < 0.1, INT32_MIN, np.where(edge > 0.9, INT32_MAX,
                                                    vals))
    valid = rng.rand(*shape) < 0.9
    return keys.astype(np.int32), vals.astype(np.int32), valid


def _numpy_hist(keys, vals, valid, nk):
    """Record at a time in int64, wrapped to int32 at the end: the
    scatter-add's result modulo 2**32."""
    lead = keys.shape[:-1]
    k, v, m = (x.reshape(-1, keys.shape[-1]) for x in (keys, vals, valid))
    ok = m & (k >= 0) & (k < nk)
    rows = np.broadcast_to(np.arange(k.shape[0])[:, None], k.shape)
    sums = np.zeros((k.shape[0], nk), np.int64)
    cnts = np.zeros((k.shape[0], nk), np.int64)
    np.add.at(sums, (rows[ok], k[ok]), v[ok].astype(np.int64))
    np.add.at(cnts, (rows[ok], k[ok]), 1)
    wrap = lambda x: (x & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    return (wrap(sums).reshape(lead + (nk,)),
            wrap(cnts).reshape(lead + (nk,)))


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-device", "per-mesh-shard"])
@pytest.mark.parametrize("want_counts", [True, False],
                         ids=["sums-and-counts", "sums"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_mxu_kernel_equals_scatter_equals_numpy(cell, want_counts, sharded,
                                                request):
    """The factored one-hot kernel (interpret mode) == the XLA scatter ==
    a NumPy fold modulo 2**32, at the cells' call shapes: negative
    values, both int32 edges, sums that wrap, invalid records, keys out
    of range on both sides, ``nk`` off a multiple of 128, one key, and
    the widest table callers may hand it."""
    lead, b, nk, unique = CELL_SHAPES[cell]
    keys, vals, valid = _cell_inputs(lead, b, nk, unique, seed=len(cell))
    want_s, want_c = _numpy_hist(keys, vals, valid, nk)
    assert (want_s < 0).any() or nk == 1
    args = tuple(map(jnp.asarray, (keys, vals, valid)))

    def kernel(k, v, m):
        return keyed_hist(k, v, m, nk, force="interpret",
                          want_counts=want_counts)

    if sharded:
        devices = request.getfixturevalue("eight_devices")
        mesh = jax.sharding.Mesh(np.array(devices[:4]), ("tasks",))
        s1, c1 = jax.jit(histogram.over_mesh(kernel, mesh, "tasks"))(*args)
    else:
        s1, c1 = kernel(*args)
    s2, c2 = keyed_hist(*args, nk, force="xla", want_counts=want_counts)
    np.testing.assert_array_equal(np.asarray(s1), want_s)
    np.testing.assert_array_equal(np.asarray(s2), want_s)
    if want_counts:
        np.testing.assert_array_equal(np.asarray(c1), want_c)
        np.testing.assert_array_equal(np.asarray(c2), want_c)
    else:
        assert c1 is None and c2 is None


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs it holds (the
    kernel's body, its loops), as primitive names."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_equations(sub))
    return names


@pytest.mark.parametrize("want_counts", [True, False],
                         ids=["sums-and-counts", "sums"])
def test_kernel_trace_does_not_grow_with_key_lanes(want_counts):
    """What refused PR 29: a kernel body unrolled over key blocks is
    traced, lowered and loaded into every program that holds it. The
    body here is one chunk step: the same equations — one ``dot_general``,
    the same compares — at 512 keys and at 16,384."""
    arg = jax.ShapeDtypeStruct((64, 1024), jnp.int32)
    mask = jax.ShapeDtypeStruct((64, 1024), jnp.bool_)
    counts = []
    for nk in (512, KERNEL_MAX_KEYS):
        jaxpr = jax.make_jaxpr(
            lambda k, v, m: histogram._hist_pallas(k, v, m, nk, True,
                                                   want_counts))(
            arg, arg, mask)
        counts.append(collections.Counter(_equations(jaxpr.jaxpr)))
    narrow, wide = counts
    # the one thing that may differ: the widest table fills its padded
    # ``hi`` rows, so the slice that drops the padding is not traced
    assert 0 <= narrow.pop("slice") - wide.pop("slice") <= 2
    assert narrow == wide
    assert narrow["pallas_call"] == 1 and narrow["dot_general"] == 1
    assert narrow["eq"] == 2 and sum(narrow.values()) < 120


def test_calls_of_one_shape_share_one_kernel_body():
    """N ``keyed_hist`` calls of one call shape (an exchange places keys,
    values and timestamps over the same slots) trace the kernel once:
    the program holds one body, called N times; the ``hist.kernel``
    instants say which form each call site took."""
    tracer = trace.configure("hist-test")
    try:
        def place(slot, k, v, t, keep):
            outs = [keyed_hist(slot, x, keep, 1024, force="interpret",
                               want_counts=False)[0] for x in (k, v, t)]
            return outs + list(keyed_hist(slot, k, keep, 1024,
                                          force="interpret"))

        a = jax.ShapeDtypeStruct((16, 256), jnp.int32)
        jaxpr = jax.make_jaxpr(place)(
            a, a, a, a, jax.ShapeDtypeStruct((16, 256), jnp.bool_))
        notes = [r["args"] for r in tracer.records()
                 if r["name"] == "hist.kernel"]
    finally:
        trace.reset()
    calls = [e for e in jaxpr.jaxpr.eqns
             if "_hist_pallas" in str(e.params.get("name"))]
    assert len(calls) == 4
    assert len({id(e.params["jaxpr"]) for e in calls}) == 2   # sums; counts
    assert [(n["form"], n["rows"], n["cols"], n["lanes"], n["hi"],
             n["planes"]) for n in notes] == (
        [("mxu", 16, 256, 1024, 8, 4)] * 3 + [("mxu", 16, 256, 1024, 8, 5)])


@pytest.mark.parametrize("cap,sizes", [
    (64, (4, 16, 28)),       # dense pad/roll branch (n * 64 >= cap)
    (512, (4, 6, 3)),        # small-append scatter branch (n * 64 < cap)
    (512, (4, 200, 3, 380)), # mixed: scatter resumes at a head the
                             # dense branch advanced, and wraps
    (64, (16,) * 6),         # cap == 4n (``kafka-window-64``): dense,
                             # the ring wrapped once
    (512, (3, 16, 16, 16)),  # cap == 32n (``allround-32``): windowed
                             # read-merge-write, off the window grid
])
def test_bulk_append_full_matches_masked_append(cap, sizes):
    """The block executor's bulk path (append_full — dense pad/roll or
    the windowed read-merge-write for large appends, unique-index
    scatter for small ones: ``append_form``) must agree with the general
    masked append, including ring wraps. (The replica stack's run-wise
    form: tests/test_replica_append.py.)"""
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
