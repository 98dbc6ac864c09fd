"""The transactional sink's tap (runtime/sinktap.py): the rows a block's
sink output holds, compacted on the device per subtask, equal the NumPy
mask-and-stack of the whole ``[K, P, capacity]`` output bit for bit —
whatever rung of the budget ladder the block is read through."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from clonos_tpu import obs
from clonos_tpu.api.records import RecordBatch
from clonos_tpu.runtime import sinktap
from clonos_tpu.runtime.txn import TransactionLog

#: a block of 8 steps has the rungs (256, 2048, 16384), a single step
#: (256, 2048)
P, CAP = 4, 2048


@pytest.fixture(autouse=True)
def _fresh_recorder():
    obs.reset()
    yield
    obs.reset()


def _mask_and_stack(host, sub):
    """What ``TransactionLog.absorb`` appended for one subtask before the
    tap compacted on the device: the reference."""
    keys, values, timestamps, valid = host
    m = valid[:, sub].reshape(-1)
    return np.stack([keys[:, sub].reshape(-1)[m],
                     values[:, sub].reshape(-1)[m],
                     timestamps[:, sub].reshape(-1)[m]], axis=1)


def _valid(pattern, k, rng):
    if pattern == "empty":
        return np.zeros((k, P, CAP), bool)
    if pattern == "full":
        return np.ones((k, P, CAP), bool)
    v = rng.rand(k, P, CAP) < 0.001          # the cells' ~0.1 %
    v[0, 1, 7] = True                        # never quite empty
    if pattern == "hot":
        v[:, 2] = rng.rand(k, CAP) < 0.6
    return v


def _block(pattern, k, seed, mesh=None):
    rng = np.random.RandomState(seed)
    valid = _valid(pattern, k, rng)
    host = tuple(rng.randint(-2 ** 31, 2 ** 31 - 1, size=valid.shape,
                             dtype=np.int64).astype(np.int32)
                 for _ in range(3)) + (valid,)
    put = (jnp.asarray if mesh is None else
           lambda a: jax.device_put(a, NamedSharding(
               mesh, PartitionSpec(None, "tasks", None))))
    return host, RecordBatch(*[put(a) for a in host])


def _tap(mesh):
    return sinktap.SinkTap(mesh, "tasks")


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh4"])
@pytest.mark.parametrize("k", [1, 8], ids=["step", "block"])
@pytest.mark.parametrize("pattern", ["empty", "sparse", "hot", "full"])
def test_packed_rows_equal_the_numpy_mask_and_stack(pattern, k, meshed,
                                                    eight_devices):
    mesh = (jax.sharding.Mesh(np.asarray(eight_devices[:4]), ("tasks",))
            if meshed else None)
    tap, log = _tap(mesh), TransactionLog(0)
    want = {sub: [] for sub in range(P)}
    misses = 0
    # two blocks, so that the second is read through the rung the first
    # one's counts chose
    for seed in (3, 4):
        host, batch = _block(pattern, k, seed, mesh)
        packed = tap.dispatch(batch)
        if meshed:              # each device holds its own subtask's rows
            assert packed.rows.sharding.shard_shape(
                packed.rows.shape) == (P // 4, 3, packed.rung)
        counts, rows = tap.read(packed)
        misses += packed.missed
        assert counts.dtype == rows.dtype == np.int32
        assert rows.shape == (P, 3, packed.rung)
        assert packed.nbytes >= counts.nbytes + rows.nbytes
        log.absorb(0, counts, rows)
        for sub in range(P):
            want[sub].append(_mask_and_stack(host, sub))
    got = log.pending_shards(0)
    for sub in range(P):
        ref = np.concatenate(want[sub], axis=0)
        assert got[sub].dtype == ref.dtype and got[sub].shape == ref.shape
        np.testing.assert_array_equal(got[sub], ref)
        assert got[sub].flags.c_contiguous
    rungs = sinktap.ladder(k * CAP)
    assert rungs[-1] == k * CAP
    # a dense lane of a block overflows the rung an unseen shape starts
    # on, is read again through the top one, and starts there next time
    assert len(rungs) > 1
    assert misses == (1 if pattern in ("hot", "full") else 0)
    if pattern == "full":
        assert packed.rung == rungs[-1]


@pytest.mark.parametrize("budget", [1, 60, 300, 511, 512, 2048, 8192])
def test_pack_lanes_at_any_budget(budget, monkeypatch):
    """The ranks searched in one chunk, in several, and with a budget
    its chunks do not divide: the first ``budget`` rows of every lane,
    in order."""
    monkeypatch.setattr(sinktap, "_RANK_CHUNK", 64)
    k, cap = 8, 1024
    rng = np.random.RandomState(budget)
    valid = rng.rand(k, P, cap) < 0.05
    valid[:, 3] = False                      # an empty lane
    valid[2:4, 0] = True                     # two full steps in another
    host = tuple(rng.randint(-2 ** 31, 2 ** 31 - 1, size=valid.shape,
                             dtype=np.int64).astype(np.int32)
                 for _ in range(3)) + (valid,)
    counts, rows = jax.jit(sinktap.pack_lanes, static_argnums=1)(
        RecordBatch(*[jnp.asarray(a) for a in host]), budget)
    assert rows.shape == (P, 3, budget) and counts.shape == (P,)
    for sub in range(P):
        ref = _mask_and_stack(host, sub)
        assert int(counts[sub]) == ref.shape[0]
        n = min(budget, ref.shape[0])
        np.testing.assert_array_equal(np.asarray(rows)[sub, :, :n].T,
                                      ref[:n])


def test_every_rung_is_built_when_a_shape_is_first_seen():
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: built.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    tap = _tap(None)
    _, sparse = _block("sparse", 8, 5)
    tap.read(tap.dispatch(sparse))
    first = len(built)
    assert first >= len(sinktap.ladder(8 * CAP)) == 3
    for pattern in ("full", "sparse", "hot", "empty"):
        _, batch = _block(pattern, 8, 6)
        tap.read(tap.dispatch(batch))
    assert len(built) == first               # nothing built on a miss


def test_ladder_is_short_and_ends_at_the_lane():
    for k, cap in ((512, 640), (1024, 1152), (1024, 1280), (1, 640),
                   (8, 16)):
        rungs = sinktap.ladder(k * cap)
        assert rungs[-1] == k * cap and list(rungs) == sorted(set(rungs))
        assert len(rungs) <= 5 and all(r >= min(256, k * cap)
                                       for r in rungs)


def _runner_with_a_transactional_sink():
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.cluster import ClusterRunner
    env = StreamEnvironment(name="tap", num_key_groups=16)
    (env.synthetic_source(vocab=13, batch_size=4, parallelism=2)
        .key_by().window_count(num_keys=13, window_size=40)
        .sink(transactional=True))
    return ClusterRunner(env.build(), steps_per_epoch=3, seed=3)


def test_a_count_over_the_speculated_rung_is_read_again_and_counted():
    """Through the runner's own tap: a sparse block, then one whose
    hottest subtask holds more rows than the rung the sparse one chose."""
    runner = _runner_with_a_transactional_sink()
    (vid,) = runner.txn_logs
    log = runner.txn_logs[vid]
    tr = obs.get_tracer()
    want = {sub: [] for sub in range(P)}
    rungs = sinktap.ladder(8 * CAP)
    for pattern, rung, missed in (("sparse", rungs[0], 0),
                                  ("hot", rungs[-1], 1),
                                  ("hot", rungs[-1], 0),
                                  ("sparse", rungs[-1], 0),
                                  ("sparse", rungs[0], 0)):
        host, batch = _block(pattern, 8, len(want[0]) + 20)
        before = tr.counters().get("sink.rung_misses", 0)
        runner._absorb_sink_outputs(
            types.SimpleNamespace(sinks={vid: batch}), 0)
        runner._read_sink_tap()          # the tap only launched: drain it
        d2h = [r for r in tr.records() if r["name"] == "block.sink.d2h"][-1]
        assert d2h["args"]["rung"] == rung
        assert tr.counters().get("sink.rung_misses", 0) - before == missed
        first = 4 * P + 4 * P * 3 * (rungs[0] if missed else rung)
        again = 4 * P * 3 * rung if missed else 0
        assert d2h["args"]["bytes"] == first + again
        for sub in range(P):
            want[sub].append(_mask_and_stack(host, sub))
    c = tr.counters()
    assert c["sink.rung_reads"] == 5 and c["sink.rung_misses"] == 1
    assert c["block.dispatches.sink_pack"] == 6
    got = log.pending_shards(0)
    for sub in range(P):
        np.testing.assert_array_equal(got[sub], np.concatenate(want[sub]))
    assert c["sink.rows"] == sum(g.shape[0] for g in got.values())
