"""The transactional sink's tap (runtime/sinktap.py): the rows a block's
sink output holds, compacted on the device per subtask, equal the NumPy
mask-and-stack of the whole ``[K, P, capacity]`` output bit for bit —
whatever rung of the budget ladder the block is read through."""

import re
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

#: a block of 8 steps has the rungs 256, 512, ... 16384, a single step
#: 256 ... 2048: a factor of two apart
P, CAP = 4, 2048
#: (steps, rung) for every rung of both shapes
RUNGS = [(k, r) for k in (1, 8) for r in sinktap.ladder(k * CAP)]


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


def _fields(valid, rng, mesh=None):
    """Any keys, values and timestamps under ``valid``: the block on the
    host and on the device."""
    host = tuple(rng.randint(-2 ** 31, 2 ** 31 - 1, size=valid.shape,
                             dtype=np.int64).astype(np.int32)
                 for _ in range(3)) + (valid,)
    put = (jnp.asarray if mesh is None else
           lambda a: jax.device_put(a, NamedSharding(
               mesh, PartitionSpec(None, "tasks", None))))
    return host, RecordBatch(*[put(a) for a in host])


def _block(pattern, k, seed, mesh=None):
    rng = np.random.RandomState(seed)
    return _fields(_valid(pattern, k, rng), rng, mesh)


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


FORMS = {"by-rank": sinktap._pack_by_rank,
         "by-shifts": sinktap._pack_by_shifts}


def _uneven_block(k, cap, seed):
    """Sparse lanes, an empty one, and two full steps in another."""
    rng = np.random.RandomState(seed)
    valid = rng.rand(k, P, cap) < 0.05
    valid[:, 3] = False
    valid[2:4, 0] = True
    return _fields(valid, rng)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("budget", [1, 60, 300, 511, 512, 2048, 8192])
def test_either_form_at_any_budget(budget, form, monkeypatch):
    """Both forms of ``pack_lanes`` at budgets either would be given and
    neither: the ranks searched in one chunk, in several, and with a
    budget its chunks do not divide; the packed lane cut short, and
    whole. The first ``budget`` rows of every lane, in order, and
    nothing behind them."""
    monkeypatch.setattr(sinktap, "_RANK_CHUNK", 64)
    host, batch = _uneven_block(8, 1024, budget)
    counts, rows = jax.jit(FORMS[form], static_argnums=1)(batch, budget)
    _held_to_the_mask(host, counts, rows, budget)


@pytest.mark.parametrize("budget,shifts", [(255, False), (256, True),
                                           (257, True), (8192, True)])
def test_the_form_goes_by_the_budget_against_the_lane(budget, shifts,
                                                      monkeypatch):
    """A thirty-second of the lane is where the forms change; on both
    sides of it ``pack_lanes`` is the form ``packs_by_shifts`` names, and
    the two forms agree to the bit, zeros behind the count included."""
    k, cap = 8, 1024
    assert sinktap.packs_by_shifts(budget, k * cap) is shifts
    taken = []
    for name, form in FORMS.items():
        monkeypatch.setattr(
            sinktap, form.__name__,
            lambda b, n, name=name, form=form: taken.append(name)
            or form(b, n))
    host, batch = _uneven_block(k, cap, budget)
    counts, rows = jax.jit(sinktap.pack_lanes, static_argnums=1)(
        batch, budget)
    assert taken == ["by-shifts" if shifts else "by-rank"]
    _held_to_the_mask(host, counts, rows, budget)
    for form in FORMS.values():
        again = jax.jit(form, static_argnums=1)(batch, budget)
        np.testing.assert_array_equal(again[0], counts)
        np.testing.assert_array_equal(again[1], rows)


def _held_to_the_mask(host, counts, rows, budget):
    """``pack_lanes``' contract at one budget: every lane's count, its
    first ``budget`` rows in ``(step, slot)`` order, zeros behind them."""
    counts, rows = np.asarray(counts), np.asarray(rows)
    assert counts.dtype == rows.dtype == np.int32
    assert rows.shape == (counts.shape[0], 3, budget)
    for sub in range(counts.shape[0]):
        ref = _mask_and_stack(host, sub)
        assert counts[sub] == ref.shape[0]
        n = min(budget, ref.shape[0])
        np.testing.assert_array_equal(rows[sub, :, :n].T, ref[:n])
        assert not rows[sub, :, n:].any()


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh4"])
@pytest.mark.parametrize("k,rung", RUNGS)
@pytest.mark.parametrize("pattern", ["empty", "sparse", "hot", "full"])
def test_every_rung_holds_the_first_rows_of_every_lane(pattern, k, rung,
                                                       meshed, eight_devices):
    """The matrix again, rung by rung, so in both forms (a block's first
    rung is packed by rank, every other and all of a single step's by
    shifts): whichever rung a block is packed through — one that holds
    every lane, or one the hot lane overflows — it gives each lane's
    count and its first ``rung`` rows."""
    assert sinktap.packs_by_shifts(rung, k * CAP) is (rung > 256 or k == 1)
    mesh = (jax.sharding.Mesh(np.asarray(eight_devices[:4]), ("tasks",))
            if meshed else None)
    host, batch = _block(pattern, k, 7, mesh)
    counts, rows = _tap(mesh)._program(batch, rung)(batch)
    if meshed:
        assert rows.sharding.shard_shape(rows.shape) == (P // 4, 3, rung)
    _held_to_the_mask(host, counts, rows, rung)


def _lane_of(count, k, cap, rng, last_steps=0):
    """``[k, cap]`` validity with exactly ``count`` rows, anywhere or
    all in the lane's last ``last_steps`` steps."""
    v = np.zeros(k * cap, bool)
    lo = (k - last_steps) * cap if last_steps else 0
    v[lo + rng.choice(k * cap - lo, size=count, replace=False)] = True
    return v.reshape(k, cap)


@pytest.mark.parametrize("off", [-1, 0, 1], ids=["under", "at", "over"])
@pytest.mark.parametrize("rung", [256, 2048])
def test_a_count_at_under_and_over_a_rung(rung, off):
    """The hottest lane holds one row fewer than the rung, exactly the
    rung, one more: the first two are read through it, the third is read
    again through the next, and no row is lost or repeated."""
    k = 8
    rng = np.random.RandomState(rung + off)
    valid = np.stack([_lane_of(c, k, CAP, rng) for c in
                      (0, rung + off, 17, rung // 2)], axis=1)
    host, batch = _fields(valid, rng)
    tap, log = _tap(None), TransactionLog(0)
    lad = tap._ladders[(valid.shape, batch.valid.sharding)] = \
        tap._build(batch)
    lad.seen = rung * 2 // 3            # what speculates exactly ``rung``
    packed = tap.dispatch(batch)
    assert packed.rung == rung
    _held_to_the_mask(host, packed.counts, packed.rows, rung)
    counts, rows = tap.read(packed)
    assert packed.missed == (off > 0)
    assert packed.rung == (2 * rung if off > 0 else rung)
    assert packed.slots == P * rung * (3 if off > 0 else 1)
    assert packed.shifted == (rung > 256) + (off > 0)
    log.absorb(0, counts, rows)
    for sub, got in log.pending_shards(0).items():
        np.testing.assert_array_equal(got, _mask_and_stack(host, sub))


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("last_steps", [1, 2])
def test_rows_that_all_sit_in_a_lanes_last_steps(last_steps, form):
    """Every row as far from its packed place as a lane allows: the
    largest shifts, the last steps' marks."""
    k, rung = 8, 1024
    rng = np.random.RandomState(last_steps)
    valid = np.stack([_lane_of(c, k, CAP, rng, last_steps) for c in
                      (rung, 1, 300, 0)], axis=1)
    host, batch = _fields(valid, rng)
    counts, rows = jax.jit(FORMS[form], static_argnums=1)(batch, rung)
    _held_to_the_mask(host, counts, rows, rung)


#: the two densest cells' sink blocks, ``[K, P, capacity]``, the rows a
#: subtask holds a block there and the rung that takes them
DENSE_CELLS = {"nexmark-q8": ((1024, 16, 320), 9970, 20480),
               "nexmark-q3": ((1024, 16, 256), 3900, 8192)}


@pytest.mark.parametrize("cell", sorted(DENSE_CELLS))
def test_the_dense_cells_own_shapes_at_their_density(cell):
    (k, p, cap), held, rung = DENSE_CELLS[cell]
    assert sinktap._Ladder(dict.fromkeys(sinktap.ladder(k * cap))) \
        .rung_for(held + (held >> 1)) == rung
    rng = np.random.RandomState(held)
    valid = rng.rand(k, p, cap) < held / (k * cap)
    host, batch = _fields(valid, rng)
    counts, rows = jax.jit(sinktap.pack_lanes, static_argnums=1)(batch, rung)
    assert int(np.asarray(counts).max()) <= rung
    _held_to_the_mask(host, counts, rows, rung)


@pytest.mark.parametrize("cell,rung,gathers", [
    ("nexmark-q8", 20480, 0), ("nexmark-q8", 5120, 4),
    ("nexmark-q3", 8192, 0), ("nexmark-q3", 4096, 4)])
def test_the_dense_cells_programs_never_sort_and_gather_as_stated(
        cell, rung, gathers):
    """The lowered text of the programs a dense cell's blocks go
    through, at the rung its rows take and at one for a quarter or half
    of them: no sort, no scatter, and the gathers the form states — none
    packing by shifts, four a slot packing by rank — so that a later
    edit cannot bring the five of the first form back unseen."""
    shape, _, dense = DENSE_CELLS[cell]
    assert sinktap.packs_by_shifts(rung, shape[0] * shape[2]) is \
        (rung == dense) is (gathers == 0)
    spec = RecordBatch(*[jax.ShapeDtypeStruct(shape, dt) for dt in
                         (jnp.int32, jnp.int32, jnp.int32, jnp.bool_)])
    text = jax.jit(sinktap.pack_lanes, static_argnums=1).lower(
        spec, rung).as_text()
    assert "stablehlo.sort" not in text and "stablehlo.scatter" not in text
    assert len(re.findall(r'stablehlo\.gather"?\(', text)) == gathers
    # the ranks go in chunks, the shifts in one pass a bit
    assert ("stablehlo.while" in text) is (gathers > 0)


def test_every_rung_is_built_when_a_shape_is_first_seen():
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: built.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    tap = _tap(None)
    _, sparse = _block("sparse", 8, 5)
    tap.read(tap.dispatch(sparse))
    first = len(built)
    assert first >= len(sinktap.ladder(8 * CAP)) == 7
    for pattern in ("full", "sparse", "hot", "empty"):
        _, batch = _block(pattern, 8, 6)
        tap.read(tap.dispatch(batch))
    assert len(built) == first               # nothing built on a miss


@pytest.mark.parametrize("k,cap", [(512, 640), (1024, 1152), (1024, 1280),
                                   (1024, 320), (1024, 256), (1, 640),
                                   (8, 16), (1, 255)])
def test_ladder_is_a_factor_of_two_apart_and_ends_at_the_lane(k, cap):
    rungs = sinktap.ladder(k * cap)
    assert rungs[-1] == k * cap and list(rungs) == sorted(set(rungs))
    assert all(r >= min(256, k * cap) for r in rungs)
    assert all(hi // 2 == lo for lo, hi in zip(rungs, rungs[1:]))
    assert rungs[0] < 512 and len(rungs) <= 13
    # so the headroom dispatch asks for costs at most three slots a row
    lad = sinktap._Ladder(dict.fromkeys(rungs))
    for count in range(rungs[0], k * cap, max(1, k * cap // 97)):
        want = count + (count >> 1)
        assert lad.rung_for(want) <= max(3 * count, rungs[0])


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
    # every block's speculated rung, and the read-again's
    assert c["sink.pack_slots"] == P * (3 * rungs[0] + 3 * rungs[-1])
    # the first rung packs by rank, the top one by shifts
    assert c["sink.packs_by_rank"] == c["sink.packs_by_shifts"] == 3
    assert c["block.dispatches.sink_pack"] == 6
    got = log.pending_shards(0)
    for sub in range(P):
        np.testing.assert_array_equal(got[sub], np.concatenate(want[sub]))
    assert c["sink.rows"] == sum(g.shape[0] for g in got.values())


# --- the benchmark's reader of the tap's device time --------------------------


def _reader():
    import importlib.util
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "sink_pack_device_ms_per_block", os.path.join(
            bench, "readers", "sink_pack_device_ms_per_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MS = 1e6        # the trace's nanoseconds


@pytest.mark.parametrize("modules,window,want", [
    # two blocks and their taps inside the window, a read-again among
    # them; the third block and its tap run past the window's end
    ([("jit_run_block(7)", 0.0, 100 * MS), ("jit_sink_pack(9)", 100 * MS, 8 * MS),
      ("jit_run_block(7)", 108 * MS, 100 * MS),
      ("jit_sink_pack(9)", 208 * MS, 8 * MS),
      ("jit_sink_pack(11)", 216 * MS, 14 * MS),
      ("jit_run_block(7)", 900 * MS, 100 * MS),
      ("jit_sink_pack(9)", 1000 * MS, 8 * MS)], (0.0, 950 * MS), 15.0),
    # another program's name that begins alike is not the tap's
    ([("jit_run_block(7)", 0.0, 100 * MS),
      ("jit_sink_pack_again(2)", 100 * MS, 8 * MS)], (0.0, 950 * MS), None),
    # no block program in the window, no trace, no window
    ([("jit_sink_pack(9)", 100 * MS, 8 * MS)], (0.0, 950 * MS), None),
    ([], (0.0, 950 * MS), None),
    ([("jit_run_block(7)", 0.0, 100 * MS),
      ("jit_sink_pack(9)", 100 * MS, 8 * MS)], None, None),
], ids=["two-blocks", "other-program", "no-block", "no-trace", "no-window"])
def test_the_reader_of_the_taps_device_time_a_block(modules, window, want):
    run = types.SimpleNamespace(
        events=types.SimpleNamespace(modules={0: modules} if modules else {}),
        trace_window=lambda span: window)
    got = _reader().read(run)
    assert got == (want if want is None else pytest.approx(want))
