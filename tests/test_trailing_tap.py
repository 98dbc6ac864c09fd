"""The sink tap trails the block program by one block
(runtime/sinktap.py, ``ClusterRunner._absorb_sink_outputs``): block k's
rows are read after block k+1 is on the device, and whatever is still in
flight is read where a block loop ends. What a consumer of the sink, a
fence and a checkpoint see must not depend on that: every run here is
held, row for row and epoch for epoch, to a second runner whose tap is
strictly serial and written in this file from each block's dense sink
output (a NumPy mask-and-stack), not from the program's tap."""

import jax
import numpy as np
import pytest

from clonos_tpu import obs
from clonos_tpu.api.environment import StreamEnvironment
from clonos_tpu.api.feeds import ListFeedReader
from clonos_tpu.parallel import distributed as dist
from clonos_tpu.runtime.cluster import ClusterRunner

SPE = 16                # steps an epoch of the served job
EPOCHS = 3


@pytest.fixture(autouse=True)
def _fresh_recorder():
    obs.reset()
    yield
    obs.reset()


# --- two jobs, each with a feed ------------------------------------------------


def _served(tmp_path, tag, block_steps, overlap=False, mesh=None):
    """chip_smoke's served shape at a tiny size: host source -> keyBy ->
    count window -> keyBy -> reduce -> transactional sink."""
    import chip_smoke as cs
    shape = cs.ServedShape(parallelism=2, batch=4, num_keys=7,
                           edge_capacity=16, steps_per_epoch=SPE,
                           window_steps=4, kill_after=8, epochs=8)
    runner = ClusterRunner(
        cs.build_served_job(shape), steps_per_epoch=SPE, log_capacity=512,
        max_epochs=8, inflight_ring_steps=64, seed=1, logical_time=True,
        audit=False, checkpoint_dir=str(tmp_path / tag),
        block_steps=block_steps, overlap_epoch=overlap, mesh=mesh)
    runner.executor.register_feed(
        0, ListFeedReader(list(cs.make_feed(shape, 3))))
    return runner


#: the bursty job: 32 steps an epoch in blocks of 8; a sink lane of
#: 8 x 256 slots has the rungs (256, 2048)
BURST_SPE, BURST_BLOCK, BURST_BATCH = 32, 8, 64


def _bursty(tmp_path, tag, serial_block=None, mesh=None):
    """host source -> filter -> transactional sink, fed so that blocks
    alternate between a sliver of rows and 512 a subtask: every dense
    block passes the rung the sparse one before it chose."""
    env = StreamEnvironment(name="bursty", num_key_groups=16)
    (env.host_source(batch_size=BURST_BATCH, parallelism=2)
        .filter(lambda k, v, t: v > 0)
        .sink(transactional=True, capacity=256))
    runner = ClusterRunner(
        env.build(), steps_per_epoch=BURST_SPE, log_capacity=1024,
        max_epochs=8, inflight_ring_steps=128, seed=2, logical_time=True,
        audit=False, checkpoint_dir=str(tmp_path / tag),
        block_steps=serial_block or BURST_BLOCK, mesh=mesh)
    rng = np.random.RandomState(5)
    steps = 4 * BURST_SPE
    dense = (np.arange(steps) // BURST_BLOCK) % 2 == 1
    keep = np.where(dense[:, None], 1, rng.rand(steps, BURST_BATCH) < 0.02)
    parts = []
    for _ in range(2):
        keys = rng.randint(0, 1000, steps * BURST_BATCH)
        vals = keep.reshape(-1) * rng.randint(1, 1 << 20, keys.shape)
        parts.append(np.stack([keys, vals], axis=1))
    runner.executor.register_feed(0, ListFeedReader(parts))
    return runner


# --- the strictly serial tap, and what a run shows -----------------------------


def _serial_tap(runner):
    """Replace the program's tap: wait for each block, read its dense
    ``[K, P, capacity]`` sink output whole, mask and stack it per
    subtask in (step, slot) order."""
    (vid, log), = runner.txn_logs.items()

    def absorb(outs, epoch):
        keys, values, timestamps, valid = (
            np.asarray(a) for a in outs.sinks[vid])
        p = valid.shape[1]
        lanes = [np.stack([a[:, sub].reshape(-1)[valid[:, sub].reshape(-1)]
                           for a in (keys, values, timestamps)])
                 for sub in range(p)]
        counts = np.asarray([m.shape[1] for m in lanes], np.int32)
        rows = np.zeros((p, 3, max(1, counts.max())), np.int32)
        for sub, m in enumerate(lanes):
            rows[sub, :, :counts[sub]] = m
        log.absorb(epoch, counts, rows)

    runner.executor.on_block_outputs = absorb
    runner.executor.drain_block_outputs = None


class Seen:
    """What a run shows from outside: the pending shards at every fence
    (read where the transaction has just sealed), the committed stream,
    the final carry's log heads."""

    def __init__(self, runner):
        self.runner = runner
        (self.log,) = runner.txn_logs.values()
        self.fences = []
        runner.fence_hooks.append(lambda closed: self.fences.append(
            (closed, self.log.pending_shards(closed))))

    def close(self):
        self.runner.drain_fence()
        carry = self.runner.executor.carry
        self.heads = (np.asarray(carry.logs.head),
                      np.asarray(carry.replicas.head))
        return self


def _assert_same(got: Seen, want: Seen):
    assert [e for e, _ in got.fences] == [e for e, _ in want.fences]
    for (epoch, g), (_, w) in zip(got.fences, want.fences):
        assert sorted(g) == sorted(w), epoch
        for sub in w:
            np.testing.assert_array_equal(g[sub], w[sub],
                                          err_msg=f"{epoch=} {sub=}")
    assert [e for e, _ in got.log.committed] == \
        [e for e, _ in want.log.committed]
    for (epoch, g), (_, w) in zip(got.log.committed, want.log.committed):
        np.testing.assert_array_equal(g, w, err_msg=f"{epoch=}")
    assert got.log.committed_stream().shape[0] > 0
    for g, w in zip(got.heads, want.heads):
        np.testing.assert_array_equal(g, w)


def _drive(runner, steps_first):
    """``EPOCHS`` epochs, the middle one begun with single steps; after
    every call that ends a block loop, nothing is in flight."""
    for e in range(EPOCHS):
        for _ in range(steps_first if e == 1 else 0):
            runner.step()
            assert runner._tap_pending is None
        runner.run_epoch(complete_checkpoint=True)
        assert runner._tap_pending is None


_serial_runs = {}


def _serial_served(tmp_path_factory, steps_first):
    """The serial expectation does not depend on the block size, the
    fence or the mesh: one run for each way of driving."""
    if steps_first not in _serial_runs:
        runner = _served(tmp_path_factory.mktemp("serial"), "ck", 8)
        _serial_tap(runner)
        seen = Seen(runner)
        _drive(runner, steps_first)
        _serial_runs[steps_first] = seen.close()
    return _serial_runs[steps_first]


def _mesh(meshed):
    if meshed and len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    return dist.task_mesh(max_devices=2) if meshed else None


#: block_steps, single steps before the middle epoch's run_epoch, the
#: blocks that epoch then runs
SHAPES = {"1-block": (16, 0, [16]), "2-blocks": (8, 0, [8, 8]),
          "4-blocks": (4, 0, [4, 4, 4, 4]), "short-last": (6, 0, [6, 6, 4]),
          "steps-first": (4, 3, [4, 4, 4, 1])}


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh2"])
@pytest.mark.parametrize("overlap", [False, True],
                         ids=["inline", "pipelined"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_trailing_tap_shows_what_a_serial_tap_shows(
        shape, overlap, meshed, tmp_path, tmp_path_factory):
    block_steps, steps_first, blocks = SHAPES[shape]
    want = _serial_served(tmp_path_factory, steps_first)
    obs.reset()
    runner = _served(tmp_path, "ck", block_steps, overlap, _mesh(meshed))
    seen = Seen(runner)
    absorbed = []
    inner = seen.log.absorb
    seen.log.absorb = lambda epoch, *a: (absorbed.append(epoch),
                                         inner(epoch, *a))
    _drive(runner, steps_first)
    _assert_same(seen.close(), want)
    # every block sharded under the epoch it ran in, the epoch's last
    # one included (the executor's epoch has moved on by the fence)
    tr = obs.get_tracer()
    ran = [r["args"]["epoch"] for r in tr.records() if r["name"] == "block"]
    assert absorbed == ran and sorted(set(ran)) == list(range(EPOCHS))
    # and only an epoch's (or a step's) last block is waited for with
    # nothing queued behind it
    waits = [r["args"]["trailing"] for r in tr.records()
             if r["name"] == "block.sink.wait"]
    full = [1] * (-(-SPE // block_steps) - 1) + [0]
    middle = [0] * steps_first + [1] * (len(blocks) - 1) + [0]
    assert waits == full + middle + full
    c = tr.counters()
    assert c["sink.rung_reads"] == len(waits) == len(ran)
    assert c.get("sink.taps_trailing", 0) == sum(waits)


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh2"])
def test_a_count_past_the_rung_while_the_block_trails_loses_no_row(
        meshed, tmp_path):
    want_runner = _bursty(tmp_path, "serial", serial_block=BURST_SPE)
    _serial_tap(want_runner)
    runner = _bursty(tmp_path, "ck", mesh=_mesh(meshed))
    want, seen = Seen(want_runner), Seen(runner)
    for r in (want_runner, runner):
        for _ in range(3):
            r.run_epoch(complete_checkpoint=True)
    _assert_same(seen.close(), want.close())
    tr = obs.get_tracer()
    c = tr.counters()
    assert c["sink.rung_reads"] == 3 * BURST_SPE // BURST_BLOCK == 12
    # sparse, dense, sparse, dense: each dense block passes the rung the
    # sparse one chose, the epoch's second while trailing, its fourth at
    # the drain
    assert c["sink.rung_misses"] == 6
    assert c["block.dispatches.sink_pack"] == 12 + 6
    rungs = [r["args"]["rung"] for r in tr.records()
             if r["name"] == "block.sink.d2h"]
    # a dense block is read again through the rung that holds its 512 a
    # subtask; the sparse one behind it is speculated at half as much again
    assert rungs == [256] + [512, 1024] * 5 + [512]
    dense = seen.log.committed_stream().shape[0] / 12
    assert dense > 2 * 256 / 2         # two subtasks, half the blocks dense


# --- drains ---------------------------------------------------------------------


def test_a_kill_after_single_steps_replays_into_the_same_stream(
        tmp_path, tmp_path_factory):
    """Single steps into an epoch, a kill that takes a sink subtask,
    recovery, the rest of the epoch: nothing is in flight where the kill
    and the recovery begin, and the committed stream is the no-failure
    run's."""
    runner = _served(tmp_path, "ck", 4)
    seen = Seen(runner)
    runner.run_epoch(complete_checkpoint=True)
    for _ in range(3):
        runner.step()
        assert runner._tap_pending is None
    base = runner.job.subtask_base
    at_entry = []
    inner = runner.failover._run
    runner.failover._run = lambda *a, **kw: (
        at_entry.append(runner._tap_pending), inner(*a, **kw))[1]
    runner.inject_failure([base(1) + 1, base(2), base(3) + 1])
    assert runner._tap_pending is None
    report = runner.recover()
    assert at_entry == [None] and report.steps_replayed == 3
    runner.run_epoch(complete_checkpoint=True)
    runner.run_epoch(complete_checkpoint=True)
    _assert_same(seen.close(), _serial_served(tmp_path_factory, 3))


def test_an_abandoned_block_loop_is_drained_before_a_kill(tmp_path):
    """A block loop that an exception cut short leaves its last block in
    flight; a kill, which decides over the transaction log's pending
    shards, reads it first, under the epoch the block ran in."""
    runner = _served(tmp_path, "ck", 4)
    (log,) = runner.txn_logs.values()
    runner.run_epoch(complete_checkpoint=True)
    runner.executor._host_block(4)          # no drain: as if cut short
    epoch, packed = runner._tap_pending
    assert epoch == 1 and len(packed) == 1
    before = sum(len(v) for v in log.pending_shards(1).values())
    runner.inject_failure([runner.job.subtask_base(1)])
    assert runner._tap_pending is None
    (wait,) = [r for r in obs.get_tracer().records()
               if r["name"] == "block.sink.wait"][-1:]
    assert wait["args"]["trailing"] == 0
    assert sum(len(v) for v in log.pending_shards(1).values()) > before
    assert log.pending_epochs() == [1]


def test_nothing_is_in_flight_where_a_live_recut_begins(tmp_path):
    """``rescale_live`` starts from a completed fence (or refuses):
    ``run_epoch`` and ``step`` have drained the tap before it looks."""
    from clonos_tpu.causal import recovery as rec
    runner = _served(tmp_path, "ck", 4)
    at_entry = []
    inner = runner.drain_fence              # the first thing it calls
    runner.drain_fence = lambda: (at_entry.append(runner._tap_pending),
                                  inner())[1]
    runner.run_epoch(complete_checkpoint=False)
    with pytest.raises(rec.RecoveryError, match="not the current fence|no "
                                                "completed checkpoint"):
        runner.rescale_live(runner.job)
    runner.step()
    with pytest.raises(rec.RecoveryError, match="mid-epoch"):
        runner.rescale_live(runner.job)
    assert at_entry == [None, None]


def test_listeners_get_each_blocks_own_last_step(tmp_path):
    """The tap trails, the listeners do not: a block's notify comes
    before the next block's causal draws, with the block's own last
    (time, stamp)."""
    runner = _served(tmp_path, "ck", 6)
    got = []
    runner.executor.block_listeners.append(lambda t, s: got.append((t, s)))
    runner.run_epoch(complete_checkpoint=True)
    runner.step()
    runner.run_epoch(complete_checkpoint=True)
    hist = runner.executor.step_input_history
    ends = np.cumsum([6, 6, 4, 1, 6, 6, 3])
    assert got == [(hist[e - 1][0], int(e) + 1) for e in ends]
    assert len(hist) == ends[-1]
