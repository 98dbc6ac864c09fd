"""The yardstick's arithmetic on inputs small enough to do by hand."""

import os

import numpy as np
import pytest

from benchlib import pacing, program_spans, quarters, trace_reduce
from benchlib.byname import module_at
from benchlib.stream import TableFeedReader, TableStream, draw_keys

HERE = os.path.dirname(os.path.abspath(__file__))
reference = module_at(os.path.join(
    os.path.dirname(HERE), "topologies", "source-window-reduce-sink",
    "reference.py"))
UNIFORM = {"kind": "uniform"}


def loop_fold(stream, steps_run, window_steps):
    """The fold as a loop over steps and records: the slow, obvious
    reference that the vectorised one must equal."""
    n_windows = max(0, (steps_run - 3) // window_steps)
    sums = np.zeros((n_windows, stream.num_keys), np.int64)
    for s in range(steps_run):
        w = (s + 1) // window_steps
        if w >= n_windows:
            continue
        for p in range(stream.partitions):
            ks, vs = stream.take(p, s * stream.batch, stream.batch)
            for k, v in zip(ks.tolist(), vs.tolist()):
                sums[w, k] += v
    return sums.astype(np.int32), np.cumsum(sums, axis=0).astype(np.int32)


def test_fold_equals_loop_over_several_table_periods():
    stream = TableStream(seed=7, partitions=3, batch=2, table_steps=8,
                         num_keys=5, value_bits=30, key_dist=UNIFORM)
    steps_run = 8 * 5 + 3                      # five periods; sums wrap
    want_sums, want_running = loop_fold(stream, steps_run, window_steps=4)
    sums, running = reference.expected_tables(
        stream.keys, stream.vals, 2, 4, 5, n_windows=want_sums.shape[0])
    np.testing.assert_array_equal(sums, want_sums)
    np.testing.assert_array_equal(running, want_running)


def test_fold_by_hand():
    """Two partitions, one record a step, windows of two steps: batch s
    counts into window (s + 1) // 2, whose rows are stamped 2 (w + 1) and
    carry the key's running sum (chip_smoke's hand-made case)."""
    keys = np.array([[0, 0, 1, 0, 0, 1], [1, 0, 1, 1, 0, 0]], np.int32)
    vals = np.array([[5, 7, 2, 1, 9, 4], [3, 1, 8, 6, 2, 3]], np.int32)
    sums, running = reference.expected_tables(keys, vals, 1, 2, 2, 3)
    rows = reference.rows_of_windows(sums, running, 0, 3, 2)
    want = np.array([[0, 5, 2], [1, 3, 2], [0, 13, 4], [1, 13, 4],
                     [0, 25, 6], [1, 19, 6]], np.int32)
    np.testing.assert_array_equal(rows, want)
    assert reference.compare_epoch(want, sums, running, 0, 3, 2) == 0


@pytest.mark.parametrize("spoil,n_bad", [
    (lambda r: r[1:], 1),                                   # a row lost
    (lambda r: np.concatenate([r, r[:1]]), 1),              # a row twice
    (lambda r: r + np.array([0, 1, 0], np.int32) * (np.arange(6) == 2)[:, None], 1),
    (lambda r: np.concatenate([r, [[1, 1, 8]]]), 1),        # a foreign row
])
def test_compare_epoch_counts_every_kind_of_fault(spoil, n_bad):
    keys = np.array([[0, 0, 1, 0, 0, 1], [1, 0, 1, 1, 0, 0]], np.int32)
    vals = np.array([[5, 7, 2, 1, 9, 4], [3, 1, 8, 6, 2, 3]], np.int32)
    sums, running = reference.expected_tables(keys, vals, 1, 2, 2, 3)
    rows = reference.rows_of_windows(sums, running, 0, 3, 2)
    assert reference.compare_epoch(spoil(rows), sums, running, 0, 3, 2) \
        == n_bad


def test_windows_of_epoch_partition_the_windows():
    """Window w's row reaches the sink at step (w + 1) W + 2."""
    spe, w = 16, 4
    seen = []
    for e in range(5):
        lo, hi = reference.windows_of_epoch(e, spe, w)
        for win in range(lo, hi):
            assert ((win + 1) * w + 2) // spe == e
        seen += list(range(lo, hi))
    assert seen == list(range(len(seen)))
    steps = np.arange(5 * spe)
    ce = reference.commit_epoch_of_step(steps, spe, w)
    assert ce[0] == 0 and ce[spe - w - 2] == 0 and ce[spe - w - 1] == 1


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_each_control_moves_the_fold(control):
    stream = TableStream(seed=3, partitions=4, batch=4, table_steps=32,
                         num_keys=13, value_bits=18, key_dist=UNIFORM)
    args = (stream.keys, stream.vals, 4, 4, 13, 40)
    sums, running = reference.expected_tables(*args)
    _, c_running = reference.expected_tables(*args, control=control,
                                             control_step=50)
    assert (running != c_running).sum() > 0


def test_harness_entry_points_agree_with_the_tables():
    """``expected`` / ``committed_of`` / ``check``: the stream a sound
    program commits checks clean, and each control's does not."""
    stream = TableStream(seed=3, partitions=4, batch=4, table_steps=32,
                         num_keys=13, value_bits=18, key_dist=UNIFORM)
    cfg = {"steps_per_epoch": 16, "window_steps": 4, "batch": 4,
           "num_keys": 13}
    table = (cfg, stream.keys, stream.vals, 6)
    want = reference.expected(*table)
    sound = reference.committed_of(want, cfg, 6)
    assert reference.check(sound, want, cfg, 6)[:2] == (0, [])
    for control in reference.CONTROLS:
        broken = reference.committed_of(
            reference.expected(*table, control=control, control_step=40),
            cfg, 6)
        assert reference.check(broken, want, cfg, 6)[0] > 0


def test_zipf_keys_are_skewed_in_range_and_from_the_seed():
    dist = {"kind": "zipf", "s": 1.2}
    keys = draw_keys(np.random.default_rng(5), dist, 97, (4, 5000))
    again = draw_keys(np.random.default_rng(5), dist, 97, (4, 5000))
    np.testing.assert_array_equal(keys, again)
    assert keys.dtype == np.int32 and keys.min() >= 0 and keys.max() < 97
    counts = np.sort(np.bincount(keys.ravel(), minlength=97))[::-1]
    # rank 1 holds 1 / sum(r ** -1.2) = 27.8 % of the draws, rank 2 12.1 %
    assert 0.26 < counts[0] / keys.size < 0.30
    assert 0.11 < counts[1] / keys.size < 0.135
    with pytest.raises(ValueError):
        draw_keys(np.random.default_rng(5), {"kind": "pareto"}, 97, (1, 1))


def test_reader_wraps_and_rereads():
    stream = TableStream(seed=1, partitions=2, batch=2, table_steps=4,
                         num_keys=7, value_bits=8, key_dist=UNIFORM)
    reader = TableFeedReader(stream)
    first = [reader.pull_block(1, 2, 3) for _ in range(2)]   # 6 of 4 steps
    flat = np.concatenate([f[0].ravel() for f in first])
    np.testing.assert_array_equal(flat, np.tile(stream.keys[1], 2)[:12])
    ks, vs = reader.read_at(1, 6, 4)                         # across the wrap
    np.testing.assert_array_equal(ks, np.tile(stream.keys[1], 2)[6:10])
    np.testing.assert_array_equal(vs, np.tile(stream.vals[1], 2)[6:10])


# --- commit stamps -----------------------------------------------------------


def test_fence_aligned_rate_counts_whole_epochs_between_stamps():
    stamps = {3: 9.9, 4: 10.5, 5: 11.0, 6: 11.5, 7: 12.25, 8: 14.2}
    rate, epochs, span = pacing.fence_aligned_rate(stamps, 10.0, 13.0, 1000)
    assert (epochs, span) == (3, 1.75)           # epochs 5, 6, 7 after 4's
    assert rate == pytest.approx(3000 / 1.75)
    assert pacing.fence_aligned_rate({1: 10.5}, 10.0, 13.0, 1000) is None
    # four epochs between five stamps, two parts: 2 epochs over 1.0 s,
    # then 2 over 2.0 s
    stamps = {4: 10.5, 5: 11.0, 6: 11.5, 7: 12.5, 8: 13.5}
    assert pacing.rates_by_part(stamps, 10.0, 14.0, 1000, parts=2) \
        == pytest.approx([2000.0, 1000.0])
    assert pacing.rates_by_part(stamps, 10.0, 11.2, 1000, parts=2) == []


def test_window_by_quarter_by_hand():
    """Five stamps a second apart but the last (two seconds), two blocks
    an epoch, in halves. The recorder's ring has evicted what lies before
    12.0, so the first half says so and gives no span; the second half
    holds one draw alone and one beside another thread's span."""
    class FakeRun:
        stamps = {4: 10.0, 5: 11.0, 6: 12.0, 7: 13.0, 8: 15.0}
        window = (9.5, 15.5)
        cfg = {"steps_per_epoch": 8, "block_steps": 4}
        records_per_epoch = 1000
        spans = None
    span = lambda name, tid, mono, dur: {
        "name": name, "tid": tid, "mono": mono, "dur": dur}
    run = FakeRun()
    run._program_spans = program_spans.Program(
        spans=[span("epoch", 1, 12.0, 1.0), span("epoch", 1, 13.0, 2.0),
               span(quarters.DRAW, 1, 12.1, 0.004),
               span(quarters.DRAW, 1, 13.1, 0.020),
               span("fence.snapshot", 2, 13.09, 0.025),
               span(quarters.DRAW, 1, 15.2, 0.004)],   # past the last stamp
        counters={}, dropped=7, oldest=12.0)
    first, second = quarters.by_quarter(run, parts=2)
    assert (first["epochs"], first["blocks"], first["seconds"]) == (2, 4, 2.0)
    assert first["records_per_s"] == pytest.approx(1000.0)
    assert first["program"] == "evicted"
    assert "program_ms_per_block" not in first and "draws" not in first
    assert second["records_per_s"] == pytest.approx(2000 / 3.0)
    assert second["program"] == "whole"
    assert second["program_ms_per_block"] == pytest.approx({
        "epoch": 3000 / 4, quarters.DRAW: 24 / 4, "fence.snapshot": 25 / 4})
    assert second["draws"]["alone"] == {"n": 1, "mean_ms": pytest.approx(4.0)}
    beside = second["draws"]["beside_another_thread"]
    assert beside["n"] == 1 and beside["mean_ms"] == pytest.approx(20.0)
    assert beside["mean_overlap_ms"] == pytest.approx(15.0)  # 13.10-13.115
    # too few commits inside for the cut: no quarters, as rates_by_part
    assert quarters.by_quarter(run, parts=5) == []
    run._program_spans = program_spans.Program([], {}, 0, 0.0)
    assert [q["program"] for q in quarters.by_quarter(run, parts=2)] \
        == ["no recorder"] * 2


def test_latency_from_intended_send_by_hand():
    """Epochs of 8 steps due every second from t0 = 100, windows of 2
    steps. Steps 0..2 of an epoch become visible with that epoch's
    commit, steps 3..7 (their window's row reaches the sink in the next
    epoch) with the next one's."""
    sched = pacing.Schedule(t0=100.0, rate=16.0, records_per_epoch=16,
                            steps_per_epoch=8, first_epoch=2)
    assert sched.period == 1.0 and sched.due(2) == 101.0
    stamps = {2: 101.25, 3: 102.25, 4: 103.5}
    cfg = {"steps_per_epoch": 8, "window_steps": 2}
    lat = pacing.commit_latencies_ms(
        stamps, sched, 100.0, 103.0,
        lambda steps: reference.visible_epoch_of_step(steps, cfg),
        last_epoch=4)
    # epoch 2: steps 0..2 sent at 100 + (k + .5)/8 commit at 101.25;
    # steps 3..7 commit with epoch 3 at 102.25, as do epoch 3's 0..2;
    # epoch 4's commit (103.5) is outside the window.
    want = ([101.25 - (100 + (k + .5) / 8) for k in range(3)]
            + [102.25 - (100 + (k + .5) / 8) for k in range(3, 11)])
    np.testing.assert_allclose(np.sort(lat), np.sort(np.array(want) * 1e3))
    np.testing.assert_allclose(
        pacing.service_ms(stamps, sched, 100.0, 103.0), [250.0, 250.0])


# --- the trace reduction -----------------------------------------------------


def test_busy_is_a_union_and_gaps_go_to_the_innermost_span():
    ops = [("while.1", 10, 50), ("fusion.2", 12, 8), ("hist.3", 30, 20),
           ("fusion.2", 80, 10)]
    ev = trace_reduce.Events(
        ops={0: ops}, modules={0: [("jit_block", 10, 50),
                                   ("jit_roll", 80, 10)]},
        host=[("steady", 0, 100), ("epoch", 5, 90), ("sink_absorb", 60, 20)])
    assert trace_reduce.device_busy_s(ev, 0, 100) == {0: 60 / 1e9}
    selfs = trace_reduce.self_times(ops, 0, 100)
    assert selfs == pytest.approx({"while.1": 22 / 1e9, "fusion.2": 18 / 1e9,
                                   "hist.3": 20 / 1e9})
    idle = trace_reduce.idle_by_span(ev, 0, 0, 100)
    assert idle == pytest.approx({"steady": 10 / 1e9, "epoch": 10 / 1e9,
                                  "sink_absorb": 20 / 1e9})
    assert sum(idle.values()) == pytest.approx(40 / 1e9)


def test_reduction_of_a_recorded_trace():
    """One epoch of ``kafka64.backlog`` on a v5e, recorded by PR 23
    (``trace_reduce.py <profile> desc.json one-epoch.json.gz``): the
    shape of a real trace, so that a change of profiler format shows
    here and not as a missing metric on the chip."""
    path = os.path.join(HERE, "data", "kafka64_backlog_one_epoch.json.gz")
    ev = trace_reduce.load(path)
    (lo, hi), = trace_reduce.spans_inside(ev, "epoch", 0, float("inf"))
    busy = trace_reduce.device_busy_s(ev, lo, hi)[0]
    assert 0.2 * (hi - lo) / 1e9 < busy < (hi - lo) / 1e9
    idle = trace_reduce.idle_by_span(ev, 0, lo, hi)
    assert sum(idle.values()) == pytest.approx((hi - lo) / 1e9 - busy)
    assert {"feed_pull", "sink_absorb"} <= set(idle)
    selfs = trace_reduce.self_times(ev.ops[0], lo, hi)
    assert sum(selfs.values()) == pytest.approx(busy)
    hist = trace_reduce.matching(ev.ops[0], "hist", lo, hi)
    assert hist and all(trace_reduce.shapes_of(n) for n, _, _ in hist)
