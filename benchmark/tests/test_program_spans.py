"""The readers of the program's own spans (``benchlib/program_spans.py``):
the clock mapping, leaf selection and the idle attribution on hand-made
records — alone and laid over the recorded one-epoch trace — every new
reader on an empty ring and on one that dropped records, and the tiny
CPU rehearsal of each cell for the metrics listed for it."""

import json
import os
import types

import pytest

import run as harness
from benchlib import program_spans, trace_reduce
from benchlib.byname import module_at
from benchlib.spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = os.path.join(os.path.dirname(HERE), "readers")

BACKLOG = ["sink_wait_ms_per_block", "sink_d2h_ms_per_block",
           "sink_d2h_mb_per_block", "sink_shard_ms_per_block",
           "feed_put_ms_per_block", "causal_inputs_ms_per_block",
           "block_dispatch_ms"]
PACED = ["fence_ack_ms", "fence_health_read_ms"]
RECOVERY = ["recovery_restore_ms", "recovery_fetch_ms",
            "recovery_replay_ms", "recovery_patch_ms"]
#: need a device plane, which a CPU rehearsal does not have
DEVICE = ["idle_unattributed_pct", "idle_unattributed_pct.paced"]
#: the mesh cell's twins (PR 43: its rate is an end-to-end metric of its
#: own, and what moves it carries the same suffix)
MESH = [name + ".mesh" for name in BACKLOG]


class Ring:
    """Stands in for the program's tracer: hand-made records."""

    def __init__(self, records=(), counters=None, dropped=0):
        self._records, self._counters = list(records), counters or {}
        self.dropped = dropped

    def records(self):
        return self._records

    def counters(self):
        return self._counters


def span(name, mono, dur, sid, parent=None, tid=1, **args):
    rec = {"name": name, "ph": "X", "mono": mono, "dur": dur, "ts": mono,
           "span": sid, "parent": parent, "tid": tid}
    if args:
        rec["args"] = args
    return rec


def fake_run(records, events=None, steady=None, window=(0.0, 1e9),
             recover_wall=(0.0, 0.0), **ring):
    run = types.SimpleNamespace(
        window=window, recover_wall=recover_wall, events=events,
        fence_tail_ms=[],
        spans=Spans(), trace_window=lambda name: (
            trace_reduce.span_window(events, name)
            if events is not None else None))
    if steady is not None:
        run.spans.spans["steady"] = [steady]
    run._program_spans = program_spans.snapshot(Ring(records, **ring))
    return run


def read(metric, run):
    return module_at(os.path.join(READERS, metric + ".py")).read(run)


def test_snapshot_keeps_stamped_complete_spans_only():
    recs = [span("epoch", 10.0, 1.0, "a"),
            dict(span("checkpoint", 9.0, 1.5, "b", "a"), backdated=True),
            {"name": "compile", "ph": "i", "mono": 10.2, "ts": 1.0,
             "span": "c", "parent": "a", "tid": 1},
            # a record of a tracer without the second clock (the parent's)
            {"name": "epoch", "ph": "X", "ts": 5.0, "dur": 1.0,
             "span": "d", "parent": None, "tid": 1}]
    prog = program_spans.snapshot(Ring(recs, {"sink.rows": 3}))
    assert [s["span"] for s in prog.spans] == ["a"]
    assert prog.counters == {"sink.rows": 3} and prog.oldest == 9.0
    # a tracer with no counters() and no records (the parent's NullTracer)
    null = types.SimpleNamespace(records=lambda: [])
    assert program_spans.snapshot(null).spans == []
    assert program_spans.snapshot(None).spans == []


def test_means_medians_and_args_take_the_windows_spans():
    recs = [span("block.sink.d2h", 1.0, 0.010, "a", bytes=100),
            span("block.sink.d2h", 2.0, 0.030, "b", bytes=300),
            span("block.sink.d2h", 9.5, 1.000, "c", bytes=900),  # straddles
            span("fence.ack", 3.0, 0.001, "d"),
            span("fence.ack", 4.0, 0.002, "e"),
            span("fence.ack", 5.0, 0.009, "f")]
    run = fake_run(recs, window=(0.5, 10.0))
    assert program_spans.mean_ms(run, "block.sink.d2h") == pytest.approx(20)
    assert program_spans.mean_arg(run, "block.sink.d2h", "bytes") == 200
    assert program_spans.median_ms(run, "fence.ack") == pytest.approx(2)
    assert program_spans.mean_ms(run, "block.dispatch") is None
    assert read("sink_d2h_mb_per_block", run) == pytest.approx(200 / 1e6)


def test_recovery_phases_are_the_children_of_the_kill_phases_span():
    recs = [span("recovery", 1.0, 0.5, "drill", drill=True),
            span("recovery.replay", 1.1, 0.3, "dr", "drill"),
            span("recovery", 20.0, 0.100, "kill", drill=False),
            span("recovery.restore", 20.0, 0.010, "r1", "kill"),
            span("recovery.fetch_determinants", 20.01, 0.020, "f1", "kill"),
            span("recovery.fetch_determinants", 20.05, 0.005, "f2", "kill"),
            span("recovery.replay", 20.06, 0.030, "p1", "kill")]
    run = fake_run(recs, recover_wall=(19.99, 20.2))
    assert read("recovery_restore_ms", run) == pytest.approx(10)
    assert read("recovery_fetch_ms", run) == pytest.approx(25)
    assert read("recovery_replay_ms", run) == pytest.approx(30)
    assert read("recovery_patch_ms", run) is None
    # a wall that the span does not lie inside: nothing to read
    assert read("recovery_replay_ms",
                fake_run(recs, recover_wall=(20.05, 20.2))) is None


def test_leaves_are_spans_without_children_on_their_own_thread():
    recs = [span("epoch", 0.0, 10.0, "e"),
            span("block", 1.0, 4.0, "b", "e"),
            span("block.dispatch", 1.5, 1.0, "d", "b"),
            span("fence", 6.0, 2.0, "f", "e"),
            # the fence worker's span hangs under the main thread's fence
            span("fence.snapshot", 6.5, 3.0, "s", "f", tid=2)]
    names = sorted(s["name"] for s in program_spans.leaves(recs))
    assert names == ["block.dispatch", "fence", "fence.snapshot"]


def hand_made_trace():
    """Device busy 100-200 and 400-450 ms inside a steady span 0-1000 ms
    on the profiler's clock; on the monotonic clock the same span starts
    at 50.0 s."""
    ms = 1e6
    ops = {0: [("a", 100 * ms, 100 * ms), ("b", 400 * ms, 50 * ms)]}
    events = trace_reduce.Events(ops, {0: []},
                                 [("steady", 0.0, 1000 * ms)])
    return events, (50.0, 51.0)


def test_idle_attribution_by_leaf_and_by_innermost_span():
    events, steady = hand_made_trace()
    at = lambda t_ms: 50.0 + t_ms / 1e3
    recs = [span("epoch", at(0), 0.9, "e"),            # 0-900 ms
            span("block", at(50), 0.45, "b", "e"),     # 50-500
            span("block.sink.wait", at(100), 0.1, "w", "b"),   # all busy
            span("block.sink.d2h", at(200), 0.15, "d", "b"),   # 200-350 idle
            span("fence", at(600), 0.2, "f", "e"),     # 600-800, a leaf
            span("fence.snapshot", at(0), 1.0, "s", "f", tid=7)]  # worker
    run = fake_run(recs, events, steady)
    assert program_spans.clock_offset_ns(run) == pytest.approx(-50e9)
    by_leaf = program_spans.idle_by_program_span(run, only_leaves=True)
    # idle: 0-100, 200-400, 450-1000 ms = 850 ms in all
    assert sum(by_leaf.values()) == pytest.approx(0.850)
    assert by_leaf["block.sink.d2h"] == pytest.approx(0.150)
    assert by_leaf["fence"] == pytest.approx(0.200)
    assert "block.sink.wait" not in by_leaf and "fence.snapshot" not in by_leaf
    assert by_leaf[program_spans.UNATTRIBUTED] == pytest.approx(0.500)
    assert program_spans.idle_unattributed_pct(by_leaf) == pytest.approx(
        100 * 0.5 / 0.85)
    by_span = program_spans.idle_by_program_span(run, only_leaves=False)
    # the parents' remainders get their names: block 50-100, 350-400,
    # 450-500; epoch 0-50, 500-600, 800-900; outside the epoch 900-1000
    assert by_span["block"] == pytest.approx(0.150)
    assert by_span["epoch"] == pytest.approx(0.250)
    assert by_span[program_spans.UNATTRIBUTED] == pytest.approx(0.100)
    # the harness's own sleep is attributed, and not to the program
    run.spans.spans["wait_due"] = [(at(900), at(1000))]
    by_leaf = program_spans.idle_by_program_span(run, only_leaves=True)
    assert by_leaf[program_spans.WAIT_DUE] == pytest.approx(0.100)
    assert by_leaf[program_spans.UNATTRIBUTED] == pytest.approx(0.400)


def test_inside_spans_split_the_recorded_epochs_sink_absorb():
    """Laid over the recorded ``kafka64.backlog`` epoch: the three inside
    spans of a block's sink tap cover what the outside ``sink_absorb``
    wrapper covers, so together they hold the same idle time."""
    ev = trace_reduce.load(os.path.join(
        HERE, "data", "kafka64_backlog_one_epoch.json.gz"))
    (e_lo, e_hi), = trace_reduce.spans_inside(ev, "epoch", 0, float("inf"))
    ev.host.append(("steady", e_lo, e_hi - e_lo))
    mono0 = 7000.0                      # the steady span's monotonic start
    at = lambda ns: mono0 + (ns - e_lo) / 1e9
    recs = [span("epoch", at(e_lo), (e_hi - e_lo) / 1e9, "e")]
    absorbs = trace_reduce.spans_inside(ev, "sink_absorb", e_lo, e_hi)
    for i, (a, b) in enumerate(absorbs):
        cut1, cut2 = a + (b - a) * 0.45, a + (b - a) * 0.9
        recs += [span("block.sink.wait", at(a), (cut1 - a) / 1e9,
                      f"w{i}", "e"),
                 span("block.sink.d2h", at(cut1), (cut2 - cut1) / 1e9,
                      f"d{i}", "e"),
                 span("block.sink.shard", at(cut2), (b - cut2) / 1e9,
                      f"s{i}", "e")]
    run = fake_run(recs, ev, (mono0, mono0 + (e_hi - e_lo) / 1e9))
    outside = trace_reduce.idle_by_span(ev, 0, e_lo, e_hi)
    inside = program_spans.idle_by_program_span(run, only_leaves=True)
    assert sum(inside.values()) == pytest.approx(sum(outside.values()))
    three = sum(inside.get("block.sink." + part, 0.0)
                for part in ("wait", "d2h", "shard"))
    assert three == pytest.approx(outside["sink_absorb"], rel=1e-6)
    # the block program runs during the wait: most of that span is busy
    assert inside.get("block.sink.wait", 0.0) < inside["block.sink.shard"] \
        + inside["block.sink.d2h"]
    assert read("idle_unattributed_pct", run) == pytest.approx(
        100 * inside[program_spans.UNATTRIBUTED] / sum(inside.values()))


@pytest.mark.parametrize("metric", BACKLOG + PACED + RECOVERY + DEVICE)
def test_reader_finds_nothing_on_an_empty_or_a_truncated_ring(metric):
    events, steady = hand_made_trace()
    assert read(metric, fake_run([], events, steady)) is None
    # records from 50.2 s on only: the ring dropped what came before,
    # inside the window, the recover wall and the steady span
    recs = [span("epoch", 50.2, 0.7, "e"),
            span("block.sink.wait", 50.3, 0.1, "w", "e"),
            span("block.sink.d2h", 50.4, 0.1, "d", "e", bytes=8),
            span("block.sink.shard", 50.5, 0.1, "s", "e"),
            span("block.feed.put", 50.21, 0.01, "p", "e"),
            span("block.causal-inputs", 50.2, 0.01, "c", "e"),
            span("block.dispatch", 50.22, 0.01, "x", "e"),
            span("fence.ack", 50.6, 0.01, "a", "e"),
            span("fence.health-read", 50.61, 0.01, "h", "e"),
            span("recovery", 50.7, 0.1, "r", drill=False),
            span("recovery.restore", 50.7, 0.01, "r1", "r"),
            span("recovery.fetch_determinants", 50.71, 0.01, "r2", "r"),
            span("recovery.replay", 50.72, 0.01, "r3", "r"),
            span("recovery.patch", 50.73, 0.01, "r4", "r")]
    kw = dict(window=(50.0, 51.0), recover_wall=(50.0, 51.0))
    assert read(metric, fake_run(recs, events, steady, **kw)) is not None
    assert read(metric, fake_run(recs, events, steady, dropped=3,
                                 **kw)) is None


@pytest.mark.parametrize("cell", ["kafka64.backlog", "allround32.backlog",
                                  "kafka64.paced", "allround64x4.backlog"])
def test_rehearsal_yields_every_new_metric_listed_for_the_cell(tiny_bench,
                                                               cell):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or cell in m["workloads"]}
    new = set(BACKLOG + MESH + PACED + RECOVERY + DEVICE)
    assert new <= {m["name"] for m in bench["per_layer"]}
    result = harness.run_cell(tiny_bench, cell, 2**31 + 29, seconds=1.5,
                              trace=True, check_chip=False)
    assert result["correct"] is True
    want = (listed & new) - set(DEVICE)
    assert want and want <= set(result["metrics"])
    assert all(result["metrics"][m]["value"] >= 0 for m in want)
    from clonos_tpu.obs import get_tracer
    assert get_tracer().dropped == 0
