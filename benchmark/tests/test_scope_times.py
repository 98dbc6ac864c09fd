"""The reduction of device time by named scope
(``benchlib/scope_times.py``): from an ``op_name`` to a scope, self time
inside the launches of one program, what another program's launch and a
launch cut by the window leave out, the wire reader against
``jax.profiler.ProfileData`` on a hand-made ``XSpace``, the readers with
nothing to read — and the recorded block of ``kafka64.backlog``, on which
the layers' sum is the block's self time exactly. The two readers of
recovery spans ride here too."""

import os
import types

import pytest

import conftest
from benchlib import scope_times, trace_reduce
from benchlib.byname import module_at
from test_program_spans import fake_run, span

# the session fixture maps every configuration of BENCHMARK.json
for _config in ("allround-upstream", "nexmark-q8", "nexmark-q5"):
    conftest.TINY.setdefault(_config, "tiny-" + _config)

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = os.path.join(os.path.dirname(HERE), "readers")
RECORDED = os.path.join(HERE, "data", "kafka64_backlog_one_block.json.gz")
SCOPE_METRICS = [
    "operators_device_ms_per_block", "exchange_device_ms_per_block",
    "log_append_device_ms_per_block", "replica_append_device_ms_per_block",
    "inflight_ring_device_ms_per_block", "hist_device_ms_per_block",
    "block_unscoped_pct"]
MS = 1e6                                   # ns

P = "jit(run_block)/jit(main)/"


def read(metric, run):
    return module_at(os.path.join(READERS, metric + ".py")).read(run)


@pytest.mark.parametrize("op_name, scope", [
    (P + "vertex/count/place/hist/jit(_hist_pallas)/pallas_call",
     ("vertex", "count", "place", "hist")),
    (P + "vertex/count/lookup/reduce_sum", ("vertex", "count", "lookup")),
    (P + "vertex/window/kpj,jpn->kpn/dot_general", ("vertex", "window")),
    # a vertex may be called what a layer is called
    (P + "vertex/exchange/emit/add", ("vertex", "exchange", "emit")),
    (P + "exchange/while/body/rank/jit(cumsum)/add", ("exchange", "rank")),
    (P + "exchange/place/vmap(hist)/scatter-add",
     ("exchange", "place", "hist")),
    (P + "exchange/plan/gather", ("exchange", "plan")),
    (P + "causal-log/replicas/vmap(vmap())/select_n",
     ("causal-log", "replicas")),
    (P + "causal-log/own/jit(_roll_static)/slice", ("causal-log", "own")),
    (P + "inflight-ring/scatter", ("inflight-ring",)),
    # a part counts directly beneath its own layer only
    (P + "exchange/emit/add", ("exchange",)),
    (P + "vertex/count/segsum/place/add", ("vertex", "count", "segsum")),
    (P + "causal-log/rank/add", ("causal-log",)),
    # ``hist`` ends a path
    (P + "exchange/place/hist/place/add", ("exchange", "place", "hist")),
    ("jit(sink_pack)/hist/dot_general", ("hist",)),
    (P + "concatenate", ()),
    ("carry.logs.rows", ()),
    ("", ()),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scope_times.scope_of(op_name) == scope


def block(t0, unscoped="%copy.9 = s32[8]"):
    """One launch of the block program at ``t0`` ms, 100 ms long: a
    ``while`` under ``exchange`` over 10-50 whose body covers 30 of its
    40 ms, an operator's fusion with its kernel, both appends, the ring,
    and 5 ms under no scope; 83 ms of self time."""
    at = lambda a, d, name, op: (name, (t0 + a) * MS, d * MS, op)
    return [
        at(10, 40, "%while.1", P + "exchange/while"),
        at(12, 10, "%fusion.1", P + "exchange/while/body/rank/dot_general"),
        at(25, 20, "%_hist_pallas.2",
           P + "exchange/while/body/place/hist/pallas_call"),
        at(50, 8, "%fusion.3", P + "vertex/count/place/add"),
        at(58, 2, "%_hist_pallas.4", P + "vertex/count/place/hist/x"),
        at(60, 4, "%fusion.5", P + "vertex/max/emit/add"),
        at(64, 3, "%fusion.6", P + "causal-log/rows/add"),
        at(67, 6, "%fusion.7", P + "causal-log/own/select_n"),
        at(73, 12, "%fusion.8", P + "causal-log/replicas/select_n"),
        at(85, 3, "%fusion.9", P + "inflight-ring/scatter"),
        at(90, 5, unscoped, ""),
    ]


def two_blocks_and_another_program():
    """Launches at 0, 200 (of ``jit_sink_pack``), 300 and 900 ms; the
    last one runs past a window that ends at 950."""
    modules = [("jit_run_block(7)", 0.0, 100 * MS),
               ("jit_sink_pack(9)", 200 * MS, 50 * MS),
               ("jit_run_block(7)", 300 * MS, 100 * MS),
               ("jit_run_block(7)", 900 * MS, 100 * MS),
               ("jit_run_block_again(3)", 1200 * MS, 10 * MS)]
    ops = (block(0) + block(300, "%copy.10 = s32[8]") + block(900)
           + [("%fusion.1", 210 * MS, 30 * MS,
               "jit(sink_pack)/vertex/count/place/add"),
              ("%fusion.1", 1201 * MS, 5 * MS, P + "exchange/add")])
    return scope_times.Device(ops, modules, "tf_op")


def test_launches_are_the_named_programs_wholly_inside_the_window():
    dev = two_blocks_and_another_program()
    assert scope_times.launches(
        dev.modules, "jit_run_block", 0.0, 950 * MS) == [
        (0.0, 100 * MS), (300 * MS, 400 * MS)]
    assert len(scope_times.launches(
        dev.modules, "jit_run_block", 0.0, 2000 * MS)) == 3
    assert scope_times.launches(dev.modules, "jit_roll", 0.0, 1e12) == []


def test_self_time_by_scope_over_the_launches_inside_the_window():
    st = scope_times.reduce(two_blocks_and_another_program(), 0.0, 950 * MS)
    assert st.launches == 2
    ms = lambda s: st.ms_per_block(s)
    # the while is charged what its body does not cover
    assert ms(st.by_scope[("exchange",)]) == pytest.approx(10)
    assert ms(st.by_scope[("exchange", "rank")]) == pytest.approx(10)
    assert ms(st.under("exchange")) == pytest.approx(40)
    # the other program's fusion under vertex/count is not the block's
    assert ms(st.under("vertex")) == pytest.approx(14)
    assert ms(st.under("vertex", "count")) == pytest.approx(10)
    assert ms(st.under("causal-log")) == pytest.approx(21)
    assert ms(st.under("causal-log", "replicas")) == pytest.approx(12)
    assert ms(st.under("inflight-ring")) == pytest.approx(3)
    assert ms(st.leaf("hist")) == pytest.approx(22)
    assert ms(st.by_scope[()]) == pytest.approx(5)
    assert st.unscoped_ops == {"%copy.9 = s32[8]": pytest.approx(0.005),
                               "%copy.10 = s32[8]": pytest.approx(0.005)}
    # the layers and what is under none: the block's self time
    layers = sum(st.under(l) for l in (
        "vertex", "exchange", "causal-log", "inflight-ring"))
    assert layers + st.by_scope[()] == pytest.approx(st.total_s)
    assert ms(st.total_s) == pytest.approx(83)


def test_nothing_to_reduce():
    dev = two_blocks_and_another_program()
    assert scope_times.reduce(dev, 100 * MS, 290 * MS) is None
    no_stat = scope_times.Device(dev.ops, dev.modules, None)
    assert scope_times.reduce(no_stat, 0.0, 950 * MS) is None


def test_table_has_every_vertex_every_part_and_the_unscoped_ops():
    st = scope_times.reduce(two_blocks_and_another_program(), 0.0, 950 * MS)
    text = "\n".join(scope_times.table(st))
    for label in ("2 launches", "vertex (every vertex)", "vertex/count",
                  "vertex/count/place", "vertex/count/place/hist",
                  "vertex/max/emit", "exchange/rank", "exchange/place/hist",
                  "(itself)", "causal-log/rows", "causal-log/own",
                  "causal-log/replicas", "inflight-ring",
                  "(under no scope)", "%copy.9 = s32[8]"):
        assert label in text, label
    share = lambda label: float(next(
        l for l in text.splitlines() if l.strip().startswith(label)
    ).split()[-2])
    assert share("exchange ") == pytest.approx(100 * 40 / 83, abs=0.01)
    assert share("(under no scope)") == pytest.approx(100 * 5 / 83, abs=0.01)
    assert "by kind of op, ms: copy 5.000" in text


XSPACE = '''
planes { id: 0 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 7
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 2000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:steady" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 5
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "%other.1" } } }
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 7000000
             stats { metadata_id: 2 int64_value: 42 } }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 13000000 duration_ps: 500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 4000000 duration_ps: 10000000 } }
  lines { id: 3 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 1 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = s32[8]"
    display_name: "while.1"
    stats { metadata_id: 1 str_value: "jit(run_block)/exchange/while" }
    stats { metadata_id: 3 ref_value: 4 } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = s32[8]"
    stats { metadata_id: 1 ref_value: 5 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = s32[8]" } }
  event_metadata { key: 9 value { id: 9 name: "jit_run_block(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "own" } }
  stat_metadata { key: 3 value { id: 3 name: "hlo_category" } }
  stat_metadata { key: 4 value { id: 4 name: "data formatting" } }
  stat_metadata { key: 5 value { id: 5
    name: "jit(run_block)/exchange/while/body/rank/dot_general" } }
}
'''


def test_wire_reader_agrees_with_profile_data(tmp_path):
    """The device plane as this file reads it against
    ``jax.profiler.ProfileData``: the same names and the same clock; the
    ``op_name`` from the metadata's stats, a string or a reference."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    dev = scope_times.load(str(path))
    ev = trace_reduce.load(str(path))
    assert [e[:3] for e in dev.ops] == ev.ops[0]
    assert dev.modules == ev.modules[0]
    assert dev.stat == "tf_op"
    assert [e[3] for e in dev.ops] == [
        "jit(run_block)/exchange/while",
        "jit(run_block)/exchange/while/body/rank/dot_general", ""]
    assert scope_times.load(str(path), 1).ops == [
        ("%other.1", 6.0, 1.0, "")]
    assert scope_times.load(str(path), 1).stat is None
    assert scope_times.load(str(path), 3) is None
    st = scope_times.reduce(dev, 0.0, 1e9)
    assert st.by_scope == {("exchange",): pytest.approx(5e-6),
                           ("exchange", "rank"): pytest.approx(2e-6),
                           (): pytest.approx(0.5e-6)}
    # through JSON, the form the recorded trace is kept in
    again = scope_times.Device.from_json(dev.to_json())
    assert again == dev


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_scope_reader_finds_nothing_without_a_device_plane(metric):
    """A CPU rehearsal (no ops) and an untraced run (no events)."""
    no_ops = types.SimpleNamespace(
        events=trace_reduce.Events({}, {}, [("steady", 0.0, 1e9)]),
        trace_window=lambda name: (0.0, 1e9))
    assert read(metric, no_ops) is None
    untraced = types.SimpleNamespace(events=None,
                                     trace_window=lambda name: None)
    assert read(metric, untraced) is None


def run_with(st):
    return types.SimpleNamespace(_scope_times=st)


def test_scope_readers_read_the_shared_reduction(capsys):
    st = scope_times.reduce(two_blocks_and_another_program(), 0.0, 950 * MS)
    run = run_with(st)
    got = {m: read(m, run) for m in SCOPE_METRICS}
    assert got == {
        "operators_device_ms_per_block": pytest.approx(14),
        "exchange_device_ms_per_block": pytest.approx(40),
        "log_append_device_ms_per_block": pytest.approx(21),
        "replica_append_device_ms_per_block": pytest.approx(12),
        "inflight_ring_device_ms_per_block": pytest.approx(3),
        "hist_device_ms_per_block": pytest.approx(22),
        "block_unscoped_pct": pytest.approx(100 * 5 / 83)}
    assert "scope table: 2 launches" in capsys.readouterr().out
    # a job that keeps no replica: nothing to read, not a zero
    none = scope_times.ScopeTimes(1, {("causal-log", "own"): 0.001}, {})
    assert read("replica_append_device_ms_per_block", run_with(none)) is None
    assert read("log_append_device_ms_per_block",
                run_with(none)) == pytest.approx(1)


def test_recorded_block_splits_into_its_layers_exactly():
    """One block of ``kafka64.backlog`` as the chip recorded it, with
    the stat that carries the scope: every layer is there, the replica
    append is part of the log append, and the layers' sum with what is
    under no scope IS the block's self time."""
    dev = scope_times.load(RECORDED)
    assert dev.stat == scope_times.SCOPE_STAT
    st = scope_times.reduce(dev, float("-inf"), float("inf"))
    assert st.launches == 1
    layers = [st.under(l) for l in ("vertex", "exchange", "causal-log",
                                    "inflight-ring")]
    assert all(s > 0 for s in layers)
    assert sum(layers) + st.by_scope.get((), 0.0) == pytest.approx(
        st.total_s, rel=1e-12)
    assert 0 < st.under("causal-log", "replicas") < st.under("causal-log")
    assert st.leaf("hist") > 0
    for vertex in ("host-source", "window", "reduce", "sink"):
        assert st.under("vertex", vertex) >= 0
    assert st.under("vertex", "window") > 0
    # against the reduction that reads names only: the same self time
    (a, b), = scope_times.launches(dev.modules, scope_times.BLOCK_PROGRAM,
                                   float("-inf"), float("inf"))
    by_name = trace_reduce.self_times(
        [e[:3] for e in scope_times.inside(dev.ops, [(a, b)])], a, b)
    assert sum(by_name.values()) == pytest.approx(st.total_s, rel=1e-9)
    assert st.total_s <= (b - a) / 1e9


def test_recovery_inputs_and_finalize_are_read_like_the_other_phases():
    recs = [span("recovery", 1.0, 0.5, "drill", drill=True),
            span("recovery.inputs", 1.1, 0.3, "di", "drill"),
            span("recovery", 20.0, 0.100, "kill", drill=False),
            span("recovery.inputs", 20.01, 0.012, "i1", "kill"),
            span("recovery.inputs", 20.04, 0.010, "i2", "kill"),
            span("recovery.finalize", 20.07, 0.020, "f", "kill"),
            span("recovery.finalize.barrier-read", 20.075, 0.010, "b", "f")]
    run = fake_run(recs, recover_wall=(19.99, 20.2))
    assert read("recovery_inputs_ms", run) == pytest.approx(22)
    assert read("recovery_finalize_ms", run) == pytest.approx(20)
    empty = fake_run([], recover_wall=(19.99, 20.2))
    assert read("recovery_inputs_ms", empty) is None
    assert read("recovery_finalize_ms", empty) is None


def test_rehearsal_reports_the_recovery_readers_and_no_scope_metric(
        tiny_bench):
    """The harness finds the nine new readers by name; on the CPU there
    is no device plane, so the seven scope metrics are left out of the
    line and the two recovery phases are in it."""
    import run as harness
    result = harness.run_cell(tiny_bench, "kafka64.backlog", 2**31 + 38,
                              seconds=1.5, trace=True, check_chip=False)
    assert result["correct"] is True
    assert not set(SCOPE_METRICS) & set(result["metrics"])
    for metric in ("recovery_inputs_ms", "recovery_finalize_ms"):
        assert result["metrics"][metric]["value"] > 0
