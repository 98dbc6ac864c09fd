"""The two readers of the own-column lookup (PR 49):
``own_lookup_device_ms_per_block`` on a hand-made reduction, on the
recorded block of ``kafka64.backlog`` (a job with no such scope: None)
and with no device plane; ``own_lookup_dense_blocks_per_epoch`` on
hand-made counters (None where the program keeps no such counter, as
before PR 49) and in the tiny rehearsals of ``nexmarkq5.backlog`` and
``nexmarkq11.backlog``, whose receive windows are too narrow for head
and tails: the counters are there and read 0."""

import os
import types

import pytest

import conftest
import run as harness
from benchlib import scope_times, trace_reduce
from test_program_spans import fake_run, read

# the session fixture maps every configuration of BENCHMARK.json
for _config in ("allround-upstream", "nexmark-q8", "nexmark-q5",
                "nexmark-q11", "nexmark-q3", "nexmark-q4"):
    conftest.TINY.setdefault(_config, "tiny-" + _config)

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "kafka64_backlog_one_block.json.gz")
DEVICE_MS, DENSE = ("own_lookup_device_ms_per_block",
                    "own_lookup_dense_blocks_per_epoch")


def test_lookup_time_is_every_vertexs_lookup_scope():
    """Both vertices' lookups and what runs beneath them; not another
    part of the vertex, not the exchange."""
    st = scope_times.ScopeTimes(2, {
        ("vertex", "count", "lookup"): 0.012,
        ("vertex", "max", "lookup"): 0.002,
        ("vertex", "count", "place"): 0.040,
        ("vertex", "count", "place", "hist"): 0.008,
        ("exchange", "place"): 0.020, (): 0.001}, {})
    run = types.SimpleNamespace(_scope_times=st)
    assert read(DEVICE_MS, run) == pytest.approx(7.0)
    # a trace of a program with no own columns: nothing to read, not 0
    none = scope_times.ScopeTimes(1, {("vertex", "window"): 0.001}, {})
    assert read(DEVICE_MS, types.SimpleNamespace(_scope_times=none)) is None


def test_lookup_time_on_the_recorded_block_and_without_a_device_plane():
    dev = scope_times.load(RECORDED)
    st = scope_times.reduce(dev, float("-inf"), float("inf"))
    assert st.leaf("lookup") == 0
    assert read(DEVICE_MS, types.SimpleNamespace(_scope_times=st)) is None
    no_ops = types.SimpleNamespace(
        events=trace_reduce.Events({}, {}, [("steady", 0.0, 1e9)]),
        trace_window=lambda name: (0.0, 1e9))
    assert read(DEVICE_MS, no_ops) is None
    untraced = types.SimpleNamespace(events=None,
                                     trace_window=lambda name: None)
    assert read(DEVICE_MS, untraced) is None


def test_dense_blocks_are_the_counters_over_the_committed_epochs():
    run = fake_run([], counters={"lookup.dense_blocks.count": 3,
                                 "lookup.dense_blocks.max": 0,
                                 "window.dropped_rows.count": 7})
    run.stamps = [1.0, 2.0, 3.0, 4.0]
    assert read(DENSE, run) == pytest.approx(0.75)
    run.stamps = []
    assert read(DENSE, run) is None
    # the program before PR 49 keeps no such counter: nothing, not 0
    older = fake_run([], counters={"window.dropped_rows.count": 7})
    older.stamps = [1.0]
    assert read(DENSE, older) is None


@pytest.mark.parametrize("cell, vertices", [
    ("nexmarkq5.backlog", ("count", "max")),
    ("nexmarkq11.backlog", ("sessions",))])
def test_rehearsal_reports_no_dense_block_and_no_device_time(tiny_bench, cell,
                                                             vertices):
    from clonos_tpu.obs import get_tracer, trace
    trace.reset()      # counters are the process's: a run is one process
    result = harness.run_cell(tiny_bench, cell, 2**31 + 49, seconds=1.5,
                              trace=True, check_chip=False)
    assert result["correct"] is True
    assert result["metrics"][DENSE]["value"] == 0
    assert DEVICE_MS not in result["metrics"]       # no device plane here
    counters = get_tracer().counters()
    for v in vertices:
        assert counters["lookup.dense_blocks." + v] == 0
