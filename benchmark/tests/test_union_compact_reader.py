"""The reader of the union's compaction (PR 50):
``union_compact_device_ms_per_block`` on a hand-made reduction — the
scope and the histograms beneath it, not the union's other ops, not
another vertex's ``compact`` — on the recorded block of
``kafka64.backlog`` (a job with no union: None) and with no device
plane."""

import os
import types

import pytest

from benchlib import scope_times, trace_reduce
from test_program_spans import read

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "kafka64_backlog_one_block.json.gz")
NAME = "union_compact_device_ms_per_block"


def test_compact_time_is_the_unions_scope_and_what_runs_beneath_it():
    st = scope_times.ScopeTimes(4, {
        ("vertex", "union", "compact"): 0.0016,
        ("vertex", "union", "compact", "hist"): 0.0064,
        ("vertex", "union"): 0.0100,
        ("vertex", "join", "compact"): 0.0200,
        ("vertex", "sliding", "place", "hist"): 0.0400,
        ("exchange", "plan"): 0.0010, (): 0.0020}, {})
    run = types.SimpleNamespace(_scope_times=st)
    assert read(NAME, run) == pytest.approx(2.0)
    # a trace of a program with no union: nothing to read, not 0
    none = scope_times.ScopeTimes(1, {("vertex", "join", "compact"): 0.001},
                                  {})
    assert read(NAME, types.SimpleNamespace(_scope_times=none)) is None


def test_compact_time_on_the_recorded_block_and_without_a_device_plane():
    dev = scope_times.load(RECORDED)
    st = scope_times.reduce(dev, float("-inf"), float("inf"))
    assert st.under(scope_times.VERTEX, "union", "compact") == 0
    assert read(NAME, types.SimpleNamespace(_scope_times=st)) is None
    no_ops = types.SimpleNamespace(
        events=trace_reduce.Events({}, {}, [("steady", 0.0, 1e9)]),
        trace_window=lambda name: (0.0, 1e9))
    assert read(NAME, no_ops) is None
    untraced = types.SimpleNamespace(events=None,
                                     trace_window=lambda name: None)
    assert read(NAME, untraced) is None
