"""The reader of the keyed reduce's read-back (PR 52):
``keyed_readback_device_ms_per_block`` on a hand-made reduction — every
``vertex/<name>/readback`` scope, not the reduce's other parts, not
another part that ends alike — on the recorded block of
``kafka64.backlog`` (its ``reduce`` vertex reads back behind a static
route) and with no device plane."""

import os
import types

import pytest

from benchlib import scope_times, trace_reduce
from test_program_spans import read

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "kafka64_backlog_one_block.json.gz")
NAME = "keyed_readback_device_ms_per_block"


def test_readback_time_is_every_vertex_readback_scope():
    st = scope_times.ScopeTimes(4, {
        ("vertex", "keyed-state", "readback"): 0.0060,
        ("vertex", "reduce", "readback"): 0.0020,
        ("vertex", "keyed-state", "segsum"): 0.0100,
        ("vertex", "keyed-state", "hist"): 0.0200,
        ("vertex", "keyed-state"): 0.0400,
        ("exchange", "plan"): 0.0010, (): 0.0020}, {})
    run = types.SimpleNamespace(_scope_times=st)
    assert read(NAME, run) == pytest.approx(2.0)
    # a trace of a program with no keyed reduce: nothing to read, not 0
    none = scope_times.ScopeTimes(1, {("vertex", "join", "segsum"): 0.001},
                                  {})
    assert read(NAME, types.SimpleNamespace(_scope_times=none)) is None


def test_readback_time_on_the_recorded_block_and_without_a_device_plane():
    dev = scope_times.load(RECORDED)
    st = scope_times.reduce(dev, float("-inf"), float("inf"))
    want = st.ms_per_block(st.under(scope_times.VERTEX, "reduce",
                                    "readback"))
    assert 0 < want < 1                   # a static gather: 0.07 ms on the chip
    assert read(NAME, types.SimpleNamespace(_scope_times=st)) == \
        pytest.approx(want)
    no_ops = types.SimpleNamespace(
        events=trace_reduce.Events({}, {}, [("steady", 0.0, 1e9)]),
        trace_window=lambda name: (0.0, 1e9))
    assert read(NAME, no_ops) is None
    untraced = types.SimpleNamespace(events=None,
                                     trace_window=lambda name: None)
    assert read(NAME, untraced) is None
