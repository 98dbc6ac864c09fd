"""The cell ``nexmarkq3x4.backlog`` at a tiny size on four forced host
devices, through all four phases to the last JSON line — the drill and
the kill of one subtask of every vertex on the auctions' path included —
its own metrics in the traced run, its three controls, each of which
must come out ``correct: false``, and the four readers PR 51 added on
hand-made records, on the recorded epoch of ``kafka64.backlog`` and on a
program that has nothing for them.

A configuration's tiny stand-in is found by its file name,
``tiny/bench/configs/tiny-<configuration>.json``: this module enters
into ``conftest.TINY`` (a closed dict the session fixture reads) every
configuration of BENCHMARK.json that has such a file and no entry yet,
so it runs alone too."""

import json
import os
import types

import pytest

import conftest
import run as harness
from test_program_spans import fake_run, span
from benchlib import trace_reduce
from benchlib.byname import module_at

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = os.path.join(os.path.dirname(HERE), "readers")
for _c in json.load(open(os.path.join(conftest.ROOT, "BENCHMARK.json")))[
        "configs"]:
    if os.path.isfile(os.path.join(HERE, "tiny", "bench", "configs",
                                   f"tiny-{_c['name']}.json")):
        conftest.TINY.setdefault(_c["name"], "tiny-" + _c["name"])

CELL = "nexmarkq3x4.backlog"
MESH_ONLY = {"setup_s", "time_to_resume_ms", "served_records_per_s.mesh"}


def read(metric, run):
    return module_at(os.path.join(READERS, metric + ".py")).read(run)


def rehearse(tiny_bench, seed=2**31 + 91, trace=False, **kw):
    return harness.run_cell(tiny_bench, CELL, seed, seconds=1.5, trace=trace,
                            check_chip=False, **kw)


def test_cell_runs_to_a_correct_last_line(tiny_bench, capsys):
    result = rehearse(tiny_bench)
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8 and result["device"]["count"] == 4
    assert "check steps_replayed=128 " in out
    assert "check overflow_messages=0 " in out
    assert "check compilations_after_setup=0 " in out
    assert set(result["metrics"]) == MESH_ONLY
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_traced_run_reports_the_program_counters(tiny_bench):
    """No device plane on the CPU; the readers of the program's spans
    and counters report: the join's own three equal to the reference's,
    the carry as it was built (a quarter a device), the cascade's
    victims and how far its determinants came from."""
    from benchlib import job
    from clonos_tpu.obs import trace
    trace.reset()      # counters are the process's: a run is one process
    result = rehearse(tiny_bench, seed=2**31 + 93, trace=True)
    assert result["correct"] is True
    cell = harness.Cell(tiny_bench, CELL)
    stream = job.make_stream(cell.cfg, cell.traffic, 2**31 + 93)
    want = cell.reference.expected(cell.cfg, stream.keys, stream.vals,
                                   result["attempted"])
    value = lambda name: result["metrics"][name]["value"]
    assert value("incjoin_edge_peak_fill_pct.mesh") == pytest.approx(
        100.0 * want.peak_auctions / cell.cfg["edge_capacity"])
    assert value("incjoin_live_persons.mesh") == want.peak_live
    assert value("incjoin_rows_per_epoch.mesh") == pytest.approx(
        want.fired / result["attempted"], rel=0.1)
    assert value("sink_rows_per_block.mesh") > 0
    assert value("recovery_victims") == 4
    assert value("recovery_fetch_hops") == 1
    counters = trace.get_tracer().counters()
    assert value("carry_max_device_gib.mesh") * 2**30 == pytest.approx(
        counters["carry.max_device_bytes"])
    assert (counters["carry.max_device_bytes"]
            < 0.26 * counters["carry.bytes"])
    assert 0 < value("carry_build_s.mesh") < 60
    for name in ("recovery_replay_ms", "recovery_fetch_ms",
                 "causal_inputs_ms_per_block.mesh",
                 "feed_put_ms_per_block.mesh"):
        assert value(name) > 0
    # nothing on the CPU for the device-trace readers, nor under the
    # one-chip cells' names
    for name in ("incjoin_lookup_device_ms_per_block.mesh",
                 "sink_pack_device_ms_per_block.mesh",
                 "incjoin_rows_per_epoch", "hbm_peak_gib"):
        assert name not in result["metrics"]


@pytest.mark.parametrize("control", ["f32", "at-least-once", "no-filter"])
def test_control_in_the_programs_place_is_not_correct(tiny_bench, control,
                                                      capsys):
    result = rehearse(tiny_bench, control=control)
    out = capsys.readouterr().out
    assert "check program (before the control takes its place): " \
           "mismatched_rows=0 limit=0" in out
    assert result["correct"] is False and result["failed"] > 0


# --- the readers PR 51 added --------------------------------------------------


def test_carry_readers_on_hand_made_records():
    """The span where the ring holds it, the counter where the ring has
    moved past it, None on a program with neither."""
    run = fake_run([span("setup.init-carry", 1.0, 2.5, 1),
                    span("epoch", 5.0, 1.0, 2)],
                   counters={"carry.bytes": 16 << 30,
                             "carry.max_device_bytes": 4 << 30,
                             "carry.build_us": 2_600_000})
    assert read("carry_build_s", run) == pytest.approx(2.5)
    assert read("carry_max_device_gib", run) == pytest.approx(4.0)
    evicted = fake_run([span("epoch", 5.0, 1.0, 2)], dropped=7,
                       counters={"carry.build_us": 2_600_000})
    assert read("carry_build_s", evicted) == pytest.approx(2.6)
    assert read("carry_max_device_gib", evicted) is None
    parent = fake_run([span("epoch", 5.0, 1.0, 2)])
    assert read("carry_build_s", parent) is None
    assert read("carry_max_device_gib", parent) is None


def test_cascade_readers_read_the_report():
    report = types.SimpleNamespace(victims=4, fetch_hops=2)
    run = types.SimpleNamespace(report=report)
    assert read("recovery_victims", run) == 4
    assert read("recovery_fetch_hops", run) == 2
    # the parent's report says neither; a run that never recovered has none
    old = types.SimpleNamespace(report=types.SimpleNamespace(
        failed_subtasks=(17,)))
    none = types.SimpleNamespace(report=None)
    for run in (old, none):
        assert read("recovery_victims", run) is None
        assert read("recovery_fetch_hops", run) is None


def test_new_readers_on_the_recorded_epoch():
    """The recorded epoch of ``kafka64.backlog`` laid under hand-made
    records, as a traced run holds both: the readers take the program's
    records and nothing of the device's; with the recorder of a program
    of before PR 51 (no such span, no such counter) they find nothing."""
    ev = trace_reduce.load(os.path.join(
        HERE, "data", "kafka64_backlog_one_epoch.json.gz"))
    (e_lo, e_hi), = trace_reduce.spans_inside(ev, "epoch", 0, float("inf"))
    epoch = span("epoch", 7000.0, (e_hi - e_lo) / 1e9, "e")
    run = fake_run([span("setup.init-carry", 6990.0, 0.75, "c"), epoch],
                   events=ev, counters={"carry.max_device_bytes": 3 << 29})
    run.report = types.SimpleNamespace(victims=3, fetch_hops=1)
    assert read("carry_build_s", run) == pytest.approx(0.75)
    assert read("carry_max_device_gib", run) == pytest.approx(1.5)
    assert read("recovery_victims", run) == 3
    before = fake_run([epoch], events=ev)
    before.report = None
    for name in ("carry_build_s", "carry_max_device_gib",
                 "recovery_victims", "recovery_fetch_hops"):
        assert read(name, before) is None
