"""The cell ``nexmarkq4.backlog`` at a tiny size on the CPU, through all
four phases to the last JSON line, its own metrics in the traced run,
and its three controls, each of which must come out ``correct: false``.

``conftest.TINY`` maps a configuration to its tiny stand-in and is a
closed dict; this module adds its own entry as it is imported, which is
before the session fixture reads the dict (so: run ``pytest
benchmark/tests`` whole)."""

import json

import pytest

import conftest
import run as harness

conftest.TINY.setdefault("nexmark-q4", "tiny-nexmark-q4")
# the session fixture maps every configuration of BENCHMARK.json
conftest.TINY.setdefault("nexmark-q3", "tiny-nexmark-q3")
conftest.TINY.setdefault("nexmark-q11", "tiny-nexmark-q11")
conftest.TINY.setdefault("nexmark-q5", "tiny-nexmark-q5")
conftest.TINY.setdefault("nexmark-q8", "tiny-nexmark-q8")
conftest.TINY.setdefault("allround-upstream", "tiny-allround-upstream")

CELL = "nexmarkq4.backlog"


def rehearse(tiny_bench, seed=2**31 + 81, trace=False, **kw):
    return harness.run_cell(tiny_bench, CELL, seed, seconds=1.5, trace=trace,
                            check_chip=False, **kw)


def test_cell_runs_to_a_correct_last_line(tiny_bench, capsys):
    result = rehearse(tiny_bench)
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert "check steps_replayed=128 " in out
    assert "check overflow_messages=0 " in out
    assert set(result["metrics"]) == {"setup_s", "time_to_resume_ms",
                                      "served_records_per_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_traced_run_reports_the_program_counters(tiny_bench):
    """No device plane on the CPU, so the device-trace readers find
    nothing (the match's device time among them); the readers of the
    program's spans and counters report, the cell's own three among
    them: the rows of the join, the bids that counted and the bids'
    edge at its fullest, each equal to the reference's; and no row came
    late to the mean."""
    from benchlib import job
    from clonos_tpu.obs import trace
    trace.reset()      # counters are the process's: a run is one process
    result = rehearse(tiny_bench, seed=2**31 + 83, trace=True)
    assert result["correct"] is True
    cell = harness.Cell(tiny_bench, CELL)
    stream = job.make_stream(cell.cfg, cell.traffic, 2**31 + 83)
    want = cell.reference.expected(cell.cfg, stream.keys, stream.vals,
                                   result["attempted"])
    metrics = result["metrics"]
    assert metrics["winbid_edge_peak_fill_pct"]["value"] == pytest.approx(
        100.0 * want.peak_bids / cell.cfg["edge_capacity"])
    # the counters hold what the fences read: every epoch but the part
    # of the last that the final drain's fence had not closed
    rows = metrics["winbid_rows_per_epoch"]["value"]
    assert rows == pytest.approx(want.winning_rows / result["attempted"],
                                 rel=0.1)
    bids = metrics["winbid_valid_bids_per_epoch"]["value"]
    assert bids == pytest.approx(want.valid / result["attempted"], rel=0.1)
    assert bids > rows > 50
    assert metrics["window_late_records_per_epoch"]["value"] == 0
    assert metrics["sink_rows_per_block"]["value"] > 0
    assert "winbid_match_device_ms_per_block" not in metrics
    assert "incjoin_rows_per_epoch" not in metrics
    assert "join_rows_per_epoch" not in metrics


@pytest.mark.parametrize("control", ["f32", "no-interval", "lose-a-step"])
def test_control_in_the_programs_place_is_not_correct(tiny_bench, control,
                                                      capsys):
    result = rehearse(tiny_bench, control=control)
    out = capsys.readouterr().out
    assert "check program (before the control takes its place): " \
           "mismatched_rows=0 limit=0" in out
    assert result["correct"] is False and result["failed"] > 0


def drop_a_block_of_rows(runner):
    """The timed path broken underneath: the 12th block's sink emissions
    reach the transaction log with no row valid."""
    inner = runner.executor.on_block_outputs
    seen = [0]

    def broken(outs, epoch):
        seen[0] += 1
        if seen[0] == 12:
            outs = outs._replace(sinks={
                vid: b._replace(valid=b.valid & False)
                for vid, b in outs.sinks.items()})
        return inner(outs, epoch)
    runner.executor.on_block_outputs = broken


def test_a_broken_timed_path_is_not_correct(tiny_bench):
    result = rehearse(tiny_bench, seed=2**31 + 85,
                      sabotage=drop_a_block_of_rows)
    assert result["correct"] is False and result["failed"] >= 1
