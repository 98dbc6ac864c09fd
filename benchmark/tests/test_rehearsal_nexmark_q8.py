"""The cell ``nexmarkq8.backlog`` at a tiny size on the CPU, through all
four phases to the last JSON line, and its three controls, each of which
must come out ``correct: false``.

``conftest.TINY`` maps a configuration to its tiny stand-in and is a
closed dict; this module adds its own entry as it is imported, which is
before the session fixture reads the dict (so: run ``pytest
benchmark/tests`` whole)."""

import json

import pytest

import conftest
import run as harness

conftest.TINY.setdefault("nexmark-q8", "tiny-nexmark-q8")
# the session fixture maps every configuration of BENCHMARK.json
conftest.TINY.setdefault("allround-upstream", "tiny-allround-upstream")

CELL = "nexmarkq8.backlog"


def rehearse(tiny_bench, seed=2**31 + 37, trace=False, **kw):
    return harness.run_cell(tiny_bench, CELL, seed, seconds=1.5, trace=trace,
                            check_chip=False, **kw)


def test_cell_runs_to_a_correct_last_line(tiny_bench, capsys):
    result = rehearse(tiny_bench)
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert "check steps_replayed=128 " in out
    assert set(result["metrics"]) == {"setup_s", "time_to_resume_ms",
                                      "served_records_per_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_traced_run_reports_the_program_counters(tiny_bench):
    """No device plane on the CPU, so the device-trace readers find
    nothing; the readers of the program's spans and counters report,
    the join's own among them: rows every epoch, no record late."""
    result = rehearse(tiny_bench, seed=2**31 + 41, trace=True)
    assert result["correct"] is True
    assert result["metrics"]["join_rows_per_epoch"]["value"] > 100
    assert result["metrics"]["window_late_records_per_epoch"]["value"] == 0
    assert result["metrics"]["sink_rows_per_block"]["value"] > 0


@pytest.mark.parametrize("control", ["f32", "at-least-once", "no-join"])
def test_control_in_the_programs_place_is_not_correct(tiny_bench, control,
                                                      capsys):
    result = rehearse(tiny_bench, control=control)
    out = capsys.readouterr().out
    assert "check program (before the control takes its place): " \
           "mismatched_rows=0 limit=0" in out
    assert result["correct"] is False and result["failed"] > 0
