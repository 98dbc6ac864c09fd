"""Every cell at a tiny size on the CPU, through all four phases to the
last JSON line; the controls and a timed path broken underneath, each of
which must come out ``correct: false``; and the refusal to run with no
TPU."""

import json

import pytest

import run as harness
from benchlib import quarters

CELLS = ["kafka64.backlog", "allround32.backlog", "kafka64.paced",
         "allround64x4.backlog"]


def rehearse(tiny_bench, cell, seed=2**31 + 11, trace=False, **kw):
    return harness.run_cell(tiny_bench, cell, seed, seconds=1.5, trace=trace,
                            check_chip=False, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_to_a_correct_last_line(tiny_bench, cell, capsys):
    result = rehearse(tiny_bench, cell)
    captured = capsys.readouterr()
    last = captured.out.strip().splitlines()[-1]
    assert json.loads(last) == result
    # each number compared beside its limit: the result's last key, and
    # the last lines on stderr
    assert list(result)[-1] == "checks"
    assert result["checks"]["mismatched_rows"] == {
        "value": 0, "limit": 0, "ok": True}
    assert captured.err.strip().splitlines()[-len(result["checks"]):] == [
        f"check {name}={c['value']} {kind}={c[kind]} ok"
        for name, c in result["checks"].items()
        for kind in c if kind not in ("value", "ok")]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert result["device"]["count"] == (4 if "x4" in cell else 1)
    want = {"setup_s", "time_to_resume_ms"} | (
        {"commit_latency_p50_ms", "commit_latency_p95_ms"}
        if cell.endswith(".paced")
        # the mesh's rate is an end-to-end metric of its own (PR 43)
        else {"served_records_per_s.mesh"} if "x4" in cell
        else {"served_records_per_s"})
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("cell", ["kafka64.backlog", "kafka64.paced"])
def test_traced_run_reports_what_its_readers_find(tiny_bench, cell):
    """No device plane on the CPU, so the device-trace readers find
    nothing and are left out; the outside span readers report, and
    whatever readers of the program's own spans later PRs have added."""
    result = rehearse(tiny_bench, cell, trace=True)
    assert result["correct"] is True
    want = ({"commit_service_ms", "fence_tail_ms"}
            if cell.endswith(".paced")
            else {"feed_pull_ms_per_block", "sink_absorb_ms_per_block"})
    assert set(result["metrics"]) >= want
    per_layer = {m["name"] for m in json.load(open(tiny_bench))["per_layer"]}
    assert set(result["metrics"]) <= per_layer


def by_quarter_line(out: str) -> list:
    (line,) = [ln for ln in out.splitlines()
               if ln.startswith(quarters.LINE)]
    return json.loads(line[len(quarters.LINE):])


@pytest.mark.parametrize("cell,trace", [("kafka64.backlog", True),
                                        ("allround64x4.backlog", True),
                                        ("kafka64.backlog", False)])
def test_window_by_quarter_is_printed_and_parses(tiny_bench, cell, trace,
                                                 capsys):
    """One line before the result: the cut of ``rates_by_part``, each
    quarter's rate beside the host's spans per block. A traced run has
    the harness's wrappers over the whole window; any run the program's
    recorder."""
    result = rehearse(tiny_bench, cell, trace=trace)
    out = capsys.readouterr().out
    parts = by_quarter_line(out)
    assert result["correct"] is True and len(parts) == 4
    if not trace:      # the rate's reader prints the same cut's rates
        (window,) = [ln for ln in out.splitlines()
                     if ln.startswith("window:")]
        printed = window.split("by quarter ")[1].split(" records/s")[0]
        assert [p["records_per_s"] for p in parts] == pytest.approx(
            [float(r) for r in printed.split()], abs=1)
    for p in parts:
        assert p["epochs"] >= 1 and p["blocks"] == 2 * p["epochs"]
        assert p["program"] == "whole"
        assert {"block.causal-inputs", "block.dispatch", "block.feed.pull",
                "block.feed.put"} <= set(p["program_ms_per_block"])
        assert all(v > 0 for v in p["program_ms_per_block"].values())
        draws = p["draws"]
        # a commit stamp is not a block's edge: a draw more or less
        assert abs(draws["alone"]["n"] + draws["beside_another_thread"]["n"]
                   - p["blocks"]) <= 2
        if trace:
            assert {"epoch", "feed_pull", "sink_absorb"} <= set(
                p["harness_ms_per_block"])
        else:
            assert "harness_ms_per_block" not in p


@pytest.mark.parametrize("control", ["f32", "at-least-once"])
def test_control_in_the_programs_place_is_not_correct(tiny_bench, control,
                                                      capsys):
    result = rehearse(tiny_bench, "allround32.backlog", control=control)
    out = capsys.readouterr().out
    assert "check program (before the control takes its place): " \
           "mismatched_rows=0 limit=0" in out
    assert result["correct"] is False and result["failed"] > 0


def add_one_to_a_block_of_sink_values(runner):
    """An answer altered where it is produced: the 12th block's sink
    emissions reach the transaction log with every value one too high."""
    inner = runner.executor.on_block_outputs
    seen = [0]

    def broken(outs, epoch):
        seen[0] += 1
        if seen[0] == 12:
            outs = outs._replace(sinks={
                vid: b._replace(values=b.values + 1)
                for vid, b in outs.sinks.items()})
        return inner(outs, epoch)
    runner.executor.on_block_outputs = broken


def drop_a_commit(runner):
    """An epoch's rows never become visible."""
    (txn,) = runner.txn_logs.values()
    inner = txn.commit

    def broken(epoch):
        if epoch == 6:
            txn._pending.pop(6, None)
        return inner(epoch)
    txn.commit = broken


@pytest.mark.parametrize("sabotage", [add_one_to_a_block_of_sink_values,
                                      drop_a_commit])
def test_broken_timed_path_is_not_correct(tiny_bench, sabotage):
    result = rehearse(tiny_bench, "kafka64.backlog", sabotage=sabotage)
    assert result["correct"] is False and result["failed"] >= 1


def test_refuses_to_run_without_a_tpu(capsys):
    rc = harness.main(["--workload", "kafka64.backlog", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 2 and captured.out == "" and "TPU" in captured.err
