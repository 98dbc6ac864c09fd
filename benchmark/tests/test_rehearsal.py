"""Every cell at a tiny size on the CPU, through all four phases to the
last JSON line; the controls and a timed path broken underneath, each of
which must come out ``correct: false``; and the refusal to run with no
TPU."""

import json

import pytest

import run as harness

CELLS = ["kafka64.backlog", "allround32.backlog", "kafka64.paced",
         "allround64x4.backlog"]


def rehearse(tiny_bench, cell, seed=2**31 + 11, trace=False, **kw):
    return harness.run_cell(tiny_bench, cell, seed, seconds=1.5, trace=trace,
                            check_chip=False, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_to_a_correct_last_line(tiny_bench, cell, capsys):
    result = rehearse(tiny_bench, cell)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8
    assert result["device"]["count"] == (4 if "x4" in cell else 1)
    want = {"setup_s", "time_to_resume_ms"} | (
        {"commit_latency_p50_ms", "commit_latency_p95_ms"}
        if cell.endswith(".paced") else {"served_records_per_s"})
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())


@pytest.mark.parametrize("cell", ["kafka64.backlog", "kafka64.paced"])
def test_traced_run_reports_what_its_readers_find(tiny_bench, cell):
    """No device plane on the CPU, so the device-trace readers find
    nothing and are left out; the span readers report."""
    result = rehearse(tiny_bench, cell, trace=True)
    assert result["correct"] is True
    want = ({"commit_service_ms", "fence_tail_ms"}
            if cell.endswith(".paced")
            else {"feed_pull_ms_per_block", "sink_absorb_ms_per_block"})
    assert set(result["metrics"]) == want


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
