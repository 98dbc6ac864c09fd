"""The ``allround-event-time`` job (upstream's all-round test job: event
time assigned on the device, a keyed-state and an operator-state mapper,
a tumbling and a sliding event-time window behind watermarks, a union in
front of a transactional sink) through ``ClusterRunner`` against its
plain NumPy reference, at a tiny size: the whole committed stream before
and after a kill, the late records of a table whose lag exceeds the
bound, the reference's own fold against its periodic extension, and each
control."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import job  # noqa: E402
from benchlib.byname import module_at  # noqa: E402

from clonos_tpu import obs  # noqa: E402
from clonos_tpu.parallel import routing  # noqa: E402

TUMBLING, SLIDING = 4, 5          # vertex ids in job.py's build order


def config(**over):
    cfg = {"name": "tiny-allround-upstream",
           "topology": "allround-event-time",
           "parallelism": 4, "batch": 8, "num_keys": 20,
           "key_dist": {"kind": "uniform"}, "value_bits": 18,
           "num_key_groups": 64, "clock_ms_per_step": 100,
           "max_out_of_order_ms": 500, "max_lag_ms": 500,
           "tumbling_ms": 2000, "slide_ms": 250, "slide_factor": 3,
           "edge_capacity": 32, "union_capacity": 64,
           "steps_per_epoch": 64, "block_steps": 16, "log_capacity": 2048,
           "max_epochs": 32, "inflight_ring_steps": 256,
           "recovery_block_steps": 128, "overlap_epoch": True}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return module_at(job.topology_file(config(), "reference.py"))


def run_job(cfg, seed, epochs, tmp_path, kill=None):
    """``epochs`` completed epochs; ``kill = (vertex, subtask)`` fails
    that subtask half-way, behind two epochs whose checkpoints stay
    pending. Returns (runner, stream, epoch -> committed row arrays)."""
    stream = job.make_stream(cfg, {"table_epochs": 2}, seed)
    runner = job.make_runner(cfg, stream, seed, str(tmp_path / "ck"), 1)
    (txn,) = runner.txn_logs.values()
    got = {}
    txn.committer = lambda e, rows: got.setdefault(e, []).append(
        np.asarray(rows))
    for i in range(epochs):
        if kill is not None and i == epochs // 2:
            runner.run_epoch(complete_checkpoint=False)
            runner.run_epoch(complete_checkpoint=False)
            runner.inject_failure(
                [runner.job.subtask_base(kill[0]) + kill[1]])
            assert runner.recover().steps_replayed == \
                2 * cfg["steps_per_epoch"]
        runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    assert runner.executor.check_overflow() == []
    return runner, stream, got


def late_of(runner, vid):
    return int(np.asarray(runner.executor.vertex_state(vid)["late"]).sum())


@pytest.mark.parametrize("victim", [(TUMBLING, 1), (SLIDING, 2)],
                         ids=["tumbling", "sliding"])
def test_committed_stream_equals_the_reference_through_a_kill(
        ref, tmp_path, victim):
    cfg = config()
    runner, stream, got = run_job(cfg, 11, 8, tmp_path, kill=victim)
    epochs = runner.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 1000
    stamps = np.concatenate([r for parts in got.values() for r in parts]
                            )[:, 2] % cfg["tumbling_ms"]
    assert set(stamps.tolist()) == {0, 250, 500, 750}
    assert late_of(runner, TUMBLING) == want.late_tumbling == 0
    assert late_of(runner, SLIDING) == want.late_sliding == 0


def test_late_records_are_dropped_and_counted_as_the_reference_counts(
        ref, tmp_path, monkeypatch):
    """A lag of up to 1,300 ms against a bound of 500: the windows drop
    what arrives behind their watermark, the committed stream is the
    reference's all the same, and both count the same records. The
    killed subtask holds 16 replica logs; rebuilt three at a time here,
    so that the rebuild takes several calls of its program."""
    from clonos_tpu.runtime.cluster import ClusterRunner
    monkeypatch.setattr(ClusterRunner, "REPLICA_COPY_ROWS", 3)
    cfg = config(max_lag_ms=1300)
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner, stream, got = run_job(cfg, 5, 8, tmp_path, kill=(TUMBLING, 3))
    epochs = runner.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    assert ref.check(got, want, cfg, epochs)[:2] == (0, [])
    assert want.late_tumbling > 1000
    assert late_of(runner, TUMBLING) == want.late_tumbling
    assert late_of(runner, SLIDING) == want.late_sliding == 0
    # the fence read the same totals into the tracer's counters
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("window.late_records.tumbling") == want.late_tumbling
    assert grew("window.late_records.sliding") == 0
    fired = sum(len(r) for parts in got.values() for r in parts)
    assert fired <= (grew("window.fired_rows.tumbling")
                     + grew("window.fired_rows.sliding")) <= fired + 400


@pytest.mark.parametrize("max_lag_ms", [500, 1300])
def test_reference_extends_its_fold_as_it_would_fold_on(ref, max_lag_ms):
    """Past two common periods of table and window grid (640 steps here)
    the reference derives windows from the second period; folding every
    record of the run instead gives the same commits and late count."""
    cfg = config(max_lag_ms=max_lag_ms)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 23)
    table = (cfg, stream.keys, stream.vals, 57)
    short = ref.expected(*table)
    whole = ref.expected(*table, direct_periods=100)
    assert short.late_tumbling == whole.late_tumbling
    assert (short.late_tumbling > 0) == (max_lag_ms > 500)
    assert ref.check(ref.committed_of(short, cfg, 57), whole, cfg, 57)[0] == 0
    assert sum(len(r) for r in whole.rows) > 10000


@pytest.mark.parametrize("control,epochs,sizes", [
    ("at-least-once", 12, {}), ("arrival-time", 12, {}),
    ("f32", 40, {"batch": 64, "num_keys": 8})])
def test_each_control_differs_from_the_reference(ref, control, epochs,
                                                 sizes):
    """``f32`` needs window sums past 2**24: with 32 records a key a
    step, counts times records per window get there after ~820 steps."""
    cfg = config(**sizes)
    stream = job.make_stream(cfg, {"table_epochs": 2}, 3)
    table = (cfg, stream.keys, stream.vals, epochs)
    want = ref.expected(*table)
    perturbed = ref.expected(*table, control=control,
                             control_step=epochs * 32)
    bad, failed, _ = ref.check(ref.committed_of(perturbed, cfg, epochs),
                               want, cfg, epochs)
    assert bad > 0 and failed
    assert ref.check(ref.committed_of(want, cfg, epochs), want, cfg,
                     epochs)[:2] == (0, [])


def test_check_counts_missing_duplicated_and_foreign_rows(ref):
    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 3)
    want = ref.expected(cfg, stream.keys, stream.vals, 4)
    got = ref.committed_of(want, cfg, 4)
    rows = got[2][0]
    got[2] = [np.concatenate([rows[1:], rows[-1:]])]    # one lost, one twice
    got[3] = [got[3][0], got[3][0][:0]]                 # committed twice
    got[9] = [rows[:2]]                                 # no such epoch
    del got[1]
    bad, failed, _ = ref.check(got, want, cfg, 4)
    assert bad == 2 + 1 + 2 + len(want.rows[1])
    assert sorted(failed) == [1, 2, 3, 9]


def test_window_edges_take_static_routes_that_lose_no_row(tmp_path):
    """Both windows emit statically keyed slots, so the edges behind the
    tumbling window take the gather plan; the plan reserves a slot for
    every (producer subtask, slot) pair, so a row reaches its key's
    owner from whichever subtask fired it: a key at or past ``num_keys``
    is summed under the last key by the subtask that *received* it, not
    by that key's owner."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.cluster import ClusterRunner

    cfg = config()
    stream = job.make_stream(cfg, {"table_epochs": 2}, 1)
    compiled = job.make_runner(cfg, stream, 1, str(tmp_path / "ck"),
                               1).executor.compiled
    edges = {(e.src, e.dst): i for i, e in enumerate(compiled.job.edges)}
    assert {edges[TUMBLING, SLIDING], edges[TUMBLING, 6]} <= set(
        compiled.static_route)
    assert edges[3, TUMBLING] not in compiled.static_route
    plan = compiled.static_route[edges[TUMBLING, SLIDING]]
    sk = compiled.job.vertices[TUMBLING].operator.static_out_keys()
    assert plan.ok.sum() == cfg["parallelism"] * len(sk)

    # keys 0..29 into windows over 20 keys, through the runner
    env = StreamEnvironment(name="past-num-keys", num_key_groups=64)
    (env.synthetic_source(vocab=30, batch_size=6, parallelism=4)
        .key_by().window_event_time(num_keys=20, window_size=64,
                                    out_of_orderness=16, name="window")
        .key_by().sink())
    r = ClusterRunner(env.build(), steps_per_epoch=8, seed=3)
    r.executor.time_source.now = lambda it=iter(range(0, 40000, 20)): next(it)
    for _ in range(4):
        r.run_epoch()
    assert r.executor.check_overflow() == []
    assert 1 in r.executor.compiled.static_route
    fired = int(np.asarray(r.executor.vertex_state(1)["fired"]).sum())
    r.step()                    # the sink takes a step's rows the step after
    base = r.job.subtask_base(2)
    at_sink = int(np.asarray(r.executor.carry.record_counts)[base:base + 4]
                  .sum())
    owner_of_last = int(routing._static_targets(np.asarray([19]), 4, 64)[0])
    elsewhere = np.delete(np.asarray(
        r.executor.vertex_state(1)["acc"])[:, :, 19], owner_of_last, axis=0)
    assert fired > 100 and elsewhere.any()
    assert at_sink == fired
