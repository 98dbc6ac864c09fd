"""Soak & chaos harness (clonos_tpu/soak/): open-loop SLO tracking
with exactly-once asserted under injected failure.

Unit layers first — the chaos DSL must be seeded-replayable (same seed,
same fault sequence, byte for byte), the SLO windows must breach on the
right bound, the coordinated-omission correction must charge queueing
delay to exactly the samples whose fence ran late, and a gray failure
must land a worker in ``degraded()`` without ever reaching
``expired()``. The slow tests then run the real driver: a paced run
surviving a kill cascade + gray failure with the audit ledger clean
end-to-end, an injected unlogged perturbation that MUST fail the run,
and the ``clonos_tpu soak --report json`` exit-0/1 CI contract.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from clonos_tpu.soak import (ChaosEvent, ChaosSchedule, SLOSpec,
                             SLOTracker, Window, corrected_closed_loop,
                             parse_schedule, quantile)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- chaos DSL ---------------------------------------------------------------


def test_dsl_parse_all_kinds_and_roundtrip():
    text = """
    # warm-in stays quiet
    at 5s kill 1,9,17
    at 12s gray 2 delay=50ms for 3s
    at 20s leader-loss hold=1s ; at 30s stall delay=200ms for 2s
    at 40s nondet
    """
    sched = parse_schedule(text)
    assert sched.kinds() == ["kill", "gray", "leader-loss", "stall",
                             "nondet"]
    kill, gray, ll, stall, nondet = list(sched)
    assert kill.targets == (1, 9, 17)
    assert gray.targets == (2,) and gray.delay_s == 0.05 \
        and gray.duration_s == 3.0
    assert ll.hold_s == 1.0
    assert stall.delay_s == 0.2 and stall.duration_s == 2.0
    assert nondet.at_s == 40.0
    # Round-trip: to_text() re-parses to the identical schedule.
    assert parse_schedule(sched.to_text()) == sched


def test_dsl_sorts_events_by_fire_time():
    sched = parse_schedule("at 30s nondet\nat 5s kill 1")
    assert [e.at_s for e in sched] == [5.0, 30.0]


@pytest.mark.parametrize("line", [
    "kill 1",                            # missing 'at <time>'
    "at 5s explode 1",                   # unknown kind
    "at 5s kill",                        # kill needs targets
    "at 5s kill a,b",                    # non-integer targets
    "at 5s gray 2,3 delay=50ms for 3s",  # gray takes exactly one
    "at 5s gray 2",                      # gray needs delay + for
    "at 5s stall delay=200ms",           # stall needs for
    "at 5s stall delay=200ms for",       # 'for' needs a duration
    "at 5m kill 1",                      # bad duration unit
    "at 5s kill 1 bogus=1",              # unexpected token
    "at 5s rescale",                     # rescale needs a target cut
    "at 5s rescale 0",                   # target must be >= 1 worker
    "at 5s rescale 2 4",                 # exactly one target
])
def test_dsl_rejects_malformed_events(line):
    with pytest.raises(ValueError):
        parse_schedule(line)


def test_dsl_rescale_parses_and_roundtrips():
    """`rescale N` is a first-class chaos kind: the target cut rides in
    targets, and to_text() reproduces the line byte-exactly."""
    text = "at 1.5s rescale 4"
    sched = parse_schedule(text)
    (ev,) = list(sched)
    assert ev.kind == "rescale" and ev.targets == (4,)
    assert sched.to_text() == text
    assert parse_schedule(sched.to_text()) == sched


def test_seeded_schedule_can_draw_rescales():
    sched = ChaosSchedule.seeded(7, 60.0, [0, 1], kinds=("rescale",),
                                 n_events=3)
    assert len(sched) == 3
    assert all(e.kind == "rescale" and e.targets[0] in (2, 4)
               for e in sched)
    assert parse_schedule(sched.to_text()) == sched
    assert ChaosSchedule.seeded(7, 60.0, [0, 1], kinds=("rescale",),
                                n_events=3) == sched


def test_seeded_schedule_is_replayable():
    """Same seed + same args -> the identical fault sequence; the whole
    point of the DSL split is that a soak that tripped the audit can be
    re-run bit for bit."""
    a = ChaosSchedule.seeded(5, 60.0, [1, 3, 5])
    b = ChaosSchedule.seeded(5, 60.0, [1, 3, 5])
    assert a == b and a.to_text() == b.to_text()
    # ... and a different seed gives a different sequence.
    c = ChaosSchedule.seeded(6, 60.0, [1, 3, 5])
    assert a != c


def test_seeded_schedule_covers_kinds_inside_the_paced_band():
    kinds = ("kill", "gray", "leader-loss", "stall", "nondet")
    sched = ChaosSchedule.seeded(11, 100.0, [1, 3, 5, 7], kinds=kinds,
                                 n_events=8, cascade=3)
    assert len(sched) == 8
    assert set(sched.kinds()) == set(kinds)     # every kind at least once
    for ev in sched:
        # warm-in and the final seal/audit window stay fault-free
        assert 20.0 <= ev.at_s <= 85.0
        if ev.kind == "kill":
            assert len(ev.targets) == 3
            assert len(set(ev.targets)) == 3    # distinct cascade
        if ev.kind == "gray":
            assert len(ev.targets) == 1
            assert ev.delay_s > 0 and ev.duration_s > 0
    assert parse_schedule(sched.to_text()) == sched


def test_seeded_schedule_rejects_unknown_kind_and_missing_targets():
    with pytest.raises(ValueError):
        ChaosSchedule.seeded(1, 60.0, [1], kinds=("explode",))
    with pytest.raises(ValueError):
        ChaosSchedule.seeded(1, 60.0, [], kinds=("kill",))


# --- SLO windows -------------------------------------------------------------


def test_quantile_empty_is_zero():
    assert quantile([], 0.99) == 0.0


def test_window_evaluate_breaches_each_bound():
    spec = SLOSpec(max_p99_ms=100.0, min_throughput=50.0,
                   max_recovery_ms=500.0)
    w = Window(0, 0.0, 2.0)
    for _ in range(95):
        w.observe(corrected_ms=10.0, actual_ms=10.0, records=1)
    for _ in range(5):
        w.observe(corrected_ms=900.0, actual_ms=900.0, records=1)
    w.recoveries_ms.append(800.0)
    breaches = w.evaluate(spec)
    # 100 records / 2s = 50/s is AT the floor (no breach); p99 and the
    # recovery both breach.
    assert len(breaches) == 2
    assert any("p99" in b for b in breaches)
    assert any("recovery" in b for b in breaches)
    assert w.stats()["breaches"] == breaches


def test_window_throughput_breach():
    spec = SLOSpec(min_throughput=100.0)
    w = Window(0, 0.0, 2.0)
    w.observe(corrected_ms=1.0, actual_ms=1.0, records=60)
    assert w.evaluate(spec) == ["throughput 30/s < 100/s"]


class _FakeTracer:
    def __init__(self):
        self.events = []

    def event(self, name, **kw):
        self.events.append((name, kw))


def test_slo_tracker_rolls_windows_on_the_soak_clock():
    tr = _FakeTracer()
    t = SLOTracker(SLOSpec(max_p99_ms=50.0), window_s=5.0, tracer=tr)
    t.observe(1.0, corrected_ms=10.0, actual_ms=10.0, records=8)
    t.observe(6.0, corrected_ms=500.0, actual_ms=20.0, records=8)
    t.observe_fault(6.5, "kill")
    t.observe_recovery(7.0, 321.0)
    windows = t.finish()
    assert [w.index for w in windows] == [0, 1]
    assert windows[0].breaches == []
    assert windows[1].breaches and "p99" in windows[1].breaches[0]
    assert windows[1].faults == ["kill"]
    assert windows[1].recoveries_ms == [321.0]
    # breach trace instant emitted at window close
    assert any(n == "soak.slo.breach" and kw["window"] == 1
               for n, kw in tr.events)
    assert t.breached_windows() == [windows[1]]
    assert t.worst_window() is windows[1]


# --- coordinated-omission correction (closed-loop bench) ---------------------


def test_corrected_closed_loop_charges_late_fences_only():
    """One fence runs 500ms late on a fixed 1ms/step schedule: every
    marker sample in that epoch (and the still-late next one) gets the
    queueing delay added; samples under on-time fences are untouched."""
    fences = [(100, 0.1), (200, 0.2), (300, 0.8), (400, 0.9)]
    samples = [(50, 1.0), (250, 2.0), (350, 3.0)]
    out = corrected_closed_loop(samples, fences, steps_per_epoch=100,
                                records_per_step=10, rate=10_000.0)
    assert out["max_queue_ms"] == pytest.approx(500.0)
    assert out["per_step_us"] == pytest.approx(1000.0)
    # sample 50 -> fence 100 (on time): stays 1.0ms; 250 -> fence 300:
    # 2.0 + 500; 350 -> fence 400: 3.0 + 500
    assert out["p99_ms"] == pytest.approx(
        quantile([1.0, 502.0, 503.0], 0.99))
    assert out["p50_ms"] == pytest.approx(502.0)


def test_corrected_closed_loop_derives_rate_from_fence_span():
    # 1ms/step derived from the (step, wall) span when rate is omitted;
    # evenly paced fences carry zero queueing delay.
    fences = [(0, 0.0), (100, 0.1), (200, 0.2)]
    out = corrected_closed_loop([(10, 7.0), (110, 9.0)], fences,
                                steps_per_epoch=100, records_per_step=10)
    assert out["per_step_us"] == pytest.approx(1000.0)
    assert out["max_queue_ms"] == pytest.approx(0.0)
    assert out["p99_ms"] == pytest.approx(quantile([7.0, 9.0], 0.99))


def test_corrected_closed_loop_empty_inputs():
    assert corrected_closed_loop([], [(0, 0.0), (8, 1.0)], 8, 4) == {
        "p50_ms": 0.0, "p99_ms": 0.0, "max_queue_ms": 0.0}
    assert corrected_closed_loop([(1, 2.0)], [(0, 0.0)], 8, 4)[
        "p99_ms"] == 0.0


# --- gray failure: degraded, never dead --------------------------------------


def test_heartbeat_monitor_gray_degrades_without_killing():
    from clonos_tpu.runtime.cluster import HeartbeatMonitor

    t = [0.0]
    mon = HeartbeatMonitor([0, 1, 2], timeout_s=5.0,
                           clock=lambda: t[0])
    mon.beat_all_except(set())
    assert mon.degraded(0.01) == []
    # inject a 0.5s heartbeat lag on subtask 1 (the chaos injector's
    # surface): its beats now ARRIVE half a second behind its peers'
    mon.lag[1] = 0.5
    t[0] = 1.0
    mon.beat_all_except(set())
    assert mon.degraded(0.01) == [1]
    assert mon.expired() == []          # degraded, NOT dead
    # paced-driver gap: all beats age identically while the driver
    # sleeps — relative lateness keeps the healthy workers out
    t[0] = 4.0
    assert mon.degraded(0.01) == [1]
    assert mon.expired() == []
    # past the death timeout the worker leaves degraded() for expired()
    t[0] = 7.0
    assert 1 not in mon.degraded(0.01)
    assert mon.expired() == [0, 1, 2]
    # revive clears the injected lag
    mon.revive(1)
    assert 1 not in mon.lag


def test_standby_pool_completion_is_monotonic():
    """Out-of-order async checkpoint completions must never regress the
    restore point behind the ring truncation the newer completion
    already performed."""
    from clonos_tpu.runtime.cluster import StandbyPool

    class _Ckpt:
        def __init__(self, cid):
            self.checkpoint_id = cid

    pool = StandbyPool()
    pool.on_completed_checkpoint(_Ckpt(5))
    pool.on_completed_checkpoint(_Ckpt(3))     # stale completion
    assert pool.latest.checkpoint_id == 5
    pool.on_completed_checkpoint(_Ckpt(7))
    assert pool.latest.checkpoint_id == 7


# --- metrics history: pacing under load + torn tail --------------------------


class _VirtualTime:
    """The sampling loop's clock and its stop event in one: time passes
    only where the loop waits (``wait``) or the sampler works
    (``spend``), and the loop is told to stop once ``until`` is
    reached. The loop then runs on the test's own thread, to its end."""

    def __init__(self, until):
        self.now, self.until = 0.0, until

    def mono(self):
        return self.now

    def spend(self, seconds):
        self.now += seconds

    def wait(self, timeout):            # threading.Event.wait
        self.now += timeout
        return self.now >= self.until

    def set(self):                      # threading.Event.set, by close()
        self.until = self.now


def _paced_history(vt, sample_s, interval_s=0.05):
    from clonos_tpu.obs.history import MetricsHistory

    def sample():
        vt.spend(sample_s)
        return {"x": 1}

    h = MetricsHistory(sample_fn=sample, interval_s=interval_s, window=64,
                       clock=vt.mono, mono=vt.mono)
    h._stop = vt
    h._loop()
    h.close()
    return h


def test_history_interval_holds_under_slow_sampler():
    """Absolute-deadline pacing: a sample_fn that takes a large slice
    of the interval must NOT stretch the period (the old wait-then-
    sample loop ran at interval + sample_time). On a virtual clock: the
    count is exact, and no real time passes."""
    h = _paced_history(_VirtualTime(until=0.53), sample_s=0.03)
    ts = [r["ts"] for r in h.query()]
    # drift pacing would deliver 6 samples in 0.53 s (a 0.08 s period);
    # deadline pacing 10, one per 0.05 s slot
    assert len(ts) == 10
    assert ts == pytest.approx([0.05 * (i + 1) + 0.03 for i in range(10)])
    assert h.missed_slots == 0


def test_history_counts_missed_slots_instead_of_bursting():
    h = _paced_history(_VirtualTime(until=0.5), sample_s=0.12)
    # every 0.12 s sample overruns two 0.05 s slots: re-anchored on the
    # next slot, so one sample per 0.15 s
    ts = [r["ts"] for r in h.query()]
    assert h.missed_slots == 2 * len(ts) and len(ts) == 3
    # no catch-up burst: consecutive samples stay >= one sample time
    assert all(b - a >= 0.12 for a, b in zip(ts, ts[1:]))


def test_history_file_torn_tail_readable_mid_run(tmp_path):
    """The JSONL file stays readable WHILE the sampler appends, and a
    SIGKILL-torn final line is tolerated on resume."""
    from clonos_tpu.obs.history import MetricsHistory, read_history_file

    path = str(tmp_path / "hist.jsonl")
    h = MetricsHistory(sample_fn=lambda: {"ok": 1}, path=path,
                       interval_s=0.02, window=64)
    h.start()
    deadline = time.monotonic() + 0.4
    reads = 0
    while time.monotonic() < deadline:
        recs = read_history_file(path)      # concurrent with appends
        for r in recs:
            assert "ts" in r
        reads += 1
    h.close()
    assert reads > 0 and len(read_history_file(path)) > 0
    # SIGKILL artifact: torn final append
    with open(path, "a") as f:
        f.write('{"ts": 1, "metr')
    recs = read_history_file(path)
    assert all("metrics" in r for r in recs)


# --- top: soak status row ----------------------------------------------------


def test_top_table_renders_soak_row():
    from clonos_tpu.cli import _top_table

    snap = {"soak.target-rate": 2000.0, "soak.rate": 1874.2,
            "soak.faults-injected": 4, "soak.audit-ok": 1,
            "worker.w0.slots": 2}
    table = _top_table(snap)
    soak_lines = [ln for ln in table.splitlines()
                  if ln.startswith("soak:")]
    assert len(soak_lines) == 1
    assert "audit-ok=1" in soak_lines[0]
    assert "target-rate=2000.0" in soak_lines[0]
    # suffix match: worker-prefixed gauges feed the same row
    table2 = _top_table({"worker.w1.soak.rate": 9.0})
    assert any(ln.startswith("soak: rate=9.0")
               for ln in table2.splitlines())
    # absent gauges, absent row
    assert "soak:" not in _top_table({"worker.w0.slots": 1})


# --- runner surfaces the driver depends on -----------------------------------


def _small_job(name):
    from clonos_tpu.api.environment import StreamEnvironment
    env = StreamEnvironment(name=name, num_key_groups=8)
    (env.synthetic_source(vocab=11, batch_size=4, parallelism=2)
        .key_by()
        .window_count(num_keys=11, window_size=1 << 30)
        .sink())
    return env.build()


def test_latency_markers_keep_raw_samples(tmp_path):
    """The histogram forgets WHEN a sample happened; the raw (step,
    latency) series behind it is what coordinated-omission correction
    re-attributes queueing delay from."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    r = ClusterRunner(_small_job("lat"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      latency_marker_every=2)
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)
    samples = r.latency.samples
    assert samples and len(samples) == r.latency.hist.count
    steps = [s for s, _ in samples]
    assert steps == sorted(steps)
    assert all(isinstance(ms, float) for _, ms in samples)
    # bounded: the series trims from the front, keeping the newest
    r.latency.max_samples = 4
    r.run_epoch(complete_checkpoint=False)
    assert len(r.latency.samples) <= 4
    assert r.latency.samples[-1][0] == max(steps + [
        s for s, _ in r.latency.samples])


def test_discard_pending_through_abandons_skipped_fences(tmp_path):
    """complete_every>1 leaves skipped fences' checkpoints pending
    forever; a completing fence must be able to abandon them WITHOUT
    firing completion listeners (completing old checkpoints late would
    regress the standby restore point — see the monotonic test above)."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    r = ClusterRunner(_small_job("dp"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=64, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"))
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)
    r.run_epoch(complete_checkpoint=False)
    r.run_epoch(complete_checkpoint=True)
    co = r.coordinator
    latest_before = r.standbys.latest.checkpoint_id
    pending = sorted(co._pending)
    assert pending, "expected skipped fences to leave pendings"
    discarded = co.discard_pending_through(max(pending))
    assert discarded == pending
    assert not co._pending
    # quiet abandon: no completion fired, restore point unchanged
    assert r.standbys.latest.checkpoint_id == latest_before
    assert co.discard_pending_through(10**6) == []


# --- the real driver (slow) --------------------------------------------------


def _fixture(tmp_path, duration_s, rate=1200.0):
    from clonos_tpu.soak import build_soak_fixture
    return build_soak_fixture(str(tmp_path), rate=rate,
                              duration_s=duration_s,
                              steps_per_epoch=32, seed=11)


@pytest.mark.slow
def test_soak_smoke_kill_and_gray_hold_slo_and_audit(tmp_path):
    """~20s smoke: a paced run takes one kill + one gray failure and
    must come out with every SLO window evaluated on corrected latency,
    both faults survived, and the audit ledger byte-identical to the
    fault-free control chain (exactly_once: true). The kill exercises
    the OVERLAPPED recovery tail end-to-end: its window is held to a
    per-window max_recovery_ms budget, the finalize.overlap-saved
    attribution is recorded per kill, and the immediate post-kill
    ledger re-diff vs the control twin stays empty. (The 150 ms device
    budget is asserted by bench.py at bench shapes; the CPU-CI bound
    here guards the SLO plumbing, not device latency.)"""
    from clonos_tpu.soak import SLOSpec, SoakConfig, SoakDriver

    runner, control, election = _fixture(tmp_path, duration_s=5.0)
    schedule = parse_schedule(
        "at 1.2s kill 1,3\nat 2.2s gray 3 delay=30ms for 1.5s")
    driver = SoakDriver(
        runner, SoakConfig(rate=1200.0, duration_s=5.0, window_s=2.0,
                           chunk_steps=8),
        schedule=schedule,
        spec=SLOSpec(exactly_once=True, max_recovery_ms=30000.0),
        control=control, election=election, records_per_step=16)
    v = driver.run()

    assert v["pass"] is True
    assert v["audit"]["exactly_once"] is True
    assert v["audit"]["divergences"] == []
    assert v["audit"]["epochs_checked"] > 0
    assert v["faults"]["injected"] == 2
    assert v["faults"]["survived"] == 2
    assert v["faults"]["by_kind"] == {"gray": 1, "kill": 1}
    assert v["faults"]["recoveries_ms"]          # the kill's recovery
    assert v["slo"]["max_recovery_ms"] == 30000.0
    # overlapped-recovery acceptance under chaos kill
    assert len(v["faults"]["kill_overlap_saved_ms"]) == 1
    assert v["faults"]["kill_overlap_saved_ms"][0] >= 0.0
    assert v["faults"]["kill_rediff_problems"] == 0
    assert v["windows"] and all(
        "p99_ms" in w and "p50_ms" in w for w in v["windows"])
    assert "corrected" in v["latency"]["basis"]
    assert v["events_fired"] == 2
    # the soak.* gauges top renders are live in the registry
    snap = runner.metrics.snapshot()
    assert snap["soak.faults-injected"] == 2
    assert snap["soak.audit-ok"] == 1
    assert snap["soak.target-rate"] == 1200.0


@pytest.mark.slow
def test_soak_injected_nondet_fails_the_run(tmp_path):
    """Audit bait: an unlogged value perturbation survives every
    structural check and MUST be caught by the post-event ledger diff —
    the run fails even though nothing crashed and no SLO breached."""
    from clonos_tpu.soak import SLOSpec, SoakConfig, SoakDriver

    runner, control, election = _fixture(tmp_path, duration_s=4.0)
    driver = SoakDriver(
        runner, SoakConfig(rate=1200.0, duration_s=4.0, window_s=2.0),
        schedule=parse_schedule("at 1.5s nondet"),
        spec=SLOSpec(exactly_once=True),
        control=control, election=election, records_per_step=16)
    v = driver.run()

    assert v["pass"] is False
    assert v["audit"]["exactly_once"] is False
    assert v["audit"]["divergences"]
    assert any("ring" in d for d in v["audit"]["divergences"])
    assert runner.metrics.snapshot()["soak.audit-ok"] == 0


@pytest.mark.slow
def test_soak_mid_run_rescale_holds_exactly_once(tmp_path):
    """Elastic repartition under live soak traffic: a `rescale 4` event
    re-cuts the running 2-wide job to 4 keyed workers at a completing
    fence. The control twin is re-cut identically, so the byte-exact
    ledger diff must stay empty across the handoff — no record lost or
    duplicated — and the driver must keep pacing the NEW incarnation."""
    from clonos_tpu.soak import SLOSpec, SoakConfig, SoakDriver

    runner, control, election = _fixture(tmp_path, duration_s=4.0,
                                         rate=4000.0)
    driver = SoakDriver(
        runner, SoakConfig(rate=4000.0, duration_s=4.0, window_s=1.0,
                           chunk_steps=8, complete_every=2),
        schedule=parse_schedule("at 1.2s rescale 4"),
        spec=SLOSpec(exactly_once=True),
        control=control, election=election, records_per_step=16)
    v = driver.run()

    assert v["pass"] is True
    assert v["audit"]["exactly_once"] is True
    assert v["audit"]["divergences"] == []
    assert v["audit"]["epochs_checked"] > 0
    assert v["faults"]["rescales"] == 1
    (stats,) = v["faults"]["rescale_stats"]
    assert stats["target"] == 4
    assert stats["drained_records"] >= 0
    assert sum(stats["moved_key_groups"].values()) > 0
    assert stats["fence_stall_ms"] >= 0.0
    # the driver really swapped to the re-cut incarnation
    assert driver.runner is not runner
    assert any(vx.parallelism == 4 for vx in driver.runner.job.vertices)
    assert driver.runner.metrics.snapshot()["soak.rescales"] == 1


@pytest.mark.slow
def test_soak_cli_report_json_exit_codes(tmp_path):
    """CI contract: ``clonos_tpu soak --report json`` prints one JSON
    line and exits 0 on a clean run, 1 when the audit catches an
    injected nondeterminism."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    base = [sys.executable, "-m", "clonos_tpu", "soak",
            "--rate", "1200", "--duration", "4", "--window", "2",
            "--steps-per-epoch", "32", "--report", "json"]

    ok = subprocess.run(
        base + ["--schedule", "at 1.2s kill 1,3",
                "--workdir", str(tmp_path / "ok"),
                "--out", str(tmp_path / "ok.json")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    assert ok.returncode == 0, ok.stderr[-2000:]
    line = json.loads(ok.stdout.strip().splitlines()[-1])
    assert line["pass"] is True and line["exactly_once"] is True
    # durable artifact with the full verdict
    art = json.load(open(tmp_path / "ok.json"))
    assert art["metric"] == "soak_slo_verdict" and art["windows"]

    bad = subprocess.run(
        base + ["--schedule", "at 1.5s nondet",
                "--workdir", str(tmp_path / "bad"),
                "--out", str(tmp_path / "bad.json")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)
    assert bad.returncode == 1, bad.stderr[-2000:]
    line = json.loads(bad.stdout.strip().splitlines()[-1])
    assert line["pass"] is False and line["divergences"] >= 1
