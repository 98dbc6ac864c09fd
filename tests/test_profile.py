"""Overhead attribution profiler, metrics history, ledger compaction,
and the ``clonos_tpu top`` cluster view (obs/profile.py, obs/history.py,
cli.py).

The paper's headline overhead claim (causal logging costs a few percent
of steady-state throughput) is measured here as a first-class runtime
metric: section timers attribute each superstep's wall between user
compute and fault-tolerance machinery, rolled up per epoch into
``overhead.ft-fraction``. All of it is opt-in — the default NullProfiler
must add nothing to the hot path, like NullTracer and NullAuditor.
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from clonos_tpu import obs
from clonos_tpu.obs import profile as prof_mod
from clonos_tpu.utils import metrics as met

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _null_obs_after():
    """Every test leaves the process-global tracer/auditor/profiler
    off."""
    yield
    obs.reset()
    obs.reset_audit()
    obs.reset_profile()


def _small_job(name):
    from clonos_tpu.api.environment import StreamEnvironment
    env = StreamEnvironment(name=name, num_key_groups=8)
    (env.synthetic_source(vocab=11, batch_size=4, parallelism=2)
        .key_by()
        .window_count(num_keys=11, window_size=1 << 30)
        .sink())
    return env.build()


# --- profiler unit behavior --------------------------------------------------


def test_null_profiler_default_zero_overhead():
    """Default process profiler is the Null one: sections are a shared
    no-op context manager, ``fence`` passes values through untouched
    (no device sync), and every aggregate reads as zero."""
    p = obs.get_profiler()
    assert isinstance(p, obs.NullProfiler) and not p.enabled
    assert p.section("roll") is p.section("truncate"), \
        "null sections are one shared object — no per-call allocation"
    with p.section("anything"):
        pass
    sentinel = object()
    assert p.fence(sentinel) is sentinel
    p.observe("roll", 1.0)
    assert p.rollup() == 0.0 and p.ft_fraction() == 0.0
    assert p.lifetime() == {} and p.lifetime_ft_fraction() == 0.0


def test_profiler_attribution_rollup_and_binding():
    """FT fraction = ft seconds / total attributed seconds per rollup
    window; histograms and the gauge land in the bound metric group."""
    t = [0.0]
    p = prof_mod.Profiler(clock=lambda: t[0], fence_device=False)
    reg = met.MetricRegistry()
    g = reg.group("job.t")
    p.bind(g)

    with p.section("compute", kind=prof_mod.COMPUTE):
        t[0] += 3.0
    with p.section("roll"):
        t[0] += 0.5
    with p.section("digest-seal"):
        t[0] += 0.5
    assert p.rollup() == pytest.approx(0.25)
    assert p.ft_fraction() == pytest.approx(0.25)

    # Second window: only FT work -> fraction 1.0; empty windows keep
    # the last real fraction instead of snapping the gauge to zero.
    with p.section("truncate"):
        t[0] += 1.0
    assert p.rollup() == pytest.approx(1.0)
    assert p.rollup() == pytest.approx(1.0), "empty window keeps last"

    snap = reg.snapshot()
    assert snap["job.t.overhead.ft-fraction"] == pytest.approx(1.0)
    assert snap["job.t.overhead.roll-ms"]["count"] == 1
    assert snap["job.t.overhead.roll-ms"]["mean"] == pytest.approx(500.0)
    assert snap["job.t.overhead.compute-ms"]["count"] == 1
    # Lifetime spans both windows: 2s FT of 5s total.
    assert p.lifetime_ft_fraction() == pytest.approx(0.4)
    assert p.lifetime()["compute"] == pytest.approx(3.0)


def test_profiled_run_exposes_ft_fraction(tmp_path):
    """A profiled runner attributes real epochs: the per-epoch rollup
    lands in the registry as ``overhead.ft-fraction`` with the
    per-section histograms beside it."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    obs.configure_profile()
    r = ClusterRunner(_small_job("prof"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"), audit=True)
    assert r.profiler.enabled
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)
    snap = r.metrics.snapshot()
    frac = snap["job.prof.overhead.ft-fraction"]
    assert 0.0 < frac < 1.0, \
        "an epoch has both compute and FT sections attributed"
    assert snap["job.prof.overhead.compute-ms"]["count"] == 2
    assert snap["job.prof.overhead.roll-ms"]["count"] == 2
    assert snap["job.prof.overhead.snapshot-ms"]["count"] >= 1
    assert snap["job.prof.overhead.digest-seal-ms"]["count"] >= 1
    life = r.profiler.lifetime()
    assert life["compute"] > 0 and life["roll"] > 0


def test_disabled_run_adds_no_overhead_keys(tmp_path):
    """Profiling off (the default): no overhead.* metric exists —
    the instrumented call sites register nothing."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    r = ClusterRunner(_small_job("noprof"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"))
    assert not r.profiler.enabled
    r.run_epoch(complete_checkpoint=True)
    snap = r.metrics.snapshot()
    assert not [k for k in snap if ".overhead." in k]


def test_profile_config_option_enables_via_from_config(tmp_path):
    """``observability.profile.enabled`` is the config-file gate."""
    from clonos_tpu.config.options import Configuration
    from clonos_tpu.runtime.cluster import ClusterRunner

    cfg = Configuration()
    cfg.set_raw("observability.profile.enabled", True)
    r = ClusterRunner.from_config(_small_job("cfgprof"), cfg,
                                  steps_per_epoch=8, log_capacity=512,
                                  max_epochs=8, inflight_ring_steps=32,
                                  seed=3)
    assert r.profiler.enabled


def test_profile_context_rides_deploy_headers():
    """DEPLOY-header convention like trace/audit: a profiling JobMaster
    stamps ``profile`` so deployed runners inherit; disabled adds no
    wire fields at all."""
    from clonos_tpu.parallel import transport as tp

    h = tp.attach_profile({})
    assert h == {}, "disabled profiler leaves wire bytes identical"
    tp.adopt_profile(h)
    assert not obs.get_profiler().enabled

    obs.configure_profile()
    h = tp.attach_profile({})
    assert h == {"profile": True}
    obs.reset_profile()
    tp.adopt_profile(h)
    assert obs.get_profiler().enabled


# --- finalize attribution ----------------------------------------------------


def test_recover_finalize_subspans_partition_finalize(tmp_path):
    """The finalize mystery, attributable: ``recover()`` splits its
    finalize phase into named sub-spans that are in ``phase_ms`` AND
    account for the recorded finalize total, each emitted as a span
    under the recovery's trace id. Sub-spans keep their true wall
    durations and what ran under other work is surfaced as
    ``finalize.overlap-saved`` — so the identity is
    sum(sub-spans) - overlap-saved == finalize (overlap is attributed,
    never hidden), exact because both sides come from the sub-spans'
    own stamps."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    tr = obs.configure("runner")
    r = ClusterRunner(_small_job("fin"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"))
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)
    r.inject_failure([2 + 1])
    report = r.recover()
    pm = report.phase_ms
    assert "finalize" in pm
    subs = {k: v for k, v in pm.items() if k.startswith("finalize.")}
    saved = subs.pop("finalize.overlap-saved")
    assert set(subs) == {"finalize.barrier-dispatch",
                        "finalize.barrier-read",
                        "finalize.state-verify"}
    assert saved >= 0.0
    assert sum(subs.values()) - saved == pytest.approx(
        pm["finalize"], rel=0, abs=1e-6)
    recs = tr.records()
    recovery = next(x for x in recs if x["name"] == "recovery")
    for name in ("recovery.finalize.barrier-read",
                 "recovery.finalize.state-verify"):
        span = next(x for x in recs if x["name"] == name)
        assert span["trace"] == recovery["trace"]


# --- ledger compaction -------------------------------------------------------


def test_compact_ledger_entries_last_wins_below_fence():
    from clonos_tpu.runtime.checkpoint import compact_ledger_entries

    e = lambda ep, tag: {"epoch": ep, "combined": tag}
    entries = [e(0, "a"), e(1, "b"), e(0, "a2"),       # re-sealed epoch 0
               e(2, "c"), {"weird": True}, e(1, "b2"), e(2, "c2")]
    out = compact_ledger_entries(entries, below_epoch=2)
    # Below the fence: one per epoch, last wins, epoch order. At/above
    # (and unparseable): verbatim in append order, after them.
    assert out == [e(0, "a2"), e(1, "b2"),
                   e(2, "c"), {"weird": True}, e(2, "c2")]
    assert compact_ledger_entries(entries, below_epoch=0) == entries


def test_checkpoint_completion_compacts_ledger(tmp_path):
    """Completion-driven compaction keeps a long run's ledger bounded:
    duplicates below the completed fence collapse to one line per
    epoch in ledger.jsonl, resolved last-wins like the readers do."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    r = ClusterRunner(_small_job("cmp"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"), audit=True)
    r.run_epoch(complete_checkpoint=True)     # seals + completes epoch 0
    # A rebuilt runner re-seals replayed epochs: simulate the duplicate
    # appends a few recoveries would leave behind.
    dup = dict(r.coordinator.read_ledger()[0])
    dup["combined"] = "resealed-last"
    for _ in range(3):
        r.coordinator.storage.write_ledger(dup)
    assert len(r.coordinator.read_ledger()) == 4
    r.run_epoch(complete_checkpoint=True)     # fence moves past epoch 0
    entries = r.coordinator.read_ledger()
    by_epoch = [e["epoch"] for e in entries]
    assert by_epoch.count(0) == 1, "duplicates below the fence collapse"
    assert next(e for e in entries
                if e["epoch"] == 0)["combined"] == "resealed-last"
    # The file itself shrank, not just the parsed view.
    lines = open(str(tmp_path / "ck" / "ledger.jsonl")).read().splitlines()
    assert len(lines) == len(entries)


# --- metrics history ---------------------------------------------------------


def test_metrics_history_ring_torn_tail_and_resume(tmp_path):
    """History samples ring-buffer in memory and append to a JSONL a
    torn final line cannot corrupt; a restarted history resumes from
    the file tail; the file compacts once it outgrows 2*window."""
    path = str(tmp_path / "history.jsonl")
    t = [100.0]
    h = obs.MetricsHistory(sample_fn=lambda: {"x": t[0]}, path=path,
                           interval_s=60.0, window=4,
                           clock=lambda: t[0])
    for _ in range(6):                   # > window: ring drops oldest
        h.sample_once()
        t[0] += 1.0
    assert [r["ts"] for r in h.query()] == [102.0, 103.0, 104.0, 105.0]
    assert [r["ts"] for r in h.query(since=104.0)] == [104.0, 105.0]
    assert [r["ts"] for r in h.query(last=2)] == [104.0, 105.0]
    h.close()

    with open(path, "a") as f:           # SIGKILL artifact
        f.write('{"ts": 999, "metr')
    assert obs.read_history_file(path)[-1]["ts"] == 105.0
    h2 = obs.MetricsHistory(sample_fn=lambda: {}, path=path,
                            interval_s=60.0, window=4,
                            clock=lambda: t[0])
    assert [r["ts"] for r in h2.query()] == [102.0, 103.0, 104.0, 105.0]
    # Push past 2*window file lines: compaction rewrites to ring size.
    for _ in range(6):
        h2.sample_once()
        t[0] += 1.0
    h2.close()
    assert len(open(path).read().splitlines()) <= 2 * 4
    recs = obs.read_history_file(path)
    ts = [r["ts"] for r in recs]
    assert ts == sorted(ts) and ts[-1] == 111.0


def test_history_endpoint_serves_samples_under_concurrent_scrapes():
    """Endpoint integration: /metrics/history.json grows while /metrics
    is scraped concurrently; exposition keeps # HELP/# TYPE; history
    timestamps are monotone and ?last= windows the payload."""
    reg = met.MetricRegistry()
    reg.group("job.t").counter("things").inc(5)
    hist = obs.MetricsHistory(interval_s=0.05, window=64)
    ep = met.MetricsEndpoint(reg, history=hist)
    host, port = ep.address
    base = f"http://{host}:{port}"
    errors = []

    def scrape_loop():
        try:
            for _ in range(20):
                txt = urllib.request.urlopen(base + "/metrics").read()
                assert b"# HELP" in txt and b"# TYPE" in txt
                assert b"job_t_things 5" in txt
        except Exception as e:           # surfaced on the main thread
            errors.append(e)

    scraper = threading.Thread(target=scrape_loop)
    scraper.start()
    try:
        deadline = time.monotonic() + 20
        samples = []
        while len(samples) < 2:
            assert time.monotonic() < deadline, "sampler never produced"
            js = json.loads(urllib.request.urlopen(
                base + "/metrics/history.json").read())
            samples = js["samples"]
            time.sleep(0.02)
        ts = [s["ts"] for s in samples]
        assert ts == sorted(ts), "ring order means monotone timestamps"
        assert all(s["metrics"]["job.t.things"] == 5 for s in samples)
        js = json.loads(urllib.request.urlopen(
            base + "/metrics/history.json?last=1").read())
        assert len(js["samples"]) == 1
        assert js["samples"][0]["ts"] == max(ts) or \
            js["samples"][0]["ts"] > max(ts)     # sampler kept running
    finally:
        scraper.join()
        ep.close()
    assert not errors
    assert not hist.started or hist._thread is None, \
        "endpoint owns the history it started: close() stopped it"


# --- audit --report json (CI convention) -------------------------------------


def test_audit_report_json_exit_codes(tmp_path, capsys):
    from clonos_tpu.cli import main
    from clonos_tpu.obs.digest import EpochDigest

    def write_ledger(dirpath, entries):
        os.makedirs(dirpath, exist_ok=True)
        with open(os.path.join(dirpath, "ledger.jsonl"), "w") as f:
            for e in entries:
                f.write(json.dumps(e) + "\n")

    def entry(epoch, payload):
        d = EpochDigest(epoch)
        d.fold("ring/v2", payload, 4)
        return d.to_entry()

    run1 = tmp_path / "run1"
    run2 = tmp_path / "run2"
    write_ledger(str(run1 / "g0"), [entry(0, b"aa"), entry(1, b"bb")])
    write_ledger(str(run2 / "g0"), [entry(0, b"aa"), entry(1, b"XX")])

    assert main(["audit", str(run1), "--diff", str(run1),
                 "--report", "json"]) == 0
    js = json.loads(capsys.readouterr().out)
    assert js["match"] is True and js["problems"] == []
    assert js["groups"]["g0/ledger.jsonl"]["entries"] == 2

    assert main(["audit", str(run1), "--diff", str(run2),
                 "--report", "json"]) == 1
    js = json.loads(capsys.readouterr().out)
    assert js["match"] is False
    assert any("epoch 1" in p for p in js["problems"])
    assert js["groups"]["g0/ledger.jsonl"]["problems"]

    assert main(["audit", str(run1), "--report", "json"]) == 0
    js = json.loads(capsys.readouterr().out)
    assert js["match"] is True and js["groups"]

    assert main(["audit", str(tmp_path / "absent"),
                 "--report", "json"]) == 1
    assert json.loads(capsys.readouterr().out)["match"] is False


# --- clonos_tpu top ----------------------------------------------------------


_TOP_SNAP = {
    "worker.w-0.slots": 2,
    "worker.w-0.group.g0.job.b.audit.epochs-sealed": 5,
    "worker.w-0.group.g0.job.b.audit.epochs-validated": 3,
    "worker.w-0.group.g0.job.b.backpressure.inflight-occupancy": 0.25,
    "worker.w-0.group.g0.job.b.causal-log.max-occupancy": 0.5,
    "worker.w-0.group.g0.job.b.recovery.replay-lag-steps": 7,
    "worker.w-0.group.g0.job.b.overhead.ft-fraction": 0.031,
    "worker.w-0.group.g0.job.b.recovery.finalize-ms":
        {"count": 2, "mean": 450.0, "p50": 448.0, "p99": 460.0},
    "worker.w-1.slots": 1,
    "worker.w-1.group.g1.job.b.audit.epochs-sealed": 4,
    "cluster.audit.exactly-once-ok": 1,
    "cluster.overhead.ft-fraction-max": 0.031,
}


def test_top_table_parses_cluster_snapshot():
    from clonos_tpu.cli import _top_rows, _top_table

    rows = _top_rows(_TOP_SNAP)
    assert set(rows) == {"w-0", "w-1"}
    r0 = rows["w-0"]
    assert r0["slots"] == 2 and r0["sealed"] == 5 and \
        r0["validated"] == 3
    assert r0["ring"] == 0.5, "max over ring occupancy gauges"
    assert r0["lag"] == 7 and r0["ft"] == 0.031
    assert r0["phases"] == {"finalize": 448.0}
    assert rows["w-1"]["slots"] == 1 and rows["w-1"]["ft"] is None

    table = _top_table(_TOP_SNAP)
    lines = table.splitlines()
    assert lines[0].split()[:4] == ["WORKER", "SLOTS", "GROUPS",
                                    "SEALED"]
    w0 = next(l for l in lines if l.startswith("w-0"))
    cols = w0.split()
    assert cols[1] == "2" and cols[3] == "5" and cols[7] == "3.10"
    assert "finalize=448" in w0
    assert next(l for l in lines if l.startswith("w-1")).split()[1] == "1"
    assert "ft-fraction-max=0.031" in table


@pytest.mark.slow
def test_top_once_against_live_endpoint(capsys):
    """Smoke: ``clonos_tpu top --once`` renders every worker row from a
    live MetricsEndpoint serving a cluster snapshot."""
    from clonos_tpu import cli

    reg = met.MetricRegistry()
    ep = met.MetricsEndpoint(reg, extra=lambda: dict(_TOP_SNAP))
    try:
        host, port = ep.address
        rc = cli.main(["top", f"{host}:{port}", "--once"])
    finally:
        ep.close()
    assert rc == 0
    out = capsys.readouterr().out
    lines = [l for l in out.splitlines() if l.strip()]
    assert lines[0].startswith("WORKER")
    for eid in ("w-0", "w-1"):
        assert any(l.startswith(eid) for l in lines), \
            f"every worker gets a row ({eid})"
    assert "cluster:" in out
