"""Observability: distributed tracing + the recovery flight recorder
(clonos_tpu/obs; reference MetricRegistryImpl scopes + the ad-hoc log
lines around RecoveryManager.java state transitions, here turned into
spans that follow one job across worker OS processes).

The headline test re-drives the slot-pool SIGKILL scenario
(tests/test_scheduler.py) with tracing enabled: the JobMaster's and
both workers' trace files must reconstruct the full recovery timeline —
failure detect -> redeploy -> determinant fetch -> rebuild -> replay ->
caught up — under ONE trace id carried over the control wire, with
per-phase durations in the registries and the worker metrics
piggybacked on HEARTBEAT into the JobMaster's cluster-wide view, and
the merged files must convert to valid Chrome trace JSON.
"""

import collections
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from clonos_tpu import obs
from clonos_tpu.parallel import transport as tp
from clonos_tpu.utils import metrics as met

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _null_tracer_after():
    """Every test leaves the process-global tracer the local recorder."""
    yield
    obs.reset()


# --- tracer core -------------------------------------------------------------


def test_tracer_spans_nest_backdate_and_persist(tmp_path):
    t = [100.0]
    path = str(tmp_path / "t.jsonl")
    tr = obs.Tracer("svc", path=path, clock=lambda: t[0])
    with tr.span("outer", epoch=3) as outer:
        t[0] += 1.0
        with tr.span("inner") as inner:
            t[0] += 0.5
        tr.event("mark", k=7)
    t[0] += 2.0
    tr.complete("measured", 2.0, phase="replay")
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")

    recs = tr.records()
    assert [r["name"] for r in recs] == ["inner", "mark", "outer",
                                        "measured", "boom"]
    by = {r["name"]: r for r in recs}
    # Parent nesting: inner span and the instant event sit under outer.
    assert by["inner"]["parent"] == outer.span_id
    assert by["inner"]["span"] == inner.span_id
    assert by["mark"]["parent"] == outer.span_id
    assert by["outer"]["parent"] is None
    # Complete spans carry ts + dur; the event is an instant.
    assert by["outer"]["ph"] == "X"
    assert by["outer"]["ts"] == 100.0
    assert by["outer"]["dur"] == pytest.approx(1.5)
    assert by["inner"]["ts"] == 101.0
    assert by["inner"]["dur"] == pytest.approx(0.5)
    assert by["mark"]["ph"] == "i" and by["mark"]["args"] == {"k": 7}
    # complete() back-dates ts so the timeline lays out correctly.
    assert by["measured"]["ts"] == pytest.approx(101.5)
    assert by["measured"]["dur"] == pytest.approx(2.0)
    # A span that raises still closes, recording the error.
    assert "ValueError" in by["boom"]["args"]["error"]
    # Every record is tagged with the one trace id + emitting service.
    assert {r["trace"] for r in recs} == {tr.trace_id}
    assert {r["service"] for r in recs} == {"svc"}
    # Flushed per record: the file is complete BEFORE close (SIGKILL
    # loses at most the record being written).
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["name"] for ln in lines] == [r["name"] for r in recs]
    tr.close()

    # The flight-recorder ring is bounded: only the most recent survive.
    small = obs.Tracer("s2", clock=lambda: t[0], buffer=4)
    for i in range(9):
        small.event(f"e{i}")
    assert [r["name"] for r in small.records()] == ["e5", "e6", "e7", "e8"]


def test_wire_context_propagation_and_local_recorder_adds_nothing(tmp_path):
    # Default: the local flight recorder. It records, but attach_trace
    # adds NO wire field, no file is opened, and ``enabled`` is False so
    # call sites that gate cross-process work on it skip that work.
    tr0 = obs.get_tracer()
    assert isinstance(tr0, obs.Tracer) and not tr0.enabled
    assert tr0._path is None and tr0._file is None
    hdr = tp.attach_trace({"group": 1})
    assert hdr == {"group": 1}, "local recorder must add no wire fields"
    before = tr0.trace_id
    tp.adopt_trace({"group": 1, "trace": {"trace_id": "deadbeef"}})  # no-op
    assert tr0.trace_id == before
    with tr0.span("x") as s:
        assert s.span_id is not None
    tr0.event("y")
    tr0.complete("z", 1.0)
    assert [r["name"] for r in tr0.records()] == ["x", "y", "z"]
    assert tr0.wire_context() is None and tr0._file is None
    assert os.listdir(tmp_path) == []

    # The NullTracer stays for callers that pass one in.
    null = obs.NullTracer()
    with null.span("x") as s:
        assert s.span_id is None
    null.event("y")
    null.complete("z", 1.0)
    null.count("c", 3)
    assert null.records() == [] and null.wire_context() is None
    assert null.counters() == {}

    # Opt-in: the sender's header carries {trace_id, span}; the
    # receiving process adopts it and lands under the SAME trace id.
    jm = obs.configure("jm")
    with jm.span("deploy", group=1) as sp:
        hdr = tp.attach_trace({"group": 1})
    assert hdr["trace"] == {"trace_id": jm.trace_id, "span": sp.span_id}

    worker = obs.Tracer("worker-a")
    assert worker.trace_id != jm.trace_id
    worker.adopt(hdr["trace"])
    worker.event("recovery.caught_up", group=1)
    assert worker.records()[0]["trace"] == jm.trace_id
    worker.adopt(None)                      # idempotent / null-safe
    assert worker.trace_id == jm.trace_id

    # adopt_trace routes a received header into the process tracer.
    tp.adopt_trace({"trace": {"trace_id": "feedc0de00000000"}})
    assert jm.trace_id == "feedc0de00000000"
    obs.reset()
    assert not obs.get_tracer().enabled


# --- Chrome conversion + the standalone converter ----------------------------


def test_chrome_conversion_validation_and_converter_tool(tmp_path):
    t = [50.0]
    jm_path = str(tmp_path / "trace-jm.jsonl")
    jm = obs.Tracer("jm", path=jm_path, clock=lambda: t[0])
    jm.event("recovery.detect", worker="b")
    with jm.span("recovery.redeploy", worker="b"):
        t[0] += 0.25
    jm.close()
    # A worker file of the same trace (context carried over the wire).
    wk_path = str(tmp_path / "trace-a.jsonl")
    wk = obs.Tracer("a", path=wk_path, trace_id=jm.trace_id,
                    clock=lambda: t[0])
    wk.complete("recovery.replay", 0.1)
    wk.close()

    records = obs.load_jsonl([jm_path, wk_path])
    assert len(records) == 3
    assert records == sorted(records, key=lambda r: r["ts"])
    doc = obs.to_chrome(records)
    n = obs.validate_chrome(doc)
    evs = doc["traceEvents"]
    assert n == len(evs)
    # process_name metadata labels each (pid, service) lane.
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"jm", "a"}
    # Seconds -> microseconds; instants carry process scope.
    redeploy = next(e for e in evs
                    if e["ph"] == "X" and e["name"] == "recovery.redeploy")
    assert redeploy["dur"] == pytest.approx(0.25 * 1e6)
    assert all(e["s"] == "p" for e in evs if e["ph"] == "i")
    # Span ids survive the conversion (stashed in args).
    assert redeploy["args"]["trace"] == jm.trace_id

    # trace_id filtering drops foreign records.
    other = obs.Tracer("x")
    other.event("noise")
    only = obs.to_chrome(records + other.records(), trace_id=jm.trace_id)
    assert all(e["ph"] == "M" or e["args"]["trace"] == jm.trace_id
               for e in only["traceEvents"])

    # Malformed docs are rejected loudly.
    with pytest.raises(ValueError, match="traceEvents"):
        obs.validate_chrome({})
    with pytest.raises(ValueError, match="unknown ph"):
        obs.validate_chrome({"traceEvents": [
            {"ph": "Z", "name": "x", "ts": 0, "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError, match="dur"):
        obs.validate_chrome({"traceEvents": [
            {"ph": "X", "name": "x", "ts": 0, "pid": 1, "tid": 1,
             "dur": -1}]})

    s = obs.summarize(records)
    assert s["records"] == 3 and s["main_trace"] == jm.trace_id
    assert s["names"]["recovery.redeploy"]["count"] == 1
    assert [e["name"] for e in s["timeline"]] == [
        "recovery.detect", "recovery.redeploy", "recovery.replay"]

    # The standalone converter (tools/trace2chrome.py) over the same
    # files: validates and writes a loadable Chrome trace.
    out = str(tmp_path / "chrome.json")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace2chrome.py"),
         jm_path, wk_path, "-o", out, "--trace-id", jm.trace_id],
        cwd=REPO, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr
    info = json.loads(res.stdout)
    assert info["valid"] and info["records"] == 3
    assert info["traces"] == [jm.trace_id]
    assert obs.validate_chrome(json.load(open(out))) > 0


# --- metrics satellites ------------------------------------------------------


def test_meter_and_histogram_use_bounded_deques():
    t = [0.0]
    m = met.Meter(window_s=10.0, clock=lambda: t[0])
    assert isinstance(m._events, collections.deque)
    for _ in range(5):
        m.mark(2)
        t[0] += 1.0
    assert m.rate == pytest.approx(1.0)
    # mark() prunes everything past the window from the left in O(1).
    t[0] = 100.0
    m.mark(1)
    assert len(m._events) == 1
    assert m.rate == pytest.approx(0.1)

    h = met.Histogram(max_samples=4)
    assert isinstance(h._buf, collections.deque)
    for v in (1, 2, 3, 4, 5, 6):
        h.update(v)
    assert h.count == 4                       # oldest two evicted
    assert h.mean == pytest.approx(4.5)
    assert h.quantile(0.5) == pytest.approx(4.5)
    assert h.quantile(0.99) == pytest.approx(5.97)


def test_jsonlines_reporter_single_handle_flush_and_close(tmp_path):
    path = str(tmp_path / "m.jsonl")
    r = met.JsonLinesReporter(path, clock=lambda: 1.0)
    r.report({"a": 1})
    handle = r._file
    r.report({"a": 2})
    assert r._file is handle, "one append-mode handle for the lifetime"
    # Flushed per record: both lines readable before close.
    assert [json.loads(ln)["a"] for ln in open(path)] == [1, 2]
    r.close()
    assert r._file is None
    r.report({"a": 3})                        # reopens, appends
    r.close()
    assert [json.loads(ln)["a"] for ln in open(path)] == [1, 2, 3]

    # ReporterThread.stop() closes closeable reporters.
    reg = met.MetricRegistry()
    reg.group("g").counter("c").inc()
    r2 = met.JsonLinesReporter(str(tmp_path / "n.jsonl"))
    reg.add_reporter(r2)
    th = met.ReporterThread(reg, interval_s=0.05)
    th.start()
    deadline = time.monotonic() + 10
    while not os.path.exists(r2._path) or not os.path.getsize(r2._path):
        assert time.monotonic() < deadline
        time.sleep(0.02)
    th.stop()
    assert r2._file is None


def test_metrics_endpoint_serves_cluster_view_and_trace():
    reg = met.MetricRegistry()
    reg.group("scheduler").counter("deploys").inc(3)
    tr = obs.Tracer("jm")
    tr.event("recovery.detect", worker="b")
    # ``extra`` is the JobMaster's aggregated per-worker heartbeat view.
    extra = lambda: {"worker.a.group.1.supersteps": 12}
    ep = met.MetricsEndpoint(reg, port=0, extra=extra, tracer=tr)
    try:
        base = "http://%s:%d" % ep.address
        txt = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "scheduler_deploys 3" in txt
        assert "worker_a_group_1_supersteps 12" in txt
        js = json.loads(urllib.request.urlopen(base
                                               + "/metrics.json").read())
        assert js["scheduler.deploys"] == 3
        assert js["worker.a.group.1.supersteps"] == 12
        # /trace serves the flight-recorder ring as valid Chrome JSON.
        doc = json.loads(urllib.request.urlopen(base + "/trace").read())
        assert obs.validate_chrome(doc) > 0
        assert "recovery.detect" in [e["name"] for e in doc["traceEvents"]]
    finally:
        ep.close()

    # Without a tracer the /trace surface does not exist.
    ep2 = met.MetricsEndpoint(met.MetricRegistry(), port=0)
    try:
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen("http://%s:%d/trace" % ep2.address)
    finally:
        ep2.close()


def test_heartbeat_piggybacks_metrics_into_jobmaster_view():
    from clonos_tpu.runtime.remote import JobMasterServer, TaskExecutorClient

    jm = JobMasterServer(heartbeat_timeout_s=30.0)
    good = bad = None
    try:
        good = TaskExecutorClient(
            "a", jm.address, interval_s=0.05,
            payload_fn=lambda: {"metrics": {"group.1.supersteps": 4}})
        deadline = time.monotonic() + 20
        while "worker.a.group.1.supersteps" not in jm.cluster_metrics():
            assert time.monotonic() < deadline, "piggyback never arrived"
            time.sleep(0.02)
        assert jm.cluster_metrics()["worker.a.group.1.supersteps"] == 4

        # A crashing payload_fn must not kill the heartbeat itself.
        bad = TaskExecutorClient("b", jm.address, interval_s=0.05,
                                 payload_fn=lambda: 1 // 0)
        time.sleep(0.3)
        assert bad.missed_beats == 0
        assert not any(k.startswith("worker.b.")
                       for k in jm.cluster_metrics())
    finally:
        for c in (good, bad):
            if c is not None:
                c.close()
        jm.close()


# --- lifecycle instrumentation, in-process -----------------------------------


def test_checkpoint_lifecycle_traced_with_latency():
    from clonos_tpu.runtime.checkpoint import (CheckpointCoordinator,
                                               InMemoryCheckpointStorage)

    tr = obs.configure("runner")
    co = CheckpointCoordinator(InMemoryCheckpointStorage(), num_subtasks=2)
    carry = {"w": np.zeros(4, np.float32)}
    co.trigger(7, carry, async_write=False, owned=True)
    co.ack(7, 0)
    assert 7 not in co.completion_latency_s, "half-acked is not complete"
    co.ack(7, 1)
    assert co.completion_latency_s[7] >= 0.0

    recs = tr.records()
    names = [r["name"] for r in recs]
    assert names.index("checkpoint.trigger") \
        < names.index("checkpoint") < names.index("checkpoint.truncate")
    ck = next(r for r in recs if r["name"] == "checkpoint")
    assert ck["ph"] == "X" and ck["args"]["cid"] == 7
    assert ck["args"]["size_bytes"] == 16
    assert ck["dur"] == pytest.approx(co.completion_latency_s[7])

    # The latency ledger is bounded (oldest entries pruned).
    for cid in range(100, 170):
        co.trigger(cid, carry, async_write=False, owned=True)
        co.ack_all(cid)
    assert len(co.completion_latency_s) <= 64
    assert 169 in co.completion_latency_s


def test_epoch_spans_and_histograms_in_process(tmp_path):
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.cluster import ClusterRunner

    tr = obs.configure("runner")
    env = StreamEnvironment(name="obsjob", num_key_groups=8)
    env.synthetic_source(vocab=7, batch_size=4, parallelism=1)
    job = env.build()
    r = ClusterRunner(job, steps_per_epoch=2,
                      checkpoint_dir=str(tmp_path / "ck"),
                      log_capacity=256, max_epochs=8, seed=2)
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=True)

    recs = tr.records()
    names = [rec["name"] for rec in recs]
    for want in ("epoch", "epoch.steps", "fence",
                 "checkpoint.trigger", "checkpoint", "checkpoint.truncate",
                 "ckpt.truncate"):
        assert want in names, f"missing {want} in {sorted(set(names))}"
    # Phase records nest under their epoch span.
    epoch0 = next(rec for rec in recs if rec["name"] == "epoch")
    assert epoch0["args"]["epoch"] == 0
    steps0 = next(rec for rec in recs if rec["name"] == "epoch.steps")
    fence0 = next(rec for rec in recs if rec["name"] == "fence")
    assert steps0["parent"] == epoch0["span"]
    assert fence0["parent"] == epoch0["span"]
    assert epoch0["dur"] >= steps0["dur"]

    # Per-phase durations feed the registry histograms.
    snap = r.metrics.snapshot()
    assert snap["job.obsjob.epoch.steps-ms"]["count"] == 2
    assert snap["job.obsjob.epoch.fence-ms"]["count"] == 2
    assert snap["job.obsjob.checkpoint.trigger-to-complete-ms"]["count"] >= 1
    assert snap["job.obsjob.epoch.steps-ms"]["p99"] >= 0.0


# --- THE acceptance run: SIGKILL recovery under one trace id -----------------


def _line_server(lines):
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(2)

    def serve():
        try:
            while True:
                conn, _ = srv.accept()
                conn.sendall("".join(f"{k}:{v}\n"
                                     for k, v in lines).encode())
        except OSError:
            return

    threading.Thread(target=serve, daemon=True).start()
    return srv, srv.getsockname()[1]


def _read_status(proc, want, deadline_s=300.0):
    deadline = time.monotonic() + deadline_s
    for line in iter(proc.stdout.readline, ""):
        assert time.monotonic() < deadline, "worker status timeout"
        st = json.loads(line)
        if want(st):
            return st
    raise AssertionError("worker stdout closed before expected status")


def test_trace_reconstructs_recovery_timeline_across_processes(tmp_path):
    """Acceptance: the slot-pool SIGKILL/redeploy run with tracing on.
    The JobMaster (this process, ``--trace-dir``-equivalent via
    obs.configure) and both worker processes (``--trace-dir``) write
    JSON-lines trace files; DEPLOY/DETERMINANT_REQUEST/FETCH_EDGE
    headers carry the trace context, so afterwards the three files
    reconstruct the whole recovery — detect -> redeploy -> determinant
    fetch -> rebuild -> replay -> caught up — under ONE trace id, with
    per-phase durations in the scheduler's registry, worker metrics
    aggregated over HEARTBEAT, and a valid Chrome trace out of
    tools/trace2chrome.py."""
    from clonos_tpu.runtime import scheduler as sch
    from clonos_tpu.runtime.leader import FileLeaderElection
    from clonos_tpu.runtime.remote import JobMasterServer

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    lease = str(tmp_path / "jm.lease")
    lines = [((i * 37) % 997, 1 + i % 5) for i in range(600)]
    srv, lport = _line_server(lines)

    jm_tracer = obs.configure("jm", path=str(trace_dir / "trace-jm.jsonl"))
    jm = JobMasterServer(heartbeat_timeout_s=2.0)
    election = FileLeaderElection(lease, "jm-0", lease_ttl_s=30.0)
    assert election.try_acquire()
    runner_kw = dict(steps_per_epoch=4, log_capacity=512, max_epochs=64,
                     inflight_ring_steps=64, seed=7, logical_time=True)
    scheduler = sch.SlotPoolScheduler(
        jm, election, "examples.spanning:build_job", runner_kw=runner_kw,
        feed_batch=4, target_epochs=8, complete_every=2,
        checkpoint_root=str(tmp_path / "ck"), deploy_timeout_s=300.0)

    def spawn(eid):
        return subprocess.Popen(
            [sys.executable, "-m", "clonos_tpu", "slotworker",
             "--jm", f"127.0.0.1:{jm.address[1]}",
             "--executor-id", eid, "--slots", "2", "--lease", lease,
             "--heartbeat-interval", "0.3", "--max-seconds", "600",
             "--epoch-sleep", "0.25", "--trace-dir", str(trace_dir)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)

    pa, pb = spawn("a"), spawn("b")
    try:
        assert json.loads(pa.stdout.readline())["registered"] == "a"
        assert json.loads(pb.stdout.readline())["registered"] == "b"
        deadline = time.monotonic() + 30
        while {"a", "b"} - set(jm.registered()):
            assert time.monotonic() < deadline
            time.sleep(0.05)

        placements = scheduler.deploy(external_feeds={
            0: {"kind": "socket", "host": "127.0.0.1", "port": lport,
                "num_subtasks": 1}})
        assert placements == {0: "a", 1: "b"}
        _read_status(pa, lambda st: st.get("deployed") == 0)
        _read_status(pb, lambda st: st.get("deployed") == 1)
        _read_status(pa, lambda st: st.get("finished") == 0)

        # Mirror determinants at each downstream fence; kill at
        # epoch >= 5 (checkpoints 0, 2, 4 completed by then).
        def at_fence(st):
            if "group" in st and "digest" in st:
                scheduler.sync()
            return st.get("epoch", -1) >= 5 or "finished" in st

        _read_status(pb, at_fence)
        pb.send_signal(signal.SIGKILL)
        pb.wait(timeout=15)

        deadline = time.monotonic() + 20
        while "b" not in scheduler.failed_workers():
            assert time.monotonic() < deadline, "heartbeat expiry not seen"
            time.sleep(0.1)

        assert scheduler.recover_worker("b") == {1: "a"}
        dep = _read_status(pa, lambda st: st.get("deployed") == 1)
        assert dep["recovered"] and dep["vertices"] == [2, 3]

        # Per-phase recovery durations landed in the JobMaster-side
        # registry histograms...
        snap = scheduler.metrics.snapshot()
        assert snap["scheduler.deploy-ms"]["count"] >= 3
        assert snap["scheduler.recovery.redeploy-ms"]["count"] == 1
        assert snap["scheduler.recovery.determinant-fetch-ms"]["count"] == 1
        assert snap["scheduler.recovery.redeploy-ms"]["p99"] > 0.0

        # ...and the worker's (recovery.replay-ms & co) reach the
        # JobMaster's cluster-wide view piggybacked on HEARTBEAT.
        deadline = time.monotonic() + 60
        while not any(k.startswith("worker.a.")
                      and k.endswith("recovery.replay-ms")
                      for k in jm.cluster_metrics()):
            assert time.monotonic() < deadline, \
                f"no replay histogram in {sorted(jm.cluster_metrics())}"
            time.sleep(0.2)
        replay_ms = next(v for k, v in jm.cluster_metrics().items()
                         if k.startswith("worker.a.")
                         and k.endswith("recovery.replay-ms"))
        assert replay_ms["count"] >= 1

        # The rebuilt slice runs on to the job's target.
        fin = _read_status(pa, lambda st: st.get("finished") == 1)
        assert fin["global_step"] == 8 * runner_kw["steps_per_epoch"]
    finally:
        for p in (pa, pb):
            if p.poll() is None:
                p.kill()
        scheduler.close()
        jm.close()
        srv.close()
        obs.reset()          # also flushes/closes trace-jm.jsonl

    # --- reconstruct the timeline from the three trace files -----------------
    T = jm_tracer.trace_id
    paths = [str(trace_dir / f"trace-{s}.jsonl") for s in ("jm", "a", "b")]
    for p in paths:
        assert os.path.exists(p), f"missing trace file {p}"
    records = obs.load_jsonl(paths)
    ours = [r for r in records if r["trace"] == T]

    # One trace id spans all three processes: the workers ADOPTED the
    # JobMaster's id from the DEPLOY header.
    assert {r["service"] for r in ours} >= {"jm", "a", "b"}
    assert len({r["pid"] for r in ours}) >= 3

    def first(name, service=None):
        for r in ours:
            if r["name"] == name and (service is None
                                      or r["service"] == service):
                return r
        raise AssertionError(
            f"{name} ({service}) not in trace: "
            f"{sorted({(r['service'], r['name']) for r in ours})}")

    # The full recovery timeline, each phase attributed to its process.
    detect = first("recovery.detect", "jm")
    assert detect["args"]["worker"] == "b"
    redeploy = first("recovery.redeploy", "jm")
    fetch = first("recovery.determinant_fetch", "jm")
    rebuild = first("recovery.rebuild", "a")
    replay = first("recovery.replay", "a")
    caught = first("recovery.caught_up", "a")
    recovery = first("recovery", "a")
    first("recovery.restore", "a")
    first("recovery.fetch_determinants", "a")
    first("epoch", "b")                  # pre-kill epochs, same trace
    first("epoch", "a")
    # The deploy that carried the recovery is in the trace too.
    rec_deploy = next(r for r in ours
                      if r["name"] == "deploy" and r["service"] == "jm"
                      and r["args"].get("recover"))
    assert rec_deploy["args"]["worker"] == "a"

    # Causal order: detect -> redeploy window covering fetch/rebuild,
    # replay ends before the worker reports caught up.
    assert detect["ts"] <= redeploy["ts"]
    assert redeploy["ts"] <= fetch["ts"]
    assert rebuild["ts"] + rebuild["dur"] <= caught["ts"] + 1e-6
    assert replay["ts"] + replay["dur"] <= caught["ts"] + 1e-6
    assert recovery["dur"] > 0           # recovery_ms, back-dated span
    # The determinant fetch nests inside the redeploy span.
    assert fetch["parent"] == redeploy["span"]

    # The merged files convert to a VALID Chrome trace, and the
    # standalone converter agrees.
    doc = obs.to_chrome(records, trace_id=T)
    assert obs.validate_chrome(doc) > len(ours)      # + metadata events
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace2chrome.py"),
         *paths, "--check"],
        cwd=REPO, capture_output=True, text=True, env=env)
    assert res.returncode == 0, res.stderr
    info = json.loads(res.stdout)
    assert info["valid"] and T in info["traces"]
