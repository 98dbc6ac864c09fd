"""Finalize-phase accounting guard (PR12): the overlapped recovery
pipeline must ATTRIBUTE its concurrency, never hide it. Invariant, for
every RecoveryReport.phase_ms:

    sum(finalize.* sub-spans) - finalize.overlap-saved == finalize

Sub-spans keep their true wall durations (what each piece of work
cost); ``finalize`` is the part of them on the critical path;
``finalize.overlap-saved`` is the part that ran under other work. Both
are derived from the sub-spans' own stamps, so the identity is exact:
no tolerance on a wall-clock sum here. Wired next to the conftest
lint/analyze gates: this file is tier-1, so any accounting regression
fails CI fast.
"""

import numpy as np
import pytest

from clonos_tpu import obs


def _finalize_identity(pm):
    subs = {k: v for k, v in pm.items()
            if k.startswith("finalize.") and k != "finalize.overlap-saved"}
    saved = pm["finalize.overlap-saved"]
    assert saved >= 0.0
    assert all(v >= 0.0 for v in subs.values())
    # exact by construction; 1e-6 ms is float rounding, not a tolerance
    assert sum(subs.values()) - saved == pytest.approx(
        pm["finalize"], rel=0, abs=1e-6), (
        f"finalize attribution broke: subs={subs} saved={saved} "
        f"finalize={pm['finalize']}")
    return subs, saved


def _window_job(name):
    from clonos_tpu.api.environment import StreamEnvironment
    env = StreamEnvironment(name=name, num_key_groups=8)
    (env.synthetic_source(vocab=11, batch_size=4, parallelism=2)
        .key_by()
        .window_count(num_keys=11, window_size=1 << 30)
        .sink())
    return env.build()


def _two_epoch_runner(name, tmp_path, **kw):
    from clonos_tpu.runtime.cluster import ClusterRunner

    r = ClusterRunner(_window_job(name), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"), **kw)
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)
    return r


def test_recover_keeps_the_identity_and_repeats_it(tmp_path):
    obs.configure("phases")
    r = _two_epoch_runner("ph", tmp_path)
    for _ in range(2):
        r.inject_failure([2 + 1])
        subs, _saved = _finalize_identity(r.recover().phase_ms)
        assert set(subs) == {"finalize.barrier-dispatch",
                             "finalize.barrier-read",
                             "finalize.state-verify"}


@pytest.mark.parametrize("drill", [False, True], ids=["live", "drill"])
@pytest.mark.parametrize("audit", [False, True], ids=["plain", "audited"])
def test_every_report_carries_overlap_saved_and_the_exact_identity(
        tmp_path, drill, audit):
    """A live ``recover()`` and a rehearsal, with and without the audit
    validator inside the barrier's window: the report always has
    ``finalize.overlap-saved``, the saving is never more than the
    barrier read it is a part of, and without an audit there is nothing
    the read could have run under."""
    r = _two_epoch_runner("phid", tmp_path, audit=audit)
    r.inject_failure([2 + 1])
    report = r.recover(drill=drill)
    assert report.drill is drill
    pm = report.phase_ms
    _subs, saved = _finalize_identity(pm)
    assert saved <= pm["finalize.barrier-read"] + 1e-9
    if audit:
        assert pm["audit"] > 0.0
    else:
        assert saved == 0.0 and "audit" not in pm


def test_bootstrap_standby_folds_overlap_into_the_identity(tmp_path):
    """The standby-host rebuild runs ledger derivation + RNG
    fast-forward + AOT warm on a worker thread; its report must still
    satisfy the identity, with the bootstrap sub-spans (rehydrate /
    listener-reattach / first-step-recompile) folded in and the thread's
    off-critical-path time credited to finalize.overlap-saved."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.cluster import ClusterRunner

    env = StreamEnvironment(name="phboot", num_key_groups=8)
    env.synthetic_source(vocab=7, batch_size=4, parallelism=1)
    job = env.build()
    ck = str(tmp_path / "ck")
    r = ClusterRunner(job, steps_per_epoch=4, checkpoint_dir=ck,
                      log_capacity=256, max_epochs=8, seed=2)
    for _ in range(3):
        r.run_epoch(complete_checkpoint=True)
    logs = r.executor.carry.logs
    head = int(np.asarray(logs.head)[0])
    tail = int(np.asarray(logs.tail)[0])
    cap = np.asarray(logs.rows).shape[1]
    pos = np.arange(tail, head) & (cap - 1)
    mirror_rows = {0: (np.asarray(logs.rows)[0][pos], tail)}

    rebuilt, report = ClusterRunner.bootstrap_standby(
        job, ck, mirror_rows, steps_per_epoch=4, log_capacity=256,
        max_epochs=8, seed=2)
    pm = report.phase_ms
    subs, saved = _finalize_identity(pm)
    assert {"finalize.state-rehydrate", "finalize.listener-reattach",
            "finalize.first-step-recompile", "finalize.barrier-read",
            "finalize.state-verify"} <= set(subs)
    # the worker thread existed: derive+warm walls were recorded
    assert pm["finalize.first-step-recompile"] >= 0.0
    # the rebuilt runner is live (the join points held)
    assert rebuilt.global_step == 12 + report.steps_replayed


def test_overlap_verify_failure_keeps_subtasks_dead_and_retryable(tmp_path):
    """Safety-order guard: in overlapped mode, revive bookkeeping must
    run AFTER the barrier join + state-verify (the sequential order). A
    packed-read deferred assert that raises must leave ``self.failed``
    and the heartbeat dead-set intact, so the failure is visible and
    ``recover()`` can simply be retried; the barrier thread must not
    outlive the call."""
    import threading

    from clonos_tpu.causal import recovery as rec
    from clonos_tpu.runtime.cluster import ClusterRunner

    r = ClusterRunner(_window_job("phdead"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"))
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)

    flat = 2 + 1
    progs = r.failover.programs
    orig_bounds = progs.ring_bounds()
    assert r.executor.carry.out_rings       # the job has in-flight rings
    r.inject_failure([flat])
    # Deterministic verify trip: skew the ring-bounds lanes of the
    # packed read so the deferred assert sees device bounds that
    # contradict the host mirror. Routing coverage decisions read the
    # (valid, untampered) host mirror, so the replay itself is sound —
    # only the final state-verify fires.
    progs.ring_bounds = lambda: lambda rings: orig_bounds(rings) + 1
    with pytest.raises(rec.RecoveryError, match="state suspect"):
        r.recover()
    assert flat in r.failed                    # NOT marked healthy
    assert flat in r.heartbeats._dead
    assert not any(t.name == "recovery-finalize-barrier"
                   for t in threading.enumerate())
    # Un-tamper and retry: the protocol reruns end-to-end, and only a
    # recover() that passed verify revives the subtask.
    progs.ring_bounds = lambda: orig_bounds
    report = r.recover()
    assert not r.failed
    assert flat not in r.heartbeats._dead
    assert "finalize.state-verify" in report.phase_ms


def test_overlap_audit_divergence_defers_past_verify_and_joins(tmp_path):
    """An audit divergence under the abort policy in overlapped mode
    must not short-circuit the window: the barrier thread is joined,
    state-verify's deferred asserts still run, revive keeps its
    sequential place, and only then does AuditDivergenceError
    propagate — the same observable order as the sequential control."""
    import json
    import threading

    from clonos_tpu.causal.recovery import AuditDivergenceError
    from clonos_tpu.runtime.cluster import ClusterRunner

    tr = obs.configure("phaud")
    r = ClusterRunner(_window_job("phaud"), steps_per_epoch=8,
                      log_capacity=512, max_epochs=8,
                      inflight_ring_steps=32, seed=3,
                      checkpoint_dir=str(tmp_path / "ck"),
                      audit=True, audit_on_divergence="abort")
    r.run_epoch(complete_checkpoint=True)
    r.run_epoch(complete_checkpoint=False)

    # Tamper every sealed fingerprint on disk: whatever epoch window the
    # recovery validates, its recompute diverges from the ledger.
    ledger = tmp_path / "ck" / "ledger.jsonl"
    entries = [json.loads(ln) for ln in
               ledger.read_text().splitlines() if ln]
    for e in entries:
        for ch in e["channels"].values():
            ch["fp"] = "00" * 8
    ledger.write_text("".join(json.dumps(e) + "\n" for e in entries))

    flat = 2 + 1
    r.inject_failure([flat])
    with pytest.raises(AuditDivergenceError):
        r.recover()
    # state-verify ran before the deferred divergence propagated
    assert any(x["name"] == "recovery.finalize.state-verify"
               for x in tr.records())
    # ... and so did revive (verify passed), matching the sequential
    # control where the abort fires after barrier→verify→revive.
    assert not r.failed
    assert flat not in r.heartbeats._dead
    assert not any(t.name == "recovery-finalize-barrier"
                   for t in threading.enumerate())
