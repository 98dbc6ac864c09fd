"""Whole-program static analysis (clonos_tpu/analysis/): call graph,
nondet reachability, lock-order cycles, census + cost model, ablation.

The acceptance pairs:

- ``clonos_tpu analyze clonos_tpu/ examples/`` exits 0 on the repo
  (every exemption a justified waiver), and a synthetic helper chain
  from a step function to ``time.time()`` exits 1 naming BOTH ends.
- An injected A->B / B->A lock pair is reported as a ``lock-order``
  ERROR naming both acquisition sites (the deadlock the per-class lint
  cannot see).
- The no-FT ablation twin produces bit-identical record outputs to the
  real executor (only its logs stay empty), and stripping FT from
  ``examples/audit_nondet.py``'s world is REFUSED — its nondeterminism
  is load-bearing.
"""

import json
import os
import textwrap

import numpy as np
import pytest

from clonos_tpu.analysis import (ANALYSIS_RULES, AblationRefused,
                                 CallGraph, LOCK_ORDER, NONDET_REACH,
                                 ablated_executor,
                                 build_census, census_fingerprint,
                                 check_ablatable, fingerprint,
                                 format_json, format_text,
                                 run_analysis, static_cost_model)
from clonos_tpu.analysis.ablate import transform_source
from clonos_tpu.lint import FileContext

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ctx(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return FileContext(name, textwrap.dedent(src))


def _analyze_src(tmp_path, monkeypatch, files, use_waivers=True):
    monkeypatch.chdir(tmp_path)
    for name, src in files.items():
        (tmp_path / name).write_text(textwrap.dedent(src))
    return run_analysis(sorted(files), use_waivers=use_waivers)


def _hits(result, rule):
    return [f for f in result.findings if f.rule == rule]


# --- call graph ----------------------------------------------------------

def test_callgraph_resolves_methods_and_attr_chains(tmp_path):
    ctx = _ctx(tmp_path, "m.py", """\
        class Helper:
            def leaf(self):
                return 1

        class Op:
            def __init__(self):
                self.h = Helper()

            def process_block(self, state, ins):
                return self._step(state)

            def _step(self, state):
                return self.h.leaf()
        """)
    g = CallGraph([ctx])
    entries = g.step_entries()
    assert [e.qname for e in entries] == ["m.Op.process_block"]
    chain = g.chain("m.Op.process_block", {"m.Helper.leaf"})
    assert chain == ["m.Op.process_block", "m.Op._step",
                     "m.Helper.leaf"]


def test_callgraph_resolves_import_aliases(tmp_path):
    a = _ctx(tmp_path, "util.py", """\
        def helper():
            return 2
        """)
    b = _ctx(tmp_path, "op.py", """\
        import util as u

        class Op:
            def process_block(self, state, ins):
                return u.helper()
        """)
    g = CallGraph([a, b])
    chain = g.chain("op.Op.process_block", {"util.helper"})
    assert chain == ["op.Op.process_block", "util.helper"]


def test_callgraph_enclosing_and_nested_defs(tmp_path):
    # Nested defs are analyzed as part of their enclosing function (a
    # closure acquiring locks / reading clocks is charged to the
    # function that built it); methods resolve innermost-span-first.
    ctx = _ctx(tmp_path, "n.py", """\
        def outer():
            x = 1
            def inner():
                return 2
            return inner

        class C:
            def method(self):
                return 3
        """)
    g = CallGraph([ctx])
    fi = g.enclosing("n.py", 4)
    assert fi is not None and fi.name == "outer"
    fi2 = g.enclosing("n.py", 9)
    assert fi2 is not None and fi2.qname == "n.C.method"


# --- nondet-reach --------------------------------------------------------

def test_nondet_reach_through_helper_chain(tmp_path, monkeypatch):
    res = _analyze_src(tmp_path, monkeypatch, {"mod.py": """\
        import time

        class Op:
            def process_block(self, state, ins):
                return self._helper(state)

            def _helper(self, state):
                return deep_helper(state)

        def deep_helper(state):
            return state + time.time()
        """}, use_waivers=False)
    reach = _hits(res, NONDET_REACH)
    assert len(reach) == 1
    f = reach[0]
    assert f.line == 11                    # the SOURCE line
    assert "process_block" in f.message
    assert "_helper" in f.message and "deep_helper" in f.message
    assert res.exit_code() == 1


def test_nondet_reach_waived_source_is_quiet(tmp_path, monkeypatch):
    res = _analyze_src(tmp_path, monkeypatch, {"mod.py": """\
        import time

        class Op:
            def process_block(self, state, ins):
                # clonos: allow(wallclock): test fixture, never replayed
                return state + time.time()
        """})
    assert _hits(res, NONDET_REACH) == []
    assert res.ok


def test_nondet_unreachable_helper_not_escalated(tmp_path, monkeypatch):
    # The lint still flags the line, but no step function reaches it,
    # so there is no nondet-reach escalation.
    res = _analyze_src(tmp_path, monkeypatch, {"mod.py": """\
        import time

        def orphan_helper():
            return time.time()

        class Op:
            def process_block(self, state, ins):
                return state
        """}, use_waivers=False)
    assert _hits(res, NONDET_REACH) == []


# --- lock-order ----------------------------------------------------------

LOCK_CYCLE_SRC = """\
    import threading

    class Dispatcher:
        def __init__(self):
            self._admission_lock = threading.Lock()
            self.jm = JobMaster()

        def submit(self, job):
            with self._admission_lock:
                self.jm.seal(job)

    class JobMaster:
        def __init__(self):
            self._lock = threading.Lock()

        def seal(self, job):
            with self._lock:
                return job

        def heartbeat(self, d: "Dispatcher"):
            with self._lock:
                with d._admission_lock:
                    return 1
    """


def test_lock_order_cycle_detected(tmp_path, monkeypatch):
    res = _analyze_src(tmp_path, monkeypatch,
                       {"locks.py": LOCK_CYCLE_SRC}, use_waivers=False)
    cyc = _hits(res, LOCK_ORDER)
    assert len(cyc) == 1
    msg = cyc[0].message
    assert "Dispatcher._admission_lock" in msg
    assert "JobMaster._lock" in msg
    assert "submit" in msg and "heartbeat" in msg
    assert res.exit_code() == 1


def test_lock_order_consistent_order_is_quiet(tmp_path, monkeypatch):
    # Same two locks, both paths take them in the SAME order: no cycle.
    res = _analyze_src(tmp_path, monkeypatch, {"locks.py": """\
        import threading

        class Dispatcher:
            def __init__(self):
                self._admission_lock = threading.Lock()
                self.jm = JobMaster()

            def submit(self, job):
                with self._admission_lock:
                    self.jm.seal(job)

            def cancel(self, job):
                with self._admission_lock:
                    with self.jm._lock:
                        return job

        class JobMaster:
            def __init__(self):
                self._lock = threading.Lock()

            def seal(self, job):
                with self._lock:
                    return job
        """}, use_waivers=False)
    assert _hits(res, LOCK_ORDER) == []


def test_lock_order_reentrant_not_flagged(tmp_path, monkeypatch):
    res = _analyze_src(tmp_path, monkeypatch, {"locks.py": """\
        import threading

        class Log:
            def __init__(self):
                self._lock = threading.RLock()

            def append(self, row):
                with self._lock:
                    self._extend(row)

            def _extend(self, row):
                with self._lock:
                    return row
        """}, use_waivers=False)
    assert _hits(res, LOCK_ORDER) == []


def test_lock_balance_bare_acquire_without_release_warns(
        tmp_path, monkeypatch):
    from clonos_tpu.analysis import LOCK_BALANCE
    from clonos_tpu.lint.core import WARNING

    res = _analyze_src(tmp_path, monkeypatch, {"locks.py": """\
        import threading

        class Ledger:
            def __init__(self):
                self._lock = threading.Lock()

            def seal(self, row):
                self._lock.acquire()
                return row
        """}, use_waivers=False)
    (w,) = _hits(res, LOCK_BALANCE)
    assert w.severity == WARNING
    assert "release()" in w.message and "with" in w.message
    # a warning, not an error: the run still exits 0
    assert res.exit_code() == 0


def test_lock_balance_matched_pair_is_quiet(tmp_path, monkeypatch):
    from clonos_tpu.analysis import LOCK_BALANCE

    res = _analyze_src(tmp_path, monkeypatch, {"locks.py": """\
        import threading

        class Ledger:
            def __init__(self):
                self._lock = threading.Lock()

            def seal(self, row):
                self._lock.acquire()
                try:
                    return row
                finally:
                    self._lock.release()
        """}, use_waivers=False)
    assert _hits(res, LOCK_BALANCE) == []


def test_lock_order_sees_bare_acquire_release_pairs(
        tmp_path, monkeypatch):
    # The cycle only exists because one leg holds its lock through
    # bare .acquire()/.release() calls instead of a with block — the
    # order graph must treat both idioms as the same held region.
    res = _analyze_src(tmp_path, monkeypatch, {"locks.py": """\
        import threading

        class Dispatcher:
            def __init__(self):
                self._admission_lock = threading.Lock()
                self.jm = JobMaster()

            def submit(self, job):
                self._admission_lock.acquire()
                try:
                    self.jm.seal(job)
                finally:
                    self._admission_lock.release()

        class JobMaster:
            def __init__(self):
                self._lock = threading.Lock()

            def seal(self, job):
                with self._lock:
                    return job

            def heartbeat(self, d):
                with self._lock:
                    with d._admission_lock:
                        return 1
        """}, use_waivers=False)
    cyc = _hits(res, LOCK_ORDER)
    assert len(cyc) == 1
    assert "Dispatcher._admission_lock" in cyc[0].message
    assert "JobMaster._lock" in cyc[0].message


# --- census + cost model -------------------------------------------------

def test_repo_census_sync_lanes_and_fingerprint_stable():
    fp1 = census_fingerprint()
    fp2 = census_fingerprint()
    assert fp1 == fp2 and len(fp1) == 16
    res = run_analysis()
    assert res.census_fingerprint == fp1
    # The executor's fixed per-step sync rows, in stamp order.
    assert res.census["sync_lanes"] == [
        "TIMESTAMP", "RNG", "ORDER", "BUFFER_BUILT"]
    assert res.census["dets_per_step"] == 4
    assert res.census["encoding"]["row_bytes"] == 32
    assert len(res.census["step_functions"]) > 0
    assert any(s["callee"] == "serializable_service"
               for s in res.census["service_call_sites"])


def test_census_fingerprint_tracks_source_changes(tmp_path):
    c1 = build_census([_ctx(tmp_path, "a.py", """\
        class Op:
            def process_block(self, state, ins, ctx):
                return state + ctx.times
        """)])
    c2 = build_census([_ctx(tmp_path, "b.py", """\
        class Op:
            def process_block(self, state, ins, ctx):
                return state + ctx.times + ctx.rng_bits
        """)])
    assert fingerprint(c1) != fingerprint(c2)


def test_census_fingerprint_survives_a_shifted_line(tmp_path):
    """A blank line above a call site moves its line number, in the
    census, and nothing in what the pin hashes."""
    src = """\
        class Op:
            def process_block(self, state, ins, ctx):
                return state + ctx.times

        def fence(services):
            return services.current_time_millis()
        """
    c1 = build_census([_ctx(tmp_path, "m.py", src)])
    c2 = build_census([_ctx(tmp_path, "m.py", src.replace(
        "        def fence", "\n\n        def fence"))])
    (s1,), (s2,) = c1["service_call_sites"], c2["service_call_sites"]
    assert s1["callee"] == "current_time_millis"
    assert s2["line"] == s1["line"] + 2
    assert c1 != c2 and fingerprint(c1) == fingerprint(c2)
    # ... while a second call in the same function is a new site
    c3 = build_census([_ctx(tmp_path, "m.py", src.replace(
        "return services.current_time_millis()",
        "return (services.current_time_millis(),\n"
        "                    services.current_time_millis())"))])
    assert len(c3["service_call_sites"]) == 2
    assert fingerprint(c3) != fingerprint(c1)


def test_static_cost_model_scales_linearly():
    census = run_analysis().census
    m1 = static_cost_model(census, steps_per_epoch=100, subtasks=8,
                           records_per_step=64)
    m2 = static_cost_model(census, steps_per_epoch=200, subtasks=8,
                           records_per_step=64)
    assert m1["calls_per_step"] == census["dets_per_step"] * 8
    assert m2["determinant_bytes_per_epoch"] == \
        2 * m1["determinant_bytes_per_epoch"]
    assert 0.0 < m1["ft_fraction_static"] < 1.0
    # No rings, no replicas -> determinants are the only FT bytes.
    assert m1["ring_bytes_per_epoch"] == 0
    assert m1["replica_bytes_per_epoch"] == 0


def test_static_cost_model_spill_lanes():
    """``spill=True`` adds the tiered-storage lanes (d2h staging + disk
    write, both sized at the spilled payload) and raises the predicted
    ft-fraction; off, the lanes are present but zero (stable schema for
    BENCH json diffing)."""
    census = run_analysis().census
    base = static_cost_model(census, steps_per_epoch=100, subtasks=8,
                             records_per_step=64, ring_vertices=2)
    on = static_cost_model(census, steps_per_epoch=100, subtasks=8,
                           records_per_step=64, ring_vertices=2,
                           spill=True)
    assert base["spill_d2h_bytes_per_epoch"] == 0
    assert base["spill_disk_bytes_per_epoch"] == 0
    assert on["spill_d2h_bytes_per_epoch"] > 0
    assert on["spill_disk_bytes_per_epoch"] == \
        on["spill_d2h_bytes_per_epoch"]
    assert on["ft_fraction_static"] > base["ft_fraction_static"]


# --- repo gate -----------------------------------------------------------

def test_repo_analyzes_clean(monkeypatch):
    monkeypatch.chdir(_REPO)
    res = run_analysis(["clonos_tpu", "examples"])
    assert res.errors == [], format_text(res)
    assert res.exit_code() == 0


def test_format_json_one_line_contract(tmp_path, monkeypatch):
    res = _analyze_src(tmp_path, monkeypatch, {"mod.py": """\
        import time

        class Op:
            def process_block(self, state, ins):
                return time.time()
        """}, use_waivers=False)
    line = format_json(res)
    assert "\n" not in line
    doc = json.loads(line)
    assert doc["ok"] is False and doc["errors"] >= 1
    assert doc["census_fingerprint"] == res.census_fingerprint
    assert "census" in doc
    slim = json.loads(format_json(res, with_census=False))
    assert "census" not in slim


def test_stale_analysis_waiver_warns_not_fails(tmp_path, monkeypatch):
    res = _analyze_src(tmp_path, monkeypatch, {"mod.py": """\
        # clonos: allow(nondet-reach): nothing here any more
        X = 1
        """})
    assert res.ok                 # warnings don't flip the exit code
    assert any(f.rule == "stale-waiver" for f in res.warnings)


def test_analysis_rules_registered_for_waiver_validation():
    from clonos_tpu.lint import rule_names
    assert ANALYSIS_RULES <= set(rule_names())


# --- ablation ------------------------------------------------------------

def test_transform_strips_ft_lanes(tmp_path):
    src = textwrap.dedent("""\
        from clonos_tpu.causal import log as clog
        from clonos_tpu.causal import replication as rep
        from clonos_tpu.inflight import log as ifl

        def run(logs, replicas, ring, rows, out, plan):
            replicas = rep.append_block(replicas, rows, logs.head, plan)
            logs = clog.v_append_full(logs, rows)
            ring = ifl.append_block(ring, out)
            return logs, replicas, ring
        """)
    tree, report = transform_source("twin.py", src)
    assert {c for _l, c in report.stripped} == {
        "clonos_tpu.causal.log.v_append_full",
        "clonos_tpu.causal.replication.append_block",
        "clonos_tpu.inflight.log.append_block"}
    import ast
    code = ast.unparse(tree)
    assert "v_append_full" not in code and "append_block" not in code
    assert "logs = logs" in code and "replicas = replicas" in code


def test_ablation_refused_on_load_bearing_nondet(monkeypatch):
    monkeypatch.chdir(_REPO)
    with pytest.raises(AblationRefused) as ei:
        check_ablatable([os.path.join("examples", "audit_nondet.py")])
    assert any(f.rule == "entropy" for f in ei.value.findings)
    assert "stripping FT would change results" in str(ei.value)


def test_ablated_twin_bit_identical_outputs():
    """The golden equivalence run: same tiny job, same seed, logical
    time — the twin's sinks/states/counts are bit-identical to the real
    executor's; only the causal logs differ (twin logs stay empty)."""
    import jax
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime import executor as real_ex

    twin_mod, report = ablated_executor()
    assert len(report.stripped) >= 7, report.to_dict()
    # the block's replica append goes with the tasks' own
    assert {"clonos_tpu.causal.log.v_append_full",
            "clonos_tpu.causal.replication.append_block"} <= {
        c for _l, c in report.stripped}

    def build():
        env = StreamEnvironment(name="ablate-golden", num_key_groups=16)
        (env.synthetic_source(vocab=13, batch_size=8, parallelism=2)
            .key_by()
            .window_count(num_keys=13, window_size=1 << 30)
            .sink())
        return env.build()

    def drive(ex_mod):
        ex = ex_mod.LocalExecutor(build(), steps_per_epoch=16,
                                  log_capacity=1 << 10, max_epochs=8,
                                  inflight_ring_steps=32, block_steps=8,
                                  seed=3, logical_time=True)
        outs = None
        for _ in range(2):
            outs = ex.run_epoch()
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            (ex.carry.op_states, ex.carry.edge_bufs,
             ex.carry.record_counts, outs.sinks))]
        return leaves, max(int(np.asarray(ex.carry.logs.head).max()),
                           int(np.asarray(ex.carry.replicas.head).max()))

    real_leaves, real_head = drive(real_ex)
    twin_leaves, twin_head = drive(twin_mod)
    assert len(real_leaves) == len(twin_leaves)
    for a, b in zip(real_leaves, twin_leaves):
        np.testing.assert_array_equal(a, b)
    # Only the FT side differs: real logged, twin logged nothing, on
    # the tasks' own logs and on their replicas.
    assert real_head > 0
    assert twin_head == 0


# --- CLI -----------------------------------------------------------------

def test_cli_analyze_json_and_exit_codes(monkeypatch, capsys):
    from clonos_tpu import cli

    monkeypatch.chdir(_REPO)
    rc = cli.main(["analyze", "--report", "json", "--no-census"])
    doc = json.loads(capsys.readouterr().out.strip())
    assert rc == 0 and doc["ok"] is True
    assert len(doc["census_fingerprint"]) == 16


def test_cli_analyze_census_dump(monkeypatch, capsys):
    from clonos_tpu import cli

    monkeypatch.chdir(_REPO)
    rc = cli.main(["analyze", "--census"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["sync_lanes"] == ["TIMESTAMP", "RNG", "ORDER",
                                 "BUFFER_BUILT"]


def test_cli_analyze_expect_census_pin_and_drift(monkeypatch, capsys):
    """The census-drift gate: the repo's pinned fingerprint
    (.clonos-census) passes; a wrong pin fails with a drift message
    naming both fingerprints."""
    from clonos_tpu import cli

    monkeypatch.chdir(_REPO)
    rc = cli.main(["analyze", "--expect-census", ".clonos-census"])
    capsys.readouterr()
    assert rc == 0
    rc = cli.main(["analyze", "--expect-census", "0" * 16])
    err = capsys.readouterr().err
    assert rc == 1
    assert "census drift" in err and "0" * 16 in err
