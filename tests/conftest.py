"""Test harness: run on a virtual 8-device CPU mesh.

The reference tests multi-node behavior without a cluster via the in-JVM
MiniCluster (flink-runtime .../minicluster/MiniCluster.java:108). The JAX
analog is forcing the host platform to expose 8 virtual devices, so every
sharding/collective path is exercised single-process. The suite runs on
the CPU (``JAX_PLATFORMS=cpu``, set here when the caller did not);
the chip is ``chip_smoke.py``'s.
"""

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (import after the environment is set)

# Persistent compile cache: the suite is compile-dominated; a warm cache
# cuts repeat runs several-fold. Keyed by HLO hash — safe across edits.
from clonos_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    """Register this repo's markers (clonos_tpu/lint/markers.py is the
    single source of truth) and run the full determinism lint — a
    typo'd marker is a silent no-op under ``-m 'not slow'``, and an
    unlogged time.time() is a replay divergence waiting for a failure
    to surface it, so both fail the session here with file:line
    findings instead."""
    from clonos_tpu.lint import format_text, run_lint
    from clonos_tpu.lint.markers import REGISTERED_MARKERS

    for name, help_text in REGISTERED_MARKERS.items():
        config.addinivalue_line("markers", f"{name}: {help_text}")
    cwd = os.getcwd()
    os.chdir(_REPO_ROOT)   # finding paths & waiver globs repo-relative
    try:
        result = run_lint(["clonos_tpu", "examples", "tests"])
    finally:
        os.chdir(cwd)
    if not result.ok:
        raise pytest.UsageError(
            "determinism lint failed (clonos_tpu lint):\n"
            + format_text(result))
    # Same gate for the whole-program analysis (clonos_tpu analyze):
    # a nondet escape that reaches a step function, or a lock-order
    # cycle, fails the session before any test runs. Stale analysis
    # waivers are warnings — printed, not fatal.
    import sys as _sys
    from clonos_tpu.analysis import (format_text as a_format,
                                     run_analysis)
    cwd = os.getcwd()
    os.chdir(_REPO_ROOT)
    try:
        aresult = run_analysis(["clonos_tpu", "examples"])
    finally:
        os.chdir(cwd)
    if not aresult.ok:
        raise pytest.UsageError(
            "whole-program analysis failed (clonos_tpu analyze):\n"
            + a_format(aresult))
    for w in aresult.warnings:
        print(f"analyze warning: {w.location()}: [{w.rule}] "
              f"{w.message}", file=_sys.stderr)
    # Census-drift gate: the pinned fingerprint (.clonos-census) must
    # match — the FT call-site population changing silently is how a
    # new unlogged call site slips past review.
    pin_path = os.path.join(_REPO_ROOT, ".clonos-census")
    if os.path.isfile(pin_path):
        with open(pin_path) as f:
            toks = f.read().split()
        pinned = toks[0] if toks else ""
        if aresult.census_fingerprint != pinned:
            raise pytest.UsageError(
                f"census drift: fingerprint "
                f"{aresult.census_fingerprint} != pinned {pinned} "
                f"(.clonos-census) — the FT call-site population "
                f"changed; review `clonos_tpu analyze --census`, then "
                f"re-pin with\n  python -m clonos_tpu.cli analyze "
                f"--report json | python -c \"import json,sys; "
                f"print(json.load(sys.stdin)['census_fingerprint'])\" "
                f"> .clonos-census")
    # Thread-census drift gate: the pinned fingerprint (.clonos-threads)
    # must match — a new thread root appearing (or one being re-homed)
    # silently is how an unreviewed concurrency interaction slips past
    # the race pass's discharge reasoning.
    tpin_path = os.path.join(_REPO_ROOT, ".clonos-threads")
    if os.path.isfile(tpin_path):
        with open(tpin_path) as f:
            toks = f.read().split()
        pinned = toks[0] if toks else ""
        if aresult.threads_fingerprint != pinned:
            raise pytest.UsageError(
                f"thread-census drift: fingerprint "
                f"{aresult.threads_fingerprint} != pinned {pinned} "
                f"(.clonos-threads) — the thread-root population "
                f"changed (a thread was added, removed, or re-homed); "
                f"review `clonos_tpu analyze --threads`, then re-pin "
                f"with\n  python -m clonos_tpu.cli analyze "
                f"--report json --no-census | python -c \"import json,"
                f"sys; print(json.load(sys.stdin)"
                f"['threads_fingerprint'])\" > .clonos-threads")
    # Protocol model-checker gate (clonos_tpu verify --quick): every
    # safety invariant on every reachable state of the four protocol
    # models at the quick bound, sub-second and jax-free. A violation
    # prints the minimal counterexample trace.
    from clonos_tpu.verify import format_text as v_format, run_verify
    vresult = run_verify(quick=True)
    if not vresult.ok:
        raise pytest.UsageError(
            "protocol model check failed (clonos_tpu verify --quick):\n"
            + v_format(vresult))
    # Timeline causality gate (clonos_tpu timeline --self-check): two
    # skew-clocked simulated processes exchange HLC-stamped messages;
    # the merged stream must show zero inversions. Pure and sub-
    # millisecond — a broken receive rule fails the session here, not
    # in a flaky multi-process soak.
    from clonos_tpu.obs.timeline import timeline_self_check
    findings = timeline_self_check()
    if findings:
        raise pytest.UsageError(
            "HLC causality self-check failed (clonos_tpu timeline "
            "--self-check): " + "; ".join(
                f"[{f['rule']}] {f['detail']}" for f in findings))
    # Incident forensics gate (clonos_tpu incident --self-check):
    # synthetic bundles through capture → root-cause localization,
    # byte-identity enforced across a JSON round-trip. Pure and
    # jax-free — a drifting report encoding fails the session here,
    # not in a post-mortem.
    from clonos_tpu.obs.incident import (bundle_schema_fingerprint,
                                         incident_self_check)
    ifindings = incident_self_check()
    if ifindings:
        raise pytest.UsageError(
            "incident forensics self-check failed (clonos_tpu "
            "incident --self-check): " + "; ".join(
                f"[{f['rule']}] {f['detail']}" for f in ifindings))
    # Bundle-schema drift gate: landed bundles are durable post-mortem
    # artifacts — the schema changing silently orphans every bundle
    # already on disk. The pinned fingerprint must match.
    ipin_path = os.path.join(_REPO_ROOT, ".clonos-incident-schema")
    if os.path.isfile(ipin_path):
        with open(ipin_path) as f:
            toks = f.read().split()
        pinned = toks[0] if toks else ""
        fp = bundle_schema_fingerprint()
        if fp != pinned:
            raise pytest.UsageError(
                f"incident bundle-schema drift: fingerprint {fp} != "
                f"pinned {pinned} (.clonos-incident-schema) — the "
                f"bundle layout changed; bump BUNDLE_SCHEMA's version "
                f"(obs/incident.py) so old bundles stay decodable, "
                f"then re-pin with\n  python -c \"from clonos_tpu.obs."
                f"incident import bundle_schema_fingerprint; "
                f"print(bundle_schema_fingerprint())\" "
                f"> .clonos-incident-schema")
    # Record-lineage gate (clonos_tpu lineage --self-check): synthetic
    # observations through the full dye → hop → terminus join, with
    # byte-identity enforced across a JSON round-trip AND a shuffled
    # observation order (two processes must render the same trace).
    # Pure and jax-free — a drifting reconstructor fails the session
    # here, not while someone is tracing a lost record.
    from clonos_tpu.obs.lineage import (lineage_schema_fingerprint,
                                        lineage_self_check)
    lfindings = lineage_self_check()
    if lfindings:
        raise pytest.UsageError(
            "record-lineage self-check failed (clonos_tpu lineage "
            "--self-check): " + "; ".join(
                f"[{f['rule']}] {f['detail']}" for f in lfindings))
    # Lineage-schema drift gate: lineage-*.jsonl observation files are
    # durable run artifacts — the schema changing silently orphans
    # every file already on disk. The pinned fingerprint must match.
    lpin_path = os.path.join(_REPO_ROOT, ".clonos-lineage-schema")
    if os.path.isfile(lpin_path):
        with open(lpin_path) as f:
            toks = f.read().split()
        pinned = toks[0] if toks else ""
        fp = lineage_schema_fingerprint()
        if fp != pinned:
            raise pytest.UsageError(
                f"lineage schema drift: fingerprint {fp} != pinned "
                f"{pinned} (.clonos-lineage-schema) — the observation "
                f"layout changed; bump LINEAGE_SCHEMA's version "
                f"(obs/lineage.py) so old observation files stay "
                f"readable, then re-pin with\n  python -c \"from "
                f"clonos_tpu.obs.lineage import "
                f"lineage_schema_fingerprint; "
                f"print(lineage_schema_fingerprint())\" "
                f"> .clonos-lineage-schema")


@pytest.fixture
def eight_devices():
    """The 8 virtual host devices the multi-device (mesh-sharded) tests
    run on. XLA_FLAGS above forces the count before the backend
    initializes; if something else initialized it first (e.g. a real
    single-chip backend), skip rather than fail."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"needs 8 devices, have {len(devs)}")
    return devs[:8]
