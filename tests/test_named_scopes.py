"""The named scopes of the block program (``clonos_tpu/obs/scopes.py``):
for a tiny job of each topology under ``benchmark/topologies`` the lowered
``run_block`` names every scope the topology should produce — read off
the lowered text's debug locations by the parser the benchmark's per-layer
metrics use (``benchlib/scope_times.scope_of``) — the program enters no
scope outside the vocabulary, own and replica appends carry different
leaves, and the scopes are metadata only: the same job lowered with
``jax.named_scope`` patched out is the same program."""

import contextlib
import json
import os
import re
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import job, scope_times  # noqa: E402

from clonos_tpu.obs import scopes  # noqa: E402

#: what every job's block carries
COMMON = {
    "exchange", "causal-log/rows", "causal-log/own", "causal-log/replicas",
    "inflight-ring"}
RANKED = {"exchange/rank", "exchange/place", "exchange/place/hist"}
#: tiny stand-in of each topology, and the scopes beneath ``vertex/<name>``
#: and ``exchange`` its block must name (a bare ``vertex/<name>``: ops of
#: the operator outside any part)
TOPOLOGIES = {
    "source-window-reduce-sink": ("tiny-kafka", RANKED | {
        "exchange/plan",
        "vertex/host-source", "vertex/window", "vertex/window/hist",
        "vertex/window/segsum", "vertex/reduce", "vertex/reduce/segsum",
        "vertex/reduce/readback", "vertex/sink"}),
    "allround-event-time": ("tiny-allround-upstream", RANKED | {
        "exchange/plan",
        "vertex/host-source", "vertex/event-time", "vertex/keyed-state",
        "vertex/keyed-state/hist", "vertex/keyed-state/segsum",
        "vertex/keyed-state/readback", "vertex/operator-state",
        "vertex/tumbling", "vertex/tumbling/place",
        "vertex/tumbling/place/hist", "vertex/tumbling/segsum",
        "vertex/sliding", "vertex/sliding/place",
        "vertex/sliding/place/hist", "vertex/sliding/segsum",
        # the union is its compaction: no op of its own outside it
        "vertex/union/compact", "vertex/union/compact/hist",
        "vertex/sink"}),
    "nexmark-window-join": ("tiny-nexmark-q8", RANKED | {
        "vertex/host-source", "vertex/parse", "vertex/persons",
        "vertex/auctions", "vertex/join", "vertex/join/lookup",
        "vertex/join/place", "vertex/join/place/hist",
        "vertex/join/segsum", "vertex/join/emit", "vertex/join/emit/hist",
        "vertex/sink"}),
    "nexmark-hot-items": ("tiny-nexmark-q5", RANKED | {
        "vertex/host-source", "vertex/parse", "vertex/sink"} | {
        f"vertex/{v}{part}" for v in ("count", "max")
        for part in ("", "/lookup", "/place", "/place/hist", "/segsum",
                     "/emit", "/emit/hist")}),
    "nexmark-user-sessions": ("tiny-nexmark-q11", RANKED | {
        "vertex/host-source", "vertex/parse", "vertex/sink"} | {
        "vertex/sessions" + part
        for part in ("", "/lookup", "/place", "/segsum", "/emit",
                     "/emit/hist")}),
    "nexmark-local-items": ("tiny-nexmark-q3", RANKED | {
        "vertex/host-source", "vertex/parse", "vertex/persons",
        "vertex/auctions", "vertex/sink"} | {
        "vertex/join" + part
        for part in ("", "/compact", "/compact/hist", "/lookup", "/place",
                     "/place/hist", "/emit", "/emit/hist")}),
    "nexmark-average-price": ("tiny-nexmark-q4", RANKED | {
        "vertex/host-source", "vertex/parse", "vertex/auctions",
        "vertex/bids", "vertex/sink"} | {
        "vertex/winning" + part
        for part in ("", "/compact", "/compact/hist", "/lookup",
                     "/lookup/hist", "/place", "/place/hist", "/emit",
                     "/emit/hist")} | {
        "vertex/mean" + part
        for part in ("", "/place", "/place/hist", "/segsum")}),
}


#: the topologies whose two-input vertex takes a block in chunks
#: (``operators._ChunkedJoin``), and that vertex
CHUNKED = {"nexmark-local-items": "join", "nexmark-average-price": "winning"}


def tiny_config(name):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def lower_block(runner, cfg):
    """The block program traced anew (a fresh function, so that no
    earlier trace of it is reused) and lowered."""
    ex = runner.executor
    compiled = ex.compiled
    return jax.jit(lambda carry, binputs: compiled.run_block(
        carry, binputs)).lower(ex.carry,
                               ex._next_block_inputs(cfg["block_steps"]))


def scopes_in(text):
    """The vocabulary's paths among the debug locations of a lowered
    text or the ``op_name`` of a compiled one, as the metric readers
    parse them."""
    found = {scope_times.scope_of(name)
             for name in re.findall(r'(?:loc\(|op_name=)"([^"]+)"', text)}
    return {"/".join(s) for s in found if s}


@pytest.fixture(scope="module", params=sorted(TOPOLOGIES))
def lowered(request, tmp_path_factory):
    """(topology, runner, config, debug text, scopes entered while
    tracing as (path entered under, name))."""
    tiny, _ = TOPOLOGIES[request.param]
    cfg = tiny_config(tiny)
    assert cfg["topology"] == request.param
    stream = job.make_stream(cfg, {"table_epochs": 2}, 7)
    runner = job.make_runner(
        cfg, stream, 7, str(tmp_path_factory.mktemp("ck")), 1)
    entered, stack = [], []
    real = jax.named_scope

    @contextlib.contextmanager
    def recording(name):
        entered.append(("/".join(stack), name))
        stack.append(name)
        try:
            with real(name):
                yield
        finally:
            stack.pop()

    jax.named_scope = recording
    try:
        text = lower_block(runner, cfg).as_text(debug_info=True)
    finally:
        jax.named_scope = real
    return request.param, runner, cfg, text, entered


def test_benchmark_reads_the_programs_vocabulary():
    assert scope_times.PARTS == scopes.PARTS
    assert (scope_times.VERTEX, scope_times.EXCHANGE, scope_times.CAUSAL_LOG,
            scope_times.INFLIGHT_RING, scope_times.HIST) == (
        scopes.VERTEX, scopes.EXCHANGE, scopes.CAUSAL_LOG,
        scopes.INFLIGHT_RING, scopes.HIST)


def test_block_names_every_scope_its_topology_should_produce(lowered):
    """By the compiled program's ``op_name``, which is what a trace's
    device plane carries: a loop whose body is a function of its own (the
    incremental join's chunks) starts its debug locations anew inside
    the body, and only the compiled program joins them to the caller's
    (``vertex/join/while/body/closed_call/lookup/...``)."""
    topology, runner, cfg, text, _ = lowered
    found = scopes_in(lower_block(runner, cfg).compile().as_text())
    if topology in CHUNKED:
        # the chunk that runs step by step is a loop in a conditional in
        # a loop, and XLA:CPU calls the inner body where the TPU's
        # compiler inlines it: its kernels may show without (part of)
        # their caller's path here, by whichever trace of the jitted
        # histogram came first (tests/test_tpu_aot.py reads the TPU's
        # program)
        found -= {"hist", f"vertex/{CHUNKED[topology]}/hist"}
    else:
        assert scopes_in(text) == found
    assert found == COMMON | TOPOLOGIES[topology][1]


def test_program_enters_no_scope_outside_the_vocabulary(lowered):
    """Every ``jax.named_scope`` the program entered while the block was
    traced is a layer at the top, the parts of its layer directly
    beneath it, or ``hist`` as a leaf."""
    _, runner, _, _, entered = lowered
    assert entered
    vertices = {f"vertex/{v.name}" for v in runner.job.vertices}
    for under, name in entered:
        if under == "":
            assert (name in vertices
                    or name in (scopes.EXCHANGE, scopes.CAUSAL_LOG,
                                scopes.INFLIGHT_RING)), name
        elif name == scopes.HIST:
            assert under.split("/")[0] in (
                "vertex", scopes.EXCHANGE), (under, name)
        elif under in vertices:
            assert name in scopes.PARTS[scopes.VERTEX], (under, name)
        else:
            assert name in scopes.PARTS.get(under, ()), (under, name)
    assert {n for u, n in entered if u == ""} >= vertices


def test_own_and_replica_appends_carry_different_leaves(lowered):
    _, runner, _, text, _ = lowered
    assert runner.executor.compiled.plan.num_replicas > 0
    leaves = {}
    for name in re.findall(r'loc\("([^"]+)"', text):
        scope = scope_times.scope_of(name)
        if scope[:1] == (scopes.CAUSAL_LOG,) and len(scope) == 2:
            leaves.setdefault(scope[1], set()).add(name.split("/")[-1])
    assert set(leaves) == {"rows", "own", "replicas"}
    # both append: the same primitives under either leaf
    assert leaves["own"] & leaves["replicas"]


def test_scopes_are_metadata_only(lowered, monkeypatch):
    """The same job lowered with ``jax.named_scope`` a null context: the
    texts without debug info are equal, and neither names a scope."""
    _, runner, cfg, text, _ = lowered
    with_scopes = lower_block(runner, cfg).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower_block(runner, cfg)
    assert not scopes_in(bare.as_text(debug_info=True))
    assert bare.as_text() == with_scopes
    assert "causal-log" not in with_scopes and "vertex/" not in with_scopes
    assert "causal-log" in text


def test_roll_and_truncate_name_own_and_replica_logs(lowered):
    """The fence's programs run other code on the same logs and take the
    same names for them."""
    _, runner, _, _, _ = lowered
    ex = runner.executor
    for prog in (ex._jit_roll, ex._jit_trunc):
        found = scopes_in(prog.lower(ex.carry, 1).as_text(debug_info=True))
        assert found == {"causal-log/own", "causal-log/replicas",
                         "inflight-ring"}


def test_replay_program_takes_the_block_programs_names(tmp_path):
    """The replayer of a stateful vertex runs the operator's block form
    under ``vertex/<name>``, so the parts beneath it are the block
    program's."""
    cfg = tiny_config("tiny-nexmark-q5")
    stream = job.make_stream(cfg, {"table_epochs": 2}, 7)
    runner = job.make_runner(cfg, stream, 7, str(tmp_path / "ck"), 1)
    replayer = runner.failover.programs.replayer(2, 1)          # ``count``, subtask 1
    assert replayer.vertex_name == "count"
    recorded = []
    real = jax.named_scope

    @contextlib.contextmanager
    def recording(name):
        recorded.append(name)
        with real(name):
            yield

    runner.run_epoch(complete_checkpoint=True)
    runner.run_epoch(complete_checkpoint=False)
    jax.named_scope = recording
    try:
        runner.inject_failure([runner.job.subtask_base(2) + 1])
        runner.recover()
    finally:
        jax.named_scope = real
    assert {"vertex/count", "lookup", "place", "segsum", "emit", "hist",
            "exchange", "rank"} <= set(recorded)
    assert set(recorded) <= {"vertex/count", "hist", "exchange",
                             "causal-log", "inflight-ring"} | {
        p for parts in scopes.PARTS.values() for p in parts}
