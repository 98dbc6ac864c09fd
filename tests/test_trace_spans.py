"""The flight recorder that is always on (obs/trace.py) and the spans the
served path and recovery emit into it from inside: both clocks on every
record, nesting across the fence worker, the exact set of spans a block
of a host-fed job emits, one pair of stamps behind ``last_fence_phases``
and ``RecoveryReport.phase_ms``, counters, the named scopes of the block
program, and a loose guard on what a span costs."""

import re
import threading
import time

import pytest

from clonos_tpu import obs
from clonos_tpu.obs import trace as trace_mod
from clonos_tpu.runtime import sinktap

#: the spans one host-fed block emits, in the order they close (the
#: parent last) — PERF.md section 3 carries the same names. The sink
#: tap trails by one block: the three ``block.sink.*`` spans inside a
#: ``block`` read the block BEFORE it, so an epoch's (or a step's) first
#: block has none, and the last block is read by the same three spans
#: right behind its ``block``, as its siblings (the drain).
SINK_SPANS = ["block.sink.wait", "block.sink.d2h", "block.sink.shard"]
FIRST_BLOCK_SPANS = ["block.causal-inputs", "block.feed.pull",
                     "block.feed.put", "block.dispatch", "block.notify",
                     "block"]
BLOCK_SPANS = FIRST_BLOCK_SPANS[:4] + SINK_SPANS + FIRST_BLOCK_SPANS[4:]


@pytest.fixture(autouse=True)
def _fresh_recorder():
    obs.reset()
    yield
    obs.reset()


def _served_runner(tmp_path, overlap=False, block_steps=8):
    """A tiny host-fed job of the served shape: host source -> keyBy ->
    count window -> keyBy -> reduce -> transactional sink."""
    import chip_smoke as cs
    from clonos_tpu.api.feeds import ListFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner

    shape = cs.ServedShape(parallelism=2, batch=4, num_keys=7,
                           edge_capacity=16, steps_per_epoch=16,
                           window_steps=4, kill_after=8, epochs=8)
    job = cs.build_served_job(shape)
    runner = ClusterRunner(
        job, steps_per_epoch=16, log_capacity=512, max_epochs=8,
        inflight_ring_steps=64, seed=1, logical_time=True, audit=False,
        checkpoint_dir=str(tmp_path / "ck"), block_steps=block_steps,
        overlap_epoch=overlap)
    runner.executor.register_feed(
        0, ListFeedReader(list(cs.make_feed(shape, 3))))
    return runner


def _children(recs, parent):
    return [r for r in recs if r["parent"] == parent["span"]]


def _inside(child, parent, slack=1e-6):
    return (child["mono"] >= parent["mono"] - slack and
            child["mono"] + child.get("dur", 0.0)
            <= parent["mono"] + parent["dur"] + slack)


# --- the recorder -------------------------------------------------------------


def test_every_record_carries_both_clocks_and_mono_is_monotone_per_thread():
    tr = obs.get_tracer()

    def work(tag):
        for i in range(20):
            with tr.span(f"{tag}.outer", i=i):
                tr.event(f"{tag}.mark")
                with tr.span(f"{tag}.inner"):
                    pass

    th = threading.Thread(target=work, args=("worker",))
    th.start()
    work("main")
    th.join()
    recs = tr.records()
    assert len(recs) == 2 * 20 * 3 and tr.dropped == 0
    wall, mono = time.time(), time.monotonic()
    for r in recs:
        assert abs(r["ts"] - wall) < 60 and abs(r["mono"] - mono) < 60
        assert r["ph"] in ("X", "i")
        # the two clocks were read at the same instant: their difference
        # is the same for every record, to well under a millisecond
        assert (r["ts"] - r["mono"]) == pytest.approx(
            recs[0]["ts"] - recs[0]["mono"], abs=5e-3)
    for tid in {r["tid"] for r in recs}:
        # records land in the ring as spans CLOSE; by entry stamp a
        # thread's instants and spans never go backwards
        mine = [r for r in recs if r["tid"] == tid]
        ends = [r["mono"] + r.get("dur", 0.0) for r in mine]
        assert ends == sorted(ends)
    ids = [r["span"] for r in recs]
    assert len(set(ids)) == len(ids), "span ids are unique in a process"


def test_complete_is_marked_backdated_and_span_args_can_be_set_inside():
    tr = obs.get_tracer()
    with tr.span("d2h") as sp:
        sp.set(bytes=12)
    tr.complete("checkpoint", 0.25, cid=3)
    d2h, ck = tr.records()
    assert d2h["args"] == {"bytes": 12} and "backdated" not in d2h
    assert sp.ms == pytest.approx(d2h["dur"] * 1e3)
    assert ck["backdated"] is True and ck["dur"] == 0.25
    assert ck["mono"] == pytest.approx(time.monotonic() - 0.25, abs=0.05)


def test_counters_and_chain_share_the_tracer(tmp_path):
    tr = obs.get_tracer()
    tr.count("sink.rows", 3)
    tr.count("sink.rows", 4)
    tr.count("block.dispatches.roll")
    assert tr.counters() == {"sink.rows": 7, "block.dispatches.roll": 1}
    phases = {}
    with tr.span("recovery") as top:
        chain = tr.chain("recovery.", into=phases, drill=True)
        chain.switch("restore")
        chain.switch("replay")
        chain.switch("restore")
        chain.close()
        chain.close()                       # idempotent
    recs = tr.records()
    kids = _children(recs, {"span": top.span_id})
    assert [k["name"] for k in kids] == ["recovery.restore",
                                         "recovery.replay",
                                         "recovery.restore"]
    assert all(k["args"] == {"drill": True} for k in kids)
    assert phases["restore"] == pytest.approx(
        sum(k["dur"] for k in kids if k["name"].endswith("restore")) * 1e3)
    assert set(phases) == {"restore", "replay"}
    # a fresh recorder starts its counters again
    obs.reset()
    assert obs.get_tracer().counters() == {}


def test_a_span_left_open_by_an_exception_does_not_adopt_later_spans():
    tr = obs.get_tracer()
    chain = tr.chain("recovery.")
    with pytest.raises(RuntimeError):
        with tr.span("recovery"):
            chain.switch("replay")          # never closed
            raise RuntimeError("boom")
    with tr.span("epoch"):
        pass
    by = {r["name"]: r for r in tr.records()}
    assert by["epoch"]["parent"] is None
    assert "RuntimeError" in by["recovery"]["args"]["error"]


def test_attach_parents_a_worker_threads_spans():
    tr = obs.get_tracer()
    seen = {}

    def worker(parent):
        with tr.attach(parent):
            with tr.span("fence.snapshot") as sp:
                seen["parent_inside"] = tr.current_span()
        seen["after"] = tr.current_span()
        seen["span"] = sp.span_id

    with tr.span("fence") as fence:
        th = threading.Thread(target=worker, args=(tr.current_span(),))
        th.start()
        th.join()
    snap = next(r for r in tr.records() if r["name"] == "fence.snapshot")
    assert snap["parent"] == fence.span_id
    assert seen["after"] is None
    assert snap["tid"] != next(r for r in tr.records()
                               if r["name"] == "fence")["tid"]


def test_default_recorder_holds_a_run_and_counts_what_it_drops():
    assert obs.get_tracer()._ring.maxlen == trace_mod.DEFAULT_RING >= 65536
    small = obs.Tracer("s", buffer=8, enabled=False)
    for i in range(11):
        with small.span(f"s{i}"):
            pass
    assert small.dropped == 3
    assert [r["name"] for r in small.records()] == [f"s{i}"
                                                    for i in range(3, 11)]


def test_ten_thousand_spans_cost_well_under_a_fifth_of_a_second():
    tr = obs.get_tracer()
    with tr.span("warm"):
        pass                                # imports the annotation class
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(10_000):
            with tr.span("block.sink.d2h") as sp:
                sp.set(bytes=i)
        best = min(best, time.perf_counter() - t0)
    assert best < 0.2, f"{best * 100:.1f} us a span"
    assert tr.dropped == 0


def test_compile_instants_name_the_program_and_count_it():
    import jax
    import jax.numpy as jnp

    trace_mod.install_compile_listener()
    trace_mod.install_compile_listener()        # idempotent
    tr = obs.get_tracer()

    def a_fresh_program_for_this_test(x):
        return x * 3 + 1

    jax.jit(a_fresh_program_for_this_test)(jnp.arange(5))
    mine = [r for r in tr.records() if r["name"] == "compile"
            and "a_fresh_program_for_this_test" in r["args"]["fun_name"]]
    assert len(mine) == 1 and mine[0]["ph"] == "i"
    assert mine[0]["args"]["seconds"] > 0
    assert tr.counters()["compile.programs"] >= 1


# --- the served path ----------------------------------------------------------


def test_a_host_fed_block_emits_exactly_its_spans_in_order(tmp_path):
    runner = _served_runner(tmp_path)
    runner.run_epoch(complete_checkpoint=True)      # compiles
    obs.reset()
    tr = obs.get_tracer()
    runner.run_epoch(complete_checkpoint=True)
    runner.run_epoch(complete_checkpoint=True)
    recs = tr.records()
    blocks = [r for r in recs if r["name"] == "block"]
    assert len(blocks) == 4                         # two a 16-step epoch
    for i, b in enumerate(blocks):
        assert b["args"]["k"] == 8 and b["args"]["program"] == "run_block"
        kids = _children(recs, b)
        assert [k["name"] for k in kids] + ["block"] == (
            BLOCK_SPANS if i % 2 else FIRST_BLOCK_SPANS)
        assert all(_inside(k, b) for k in kids)
        starts = [k["mono"] for k in kids]
        assert starts == sorted(starts)
        sink = sum(k["dur"] for k in kids
                   if k["name"].startswith("block.sink."))
        assert sink <= b["dur"]
        assert sum(k["dur"] for k in kids) <= b["dur"] + 1e-6
    epochs = [r for r in recs if r["name"] == "epoch"]
    assert [e["args"] for e in epochs] == [{"epoch": 1, "steps": 16},
                                           {"epoch": 2, "steps": 16}]
    for e in epochs:
        names = [k["name"] for k in _children(recs, e)]
        assert names == ["epoch.steps", "fence"]
        steps = _children(recs, e)[0]
        assert [k["name"] for k in _children(recs, steps)] == [
            "block", "block"] + SINK_SPANS + ["epoch.roll"]
    # one wait, one copy, one sharding a block read, whoever reads it:
    # inside the next block (trailing) or at the drain
    waits = [r for r in recs if r["name"] == "block.sink.wait"]
    assert [w["args"] for w in waits] == [{"trailing": 1},
                                          {"trailing": 0}] * 2
    for name in SINK_SPANS:
        assert sum(r["name"] == name for r in recs) == len(blocks)
    # at most 16 records a block and 16 a fence
    per_epoch = len(recs) / len(epochs)
    assert per_epoch <= 2 * 16 + 16 + 3
    # a single step is a block of one, drained at once
    obs.reset()
    runner.step()
    recs = [r for r in obs.get_tracer().records()
            if r["ph"] == "X"]          # its first call compiles: instants
    assert [r["name"] for r in recs] == FIRST_BLOCK_SPANS + SINK_SPANS
    assert recs[5]["args"]["k"] == 1
    assert recs[6]["args"] == {"trailing": 0}


@pytest.mark.parametrize("block_steps", [16, 8, 4, 2])
def test_the_share_of_trailing_taps_is_all_but_an_epochs_last_block(
        tmp_path, block_steps):
    runner = _served_runner(tmp_path, block_steps=block_steps)
    for _ in range(3):
        runner.run_epoch(complete_checkpoint=True)
    tr = obs.get_tracer()
    b = 16 // block_steps
    c = tr.counters()
    assert c["sink.rung_reads"] == 3 * b
    assert c.get("sink.taps_trailing", 0) * b == c["sink.rung_reads"] * (b - 1)
    waits = [r["args"] for r in tr.records()
             if r["name"] == "block.sink.wait"]
    assert len(waits) == 3 * b and all(set(w) == {"trailing"} for w in waits)
    assert sum(w["trailing"] for w in waits) == c.get("sink.taps_trailing", 0)


def test_block_notify_span_only_with_listeners(tmp_path):
    """A ClusterRunner always listens (its timer services advance at
    block boundaries); an executor nobody listens to emits no span."""
    runner = _served_runner(tmp_path)
    runner.run_epoch(complete_checkpoint=True)
    names = [r["name"] for r in obs.get_tracer().records()]
    assert names.count("block.notify") == names.count("block") == 2
    obs.reset()
    runner.executor.block_listeners.clear()
    runner.run_epoch(complete_checkpoint=True)
    names = [r["name"] for r in obs.get_tracer().records()]
    assert names.count("block") == 2 and "block.notify" not in names


def test_device_source_job_of_four_full_blocks_emits_four_blocks(tmp_path):
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.cluster import ClusterRunner
    env = StreamEnvironment(name="devsrc", num_key_groups=8)
    env.synthetic_source(vocab=7, batch_size=4, parallelism=1)
    r = ClusterRunner(env.build(), steps_per_epoch=8, block_steps=2,
                      checkpoint_dir=str(tmp_path / "ck"),
                      log_capacity=256, max_epochs=8, seed=2)
    r.run_epoch(complete_checkpoint=True)
    tr = obs.get_tracer()
    recs = tr.records()
    blocks = [x for x in recs if x["name"] == "block"]
    assert len(blocks) == 4
    assert {b["args"]["program"] for b in blocks} == {"run_block"}
    drawn = [x for x in recs if x["name"] == "block.causal-inputs"]
    assert len(drawn) == 4
    assert all(d["parent"] == b["span"] for d, b in zip(drawn, blocks))
    assert tr.counters()["block.dispatches.run_block"] == 4
    assert "feed.records" not in tr.counters()


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["inline", "pipelined"])
def test_fence_phases_are_the_durations_of_the_spans_of_their_name(
        tmp_path, overlap):
    runner = _served_runner(tmp_path, overlap=overlap)
    runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    obs.reset()
    tr = obs.get_tracer()
    runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    phases = runner.last_fence_phases
    recs = tr.records()
    fences = [r for r in recs if r["name"] == "fence"]
    mode = "pipelined" if overlap else "inline"
    assert [f["args"]["mode"] for f in fences] == [mode] * len(fences)
    assert {f["args"]["epoch"] for f in fences} == {1}
    want = {"fence.health-read", "fence.snapshot", "fence.source-append",
            "fence.txn-seal", "fence.ack"} | (
        {"fence.capture"} if overlap else set())
    timed = {k for k in phases
             if k.startswith("fence.") and k != "fence.overlap-saved"}
    assert timed == want
    for key in timed:
        (sp,) = [r for r in recs if r["name"] == key]
        assert phases[key] == sp["dur"] * 1e3       # the same stamps
        parent = next(f for f in fences if f["span"] == sp["parent"])
        if sp["tid"] == parent["tid"]:
            assert _inside(sp, parent)
    assert phases["fence-tail"] == pytest.approx(
        sum(f["dur"] for f in fences) * 1e3)
    if overlap:
        assert [f["args"]["part"] for f in fences] == ["begin", "join"]
        worker = [r for r in recs if r["name"] in
                  ("fence.health-read", "fence.snapshot")]
        assert {r["tid"] for r in worker} != {fences[0]["tid"]}
        assert all(r["parent"] == fences[0]["span"] for r in worker)
        (join,) = [r for r in recs if r["name"] == "fence.join"]
        assert join["parent"] == fences[1]["span"]
        assert "fence.overlap-saved" in phases
    else:
        assert "fence.overlap-saved" not in phases
    # the commit instant, from inside: txn.commit under the ack
    (ack,) = [r for r in recs if r["name"] == "fence.ack"]
    (commit,) = [r for r in recs if r["name"] == "txn.commit"]
    assert commit["parent"] == ack["span"] and _inside(commit, ack)
    assert commit["args"]["epoch"] == 1
    (trunc,) = [r for r in recs if r["name"] == "ckpt.truncate"]
    assert trunc["parent"] == ack["span"]


def test_sink_counters_are_the_bytes_read_and_the_rows_committed(tmp_path):
    runner = _served_runner(tmp_path)
    (txn,) = runner.txn_logs.values()
    for _ in range(3):
        runner.run_epoch(complete_checkpoint=True)
    tr = obs.get_tracer()
    c = tr.counters()
    # what the tap copies: [P] counts and [P, 3, rung] packed rows
    # (int32), not the [K, P, capacity] output; a lane of 8 x 16 slots
    # has the one rung
    k, p, cap = 8, 2, 16
    (rung,) = sinktap.ladder(k * cap)
    per_block = p * 4 + p * 3 * rung * 4
    blocks = c["block.dispatches.run_block"]
    assert blocks == 6 and c["sink.d2h_bytes"] == blocks * per_block
    d2h = [r for r in tr.records() if r["name"] == "block.sink.d2h"]
    assert [r["args"] for r in d2h] == [
        {"bytes": per_block, "rung": rung}] * blocks
    # every block read through a rung, none read again
    assert c["sink.rung_reads"] == c["block.dispatches.sink_pack"] == blocks
    assert "sink.rung_misses" not in c
    # the budget slots compacted for them: rows / slots is the tap's fill
    assert c["sink.pack_slots"] == blocks * p * rung
    # a lane's one rung is the whole lane: packed by shifts, none by rank
    assert c["sink.packs_by_shifts"] == blocks and "sink.packs_by_rank" not in c
    committed = txn.committed_stream().shape[0]
    assert committed > 0
    assert c["sink.rows"] == c["txn.rows_committed"] == committed
    shard_rows = sum(r["args"]["rows"] for r in tr.records()
                     if r["name"] == "block.sink.shard")
    assert shard_rows == committed
    # the feed side: what was pulled and what went up
    assert c["feed.records"] == 3 * 16 * p * 4
    assert c["feed.h2d_bytes"] == blocks * k * p * 4 * (4 + 4 + 1)
    assert c["block.dispatches.roll"] == c["block.dispatches.trunc"] == 3


# --- recovery -----------------------------------------------------------------


def test_recovery_phases_are_children_of_recovery_and_sum_to_the_report(
        tmp_path):
    runner = _served_runner(tmp_path)
    runner.run_epoch(complete_checkpoint=True)
    runner.run_epoch(complete_checkpoint=False)
    for _ in range(4):
        runner.step()
    victims = [runner.job.subtask_base(1) + 1, runner.job.subtask_base(2)]
    runner.inject_failure(victims)
    obs.reset()
    tr = obs.get_tracer()
    report = runner.recover()
    recs = tr.records()
    (top,) = [r for r in recs if r["name"] == "recovery"]
    assert top["args"]["drill"] is False
    assert top["args"]["victims"] == sorted(victims)
    assert top["args"]["steps_replayed"] == report.steps_replayed == 20
    assert top["args"]["recovery_ms"] == report.recovery_ms
    assert report.recovery_ms <= top["dur"] * 1e3 + 1e-6
    kids = [k for k in _children(recs, top) if k["ph"] == "X"]
    assert all(k["name"].startswith("recovery.") for k in kids)
    assert all(_inside(k, top) for k in kids)
    # consecutive: one phase at a time on the recovering thread
    for a, b in zip(kids, kids[1:]):
        assert a["mono"] + a["dur"] <= b["mono"] + 1e-9
    by_phase = {}
    for k in kids:
        by_phase[k["name"][len("recovery."):]] = (
            by_phase.get(k["name"][len("recovery."):], 0.0)
            + k["dur"] * 1e3)
    pm = report.phase_ms
    assert {"restore", "fetch_determinants", "inputs", "replay", "patch",
            "replica_rebuild", "finalize"} <= set(by_phase)
    for phase, ms in by_phase.items():
        if phase == "finalize":
            continue
        assert pm[phase] == pytest.approx(ms, abs=1e-9), phase
    # two victims: the per-subtask phases ran twice
    assert sum(k["name"] == "recovery.replay" for k in kids) == 2
    # finalize's children, on whichever thread ran them
    (fin,) = [k for k in kids if k["name"] == "recovery.finalize"]
    subs = {r["name"]: r for r in recs if r["parent"] == fin["span"]}
    assert set(subs) == {"recovery.finalize.barrier-dispatch",
                         "recovery.finalize.barrier-read",
                         "recovery.finalize.state-verify"}
    for name, r in subs.items():
        assert pm[name[len("recovery."):]] == r["dur"] * 1e3
        assert _inside(r, fin)
    assert subs["recovery.finalize.barrier-read"]["tid"] != fin["tid"]
    # no audit in this job: nothing ran beside the barrier read, so the
    # finalize phase is its three sub-spans, all inside the window
    assert pm["finalize.overlap-saved"] == 0.0
    assert pm["finalize"] == pytest.approx(
        sum(r["dur"] for r in subs.values()) * 1e3, abs=1e-6)
    assert pm["finalize"] <= fin["dur"] * 1e3 + 1e-6
    covered = sum(k["dur"] for k in kids) * 1e3
    assert covered <= report.recovery_ms + 1e-6
    assert covered >= 0.9 * report.recovery_ms


# --- the device side ----------------------------------------------------------


def test_block_roll_and_replay_programs_carry_the_named_scopes(tmp_path):
    runner = _served_runner(tmp_path)
    ex = runner.executor
    text = ex._jit_block.lower(
        ex.carry, ex._next_block_inputs(8)).as_text(debug_info=True)
    scopes = set(re.findall(
        r"vertex/[\w\-.]+|/exchange|/causal-log|/inflight-ring|/hist", text))
    assert scopes == {"vertex/host-source", "vertex/window",
                      "vertex/reduce", "vertex/sink", "/exchange",
                      "/causal-log", "/inflight-ring", "/hist"}
    for prog in (ex._jit_roll, ex._jit_trunc):
        text = prog.lower(ex.carry, 1).as_text(debug_info=True)
        assert {"/causal-log", "/inflight-ring"} <= set(re.findall(
            r"/causal-log|/inflight-ring", text))
    # metadata only: the program text without debug info does not name them
    plain = ex._jit_roll.lower(ex.carry, 1).as_text()
    assert "causal-log" not in plain
    replayer = runner.failover.programs.replayer(1, 0)
    assert replayer.vertex_name == "window"
