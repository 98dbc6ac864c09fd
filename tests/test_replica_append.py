"""The replica logs' append, run by run at the owner's offset
(``causal/replication.append_block`` -> ``causal/log.append_runs``), on
the replication plans of every benchmark topology's tiny stand-in:

- it writes what the batched append it replaced writes
  (``v_append_full(replicas, rows[owner_idx])``): equal heads, equal
  rows over the whole ring and so ``_canon_log``-equal, with heads that
  differ between owners, offsets off the slot grid, chunks that wrap
  the ring, ``cap == 4n`` and ``cap == 32n``, runs of more than one
  length, no replica at all, and the shapes that keep the batched form;
- what it rests on — a replica's ``head`` / ``tail`` / live rows equal
  its owner's — holds through blocks, single steps and single rows,
  fences, truncation, a kill and a recovery on each of them;
- the ``log.append`` instant says which form each block program took.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import job as benchjob  # noqa: E402
from benchlib.byname import module_at  # noqa: E402

from clonos_tpu.causal import log as clog  # noqa: E402
from clonos_tpu.causal import replication as rep  # noqa: E402
from clonos_tpu.obs import trace  # noqa: E402
from clonos_tpu.runtime.executor import _canon_log  # noqa: E402

#: tiny stand-in -> chips its cell runs on
TINY = {"tiny-kafka": 1, "tiny-allround": 1, "tiny-allround-x4": 4,
        "tiny-allround-upstream": 1, "tiny-nexmark-q8": 1,
        "tiny-nexmark-q5": 1}


def tiny_config(name):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def plan_of(name):
    cfg = tiny_config(name)
    job = module_at(benchjob.topology_file(cfg, "job.py")).build(cfg)
    return rep.ReplicationPlan.from_job(job, job.sharing_depth)


def test_runs_cover_the_pairs_in_order():
    for name in TINY:
        plan = plan_of(name)
        covered = []
        for start, k, owner in plan.runs:
            assert start == len(covered) and k >= 1
            assert all(o == owner for o, _ in plan.pairs[start:start + k])
            covered += [owner] * k
        assert covered == [o for o, _ in plan.pairs]
        # maximal: neighbours copy different owners
        owners = [o for _, _, o in plan.runs]
        assert all(a != b for a, b in zip(owners, owners[1:]))
    # ``nexmark-q5``'s last stages have parallelism 1: runs of 4 and of 1
    assert {k for _, k, _ in plan_of("tiny-nexmark-q5").runs} == {1, 4}


def _stack(rng, logs, cap, heads):
    """``logs`` empty logs at the given heads over a ring of garbage."""
    st = jax.vmap(lambda _: clog.create(cap, 8))(jnp.arange(logs))
    heads = jnp.asarray(heads, jnp.int32)
    return st._replace(
        rows=jnp.asarray(rng.randint(-99, 99, (logs, cap, 8)), jnp.int32),
        head=heads, tail=heads)


#: (capacity, rows a block, blocks, form the replicas take)
SHAPES = [
    (64, 16, 3, "runs"),      # cap == 4n (``kafka-window-64``), wraps
    (512, 16, 5, "runs"),     # cap == 32n (``allround-32``)
    (256, 32, 7, "runs"),     # cap == 8n, live rows wrap the ring twice
    (512, 4, 5, "scatter"),   # single steps: the batched row scatter
    (256, 12, 5, "window"),   # no whole slots of n: the batched form
]


@pytest.mark.parametrize("cap,n,blocks,form", SHAPES)
@pytest.mark.parametrize("name", sorted(TINY))
def test_run_wise_append_writes_what_the_batched_append_writes(
        name, cap, n, blocks, form):
    plan = plan_of(name)
    rng = np.random.RandomState(len(name) + cap + n)
    L, oi = plan.num_subtasks, plan.owner_index()
    # heads differ between owners, lie off the slot grid and near the wrap
    heads = rng.randint(0, 3 * cap, L)
    heads[0], heads[-1] = cap - 3, 2 * cap - n + 1
    own = _stack(rng, L, cap, heads)
    want = _stack(rng, plan.num_replicas, cap, heads[np.asarray(oi)])
    got = want
    tracer = trace.configure("replica-append-test")
    try:
        append = jax.jit(lambda st, rows, heads: rep.append_block(
            st, rows, heads, plan))
        for _ in range(blocks):
            rows = jnp.asarray(rng.randint(-9, 9, (L, n, 8)), jnp.int32)
            want = clog.v_append_full(want, rows[oi])
            got = append(got, rows, own.head)
            own = clog.v_append_full(own, rows)
        notes = [r["args"] for r in tracer.records()
                 if r["name"] == "log.append"]
    finally:
        trace.reset()
    assert notes == [dict(
        form=form, logs=plan.num_replicas, rows=n, capacity=cap,
        runs=len(plan.runs) if form == "runs" else 0)]
    np.testing.assert_array_equal(np.asarray(got.head), np.asarray(want.head))
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(want.rows))
    canon = jax.vmap(_canon_log)
    for a, b in zip(canon(got), canon(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the replicas' live rows are their owners'
    np.testing.assert_array_equal(
        np.asarray(canon(got).rows), np.asarray(canon(own).rows)[oi])


def test_a_plan_without_replicas_appends_nothing():
    plan = rep.ReplicationPlan((), 4)
    assert plan.runs == ()
    replicas = rep.create_replicas(plan, 64, 8)
    out = rep.append_block(replicas, jnp.ones((4, 16, 8), jnp.int32),
                           jnp.zeros((4,), jnp.int32), plan)
    assert out is replicas


def test_run_wise_append_refuses_a_block_without_whole_slots():
    plan = plan_of("tiny-kafka")
    st = _stack(np.random.RandomState(0), plan.num_replicas, 256, [0] * 96)
    with pytest.raises(ValueError, match="run-wise"):
        clog.append_runs(st, jnp.zeros((16, 12, 8), jnp.int32),
                         jnp.zeros((16,), jnp.int32), plan.runs)


# --- the invariant the form rests on, through a job's life -----------------


def _assert_replicas_equal_owners(runner, when):
    compiled = runner.executor.compiled
    oi = np.asarray(compiled.plan.owner_index())
    carry = runner.executor.carry
    own = jax.vmap(_canon_log)(carry.logs)
    held = jax.vmap(_canon_log)(carry.replicas)
    for field, a, b in zip(own._fields, held, own):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)[oi],
            err_msg=f"{when}: replicas' {field} differ from their owners'")


@pytest.mark.parametrize("name", sorted(TINY))
def test_replicas_equal_their_owners_through_a_jobs_life(name, tmp_path):
    cfg = tiny_config(name)
    stream = benchjob.make_stream(cfg, {"table_epochs": 2}, 11)
    tracer = trace.configure("replica-invariant-test")
    try:
        runner = benchjob.make_runner(cfg, stream, 11, str(tmp_path / "ck"),
                                      TINY[name])
        assert runner.executor.compiled.plan.num_replicas > 0
        _assert_replicas_equal_owners(runner, "at the start")
        # blocks, the fence's single rows and roll, truncation
        runner.run_epoch(complete_checkpoint=True)
        _assert_replicas_equal_owners(runner, "after a completed epoch")
        heads = np.asarray(runner.executor.carry.logs.head)
        # the kill recipe: pending epochs, single steps, the kill
        for _ in range(cfg["kill"]["uncompleted_epochs"]):
            runner.run_epoch(complete_checkpoint=False)
        _assert_replicas_equal_owners(runner, "after the pending epochs")
        for _ in range(cfg["kill"]["steps_into_epoch"] or 3):
            runner.step()
        _assert_replicas_equal_owners(runner, "after single steps")
        runner.inject_failure(
            [runner.job.subtask_base(v) + s for v, s in
             cfg["kill"]["victims"]])
        runner.recover()
        _assert_replicas_equal_owners(runner, "after the recovery")
        runner.run_epoch(complete_checkpoint=True)
        runner.run_epoch(complete_checkpoint=True)
        _assert_replicas_equal_owners(runner, "after the epochs behind it")
        forms = {(r["args"]["logs"], r["args"]["rows"]): r["args"]["form"]
                 for r in tracer.records() if r["name"] == "log.append"}
    finally:
        trace.reset()
    # SOURCE_CHECKPOINT rows go to the sources alone: heads differ
    # between owners (what makes the offset a run's, not the stack's)
    assert len(set(heads.tolist())) > 1
    # which form each block program took, by what the code can see
    compiled = runner.executor.compiled
    n, cap = 4 * cfg["block_steps"], cfg["log_capacity"]
    assert forms[(compiled.L, n)] == clog.append_form(n, cap) != "scatter"
    assert forms[(compiled.plan.num_replicas, n)] == (
        "runs" if TINY[name] == 1 else clog.append_form(n, cap))
    # a single step's four rows: a scatter where the ring is long
    assert forms[(compiled.plan.num_replicas, 4)] == (
        "scatter" if cap > 256 else "runs")
