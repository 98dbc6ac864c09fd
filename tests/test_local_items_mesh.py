"""``nexmark-local-items`` (NEXmark Q3's join, ``tests/test_local_items.py``)
deployed as ``nexmark-q3-x4`` deploys it: over a task mesh of four
devices at the default determinant sharing depth, hit by the connected
failure of one subtask of every vertex on the auctions' path. At a tiny
size on forced host devices: the committed stream against the NumPy
reference and the carry against the unsharded program's bit for bit,
epoch by epoch, through the four-victim cascade; what a sharing depth
buys and what it does not (every subtask of a holder vertex keeps a
copy, so depth 1 survives that kill: it takes the loss of every holder
one edge down to need depth 2); no program built by the kill after
set-up's drill; and the carry built under its shardings."""

import json
import os
import sys

import jax
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
from clonos_tpu.causal.recovery import RecoveryError  # noqa: E402
from clonos_tpu.parallel import distributed as dist  # noqa: E402
from clonos_tpu.runtime.executor import (CompiledJob,  # noqa: E402
                                         canonical_carry)

SOURCE, PARSE, PERSONS, AUCTIONS, JOIN, SINK = range(6)
PATH = (SOURCE, PARSE, AUCTIONS, JOIN)     # the auctions' path
CHIPS = 4


def config(**over):
    with open(os.path.join(BENCH, "tests", "tiny", "bench", "configs",
                           "tiny-nexmark-q3-x4.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


def make(cfg, tmp_path, tag, chips=CHIPS):
    stream = job.make_stream(cfg, {"table_epochs": 2}, 11)
    runner = job.make_runner(cfg, stream, 11, str(tmp_path / tag), chips)
    (txn,) = runner.txn_logs.values()
    got = {}
    txn.committer = lambda e, rows: got.setdefault(e, []).append(
        np.asarray(rows))
    return runner, stream, got


def flats(runner, victims):
    return [runner.job.subtask_base(v) + s for v, s in victims]


def into_kill_position(runner):
    runner.run_epoch(complete_checkpoint=True)
    runner.run_epoch(complete_checkpoint=False)
    runner.run_epoch(complete_checkpoint=False)
    runner.drain_fence()


def host(carry):
    """``(path, array)`` of every leaf of the canonical carry."""
    return [(dist._path_str(p), np.asarray(x)) for p, x in
            jax.tree_util.tree_flatten_with_path(
                jax.device_get(canonical_carry(carry)))[0]]


def test_the_cascade_on_the_mesh_is_the_reference_and_the_unsharded_run(
        tmp_path):
    """One subtask of every vertex on the auctions' path fails together
    on the mesh, behind two epochs whose checkpoints stay pending: the
    source replays from its feed, parse and the filter from rings whose
    shard a victim upstream has just rebuilt, the join's two inputs from
    two rings through two dynamic exchanges. The committed stream is the
    reference's, before and after, and at every epoch's end the carry is
    bit for bit that of the unsharded program through the same kill (a
    recovery leaves its mark in the survivors' logs: the checkpoints
    they were told to ignore)."""
    cfg = config()
    ref = module_at(job.topology_file(cfg, "reference.py"))
    sharded, stream, got = make(cfg, tmp_path, "mesh")
    plain, _, _ = make(cfg, tmp_path, "plain", chips=1)
    victims = [tuple(v) for v in cfg["kill"]["victims"]]
    assert [v for v, _ in victims] == list(PATH)
    for epoch in range(8):
        if epoch == 3:
            for r in (sharded, plain):
                r.run_epoch(complete_checkpoint=False)
                r.run_epoch(complete_checkpoint=False)
                r.inject_failure(flats(r, victims))
                report = r.recover()
                assert report.steps_replayed == 2 * cfg["steps_per_epoch"]
                assert (report.victims, report.fetch_hops) == (4, 1)
        sharded.run_epoch(complete_checkpoint=True)
        plain.run_epoch(complete_checkpoint=True)
        for (path, a), (_, b) in zip(host(sharded.executor.carry),
                                     host(plain.executor.carry)):
            np.testing.assert_array_equal(a, b, err_msg=f"{path} @ {epoch}")
    sharded.drain_fence()
    assert sharded.executor.check_overflow() == []
    epochs = sharded.executor.epoch_id
    want = ref.expected(cfg, stream.keys, stream.vals, epochs)
    bad, failed, compared = ref.check(got, want, cfg, epochs)
    assert (bad, failed) == (0, []) and compared > 1000


@pytest.mark.parametrize("depth,survives", [(1, False), (2, True),
                                            (-1, True)])
def test_what_a_sharing_depth_survives(tmp_path, depth, survives):
    """Every subtask of a vertex within the depth keeps a copy of a
    task's determinants (``replication_factor`` -1), so the kill of one
    subtask a vertex leaves depth 1 its holders. The failure that needs
    depth 2 takes every holder one edge down: a source subtask and all
    of ``parse``. At depth 1 it is refused, naming the log and who held
    it, before anything is replayed; from depth 2 on the source's
    determinants come from a filter subtask two edges down."""
    cfg = config(sharing_depth=depth)
    runner, _, _ = make(cfg, tmp_path, f"depth{depth}")
    into_kill_position(runner)
    p = cfg["parallelism"]
    runner.inject_failure(flats(
        runner, [(SOURCE, 5)] + [(PARSE, s) for s in range(p)]))
    if not survives:
        with pytest.raises(RecoveryError) as err:
            runner.recover()
        text = str(err.value)
        assert "source[5]" in text and "no surviving replica" in text
        assert all(f"parse[{s}]" in text for s in range(p))
        assert "sharing depth 1" in text
        return
    report = runner.recover()
    assert (report.victims, report.fetch_hops) == (1 + p, 2)
    assert report.steps_replayed == 2 * cfg["steps_per_epoch"]
    runner.run_epoch(complete_checkpoint=True)
    assert runner.executor.check_overflow() == []


def test_depth_1_survives_the_cells_kill(tmp_path):
    """The cell's own kill at depth 1: each victim's log is still held
    by the other subtasks of the vertex one edge down."""
    cfg = config(sharing_depth=1)
    runner, _, _ = make(cfg, tmp_path, "depth1-cell")
    into_kill_position(runner)
    runner.inject_failure(flats(runner, cfg["kill"]["victims"]))
    report = runner.recover()
    assert (report.victims, report.fetch_hops) == (4, 1)


def test_after_the_drill_the_kill_builds_no_program(tmp_path):
    """Set-up as the benchmark makes it — a warm epoch, the prewarm, the
    drill by the cell's own recipe on other subtasks — leaves the kill
    and its recovery nothing to build or fetch (the tracer's
    ``compile.programs``), the fewer-holders ``fetch_meta`` included."""
    cfg = config()
    runner, _, _ = make(cfg, tmp_path, "drilled")
    runner.run_epoch(complete_checkpoint=True)
    runner.prewarm_recovery()
    runner.run_epoch(complete_checkpoint=False)
    runner.run_epoch(complete_checkpoint=False)
    runner.drain_fence()
    runner.failover_drill(flats(runner, cfg["drill"]["victims"]))
    runner.run_epoch(complete_checkpoint=True)
    runner.drain_fence()
    obs.trace.install_compile_listener()
    tracer = obs.get_tracer()
    before = tracer.counters()
    runner.run_epoch(complete_checkpoint=False)
    runner.run_epoch(complete_checkpoint=False)
    runner.inject_failure(flats(runner, cfg["kill"]["victims"]))
    report = runner.recover()
    runner.run_epoch(complete_checkpoint=True)
    after = tracer.counters()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    assert grew("compile.programs") == 0, [
        c["args"]["fun_name"] for c in tracer.records()
        if c["name"] == "compile"][-grew("compile.programs"):]
    assert report.steps_replayed == 2 * cfg["steps_per_epoch"]
    assert grew("recovery.victims") == 4       # the drill counts nothing
    assert grew("recovery.fetch_hops") == 1


def test_the_carry_is_built_under_its_shardings():
    """``build_carry`` on the mesh equals the unsharded build leaf for
    leaf, lays every leaf out by its partition rule — every leaf of the
    join's state leads with the subtask axis — and leaves no device more
    than its share of a sharded leaf; the counters say so."""
    cfg = config()
    mesh = dist.task_mesh(max_devices=CHIPS)

    def compiled(mesh):
        return CompiledJob(
            module_at(job.topology_file(cfg, "job.py")).build(cfg),
            log_capacity=cfg["log_capacity"], max_epochs=cfg["max_epochs"],
            inflight_ring_steps=cfg["inflight_ring_steps"], mesh=mesh)

    tracer = obs.get_tracer()
    before = tracer.counters()
    on_mesh = compiled(mesh)
    carry = on_mesh.build_carry()
    after = tracer.counters()
    plain = compiled(None).build_carry()
    paths = [dist._path_str(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(carry)[0]]
    specs = jax.tree_util.tree_leaves(
        dist.infer_partition_spec(carry, mesh),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    leaves, plain_leaves = (jax.tree_util.tree_leaves(c)
                            for c in (carry, plain))
    assert len(paths) == len(leaves) == len(plain_leaves) == len(specs)
    seen, fullest = set(), {}
    for path, leaf, other, spec in zip(paths, leaves, plain_leaves, specs):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(other))
        assert leaf.sharding.spec == spec, path
        if path.startswith(f"op_states/{JOIN}/"):
            assert leaf.shape[0] == cfg["parallelism"], path
            assert spec == jax.sharding.PartitionSpec("tasks"), path
        share = leaf.nbytes // (CHIPS if any(spec) else 1)
        for shard in leaf.addressable_shards:
            assert shard.data.nbytes == share, path
            fullest[shard.device] = (fullest.get(shard.device, 0)
                                     + shard.data.nbytes)
            here = (shard.device, shard.data.unsafe_buffer_pointer())
            assert here not in seen, f"{path} shares a buffer"
            seen.add(here)
    grew = lambda name: after.get(name, 0) - before.get(name, 0)
    total = sum(leaf.nbytes for leaf in leaves)
    assert grew("carry.bytes") == total
    assert grew("carry.max_device_bytes") == max(fullest.values())
    # the logs, the replicas and the rings are all of it but kilobytes
    assert max(fullest.values()) < 0.26 * total
    built = [r for r in tracer.records() if r["name"] == "setup.init-carry"]
    assert built[-2]["args"]["max_device_bytes"] == max(fullest.values())


def test_a_row_of_a_sharded_stack_is_read_without_gathering_the_stack():
    """``take_row`` over a mesh: a chip reads the row out of its own
    shard and one all-reduce of the row hands it to all — no all-gather
    of the stack, which at the default sharing depth is 14 GiB a chip
    could not hold; a stack the mesh does not divide is indexed as on
    one device."""
    import re

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from clonos_tpu.causal import log as clog
    from clonos_tpu.runtime.recovery_programs import take_row
    mesh = dist.task_mesh(max_devices=CHIPS)
    stack = jax.vmap(lambda i: clog.create(64, 4)._replace(
        rows=jnp.full((64, 8), i, jnp.int32), head=i * 3))(jnp.arange(12))
    sharded = jax.device_put(stack, NamedSharding(
        mesh, PartitionSpec("tasks")))
    read = jax.jit(lambda s, r: take_row(s, r, mesh, "tasks"))
    for r in (0, 2, 3, 7, 11):
        got = read(sharded, jnp.asarray(r, jnp.int32))
        assert int(got.head) == 3 * r and got.rows.shape == (64, 8)
        np.testing.assert_array_equal(np.asarray(got.rows), r)
    text = read.lower(sharded, jnp.asarray(0, jnp.int32)).compile().as_text()
    assert not re.findall(r" all-gather(?:-start)?\(", text)
    assert re.findall(r" all-reduce(?:-start)?\(", text)
    odd = jax.tree_util.tree_map(lambda x: x[:10], stack)   # 10 % 4 != 0
    got = take_row(odd, jnp.asarray(9, jnp.int32), mesh, "tasks")
    assert int(got.head) == 27
