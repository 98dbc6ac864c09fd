"""Rule-driven carry partitioning (parallel/distributed.py),
per-shard snapshot slicing (runtime/checkpoint.py), and the lint's
pjit/shard_map traced-scope detection — all host-side and fast."""

import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from clonos_tpu.parallel import distributed as dist

P = jax.sharding.PartitionSpec

needs2 = pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")


def _tree(n=8):
    """A fake carry with one leaf per partition-rule family."""
    return {
        "op_states": [{"acc": jnp.zeros((n, 4))}],
        "out_rings": [{"keys": jnp.zeros((3, n, 16)),
                       "head": jnp.zeros((3,), jnp.int32)}],
        "logs": {"rows": jnp.zeros((3 * n, 5))},
        "rr_offsets": {"window": jnp.zeros((n,), jnp.int32)},
        "record_counts": jnp.zeros((3 * n,), jnp.int32),
        "epoch": jnp.zeros((), jnp.int32),
    }


@needs2
def test_partition_rules_per_leaf_family():
    mesh = dist.task_mesh(max_devices=2)
    spec = dist.infer_partition_spec(_tree(8), mesh)
    assert spec["op_states"][0]["acc"] == P("tasks")
    assert spec["out_rings"][0]["keys"] == P(None, "tasks"), \
        "ring tensors shard the subtask axis (axis 1 of [S, P, cap])"
    assert spec["out_rings"][0]["head"] == P(), "ring scalars replicate"
    assert spec["logs"]["rows"] == P("tasks")
    assert spec["rr_offsets"]["window"] == P(), "rr offsets replicate"
    assert spec["record_counts"] == P("tasks")
    assert spec["epoch"] == P(), "unmatched scalars replicate"


@needs2
def test_partition_rules_divisibility_guard():
    mesh = dist.task_mesh(max_devices=2)
    tree = {"op_states": [{"odd": jnp.zeros((7, 4))}],
            "logs": {"rows": jnp.zeros((0, 5))}}
    spec = dist.infer_partition_spec(tree, mesh)
    assert spec["op_states"][0]["odd"] == P(), \
        "a dim not divisible by the mesh replicates instead of failing"
    assert spec["logs"]["rows"] == P(), "zero-size dims never shard"


@needs2
def test_named_shardings_wrap_the_specs():
    mesh = dist.task_mesh(max_devices=2)
    ns = dist.named_shardings(_tree(8), mesh)
    leaf = ns["op_states"][0]["acc"]
    assert isinstance(leaf, jax.sharding.NamedSharding)
    assert leaf.spec == P("tasks") and leaf.mesh.shape["tasks"] == 2


def test_snapshot_subtask_slice_and_nbytes():
    from clonos_tpu.runtime import checkpoint as cp

    snap = types.SimpleNamespace(op_states={
        1: {"a": np.zeros((4, 3), np.float32),
            "s": np.float32(0.0)}})
    sl = cp.snapshot_subtask_slice(snap, 1, 2)
    assert sl["a"].shape == (1, 3), "one [P, ...] row, batch dim kept"
    # One row of `a` (3 floats) + the scalar: 12 + 4 bytes.
    assert cp.snapshot_subtask_nbytes(snap, 1, 2) == 16
    full = sum(x.nbytes for x in (snap.op_states[1]["a"],
                                  snap.op_states[1]["s"]))
    assert cp.snapshot_subtask_nbytes(snap, 1, 2) < full


def test_lint_flags_pjit_and_shard_map_scopes(tmp_path, monkeypatch):
    from clonos_tpu.lint import run_lint

    monkeypatch.chdir(tmp_path)
    (tmp_path / "m.py").write_text(textwrap.dedent("""\
        from jax.experimental.pjit import pjit
        from jax.experimental.shard_map import shard_map

        @pjit
        def f(x):
            print(x)
            return x

        @shard_map
        def g(y):
            if y > 0:
                return y
            return -y
        """))
    res = run_lint(["m.py"], use_waivers=False)
    hits = {(f.rule, f.line) for f in res.findings}
    assert ("host-callback", 6) in hits, \
        "host call inside a pjit-wrapped def must be flagged"
    assert ("host-branch", 11) in hits, \
        "host branch inside a shard_map-wrapped def must be flagged"
