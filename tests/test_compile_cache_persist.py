"""Compile-cache persistence across process restarts: a fresh subprocess
restoring the same job must HIT the persistent cache its predecessor
wrote — the restarted standby pays cache-deserialize, not XLA recompile,
for the first-step executable. The cache has one directory per process,
placeable from outside: with ``JAX_COMPILATION_CACHE_DIR`` set nothing
in the program moves it, with or without a mesh.
"""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One restore cycle of a tiny job in a clean interpreter whose cache
# directory came in through the environment: enable the cache the way an
# entry point does, build an unsharded and a 2-device-mesh runner, run
# an epoch on each, AOT-lower the first-step program, and report where
# the cache ended up and what it holds.
_PROBE = """
import json, os, sys
from clonos_tpu.utils.compile_cache import (aot_lower_first_step,
                                            enable_compile_cache)
used = enable_compile_cache()
import jax
from clonos_tpu.api.environment import StreamEnvironment
from clonos_tpu.parallel.distributed import task_mesh
from clonos_tpu.runtime.cluster import ClusterRunner

def runner(ck, mesh):
    env = StreamEnvironment(name="persist", num_key_groups=8)
    (env.synthetic_source(vocab=7, batch_size=4, parallelism=2)
        .key_by().window_count(num_keys=7, window_size=1 << 30,
                               parallelism=2).sink(parallelism=2))
    r = ClusterRunner(env.build(), steps_per_epoch=4, log_capacity=256,
                      max_epochs=8, inflight_ring_steps=16, seed=5,
                      checkpoint_dir=ck, mesh=mesh)
    r.run_epoch(complete_checkpoint=True)
    return r

r = runner(sys.argv[1] + "-a", None)
aot_lower_first_step(r.executor)
runner(sys.argv[1] + "-b", task_mesh(max_devices=2))
print(json.dumps({
    "used": used, "config_dir": jax.config.jax_compilation_cache_dir,
    "entries": sorted(f for f in os.listdir(used)
                      if f.endswith("-cache"))}))
"""


def _run_probe(cache_dir, ck_dir):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(ck_dir)],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_fresh_process_hits_cache_placed_from_outside(tmp_path):
    """Restart cycle: process 1 populates the directory the environment
    names, process 2 (same jobs, fresh interpreter) must add ZERO new
    entries — every compile, including the AOT first-step lower and the
    mesh-sharded programs, was a cache hit — and in both the runners
    left ``jax_compilation_cache_dir`` where the environment put it."""
    shared = tmp_path / "cache"
    p1 = _run_probe(shared, tmp_path / "ck1")
    assert p1["entries"], "first process must populate the persistent cache"
    p2 = _run_probe(shared, tmp_path / "ck2")
    assert p2["entries"] == p1["entries"], \
        "restarted process recompiled (new persistent entries appeared)"
    for p in (p1, p2):
        assert p["used"] == p["config_dir"] == str(shared)


def test_unset_environment_means_the_checkout(monkeypatch):
    """Without ``JAX_COMPILATION_CACHE_DIR`` the cache is the fixed
    ``<checkout>/.jax_cache``; restore the session's directory after
    (conftest owns it)."""
    from clonos_tpu.utils.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
