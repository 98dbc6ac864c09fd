"""The system under test, built from a configuration file: the job (by
the ``topologies/<topology>/job.py`` the configuration names), its
``ClusterRunner`` and its feed. With those job files, the one part of
the yardstick that imports the program.

The runner's sizing is a copy of ``chip_smoke.run_served`` /
``run_headline``, kept here because later PRs may change those files and
may not change the yardstick.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchlib.byname import module_at
from benchlib.stream import TableFeedReader, TableStream

TOPOLOGIES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "topologies")


def topology_file(cfg: Dict[str, Any], which: str) -> str:
    """``job.py`` or ``reference.py`` of the configuration's topology."""
    return os.path.join(TOPOLOGIES, cfg["topology"], which)


def make_stream(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int
                ) -> TableStream:
    return TableStream(
        seed, partitions=cfg["parallelism"], batch=cfg["batch"],
        table_steps=traffic["table_epochs"] * cfg["steps_per_epoch"],
        num_keys=cfg["num_keys"], value_bits=cfg["value_bits"],
        key_dist=cfg["key_dist"])


def make_runner(cfg: Dict[str, Any], stream: TableStream, seed: int,
                checkpoint_dir: str, chips: int):
    """The deployment as its file sizes it, on logical causal time, over
    a task mesh when the cell asks for four chips."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    mesh = None
    if chips > 1:
        from clonos_tpu.parallel import distributed
        mesh = distributed.task_mesh(max_devices=chips)
    job = module_at(topology_file(cfg, "job.py")).build(cfg)
    runner = ClusterRunner(
        job, steps_per_epoch=cfg["steps_per_epoch"],
        log_capacity=cfg["log_capacity"], max_epochs=cfg["max_epochs"],
        inflight_ring_steps=cfg["inflight_ring_steps"],
        recovery_block_steps=cfg["recovery_block_steps"],
        block_steps=cfg["block_steps"], seed=int(seed) % (1 << 32),
        overlap_epoch=cfg["overlap_epoch"], logical_time=True,
        audit=False,     # no cell audits yet: PERF.md, Open questions
        checkpoint_dir=checkpoint_dir, mesh=mesh)
    for vid in runner.executor.compiled.feed_vertices:
        runner.executor.register_feed(vid, TableFeedReader(stream))
    return runner
