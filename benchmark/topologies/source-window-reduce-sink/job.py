"""The ``source-window-reduce-sink`` topology on the program's job API: a
copy of ``chip_smoke.build_served_job``, kept here because later PRs may
change that file and may not change the yardstick. Its plain reference
is ``reference.py`` beside it."""

from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any]):
    """host source -> keyBy -> tumbling count window -> keyBy -> running
    reduce -> transactional sink, every vertex at ``parallelism``."""
    from clonos_tpu.api.environment import StreamEnvironment

    p = cfg["parallelism"]
    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            default_edge_capacity=cfg["edge_capacity"])
    (env.host_source(batch_size=cfg["batch"], parallelism=p)
        .key_by().window_count(num_keys=cfg["num_keys"],
                               window_size=cfg["window_steps"],
                               parallelism=p)
        .key_by().reduce(num_keys=cfg["num_keys"], parallelism=p)
        .sink(parallelism=p, transactional=True))
    return env.build()
