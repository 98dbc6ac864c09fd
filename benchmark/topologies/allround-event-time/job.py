"""The ``allround-event-time`` topology on the program's job API: the
operator chain of apache/flink's ``DataStreamAllroundTestProgram`` as
``configs/allround-upstream.json`` describes it (and lists where it
departs). Its plain reference is ``reference.py`` beside it."""

from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any]):
    """host source -> event-time assigner -> keyBy -> keyed-state mapper
    (running count per key) -> operator-state mapper -> keyBy -> tumbling
    event-time window -> { keyBy -> sliding event-time window over the
    tumbling output } ; both windows' outputs -> keyBy -> union ->
    transactional sink. Every vertex at ``parallelism``."""
    import jax.numpy as jnp

    from clonos_tpu.api.environment import StreamEnvironment

    p, nk = cfg["parallelism"], cfg["num_keys"]
    tick, bound = cfg["clock_ms_per_step"], cfg["max_out_of_order_ms"]
    lag_span = cfg["max_lag_ms"] + 1
    slide = cfg["slide_ms"]

    def assign(keys, vals, step):
        # the source stamped the step it pulled the record in; the record
        # lags the clock by what its value lane says; it then counts 1
        return keys, jnp.ones_like(vals), tick * step - vals % lag_span

    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            default_edge_capacity=cfg["edge_capacity"])
    tumbling = (env.host_source(batch_size=cfg["batch"], parallelism=p)
                .map(assign, name="event-time", capacity=cfg["batch"])
                .key_by().reduce(num_keys=nk, name="keyed-state")
                .count_through(name="operator-state")
                .key_by().window_event_time(
                    num_keys=nk, window_size=cfg["tumbling_ms"],
                    out_of_orderness=bound, name="tumbling"))
    sliding = tumbling.key_by().window_slide_event_time(
        num_keys=nk, window_size=slide * cfg["slide_factor"], slide=slide,
        out_of_orderness=bound, name="sliding")
    (tumbling.key_by().union(sliding.key_by(),
                             capacity=cfg["union_capacity"])
        .sink(parallelism=p, transactional=True,
              capacity=cfg["union_capacity"]))
    return env.build()
