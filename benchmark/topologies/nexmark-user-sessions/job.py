"""The ``nexmark-user-sessions`` topology on the program's job API:
NEXmark query 11, "User Sessions" — how many bids a user made in each
session of activity — as ``configs/nexmark-q11.json`` describes it (and
lists where it departs). Its plain reference is ``reference.py`` beside
it."""

from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any]):
    """host source (bids only) -> map ``parse`` (bidder id and event
    time out of the value lane and the step the source stamped; the key
    lane is not read) -> keyBy(bidder) -> ``sessions``: event-time
    session windows per bidder over the columns each subtask owns, one
    row a closed session -> keyBy -> transactional sink."""
    import jax.numpy as jnp

    from clonos_tpu.api.environment import StreamEnvironment

    p, tick = cfg["parallelism"], cfg["clock_ms_per_step"]
    every, active = cfg["hot_bidder_every"], cfg["active_people"]
    bits = cfg["value_lane"]

    def parse(keys, vals, step):
        # the value lane stands for the fields the two-lane feed has no
        # room for: two bits say whether the bid is the hot bidder's
        # (unless they read 0: 3 in 4), ten where inside its step the
        # event fell, the rest which of the active people a cold bid is
        ts = tick * step + ((vals >> bits["offset_shift"])
                            & bits["offset_mask"]) % cfg["spread_ms"]
        last = ts // cfg["person_every_ms"]      # the newest person's id
        hot = ((vals >> bits["hot_shift"]) & bits["hot_mask"]) \
            % cfg["hot_ratio"] != 0
        bidder = jnp.where(
            hot, last // every * every + 1,
            last - (active - 1) + (vals >> bits["cold_shift"])
            % (active + cfg["person_id_lead"]))
        # a bid counts 1; the id ring holds every id a session can see
        return bidder % cfg["num_keys"], jnp.ones_like(vals), ts

    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            sharing_depth=cfg["sharing_depth"],
                            default_edge_capacity=cfg["batch"])
    (env.host_source(batch_size=cfg["batch"], parallelism=p)
     .map(parse, name="parse", capacity=cfg["batch"])
     .key_by().window_session(
         num_keys=cfg["num_keys"], gap=cfg["gap_ms"],
         out_of_orderness=cfg["max_out_of_order_ms"],
         capacity=cfg["session_capacity"], own_columns=cfg["own_columns"],
         edge_capacity=cfg["edge_capacity"], name="sessions")
     .key_by().sink(parallelism=p, transactional=True,
                    capacity=cfg["session_capacity"]))
    return env.build()
