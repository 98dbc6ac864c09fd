"""The ``nexmark-hot-items`` topology on the program's job API: NEXmark
query 5, "Hot Items" — per sliding event-time window the auctions with
the most bids — as ``configs/nexmark-q5.json`` describes it (and lists
where it departs). Its plain reference is ``reference.py`` beside it."""

from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any]):
    """host source (bids only) -> map ``parse`` (auction id and event
    time out of the value lane and the step the source stamped; the key
    lane, the bidder, is not read) -> keyBy(auction) -> ``count``: a
    sliding event-time count per auction over the columns each subtask
    owns, that passes on each subtask's leaders -> keyBy -> ``max`` at
    parallelism 1: per window the leaders over all subtasks'
    (``windowAll``) -> transactional sink at parallelism 1."""
    import jax.numpy as jnp

    from clonos_tpu.api.environment import StreamEnvironment

    p, tick = cfg["parallelism"], cfg["clock_ms_per_step"]
    per_ms = cfg["auctions_per_ms"]              # [3, 5]: 600 a second
    batch_of, in_flight = cfg["hot_auction_every"], cfg["in_flight_auctions"]
    bits = cfg["value_lane"]

    def parse(keys, vals, step):
        # the value lane stands for the fields the two-lane feed has no
        # room for: one bit says whether the bid is on the hot auction,
        # ten where inside its step the event fell, the rest which of
        # the auctions in flight a cold bid is on
        ts = tick * step + ((vals >> bits["offset_shift"])
                            & bits["offset_mask"]) % cfg["spread_ms"]
        last = ts * per_ms[0] // per_ms[1]       # the newest auction's id
        hot = (vals >> bits["hot_shift"]) % cfg["hot_ratio"] == 1
        auction = jnp.where(
            hot, last // batch_of * batch_of,
            last - (vals >> bits["cold_shift"]) % (in_flight + 1))
        # a bid counts 1; the id ring holds every id a window can see
        return auction % cfg["num_keys"], jnp.ones_like(vals), ts

    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            sharing_depth=cfg["sharing_depth"],
                            default_edge_capacity=cfg["batch"])
    (env.host_source(batch_size=cfg["batch"], parallelism=p)
     .map(parse, name="parse", capacity=cfg["batch"])
     .key_by().window_top(
         num_keys=cfg["num_keys"], window_size=cfg["window_ms"],
         slide=cfg["slide_ms"], out_of_orderness=cfg["max_out_of_order_ms"],
         capacity=cfg["partial_capacity"], own_columns=cfg["own_columns"],
         edge_capacity=cfg["edge_capacity"], name="count")
     # at parallelism 1 every key's target is subtask 0: what keyBy(window
     # end) over one subtask gives
     .key_by().window_top(
         num_keys=cfg["num_keys"], window_size=cfg["slide_ms"],
         out_of_orderness=cfg["top_out_of_order_ms"],
         capacity=cfg["top_capacity"],
         edge_capacity=cfg["partial_edge_capacity"], name="max",
         parallelism=1)
     .sink(parallelism=1, transactional=True, capacity=cfg["top_capacity"]))
    return env.build()
