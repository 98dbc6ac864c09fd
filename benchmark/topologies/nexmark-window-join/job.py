"""The ``nexmark-window-join`` topology on the program's job API: NEXmark
query 8, "Monitor New Users" — persons joined with the auctions they
opened in the tumbling event-time window they registered in — as
``configs/nexmark-q8.json`` describes it (and lists where it departs).
Its plain reference is ``reference.py`` beside it."""

from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any]):
    """host source -> map (reads the record: its kind, person id or
    seller id, reserve and event time, out of the feed's two lanes and
    the step the source stamped) -> { filter persons -> keyBy ;
    filter auctions -> keyBy } -> tumbling event-time window join on
    id = seller -> keyBy -> transactional sink. Every vertex at
    ``parallelism``."""
    from clonos_tpu.api.environment import StreamEnvironment

    p, tick = cfg["parallelism"], cfg["clock_ms_per_step"]
    every = cfg["person_every"]

    def parse(keys, vals, step):
        # the key lane is the person's id or the auction's seller; the
        # value lane is the reserve, and what stands for the fields the
        # two-lane feed has no room for: its residue the record's kind,
        # its higher bits where inside its step the event fell
        return keys, vals, tick * step + (vals // every) % cfg["spread_ms"]

    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            sharing_depth=cfg["sharing_depth"],
                            default_edge_capacity=cfg["batch"])
    events = (env.host_source(batch_size=cfg["batch"], parallelism=p)
              .map(parse, name="parse", capacity=cfg["batch"]))
    persons = events.filter(lambda k, v, t: v % every == 0, name="persons")
    auctions = events.filter(lambda k, v, t: v % every != 0, name="auctions")
    (persons.key_by().window_join(
        auctions.key_by(), num_keys=cfg["num_keys"],
        window_size=cfg["window_ms"],
        out_of_orderness=cfg["max_out_of_order_ms"],
        capacity=cfg["join_capacity"], edge_capacity=cfg["edge_capacity"],
        name="join")
     .key_by().sink(parallelism=p, transactional=True,
                    capacity=cfg["join_capacity"]))
    return env.build()
