"""The ``nexmark-local-items`` topology on the program's job API: NEXmark
query 3, "Local Item Suggestion" — who is selling in OR, ID or CA in
category 10 — the full-history join of persons and the auctions they
sell, as ``configs/nexmark-q3.json`` describes it (and lists where it
departs). Its plain reference is ``reference.py`` beside it."""

from __future__ import annotations

from typing import Any, Dict


def build(cfg: Dict[str, Any]):
    """host source (persons and auctions, 1 : 3) -> map ``parse`` (the
    record's person id or seller id and its event time, out of the
    feed's two lanes and the step the source stamped) -> { filter
    ``persons`` of the local states -> keyBy ; filter ``auctions`` of the
    category -> keyBy } -> ``join``: a person registers its id for
    ``ttl_ms``, an auction whose seller is registered is a row, any
    other waits for its seller -> keyBy -> transactional sink. Every
    vertex at ``parallelism``."""
    import jax.numpy as jnp

    from clonos_tpu.api.environment import StreamEnvironment

    p, tick = cfg["parallelism"], cfg["clock_ms_per_step"]
    every, spread = cfg["person_every"], cfg["spread_ms"]
    hot_every, active = cfg["hot_seller_every"], cfg["active_people"]

    def rest(vals):
        # the value lane stands for the fields the two-lane feed has no
        # room for: its residue the record's kind, above it where inside
        # its step the event fell, above that the person's state, or the
        # auction's category and whether its seller is the hot one
        return (vals // every) // spread

    def parse(keys, vals, step):
        ts = tick * step + (vals // every) % spread
        last = ts // cfg["person_every_ms"]    # the newest person's number
        hot = (rest(vals) // cfg["categories"]) % cfg["hot_ratio"] != 0
        seller = jnp.where(
            hot, last // hot_every * hot_every,
            last - (active - 1) + keys % (active + cfg["person_id_lead"]))
        # a person is numbered as the generator numbers them, one every
        # person_every_ms; the id ring holds every id alive in ttl_ms
        ident = jnp.where(vals % every == 0, last, seller)
        return ident % cfg["num_keys"], vals, ts

    local = jnp.asarray(cfg["local_states"], jnp.int32)

    def is_person(k, v, t):
        state = rest(v) % cfg["states"]
        return (v % every == 0) & jnp.any(state[..., None] == local, axis=-1)

    def is_auction(k, v, t):
        return (v % every != 0) & (
            rest(v) % cfg["categories"]
            == cfg["category"] - cfg["first_category"])

    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            sharing_depth=cfg["sharing_depth"],
                            default_edge_capacity=cfg["batch"])
    events = (env.host_source(batch_size=cfg["batch"], parallelism=p)
              .map(parse, name="parse", capacity=cfg["batch"]))
    persons = events.filter(is_person, name="persons")
    auctions = events.filter(is_auction, name="auctions")
    (persons.key_by().join_incremental(
        auctions.key_by(), num_keys=cfg["num_keys"], ttl=cfg["ttl_ms"],
        out_of_orderness=cfg["max_out_of_order_ms"],
        capacity=cfg["join_capacity"], own_columns=cfg["own_columns"],
        bag_capacity=cfg["bag_capacity"],
        edge_capacity=cfg["edge_capacity"], name="join")
     .key_by().sink(parallelism=p, transactional=True,
                    capacity=cfg["join_capacity"]))
    return env.build()
