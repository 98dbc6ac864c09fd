"""The ``nexmark-average-price`` topology on the program's job API: NEXmark
query 4, "Average Price for a Category" — the winning bid of every closed
auction, then the mean of the winning prices per category over a sliding
event-time window — as ``configs/nexmark-q4.json`` describes it (and lists
where it departs). Its plain reference is ``reference.py`` beside it."""

from __future__ import annotations

from typing import Any, Dict


def price_knots(cfg: Dict[str, Any]):
    """``PriceGenerator``'s ``round(10 ** (6 u) * 100)`` at ``u = i /
    knots``, ``i`` in ``[0, knots]``, worked out in float64 on the host:
    the table a price code is looked up in (``reference.py`` builds its
    own)."""
    import numpy as np
    knots = cfg["price_knots"]
    return np.rint(10.0 ** (6.0 * np.arange(knots + 1) / knots) * 100.0
                   ).astype(np.int32)


def build(cfg: Dict[str, Any]):
    """host source (auctions and bids, 3 : 46) -> map ``parse`` (kind, id,
    event time and, of a bid, its price, out of the feed's two lanes and
    the step the source stamped) -> { filter ``auctions`` -> keyBy ;
    filter ``bids`` -> keyBy } -> ``winning``: an auction opens ``[dateTime,
    expires)`` for its id with its reserve, a bid inside it at or over the
    reserve counts, the best is the auction's row when it closes ->
    keyBy(category) -> ``mean`` at parallelism 1: the exact mean of the
    winning prices per category and sliding window -> transactional sink
    at parallelism 1."""
    import jax.numpy as jnp
    import numpy as np

    from clonos_tpu.api.environment import StreamEnvironment

    p, tick = cfg["parallelism"], cfg["clock_ms_per_step"]
    kinds, spread = cfg["kinds"], cfg["spread_ms"]
    per_ms = cfg["auctions_per_ms"]              # [3, 5]: 600 a second
    batch_of, in_flight = cfg["hot_auction_every"], cfg["in_flight_auctions"]
    bits = cfg["value_lane"]
    knots = price_knots(cfg)
    # a bid reads its price between two knots, a reserve's two codes one
    # knot in ``reserve_knot_stride``
    knot, rise = jnp.asarray(knots[:-1]), jnp.asarray(np.diff(knots))
    coarse = jnp.asarray(knots[::cfg["reserve_knot_stride"]])
    fine = cfg["price_fine_bits"]
    is_auction_bit = 1 << cfg["auction_flag_bit"]

    def look_up(i, *tables):
        # tables of a few hundred entries, by one comparison with every
        # index: no gather by a computed index
        hit = i[..., None] == jnp.arange(tables[0].shape[0], dtype=jnp.int32)
        return [jnp.sum(jnp.where(hit, t, 0), axis=-1) for t in tables]

    def parse(keys, vals, step):
        # the key lane stands for which of the 49 events of a cycle the
        # record is and where inside its step it fell; the value lane for
        # a bid's auction and price, or an auction's length, category and
        # reserve (decoded where they are read: ``winning``)
        j = (keys * (kinds * spread)) >> cfg["key_bits"]
        auction = j % kinds < cfg["auctions_of_kinds"]
        ts = tick * step + j // kinds
        last = ts * per_ms[0] // per_ms[1]       # the newest auction's id
        hot = (vals >> bits["hot_shift"]) % cfg["hot_ratio"] == 1
        cold = ((vals >> bits["cold_shift"]) & bits["cold_mask"]) % (
            in_flight + 1)
        bid_on = jnp.where(hot, last // batch_of * batch_of, last - cold)
        code = vals >> bits["price_shift"]
        lo, step_up = look_up(code >> fine, knot, rise)
        price = lo + ((step_up * (code & ((1 << fine) - 1))) >> fine)
        return (jnp.where(auction, last, bid_on) % cfg["num_keys"],
                jnp.where(auction, vals | is_auction_bit, price), ts)

    def rest(v):
        return (v & (is_auction_bit - 1)) >> bits["rest_shift"]

    def reserve(v):
        return sum(look_up((v >> shift) & bits["reserve_mask"], coarse)[0]
                   for shift in (0, bits["reserve_shift"]))

    env = StreamEnvironment(name=cfg["name"],
                            num_key_groups=cfg["num_key_groups"],
                            sharing_depth=cfg["sharing_depth"],
                            default_edge_capacity=cfg["batch"])
    events = (env.host_source(batch_size=cfg["batch"], parallelism=p)
              .map(parse, name="parse", capacity=cfg["batch"]))
    auctions = events.filter(lambda k, v, t: v >= is_auction_bit,
                             name="auctions")
    bids = events.filter(lambda k, v, t: v < is_auction_bit, name="bids")
    categories = cfg["first_category"] + cfg["categories"]
    mean = (auctions.key_by().join_best_in_interval(
        bids.key_by(), num_keys=cfg["num_keys"],
        length_of=lambda v: 1 + rest(v) % cfg["length_span_ms"],
        floor_of=reserve,
        emit_of=lambda v: cfg["first_category"]
        + rest(v) // cfg["length_span_ms"] % cfg["categories"],
        out_of_orderness=cfg["max_out_of_order_ms"],
        capacity=cfg["winning_capacity"], own_columns=cfg["own_columns"],
        pool_capacity=cfg["pool_capacity"],
        edge_capacity=cfg["edge_capacity"], name="winning")
        # at parallelism 1 every key's target is subtask 0
        .key_by().window_mean(
            num_keys=categories, window_size=cfg["window_ms"],
            slide=cfg["slide_ms"],
            out_of_orderness=cfg["mean_out_of_order_ms"],
            edge_capacity=cfg["mean_edge_capacity"], name="mean",
            parallelism=1))
    # the mean's rows lie on dense slot x category lanes
    mean.sink(parallelism=1, transactional=True,
              capacity=mean.vertex.operator.out_capacity)
    return env.build()
