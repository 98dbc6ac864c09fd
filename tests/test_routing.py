"""Exchange routing vs a numpy oracle (reference partitioner semantics:
KeyGroupStreamPartitioner / RebalancePartitioner / BroadcastPartitioner)."""

import numpy as np
import jax.numpy as jnp
import pytest

from clonos_tpu.api import records
from clonos_tpu.parallel import routing


def _np_hash32(x):
    u = np.asarray(x, np.uint64) & 0xFFFFFFFF
    u = ((u ^ (u >> 16)) * 0x7FEB352D) & 0xFFFFFFFF
    u = ((u ^ (u >> 15)) * 0x846CA68B) & 0xFFFFFFFF
    return (u ^ (u >> 16)) & 0xFFFFFFFF


def _mkbatch(rows, cap):
    """rows: list per upstream subtask of (key, val) lists."""
    p = len(rows)
    keys = np.zeros((p, cap), np.int32)
    vals = np.zeros((p, cap), np.int32)
    valid = np.zeros((p, cap), bool)
    for i, r in enumerate(rows):
        for j, (k, v) in enumerate(r):
            keys[i, j], vals[i, j], valid[i, j] = k, v, True
    return records.RecordBatch(jnp.asarray(keys), jnp.asarray(vals),
                               jnp.zeros((p, cap), jnp.int32),
                               jnp.asarray(valid))


def test_hash32_matches_oracle():
    xs = np.arange(-50, 50, dtype=np.int32)
    got = np.asarray(routing.hash32(jnp.asarray(xs)))
    want = _np_hash32(xs).astype(np.uint32)
    np.testing.assert_array_equal(got, want)


def test_key_group_routing_owns_all_records():
    G, P = 16, 4
    batch = _mkbatch([[(k, k * 10) for k in range(5)],
                      [(k, k) for k in range(7, 12)]], cap=8)
    routed, dropped = routing.route_hash(batch, P, G, out_capacity=16)
    assert int(dropped.sum()) == 0
    # Every record lands on the subtask owning its key group.
    out = []
    for t in range(P):
        lo, hi = routing.key_group_range(t, P, G)
        row = records.to_numpy(
            records.RecordBatch(routed.keys[t], routed.values[t],
                                routed.timestamps[t], routed.valid[t]))
        for k, v, _ in row:
            kg = int(_np_hash32(k) % G)
            assert lo <= kg < hi, (k, kg, t)
            out.append((k, v))
    assert sorted(out) == sorted((int(k), int(v)) for k, v, _ in
                                 records.to_numpy(batch))


def test_routing_preserves_arrival_order_within_target():
    # All keys equal -> single target; order must match flattened input.
    batch = _mkbatch([[(7, i) for i in range(4)],
                      [(7, 10 + i) for i in range(4)]], cap=4)
    routed, _ = routing.route_hash(batch, 2, 8, out_capacity=16)
    t = int(routing.subtask_for_key_group(
        routing.key_group(jnp.asarray([7]), 8), 2, 8)[0])
    vals = [v for _, v, _ in records.to_numpy(
        records.RecordBatch(routed.keys[t], routed.values[t],
                            routed.timestamps[t], routed.valid[t]))]
    assert vals == [0, 1, 2, 3, 10, 11, 12, 13]


def test_overflow_drops_are_counted():
    batch = _mkbatch([[(3, i) for i in range(6)]], cap=6)
    routed, dropped = routing.route_hash(batch, 1, 4, out_capacity=4)
    assert int(routed.valid.sum()) == 4
    assert int(dropped.sum()) == 2


def test_rebalance_round_robin_deterministic():
    batch = _mkbatch([[(i, i) for i in range(6)]], cap=6)
    routed, dropped = routing.route_rebalance(batch, 3, out_capacity=4)
    assert int(dropped.sum()) == 0
    per = [sorted(v for _, v, _ in records.to_numpy(
        records.RecordBatch(routed.keys[t], routed.values[t],
                            routed.timestamps[t], routed.valid[t])))
           for t in range(3)]
    assert per == [[0, 3], [1, 4], [2, 5]]
    # offset shifts the cycle
    routed2, _ = routing.route_rebalance(batch, 3, out_capacity=4, offset=1)
    per2 = sorted(v for _, v, _ in records.to_numpy(
        records.RecordBatch(routed2.keys[0], routed2.values[0],
                            routed2.timestamps[0], routed2.valid[0])))
    assert per2 == [2, 5]


def test_broadcast_replicates_and_compacts():
    batch = _mkbatch([[(1, 1)], [(2, 2)]], cap=3)
    routed, dropped = routing.route_broadcast(batch, 3, out_capacity=4)
    assert int(dropped.sum()) == 0
    for t in range(3):
        vals = sorted(v for _, v, _ in records.to_numpy(
            records.RecordBatch(routed.keys[t], routed.values[t],
                                routed.timestamps[t], routed.valid[t])))
        assert vals == [1, 2]


def _rand_block(rng, K, P, B, vocab=37, fill=0.7):
    keys = rng.randint(0, vocab, size=(K, P, B)).astype(np.int32)
    vals = rng.randint(-1000, 1000, size=(K, P, B)).astype(np.int32)
    ts = rng.randint(0, 100, size=(K, P, B)).astype(np.int32)
    valid = rng.rand(K, P, B) < fill
    return records.zero_invalid(records.RecordBatch(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts),
        jnp.asarray(valid)))


#: blocks the counting route's rank must not get wrong: every record of a
#: step on one target (ranks run past the capacity, ``dropped`` is exact),
#: and no valid record at all. The shapes below bring a step shorter than
#: one 128-record tile of the running count (3 x 16) and one of 37 1/2
#: tiles (8 x 600); T = 1 is the broadcast's route and the second
#: ``(T, G)`` pair.
_TRAFFIC = {"mixed": {}, "one-target": dict(vocab=1, fill=1.0),
            "none-valid": dict(fill=0.0)}


@pytest.mark.parametrize("cap,K,P,B", [
    (4, 7, 3, 16), (16, 7, 3, 16), (64, 7, 3, 16),
    (32, 5, 8, 600),
])
@pytest.mark.parametrize("over_budget", [False, True])
@pytest.mark.parametrize("traffic", list(_TRAFFIC))
def test_block_routes_bit_identical_to_per_step(cap, K, P, B, over_budget,
                                                traffic, monkeypatch):
    """The block exchange (counting the block whole, and one step a
    chunk where even a single step's scratch is over the budget) must
    equal vmapping the per-step exchange, including overflow-drop
    accounting (the executor switched to the block form for speed;
    semantics are pinned here)."""
    import jax
    if over_budget:   # no room for one step: K chunks of one step each
        monkeypatch.setattr(routing, "_count_route_budget", lambda: 0)
    rng = np.random.RandomState(3)
    batch = _rand_block(rng, K, P, B, **_TRAFFIC[traffic])
    for T, G in [(4, 8), (1, 4), (5, 20)]:
        r1, d1 = jax.vmap(
            lambda b: routing.route_hash(b, T, G, cap))(batch)
        r2, d2 = routing.route_hash_block(batch, T, G, cap)
        for a, b in zip(jax.tree_util.tree_leaves((r1, d1)),
                        jax.tree_util.tree_leaves((r2, d2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Rebalance with a running per-step offset.
    counts = np.asarray(batch.count().sum(axis=1))
    offs = jnp.asarray(5 + np.cumsum(counts) - counts, jnp.int32)
    r1, d1 = jax.vmap(lambda b, o: routing.route_rebalance(
        b, 3, cap, o))(batch, offs)
    r2, d2 = routing.route_rebalance_block(batch, 3, cap, offs)
    for a, b in zip(jax.tree_util.tree_leaves((r1, d1)),
                    jax.tree_util.tree_leaves((r2, d2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Broadcast.
    r1, d1 = jax.vmap(lambda b: routing.route_broadcast(b, 3, cap))(batch)
    r2, d2 = routing.route_broadcast_block(batch, 3, cap)
    for a, b in zip(jax.tree_util.tree_leaves((r1, d1)),
                    jax.tree_util.tree_leaves((r2, d2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Forward at smaller/equal/larger capacity.
    for oc in (B // 2, B, B + 5):
        r1, d1 = jax.vmap(lambda b: routing.route_forward(b, oc))(batch)
        r2, d2 = routing.route_forward_block(batch, oc)
        for a, b in zip(jax.tree_util.tree_leaves((r1, d1)),
                        jax.tree_util.tree_leaves((r2, d2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cap,K,P,B", [
    (4, 12, 3, 16), (64, 12, 3, 16), (32, 6, 8, 600),
])
@pytest.mark.parametrize("traffic", list(_TRAFFIC))
def test_chunked_count_route_bit_identical_to_per_step(cap, K, P, B, traffic,
                                                       monkeypatch):
    """A block whose counting scratch is over budget counts chunk after
    chunk of steps: equal to the per-step exchange, drops included, and
    the instant says how the block was cut."""
    import jax
    from clonos_tpu.obs import trace
    T, G = 4, 8
    # room for 3 steps' scratch: K = 12 is cut into chunks of 3, K = 6 too
    monkeypatch.setattr(routing, "_count_route_budget",
                        lambda: 3 * P * B * (T + 1) * 12)
    batch = _rand_block(np.random.RandomState(5), K, P, B,
                        **_TRAFFIC[traffic])
    tracer = trace.configure("chunked-route-test")
    try:
        r2, d2 = routing.route_hash_block(batch, T, G, cap)
        took = [(r["args"]["route"], r["args"]["steps"],
                 r["args"]["chunks"], r["args"]["rank"])
                for r in tracer.records() if r["name"] == "exchange.route"]
    finally:
        trace.reset()
    assert took == [("scatter", 3, K // 3, "tri")]
    r1, d1 = jax.vmap(lambda b: routing.route_hash(b, T, G, cap))(batch)
    for a, b in zip(jax.tree_util.tree_leaves((r1, d1)),
                    jax.tree_util.tree_leaves((r2, d2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cap", [16, 4096])
def test_counting_route_lowers_without_a_gather(cap):
    """A gather on the v5e costs per index (~11 ns an element, whatever
    it reads): the counting route reads a record's arrival rank by a
    masked sum over the targets' running counts, and neither placement
    (the keyed histogram up to ``KERNEL_MAX_KEYS`` slots, the element
    scatter past them) brings one back."""
    import jax
    batch = _rand_block(np.random.RandomState(2), 4, 8, 160)
    text = jax.jit(lambda b: routing.route_hash_block(b, 8, 64, cap)
                   ).lower(batch).as_text()
    assert "dot_general" in text and "gather" not in text


# Both blocks are over a budget of four steps. Blocks up to 2,097,152
# records used to take a flat sort there and only longer ones counted in
# chunks; scaled down (n = 1,280), K = 16 stands for the shorter kind and
# K = 64 for the longer.
@pytest.mark.parametrize("K", [16, 64])
@pytest.mark.parametrize("cap", [16, 4096])
def test_over_budget_route_lowers_without_a_sort_or_a_gather(
        K, cap, monkeypatch):
    """One route past the budget, whatever the block's length: the
    chunked count lowers to the triangle's products under a loop over
    the chunks, with no sort (the flat composite-key sort is gone) and
    no gather (the twin of the test above), by either placement."""
    import jax
    from clonos_tpu.obs import trace
    P, B, T = 8, 160, 8
    monkeypatch.setattr(routing, "_count_route_budget",
                        lambda: 4 * P * B * (T + 1) * 12)
    batch = _rand_block(np.random.RandomState(2), K, P, B)
    tracer = trace.configure("over-budget-route-test")
    try:
        text = jax.jit(lambda b: routing.route_hash_block(b, T, 64, cap)
                       ).lower(batch).as_text()
        took = [(r["args"]["route"], r["args"]["steps"], r["args"]["chunks"])
                for r in tracer.records() if r["name"] == "exchange.route"]
    finally:
        trace.reset()
    assert took == [("scatter", 4, K // 4)]
    assert "dot_general" in text and "while" in text
    # (a scatter's ``indices_are_sorted`` attribute is not a sort)
    assert "stablehlo.sort" not in text and "gather" not in text


def test_step_chunk_is_a_divisor_within_the_limit():
    assert routing._step_chunk(8192, 3034) == 2048
    assert routing._step_chunk(1024, 277) == 256
    assert routing._step_chunk(12, 5) == 4
    assert routing._step_chunk(7, 3) == 1
    assert routing._step_chunk(7, 0) == 0


def test_static_route_plan_matches_dynamic_multiset():
    """StaticRoutePlan routes the same per-(step,target) record multiset
    as the dynamic hash exchange (layout differs: static slots keep holes
    instead of compacting)."""
    import jax
    rng = np.random.RandomState(11)
    K, P, NK, G, T, CAP = 5, 3, 29, 8, 4, 32
    slot_keys = np.arange(NK, dtype=np.int32)
    plan = routing.plan_static_hash(slot_keys, P, T, G, CAP)
    # Dense-table emission: slot i carries key i; random validity.
    keys = np.broadcast_to(slot_keys, (K, P, NK)).copy()
    vals = rng.randint(1, 100, size=(K, P, NK)).astype(np.int32)
    ts = rng.randint(0, 50, size=(K, P, NK)).astype(np.int32)
    valid = rng.rand(K, P, NK) < 0.6
    batch = records.zero_invalid(records.RecordBatch(
        jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ts),
        jnp.asarray(valid)))
    r_static, d_static = plan.apply(batch)
    r_dyn, d_dyn = routing.route_hash_block(batch, T, G, CAP)
    for k in range(K):
        for t in range(T):
            def multiset(r):
                m = np.asarray(r.valid[k, t])
                return sorted(zip(np.asarray(r.keys[k, t])[m].tolist(),
                                  np.asarray(r.values[k, t])[m].tolist(),
                                  np.asarray(r.timestamps[k, t])[m].tolist()))
            assert multiset(r_static) == multiset(r_dyn), (k, t)
    assert int(jnp.sum(d_static)) == 0 == int(jnp.sum(d_dyn))
    # slot_keys metadata matches what actually flows in mapped slots.
    assert np.all((plan.slot_keys >= 0) == plan.ok)


def _per_target(routed):
    """[step][target] -> the valid records there, in slot order."""
    k, v, t, m = (np.asarray(x) for x in routed)
    return [[list(zip(k[s, q][m[s, q]].tolist(), v[s, q][m[s, q]].tolist(),
                      t[s, q][m[s, q]].tolist()))
             for q in range(k.shape[1])] for s in range(k.shape[0])]


@pytest.mark.parametrize("P,T,G,nk,W,clamp", [
    (8, 8, 128, 30, 2, (0, 29)),    # the review's case: 24 of 30 at P = 8
    (8, 8, 128, 200, 7, (0, 199)),  # the sliding window's table
    (16, 16, 64, 499, 1, ()),       # a count window drops what it clips
    (4, 6, 64, 20, 3, (19,)),       # another parallelism downstream
    (3, 5, 7, 11, 2, (0, 10)),
] + [tuple(np.random.RandomState(s).randint(lo, hi) for lo, hi in
           ((1, 9), (1, 9), (1, 40), (2, 60), (1, 4))) + ((0,),)
     for s in range(5)])
def test_pruned_plan_routes_what_the_dynamic_exchange_routes(
        P, T, G, nk, W, clamp):
    """A dense emitter that holds own keys fills only its live slots
    (``own_slots``: its own keys, and the clamp columns on every
    subtask). The plan pruned to those routes, step by step, the
    multiset the hash exchange routes to every target, with no drop slot
    at the capacity ``static_hash_capacity`` gives."""
    rng = np.random.RandomState(P * 1000 + nk)
    sk = np.tile(np.arange(nk, dtype=np.int32), W)
    live = routing.own_slots(sk, P, G, clamp)
    own = routing.own_slots(sk, P, G)
    assert (live >= own).all() and own.sum() == len(sk)
    if clamp and P > 1:
        # the clamp column is live on subtasks that do not own it
        assert (live & ~own).sum() == (P - 1) * W * len(set(clamp))
    cap = routing.static_hash_capacity(sk, P, T, G, live)
    assert cap <= routing.static_hash_capacity(sk, P, T, G)
    plan = routing.plan_static_hash(sk, P, T, G, cap, live)
    assert len(plan.drop_p) == 0 and plan.ok.sum() == live.sum()
    assert plan.width == cap
    tight = routing.plan_static_hash(sk, P, T, G, cap - 1, live)
    assert len(tight.drop_p) >= 1
    K, B = 6, len(sk)
    valid = (rng.rand(K, P, B) < 0.7) & live[None]
    valid[0] = live                       # every live pair at once
    batch = records.zero_invalid(records.RecordBatch(
        jnp.asarray(np.broadcast_to(sk, (K, P, B))),
        jnp.asarray(rng.randint(1, 100, (K, P, B)).astype(np.int32)),
        jnp.asarray(rng.randint(0, 50, (K, P, B)).astype(np.int32)),
        jnp.asarray(valid)))
    wide = cap + 5                        # unmapped columns stay empty
    r_static, d_static = routing.plan_static_hash(
        sk, P, T, G, wide, live).apply(batch)
    r_dyn, d_dyn = routing.route_hash_block(batch, T, G, wide)
    assert r_static.keys.shape == (K, T, wide)
    assert int(jnp.sum(d_static)) == 0 == int(jnp.sum(d_dyn))
    # the same records in the same (arrival) order, holes apart
    assert _per_target(r_static) == _per_target(r_dyn)
    assert int(r_dyn.valid.sum()) == int(valid.sum())


@pytest.mark.parametrize("P,G,B,cap", [(8, 128, 24, 24), (8, 128, 24, 40),
                                       (4, 64, 16, 16), (5, 9, 12, 30)])
def test_identity_route_equals_the_hash_exchange_on_own_keys(P, G, B, cap):
    """Where every record sits on the subtask its key hashes to, the
    exchange moves nothing: routed in place (``route_forward_block``, the
    planner's ``identity``) it equals ``route_hash_block`` record for
    record — array for array when the producer's records are packed to
    the front, as behind a dynamic exchange; in slot order where they
    are not (the exchange packs them, in place they keep their holes)."""
    rng = np.random.RandomState(B * P)
    K = 5
    pool = rng.randint(0, 10_000, size=4000).astype(np.int64)
    owner = routing._static_targets(pool, P, G)
    keys = np.zeros((K, P, B), np.int32)
    for p in range(P):
        mine = pool[owner == p]
        keys[:, p] = mine[rng.randint(0, len(mine), size=(K, B))]
    vals = rng.randint(-99, 99, (K, P, B)).astype(np.int32)
    ts = rng.randint(0, 50, (K, P, B)).astype(np.int32)
    counts = rng.randint(0, B + 1, (K, P))
    for name, valid in (
            ("packed", np.arange(B)[None, None, :] < counts[:, :, None]),
            ("holes", rng.rand(K, P, B) < 0.6)):
        batch = records.zero_invalid(records.RecordBatch(
            *(jnp.asarray(x) for x in (keys, vals, ts, valid))))
        r_id, d_id = routing.route_forward_block(batch, cap)
        r_dyn, d_dyn = routing.route_hash_block(batch, P, G, cap)
        assert int(jnp.sum(d_id)) == 0 == int(jnp.sum(d_dyn)), name
        assert _per_target(r_id) == _per_target(r_dyn), name
        if name == "packed":
            for a, b in zip(r_id, r_dyn):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for lane in range(P):
            one = routing.route_forward_block_lane(batch, lane, cap)
            for a, b in zip(one, r_id):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b[:, lane]))


def test_static_route_plan_drop_accounting():
    """Capacity overflow drops whole static slots and counts them."""
    NK, P, T, G, CAP = 16, 2, 1, 4, 8
    plan = routing.plan_static_hash(
        np.arange(NK, dtype=np.int32), P, T, G, CAP)
    # All 16*2=32 slots target subtask 0; capacity 8 -> 24 static drops.
    assert plan.ok.sum() == CAP
    assert len(plan.drop_p) == NK * P - CAP
    batch = records.RecordBatch(
        jnp.broadcast_to(jnp.arange(NK, dtype=jnp.int32), (3, P, NK)),
        jnp.ones((3, P, NK), jnp.int32), jnp.zeros((3, P, NK), jnp.int32),
        jnp.ones((3, P, NK), jnp.bool_))
    routed, dropped = plan.apply(batch)
    assert int(routed.valid.sum()) == 3 * CAP
    assert np.all(np.asarray(dropped) == NK * P - CAP)


def test_forward_identity():
    batch = _mkbatch([[(1, 5)], [(2, 6)]], cap=3)
    routed, dropped = routing.route_forward(batch, out_capacity=3)
    assert int(dropped.sum()) == 0
    np.testing.assert_array_equal(np.asarray(routed.keys),
                                  np.asarray(batch.keys))


@pytest.mark.parametrize("cap,K,P,B", [
    (4, 7, 3, 16), (16, 7, 3, 16), (64, 5, 8, 600),
])
def test_lane_routes_bit_identical_to_full_route_lane(cap, K, P, B):
    """The single-lane exchange (recovery's fused single-failure path)
    must equal the full block route's lane slice bit-for-bit — survivors,
    positions, overflow drops, everything."""
    rng = np.random.RandomState(11)
    batch = _rand_block(rng, K, P, B)
    T, G = 3, 8
    full, _ = routing.route_hash_block(batch, T, G, cap)
    for lane in range(T):
        got = routing.route_hash_block_lane(batch, lane, T, G, cap)
        for a, b in zip(got, full):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[:, lane]))
    offs = jnp.asarray(rng.randint(0, 5, size=(K,)), jnp.int32)
    full_rb, _ = routing.route_rebalance_block(batch, T, cap, offs)
    for lane in range(T):
        got = routing.route_rebalance_block_lane(batch, lane, T, cap, offs)
        for a, b in zip(got, full_rb):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[:, lane]))
    full_bc, _ = routing.route_broadcast_block(batch, T, cap)
    for lane in range(T):
        got = routing.route_broadcast_block_lane(batch, lane, cap)
        for a, b in zip(got, full_bc):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[:, lane]))
    full_fw, _ = routing.route_forward_block(batch, cap)
    for lane in range(P):
        got = routing.route_forward_block_lane(batch, lane, cap)
        for a, b in zip(got, full_fw):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[:, lane]))


# --- the exchange's counters in the carry, and drops that are loud -----------


def _fed_runner(build, keys, B, steps_per_epoch=8):
    """A ``ClusterRunner`` over ``build(env)``'s job whose host source
    (one partition a row of ``keys [P, steps * B]``) is fed that table."""
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.api.feeds import ListFeedReader
    from clonos_tpu.runtime.cluster import ClusterRunner
    env = StreamEnvironment(name="edges", num_key_groups=16,
                            default_edge_capacity=B)
    build(env, env.host_source(batch_size=B, parallelism=len(keys)))
    runner = ClusterRunner(env.build(), steps_per_epoch=steps_per_epoch,
                           block_steps=4, log_capacity=512,
                           inflight_ring_steps=32, logical_time=True,
                           audit=False, overlap_epoch=False)
    runner.executor.register_feed(0, ListFeedReader(
        [[(int(k), 1) for k in row] for row in keys]))
    return runner


def test_exchange_counters_hold_each_targets_peak_and_drops():
    """A dynamic HASH edge of capacity 3 into four subtasks: per target,
    the carry's ``peak`` is the most records a step sent it and
    ``dropped`` the records past the capacity, as NumPy counts them from
    the table; both only grow, and the fence's read reduces them."""
    from clonos_tpu.api.operators import KeyedReduceOperator
    P, B, steps, cap, T = 2, 8, 12, 3, 4
    keys = np.random.RandomState(5).randint(0, 40, (P, steps * B))
    runner = _fed_runner(
        lambda env, src: src.key_by()._attach(
            "reduce", KeyedReduceOperator(num_keys=40), T,
            capacity=cap).sink(capacity=cap), keys, B)
    compiled = runner.executor.compiled
    assert compiled.peak_edges() == [0] and compiled.edge_name(0) == \
        "host-source->reduce"
    sent = np.zeros((steps, T), np.int64)
    for s in range(steps):
        k = keys[:, s * B:(s + 1) * B].ravel()
        tgt = (_np_hash32(k) % 16).astype(np.int64) * T // 16
        sent[s] = np.bincount(tgt, minlength=T)
    seen = []
    for s in range(steps):
        runner.step()
        ex = runner.executor.carry.exchange[0]
        seen.append((np.asarray(ex["peak"]), np.asarray(ex["dropped"])))
        np.testing.assert_array_equal(seen[-1][0], sent[:s + 1].max(axis=0))
        np.testing.assert_array_equal(
            seen[-1][1], np.maximum(sent[:s + 1] - cap, 0).sum(axis=0))
    assert all((b[0] >= a[0]).all() and (b[1] >= a[1]).all()
               for a, b in zip(seen, seen[1:]))
    ex = runner.executor
    parts = ex.health_parts(ex.health_vector())
    assert parts["peak"].tolist() == [sent.max()]
    assert parts["dropped"].tolist() == [
        np.maximum(sent - cap, 0).sum(), 0]


@pytest.mark.parametrize("kind", ["forward", "rebalance", "hash"])
def test_a_drop_on_any_edge_stops_the_run_and_names_the_edge(kind):
    """16 records a step into an edge that holds 4 a target: whatever
    the partitioner, ``check_overflow()`` has a line for the edge and
    the fence raises it; the job with room enough has none."""
    from clonos_tpu.runtime.cluster import OverflowError_
    keys = np.random.RandomState(9).randint(0, 8, (2, 8 * 8))

    def build(cap):
        def job(env, src):
            stream = {"forward": src, "rebalance": src.rebalance(),
                      "hash": src.key_by()}[kind]
            stream.map(lambda k, v, t: (k, v, t), name="narrow",
                       capacity=cap).sink(capacity=16)
        return job

    roomy = _fed_runner(build(16), keys, 8)
    roomy.run_epoch()
    assert roomy.executor.check_overflow() == []
    runner = _fed_runner(build(4), keys, 8)
    with pytest.raises(OverflowError_,
                       match="edge host-source->narrow dropped"):
        runner.run_epoch()
    (line,) = runner.executor.check_overflow()
    assert line.startswith("edge host-source->narrow dropped ") \
        and line.endswith(" records past its capacity 4")
