"""Compile for a v5e 2x2 without a chip: ``jax.experimental.topologies``
describes the devices and ``jit(...).lower(abstract args).compile()``
accepts them. A pre-check that saves chip time, not evidence — the
evidence is ``chip_smoke.py`` on the chip. Pinned here are the two
failures that bring-up found: a kernel shape the routing guard admits
but the compiler refused for VMEM, and the Mosaic kernel inside a
program partitioned over a mesh; and what refused PR 29: how many
kernels a program holds; and what PR 39 took out of the block program:
everything the size of the replica stack but its in-place update.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from clonos_tpu.ops import histogram


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as err:       # no libtpu / unknown topology name
        pytest.skip(f"cannot describe a v5e topology here: {err!r}")
    assert topo.devices[0].platform == "tpu" and len(topo.devices) == 4
    # A compile-only client cannot load executables back, so an entry
    # written here could never hit: keep these out of the persistent
    # cache (the floor is read at write time).
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    yield topo.devices
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


@pytest.mark.parametrize("want_counts", [True, False])
def test_hist_kernel_compiles_at_widest_admitted_table(v5e, want_counts):
    """Every slot-table width the exchange guard admits
    (``nk <= KERNEL_MAX_KEYS``) must compile, counts and sums-only, under
    the compiler's default scoped-VMEM limit: the factored MXU kernel's
    stacked operand is ``[8, 5 x 128, 1024]`` bf16 there, its largest."""
    sh = SingleDeviceSharding(v5e[0])
    arg = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=sh)
    mask = jax.ShapeDtypeStruct((8, 1024), jnp.bool_, sharding=sh)
    lowered = histogram._hist_pallas.lower(
        arg, arg, mask, histogram.KERNEL_MAX_KEYS, False, want_counts)
    assert lowered.as_text().count("tpu_custom_call") == 1
    lowered.compile()


def test_program_lowers_one_kernel_for_calls_of_one_shape(v5e):
    """An exchange places keys, values and timestamps over the same
    slots: three ``keyed_hist`` calls of one shape lower to one function
    with one Mosaic kernel in it, called three times — what a program
    pays per kernel at every ``lower`` (the module is built and
    serialised again, cache hit or not) it pays once."""
    sh = SingleDeviceSharding(v5e[0])
    arg = jax.ShapeDtypeStruct((64, 512), jnp.int32, sharding=sh)
    mask = jax.ShapeDtypeStruct((64, 512), jnp.bool_, sharding=sh)

    def place(slot, k, v, t, keep):
        return [histogram.keyed_hist(slot, x, keep, 8192, force="pallas",
                                     want_counts=False)[0]
                for x in (k, v, t)]

    text = jax.jit(place).lower(arg, arg, arg, arg, mask).as_text()
    assert text.count("call @_hist_pallas") == 3
    assert text.count("tpu_custom_call") == 1


def flagship_job(log_capacity, mesh=None):
    from clonos_tpu.api.environment import StreamEnvironment
    from clonos_tpu.runtime.executor import CompiledJob
    env = StreamEnvironment(name="flagship", num_key_groups=64,
                            default_edge_capacity=128)
    (env.synthetic_source(vocab=211, batch_size=32, parallelism=4)
        .key_by().window_count(num_keys=211, window_size=64)
        .key_by().reduce(num_keys=211).sink())
    return CompiledJob(env.build(), log_capacity=log_capacity, max_epochs=8,
                       inflight_ring_steps=16, mesh=mesh)


def lower_block(compiled, steps, sharding=None, **jit_kw):
    """(lowered block program of ``steps`` steps, the instants its trace
    left)."""
    from clonos_tpu.obs import trace
    from clonos_tpu.runtime.executor import BlockInputs
    carry = jax.eval_shape(compiled.init_carry)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    steps = jax.ShapeDtypeStruct((steps,), jnp.int32)
    args = (carry, BlockInputs(steps, steps, scalar, scalar))
    if sharding is not None:
        args = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), args)
    tracer = trace.configure("aot-test")
    try:
        lowered = jax.jit(compiled.run_block, donate_argnums=0,
                          **jit_kw).lower(*args)
        return lowered, tracer.records()
    finally:
        trace.reset()


def test_block_program_lowers_for_four_chip_mesh_with_kernel(v5e):
    """The flagship block program, pinned over a 4-device v5e mesh,
    compiles with the Mosaic kernel in it (bare ``pallas_call`` raised
    'Mosaic kernels cannot be automatically partitioned'), and to the
    collectives it had before the replica logs went run by run: a stack
    sharded over the mesh keeps the batched append."""
    mesh = Mesh(np.array(v5e), ("tasks",))
    compiled = flagship_job(1 << 10, mesh)
    carry = jax.eval_shape(compiled.init_carry)
    lowered, records = lower_block(
        compiled, 8, in_shardings=(compiled.carry_shardings(carry),
                                   NamedSharding(mesh, PartitionSpec())))
    routes = [(r["args"]["route"], r["args"]["rank"])
              for r in records if r["name"] == "exchange.route"]
    assert routes == [("kernel", "tri")], \
        "the exchange must take the kernel path, its rank the triangle's"
    assert [r["args"]["form"] for r in records
            if r["name"] == "log.append"] == ["window", "window"]
    assert "tpu_custom_call" in lowered.as_text()
    text = lowered.compile().as_text()
    assert {kind: len(re.findall(rf" {kind}(?:-start)?\(", text))
            for kind in ("all-reduce", "all-gather", "all-to-all",
                         "collective-permute")} == MESH_COLLECTIVES


#: of the flagship block program over the 2x2 mesh, as the parent of
#: PR 39 compiled it
MESH_COLLECTIVES = {"all-reduce": 3, "all-gather": 7, "all-to-all": 12,
                    "collective-permute": 0}


#: of ``allround-64``'s first exchange alone over the 2x2 mesh, counted
#: in two chunks of 512 steps: the fields' reshard from the subtask axis
#: to the step axis on the way in (the chip's own 128 steps of a chunk
#: are ranked and placed there), the routed chunk's reshard to the
#: target axis on the way out, and the drop counts gathered. An
#: all-gather of the ``[steps, T, n]`` one-hot, or of a field, would
#: show here.
MESH_EXCHANGE_COLLECTIVES = {"all-reduce": 0, "all-gather": 1,
                             "all-to-all": 11, "collective-permute": 0}


def test_mesh_cells_exchange_counts_in_chunks_by_step(v5e):
    """``allround64x4.backlog``'s first exchange at its own shape — 1,024
    steps of 16 x 128 records to 16 targets at capacity 1,024, the
    producer block sharded on the subtask axis, the routed block on the
    target axis — is over the counting budget and counts in chunks of
    steps through the kernel: no sort and no gather in the compiled
    program, the rank on a chip's own steps, and no collective beyond
    the reshards."""
    from clonos_tpu.api.records import RecordBatch
    from clonos_tpu.obs import trace
    from clonos_tpu.parallel import routing
    mesh = Mesh(np.array(v5e), ("tasks",))
    by_task = NamedSharding(mesh, PartitionSpec(None, "tasks", None))
    K, P, B, T, cap = 1024, 16, 128, 16, 1024
    lane = lambda dt: jax.ShapeDtypeStruct((K, P, B), dt, sharding=by_task)
    batch = RecordBatch(lane(jnp.int32), lane(jnp.int32), lane(jnp.int32),
                        lane(jnp.bool_))

    def exchange(b):
        with histogram.kernel_mesh(mesh, "tasks"):
            routed, dropped = routing.route_hash_block(b, T, 64, cap)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, by_task),
            routed), dropped

    tracer = trace.configure("aot-test")
    try:
        lowered = jax.jit(exchange).lower(batch)
        routes = [r["args"] for r in tracer.records()
                  if r["name"] == "exchange.route"]
    finally:
        trace.reset()
    assert [(r["route"], r["rank"]) for r in routes] == [("kernel", "tri")]
    assert routes[0]["steps"] * routes[0]["chunks"] == K
    assert routes[0]["chunks"] > 1
    text = lowered.compile().as_text()
    for op in ("sort", "gather"):
        assert not re.findall(rf" {op}\(", text), op
    assert {kind: len(re.findall(rf" {kind}(?:-start)?\(", text))
            for kind in MESH_EXCHANGE_COLLECTIVES} == MESH_EXCHANGE_COLLECTIVES
    # the running count's products on a chip's own quarter of a chunk
    steps = routes[0]["steps"] // len(v5e)
    assert re.search(rf"f32\[{steps},{T},{P * B // 128},128\]\S* "
                     rf"convolution\(", text)


@pytest.mark.parametrize("log_capacity,own_form", [
    (128, "dense"),        # cap == 4n, ``kafka-window-64``'s ratio
    (1024, "window"),      # cap == 32n, ``allround-32``'s
])
def test_replica_append_touches_the_stack_only_in_place(
        v5e, log_capacity, own_form):
    """The one-chip block program appends the replica logs run by run:
    nothing in it has the size of the stack ``[R, cap, 8]`` — no select
    over it, no zero state of a loop over logs, no copy — but the stack
    itself passing through the loops that update it in place, and
    nothing under ``causal-log/replicas`` is larger than one run's slot
    ``[k, n, 8]``: no ``rows[owner_idx]`` of ``[R, n, 8]``, no strip of
    ``[R, 4w, 8]``."""
    compiled = flagship_job(log_capacity)
    plan, n = compiled.plan, 4 * 8
    R, k = plan.num_replicas, max(k for _, k, _ in plan.runs)
    lowered, records = lower_block(compiled, 8, SingleDeviceSharding(v5e[0]))
    assert [(r["args"]["form"], r["args"]["logs"], r["args"]["runs"])
            for r in records if r["name"] == "log.append"] == [
        (own_form, compiled.L, 0), ("runs", R, len(plan.runs))]
    exe = lowered.compile()
    stack = f"s32[{R},{log_capacity},8]"
    in_place = ("parameter", "while", "get-tuple-element",
                "dynamic-update-slice", "tuple", "bitcast")
    for line in exe.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m is None:
            continue
        dtype, dims, opcode = m.groups()
        if f"{dtype}[{dims}]" == stack:
            assert opcode in in_place, line
        elif "causal-log/replicas" in line and dims:
            assert np.prod([int(d) for d in dims.split(",")]) <= k * n * 8, \
                line
    assert exe.memory_analysis().temp_size_in_bytes < R * log_capacity * 32


def test_window_top_block_form_compiles_at_the_cells_widths(v5e):
    """``nexmark-q5``'s ``count`` vertex at its own widths — 8,192 ids in
    640 own columns a subtask, 7 open windows, 768 records a subtask a
    step — over 64 steps of 16 subtasks: its five placements and its
    emission take the Mosaic kernel (two bodies: ``[., 768] -> 4,480``
    lanes and ``[., 4,480] -> 32``), and the column lookup (every record
    against 640 keys) fuses without a ``[K, P, B, 640]`` array."""
    from clonos_tpu.api.operators import (BlockContext,
                                          EventTimeWindowTopOperator)
    from clonos_tpu.api.records import RecordBatch
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    sh = NamedSharding(mesh, PartitionSpec())
    op = EventTimeWindowTopOperator(
        num_keys=8192, window_size=10000, slide=2000, out_of_orderness=111,
        capacity=32, own_columns=640)
    K, P, B = 64, 16, 768
    shaped = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
    state = jax.tree_util.tree_map(
        shaped, jax.eval_shape(lambda: op.init_state(P)))
    lane = lambda dt: jax.ShapeDtypeStruct((K, P, B), dt, sharding=sh)
    batches = RecordBatch(lane(jnp.int32), lane(jnp.int32), lane(jnp.int32),
                          lane(jnp.bool_))
    steps = jax.ShapeDtypeStruct((K,), jnp.int32, sharding=sh)

    def block(state, batches, times):
        with histogram.kernel_mesh(mesh, "tasks"):
            return op.process_block(state, batches, BlockContext(
                times=times, rng_bits=times, epoch=jnp.int32(0),
                step0=jnp.int32(0),
                subtask=jnp.arange(P, dtype=jnp.int32)))

    lowered = jax.jit(block).lower(state, batches, steps)
    assert lowered.as_text().count("tpu_custom_call") == 2
    compiled = lowered.compile()
    # the [K, P, B, 640] comparison never exists as an array
    assert compiled.memory_analysis().temp_size_in_bytes < K * P * B * 640


def test_session_window_block_form_compiles_at_the_cells_widths(v5e):
    """``nexmark-q11``'s ``sessions`` vertex at its own widths — 8,192 ids
    in 640 own columns a subtask, 896 records a subtask a step, 32 rows —
    over a whole block of 1,024 steps of 16 subtasks, inside the job's
    block program: under ``vertex/sessions`` no scan of 1,024 trips (no
    ``while`` at all), no scatter and no gather; its compaction takes the
    Mosaic kernel (one body: ``[., 1,280] -> 32``); the comparison of
    every record with every own column fuses without a ``[K, P, B, 640]``
    array; and ``sessions -> sink`` is planned ``identity``."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    for p in (bench, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import job
    from benchlib.byname import module_at
    from clonos_tpu.runtime.executor import CompiledJob
    with open(os.path.join(bench, "configs", "nexmark-q11.json")) as f:
        cfg = json.load(f)
    # the logs and rings at a size this test can describe quickly; the
    # vertex, its edges and the block's steps as the cell has them
    compiled = CompiledJob(
        module_at(job.topology_file(cfg, "job.py")).build(cfg),
        log_capacity=8192, max_epochs=8, inflight_ring_steps=2048)
    assert [p.route for _, p in sorted(compiled.edge_plans.items())] == [
        "dynamic", "identity"]
    K, P = cfg["block_steps"], cfg["parallelism"]
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    with histogram.kernel_mesh(mesh, "tasks"):
        lowered, records = lower_block(compiled, K,
                                       SingleDeviceSharding(v5e[0]))
    forms = [r["args"] for r in records if r["name"] == "hist.kernel"]
    assert {f["form"] for f in forms} == {"mxu"}
    assert (K * P, 2 * cfg["own_columns"], cfg["session_capacity"]) in {
        (f["rows"], f["cols"], f["lanes"]) for f in forms}
    exe = lowered.compile()
    mine = [line for line in exe.as_text().splitlines()
            if "vertex/sessions" in line]
    assert len(mine) > 500
    for op in ("while", "scatter", "gather", "sort"):
        assert not [line for line in mine
                    if re.search(rf"= \S+ {op}\(", line)], op
    # the [K, P, B, 640] comparison never exists as an array
    assert exe.memory_analysis().temp_size_in_bytes < (
        K * P * cfg["edge_capacity"] * cfg["own_columns"]) // 4


def test_incremental_join_block_form_compiles_at_the_cells_widths(v5e):
    """``nexmark-q3``'s ``join`` vertex at its own widths — 131,072 ids in
    8,448 own columns a subtask, 256 records a subtask a step from each
    input, 4,608 waiting auctions, 256 rows — over a whole block of 1,024
    steps of 16 subtasks, inside the job's block program: under
    ``vertex/join`` no scatter, no gather and no sort; no loop over the
    block's steps (the loops are the 32 chunks, and the 32 steps of a
    chunk that is not quiet, under a conditional); the packing, the bag
    and the rows take the Mosaic kernel; the comparison of a chunk's
    packed records with every own column fuses without a ``[P, M, 8,448]``
    array; and ``join -> sink`` is planned ``identity``."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    for p in (bench, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import job
    from benchlib.byname import module_at
    from clonos_tpu.runtime.executor import CompiledJob
    with open(os.path.join(bench, "configs", "nexmark-q3.json")) as f:
        cfg = json.load(f)
    compiled = CompiledJob(
        module_at(job.topology_file(cfg, "job.py")).build(cfg),
        log_capacity=8192, max_epochs=8, inflight_ring_steps=2048)
    assert [p.route for _, p in sorted(compiled.edge_plans.items())] == [
        "dynamic", "dynamic", "identity"]
    K, P, E = cfg["block_steps"], cfg["parallelism"], cfg["edge_capacity"]
    op = compiled.job.vertices[4].operator
    S = op._chunk_of(K)
    assert (S, K // S) == (32, 32)
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    with histogram.kernel_mesh(mesh, "tasks"):
        lowered, records = lower_block(compiled, K,
                                       SingleDeviceSharding(v5e[0]))
    forms = [r["args"] for r in records if r["name"] == "hist.kernel"]
    assert {f["form"] for f in forms} == {"mxu"}
    shapes = {(f["rows"], f["cols"], f["lanes"]) for f in forms}
    chunks, bag, cap = K // S, cfg["bag_capacity"], cfg["join_capacity"]
    assert {(chunks * P, S * E, 2 * E), (chunks * P, S * E, 4 * E),   # packed
            (P, bag + 4 * E, bag),                                  # the bag
            (chunks * P, S * cap, S * cap)} <= shapes               # the rows
    exe = lowered.compile()
    text = exe.as_text()
    mine = [line for line in text.splitlines() if "vertex/join" in line]
    assert len(mine) > 500
    for op_name in ("scatter", "gather", "sort"):
        assert not [line for line in mine
                    if re.search(rf"= .* {op_name}\(", line)], op_name
    # two loops, by the scope they were traced under: the chunks, and —
    # in the conditional's branch — the steps of a chunk that is not quiet
    loops = sorted(m.group(1) for line in mine if " while(" in line
                   for m in [re.search(r'op_name="[^"]*?vertex/join/([^"]*)"',
                                       line)] if m)
    assert loops == ["while", "while/body/closed_call/cond/branch_1_fun/while"]
    # the [P, M, 8,448] comparisons never exist as arrays
    assert exe.memory_analysis().temp_size_in_bytes < (
        P * 4 * E * cfg["own_columns"]) * 4


def test_best_in_interval_block_form_compiles_at_the_cells_widths(v5e):
    """``nexmark-q4``'s ``winning`` vertex at its own widths — 8,192 ids
    in 640 own columns a subtask, 768 receive slots a subtask a step on
    both inputs, 3,072 waiting bids, 32 rows — over a whole block of
    1,024 steps of 16 subtasks, inside the job's block program: under
    ``vertex/winning`` and ``vertex/mean`` no scatter, no gather and no
    sort; no loop over the block's steps (the loops are the 32 chunks,
    and the 32 steps of a chunk that is not quiet, under a conditional);
    the auctions' packing, the active intervals, the pool and the rows
    take the Mosaic kernel; and the comparison of a chunk's 27,648 bids
    with its 256 active intervals fuses without a ``[P, N, 256]``
    array."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    for p in (bench, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import job
    from benchlib.byname import module_at
    from clonos_tpu.runtime.executor import CompiledJob
    with open(os.path.join(bench, "configs", "nexmark-q4.json")) as f:
        cfg = json.load(f)
    compiled = CompiledJob(
        module_at(job.topology_file(cfg, "job.py")).build(cfg),
        log_capacity=8192, max_epochs=8, inflight_ring_steps=2048)
    assert [p.route for _, p in sorted(compiled.edge_plans.items())] == [
        "dynamic"] * 3
    K, P, E = cfg["block_steps"], cfg["parallelism"], cfg["edge_capacity"]
    op = compiled.job.vertices[4].operator
    S = op._chunk_of(K)
    assert (S, K // S) == (32, 32)
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    with histogram.kernel_mesh(mesh, "tasks"):
        lowered, records = lower_block(compiled, K,
                                       SingleDeviceSharding(v5e[0]))
    forms = [r["args"] for r in records if r["name"] == "hist.kernel"]
    assert {f["form"] for f in forms} == {"mxu"}
    shapes = {(f["rows"], f["cols"], f["lanes"]) for f in forms}
    chunks, pool, cap, cols, active = (
        K // S, cfg["pool_capacity"], cfg["winning_capacity"],
        cfg["own_columns"], op._ACTIVE)
    assert {(chunks * P, S * E, E),                    # the auctions packed
            (P, 2 * cols, active),                     # the active intervals
            (P, pool + S * E, pool),                   # the pool
            (chunks * P, 2 * cols, S * cap)} <= shapes           # the rows
    exe = lowered.compile()
    text = exe.as_text()
    for vertex, least in (("winning", 500), ("mean", 100)):
        mine = [line for line in text.splitlines()
                if f"vertex/{vertex}" in line]
        assert len(mine) > least
        for op_name in ("scatter", "gather", "sort"):
            assert not [line for line in mine
                        if re.search(rf"= .* {op_name}\(", line)], op_name
        loops = sorted(
            m.group(1) for line in mine if " while(" in line for m in [
                re.search(rf'op_name="[^"]*?vertex/{vertex}/([^"]*)"', line)]
            if m)
        # two loops, by the scope they were traced under: the chunks, and
        # — in the conditional's branch — the steps of a chunk that is
        # not quiet; the mean has none
        assert loops == (
            ["while", "while/body/closed_call/cond/branch_1_fun/while"]
            if vertex == "winning" else [])
    # the [P, N, 256] comparison never exists as an array: its shape
    # shows inside fusions only
    pairs, computation = f"[{P},{pool + S * E},{active}]", ""
    assert pairs in text
    for line in text.splitlines():
        if line and not line.startswith(" "):
            computation = line.split()[0]
        assert pairs not in line or "fused_computation" in computation, line


def test_window_join_block_form_compiles_at_the_cells_widths(v5e):
    """``nexmark-q8``'s ``join`` vertex at its own widths — 4,096 ids in
    384 own columns a subtask (``window_join`` derived them: 280 ids at
    most), 2 open windows, 192 records a subtask a step from each input,
    320 rows — over a whole block of 1,024 steps of 16 subtasks, inside
    the job's block program: under ``vertex/join`` no loop, no scatter
    and no sort; the placements take the Mosaic kernel over ``2 x 384``
    lanes and the emission over the same 768, nothing is 8,192 lanes
    wide; the comparison of every record with every own column fuses
    without a ``[K, P, B, 384]`` array; and ``join -> sink`` is planned
    ``identity``."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    for p in (bench, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import job
    from benchlib.byname import module_at
    from clonos_tpu.runtime.executor import CompiledJob
    with open(os.path.join(bench, "configs", "nexmark-q8.json")) as f:
        cfg = json.load(f)
    assert "own_columns" not in cfg         # derived, not configured
    compiled = CompiledJob(
        module_at(job.topology_file(cfg, "job.py")).build(cfg),
        log_capacity=8192, max_epochs=8, inflight_ring_steps=2048)
    assert [p.route for _, p in sorted(compiled.edge_plans.items())] == [
        "dynamic", "dynamic", "identity"]
    (vid,) = compiled.own_columns
    op = compiled.job.vertices[vid].operator
    W, C = op.open_windows, op.own_columns
    assert (compiled.job.vertices[vid].name, W, C) == ("join", 2, 384)
    assert compiled.own_columns[vid].shape == (cfg["parallelism"], C)
    K, P, E = cfg["block_steps"], cfg["parallelism"], cfg["edge_capacity"]
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    with histogram.kernel_mesh(mesh, "tasks"):
        lowered, records = lower_block(compiled, K,
                                       SingleDeviceSharding(v5e[0]))
    forms = [r["args"] for r in records if r["name"] == "hist.kernel"]
    assert {f["form"] for f in forms} == {"mxu"}
    assert {(K * P, E, W * C), (K * P, W * C, cfg["join_capacity"])} <= {
        (f["rows"], f["cols"], f["lanes"]) for f in forms}
    exe = lowered.compile()
    mine = [line for line in exe.as_text().splitlines()
            if "vertex/join" in line]
    assert len(mine) > 500
    for op_name in ("while", "scatter", "sort"):
        assert not [line for line in mine
                    if re.search(rf"= \S+ {op_name}\(", line)], op_name
    assert not [line for line in mine if f",{W * cfg['num_keys']}]" in line]
    # the [K, P, B, 384] comparisons never exist as arrays
    assert exe.memory_analysis().temp_size_in_bytes < K * P * E * C


def allround_upstream_job():
    """(``allround-upstream``'s configuration, its job compiled, its
    planned edges by the vertices' names): the logs and rings at a size a
    test can describe quickly; the vertices, their edges and the block's
    steps as the cell has them."""
    import json
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    for p in (bench, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchlib import job
    from benchlib.byname import module_at
    from clonos_tpu.runtime.executor import CompiledJob
    with open(os.path.join(bench, "configs", "allround-upstream.json")) as f:
        cfg = json.load(f)
    compiled = CompiledJob(
        module_at(job.topology_file(cfg, "job.py")).build(cfg),
        log_capacity=8192, max_epochs=8, inflight_ring_steps=2048)
    names = [v.name for v in compiled.job.vertices]
    plans = {(names[e.src], names[e.dst]): compiled.edge_plans[i]
             for i, e in enumerate(compiled.job.edges)
             if i in compiled.edge_plans}
    return cfg, compiled, plans


def test_union_block_form_compiles_at_the_cells_widths(v5e):
    """``allround-upstream``'s ``union`` vertex at its own widths — the
    tumbling window's rows over a static route 256 wide and the sliding
    window's over one 384 wide, into 256 — over a whole block of 1,024
    steps of 8 subtasks, inside the job's block program: under
    ``vertex/union`` no sort, no gather, no scatter and no loop (until
    PR 50 a stable sort of the 640 slots and four gathers of 2,097,152
    elements); the compaction takes the Mosaic kernel, ``[8192, 640] ->
    256`` a field; and the five keyed edges are planned as at the tiny
    size (``tests/test_allround_event_time.py``)."""
    cfg, compiled, plans = allround_upstream_job()
    assert {k: p.route for k, p in plans.items()} == {
        ("event-time", "keyed-state"): "dynamic",
        ("operator-state", "tumbling"): "identity",
        ("tumbling", "sliding"): "static", ("tumbling", "union"): "static",
        ("sliding", "union"): "static"}
    widths = (plans["tumbling", "union"].width,
              plans["sliding", "union"].width)
    K, P, cap = cfg["block_steps"], cfg["parallelism"], cfg["union_capacity"]
    assert widths == (256, 384) and cap == 256
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    with histogram.kernel_mesh(mesh, "tasks"):
        lowered, records = lower_block(compiled, K,
                                       SingleDeviceSharding(v5e[0]))
    forms = [r["args"] for r in records if r["name"] == "hist.kernel"]
    assert {f["form"] for f in forms} == {"mxu"}
    assert [(f["rows"], f["cols"], f["lanes"], f["planes"])
            for f in forms].count((K * P, sum(widths), cap, 4)) == 3
    exe = lowered.compile()
    mine = [line for line in exe.as_text().splitlines()
            if "vertex/union" in line]
    assert len(mine) > 20
    assert any("tpu_custom_call" in line for line in mine)
    for op in ("while", "scatter", "gather", "sort"):
        assert not [line for line in mine
                    if re.search(rf"= \S+ {op}\(", line)], op


@pytest.mark.parametrize("past", [False, True],
                         ids=["200-keys-dense", "past-the-constant-gather"])
def test_keyed_readback_compiles_without_a_gather_at_the_cells_widths(
        v5e, past, monkeypatch):
    """``allround-upstream``'s ``keyed-state`` vertex — the keyed mapper
    behind the job's one dynamic exchange, 8 subtasks x 512 receive slots
    x 1,024 steps read out of ``[1024, 8, 200]`` running counts — inside
    the job's block program: under ``vertex/keyed-state`` no gather (until
    PR 52 a ``take_along_axis`` of 4,194,304 elements, half of the
    block), no scatter, no loop, and nothing anywhere the size of slots
    x key lanes (3.4 GB: the compare, the select and the sum fuse). The
    same job one key past ``_DENSE_READBACK_KEYS`` keeps the gather (the
    constant set to 199: at 8,193 keys the job's windows and rings need
    30 GB, which the compiler refuses)."""
    from clonos_tpu.api import operators
    keys = 200
    assert keys <= operators._DENSE_READBACK_KEYS
    if past:
        monkeypatch.setattr(operators, "_DENSE_READBACK_KEYS", keys - 1)
    cfg, compiled, plans = allround_upstream_job()
    edge = plans["event-time", "keyed-state"]
    K, P, B = cfg["block_steps"], cfg["parallelism"], edge.width
    assert (edge.route, K, P, B) == ("dynamic", 1024, 8, 512)
    mesh = Mesh(np.array(v5e[:1]), ("tasks",))
    with histogram.kernel_mesh(mesh, "tasks"):
        lowered, _ = lower_block(compiled, K, SingleDeviceSharding(v5e[0]))
    exe = lowered.compile()
    mine = [line for line in exe.as_text().splitlines()
            if "vertex/keyed-state" in line]
    assert any("keyed-state/readback" in line for line in mine)
    found = {op: [line for line in mine
                  if re.search(rf"= \S+ {op}\(", line)]
             for op in ("while", "scatter", "gather")}
    assert bool(found["gather"]) == past
    assert not found["while"] and not found["scatter"]
    # all of the program's scratch is under a byte a slot a key lane
    assert exe.memory_analysis().temp_size_in_bytes < K * P * B * keys


@pytest.mark.parametrize("shape,rung,gathers", [
    ((1024, 16, 320), 20480, 0), ((1024, 16, 320), 5120, 4),
    ((1024, 16, 256), 8192, 0), ((512, 16, 640), 320, 4)],
    ids=["nexmark-q8", "nexmark-q8-sparse", "nexmark-q3", "kafka-window-64"])
def test_sink_tap_compiles_without_sort_or_scatter_at_the_cells_shapes(
        v5e, shape, rung, gathers):
    """The sink tap's compaction of a cell's sink block (``[K, P,
    capacity]``) as the chip's compiler leaves it: the two densest cells
    at the rung their ~9,970 and ~3,900 rows a subtask take (packed by
    shifts: no gather), a sparser block of the first and the sparsest
    cell's (packed by rank: four gathers a slot); never a scatter or a
    sort, and scratch a small share of what the carry leaves free."""
    from clonos_tpu.api.records import RecordBatch
    from clonos_tpu.runtime import sinktap
    k, _, cap = shape
    assert rung in sinktap.ladder(k * cap)
    assert sinktap.packs_by_shifts(rung, k * cap) is (gathers == 0)
    sh = SingleDeviceSharding(v5e[0])
    batch = RecordBatch(*[jax.ShapeDtypeStruct(shape, dt, sharding=sh)
                          for dt in (jnp.int32,) * 3 + (jnp.bool_,)])
    exe = jax.jit(lambda b: sinktap.pack_lanes(b, rung)).lower(
        batch).compile()
    text = exe.as_text()
    count = lambda op: len(re.findall(rf"= \S+ {op}\(", text))
    assert (count("gather"), count("scatter"), count("sort")) == (
        gathers, 0, 0)
    assert exe.memory_analysis().temp_size_in_bytes < 512 << 20
