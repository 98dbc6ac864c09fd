"""End-to-end dataflow: the SocketWindowWordCount shape running as jitted
supersteps, checked against a plain-Python oracle. (The reference's analog
tier is the MiniCluster ITCases, e.g.
flink-tests/.../checkpointing/*ITCase*.)"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from clonos_tpu.api import records
from clonos_tpu.api.environment import StreamEnvironment
from clonos_tpu.api.operators import SyntheticSource
from clonos_tpu.causal import log as clog
from clonos_tpu.causal import determinant as det
from clonos_tpu.parallel import routing
from clonos_tpu.runtime.executor import LocalExecutor, DETS_PER_STEP


VOCAB, BATCH, NKEYS = 13, 8, 13


def _build_wordcount(parallelism=2, window=1_000_000):
    env = StreamEnvironment(name="wordcount", num_key_groups=16)
    (env.synthetic_source(vocab=VOCAB, batch_size=BATCH,
                          parallelism=parallelism)
        .key_by()
        .window_count(num_keys=NKEYS, window_size=window)
        .sink())
    return env.build()


def _oracle_counts(parallelism, steps):
    """Reproduce SyntheticSource key generation on the host."""
    counts = np.zeros(VOCAB, np.int64)
    seq = np.zeros(parallelism, np.int64)
    for _ in range(steps):
        for s in range(parallelism):
            lane = np.arange(BATCH)
            mix = ((seq[s] + lane) * 1024 + s).astype(np.int32)
            u = np.asarray(mix, np.uint64) & 0xFFFFFFFF
            u = ((u ^ (u >> 16)) * 0x7FEB352D) & 0xFFFFFFFF
            u = ((u ^ (u >> 15)) * 0x846CA68B) & 0xFFFFFFFF
            u = (u ^ (u >> 16)) & 0xFFFFFFFF
            np.add.at(counts, (u % VOCAB).astype(np.int64), 1)
            seq[s] += BATCH
    return counts


def test_wordcount_counts_match_oracle():
    job = _build_wordcount(parallelism=2)
    ex = LocalExecutor(job, steps_per_epoch=4, log_capacity=1 << 10)
    for _ in range(6):
        ex.step()
    # Window never fired (huge window) -> all counts in the window operator
    # state. Records need one superstep to traverse the source->window edge,
    # so the window has seen 5 of the 6 source batches.
    acc = np.asarray(ex.vertex_state(1)["acc"]).sum(axis=0)
    np.testing.assert_array_equal(acc, _oracle_counts(2, 5))
    # Key ownership: each subtask only holds keys of its key-group range.
    acc2 = np.asarray(ex.vertex_state(1)["acc"])
    G, P = job.num_key_groups, 2
    for k in range(VOCAB):
        kg = int(np.asarray(routing.key_group(jnp.asarray([k]), G))[0])
        owner = kg * P // G
        for t in range(P):
            if t != owner:
                assert acc2[t, k] == 0


def test_window_fires_and_sink_receives():
    job = _build_wordcount(parallelism=1, window=5)
    ex = LocalExecutor(job, steps_per_epoch=4)
    seen = []
    # Force time forward by faking the time source.
    times = iter([0, 1, 2, 10, 11, 12, 13])
    ex.time_source.now = lambda: next(times)
    for _ in range(6):
        out = ex.step()
        for vid, batch in out.sinks.items():
            seen += records.to_numpy(records.RecordBatch(
                batch.keys.reshape(-1), batch.values.reshape(-1),
                batch.timestamps.reshape(-1), batch.valid.reshape(-1)))
    # Window [0,5) fired when time jumped to 10. With the depth-1 pipeline,
    # the window had received the batches emitted at times 0 and 1 (the
    # time-2 batch arrives at time 10 and joins the *new* window).
    assert seen, "window never fired into sink"
    total = sum(v for _, v, _ in seen)
    assert total == 2 * BATCH
    assert all(ts == 5 for _, _, ts in seen)  # window end timestamp


def test_determinants_logged_per_superstep():
    job = _build_wordcount(parallelism=2)
    ex = LocalExecutor(job, steps_per_epoch=4)
    n = 3
    for _ in range(n):
        ex.step()
    sizes = ex.log_sizes()
    assert sizes.shape == (job.total_subtasks(),)
    np.testing.assert_array_equal(sizes, np.full(sizes.shape, n * DETS_PER_STEP))
    # Decode one log: tags cycle TIMESTAMP, ORDER, BUFFER_BUILT and the
    # TIMESTAMP payload matches the recorded host time.
    one = jax.tree_util.tree_map(lambda x: x[0], ex.carry.logs)
    buf, count, _ = clog.get_determinants(one, 0, 64)
    rows = np.asarray(buf)[: int(count)]
    dets = det.unpack_batch(rows)
    assert [d.TAG for d in dets[:4]] == [det.TIMESTAMP, det.RNG, det.ORDER,
                                         det.BUFFER_BUILT]
    assert dets[0].timestamp == ex.step_input_history[0][0]
    assert dets[1].value == ex.step_input_history[0][1]
    src_emit = dets[3]
    assert src_emit.num_records == BATCH


def test_epoch_roll_and_truncation():
    job = _build_wordcount(parallelism=1)
    ex = LocalExecutor(job, steps_per_epoch=2)
    ex.run_epoch()          # epoch 0: 2 steps
    ex.run_epoch()          # epoch 1: 2 steps
    assert ex.epoch_id == 2
    sizes = ex.log_sizes()
    np.testing.assert_array_equal(sizes, np.full(sizes.shape,
                                                 4 * DETS_PER_STEP))
    ex.notify_checkpoint_complete(0)   # drop epoch 0 determinants
    sizes = ex.log_sizes()
    np.testing.assert_array_equal(sizes, np.full(sizes.shape,
                                                 2 * DETS_PER_STEP))


def test_scan_epoch_equals_stepwise():
    job = _build_wordcount(parallelism=2)
    ex1 = LocalExecutor(job, steps_per_epoch=4)
    ex2 = LocalExecutor(job, steps_per_epoch=4)
    times = list(range(0, 40, 10))
    ex1.time_source.now = lambda it=iter(times): next(it)
    ex2.time_source.now = lambda it=iter(times): next(it)
    ex1._rng = np.random.RandomState(7)
    ex2._rng = np.random.RandomState(7)
    for _ in range(4):
        ex1.step()
    ex1.run_epoch()   # no steps left; just rolls the epoch marker
    ex2.run_epoch()
    a = jax.device_get(ex1.carry)
    b = jax.device_get(ex2.carry)
    flat_a, _ = jax.tree_util.tree_flatten(a)
    flat_b, _ = jax.tree_util.tree_flatten(b)
    for xa, xb in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


@pytest.mark.parametrize("block_steps", [2, 3], ids=["quarter", "remainder"])
def test_device_source_job_is_the_same_job_at_any_block_size(
        tmp_path, block_steps):
    """One block loop for every job: a job without a feed vertex draws
    its causal inputs per block, from the same stream in the same order,
    so under logical time its state, its determinant logs and their heads
    after two epochs do not depend on how an epoch is cut into blocks
    (whole epoch / four full blocks / blocks with a remainder)."""
    from clonos_tpu.runtime.cluster import ClusterRunner

    def run(tag, bs):
        r = ClusterRunner(_build_wordcount(parallelism=2, window=5),
                          steps_per_epoch=8, block_steps=bs,
                          log_capacity=512, max_epochs=8,
                          inflight_ring_steps=32, seed=3, logical_time=True,
                          checkpoint_dir=str(tmp_path / tag))
        r.run_epoch(complete_checkpoint=True)
        r.run_epoch(complete_checkpoint=False)
        logs = r.executor.carry.logs
        return (r.state_digest(), np.asarray(logs.head).tolist(),
                list(r.executor.step_input_history))

    whole, cut = run("whole", 8), run("cut", block_steps)
    assert whole[1] == cut[1] and min(whole[1]) >= 16 * DETS_PER_STEP
    assert whole[2] == cut[2] and len(cut[2]) == 16
    assert whole[0] == cut[0]
