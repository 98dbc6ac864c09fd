"""In-flight log: retained output batches for replay after downstream failure.

Capability parity with the reference's ``inflightlogging`` package
(flink-runtime .../inflightlogging — InFlightLog.java API: log/getIterator/
notifyCheckpointComplete; InMemorySubpartitionInFlightLogger.java;
SpillableSubpartitionInFlightLogger.java:45 with per-epoch spill files so a
completed checkpoint deletes its file; SpilledReplayIterator.java:61 with
prefetch threads) — re-designed for TPU:

- The hot path is a **device ring**: one edge's routed output batches are a
  ``[S, P, cap]`` tensor ring over supersteps, appended in the jitted step
  (same absolute-offset/epoch-index scheme as the causal log — see
  causal/log.py). Replay of the last epochs is a device-side slice feed —
  no host round trip for the common in-HBM case.
- **Spill** runs at epoch boundaries on the host: the just-finished epoch's
  step range is device_get as one contiguous block and written to one file
  per epoch (truncation == file delete, exactly the reference's trick).
  HBM->host DRAM->disk instead of JVM heap->disk.
- **Replay** for spilled epochs is a producer/consumer iterator with a
  prefetch thread (SpilledReplayIterator analog) that streams epoch files
  back as device arrays in step order.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from clonos_tpu.api.records import RecordBatch


class EdgeLogState(NamedTuple):
    """Device ring of one edge's routed batches, indexed by absolute
    superstep count. Offsets follow causal/log.py discipline: absolute,
    monotonic; ring position = offset & (S-1); truncation moves ``tail``."""

    keys: jnp.ndarray         # int32[S, P, cap]
    values: jnp.ndarray       # int32[S, P, cap]
    timestamps: jnp.ndarray   # int32[S, P, cap]
    valid: jnp.ndarray        # bool[S, P, cap]
    head: jnp.ndarray         # int32 scalar: absolute steps appended
    tail: jnp.ndarray         # int32 scalar: oldest retained step
    epoch_starts: jnp.ndarray # int32[max_epochs]
    epoch_base: jnp.ndarray   # int32 scalar
    latest_epoch: jnp.ndarray # int32 scalar

    @property
    def ring_steps(self) -> int:
        return self.keys.shape[0]

    @property
    def max_epochs(self) -> int:
        return self.epoch_starts.shape[0]


def create(ring_steps: int, parallelism: int, capacity: int,
           max_epochs: int) -> EdgeLogState:
    if ring_steps & (ring_steps - 1):
        raise ValueError(f"ring_steps must be a power of two, got {ring_steps}")
    z = jnp.asarray(0, jnp.int32)
    shape = (ring_steps, parallelism, capacity)
    return EdgeLogState(
        keys=jnp.zeros(shape, jnp.int32), values=jnp.zeros(shape, jnp.int32),
        timestamps=jnp.zeros(shape, jnp.int32),
        valid=jnp.zeros(shape, jnp.bool_),
        head=z, tail=z, epoch_starts=jnp.zeros((max_epochs,), jnp.int32),
        epoch_base=z, latest_epoch=z)


def size(state: EdgeLogState) -> jnp.ndarray:
    return state.head - state.tail


def overflowed(state: EdgeLogState) -> jnp.ndarray:
    """True when un-truncated (and un-spilled) steps exceed the ring — the
    control plane must spill or checkpoint before this bites (the JVM analog
    is the buffer pool running dry, which *blocks* the producer; here the
    executor's epoch loop checks and stalls)."""
    return size(state) > state.ring_steps


def append_step(state: EdgeLogState, batch: RecordBatch) -> EdgeLogState:
    """Log one superstep's routed batch (reference InFlightLog.log)."""
    pos = state.head & (state.ring_steps - 1)
    return state._replace(
        keys=state.keys.at[pos].set(batch.keys),
        values=state.values.at[pos].set(batch.values),
        timestamps=state.timestamps.at[pos].set(batch.timestamps),
        valid=state.valid.at[pos].set(batch.valid),
        head=state.head + 1)


def append_block(state: EdgeLogState, block: RecordBatch) -> EdgeLogState:
    """Log a whole block of K steps' batches ([K, P, cap] leaves) in one
    scatter — the block-executor bulk path. K must be <= ring_steps (the
    executor enforces this), so ring positions are unique."""
    K = block.keys.shape[0]
    idx = (state.head + jnp.arange(K, dtype=jnp.int32)) & (state.ring_steps - 1)
    return state._replace(
        keys=state.keys.at[idx].set(block.keys, unique_indices=True),
        values=state.values.at[idx].set(block.values, unique_indices=True),
        timestamps=state.timestamps.at[idx].set(block.timestamps,
                                                unique_indices=True),
        valid=state.valid.at[idx].set(block.valid, unique_indices=True),
        head=state.head + K)


def start_epoch(state: EdgeLogState, epoch_id) -> EdgeLogState:
    """Record epoch ``epoch_id``'s replay-start offset (= ``head``, the
    fence). The batch appended at the fence's last step is still *in
    flight* (depth-1 pipeline — its consumer reads it one step after the
    fence), but that fence-spanning batch is checkpointed as the edge
    buffer of the LeanSnapshot, so the ring needs to retain only the
    post-fence steps (the aligned-barrier boundary condition the reference
    gets from barriers flowing through the pipeline rides the snapshot
    instead of the log)."""
    e = jnp.asarray(epoch_id, jnp.int32)
    slot = e % state.max_epochs
    return state._replace(
        epoch_starts=state.epoch_starts.at[slot].set(state.head),
        latest_epoch=jnp.maximum(state.latest_epoch, e))


def epoch_start_step(state: EdgeLogState, epoch_id) -> jnp.ndarray:
    e = jnp.asarray(epoch_id, jnp.int32)
    return state.epoch_starts[e % state.max_epochs]


def truncate(state: EdgeLogState, completed_epoch) -> EdgeLogState:
    """Checkpoint complete: drop steps of epochs <= completed_epoch
    (reference notifyCheckpointComplete -> per-epoch file delete)."""
    e = jnp.asarray(completed_epoch, jnp.int32)
    new_tail = jnp.maximum(epoch_start_step(state, e + 1), state.tail)
    return state._replace(tail=new_tail,
                          epoch_base=jnp.maximum(e + 1, state.epoch_base))


def slice_steps_at(state: EdgeLogState, abs_step, max_out: int
                   ) -> RecordBatch:
    """Gather ``max_out`` steps from exactly ``abs_step`` with NO tail
    clamp: slots before the ring tail come back as whatever the ring
    holds there (stale or clobbered) — the caller must mask them. Used
    by recovery's uniform replay windows, whose first window starts one
    slot before the fence (that dead slot is replaced by the
    checkpointed edge buffer; see failover._replay_inputs)."""
    start = jnp.asarray(abs_step, jnp.int32)
    count = jnp.clip(state.head - start, 0, max_out)
    idx = jnp.arange(max_out, dtype=jnp.int32)
    pos = (start + idx) & (state.ring_steps - 1)
    live = (idx < count)[:, None, None]
    return RecordBatch(
        keys=jnp.where(live, state.keys[pos], 0),
        values=jnp.where(live, state.values[pos], 0),
        timestamps=jnp.where(live, state.timestamps[pos], 0),
        valid=jnp.where(live, state.valid[pos], False))


def slice_steps(state: EdgeLogState, abs_step, max_out: int
                ) -> Tuple[RecordBatch, jnp.ndarray, jnp.ndarray]:
    """Gather up to ``max_out`` retained steps from ``abs_step``. Returns
    (stacked RecordBatch [max_out, P, cap], count, start). The replay feed
    (reference getInFlightIterator)."""
    start = jnp.maximum(jnp.asarray(abs_step, jnp.int32), state.tail)
    count = jnp.clip(state.head - start, 0, max_out)
    idx = jnp.arange(max_out, dtype=jnp.int32)
    pos = (start + idx) & (state.ring_steps - 1)
    live = (idx < count)[:, None, None]
    batch = RecordBatch(
        keys=jnp.where(live, state.keys[pos], 0),
        values=jnp.where(live, state.values[pos], 0),
        timestamps=jnp.where(live, state.timestamps[pos], 0),
        valid=jnp.where(live, state.valid[pos], False))
    return batch, count, start


# --- host spill path ---------------------------------------------------------


class SpillPolicy:
    """When to move completed-epoch step ranges out of the device ring
    (reference InFlightLogConfig spill.policy eager|availability|epoch)."""

    EAGER = "eager"            # spill every epoch as soon as it closes
    AVAILABILITY = "availability"  # spill when ring occupancy crosses a ratio
    DISABLED = "disabled"      # in-memory only (InMemory logger equivalent)


class SpillingInFlightLog:
    """Host-side owner of one edge's spilled epochs.

    A thin RecordBatch adapter over :class:`storage.TieredEpochStore`
    (the generalized tier fabric shared with the determinant logs): one
    checksummed segment file per epoch so truncation deletes files —
    the reference's SpillableSubpartitionInFlightLogger file-per-epoch
    design. Writes (including the device→host copy) happen on the
    store's background writer; a flush failure keeps the data
    host-resident (reference keeps the buffer in memory on flush
    failure) so replay still works.
    """

    def __init__(self, spool_dir: Optional[str], edge_id: int,
                 policy: str = SpillPolicy.EAGER,
                 availability_trigger: float = 0.3,
                 host_budget_epochs: Optional[int] = 2):
        from clonos_tpu.storage import TieredEpochStore
        self.edge_id = edge_id
        self.policy = policy
        self.availability_trigger = availability_trigger
        self.spool_dir = spool_dir
        self.store = TieredEpochStore(
            spool_dir, f"edge{edge_id}",
            durable=bool(spool_dir) and policy != SpillPolicy.DISABLED,
            host_budget_epochs=host_budget_epochs)

    def _path(self, epoch: int) -> str:
        return self.store.segment_path(epoch)

    def spill_epoch(self, epoch: int, start_step: int,
                    batches: RecordBatch) -> None:
        """Accept one closed epoch's stacked steps ([n, P, cap] per
        field) — device arrays welcome; the d2h copy overlaps the next
        epoch's compute on the store's writer thread."""
        self.store.put(epoch, start_step, {
            "keys": batches.keys, "values": batches.values,
            "timestamps": batches.timestamps, "valid": batches.valid,
        })

    def truncate(self, completed_epoch: int) -> None:
        self.store.truncate(completed_epoch)

    def retained_epochs(self) -> List[int]:
        return self.store.retained_epochs()

    def load_epoch(self, epoch: int) -> Tuple[int, RecordBatch]:
        """Synchronous read of one epoch (start_step, steps[n, P, cap])
        from whichever tier holds it (host buffer or verified disk
        segment)."""
        start, payload = self.store.load_epoch(epoch)
        return start, RecordBatch(
            jnp.asarray(payload["keys"]), jnp.asarray(payload["values"]),
            jnp.asarray(payload["timestamps"]), jnp.asarray(payload["valid"]))

    def attach_digest(self, epoch: int, digest: str) -> None:
        """Pin the audit ledger's ring-channel digest on the spilled
        epoch's segment (diff_ledgers then verifies refills for free)."""
        self.store.attach_digest(epoch, digest)

    def drain(self) -> None:
        """Block until pending spill writes are durable (tests/shutdown)."""
        self.store.drain()

    def close(self) -> None:
        self.store.close()


class ReplayIterator:
    """Prefetching replay of epochs [from_epoch, to_epoch], step-ordered
    (reference SpilledReplayIterator.java:61: producer thread fills
    per-epoch deques; consumer blocks on the deque head).

    ``skip_steps`` skips already-delivered steps of the first epoch
    (reference InFlightLogRequestEvent.numBuffersToSkip dedup)."""

    def __init__(self, log: SpillingInFlightLog, from_epoch: int,
                 to_epoch: int, skip_steps: int = 0, prefetch: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
        self._epochs = [e for e in log.retained_epochs()
                        if from_epoch <= e <= to_epoch]
        self._log = log
        self._skip = skip_steps
        self._stop = False
        self._t = threading.Thread(target=self._produce, daemon=True)
        self._t.start()

    def close(self) -> None:
        """Release the producer if the consumer stops early (a bounded
        prefetch queue would otherwise block the thread forever with an
        epoch batch pinned in memory)."""
        self._stop = True
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def _produce(self):
        # Epoch-granular prefetch: the producer thread reads files ahead
        # while the consumer drains — the reference's async-read deques.
        for e in self._epochs:
            if self._stop:
                return
            try:
                item = self._log.load_epoch(e)
            except Exception as exc:
                # A torn segment (or any refill failure) must reach the
                # CONSUMER: dying here would leave it blocked on the
                # queue forever. The exception rides the queue and
                # re-raises on the consumer thread.
                item = exc
            while not self._stop:
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._stop or isinstance(item, Exception):
                return
        while not self._stop:
            try:
                self._q.put(None, timeout=0.1)
                return
            except queue.Full:
                continue

    def epochs(self) -> Iterator[Tuple[int, RecordBatch]]:
        """Prefetched (start_step, stacked steps) per retained epoch —
        the chunk-assembly feed for recovery's spill reads."""
        first = True
        while True:
            item = self._q.get()
            if item is None:
                return
            if isinstance(item, Exception):
                raise item
            start, batch = item
            if first and self._skip:
                start = start + self._skip
                batch = jax.tree_util.tree_map(
                    lambda x: x[self._skip:], batch)
            first = False
            yield start, batch

    def __iter__(self) -> Iterator[Tuple[int, RecordBatch]]:
        for start, batch in self.epochs():
            n = batch.keys.shape[0]
            for i in range(n):
                yield (start + i, jax.tree_util.tree_map(
                    lambda x, i=i: x[i], batch))
