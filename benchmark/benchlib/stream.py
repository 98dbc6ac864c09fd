"""The stream a cell feeds: one table per partition, made from the seed,
which the reader indexes by offset modulo its length.

Nothing the size of the whole stream exists: a 40 s backlog window at
6.5 M records/s would be 2 GB of records. Offsets are absolute and the
table is immutable, so ``read_at`` serves a recovery's re-read of any
range, however old. The reader stands for the broker (a Kafka topic with
one partition per source subtask) and is the benchmark's, not the
program's: it has the four methods ``clonos_tpu.api.feeds.FeedReader``
asks for and shares no code with it.
"""

from __future__ import annotations

import numpy as np


class TableStream:
    """``keys`` / ``vals``: ``[partitions, table_steps * batch]`` int32.

    Partition ``p``'s record at absolute offset ``o`` is entry
    ``o % (table_steps * batch)`` of row ``p``; source subtask ``p``
    reads ``batch`` records per step, so step ``s`` reads table step
    ``s % table_steps``. Keys are drawn over ``[0, num_keys)`` as
    ``key_dist`` says (``draw_keys``) and values uniformly from
    ``[1, 2**value_bits)``: window sums pass 2**24 within a few windows
    (an f32 accumulation anywhere would show) and never cancel to 0."""

    def __init__(self, seed: int, partitions: int, batch: int,
                 table_steps: int, num_keys: int, value_bits: int,
                 key_dist: dict):
        rng = np.random.default_rng(int(seed))
        n = table_steps * batch
        self.batch = batch
        self.table_steps = table_steps
        self.num_keys = num_keys
        self.keys = draw_keys(rng, key_dist, num_keys, (partitions, n))
        self.vals = rng.integers(1, 1 << value_bits, (partitions, n),
                                 dtype=np.int32)

    @property
    def partitions(self) -> int:
        return self.keys.shape[0]

    def take(self, partition: int, offset: int, n: int):
        """Records ``[offset, offset + n)`` of one partition."""
        size = self.keys.shape[1]
        lo = offset % size
        if lo + n <= size:
            return (self.keys[partition, lo:lo + n],
                    self.vals[partition, lo:lo + n])
        idx = np.arange(offset, offset + n) % size
        return self.keys[partition, idx], self.vals[partition, idx]


def draw_keys(rng: np.random.Generator, key_dist: dict, num_keys: int,
              shape) -> np.ndarray:
    """int32 keys of a configuration's ``key_dist``: ``{"kind":
    "uniform"}``, or ``{"kind": "zipf", "s": <exponent>}`` — rank ``r``
    (from 1) drawn with weight ``r ** -s`` over the ``num_keys`` keys, the
    ranks dealt to keys by a permutation from the seed, so that the hot
    keys fall on key groups as chance has it and not on the first."""
    kind = key_dist["kind"]
    if kind == "uniform":
        return rng.integers(0, num_keys, shape, dtype=np.int32)
    if kind == "zipf":
        weight = np.arange(1, num_keys + 1, dtype=np.float64) \
            ** -float(key_dist["s"])
        key_of_rank = rng.permutation(num_keys).astype(np.int32)
        return key_of_rank[rng.choice(num_keys, size=shape,
                                      p=weight / weight.sum())]
    raise ValueError(f"key_dist kind {kind!r}: 'uniform' or 'zipf'")


class TableFeedReader:
    """Rewindable partitioned feed over a :class:`TableStream`. Every
    pull is a full batch: the feed never runs dry, so the step a record
    lands in does not depend on timing."""

    def __init__(self, stream: TableStream):
        self.stream = stream
        self.cursor = [0] * stream.partitions
        self._full = {}

    def pull_block(self, subtask: int, batch: int, k: int):
        n = k * batch
        ks, vs = self.stream.take(subtask, self.cursor[subtask], n)
        self.cursor[subtask] += n
        counts = self._full.get((k, batch))
        if counts is None:
            counts = self._full[(k, batch)] = np.full((k,), batch, np.int32)
        return ks.reshape(k, batch), vs.reshape(k, batch), counts

    def pull(self, subtask: int, max_n: int):
        ks, vs = self.stream.take(subtask, self.cursor[subtask], max_n)
        self.cursor[subtask] += max_n
        return ks.tolist(), vs.tolist()

    def read_at(self, subtask: int, offset: int, n: int):
        return self.stream.take(subtask, offset, n)

    def notify_checkpoint_complete(self, offsets) -> None:
        """Nothing to release: the table is the retention."""
