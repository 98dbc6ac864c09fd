"""What a kernel has to move, from its shapes. Kept with the benchmark so
that no PR that claims a gain can change how a roofline share is counted.

The keyed histogram (``clonos_tpu/ops/histogram.py``) reads ``rows x
cols`` int32 keys and as many values and writes ``rows x key_lanes`` int32
sums (and as many counts in its sums-and-counts variant). The operations
the algorithm needs are one add per record, far under any compute peak,
so its roofline is the memory one: bytes over HBM bandwidth. What the
kernel actually executes (since PR 30 a factored one-hot product on the
MXU: a one-hot of each record's key over key blocks and planes, and a
matrix product with it per plane; before that a compare and a select per
record per key lane on the VPU) is its implementation's cost, not the
algorithm's, and is why its share is low.
"""

from __future__ import annotations


def hist_bytes(rows: int, cols: int, key_lanes: int, outputs: int) -> int:
    """Bytes one call of the histogram kernel must read and write."""
    return 4 * (2 * rows * cols + outputs * rows * key_lanes)
