"""The named scopes of the device programs: one vocabulary.

A ``jax.named_scope`` is metadata on the lowered ops — the ``op_name`` of
every HLO instruction — so a profile of a live job (xprof, or
``benchmark/benchlib/scope_times.py`` for the benchmark's per-layer
metrics) groups device time by these names and not by fusion numbers.
A scope changes no operand, no order of operations and no sharding; the
program with the scopes patched out is the same program
(``tests/test_named_scopes.py``).

The block program (``CompiledJob.run_block``), the replay program
(``causal/recovery.py``) and the fence's ``_roll`` / ``_trunc`` take the
same names where they run the same code. A path reads
``<layer>[/<vertex name>][/<part>][/hist]``:

==================  =====================================================
``vertex/<name>``   an operator's ``process_block*`` (``run_block``,
                    ``_replay_block``); beneath it, where the operator
                    has such a part:
``.../lookup``      a record's own column (``_OwnColumns._column``: the
                    windowed top and the window join); with it, a
                    step's sum, earliest and latest per own column
                    (``SessionWindowOperator._arrivals``); both as a
                    block's head and tails, with the selects that pick
                    the tails out and put them back (``_column_block``,
                    ``_arrivals_block``); a chunk's
                    opening records against the own columns and the
                    intervals that are active in it
                    (``BestInIntervalJoinOperator._chunk``)
``.../place``       records into ``slot x key`` lanes (``_EventTimeSlots
                    ._block_place``); a step's arrivals into sessions: the
                    running latest, where a session starts, where two
                    merge (``SessionWindowOperator.process_block``); a
                    chunk's probes into its active intervals, the best
                    of each (``BestInIntervalJoinOperator._chunk``)
``.../segsum``      the accumulators' running sum that restarts at a fire
                    (``_block_accumulate``), or where a session starts
                    (``SessionWindowOperator.process_block``)
``.../emit``        a fire's rows, compacted (``_emit`` of the window join,
                    the windowed top and the session window)
``.../readback``    the running value read back per record: a compare
                    over the key lanes, a gather from a wide table
                    (``KeyedReduceOperator.process_block``), a static
                    gather (``process_block_static_keys``)
``.../compact``     records packed by rank to the front: both inputs'
                    (``UnionOperator.process_block``), a chunk's
                    (``_ChunkedJoin._packed``)
``exchange``        an edge's route (``CompiledJob.route_edge``);
``exchange/rank``   a record's arrival rank at its target (the running
                    count, ``matops.running_count``)
``exchange/place``  the fields moved to ``target x rank`` (three keyed
                    histograms, or an element scatter)
``exchange/plan``   a static gather plan (``StaticRoutePlan.apply``)
``causal-log``      the determinant logs;
``causal-log/rows``      the block's determinant rows (``_det_rows``)
``causal-log/own``       each task's own log (append, epoch start,
                         truncate)
``causal-log/replicas``  the copies downstream tasks keep of it
                         (``sharing_depth``'s tax)
``inflight-ring``   the in-flight log of a vertex's output
``hist``            ``ops.histogram.keyed_hist``: the kernel with the
                    one-hot and relayouts around it; a leaf under
                    whichever scope called it
==================  =====================================================

What a block runs under no scope — the depth-1 shift of the routed
batches, the per-target exchange counters, ``constrain_carry``, copies the
compiler inserts — stays so and is measured (``block_unscoped_pct``).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax

VERTEX = "vertex"
EXCHANGE = "exchange"
CAUSAL_LOG = "causal-log"
INFLIGHT_RING = "inflight-ring"
HIST = "hist"

#: layer -> the parts that may appear directly beneath it (beneath
#: ``vertex/<name>`` for :data:`VERTEX`); :data:`HIST` is a leaf anywhere
PARTS: Dict[str, Tuple[str, ...]] = {
    VERTEX: ("lookup", "place", "segsum", "emit", "readback", "compact"),
    EXCHANGE: ("rank", "place", "plan"),
    CAUSAL_LOG: ("rows", "own", "replicas"),
    INFLIGHT_RING: (),
}


def scoped(name: str):
    """Decorator: the function's ops traced inside
    ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
