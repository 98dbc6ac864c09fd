"""Block executor, on the device: of the block program's self time inside
the traced steady span, the percentage under no scope of the vocabulary —
the depth-1 shift of the routed batches, the exchange's per-target
counters, ``constrain_carry``, copies the compiler inserts. Prints,
before the result line, the whole scope table of the run: every vertex by
name, every part, ms per block and share, and what is under no scope by
kind of operation and its twelve largest (``benchlib/scope_times.py``)."""

from benchlib import scope_times


def read(run):
    st = scope_times.of(run)
    if st is None:
        return None
    print("\n".join(scope_times.table(st)), flush=True)
    return 100.0 * st.by_scope.get((), 0.0) / st.total_s
