"""Operators, from inside: blocks whose own-column lookup compared every
slot of every receive window, because some step sent more targets past
the 128-slot head than the lookup keeps tails for
(``_OwnColumns._by_head_and_tails``), per committed epoch over the whole
run — the program's ``lookup.dense_blocks.<vertex>`` counters, which the
fence adds to from the operator state its health read brings back. It
says how often the split did not engage: 0 where the traffic has one hot
key at a time, 4 (every block of an epoch) where it is no help. None
where the program keeps no such counter."""

from benchlib import program_spans


def read(run):
    dense = [n for name, n in program_spans.of(run).counters.items()
             if name.startswith("lookup.dense_blocks.")]
    if not dense or not run.stamps:
        return None
    return sum(dense) / len(run.stamps)
