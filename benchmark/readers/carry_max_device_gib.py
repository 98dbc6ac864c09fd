"""Set-up, from inside: GiB of the initial carry on the fullest device
when ``CompiledJob.build_carry`` had built it — the counter
``carry.max_device_bytes``, summed over ``addressable_shards`` as they
lie (a replicated leaf counts on every device that holds it). About a
quarter of ``carry.bytes`` on a 2x2 task mesh: the witness that no
device was handed a whole sharded leaf. None on a program that does not
count it."""

from benchlib import program_spans


def read(run):
    fullest = program_spans.of(run).counters.get("carry.max_device_bytes")
    return None if fullest is None else fullest / 2**30
