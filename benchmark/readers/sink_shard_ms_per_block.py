"""Job API / sink, from inside: the program's ``block.sink.shard`` span
(``TransactionLog.absorb``: mask, stack and append per sink subtask), mean
over the window's blocks. Third of the three parts of the outside
``sink_absorb_ms_per_block``."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_ms(run, "block.sink.shard")
