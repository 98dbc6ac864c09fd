"""Job API / sink, from inside: rows the transactional sink handed the
transaction log per block (the ``rows`` of each ``block.sink.shard``
span, what the ``sink.rows`` counter adds up), mean over the window's
blocks: the density the tap's ladder of row budgets sees."""

from benchlib import program_spans


def read(run):
    return program_spans.mean_arg(run, "block.sink.shard", "rows")
