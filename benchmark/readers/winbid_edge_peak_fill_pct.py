"""Exchange, from inside: how full the skewed edge ``bids -> winning`` of
the ``nexmark-average-price`` job has been at its fullest, in percent of
its capacity — the program's ``exchange.peak_records.bids->winning``
counter (the most bids one ``winning`` subtask was sent in one step: the
hot auction's owner; it only grows) over the configuration's
``edge_capacity``. Past 100 the edge would have dropped records, which
stops the run; the mean target is sent ~60, and the auctions' edge, which
shares the capacity, ~4."""

from benchlib import program_spans


def read(run):
    peak = program_spans.of(run).counters.get(
        "exchange.peak_records.bids->winning")
    if not peak or "edge_capacity" not in run.cfg:
        return None
    return 100.0 * peak / run.cfg["edge_capacity"]
