"""Exchange, from inside: how full the skewed edge ``auctions -> join``
of the ``nexmark-local-items`` job has been at its fullest, in percent of
its capacity — the program's ``exchange.peak_records.auctions->join``
counter (the most auctions one ``join`` subtask was sent in one step: the
hot sellers' owner; it only grows) over the configuration's
``edge_capacity``. Past 100 the edge would have dropped records, which
stops the run; the mean target is sent ~9.6."""

from benchlib import program_spans


def read(run):
    peak = program_spans.of(run).counters.get(
        "exchange.peak_records.auctions->join")
    if not peak or "edge_capacity" not in run.cfg:
        return None
    return 100.0 * peak / run.cfg["edge_capacity"]
