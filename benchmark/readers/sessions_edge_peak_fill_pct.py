"""Exchange, from inside: how full the skewed edge ``parse -> sessions``
of the ``nexmark-user-sessions`` job has been at its fullest, in percent
of its capacity — the program's ``exchange.peak_records.parse->sessions``
counter (the most records one ``sessions`` subtask was sent in one step,
which the fence adds to from the per-target counters its health read
brings back; it only grows) over the configuration's ``edge_capacity``.
Past 100 the edge would have dropped records, which stops the run; the
mean target's share is ``batch / edge_capacity``."""

from benchlib import program_spans


def read(run):
    peak = program_spans.of(run).counters.get(
        "exchange.peak_records.parse->sessions")
    if not peak or "edge_capacity" not in run.cfg:
        return None
    return 100.0 * peak / run.cfg["edge_capacity"]
