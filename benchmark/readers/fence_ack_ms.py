"""Control plane, from inside: the program's ``fence.ack`` span
(``coordinator.ack_all``: completion, ``TransactionLog.commit``, log and
ring truncation, feed-offset commit — what a consumer of the sink waits
for), median over the window's epochs."""

from benchlib import program_spans


def read(run):
    return program_spans.median_ms(run, "fence.ack")
