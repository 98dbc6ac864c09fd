"""Job API: host span around ``executor._pull_feeds``, mean over the
window's blocks."""


def read(run):
    d = run.spans.durations_ms("feed_pull", *run.window)
    return sum(d) / len(d) if d else None
