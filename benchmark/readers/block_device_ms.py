"""Block executor: device time of one block program — the program that
takes most device time in the traced steady span, mean over its
launches."""


def read(run):
    w = run.trace_window("steady")
    if w is None or not run.events.modules:
        return None
    mods = run.events.modules[min(run.events.modules)]
    by = {}
    for n, s, d in mods:
        if s >= w[0] and s + d <= w[1]:
            by.setdefault(n, []).append(d)
    if not by:
        return None
    block = max(by.values(), key=sum)
    return sum(block) / len(block) / 1e6
