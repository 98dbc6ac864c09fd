"""End to end, host clock: from the call of ``recover()`` to
``block_until_ready`` on the patched carry, in the kill phase."""


def read(run):
    t0, t1 = run.recover_wall
    return (t1 - t0) * 1e3 if t1 > t0 else None
