"""Recovery, from inside: the farthest number of edges downstream from
which any victim of the kill phase's recovery fetched its determinants
(``RecoveryReport.fetch_hops``, also the counter ``recovery.fetch_hops``:
the vertex of the surviving holder whose replica was read against the
victim's own; 0 where no victim read a replica, as a lone sink). None on
a program whose report does not say."""


def read(run):
    hops = getattr(run.report, "fetch_hops", None)
    return None if hops is None else float(hops)
