"""The plain reference of the ``nexmark-user-sessions`` topology: what
the transactional sink must have committed, folded from the same table in
NumPy. Imports nothing of the program, and nothing of the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``, as
``source-window-reduce-sink/reference.py`` documents them; the rest is
how they are worked out.

Semantics (``job.py`` beside this file; every edge one step deep). The
record source subtask ``p`` pulls at step ``s`` (value ``v``; the key
lane is not read) is a bid at event time ``ts = tick * s + ((v >> 2) &
1023) % spread`` by bidder ``b``: with ``last = ts // 5`` the newest
person's id, the hot bidder ``last // 100 * 100 + 1`` unless ``v & 3`` is
0 (3 bids in 4), else ``last - 999 + (v >> 12) % 1010`` (the last 1,000
persons and 10 ahead). It reaches the ``sessions`` subtask that owns ``b
% num_keys`` (``owner_of``: key -> key group -> subtask, the one thing
this file has to know about the program's layout, because each subtask
keeps its own watermark) at step ``s + 2``. A bidder's bids, sorted by
event time, split into sessions wherever two consecutive ones lie more
than ``gap`` apart (Flink's merging session windows: ``[ts, ts + gap)``
per bid, windows that touch or overlap merge). A session's row is
``(b % num_keys, bids, last bid + gap)`` — q11's ``count(*)`` and
``SESSION_END``. It fires at the first step at which its owner's
watermark — the largest event time that subtask has received so far, this
step's included, less the bound — reaches its end, reaches the sink a
step later and commits with that step's epoch. No bid is late and no
session is cut by its own fire: the bound covers a step's spread, so
every bid lies above the watermark of the step it arrives at, and a bid
within ``gap`` of a session arrives before the watermark reaches that
session's end (``fold`` refuses a configuration where it does not).

Cost. The table repeats every period, so what of a bid does not depend
on its step — its offset inside the step, whether it is hot, which cold
bidder it picks — is worked out once. Hot bids (3 in 4) fall on at most
two bidders a step, whose counts, earliest and latest come from a
per-table-step histogram of hot offsets; cold bids are folded a period
of steps at a time by one sort of ``(bidder, step, offset)``. A period is
folded together with the steps after it in which its youngest bidder can
still bid, and keeps only the bidders that first become eligible inside
it, so no session is cut at a period's edge.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail. No
#: ``f32`` control: a session's count stays under 4,000 (the hot bidder's
#: ~3,460 bids), which float32 holds exactly, so an f32 fold could not
#: come out false.
CONTROLS = ("at-least-once", "fixed-window", "arrival-time")

#: steps from the source's pull to ``sessions``, and from a fire there to
#: the sink
TO_SESSIONS, TO_SINK = 2, 1
NO_TS = -(1 << 62)


class Want(NamedTuple):
    """What a run must have committed — per epoch the ``[n, 3]`` (bidder
    mod ``num_keys``, bids, session end) rows — and the totals the
    program's counters are held to: sessions fired, bids that reached
    ``sessions``, bids refused as late, rows past ``session_capacity``, the
    most bids a (``sessions`` subtask, step) was sent and the bids past
    ``parse -> sessions``'s capacity, and the most sessions one subtask
    held open after a step (a session counted from its first bid: two
    that a later bid merged count as one throughout)."""
    rows: List[np.ndarray]
    fired: int
    bids: int
    late: int
    over_capacity: int
    peak: int
    dropped: int
    open_peak: int


def hash32(x: np.ndarray) -> np.ndarray:
    u = np.asarray(x, np.uint64) & 0xFFFFFFFF
    u = ((u ^ (u >> 16)) * 0x7FEB352D) & 0xFFFFFFFF
    u = ((u ^ (u >> 15)) * 0x846CA68B) & 0xFFFFFFFF
    return (u ^ (u >> 16)) & 0xFFFFFFFF


def owner_of(keys: np.ndarray, cfg: dict) -> np.ndarray:
    """Subtask that holds each key: key -> key group -> subtask."""
    groups = cfg["num_key_groups"]
    kg = (hash32(keys) % groups).astype(np.int64)
    return kg * cfg["parallelism"] // groups


class Table(NamedTuple):
    """What of a table period's bids does not depend on the step.
    ``hot``: per table step the count of hot bids at each in-step offset
    ``[period, spread]``; of the cold bids, in table-step order, the
    table step, the offset and the pick among the active people."""
    period: int
    hot: np.ndarray
    cold_step: np.ndarray
    cold_off: np.ndarray
    cold_pick: np.ndarray


def table_of(cfg: dict, vals: np.ndarray) -> Table:
    bits, batch = cfg["value_lane"], cfg["batch"]
    spread = cfg["spread_ms"]
    period = vals.shape[1] // batch
    # the table, step-major
    v = vals.reshape(vals.shape[0], period, batch).transpose(
        1, 0, 2).reshape(period, -1).astype(np.int32)
    off = ((v >> bits["offset_shift"]) & bits["offset_mask"]) % spread
    is_hot = ((v >> bits["hot_shift"]) & bits["hot_mask"]) \
        % cfg["hot_ratio"] != 0
    step = np.broadcast_to(np.arange(period, dtype=np.int32)[:, None],
                           v.shape)
    hot = np.bincount((step[is_hot] * spread + off[is_hot]),
                      minlength=period * spread).reshape(period, spread)
    cold = ~is_hot
    pick = (v[cold] >> bits["cold_shift"]) % (
        cfg["active_people"] + cfg["person_id_lead"])
    return Table(period, hot.astype(np.int64), step[cold].astype(np.int64),
                 off[cold].astype(np.int64), pick.astype(np.int64))


class Cells(NamedTuple):
    """Bids per (bidder, step): count, earliest and latest in-step
    offset, sorted by bidder and then step."""
    bidder: np.ndarray
    step: np.ndarray
    n: np.ndarray
    lo: np.ndarray
    hi: np.ndarray


class Sessions(NamedTuple):
    """Per session: the bidder's true id, its bids, its last bid's event
    time, the source step of its first bid."""
    bidder: np.ndarray
    n: np.ndarray
    last: np.ndarray
    first_step: np.ndarray


def eligible_steps(cfg: dict) -> int:
    """Steps over which one bidder can receive bids, rounded up."""
    life = cfg["person_every_ms"] * (cfg["active_people"]
                                     + cfg["person_id_lead"])
    return life // cfg["clock_ms_per_step"] + 2


def first_bidder(cfg: dict, step: int) -> int:
    """The smallest id that first becomes eligible at or after ``step``
    (an id is eligible from ``ts >= every_ms * (id - lead)``)."""
    every, lead = cfg["person_every_ms"], cfg["person_id_lead"]
    return -(-cfg["clock_ms_per_step"] * step // every) + lead


def hot_cells(cfg: dict, tab: Table, steps: np.ndarray, twice_step: int,
              flat: bool):
    """The hot bids of ``steps``: per step at most two bidders (the hot
    id changes every ``every_ms * hot_bidder_every`` ms), each with its
    count, earliest and latest offset (``flat``: every offset reads 0,
    the ``arrival-time`` control)."""
    tick, spread = cfg["clock_ms_per_step"], cfg["spread_ms"]
    every = cfg["hot_bidder_every"]
    width = cfg["person_every_ms"] * every
    h = tab.hot[steps % tab.period]                       # [n, spread]
    h = np.where((steps == twice_step)[:, None], 2 * h, h)
    clock = tick * steps
    cut = np.minimum((clock // width + 1) * width - clock, spread)
    first = clock // width * every + 1
    offs = np.arange(spread)
    out = []
    for mine, bidder in ((offs[None, :] < cut[:, None], first),
                         (offs[None, :] >= cut[:, None], first + every)):
        part = np.where(mine, h, 0)
        n = part.sum(axis=1)
        some = part > 0
        lo = some.argmax(axis=1)
        hi = spread - 1 - some[:, ::-1].argmax(axis=1)
        keep = n > 0
        zero = np.zeros(int(keep.sum()), np.int64)
        out.append((bidder[keep], steps[keep], n[keep],
                    zero if flat else lo[keep], zero if flat else hi[keep]))
    return [np.concatenate(x) for x in zip(*out)]


def cold_bids(cfg: dict, tab: Table, s0: int, n_steps: int,
              twice_step: int):
    """``(bidder, step, offset, again)`` of the cold bids of steps ``[s0,
    s0 + n_steps)``, ``s0`` a multiple of the table's period (``n_steps``
    may run into the periods after it); ``again`` marks the second delivery
    of step ``twice_step``'s batches."""
    tick = cfg["clock_ms_per_step"]
    parts = []
    for done in range(0, n_steps, tab.period):
        n = int(np.searchsorted(tab.cold_step, n_steps - done))  # a prefix
        step = s0 + done + tab.cold_step[:n]
        once = np.zeros(n, bool)
        parts.append((step, tab.cold_off[:n], tab.cold_pick[:n], once))
        twice = step == twice_step
        if twice.any():
            parts.append((step[twice], tab.cold_off[:n][twice],
                          tab.cold_pick[:n][twice], ~once[twice]))
    step, off, pick, again = (np.concatenate(x) for x in zip(*parts))
    last = (tick * step + off) // cfg["person_every_ms"]
    return last - (cfg["active_people"] - 1) + pick, step, off, again


def cells_of(cfg: dict, tab: Table, s0: int, home: int, n_src: int,
             twice_step: int, flat: bool):
    """The bids of the bidders that first become eligible in steps ``[s0,
    s0 + home)``, all of them: those steps and as many after them as such
    a bidder can still bid in, up to the run's last source step ``n_src -
    1``. Also, for the home steps alone, every cold bid once: ``(bidder,
    step, offset)``."""
    spread = cfg["spread_ms"]
    n = home + eligible_steps(cfg)
    # the run's first bids are on ids down to 1 - active_people
    lo_id = first_bidder(cfg, s0) if s0 else -cfg["active_people"]
    hi_id = first_bidder(cfg, s0 + home)
    bidder, step, off, again = cold_bids(cfg, tab, s0, n, twice_step)
    once = ~again & (step < s0 + home)
    at_home = bidder[once], step[once], off[once]
    if flat:
        off = np.zeros_like(off)
    hb, hs, hn, hlo, hhi = hot_cells(cfg, tab, np.arange(s0, s0 + n),
                                     twice_step, flat)
    keep = (bidder >= lo_id) & (bidder < hi_id) & (step < n_src)
    hkeep = (hb >= lo_id) & (hb < hi_id) & (hs < n_src)
    hb, hs, hn, hlo, hhi = (x[hkeep] for x in (hb, hs, hn, hlo, hhi))
    # one sort of (bidder, step, offset); a hot cell goes in as its
    # earliest and its latest bid, and gets its count afterwards
    code = lambda b, s, o: ((b - lo_id) * n + (s - s0)) * spread + o
    word = np.sort(np.concatenate([
        code(bidder[keep], step[keep], off[keep]),
        code(hb, hs, hlo), code(hb, hs, hhi)]))
    cell = word // spread
    start = np.flatnonzero(np.concatenate([[True], cell[1:] != cell[:-1]]))
    end = np.concatenate([start[1:], [len(word)]])
    count = end - start
    np.add.at(count, np.searchsorted(cell[start], code(hb, hs, 0) // spread),
              hn - 2)
    ident = cell[start]
    return Cells(ident // n + lo_id, ident % n + s0, count,
                 word[start] % spread, word[end - 1] % spread), at_home


def sessions_of(cfg: dict, c: Cells, fixed: bool) -> Sessions:
    """Cells into sessions: a bidder's cells in step order, cut where the
    next cell's earliest bid lies more than ``gap`` past this cell's
    latest. ``fixed`` (the ``fixed-window`` control): cut by tumbling
    windows of ``gap`` instead — a (bidder, step)'s bids go to the window
    of the earliest of them, and ``last`` reads the window's end less
    ``gap``."""
    tick, gap = cfg["clock_ms_per_step"], cfg["gap_ms"]
    if not len(c.bidder):
        none = np.zeros(0, np.int64)
        return Sessions(none, none, none, none)
    lo, hi = tick * c.step + c.lo, tick * c.step + c.hi
    new = np.concatenate([[True], c.bidder[1:] != c.bidder[:-1]])
    if fixed:
        new[1:] |= lo[1:] // gap != lo[:-1] // gap
    else:
        new[1:] |= lo[1:] - hi[:-1] > gap
    start = np.flatnonzero(new)
    last = np.concatenate([start[1:], [len(new)]]) - 1
    return Sessions(c.bidder[start], np.add.reduceat(c.n, start),
                    lo[start] // gap * gap if fixed else hi[last],
                    c.step[start])


class Fold(NamedTuple):
    """Every session of the bids of source steps ``[0, n_src)``, and per
    (source step, ``sessions`` subtask), one step further (the exchange
    routes a step's bids a step before ``sessions`` takes them), the bids
    sent (``sent``) and the largest in-step offset among them (``top``,
    -1: none)."""
    sessions: Sessions
    sent: np.ndarray
    top: np.ndarray


def fold(cfg: dict, vals: np.ndarray, n_src: int, control: Optional[str],
         twice_step: int) -> Fold:
    """Every bid of source steps ``[0, n_src]``, a table period at a
    time."""
    tick, spread = cfg["clock_ms_per_step"], cfg["spread_ms"]
    if (cfg["max_out_of_order_ms"] < spread or tick < spread
            or cfg["gap_ms"] <= cfg["max_out_of_order_ms"]):
        raise ValueError(
            "this fold takes no bid for late and cuts no session at its "
            "fire: the bound must cover a step's spread and lie under "
            "the gap, and a step's events must not pass the next step's")
    parts, nk = cfg["parallelism"], cfg["num_keys"]
    owner_of_key = owner_of(np.arange(nk), cfg).astype(np.int64)
    tab = table_of(cfg, vals)
    flat = control == "arrival-time"
    if control != "at-least-once":
        twice_step = -1
    n_sent = n_src + 1
    sent = np.zeros((n_sent, parts), np.int64)
    top = np.full((n_sent, parts), -1, np.int64)
    found = []
    for s0 in range(0, n_sent, tab.period):
        home = min(tab.period, n_sent - s0)
        cells, (bidder, step, off) = cells_of(
            cfg, tab, s0, home, n_src, twice_step, flat)
        found.append(sessions_of(cfg, cells, control == "fixed-window"))
        # what each subtask was sent in the home steps, the step's
        # batches counted once whatever the control
        steps = np.arange(s0, s0 + home)
        hb, hs, hn, _, hhi = hot_cells(cfg, tab, steps, -1, False)
        lane = np.concatenate([
            (step - s0) * parts + owner_of_key[bidder % nk],
            (hs - s0) * parts + owner_of_key[hb % nk]])
        n = np.concatenate([np.ones(len(step), np.int64), hn])
        sent[s0:s0 + home] = np.bincount(
            lane, weights=n, minlength=home * parts
        ).reshape(home, parts).astype(np.int64)
        at = np.bincount(
            lane * spread + np.concatenate([off, hhi]),
            minlength=home * parts * spread).reshape(home, parts, spread) > 0
        top[s0:s0 + home] = np.where(
            at.any(axis=2), spread - 1 - at[:, :, ::-1].argmax(axis=2), -1)
    if flat:
        top = np.where(top >= 0, 0, -1)
    return Fold(Sessions(*(np.concatenate(x) for x in zip(*found))),
                sent, top)


def over(n: np.ndarray, capacity: int) -> int:
    return int(np.maximum(n - capacity, 0).sum())


# --- what the harness calls: every topology's reference has these ------------


def expected(cfg: dict, keys: np.ndarray, vals: np.ndarray, epochs: int,
             control: Optional[str] = None, control_step: int = 0) -> Want:
    """What ``epochs`` epochs over the table ``keys`` / ``vals``
    (``[partitions, table_steps * batch]``) must have committed;
    ``control`` names a perturbation of it (``CONTROLS``):
    ``"at-least-once"`` delivers every partition's batch of step
    ``control_step`` twice; ``"fixed-window"`` counts a bidder's bids per
    tumbling window of ``gap`` in the sessions' place; ``"arrival-time"``
    takes a bid's time for its step's clock, not its own."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    spe, parts = cfg["steps_per_epoch"], cfg["parallelism"]
    tick, gap = cfg["clock_ms_per_step"], cfg["gap_ms"]
    n_steps = epochs * spe
    none = [np.zeros((0, 3), np.int64)] * epochs
    # bids of source step s reach ``sessions`` at s + TO_SESSIONS; the
    # last step's exchange has routed the bids of source step n_steps - 2
    n_src = n_steps - TO_SESSIONS
    if n_src <= 0:
        return Want(none, 0, 0, 0, 0, 0, 0, 0)
    f = fold(cfg, vals, n_src, control, control_step)
    ses = f.sessions
    owner = owner_of(ses.bidder % cfg["num_keys"], cfg)
    end = ses.last + gap
    # each subtask's watermark once a source step's bids are in
    s = np.arange(n_src)[:, None]
    wm = np.maximum.accumulate(np.where(
        f.top[:n_src] >= 0, tick * s + f.top[:n_src], NO_TS), axis=0) \
        - cfg["max_out_of_order_ms"]
    fire = np.zeros(len(end), np.int64)
    for d in range(parts):
        mine = owner == d
        # the first source step whose bids take the watermark to it
        fire[mine] = np.searchsorted(wm[:, d], end[mine])
    fire += TO_SESSIONS
    fired = fire < n_steps
    lanes = np.bincount(fire[fired] * parts + owner[fired],
                        minlength=n_steps * parts)
    # sessions a subtask holds after each step: in from its first bid's
    # arrival, out at its fire
    held = np.zeros((n_steps + 1) * parts, np.int64)
    np.add.at(held, (ses.first_step + TO_SESSIONS) * parts + owner, 1)
    np.add.at(held, np.minimum(fire, n_steps) * parts + owner, -1)
    held = np.cumsum(held.reshape(n_steps + 1, parts), axis=0)[:n_steps]
    epoch = (fire[fired] + TO_SINK) // spe
    order = np.argsort(epoch, kind="stable")
    table = np.stack([ses.bidder[fired] % cfg["num_keys"], ses.n[fired],
                      end[fired]], axis=1)[order]
    cut = np.searchsorted(epoch[order], np.arange(epochs + 1))
    return Want([table[cut[e]:cut[e + 1]] for e in range(epochs)],
                int(fired.sum()), int(ses.n.sum()), 0,
                over(lanes, cfg["session_capacity"]), int(f.sent.max()),
                over(f.sent, cfg["edge_capacity"]), int(held.max()))


def committed_of(want: Want, cfg: dict, epochs: int
                 ) -> Dict[int, List[np.ndarray]]:
    """The commits of a program that computed ``want``: epoch -> rows.
    It is how a control takes the program's place."""
    return {e: [want.rows[e].astype(np.int32)] for e in range(epochs)}


def canonical(rows: np.ndarray) -> np.ndarray:
    """``[n, 3]`` rows in (stamp, key, value) order."""
    rows = np.asarray(rows, np.int64).reshape(-1, 3)
    return rows[np.lexsort((rows[:, 1], rows[:, 0], rows[:, 2]))]


def mismatched(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of one epoch that are wrong, missing, duplicated or foreign:
    the size of the symmetric difference of the two multisets."""
    got, want = canonical(got), canonical(want)
    if got.shape == want.shape and (got == want).all():
        return 0
    rows = np.ascontiguousarray(np.concatenate([got, want]))
    _, inverse = np.unique(rows.view([("", np.int64)] * 3).ravel(),
                           return_inverse=True)
    sign = np.repeat([1.0, -1.0], [len(got), len(want)])
    return int(np.abs(np.bincount(inverse, weights=sign)).sum())


def check(committed: Dict[int, Sequence[np.ndarray]], want: Want, cfg: dict,
          epochs: int) -> Tuple[int, List[int], int]:
    """(mismatched rows, epochs whose commit is missing or wrong, rows
    compared) of the whole committed stream against ``want``, each
    epoch's rows as a multiset (limit 0); more than one commit under an
    epoch is itself a fault."""
    bad_rows, failed, compared = 0, [], 0
    for e in range(epochs):
        parts = committed.get(e)
        if parts is None:
            n_bad = len(want.rows[e]) or 1
        else:
            got = (np.concatenate([np.asarray(p).reshape(-1, 3)
                                   for p in parts], axis=0)
                   if parts else np.zeros((0, 3), np.int32))
            compared += got.shape[0]
            n_bad = mismatched(got, want.rows[e]) + len(parts) - 1
        if n_bad:
            bad_rows += n_bad
            failed.append(e)
    for e in committed:
        if not 0 <= e < epochs:
            bad_rows += sum(np.asarray(p).reshape(-1, 3).shape[0]
                            for p in committed[e]) or 1
            failed.append(e)
    return bad_rows, failed, compared


def visible_epoch_of_step(step: np.ndarray, cfg: dict) -> np.ndarray:
    """The epoch whose commit makes the bids of source step ``step``
    visible, for a bid that is its session's last and watermarks that
    trail the clock by the bound alone: the session fires once a bid
    ``gap + bound`` past it is in."""
    tick = cfg["clock_ms_per_step"]
    fire = -(-(tick * np.asarray(step) + cfg["gap_ms"]
               + cfg["max_out_of_order_ms"]) // tick) + TO_SESSIONS
    return (fire + TO_SINK) // cfg["steps_per_epoch"]
