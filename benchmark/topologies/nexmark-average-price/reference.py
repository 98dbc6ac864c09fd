"""The plain reference of the ``nexmark-average-price`` topology: what the
transactional sink must have committed, worked out from the same table in
NumPy. Imports nothing of the program, and nothing of the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``, as
``source-window-reduce-sink/reference.py`` documents them; the rest is
how they are worked out.

Semantics (``job.py`` beside this file; every edge one step deep). The
record that source subtask ``p`` pulls at step ``s`` (key lane ``k``,
value lane ``v``) is event ``j = k * (kinds * spread) >> key_bits`` of its
step: an auction if ``j % kinds`` is under ``auctions_of_kinds``, else a
bid, with event time ``ts = tick * s + j // kinds``. The newest auction
at ``ts`` is ``last = ts * 3 // 5`` (600 a second). An auction record IS
auction ``last``: it lasts ``1 + (v >> 14) % span`` ms, is of category
``first + (v >> 14) // span % 5`` and has the reserve ``knot[2 (v & 127)]
+ knot[2 (v >> 7 & 127)]``; a bid is on the hot auction ``last // 100 *
100`` if ``v & 1``, else on ``last - (v >> 1 & 2047) % 101``, at the price
its 16-bit code ``v >> 12`` reads between two knots. ``knot[i]`` is
``round(10 ** (6 i / 256) * 100)``, ``PriceGenerator``'s price at ``u = i
/ 256``. Ids live on a ring (the key is the id mod ``num_keys``), but this
fold keeps them apart: an id that met an earlier lap of its ring slot
would show as a wrong row. Auctions and bids reach ``winning``'s subtask
that owns their key (``owner_of``: key -> key group -> subtask, the one
thing this file has to know about the program's layout, because each
subtask keeps its own watermark) at step ``s + 3``.

There, each step and subtask (``BestInIntervalJoinOperator``): the
watermark becomes the smaller of the largest auction and the largest bid
event time received so far, this step's included, less the bound (none
while a side is silent). Then, in this order: every bid whose own event
time the watermark has reached — those that waited, then the step's — is
resolved against the auction its id holds open: inside ``[dateTime,
expires)`` and at or over the reserve it counts, and the auction keeps the
largest such price; the auctions whose ``expires`` the watermark has
reached close, each a row ``(category, best price, expires - 1)`` if any
bid counted; of the step's auction records of one id the one with the
smallest ``(dateTime, value lane)`` opens the auction unless the id holds
an open one, every other is a duplicate; the bids not yet resolved wait.
A row reaches ``mean`` (one subtask) a step later.

There (``EventTimeWindowMeanOperator``): the watermark is the largest row
stamp received so far less ``mean_out_of_order_ms``; a window ``[w * slide,
w * slide + size)`` fires at the first step whose watermark reaches its
end — before that step's rows are taken — as one row a category it holds:
``(category, round-half-up(sum / count), end - 1)``, which reaches the
sink a step later and commits with that step's epoch; a row one of whose
windows has fired is late (``late``: none on a sound configuration).

The fold reads the stream ``SEGMENT`` steps at a time out of one table
period (which event of its step a record is, its price and its fields are
the same in every period; its time and ids move with the step), with
``MARGIN`` steps on either side,
and keeps from each segment the auctions whose first record arrives in
it: an auction's records, its bids and its closing lie within the margin
(checked). The capacities of the program are not applied: the reference
says what their peaks are and the run must not have reached them.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail
CONTROLS = ("f32", "no-interval", "lose-a-step")

#: steps from the source's pull to ``winning``, from a row to ``mean`` and
#: from a mean to the sink
TO_JOIN, TO_MEAN, TO_SINK = 3, 1, 1
#: steps of the neighbouring segments a segment reads: an auction's
#: records, its bids and its closing lie within them (``_segment`` checks)
MARGIN = 64
NO_TS = -(1 << 62)
#: steps of a segment, at most, and segments folded at a time (NumPy
#: releases the interpreter's lock; a segment of the cell's width holds
#: ~0.5 GB while it is folded, and the run's own process is large)
SEGMENT = 2048
WORKERS = max(1, min(4, (os.cpu_count() or 2) // 2))


class Want(NamedTuple):
    """What a run must have committed — per epoch the ``[n, 3]``
    (category, mean price, window end - 1) rows in canonical order — and
    the totals and peaks of the two stages over the run: ``winning``'s
    rows, the bids that counted, those under the reserve and those no
    open auction held, duplicate auction records, auctions closed with no
    bid that counted, auctions opened; the rows ``mean`` came too late
    for; the most records one ``winning`` subtask was sent in one step by
    each edge, the most rows one emitted in one step, the most bids one
    held waiting and the most auctions one held open after a step, the
    most rows ``mean`` was sent in one step and the furthest a row's
    stamp lay behind the newest ``mean`` had seen."""
    rows: List[np.ndarray]
    winning_rows: int
    valid: int
    under: int
    orphans: int
    duplicates: int
    no_valid: int
    opened: int
    late: int
    peak_auctions: int
    peak_bids: int
    peak_rows: int
    peak_waiting: int
    peak_open: int
    peak_mean_rows: int
    peak_mean_lag: int


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


def owners(cfg: dict) -> np.ndarray:
    """``owner_of`` every key of the ring, to look a record's up in."""
    return owner_of(np.arange(cfg["num_keys"]), cfg).astype(np.int64)


def price_knots(cfg: dict) -> np.ndarray:
    """``round(10 ** (6 i / knots) * 100)`` for ``i`` in ``[0, knots]``,
    in float64."""
    knots = cfg["price_knots"]
    return np.rint(10.0 ** (6.0 * np.arange(knots + 1) / knots) * 100.0
                   ).astype(np.int64)


class Period(NamedTuple):
    """One table period's records in (step, partition, slot) order, as
    far as they do not move with the step: whether the record is an
    auction, where inside its step it fell, a
    bid's price, hot bit and cold index, an auction's length, category
    and reserve."""
    auction: np.ndarray
    offset: np.ndarray
    price: np.ndarray
    hot: np.ndarray
    cold: np.ndarray
    length: np.ndarray
    category: np.ndarray
    reserve: np.ndarray
    value: np.ndarray

    def at(self, sel) -> "Period":
        return Period(*(x[sel] for x in self))


def read_period(cfg: dict, keys: np.ndarray, vals: np.ndarray) -> Period:
    batch, kinds = cfg["batch"], cfg["kinds"]
    bits = cfg["value_lane"]
    parts, n = keys.shape
    t_steps = n // batch
    by_step = lambda x: x.reshape(parts, t_steps, batch).transpose(
        1, 0, 2).reshape(-1).astype(np.int64)
    k, v = by_step(keys), by_step(vals)
    j = (k * (kinds * cfg["spread_ms"])) >> cfg["key_bits"]
    knot, fine = price_knots(cfg), cfg["price_fine_bits"]
    code = v >> bits["price_shift"]
    lo = knot[code >> fine]
    price = lo + (((knot[(code >> fine) + 1] - lo)
                   * (code & ((1 << fine) - 1))) >> fine)
    rest = v >> bits["rest_shift"]
    stride = cfg["reserve_knot_stride"]
    reserve = (knot[(v & bits["reserve_mask"]) * stride]
               + knot[((v >> bits["reserve_shift"]) & bits["reserve_mask"])
                      * stride])
    return Period(
        auction=j % kinds < cfg["auctions_of_kinds"], offset=j // kinds,
        price=price, hot=(v >> bits["hot_shift"]) % cfg["hot_ratio"] == 1,
        cold=((v >> bits["cold_shift"]) & bits["cold_mask"])
        % (cfg["in_flight_auctions"] + 1),
        length=1 + rest % cfg["length_span_ms"],
        category=cfg["first_category"]
        + rest // cfg["length_span_ms"] % cfg["categories"],
        reserve=reserve, value=v)


class Stream:
    """The records of any range of source steps, out of one period."""

    def __init__(self, cfg: dict, keys: np.ndarray, vals: np.ndarray):
        self.cfg = cfg
        self.period = read_period(cfg, keys, vals)
        self.t_steps = keys.shape[1] // cfg["batch"]
        self.per_step = keys.shape[0] * cfg["batch"]
        self.owner = owners(cfg)

    def steps(self, lo: int, hi: int, skip: Optional[int] = None):
        """``(records, source step, event time, id)`` of the source steps
        ``[lo, hi)`` (``skip``: a step whose records are lost)."""
        cfg, n = self.cfg, self.per_step
        parts, s = [], lo
        while s < hi:
            a = s % self.t_steps
            b = min(self.t_steps, a + hi - s)
            parts.append(self.period.at(slice(a * n, b * n)))
            s += b - a
        rec = (parts[0] if len(parts) == 1 else
               Period(*(np.concatenate(x) for x in zip(*parts))))
        step = np.repeat(np.arange(lo, hi), n)
        if skip is not None and lo <= skip < hi:
            keep = step != skip
            rec, step = rec.at(keep), step[keep]
        ts = cfg["clock_ms_per_step"] * step + rec.offset
        per_ms = cfg["auctions_per_ms"]
        last = ts * per_ms[0] // per_ms[1]
        every = cfg["hot_auction_every"]
        ident = np.where(rec.auction, last, np.where(
            rec.hot, last // every * every, last - rec.cold))
        return rec, step, ts, ident


def tops(stream: Stream, n_steps: int, skip: Optional[int]) -> np.ndarray:
    """``[2, n_steps, subtasks]``: the largest auction and the largest
    bid event time each ``winning`` subtask has received through each
    step (``NO_TS``: none yet)."""
    cfg = stream.cfg
    p, spread = cfg["parallelism"], cfg["spread_ms"]
    top = np.full((2, n_steps, p), NO_TS, np.int64)
    last_source = n_steps - TO_JOIN

    def part(lo):
        hi = min(lo + SEGMENT, last_source)
        rec, step, ts, ident = stream.steps(lo, hi, skip)
        owner = stream.owner[ident % cfg["num_keys"]]
        cell = ((step - lo) * p + owner) * 2 + ~rec.auction
        # the largest offset of a cell: its highest occupied bin
        seen = np.bincount(cell * spread + rec.offset,
                           minlength=(hi - lo) * p * 2 * spread
                           ).reshape(-1, spread) > 0
        best = spread - 1 - np.argmax(seen[:, ::-1], axis=1)
        best = np.where(seen.any(axis=1), best, -1).reshape(hi - lo, p, 2)
        at = cfg["clock_ms_per_step"] * np.arange(lo, hi)[:, None, None]
        top[:, lo + TO_JOIN:hi + TO_JOIN] = np.where(
            best >= 0, at + best, NO_TS).transpose(2, 0, 1)

    with ThreadPoolExecutor(WORKERS) as pool:
        list(pool.map(part, range(0, last_source, SEGMENT)))
    return np.maximum.accumulate(top, axis=1)


def passed_at(wm: np.ndarray, owner: np.ndarray, when: np.ndarray,
              since: np.ndarray) -> np.ndarray:
    """The first step from ``since`` on at which subtask ``owner``'s
    watermark has reached ``when`` (the number of steps, if never): a
    walk forward, since it is a few steps for nearly every record."""
    n_steps = wm.shape[0]
    at = np.array(since, np.int64)
    todo = np.nonzero(at < n_steps)[0]
    while len(todo):
        todo = todo[wm[at[todo], owner[todo]] < when[todo]]
        at[todo] += 1
        todo = todo[at[todo] < n_steps]
    return at


class Closed(NamedTuple):
    """``winning``'s rows — category, best price, stamp, the step each is
    emitted at, its subtask — and the stage's totals and occupancies."""
    category: np.ndarray
    price: np.ndarray
    stamp: np.ndarray
    step: np.ndarray
    owner: np.ndarray
    counts: Dict[str, int]
    held: List[np.ndarray]                  # (open, waiting) x step x owner
    sent: List[np.ndarray]                  # (auctions, bids) x step x owner


def _segment(stream: Stream, wm: np.ndarray, lo: int, hi: int, n_steps: int,
             control: Optional[str], skip: Optional[int]):
    """The auctions whose first record reaches ``winning`` in the steps
    ``[lo, hi)``, with their bids, and the bids that reach it there."""
    cfg = stream.cfg
    first = max(lo - TO_JOIN - MARGIN, 0)
    last = min(hi - TO_JOIN + MARGIN, n_steps - TO_JOIN)
    rec, step, ts, ident = stream.steps(first, last, skip)
    step = step + TO_JOIN
    owner = stream.owner[ident % cfg["num_keys"]]
    # --- the auctions: an id's records a (id, step) group at a time ---------
    a = np.nonzero(rec.auction)[0]
    a = a[np.lexsort((rec.value[a], ts[a], step[a], ident[a]))]
    a_id, a_step = ident[a], step[a]
    head = np.ones(len(a), bool)
    head[1:] = (a_id[1:] != a_id[:-1]) | (a_step[1:] != a_step[:-1])
    g = a[head]                      # a group's first: smallest (ts, value)
    g_id, g_step, g_owner = ident[g], step[g], owner[g]
    g_end = ts[g] + rec.length[g]
    g_close = passed_at(wm, g_owner, g_end, g_step + 1)
    new_id = np.ones(len(g), bool)
    new_id[1:] = g_id[1:] != g_id[:-1]
    nth = np.arange(len(g)) - np.maximum.accumulate(
        np.where(new_id, np.arange(len(g)), 0))
    opens = np.zeros(len(g), bool)
    slot = np.cumsum(new_id) - 1                   # an id's index
    open_until = np.full(int(new_id.sum()), -1, np.int64)
    for r in range(int(nth.max()) + 1 if len(g) else 0):
        mine = np.nonzero(nth == r)[0]             # every id's r-th group
        takes = open_until[slot[mine]] <= g_step[mine]
        mine = mine[takes]
        opens[mine] = True
        open_until[slot[mine]] = g_close[mine]
    # an id belongs to the segment its first record arrives in
    first_step = g_step[np.maximum.accumulate(
        np.where(new_id, np.arange(len(g)), 0))]
    ours = opens & (first_step >= lo) & (first_step < hi)
    if ours.any() and (g_close[ours] - g_step[ours]).max() >= MARGIN:
        raise ValueError("an auction stays open past the fold's margin")
    o = np.nonzero(ours)[0]
    a_mine = (step[a] >= lo) & (step[a] < hi)
    # --- the bids: the auction of its id that is open when it is resolved ---
    # (worked over every record of the range and masked: the bids are 46
    # of 49)
    bid = ~rec.auction
    at = passed_at(wm, owner, ts, step)
    n1 = n_steps + 1
    og = np.nonzero(opens)[0]
    where_open = g_id[og] * n1 + g_step[og]        # ascending
    i = np.searchsorted(where_open, ident * n1 + at, side="left") - 1
    oi = og[np.maximum(i, 0)] if len(og) else np.zeros(len(at), np.int64)
    inside = bid & (i >= 0) & (at < n_steps)
    if len(og):
        inside &= (g_id[oi] == ident) & (at <= g_close[oi])
        if control != "no-interval":
            inside &= (ts[g][oi] <= ts) & (ts < g_end[oi])
    counts_ = inside & (rec.price >= rec.reserve[g][oi]) if len(og) \
        else inside
    b_mine = bid & (step >= lo) & (step < hi)
    if (at - step)[b_mine & (at < n_steps)].max(initial=0) >= MARGIN:
        raise ValueError("a bid waits past the fold's margin")
    # the best of each of our auctions: a maximum over its bids
    rank = np.full(len(g), -1, np.int64)
    rank[o] = np.arange(len(o))
    hit = np.nonzero(counts_)[0]
    hit = hit[rank[oi[hit]] >= 0]
    by = np.argsort(rank[oi[hit]], kind="stable")
    which, price = rank[oi[hit]][by], rec.price[hit][by]
    best = np.full(len(o), -1, np.int64)
    n_hit = np.bincount(which, minlength=len(o))
    if len(which):
        start = np.nonzero(np.diff(which, prepend=-1))[0]
        best[which[start]] = np.maximum.reduceat(price, start)
    closed = g_close[o] < n_steps
    row = closed & (n_hit > 0)
    resolved = b_mine & (at < n_steps)
    counts = dict(
        winning_rows=int(row.sum()), no_valid=int((closed & ~row).sum()),
        opened=len(o), duplicates=int(a_mine.sum()) - int(
            (opens & (g_step >= lo) & (g_step < hi)).sum()),
        valid=int((counts_ & resolved).sum()),
        under=int((inside & ~counts_ & resolved).sum()),
        orphans=int((resolved & ~inside).sum()))
    a_in = np.zeros(len(step), bool)
    a_in[a[a_mine]] = True
    sent = [np.bincount((step[m] - lo) * cfg["parallelism"] + owner[m],
                        minlength=(hi - lo) * cfg["parallelism"])
            for m in (a_in, b_mine)]
    # what each subtask holds after each step, as it changes: +1 at the
    # step an auction opens (a bid arrives), -1 where it closes (is
    # resolved), over the steps from ``lo`` on
    span = min(hi + MARGIN, n_steps) + 1 - lo
    count = lambda at, who: np.bincount(
        np.minimum(at - lo, span - 1) * cfg["parallelism"] + who,
        minlength=span * cfg["parallelism"])
    held = [count(enter, who) - count(leave, who) for enter, leave, who in (
        (g_step[o], g_close[o], g_owner[o]),
        (step[b_mine], at[b_mine], owner[b_mine]))]
    return (rec.category[g[o]][row], best[row], g_end[o][row] - 1,
            g_close[o][row], g_owner[o][row], counts, held, sent)


def winning(stream: Stream, n_steps: int, control: Optional[str],
            skip: Optional[int]) -> Closed:
    cfg = stream.cfg
    p = cfg["parallelism"]
    top = tops(stream, n_steps, skip)
    low = np.minimum(top[0], top[1])
    wm = np.where(low != NO_TS, low - cfg["max_out_of_order_ms"], NO_TS)
    parts, counts = [], {}
    delta = [np.zeros((n_steps + 1) * p, np.int64) for _ in range(2)]
    sent = [np.zeros(n_steps * p, np.int64) for _ in range(2)]
    bounds = [(lo, min(lo + SEGMENT, n_steps))
              for lo in range(TO_JOIN, n_steps, SEGMENT)]
    with ThreadPoolExecutor(WORKERS) as pool:
        segments = pool.map(
            lambda b: _segment(stream, wm, *b, n_steps, control, skip),
            bounds)
        for (lo, hi), (*rows, c, held, s) in zip(bounds, segments):
            parts.append(rows)
            for k, n in c.items():
                counts[k] = counts.get(k, 0) + n
            for d, change in zip(delta, held):
                d[lo * p:lo * p + len(change)] += change
            for total, part in zip(sent, s):
                total[lo * p:hi * p] = part
    cat, price, stamp, step, owner = (
        np.concatenate(x) for x in zip(*parts))
    order = np.argsort(step, kind="stable")
    return Closed(cat[order], price[order], stamp[order], step[order],
                  owner[order], counts,
                  [np.cumsum(d.reshape(-1, p), axis=0)[:n_steps]
                   for d in delta], sent)


def means(cfg: dict, rows: Closed, n_steps: int, f32: bool):
    """``mean``'s rows ``[n, 3]`` with the step each is emitted at, the
    rows it came too late for, the most it was sent in a step and the
    furthest a row's stamp lay behind the newest seen."""
    size, slide = cfg["window_ms"], cfg["slide_ms"]
    arrive = rows.step + TO_MEAN
    ok = arrive < n_steps
    cat, price, stamp, arrive = (x[ok] for x in (rows.category, rows.price,
                                                 rows.stamp, arrive))
    top = np.full(n_steps, NO_TS, np.int64)
    if len(arrive):
        first = np.nonzero(np.diff(arrive, prepend=-1))[0]
        top[arrive[first]] = np.maximum.reduceat(stamp, first)
    top = np.maximum.accumulate(top)
    wm = np.where(top != NO_TS, top - cfg["mean_out_of_order_ms"], NO_TS)
    taken = np.ones(len(stamp), bool)
    cells = []
    for j in range(size // slide):
        w = stamp // slide - j
        open_ = w * slide + size > wm[arrive]
        taken &= open_            # a row that misses one window is late
        cells.append((w[open_], cat[open_], price[open_]))
    w, c, v = (np.concatenate(x) for x in zip(*cells))
    order = np.lexsort((c, w))
    w, c, v = w[order], c[order], v[order]
    start = np.nonzero((np.diff(w, prepend=-1) != 0)
                       | (np.diff(c, prepend=-1) != 0))[0]
    n = np.diff(np.append(start, len(w)))
    if f32:
        total = np.add.reduceat(v.astype(np.float32), start) if len(v) \
            else v.astype(np.float32)
        mean = np.floor(total / n.astype(np.float32)
                        + np.float32(0.5)).astype(np.int64)
    else:
        total = np.add.reduceat(v, start) if len(v) else v
        mean = (2 * total + n) // (2 * n)
    end = w[start] * slide + size
    fires = np.searchsorted(wm, end, side="left")
    out = fires + TO_SINK < n_steps
    lag = top[arrive] - stamp
    return (np.stack([c[start], mean, end - 1], axis=1)[out], fires[out],
            int((~taken).sum()),
            int(np.bincount(arrive, minlength=1).max(initial=0)),
            int(lag.max(initial=0)))


def canonical(rows: np.ndarray) -> np.ndarray:
    """``[n, 3]`` rows in (stamp, key, value) order."""
    rows = np.asarray(rows, np.int64).reshape(-1, 3)
    return rows[np.lexsort((rows[:, 1], rows[:, 0], rows[:, 2]))]


# --- what the harness calls: every topology's reference has these ------------


def expected(cfg: dict, keys: np.ndarray, vals: np.ndarray, epochs: int,
             control: Optional[str] = None, control_step: int = 0) -> Want:
    """What ``epochs`` epochs over the table ``keys`` / ``vals``
    (``[partitions, table_steps * batch]``) must have committed;
    ``control`` names a perturbation of it (``CONTROLS``): ``"f32"``
    passes the winning prices and their sums through float32;
    ``"no-interval"`` leaves ``dateTime <= t < expires`` out of the join —
    a bid counts for whichever auction of its id is open when it is
    resolved; ``"lose-a-step"`` loses every partition's batch of source
    step ``control_step``."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    spe, p = cfg["steps_per_epoch"], cfg["parallelism"]
    n_steps = epochs * spe
    empty = [np.zeros((0, 3), np.int64)] * epochs
    if n_steps <= TO_JOIN:
        return Want(empty, *([0] * 15))
    stream = Stream(cfg, keys, vals)
    won = winning(stream, n_steps, control,
                  control_step if control == "lose-a-step" else None)
    rows, fires, late, peak_mean, lag = means(cfg, won, n_steps,
                                              control == "f32")
    epoch = (fires + TO_SINK) // spe
    peak = lambda x: int(x.max(initial=0))
    return Want(
        [canonical(rows[epoch == e]) for e in range(epochs)], late=late,
        peak_auctions=peak(won.sent[0]), peak_bids=peak(won.sent[1]),
        peak_rows=peak(np.bincount(won.step * p + won.owner,
                                   minlength=n_steps * p)),
        peak_open=peak(won.held[0]), peak_waiting=peak(won.held[1]),
        peak_mean_rows=peak_mean, peak_mean_lag=lag, **won.counts)


def committed_of(want: Want, cfg: dict, epochs: int
                 ) -> Dict[int, List[np.ndarray]]:
    """The commits of a program that computed ``want``: epoch -> rows.
    It is how a control takes the program's place."""
    return {e: [want.rows[e].astype(np.int32)] for e in range(epochs)}


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
    """The epoch whose commit makes the records of source step ``step``
    visible, at the earliest: a bid on an auction about to close is in a
    row a step after ``winning`` takes it, and that row in the first
    window that fires after it reaches ``mean`` — a slide at most, the
    ``mean``'s bound and a step later."""
    cfg_ms = cfg["slide_ms"] + cfg["mean_out_of_order_ms"]
    steps = -(-cfg_ms // cfg["clock_ms_per_step"])
    return (np.asarray(step) + TO_JOIN + TO_MEAN + steps + TO_SINK
            ) // cfg["steps_per_epoch"]
