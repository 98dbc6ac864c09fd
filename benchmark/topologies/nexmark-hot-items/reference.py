"""The plain reference of the ``nexmark-hot-items`` topology: what the
transactional sink must have committed, folded from the same table in
NumPy. Imports nothing of the program, and nothing of the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``, as
``source-window-reduce-sink/reference.py`` documents them; the rest is
how they are worked out.

Semantics (``job.py`` beside this file; every edge one step deep). The
record source subtask ``p`` pulls at step ``s`` (value ``v``; the key
lane, the bidder, is not read) is a bid at event time ``ts = tick * s +
((v >> 1) & 1023) % spread`` on auction ``a``: with ``last = ts * 3 // 5``
the newest auction's id, the hot auction ``last // 100 * 100`` if ``v`` is
odd, else ``last - (v >> 11) % 101``. It reaches the ``count`` subtask
that owns ``a % num_keys`` (``owner_of``: key -> key group -> subtask, the
one thing this file has to know about the program's layout, because each
count subtask keeps its own watermark and passes on its own leaders) at
step ``s + 2``. There the watermark is the largest event time received so
far, this step's included, less the bound; every window ``[m * slide, m *
slide + size)`` with ``end <= watermark`` fires FIRST and emits, for this
subtask, one row ``(a % num_keys, num, end - 1)`` (a window's result is
stamped with the window's last millisecond, as Flink stamps it, so that a
window of the same grid downstream takes it for the same window) per
auction whose bid count
``num`` in the window is the subtask's largest, ties kept, at most
``partial_capacity`` rows a step; then the step's bids are counted into
their ``size // slide`` windows. No bid is late: the bound covers a step's
spread, so the watermark trails every timestamp of the step and the
oldest window of a bid ends after the bid (``fold`` refuses a
configuration where it does not). The rows reach ``max`` (one subtask) a
step later. Its watermark is the largest stamp received less its own
bound (``top_out_of_order_ms``: how far the count subtasks' watermarks may
lie apart); the rows of window end ``E`` fire when it reaches ``E`` — with
bound 0, when the first row of the next window arrives; a row that
arrives later is late, and counted — and of them only those whose ``num``
is the largest over all subtasks go on, ties kept: ``(a % num_keys, num,
E - 1)``. A
row fired at step ``F`` reaches the sink at ``F + 1`` and commits with
that step's epoch.

Cost. Every bid of the run is folded, one table period of steps at a
time: a count per (2 s pane, auction) — a pane sees 1,300 consecutive ids
— by one ``bincount``, windows as sums of five panes.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail. No
#: ``f32`` control: a window's count stays under 800, which float32 holds
#: exactly, so an f32 fold could not come out false.
CONTROLS = ("at-least-once", "arrival-time", "local-max")

#: steps from the source's pull to ``count``, from a fire there to
#: ``max``, and from a fire there to the sink
TO_COUNT, TO_MAX, TO_SINK = 2, 1, 1
NO_TS = -(1 << 62)


class Want(NamedTuple):
    """What a run must have committed — per epoch the ``[n, 3]``
    (auction, num, window end - 1) rows — and the totals the program's
    counters are held to: rows ``count`` fired (``partial_rows``) and
    ``max`` fired (``fired``), records or rows refused as late, rows past
    a stage's row capacity or past ``count -> max``'s, the most bids a
    (count subtask, step) was sent and the bids past ``parse -> count``'s
    capacity."""
    rows: List[np.ndarray]
    fired: int
    partial_rows: int
    late: int
    over_capacity: int
    peak: int
    dropped: int


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


class Fold(NamedTuple):
    """Bids per (pane, auction): ``panes[q, i]`` counts the bids with
    event time in ``[q * slide, (q + 1) * slide)`` on auction ``first_id(q)
    + i``; per (source step, count subtask) the bids sent (``sent``) and
    the largest in-step offset among them (``top``, -1: none)."""
    panes: np.ndarray
    sent: np.ndarray
    top: np.ndarray


def pane_ids(cfg: dict) -> int:
    """Consecutive auction ids a pane can see (a bid's auction lies at
    most ``in_flight`` behind the newest), with room for the
    ``arrival-time`` control, whose pane may trail the bid by a step."""
    a, b = cfg["auctions_per_ms"]
    return ((cfg["slide_ms"] + cfg["clock_ms_per_step"]) * a // b
            + cfg["in_flight_auctions"] + 2)


def first_id(cfg: dict, pane) -> np.ndarray:
    """The smallest auction id a bid in ``pane`` can be on."""
    a, b = cfg["auctions_per_ms"]
    return np.asarray(pane) * cfg["slide_ms"] * a // b \
        - cfg["in_flight_auctions"]


def fold(cfg: dict, vals: np.ndarray, n_steps: int, control: Optional[str],
         twice_step: int) -> Fold:
    """Every bid of source steps ``[0, n_steps)``, a table period at a
    time."""
    tick, spread, slide = (cfg["clock_ms_per_step"], cfg["spread_ms"],
                           cfg["slide_ms"])
    if cfg["max_out_of_order_ms"] < spread - 1 or cfg["window_ms"] % slide:
        raise ValueError("this fold takes no bid for late: the bound must "
                         "cover a step's spread")
    a, b = cfg["auctions_per_ms"]
    bits, parts, nk = cfg["value_lane"], cfg["parallelism"], cfg["num_keys"]
    batch = cfg["batch"]
    period = vals.shape[1] // batch
    # the table, step-major: what of a bid does not depend on its step
    v = vals.reshape(vals.shape[0], period, batch).transpose(
        1, 0, 2).reshape(period, -1).astype(np.int32)
    offset = ((v >> bits["offset_shift"]) & bits["offset_mask"]) % spread
    hot = (v >> bits["hot_shift"]) % cfg["hot_ratio"] == 1
    behind = (v >> bits["cold_shift"]) % (cfg["in_flight_auctions"] + 1)
    owner_of_key = owner_of(np.arange(nk), cfg).astype(np.int32)
    width = pane_ids(cfg)
    n_panes = (tick * n_steps + spread) // slide + 1
    panes = np.zeros((n_panes, width), np.int32)
    sent = np.zeros((n_steps, parts), np.int32)
    top = np.full((n_steps, parts), -1, np.int32)
    for lo in range(0, n_steps, period):
        n = min(period, n_steps - lo)
        clock = tick * np.arange(lo, lo + n, dtype=np.int32)[:, None]
        ts = clock + offset[:n]
        last = ts * a // b
        auction = np.where(hot[:n], last // cfg["hot_auction_every"]
                           * cfg["hot_auction_every"], last - behind[:n])
        pane = (clock if control == "arrival-time" else ts) // slide
        cell = (pane * width + (auction - first_id(cfg, pane))).ravel()
        q0 = int(clock[0, 0]) // slide      # the chunk's first pane
        seen = np.bincount(cell - q0 * width)
        if control == "at-least-once" and lo <= twice_step < lo + n:
            # every partition's batch of that step, delivered twice
            again = cell.reshape(n, -1)[twice_step - lo]
            seen = seen + np.bincount(again - q0 * width,
                                      minlength=len(seen))
        rows = -(-len(seen) // width)
        panes[q0:q0 + rows].ravel()[:len(seen)] += seen.astype(np.int32)
        owner = owner_of_key[auction % nk]
        lane = np.arange(n, dtype=np.int32)[:, None] * parts + owner
        sent[lo:lo + n] = np.bincount(
            lane.ravel(), minlength=n * parts).reshape(n, parts)
        at = np.bincount((lane * spread + offset[:n]).ravel(),
                         minlength=n * parts * spread
                         ).reshape(n, parts, spread) > 0
        top[lo:lo + n] = np.where(
            at.any(axis=2), spread - 1 - at[:, :, ::-1].argmax(axis=2), -1)
    return Fold(panes, sent, top)


class Windows(NamedTuple):
    """Per fired row of a stage: window end, auction id, count, the
    ``count`` subtask that owns the auction."""
    end: np.ndarray
    auction: np.ndarray
    num: np.ndarray
    owner: np.ndarray


def leaders(cfg: dict, panes: np.ndarray, chunk: int = 512
            ) -> Tuple[Windows, Windows]:
    """``(partial, top)``: of every window with a bid in the fold, each
    count subtask's leaders (the auctions it owns whose count is its
    largest, ties kept) and the window's leaders over all auctions."""
    size, slide, nk = cfg["window_ms"], cfg["slide_ms"], cfg["num_keys"]
    per, parts = size // slide, cfg["parallelism"]
    a, b = cfg["auctions_per_ms"]
    shift = slide * a // b               # ids the next pane starts later
    if slide * a % b:
        raise ValueError("a pane must start on a whole auction id")
    width = panes.shape[1]
    owner_of_key = owner_of(np.arange(nk), cfg).astype(np.int8)
    padded = np.concatenate([np.zeros((per - 1, width), np.int32), panes])
    n_win = len(panes)                   # window m = w - (per - 1)
    out = ([], [], [], []), ([], [], [], [])
    for w0 in range(0, n_win, chunk):
        w1 = min(w0 + chunk, n_win)
        table = np.zeros((w1 - w0, (per - 1) * shift + width), np.int32)
        for j in range(per):             # pane m + j of window m
            table[:, j * shift:j * shift + width] += padded[w0 + j:w1 + j]
        m = np.arange(w0, w1) - (per - 1)
        ids = first_id(cfg, m)[:, None] + np.arange(table.shape[1])
        owner = owner_of_key[ids % nk]
        best = np.zeros((w1 - w0, parts), np.int32)
        for d in range(parts):
            best[:, d] = np.where(owner == d, table, 0).max(axis=1)
        mine = (table > 0) & (table == np.take_along_axis(
            best, owner.astype(np.int64), axis=1))
        for into, match in zip(out, (
                mine, mine & (table == best.max(axis=1)[:, None]))):
            i, j = np.nonzero(match)
            for col, x in zip(into, (m[i] * slide + size, ids[i, j],
                                     table[i, j], owner[i, j])):
                col.append(x)
    return tuple(Windows(*(np.concatenate(c).astype(np.int64)
                           for c in cols)) for cols in out)


def over(n: np.ndarray, capacity: int) -> int:
    return int(np.maximum(n - capacity, 0).sum())


def per_lane(lane: np.ndarray) -> np.ndarray:
    """Occupancy of each lane that has anything."""
    return np.unique(lane, return_counts=True)[1] if len(lane) else lane


# --- what the harness calls: every topology's reference has these ------------


def expected(cfg: dict, keys: np.ndarray, vals: np.ndarray, epochs: int,
             control: Optional[str] = None, control_step: int = 0) -> Want:
    """What ``epochs`` epochs over the table ``keys`` / ``vals``
    (``[partitions, table_steps * batch]``) must have committed;
    ``control`` names a perturbation of it (``CONTROLS``):
    ``"at-least-once"`` delivers every partition's batch of step
    ``control_step`` twice; ``"arrival-time"`` puts a bid in the windows
    of its arrival step's clock, not of its event time; ``"local-max"``
    passes every count subtask's leaders on as the window's."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    spe, parts = cfg["steps_per_epoch"], cfg["parallelism"]
    tick, slide = cfg["clock_ms_per_step"], cfg["slide_ms"]
    n_steps = epochs * spe
    none = [np.zeros((0, 3), np.int64)] * epochs
    # bids of source step s reach ``count`` at s + TO_COUNT; the last
    # step's exchange has routed the bids of source step n_steps - 2
    n_src = n_steps - TO_COUNT
    if n_src <= 0:
        return Want(none, 0, 0, 0, 0, 0, 0)
    f = fold(cfg, vals, n_steps - 1, control, control_step)
    peak = int(f.sent.max())
    dropped = over(f.sent, cfg["edge_capacity"])
    partial, top = leaders(cfg, f.panes)
    # each count subtask's watermark once a source step's bids are in
    s = np.arange(n_src)[:, None]
    wm = np.maximum.accumulate(np.where(
        f.top[:n_src] >= 0, tick * s + f.top[:n_src], NO_TS),
        axis=0) - cfg["max_out_of_order_ms"]
    fire = np.zeros(len(partial.end), np.int64)
    for d in range(parts):
        mine = partial.owner == d
        # the first source step whose bids take the watermark past it
        fire[mine] = np.searchsorted(wm[:, d], partial.end[mine])
    fire += TO_COUNT
    fired = fire < n_steps
    spill = over(per_lane(fire[fired] * parts + partial.owner[fired]),
                 cfg["partial_capacity"])
    # at ``max``: the largest window end received through each step
    reach = fire + TO_MAX
    there = reach < n_steps
    spill += over(per_lane(reach[there]), cfg["partial_edge_capacity"])
    newest = np.full(n_steps, NO_TS, np.int64)
    np.maximum.at(newest, reach[there], partial.end[there])
    # its watermark: a row is stamped with its window's last millisecond
    newest = np.maximum.accumulate(newest) - 1 - cfg["top_out_of_order_ms"]
    late = int((partial.end[there] <= newest[reach[there]]).sum())
    rows = partial if control == "local-max" else top
    fire_top = np.searchsorted(newest, rows.end)
    out = fire_top < n_steps
    if control != "local-max":
        spill += over(per_lane(fire_top[out]), cfg["top_capacity"])
    epoch = (fire_top[out] + TO_SINK) // spe
    order = np.argsort(epoch, kind="stable")
    table = np.stack([rows.auction[out] % cfg["num_keys"], rows.num[out],
                      rows.end[out] - 1], axis=1)[order]
    cut = np.searchsorted(epoch[order], np.arange(epochs + 1))
    return Want([table[cut[e]:cut[e + 1]] for e in range(epochs)],
                int(out.sum()), int(fired.sum()), late, spill, peak, dropped)


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
    visible in the newest window that holds them, for watermarks that
    trail the clock by the bound alone: that window fires at ``count``
    once a bid ``bound`` past its end is in, and at ``max`` when the next
    window's rows arrive, a slide later."""
    tick, size, slide = (cfg["clock_ms_per_step"], cfg["window_ms"],
                         cfg["slide_ms"])
    end = tick * np.asarray(step) // slide * slide + size
    fire = -(-(end + slide + cfg["top_out_of_order_ms"]
               + cfg["max_out_of_order_ms"]) // tick) + TO_COUNT + TO_MAX
    return (fire + TO_SINK) // cfg["steps_per_epoch"]
