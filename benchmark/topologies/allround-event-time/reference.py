"""The plain reference of the ``allround-event-time`` topology: what the
transactional sink must have committed, worked out record by record from
the same table in NumPy. Imports nothing of the program, and nothing of
the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``; the rest is how
they are worked out.

Semantics (``job.py`` beside this file; every edge one step deep). The
record that source subtask ``p`` pulls at step ``s`` (key ``k``, value
``v``) gets event time ``ts = tick * s - v % (max_lag + 1)`` and the
value 1. The keyed-state mapper replaces the value by ``C_k(s)``, its
key's record count over all partitions through step ``s``. Keys are
dealt to subtasks by hash (``owner``: key -> key group -> subtask, the
one thing this file has to know about the program's layout, because each
subtask keeps its own watermark). The record reaches its tumbling-window
subtask at step ``s + 4``. There, each step: the watermark becomes
``max(ts seen so far, this step's included) - bound``; every open window
with ``end <= watermark`` fires one row ``(key, sum, end)`` per key whose
sum is not 0; then the step's records are assigned, by **timestamp**, to
window ``ts // size``, unless its end is at or behind the watermark: such
a record is late, dropped and counted. A row fired at step ``F`` reaches
the sink at ``F + 2`` (through the union) and commits with that step's
epoch; it also reaches the sliding-window subtask of its key at ``F +
1``, which treats it as a record with the row's stamp as event time:
windows of ``slide * factor`` every ``slide``, same watermark rule, rows
at the sink two steps after they fire. Sums wrap at int32 like the
device's.

Cost. The stream is periodic in ``table_steps`` (``benchlib/stream.py``)
and the window grid in ``size / tick`` steps, so record-by-record work is
done for the first two common periods only (one that holds the start of
the run, one in the steady state) and later windows follow from the
second: the same records, each counting ``c_k`` more per table period.
Watermarks need one minimum per (table step, subtask) and a running
maximum per step, for the whole run.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail
CONTROLS = ("f32", "at-least-once", "arrival-time")

#: steps from the source's pull to the tumbling window, from a window's
#: fire to the sink, and from the tumbling fire to the sliding window
TO_WINDOW, TO_SINK, TO_SLIDING = 4, 2, 1
NO_TS = -(1 << 62)


class Want(NamedTuple):
    """What a run must have committed: per epoch the ``[n, 3]`` (key,
    sum, window end) rows in canonical order, and how many records each
    window vertex dropped as late."""
    rows: List[np.ndarray]
    late_tumbling: int
    late_sliding: int


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


def wrap32(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.int64).astype(np.int32)


class Table:
    """One table period, record by record, and what repeats with it."""

    def __init__(self, cfg: dict, keys: np.ndarray, vals: np.ndarray,
                 arrival_time: bool):
        self.cfg = cfg
        batch, nk = cfg["batch"], cfg["num_keys"]
        parts, n = keys.shape
        self.steps = n // batch
        #: records of one table step: the step's records lie together
        #: (step-major, then partition, then slot)
        self.per_step = parts * batch
        by_step = lambda x: x.reshape(parts, self.steps, batch).transpose(
            1, 0, 2).reshape(-1).astype(np.int64)
        self.key = by_step(keys)
        #: a block form that ignored disorder would see every lag as 0
        self.lag = (np.zeros(parts * n, np.int64) if arrival_time else
                    by_step(vals) % (cfg["max_lag_ms"] + 1))
        self.tau = np.repeat(np.arange(self.steps), self.per_step)
        self.owner_of_key = owner_of(np.arange(nk), cfg)
        self.owner = self.owner_of_key[self.key]
        per_step = np.bincount(self.tau * nk + self.key,
                               minlength=self.steps * nk
                               ).reshape(self.steps, nk)
        running = np.cumsum(per_step, axis=0)
        #: the key's count through the record's own table step, and what
        #: a whole table period adds to it
        self.count = running[self.tau, self.key]
        self.count_per_period = running[-1]
        span = int(self.lag.max()) + 1
        seen = np.zeros((self.steps * parts, span), bool)
        seen[self.tau * parts + self.owner, self.lag] = True
        self.min_lag = np.where(seen.any(axis=1), seen.argmax(axis=1),
                                -1).reshape(self.steps, parts)

    def max_ts(self, n_steps: int) -> np.ndarray:
        """``[n_steps, subtasks]``: the largest event time each tumbling
        subtask has seen through each source step."""
        tick = self.cfg["clock_ms_per_step"]
        s = np.arange(n_steps)
        lag = self.min_lag[s % self.steps]
        best = np.where(lag >= 0, tick * s[:, None] - lag, NO_TS)
        return np.maximum.accumulate(best, axis=0)

    def of_steps(self, lo: int, hi: int):
        """(source step, key, owner, event time, count) of the records
        pulled in steps ``[lo, hi)``, one table period at most."""
        tick = self.cfg["clock_ms_per_step"]
        q, first = divmod(lo, self.steps)
        pick = slice(first * self.per_step, (first + hi - lo) * self.per_step)
        step = q * self.steps + self.tau[pick]
        key = self.key[pick]
        return (step, key, self.owner[pick], tick * step - self.lag[pick],
                self.count[pick] + q * self.count_per_period[key])


class Windows:
    """Sums ``s[m - base, key]`` and accepted-record counts ``n`` of the
    tumbling windows ``base ..``."""

    def __init__(self, base: int, count: int, nk: int):
        self.base, self.nk = base, nk
        self.s = np.zeros((count, nk), np.int64)
        self.n = np.zeros((count, nk), np.int64)

    def add(self, m, key, weight) -> None:
        cell = (m - self.base) * self.nk + key
        size = self.s.size
        # float64 holds these integers exactly (< 2**53 by a wide margin)
        self.s += np.bincount(cell, weights=weight.astype(np.float64),
                              minlength=size).astype(np.int64
                                                     ).reshape(self.s.shape)
        self.n += np.bincount(cell, minlength=size).reshape(self.n.shape)


def fold(table: Table, max_ts: np.ndarray, lo: int, hi: int, into: Windows
         ) -> List[int]:
    """Assign the records of source steps ``[lo, hi)`` to their windows;
    returns the late records of each ``steps_per_epoch`` chunk."""
    cfg = table.cfg
    size, bound = cfg["tumbling_ms"], cfg["max_out_of_order_ms"]
    chunk = cfg["steps_per_epoch"]
    late = []
    a = lo
    while a < hi:
        # chunk boundaries are table boundaries too, wherever ``lo`` is
        b = min(hi, (a // chunk + 1) * chunk)
        step, key, owner, ts, count = table.of_steps(a, b)
        a = b
        m = ts // size
        ok = (m + 1) * size > max_ts[step, owner] - bound
        late.append(int((~ok).sum()))
        into.add(m[ok], key[ok], count[ok])
    return late


def tumbling_windows(cfg: dict, table: Table, max_ts: np.ndarray,
                     n_steps: int, direct_periods: int = 2
                     ) -> Tuple[Windows, int]:
    """Every window a record of the run's ``n_steps`` falls in, and the
    records that reached their window within the run and were late.
    ``direct_periods`` common periods are folded record by record (at
    least 2: the first holds the start of the run), the rest follow from
    the last of them."""
    tick, size = cfg["clock_ms_per_step"], cfg["tumbling_ms"]
    chunk, max_lag = cfg["steps_per_epoch"], cfg["max_lag_ms"]
    grid = size // math.gcd(size, tick)          # steps per grid period
    common = math.lcm(table.steps, grid)
    base = (-max_lag) // size
    top = (tick * (n_steps - 1)) // size
    win = Windows(base, top - base + 1, cfg["num_keys"])
    direct = min(n_steps, direct_periods * common)
    late = fold(table, max_ts, 0, direct, win)
    if direct < n_steps:
        shift = tick * common
        s = np.arange(direct - common, n_steps - common)
        if not (max_ts[s + common] == max_ts[s] + shift).all():
            raise ValueError("watermarks are not periodic in the common "
                             "period: fold the whole run directly")
        # windows all of whose records lie in the direct steps
        whole = (tick * direct - max_lag) // size - 1
        per = shift // size                      # windows per period
        m = np.arange(whole + 1, top + 1)
        j = -(-(m - whole) // per)
        src = m - j * per - base
        extra = j[:, None] * win.n[src] * (
            (common // table.steps) * table.count_per_period)[None, :]
        win.s[m - base], win.n[m - base] = win.s[src] + extra, win.n[src]
        period, last = common // chunk, direct // chunk - common // chunk
        late += [late[last + (i - last) % period]
                 for i in range(direct // chunk, n_steps // chunk)]
    # the records of the last steps have not reached their window yet
    on_the_way = fold(table, max_ts, max(0, n_steps - TO_WINDOW), n_steps,
                      Windows(base, top - base + 1, cfg["num_keys"]))
    return win, sum(late) - sum(on_the_way)


def tumbling_rows(cfg: dict, table: Table, max_ts: np.ndarray, win: Windows,
                  n_steps: int) -> Dict[str, np.ndarray]:
    """The rows the tumbling windows fire within the run: key, sum, end,
    the step they fire at and the subtask."""
    size, bound = cfg["tumbling_ms"], cfg["max_out_of_order_ms"]
    sums = wrap32(win.s)
    mi, key = np.nonzero((win.n > 0) & (sums != 0))
    end = (mi + win.base + 1) * size
    owner = table.owner_of_key[key]
    fire = np.zeros(len(key), np.int64)
    for d in range(cfg["parallelism"]):
        mine = owner == d
        # the first source step by which the subtask has seen that much
        fire[mine] = np.searchsorted(max_ts[:, d], end[mine] + bound)
    fire += TO_WINDOW
    keep = fire < n_steps
    return {"key": key[keep], "value": sums[mi, key][keep].astype(np.int64),
            "ts": end[keep], "step": fire[keep], "owner": owner[keep]}


def sliding_rows(cfg: dict, fed: Dict[str, np.ndarray], n_steps: int
                 ) -> Tuple[Dict[str, np.ndarray], int]:
    """The rows the sliding windows fire within the run when fed the
    tumbling rows ``fed``, and how many of those they dropped as late."""
    slide, bound = cfg["slide_ms"], cfg["max_out_of_order_ms"]
    size = slide * cfg["slide_factor"]
    out = {k: [] for k in ("key", "value", "ts", "step")}
    late = 0
    for d in range(cfg["parallelism"]):
        mine = np.nonzero(fed["owner"] == d)[0]
        if not len(mine):
            continue
        arrive = fed["step"][mine] + TO_SLIDING
        mine = mine[arrive < n_steps]
        if not len(mine):
            continue
        arrive = fed["step"][mine] + TO_SLIDING
        mine = mine[np.argsort(arrive, kind="stable")]
        arrive, key = fed["step"][mine] + TO_SLIDING, fed["key"][mine]
        ts, value = fed["ts"][mine], fed["value"][mine]
        # the watermark after each step at which rows arrive
        steps, start = np.unique(arrive, return_index=True)
        seen = np.maximum.accumulate(np.maximum.reduceat(ts, start))
        wm = seen[np.searchsorted(steps, arrive)] - bound
        any_ok = np.zeros(len(mine), bool)
        parts = []
        for j in range(cfg["slide_factor"]):
            w = ts // slide - j
            ok = w * slide + size > wm
            any_ok |= ok
            parts.append((w[ok], key[ok], value[ok]))
        late += int((~any_ok).sum())
        w, key, value = (np.concatenate(x) for x in zip(*parts))
        if not len(w):
            continue
        lo = int(w.min())
        cell = (w - lo) * cfg["num_keys"] + key
        cells, inverse = np.unique(cell, return_inverse=True)
        total = np.zeros(len(cells), np.int64)
        np.add.at(total, inverse, value)
        total = wrap32(total).astype(np.int64)
        end = (cells // cfg["num_keys"] + lo) * slide + size
        at = np.searchsorted(seen, end + bound)
        keep = (total != 0) & (at < len(steps))
        fire = steps[np.minimum(at, len(steps) - 1)]
        keep &= fire < n_steps
        out["key"].append((cells % cfg["num_keys"])[keep])
        out["value"].append(total[keep])
        out["ts"].append(end[keep])
        out["step"].append(fire[keep])
    return ({k: (np.concatenate(v) if v else np.zeros(0, np.int64))
             for k, v in out.items()}, late)


def canonical(rows: np.ndarray) -> np.ndarray:
    """``[n, 3]`` rows in (stamp, key, value) order."""
    rows = np.asarray(rows, np.int64).reshape(-1, 3)
    return rows[np.lexsort((rows[:, 1], rows[:, 0], rows[:, 2]))]


def duplicated_batch(cfg: dict, table: Table, max_ts: np.ndarray,
                     win: Windows, step: int) -> None:
    """``at-least-once``: partition 0's batch of source step ``step``
    arrives twice. Its records count twice in their windows, and every
    record of their keys from that step on counts one more per copy."""
    tick, size = cfg["clock_ms_per_step"], cfg["tumbling_ms"]
    s, key, owner, ts, count = table.of_steps(step, step + 1)
    first = np.arange(len(s)) < cfg["batch"]          # partition 0's
    more = np.bincount(key[first], minlength=cfg["num_keys"])
    # windows that may also hold records from before ``step``: count
    # again the part of them that comes from ``step`` on
    split = (tick * step) // size
    hi = ((split + 1) * size + cfg["max_lag_ms"]) // tick + 1
    part = Windows(win.base, win.n.shape[0], cfg["num_keys"])
    fold(table, max_ts, step, min(hi, len(max_ts)), part)
    after = win.n.copy()
    after[:split - win.base + 1] = part.n[:split - win.base + 1]
    win.s += after * more[None, :]
    m = ts[first] // size
    ok = (m + 1) * size > max_ts[step, owner[first]] - cfg[
        "max_out_of_order_ms"]
    win.add(m[ok], key[first][ok], (count[first] + more[key[first]])[ok])


# --- what the harness calls: every topology's reference has these ------------


def expected(cfg: dict, keys: np.ndarray, vals: np.ndarray, epochs: int,
             control: Optional[str] = None, control_step: int = 0,
             direct_periods: int = 2) -> Want:
    """What ``epochs`` epochs over the table ``keys`` / ``vals``
    (``[partitions, table_steps * batch]``) must have committed;
    ``control`` names a perturbation of it (``CONTROLS``): ``"f32"``
    passes every window sum through float32 (an accumulator a one-hot
    matmul would tempt a later change into); ``"at-least-once"`` delivers
    partition 0's batch of step ``control_step`` twice;
    ``"arrival-time"`` assigns windows by the step a record arrives in,
    not by its timestamp. ``direct_periods``: :func:`tumbling_windows`."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    spe = cfg["steps_per_epoch"]
    n_steps = epochs * spe
    if not n_steps:
        return Want([], 0, 0)
    table = Table(cfg, keys, vals, arrival_time=control == "arrival-time")
    max_ts = table.max_ts(n_steps)
    win, late = tumbling_windows(cfg, table, max_ts, n_steps,
                                 direct_periods)
    if control == "at-least-once":
        duplicated_batch(cfg, table, max_ts, win, control_step)
    elif control == "f32":
        win.s = win.s.astype(np.int32).astype(np.float32).astype(np.int64)
    first = tumbling_rows(cfg, table, max_ts, win, n_steps)
    second, late_sliding = sliding_rows(cfg, first, n_steps)
    key, value, ts, step = (np.concatenate([first[k], second[k]])
                            for k in ("key", "value", "ts", "step"))
    epoch = (step + TO_SINK) // spe
    order = np.lexsort((value, key, ts, epoch))     # canonical, per epoch
    rows = np.stack([key, value, ts], axis=1)[order]
    cut = np.searchsorted(epoch[order], np.arange(epochs + 1))
    return Want([rows[cut[e]:cut[e + 1]] for e in range(epochs)],
                late, late_sliding)


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
    compared) of the whole committed stream against ``want``; more than
    one commit under an epoch is itself a fault."""
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
    visible in their tumbling window's row, for a record and a watermark
    that do not lag: the window of the step's clock fires once a record
    ``bound`` past its end has reached the window."""
    tick, size = cfg["clock_ms_per_step"], cfg["tumbling_ms"]
    end = (tick * np.asarray(step) // size + 1) * size
    fire = -(-(end + cfg["max_out_of_order_ms"]) // tick) + TO_WINDOW
    return (fire + TO_SINK) // cfg["steps_per_epoch"]
