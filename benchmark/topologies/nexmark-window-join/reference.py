"""The plain reference of the ``nexmark-window-join`` topology: what the
transactional sink must have committed, worked out record by record from
the same table in NumPy. Imports nothing of the program, and nothing of
the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``, as
``source-window-reduce-sink/reference.py`` documents them; the rest is
how they are worked out.

Semantics (``job.py`` beside this file; every edge one step deep). The
record that source subtask ``p`` pulls at step ``s`` (key ``k``, value
``v``) is a person with id ``k`` if ``v % person_every == 0`` and else
an auction with seller ``k`` and reserve ``v``; its event time is ``ts =
tick * s + (v // person_every) % spread``. Keys are dealt to subtasks by
hash (``owner_of``: key -> key group -> subtask, the one thing this file
has to know about the program's layout, because each join subtask keeps
its own watermark). The record reaches its join subtask at step ``s +
3``. There, each step: ``max_l`` and ``max_r`` become the largest person
and auction event time received so far, this step's included; the
watermark is ``max(min(max_l, max_r), anchor) - bound``, where ``min``
ignores nothing (it does not exist while a side is silent) and
``anchor`` is that ``min`` as the first step that brought any record
read it, a silent side left out, fixed from then on. Every window
``[m * size, (m + 1) * size)`` with ``end <= watermark`` fires one row
``(key, sum of the reserves, end)`` per key that has BOTH a person and an
auction in it; then the step's records are assigned, by timestamp, to
window ``ts // size`` — unless its end is at or behind the watermark
(late), or it lies ``open`` or more windows ahead of the first window
the watermark has not passed (no slot: the join keeps ``open =
bound // size + 2`` windows a subtask): such a record is dropped and
counted. A row fired at step ``F`` reaches the sink at ``F + 1`` and
commits with that step's epoch. Sums wrap at int32 like the device's.

Cost. The stream is periodic in ``table_steps``; the window grid is not
commensurate with it inside a run, so every record of the run is folded,
one table period at a time (three ``bincount`` over its records), and
the windows no later record can reach are read off as they close.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail
CONTROLS = ("f32", "at-least-once", "no-join")

#: steps from the source's pull to the join, and from a fire to the sink
TO_JOIN, TO_SINK = 3, 1
NO_TS = -(1 << 62)


class Want(NamedTuple):
    """What a run must have committed — per epoch the ``[n, 3]`` (key,
    sum, window end) rows in canonical order — and the join's totals over
    the run: records dropped (late, or with no slot), rows fired, persons
    and auctions accepted."""
    rows: List[np.ndarray]
    late: int
    fired: int
    left: int
    right: int


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
    """One table period, record by record (step-major, then partition,
    then slot), and each side's largest in-step offset per (table step,
    join subtask)."""

    def __init__(self, cfg: dict, keys: np.ndarray, vals: np.ndarray):
        self.cfg = cfg
        batch, every = cfg["batch"], cfg["person_every"]
        parts, n = keys.shape
        self.steps = n // batch
        self.per_step = parts * batch
        by_step = lambda x: x.reshape(parts, self.steps, batch).transpose(
            1, 0, 2).reshape(-1).astype(np.int64)
        self.key, self.value = by_step(keys), by_step(vals)
        self.person = self.value % every == 0
        self.offset = (self.value // every) % cfg["spread_ms"]
        self.tau = np.repeat(np.arange(self.steps), self.per_step)
        self.owner = owner_of(self.key, cfg)
        #: partition 0's records of each step come first in the step
        self.first_partition = np.tile(np.arange(self.per_step) < batch,
                                       self.steps)
        p = cfg["parallelism"]
        self.top = {}
        for side, mine in (("l", self.person), ("r", ~self.person)):
            top = np.full(self.steps * p, -1, np.int64)
            np.maximum.at(top, (self.tau * p + self.owner)[mine],
                          self.offset[mine])
            self.top[side] = top.reshape(self.steps, p)

    def max_ts(self, side: str, n_steps: int) -> np.ndarray:
        """``[n_steps, subtasks]``: the largest event time of one side
        each join subtask has received through each source step."""
        tick = self.cfg["clock_ms_per_step"]
        s = np.arange(n_steps)
        top = self.top[side][s % self.steps]
        return np.maximum.accumulate(
            np.where(top >= 0, tick * s[:, None] + top, NO_TS), axis=0)


def watermarks(cfg: dict, table: Table, n_steps: int) -> np.ndarray:
    """``[n_steps, subtasks]``: each join subtask's watermark once the
    records of each source step have reached it (``NO_TS``: none yet)."""
    max_l, max_r = table.max_ts("l", n_steps), table.max_ts("r", n_steps)
    lo, hi = np.minimum(max_l, max_r), np.maximum(max_l, max_r)
    first = np.where(lo != NO_TS, lo, hi)       # a silent side left out
    at = (first != NO_TS).argmax(axis=0)
    anchor = np.where(np.arange(n_steps)[:, None] >= at[None],
                      np.take_along_axis(first, at[None], axis=0), NO_TS)
    base = np.maximum(lo, anchor)
    return np.where(base != NO_TS, base - cfg["max_out_of_order_ms"], NO_TS)


def open_windows(cfg: dict) -> int:
    return cfg["max_out_of_order_ms"] // cfg["window_ms"] + 2


def join_rows(cfg: dict, table: Table, wm: np.ndarray, n_steps: int,
              control: Optional[str], twice_step: int):
    """Assign every record that reaches the join within the run to its
    window, one table period of source steps at a time, and read off the
    windows no later record can reach: ``(key, sum, end)`` of every
    (window, key) with a person and an auction in it, and the join's
    totals (dropped, persons accepted, auctions accepted)."""
    tick, size, nk = cfg["clock_ms_per_step"], cfg["window_ms"], \
        cfg["num_keys"]
    ahead = open_windows(cfg)
    late = left = right = 0
    rel = tick * table.tau + table.offset      # event time less tick * lo
    lane = table.tau * cfg["parallelism"] + table.owner   # (step, subtask)
    sides = (np.nonzero(table.person)[0], np.nonzero(~table.person)[0])
    reserve = table.value.astype(np.float64)
    m_lo, carry = 0, np.zeros((3, 0, nk), np.int64)
    out = []
    last = n_steps - TO_JOIN
    for lo in range(0, last, table.steps):
        hi = min(lo + table.steps, last)
        n = (hi - lo) * table.per_step
        m = (rel[:n] + tick * lo) // size
        mark = wm[lo:hi].reshape(-1)[lane[:n]]
        ok = ((m + 1) * size > mark) & (m < mark // size + ahead)
        # persons, auctions, reserves per (window m_lo + i, key); the
        # windows the period before left open come first
        count = int(m.max()) - m_lo + 1
        acc = np.zeros((3, max(count, carry.shape[1]), nk), np.int64)
        acc[:, :carry.shape[1]] = carry
        cell = (m - m_lo) * nk + table.key[:n]
        tally = lambda at, weights=None: np.bincount(
            cell[at], weights=weights, minlength=acc[0].size
        ).astype(np.int64).reshape(acc[0].shape)
        for i, side in enumerate(sides):      # persons, then auctions
            reached = side[:np.searchsorted(side, n)]
            mine = reached[ok[reached]]
            late += len(reached) - len(mine)
            # ``at-least-once``: partition 0's batch of one step, again
            again = mine[:0]
            if control == "at-least-once" and lo <= twice_step < hi:
                again = mine[(table.tau[mine] == twice_step - lo)
                             & table.first_partition[mine]]
            acc[i] += tally(mine) + tally(again)
            if i:
                right += len(mine)
                # float64 holds these integers exactly (< 2**53)
                acc[2] += (tally(mine, reserve[mine])
                           + tally(again, reserve[again]))
            else:
                left += len(mine)
        # a later record's event time is tick * hi at least
        closed = acc.shape[1] if hi == last else (tick * hi) // size - m_lo
        sums = wrap32(acc[2, :closed]).astype(np.int64)
        if control == "f32":
            sums = sums.astype(np.float32).astype(np.int64)
        both = acc[1, :closed] > 0
        if control != "no-join":
            both &= acc[0, :closed] > 0
        i, key = np.nonzero(both)
        out.append((key, sums[i, key], (i + m_lo + 1) * size))
        m_lo, carry = m_lo + closed, acc[:, closed:]
    key, value, end = (np.concatenate(x) for x in zip(*out))
    return key, value, end, int(late), left, right


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
    passes every window's sum of reserves through float32 (an accumulator
    a one-hot matmul would tempt a later change into);
    ``"at-least-once"`` delivers partition 0's batch of step
    ``control_step`` twice; ``"no-join"`` emits a seller's row in every
    window it has an auction in, a person or not."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    spe = cfg["steps_per_epoch"]
    n_steps = epochs * spe
    if n_steps <= TO_JOIN:
        return Want([np.zeros((0, 3), np.int64)] * epochs, 0, 0, 0, 0)
    table = Table(cfg, keys, vals)
    wm = watermarks(cfg, table, n_steps)
    key, value, end, late, left, right = join_rows(
        cfg, table, wm, n_steps, control, control_step)
    owner = owner_of(key, cfg)
    fire = np.zeros(len(key), np.int64)
    for d in range(cfg["parallelism"]):
        mine = owner == d
        # the first source step whose records take the watermark past it
        fire[mine] = np.searchsorted(wm[:, d], end[mine])
    fire += TO_JOIN
    keep = fire < n_steps
    epoch = (fire[keep] + TO_SINK) // spe
    order = np.argsort(epoch, kind="stable")
    rows = np.stack([key[keep], value[keep], end[keep]], axis=1)[order]
    cut = np.searchsorted(epoch[order], np.arange(epochs + 1))
    return Want([rows[cut[e]:cut[e + 1]] for e in range(epochs)],
                late, int(keep.sum()), left, right)


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
    visible in their window's row, for a watermark that trails the clock
    by the bound alone: the window of the step's clock fires once a
    record ``bound`` past its end has reached the join."""
    tick, size = cfg["clock_ms_per_step"], cfg["window_ms"]
    end = (tick * np.asarray(step) // size + 1) * size
    fire = -(-(end + cfg["max_out_of_order_ms"]) // tick) + TO_JOIN
    return (fire + TO_SINK) // cfg["steps_per_epoch"]
