"""The plain reference of the ``nexmark-local-items`` topology: what the
transactional sink must have committed, worked out from the same table in
NumPy. Imports nothing of the program, and nothing of the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``, as
``source-window-reduce-sink/reference.py`` documents them; the rest is
how they are worked out.

Semantics (``job.py`` beside this file; every edge one step deep). The
record that source subtask ``p`` pulls at step ``s`` (key lane ``k``,
value lane ``v``) has event time ``ts = tick * s + (v // 4) % spread``
and, with ``rest = (v // 4) // spread`` and ``last = ts // 5`` (the
generator numbers a person every 5 ms): it is a person if ``v % 4 == 0``,
with id ``last`` and state ``rest % 6``; else an auction of category
``first_category + rest % 5`` whose seller is the hot one, ``last // 100
* 100``, unless ``(rest // 5) % 4 == 0``, and then ``last - 999 + k %
1010`` (one of the last 1,000 persons or the next 10). Ids live on a
ring: the key is the id mod ``num_keys``. Persons of the local states
and auctions of the one category reach the join's subtask that owns
their key (``owner_of``: key -> key group -> subtask, the one thing this
file has to know about the program's layout, because each join subtask
keeps its own watermark) at step ``s + 3``.

There, each step and subtask: the watermark becomes the smaller of the
largest person and the largest auction event time received so far, this
step's included, less the bound (none while a side is silent: nothing
expires). A person is live while ``ts + ttl > watermark``; a waiting
auction waits as long. Then, in this order: auctions that have waited
their ``ttl`` leave; of the step's persons of one key, if the key holds
a live person all are duplicates, else the earliest registers and every
auction waiting for the key becomes a row; each of the step's auctions
becomes a row at once if its key holds a live person and waits
otherwise. A row is the auction, ``(key, value, event time)``; a row the
join emits at step ``j`` reaches the sink at ``j + 1`` and commits with
that step's epoch.

The fold is the rule above key by key, since keys do not interact: a
key's persons are taken a (key, step) group at a time in step order (the
one sequential part: a round handles every key's next group at once),
an auction finds the registration in force at its step by a search among
its key's, and a waiting auction the next one. The capacities of the
program (rows a step, auctions waiting, the edges) are not applied: the
reference says what their peaks are and the run must not have reached
them.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail
CONTROLS = ("f32", "at-least-once", "no-filter")

#: steps from the source's pull to the join, and from a row to the sink
TO_JOIN, TO_SINK = 3, 1
NO_TS = -(1 << 62)


class Want(NamedTuple):
    """What a run must have committed — per epoch the ``[n, 3]`` (key,
    value, event time) rows in canonical order — and the join's totals
    over the run: rows, of them flushed out of the bag; auctions that
    waited, that left unjoined; duplicate persons; auctions that wait
    though their key holds a person that was live at their own
    timestamp (it expired before they came); and its peaks: the most records one
    subtask was sent in one step by each edge, the most rows one emitted
    in one step, the most auctions one held waiting and the most live
    persons one held after a step."""
    rows: List[np.ndarray]
    fired: int
    flushed: int
    bagged: int
    bag_expired: int
    duplicates: int
    expired_probes: int
    peak_persons: int
    peak_auctions: int
    peak_rows: int
    peak_waiting: int
    peak_live: int


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


class Records(NamedTuple):
    """One kind's records that reach the join within a run, in (step,
    partition, slot) order: key, value lane, event time, the step they
    reach the join at, the subtask that owns the key."""
    key: np.ndarray
    value: np.ndarray
    ts: np.ndarray
    step: np.ndarray
    owner: np.ndarray

    def plus(self, other: "Records") -> "Records":
        return Records(*(np.concatenate(pair) for pair in zip(self, other)))

    def at(self, mask: np.ndarray) -> "Records":
        return Records(*(x[mask] for x in self))

    def by_step(self) -> "Records":
        return self.at(np.argsort(self.step, kind="stable"))


def read_records(cfg: dict, keys: np.ndarray, vals: np.ndarray,
                 n_steps: int, unfiltered: Optional[Tuple[int, int]] = None
                 ) -> Tuple[Records, Records]:
    """The persons and the auctions that reach the join before step
    ``n_steps``, one table period at a time (which records pass the two
    predicates is the same in every period: only they are read).
    ``unfiltered``: a range of source steps whose records skip the two
    predicates."""
    batch, every, spread = cfg["batch"], cfg["person_every"], cfg["spread_ms"]
    parts, n = keys.shape
    t_steps, per_step = n // batch, parts * batch
    by_step = lambda x: x.reshape(parts, t_steps, batch).transpose(
        1, 0, 2).reshape(-1).astype(np.int64)
    k_lane, v_lane = by_step(keys), by_step(vals)
    tau = np.repeat(np.arange(t_steps), per_step)
    person = v_lane % every == 0
    rest = (v_lane // every) // spread
    passes = np.where(
        person, np.isin(rest % cfg["states"], cfg["local_states"]),
        rest % cfg["categories"] == cfg["category"] - cfg["first_category"])
    hot_every, active = cfg["hot_seller_every"], cfg["active_people"]
    owner = owners(cfg)

    def read(at: np.ndarray, lo: int, persons: bool) -> Records:
        """The persons, or the auctions, at table positions ``at``
        (ascending) of the period that starts at source step ``lo``."""
        v = v_lane[at]
        step = lo + tau[at]
        ts = cfg["clock_ms_per_step"] * step + (v // every) % spread
        ident = ts // cfg["person_every_ms"]       # the newest person
        if not persons:
            hot = ((v // every) // spread // cfg["categories"]
                   ) % cfg["hot_ratio"] != 0
            ident = np.where(
                hot, ident // hot_every * hot_every, ident - (active - 1)
                + k_lane[at] % (active + cfg["person_id_lead"]))
        key = ident % cfg["num_keys"]
        return Records(key, v, ts, step + TO_JOIN, owner[key])

    kept = [np.nonzero(passes & kind)[0] for kind in (person, ~person)]
    out = ([], [])
    last_source = n_steps - TO_JOIN
    for lo in range(0, last_source, t_steps):
        m = min(t_steps, last_source - lo) * per_step
        free = None
        if unfiltered is not None and (unfiltered[0] < lo + t_steps
                                       and unfiltered[1] > lo):
            free = (lo + tau[:m] >= unfiltered[0]) & (
                lo + tau[:m] < unfiltered[1])
        for i, kind in enumerate((person, ~person)):
            at = (kept[i][:np.searchsorted(kept[i], m)] if free is None
                  else np.nonzero((passes[:m] | free) & kind[:m])[0])
            out[i].append(read(at, lo, persons=i == 0))
    glue = lambda parts: Records(*(np.concatenate(x) for x in zip(*parts)))
    return glue(out[0]), glue(out[1])


def watermarks(cfg: dict, persons: Records, auctions: Records, n_steps: int
               ) -> np.ndarray:
    """``[n_steps, subtasks]``: each join subtask's watermark at each
    step (``NO_TS``: none yet)."""
    p = cfg["parallelism"]
    tops = []
    for r in (persons, auctions):
        top = np.full((n_steps, p), NO_TS, np.int64)
        for d in range(p):
            mine = np.nonzero(r.owner == d)[0]        # in step order
            if not len(mine):
                continue
            step, ts = r.step[mine], r.ts[mine]
            first = np.nonzero(np.diff(step, prepend=-1))[0]
            top[step[first], d] = np.maximum.reduceat(ts, first)
        tops.append(np.maximum.accumulate(top, axis=0))
    lo = np.minimum(*tops)
    return np.where(lo != NO_TS, lo - cfg["max_out_of_order_ms"], NO_TS)


def passed_at(wm: np.ndarray, owner: np.ndarray, when: np.ndarray
              ) -> np.ndarray:
    """The first step at which subtask ``owner``'s watermark has reached
    ``when`` (the number of steps, if never)."""
    out = np.empty(len(when), np.int64)
    for d in range(wm.shape[1]):
        mine = owner == d
        out[mine] = np.searchsorted(wm[:, d], when[mine], side="left")
    return out


def registrations(cfg: dict, persons: Records, wm: np.ndarray):
    """The persons that register — ``(key x steps + step, timestamp,
    step it expires at)``, ascending — and how many persons are
    duplicates."""
    n_steps = wm.shape[0]
    # by key, a key's in step order: the records come in step order, so
    # a stable sort by key does it (two passes of 16 bits: a radix sort)
    order = np.argsort((persons.key & 0xFFFF).astype(np.uint16),
                       kind="stable")
    order = order[np.argsort((persons.key[order] >> 16).astype(np.uint16),
                             kind="stable")]
    key, step, owner = (persons.key[order], persons.step[order],
                        persons.owner[order])
    where = key * n_steps + step
    head = np.ones(len(where), bool)
    head[1:] = where[1:] != where[:-1]         # a (key, step) group's first
    first = np.nonzero(head)[0]
    ts = (np.minimum.reduceat(persons.ts[order], first) if len(first)
          else persons.ts[:0])                 # its earliest registers
    key, step, where = key[first], step[first], where[first]
    expires = passed_at(wm, owner[first], ts + cfg["ttl_ms"])
    first = np.ones(len(key), bool)
    first[1:] = key[1:] != key[:-1]
    nth = np.arange(len(key)) - np.maximum.accumulate(
        np.where(first, np.arange(len(key)), 0))
    registers = np.zeros(len(key), bool)
    live_until = np.full(cfg["num_keys"], -1, np.int64)
    rounds = int(nth.max()) + 1 if len(nth) else 0
    by_round = np.argsort(nth.astype(np.uint16 if rounds < 1 << 16
                                     else np.int64), kind="stable")
    cut = np.searchsorted(nth[by_round], np.arange(rounds + 1))
    for r in range(rounds):
        mine = by_round[cut[r]:cut[r + 1]]     # every key's r-th group
        takes = live_until[key[mine]] <= step[mine]
        mine = mine[takes]
        registers[mine] = True
        live_until[key[mine]] = expires[mine]
    return (where[registers], ts[registers], expires[registers],
            len(persons.key) - int(registers.sum()))


def occupancy_peak(enter: np.ndarray, leave: np.ndarray, owner: np.ndarray,
                   n_steps: int, p: int) -> int:
    """The most intervals ``[enter, leave)`` of steps one subtask holds
    after a step."""
    count = lambda at: np.bincount(np.minimum(at, n_steps) * p + owner,
                                   minlength=(n_steps + 1) * p)
    delta = count(enter) - count(leave)
    held = np.cumsum(delta.reshape(n_steps + 1, p), axis=0)[:n_steps]
    return int(held.max()) if held.size else 0


def step_peak(r: Records, n_steps: int, p: int) -> int:
    if not len(r.key):
        return 0
    return int(np.bincount(r.step * p + r.owner,
                           minlength=n_steps * p).max())


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
    passes every row's value through float32; ``"at-least-once"``
    delivers every partition's batch of step ``control_step`` twice (one
    partition's 64 records hold ~4 auctions that join, and now and then
    none);
    ``"no-filter"`` joins without the two predicates — persons of every
    state, auctions of every category — for the records of
    ``control_step``'s epoch (the whole run unfiltered is eight times the
    rows and minutes of fold)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    spe, p = cfg["steps_per_epoch"], cfg["parallelism"]
    n_steps = epochs * spe
    if n_steps <= TO_JOIN:
        return Want([np.zeros((0, 3), np.int64)] * epochs, *([0] * 11))
    free = None
    if control == "no-filter":
        free = (control_step // spe * spe, (control_step // spe + 1) * spe)
    persons, auctions = read_records(cfg, keys, vals, n_steps, free)
    if control == "at-least-once":
        again = control_step + TO_JOIN
        persons = persons.plus(persons.at(persons.step == again)).by_step()
        auctions = auctions.plus(
            auctions.at(auctions.step == again)).by_step()
    wm = watermarks(cfg, persons, auctions, n_steps)
    reg_where, reg_ts, reg_expires, duplicates = registrations(cfg, persons,
                                                               wm)
    # the registration in force at an auction's step: its key's latest at
    # or before it (a step's persons come first)
    where = auctions.key * n_steps + auctions.step
    at = np.searchsorted(reg_where, where, side="right") - 1
    known = (at >= 0) & (reg_where[np.maximum(at, 0)] // n_steps
                         == auctions.key)
    match = known & (auctions.step < reg_expires[np.maximum(at, 0)])
    held_ts = reg_ts[np.maximum(at, 0)] if len(reg_ts) else auctions.ts
    # an auction that waits leaves when its ttl has passed, or as a row
    # when its key's next person registers, whichever comes first
    wait = ~match
    w = auctions.at(wait)
    gone = np.maximum(passed_at(wm, w.owner, w.ts + cfg["ttl_ms"]),
                      w.step + 1)
    nxt = np.minimum(at[wait] + 1, max(len(reg_where) - 1, 0))
    comes = np.full(len(w.key), n_steps, np.int64)
    if len(reg_where):
        same = (at[wait] + 1 < len(reg_where)) & (
            reg_where[nxt] // n_steps == w.key)
        comes = np.where(same, reg_where[nxt] % n_steps, n_steps)
    flushed = comes < np.minimum(gone, n_steps)
    expired = ~flushed & (gone < n_steps)
    # rows: the auctions that join at once are in step order, so an
    # epoch's are a slice; the few flushed ones go to their epochs after
    lanes = lambda r: np.stack(
        [r.key, r.value.astype(np.float32).astype(np.int64)
         if control == "f32" else r.value, r.ts], axis=1)
    now, late = auctions.at(match), w.at(flushed)
    late_at = comes[flushed]
    ends = np.arange(epochs + 1) * spe - TO_SINK   # first step of an epoch
    cut = np.searchsorted(now.step, ends)
    now_rows, late_rows = lanes(now), lanes(late)
    late_epoch = (late_at + TO_SINK) // spe
    rows = [np.concatenate([now_rows[cut[e]:cut[e + 1]],
                            late_rows[late_epoch == e]])
            for e in range(epochs)]
    emit = np.concatenate([now.step, late_at])
    owner = np.concatenate([now.owner, late.owner])
    live_from = reg_where % n_steps
    return Want(
        rows, fired=len(emit), flushed=int(flushed.sum()),
        bagged=int(wait.sum()),
        bag_expired=int(expired.sum()), duplicates=duplicates,
        expired_probes=int((wait & known & (held_ts <= auctions.ts)
                            & (auctions.ts < held_ts + cfg["ttl_ms"])).sum()),
        peak_persons=step_peak(persons, n_steps, p),
        peak_auctions=step_peak(auctions, n_steps, p),
        peak_rows=int(np.bincount(emit * p + owner,
                                  minlength=n_steps * p).max())
        if len(emit) else 0,
        peak_waiting=occupancy_peak(w.step, np.minimum(gone, comes), w.owner,
                                    n_steps, p),
        peak_live=occupancy_peak(
            live_from, np.maximum(reg_expires, live_from),
            owners(cfg)[reg_where // n_steps], n_steps, p))


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
    visible: an auction whose seller is registered is a row as it
    reaches the join."""
    return (np.asarray(step) + TO_JOIN + TO_SINK) // cfg["steps_per_epoch"]
