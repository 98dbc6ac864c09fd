"""The plain reference of the ``source-window-reduce-sink`` topology: what
the transactional sink must have committed, as a NumPy fold of the same
table. Imports nothing of the program, and nothing of the harness.

The harness finds this file by the ``topology`` a configuration names and
calls the four functions at its end (``expected``, ``committed_of``,
``check``, ``visible_epoch_of_step``) and ``CONTROLS``; the rest is how
they are worked out.

Semantics (source -> keyBy -> tumbling count window -> keyBy -> running
reduce -> sink, every edge one step deep): source subtask ``p`` emits its
step-``s`` batch at step ``s``; it reaches the window at step ``s + 1``
and counts into window ``(s + 1) // W``. Window ``w`` closes at step
``(w + 1) * W`` and emits, per key with a nonzero sum, that sum stamped
``(w + 1) * W``; the reduce adds it to the key's running sum one step
later; the sink sees ``(key, running sum, stamp)`` one step after that, at
step ``(w + 1) * W + 2``, and the row commits with the epoch that step
lies in. Sums wrap at int32 like the device's.

The stream is periodic (``benchlib/stream.py``), so window sums repeat
every ``table_steps / W`` windows — except window 0, which has no step
``-1`` to receive. That is all the fold needs to know to cover a run of
any length with one ``np.bincount`` over a single period.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: run modes of the harness that put a perturbed reference in the
#: program's place, so that the comparison can be shown to fail
CONTROLS = ("f32", "at-least-once")


def _period_sums(keys: np.ndarray, vals: np.ndarray, batch: int,
                 window_steps: int, num_keys: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``[M, num_keys]`` int64 window sums of one table period, and the
    ``[num_keys]`` share of period-window 0 that comes from the table's
    last step (absent from the run's very first window)."""
    n = keys.shape[1]
    table_steps = n // batch
    if table_steps % window_steps:
        raise ValueError("table_steps must be a multiple of window_steps")
    m = table_steps // window_steps
    step = np.arange(n) // batch
    win = ((step + 1) % table_steps) // window_steps
    cell = (win[None, :] * num_keys + keys).ravel()
    sums = np.bincount(cell, weights=vals.ravel().astype(np.float64),
                       minlength=m * num_keys)
    last = step == table_steps - 1
    head = np.bincount(keys[:, last].ravel(),
                       weights=vals[:, last].ravel().astype(np.float64),
                       minlength=num_keys)
    # float64 holds these integers exactly (< 2**53 by a wide margin)
    return (sums.reshape(m, num_keys).astype(np.int64),
            head.astype(np.int64))


def expected_tables(keys: np.ndarray, vals: np.ndarray, batch: int,
                    window_steps: int, num_keys: int, n_windows: int,
                    control: Optional[str] = None,
                    control_step: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-window sums and running sums, both ``[n_windows, num_keys]``
    int32, for the first ``n_windows`` windows of the run.

    ``control`` perturbs the fold (``CONTROLS``): ``"f32"`` accumulates
    the running sums in float32, the precision a one-hot matmul gather
    would tempt a later change into; ``"at-least-once"`` delivers
    partition 0's batch of step ``control_step`` twice, what a recovery
    that re-read without deduplicating would do."""
    period, head = _period_sums(keys, vals, batch, window_steps, num_keys)
    m = period.shape[0]
    sums = period[np.arange(n_windows) % m]
    if n_windows:
        sums[0] -= head
    if control == "at-least-once":
        table_steps = keys.shape[1] // batch
        t = control_step % table_steps
        w = (control_step + 1) // window_steps
        if w < n_windows:
            np.add.at(sums[w], keys[0, t * batch:(t + 1) * batch],
                      vals[0, t * batch:(t + 1) * batch].astype(np.int64))
    elif control == "f32":
        sums32 = sums.astype(np.int32)
        running = np.cumsum(sums32.astype(np.float32), axis=0,
                            dtype=np.float32)
        return sums32, running.astype(np.int64).astype(np.int32)
    elif control is not None:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    sums = sums.astype(np.int32)                       # the device's wrap
    running = np.cumsum(sums, axis=0, dtype=np.int64).astype(np.int32)
    return sums, running


def windows_of_epoch(epoch: int, steps_per_epoch: int, window_steps: int
                     ) -> Tuple[int, int]:
    """``[lo, hi)``: the windows whose rows reach the sink during
    ``epoch``, i.e. ``((w + 1) * W + 2) // steps_per_epoch == epoch``."""
    def first_at(step: int) -> int:           # least w with sink step >= step
        return max(0, -(-(step - 2) // window_steps) - 1)
    return (first_at(epoch * steps_per_epoch),
            first_at((epoch + 1) * steps_per_epoch))


def commit_epoch_of_step(step: np.ndarray, steps_per_epoch: int,
                         window_steps: int) -> np.ndarray:
    """The epoch whose commit makes step ``step``'s records visible."""
    w = (step + 1) // window_steps
    return ((w + 1) * window_steps + 2) // steps_per_epoch


def rows_of_windows(sums: np.ndarray, running: np.ndarray, lo: int, hi: int,
                    window_steps: int) -> np.ndarray:
    """``[n, 3]`` (key, running sum, stamp) rows of windows ``[lo, hi)``."""
    wi, ki = np.nonzero(sums[lo:hi])
    return np.stack([ki, running[lo:hi][wi, ki],
                     (wi + lo + 1) * window_steps], axis=1).astype(np.int32)


def compare_epoch(rows: np.ndarray, sums: np.ndarray, running: np.ndarray,
                  lo: int, hi: int, window_steps: int) -> int:
    """How many of one epoch's committed ``[n, 3]`` rows are wrong,
    missing, duplicated or foreign, against windows ``[lo, hi)``."""
    num_keys = sums.shape[1]
    want_present = sums[lo:hi] != 0
    rows = np.asarray(rows, np.int64).reshape(-1, 3)
    key, val, ts = rows[:, 0], rows[:, 1], rows[:, 2]
    w = ts // window_steps - 1
    ok = ((ts % window_steps == 0) & (w >= lo) & (w < hi)
          & (key >= 0) & (key < num_keys))
    bad = int((~ok).sum())
    cell = (w[ok] - lo) * num_keys + key[ok]
    n_cells = (hi - lo) * num_keys
    seen = np.bincount(cell, minlength=n_cells).reshape(hi - lo, num_keys)
    got = np.zeros(n_cells, np.int64)
    got[cell] = val[ok]
    got = got.reshape(hi - lo, num_keys)
    present = seen > 0
    bad += int((seen > 1).sum())                       # duplicated
    bad += int((present != want_present).sum())        # missing / foreign
    bad += int((present & want_present
                & (got != running[lo:hi])).sum())      # wrong value
    return bad


def check_committed(committed: Dict[int, Sequence[np.ndarray]],
                    epochs_offered: int, steps_per_epoch: int,
                    window_steps: int, sums: np.ndarray, running: np.ndarray
                    ) -> Tuple[int, List[int], int]:
    """Hold the whole committed stream to the fold. ``committed`` maps
    epoch -> the row arrays committed under it (more than one array is
    itself a fault: an epoch commits once). Returns (mismatched rows,
    epochs whose commit is missing or wrong, rows compared)."""
    mismatched, failed, compared = 0, [], 0
    for e in range(epochs_offered):
        lo, hi = windows_of_epoch(e, steps_per_epoch, window_steps)
        parts = committed.get(e)
        if parts is None:
            n_bad = int((sums[lo:hi] != 0).sum()) or 1
        else:
            rows = (np.concatenate([np.asarray(p).reshape(-1, 3)
                                    for p in parts], axis=0)
                    if parts else np.zeros((0, 3), np.int32))
            compared += rows.shape[0]
            n_bad = compare_epoch(rows, sums, running, lo, hi, window_steps)
            n_bad += len(parts) - 1
        if n_bad:
            mismatched += n_bad
            failed.append(e)
    foreign = [e for e in committed if not 0 <= e < epochs_offered]
    for e in foreign:
        mismatched += sum(np.asarray(p).reshape(-1, 3).shape[0]
                          for p in committed[e]) or 1
        failed.append(e)
    return mismatched, failed, compared


# --- what the harness calls: every topology's reference has these ------------


def expected(cfg: dict, keys: np.ndarray, vals: np.ndarray, epochs: int,
             control: Optional[str] = None, control_step: int = 0
             ) -> Tuple[np.ndarray, np.ndarray]:
    """What ``epochs`` epochs over the table ``keys`` / ``vals``
    (``[partitions, table_steps * batch]``) must have committed;
    ``control`` names a perturbation of it (``CONTROLS``)."""
    spe, w = cfg["steps_per_epoch"], cfg["window_steps"]
    n_windows = windows_of_epoch(epochs - 1, spe, w)[1] if epochs else 0
    return expected_tables(keys, vals, cfg["batch"], w, cfg["num_keys"],
                           n_windows, control, control_step)


def committed_of(want: Tuple[np.ndarray, np.ndarray], cfg: dict, epochs: int
                 ) -> Dict[int, List[np.ndarray]]:
    """The commits of a program that computed ``want``: epoch -> rows.
    It is how a control takes the program's place."""
    spe, w = cfg["steps_per_epoch"], cfg["window_steps"]
    return {e: [rows_of_windows(*want, *windows_of_epoch(e, spe, w), w)]
            for e in range(epochs)}


def check(committed: Dict[int, Sequence[np.ndarray]],
          want: Tuple[np.ndarray, np.ndarray], cfg: dict, epochs: int
          ) -> Tuple[int, List[int], int]:
    """(mismatched rows, epochs whose commit is missing or wrong, rows
    compared) of the whole committed stream against ``want``."""
    return check_committed(committed, epochs, cfg["steps_per_epoch"],
                           cfg["window_steps"], *want)


def visible_epoch_of_step(step: np.ndarray, cfg: dict) -> np.ndarray:
    """The epoch whose commit makes the records of ``step`` visible."""
    return commit_epoch_of_step(step, cfg["steps_per_epoch"],
                                cfg["window_steps"])
