"""Arithmetic on commit stamps: the fence-aligned rate, latency from the
intended send instant, and the open-loop schedule. NumPy only.

A commit stamp is ``time.monotonic()`` taken from the client's side, in
``TransactionLog.committer``, when an epoch's rows become visible.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


def stamps_in(stamps: Dict[int, float], t0: float, t1: float
              ) -> Sequence[int]:
    """Epochs whose commit stamp falls inside ``[t0, t1]``, in order."""
    return sorted(e for e, t in stamps.items() if t0 <= t <= t1)


def fence_aligned_rate(stamps: Dict[int, float], t0: float, t1: float,
                       records_per_epoch: int
                       ) -> Optional[Tuple[float, int, float]]:
    """Records in the epochs committed between the first and the last
    commit stamp inside the window, over the time between those two
    stamps: a count of whole epochs over the time they took, so the
    window's edges quantise nothing. Returns (records/s, epochs counted,
    seconds spanned), or None with fewer than two commits inside."""
    inside = stamps_in(stamps, t0, t1)
    if len(inside) < 2:
        return None
    first, last = inside[0], inside[-1]
    span = stamps[last] - stamps[first]
    return (last - first) * records_per_epoch / span, last - first, span


def parts_of(stamps: Dict[int, float], t0: float, t1: float,
             parts: int = 4) -> List[Tuple[int, int]]:
    """The window's committed epochs cut into ``parts`` runs of
    consecutive epochs, as equal in number as they come: the (first,
    last) epoch of each, a run ending on the stamp the next begins on.
    Empty with fewer than ``parts`` epochs between the first and the
    last stamp inside."""
    inside = stamps_in(stamps, t0, t1)
    if len(inside) <= parts:
        return []
    cuts = np.linspace(0, len(inside) - 1, parts + 1).astype(int)
    return [(inside[a], inside[b]) for a, b in zip(cuts[:-1], cuts[1:])]


def rates_by_part(stamps: Dict[int, float], t0: float, t1: float,
                  records_per_epoch: int, parts: int = 4) -> List[float]:
    """The fence-aligned rate of each part of :func:`parts_of`: shows
    whether a run's rate drifted inside its window."""
    return [(b - a) * records_per_epoch / (stamps[b] - stamps[a])
            for a, b in parts_of(stamps, t0, t1, parts)]


class Schedule:
    """Open loop at a fixed rate, paced by the epoch (the program's
    smallest unit of work with a commit): epoch ``first_epoch + i`` is
    due when its last record has been sent, at ``t0 + (i + 1) * period``.
    The generator is a clock: it costs the job no core."""

    def __init__(self, t0: float, rate: float, records_per_epoch: int,
                 steps_per_epoch: int, first_epoch: int):
        self.t0 = t0
        self.period = records_per_epoch / rate
        self.steps_per_epoch = steps_per_epoch
        self.first_epoch = first_epoch

    def due(self, epoch: int) -> float:
        return self.t0 + (epoch - self.first_epoch + 1) * self.period

    def send_instant(self, step: np.ndarray) -> np.ndarray:
        """When the records of absolute step ``step`` were due to be
        sent: evenly over their epoch's period (the middle of the step's
        slot)."""
        rel = step - self.first_epoch * self.steps_per_epoch
        return self.t0 + (rel + 0.5) / self.steps_per_epoch * self.period


def commit_latencies_ms(stamps: Dict[int, float], schedule: Schedule,
                        t0: float, t1: float,
                        visible_epoch: Callable[[np.ndarray], np.ndarray],
                        last_epoch: int) -> np.ndarray:
    """Per step of the schedule (every step holds the same number of
    records, so percentiles over steps are percentiles over records):
    the commit stamp of the epoch that makes its records visible
    (``visible_epoch(steps)``, the topology's reference knows it), minus
    its intended send instant — never the instant it was pulled. Only
    records whose commit falls inside ``[t0, t1]`` are in the sample."""
    spe = schedule.steps_per_epoch
    steps = np.arange(schedule.first_epoch * spe, (last_epoch + 1) * spe)
    ce = visible_epoch(steps)
    stamp = np.array([stamps.get(int(e), np.nan)
                      for e in range(int(ce.max()) + 1)])
    at = stamp[ce]
    keep = (at >= t0) & (at <= t1)
    return (at[keep] - schedule.send_instant(steps[keep])) * 1e3


def service_ms(stamps: Dict[int, float], schedule: Schedule, t0: float,
               t1: float) -> np.ndarray:
    """Commit stamp minus the due instant of the epoch's last record:
    commit latency with the epoch's own length taken out."""
    return np.array([(stamps[e] - schedule.due(e)) * 1e3
                     for e in stamps_in(stamps, t0, t1)
                     if e >= schedule.first_epoch])


def latency_sample(run) -> Optional[np.ndarray]:
    """The latency sample of a paced run (None in a cell with no
    schedule, or with no commit inside the window)."""
    if run.schedule is None:
        return None
    lat = commit_latencies_ms(
        run.stamps, run.schedule, *run.window,
        lambda steps: run.reference.visible_epoch_of_step(steps, run.cfg),
        run.last_window_epoch)
    return lat if lat.size else None
