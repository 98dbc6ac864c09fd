"""Bounded metrics history: the time axis /metrics.json lacks.

One scrape of the JobMaster's endpoint answers "what is the cluster
doing NOW"; diagnosing a slow drift (ring occupancy creeping toward
overwrite, overhead fraction rising after a redeploy) needs *history*.
This module keeps a bounded ring of periodic snapshots:

- :class:`MetricsHistory` runs a daemon sampler thread calling a
  zero-arg ``sample_fn`` (the endpoint's merged cluster view) every
  ``interval_s`` seconds. Samples land in (a) an in-memory ring
  (``deque(maxlen=window)``) and (b) optionally a JSON-lines file —
  one flushed append per sample, so a SIGKILLed process loses at most
  the line being written, and a reader tolerates that torn tail
  exactly like the checkpoint ledger. When the file outgrows
  ``2*window`` lines it is compacted from the ring via an atomic
  tmp+``os.replace`` rewrite, so a long run's history file stays
  bounded like the ring.
- :meth:`MetricsHistory.query` serves windowed reads (``since`` a
  wall-clock timestamp, ``last`` N samples) — the payload behind the
  endpoint's ``/metrics/history.json?since=TS&last=N``.

Sampling touches only host data (snapshot dicts), never the device:
safe from a thread while the main loop dispatches programs.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional


def read_history_file(path: str) -> List[dict]:
    """Read a history JSONL, tolerating a torn final line (SIGKILL mid
    append); a decode failure on any earlier line still raises."""
    from clonos_tpu.utils.jsonl import read_jsonl
    return read_jsonl(path)


class MetricsHistory:
    """Ring-bounded periodic snapshots of a metrics view."""

    def __init__(self, sample_fn: Optional[Callable[[], Dict[str, Any]]]
                 = None, path: Optional[str] = None,
                 interval_s: float = 2.0, window: int = 512,
                 # clonos: allow(wallclock): sample timestamps, obs-only
                 clock=time.time, mono=None):
        self.sample_fn = sample_fn
        self._path = path
        self.interval_s = float(interval_s)
        self.window = int(window)
        self._clock = clock
        #: what the sampling loop paces itself by; a test substitutes it
        self._mono = time.monotonic if mono is None else mono
        self._ring: Deque[dict] = collections.deque(maxlen=self.window)
        #: sampling slots skipped because a sample overran its whole
        #: interval (the loop re-anchors instead of bursting catch-up
        #: samples with bogus spacing)
        self.missed_slots = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._writer = None
        self._file_lines = 0
        if path is not None:
            from clonos_tpu.utils.jsonl import JsonlAppender
            self._writer = JsonlAppender(path, default=str)
            # A restarted process resumes its ring from the surviving
            # file tail (torn final line tolerated).
            for rec in read_history_file(path)[-self.window:]:
                self._ring.append(rec)
            self._file_lines = len(self._ring)

    # --- sampling ------------------------------------------------------------

    def sample_once(self) -> dict:
        """Take one sample now (also what the thread loop calls)."""
        try:
            metrics = self.sample_fn() if self.sample_fn else {}
        except Exception as e:       # sampler must outlive a bad gauge
            metrics = {"history-error": repr(e)}
        rec = {"ts": self._clock(), "metrics": metrics}
        with self._lock:
            self._ring.append(rec)
            if self._writer is not None:
                self._writer.append(rec)
                self._file_lines += 1
                if self._file_lines > 2 * self.window:
                    self._compact_locked()
        return rec

    def _compact_locked(self) -> None:
        # Atomic rewrite from the ring (utils/jsonl): the file never
        # exceeds 2*window lines for long, and a crash mid-compaction
        # leaves either the old file or the new one, never a mix.
        from clonos_tpu.utils.jsonl import atomic_rewrite_jsonl
        self._writer.close()     # os.replace swaps the inode under us
        self._file_lines = atomic_rewrite_jsonl(
            self._path, list(self._ring), default=str)

    def _loop(self) -> None:
        # Absolute-deadline pacing: ``wait(interval)`` THEN sample would
        # stretch every period by the sample's own duration (a slow
        # cluster-view merge under load turns a 2s interval into 3s+,
        # silently squeezing the ring's time span). Each deadline is
        # interval_s after the previous DEADLINE, not after the sample
        # finished; a sample that overruns whole intervals skips the
        # missed slots (counted) rather than firing a catch-up burst.
        next_due = self._mono() + self.interval_s
        while not self._stop.wait(max(next_due - self._mono(), 0.0)):
            self.sample_once()
            next_due += self.interval_s
            now = self._mono()
            if next_due <= now:
                missed = int((now - next_due) / self.interval_s) + 1
                self.missed_slots += missed
                next_due += missed * self.interval_s

    def start(self) -> "MetricsHistory":
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            daemon=True)
            self._thread.start()
        return self

    @property
    def started(self) -> bool:
        return self._thread is not None

    # --- queries -------------------------------------------------------------

    def query(self, since: Optional[float] = None,
              last: Optional[int] = None) -> List[dict]:
        """Samples with ``ts >= since`` (then) trimmed to the ``last``
        N, oldest first — ring order, so timestamps are monotone."""
        with self._lock:
            out = list(self._ring)
        if since is not None:
            out = [r for r in out if r.get("ts", 0) >= since]
        if last is not None and last >= 0:
            out = out[-last:]
        return out

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        with self._lock:
            if self._writer is not None:
                self._writer.close()
