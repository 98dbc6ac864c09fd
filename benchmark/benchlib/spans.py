"""Host spans the benchmark puts around its calls into each layer, from
outside: a bound method is replaced on the instance by a wrapper that
stamps ``time.monotonic()`` before and after, and (so that the profiler's
trace carries the same span and an idle gap can be attributed to it)
enters a ``jax.profiler.TraceAnnotation`` named ``bench:<span>``.

Installed only in a ``--trace 1`` run: end-to-end numbers are taken with
no wrapper in place.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple

from benchlib.trace_reduce import PREFIX  # of every annotation written


class Spans:
    def __init__(self):
        self.spans: Dict[str, List[Tuple[float, float]]] = {}

    def span(self, name: str):
        return _Span(self, name)

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with _Span(self, name):
                return fn(*args, **kwargs)
        return wrapped

    def durations_ms(self, name: str, t0: float, t1: float) -> List[float]:
        """Durations of the ``name`` spans that lie inside ``[t0, t1]``."""
        return [(b - a) * 1e3 for a, b in self.spans.get(name, ())
                if a >= t0 and b <= t1]


class _Span:
    def __init__(self, owner: Spans, name: str):
        import jax
        self._owner, self._name = owner, name
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        self._owner.spans.setdefault(self._name, []).append((self._t0, t1))
        return False
