"""Distributed tracing + recovery flight recorder.

The reference's observability story is metric scopes that follow
job→task→operator (MetricRegistryImpl + ScopeFormats) and ad-hoc log
lines around the recovery path (RecoveryManager.java state transitions,
JobCausalLogImpl.java:268-298 occupancy logging). Since the slot-pool
scheduler (runtime/scheduler.py) one job spans multiple worker OS
processes, and the question the paper's headline claim hangs on —
*where does the time go during an epoch and during a recovery?* — has
no single-process answer anymore. This module gives the framework spans
that follow a job across process boundaries:

- :class:`Tracer` mints a trace id, records **complete spans**
  (``ph: "X"``) and **instant events** (``ph: "i"``). Every record
  carries **two clocks** taken at entry: ``ts`` (wall, ``time.time``:
  what ``chrome.py`` and multi-process merging lay out by) and ``mono``
  (``time.monotonic``: what benchmark windows and commit stamps use);
  ``dur`` is the monotonic difference between entry and exit. Records
  go to (a) a bounded in-memory ring — the flight recorder, dumpable
  after the fact and served on ``MetricsEndpoint``'s ``/trace`` — and
  (b) optionally a JSON-lines file (one handle, append mode, flushed
  per record so a SIGKILLed worker's trace survives it).
- **One clock with the device**: each span also enters a
  ``jax.profiler.TraceAnnotation("clonos:<name>")``. With no profiler
  session that is an inactive TraceMe; under one (``jax.profiler.trace``)
  the program's spans lie in the same ``.xplane.pb`` as the device's
  "XLA Ops", on the profiler's clock.
- **Counters** (:meth:`Tracer.count` / :meth:`Tracer.counters`): bytes,
  rows and dispatches the spans cannot carry as durations, and one
  ``compile`` instant per program JAX builds or fetches.
- **Context propagation**: :meth:`Tracer.wire_context` returns a small
  dict (``{"trace_id", "span"}``) that control-wire JSON headers carry
  as a ``trace`` field (DEPLOY / TRIGGER_CHECKPOINT /
  DETERMINANT_REQUEST / FETCH_EDGE — parallel/transport.py); the
  receiving process calls :meth:`Tracer.adopt` and its subsequent spans
  land under the SAME trace id, so one recovery reconstructs from the
  JobMaster's and every worker's files together.
- **Always recording, locally**: the process-global tracer starts as a
  *local flight recorder* — a :class:`Tracer` with the ring only: no
  file, ``wire_context()`` → None (senders add no wire field) and
  ``enabled`` False, so call sites that gate argument building or
  cross-process work on ``tr.enabled`` skip it. What it costs is one
  slotted tuple per span (a few µs); call sites therefore keep to block,
  fence-phase and recovery-phase granularity — never a span per step
  inside a block or per record. The full tracer (file sink, wire
  propagation, ``enabled`` True) is an explicit opt-in
  (:func:`configure`, the ``--trace-dir`` CLI flags, or the
  ``observability.tracing.enabled`` config option). :class:`NullTracer`
  remains for callers that want to hand a component a tracer that
  records nothing.

Convert a recorded file with ``clonos_tpu trace run.jsonl --chrome
out.json`` (tools/trace2chrome.py) and load it in Perfetto / Chrome
``about:tracing``.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
import uuid
from typing import Any, Deque, Dict, List, Optional, Union

#: prefix of the ``jax.profiler.TraceAnnotation`` each span enters
ANNOTATION_PREFIX = "clonos:"
#: records the default local recorder keeps: a 60 s run of the busiest
#: benchmark cell (~40 records an epoch, 6 epochs a second) plus its
#: 2 x 512 single steps (9 records each) is ~25,000
DEFAULT_RING = 65536
#: JAX's event for a program built or fetched because the process did
#: not hold it
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

#: ring-internal ``ph`` of a :meth:`Tracer.complete` record ("X" outside)
_BACKDATED = "Xb"

SpanId = Union[int, str]


def _new_id() -> str:
    # clonos: allow(entropy) — trace ids are correlation metadata; they
    # never feed operator state and are not expected to replay.
    return uuid.uuid4().hex[:16]


class _NullSpan:
    """No-op context manager handed out by the disabled tracer."""

    __slots__ = ()
    span_id = None
    dur = 0.0
    ms = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """A tracer that records nothing: every operation is a no-op and
    ``wire_context()`` is None. Not the process default any more (that
    is the local flight recorder); for callers that pass one in."""

    enabled = False
    trace_id = None
    service = None
    dropped = 0

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def event(self, name: str, **args) -> None:
        pass

    def complete(self, name: str, dur_s: float, **args) -> None:
        pass

    def count(self, name: str, n: int = 1) -> None:
        pass

    def counters(self) -> Dict[str, int]:
        return {}

    def wire_context(self) -> None:
        return None

    def adopt(self, ctx) -> None:
        pass

    def records(self) -> List[dict]:
        return []

    def close(self) -> None:
        pass


_annotation_cls: Any = None


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation`` for a span (an inactive TraceMe
    unless a profiler session is running)."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation_cls = TraceAnnotation
        except Exception:            # no JAX in this process: spans only
            _annotation_cls = lambda _name: _NULL_SPAN
    return _annotation_cls(ANNOTATION_PREFIX + name)


class _Span:
    """A live span: context manager stamped on both clocks at entry and
    on the monotonic one at exit, emitting one complete record then.
    Exceptions propagate; the span still closes (its ``error`` arg
    records the fact). After exit ``dur`` / ``ms`` hold its length, so a
    call site feeds its phase table and histograms from these stamps
    instead of reading the clock again."""

    __slots__ = ("_tracer", "name", "_id", "_parent", "args", "ts",
                 "mono", "dur", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self._id = next(tracer._ids)
        self._parent: Optional[SpanId] = None
        self.args = args
        self.ts = self.mono = self.dur = 0.0
        self._ann = None

    @property
    def span_id(self) -> str:
        return self._tracer._render(self._id)

    @property
    def ms(self) -> float:
        return self.dur * 1e3

    def set(self, **args) -> None:
        """Add args known only inside the span (bytes moved, rows)."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        tr = self._tracer
        st = tr._stack()
        self._parent = st[-1] if st else None
        st.append(self._id)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.ts = tr._clock()
        self.mono = tr._mono()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tr = self._tracer
        self.dur = tr._mono() - self.mono
        self._ann.__exit__(exc_type, exc, tb)
        # down to and including this span: a child that an exception
        # left open (a chain's) must not become the next span's parent
        st = tr._stack()
        while st and st.pop() != self._id:
            pass
        if exc_type is not None:
            self.args["error"] = repr(exc)
        tr._emit(self.ts, self.mono, self.name, "X", self.dur, self._id,
                 self._parent, self.args)
        return False


class _Attach:
    """Parents this thread's spans to a span of another thread."""

    __slots__ = ("_tracer", "_parent")

    def __init__(self, tracer: "Tracer", parent: Optional[SpanId]):
        self._tracer, self._parent = tracer, parent

    def __enter__(self):
        if self._parent is not None:
            self._tracer._stack().append(self._parent)
        return self

    def __exit__(self, *exc) -> bool:
        if self._parent is not None:
            st = self._tracer._stack()
            if st:
                st.pop()
        return False


class SpanChain:
    """Consecutive spans on one thread for code that runs through its
    phases in one body: ``switch(name)`` closes the open span and opens
    ``<prefix><name>``; each closed span's milliseconds are added to
    ``into[name]`` — the phase table and the span share one pair of
    stamps. ``close()`` ends the last one; as a context manager the
    chain closes whatever is open when the body ends or raises."""

    __slots__ = ("_tracer", "_prefix", "_into", "_args", "_open", "_name")

    def __init__(self, tracer: "Tracer", prefix: str,
                 into: Optional[Dict[str, float]], args: Dict[str, Any]):
        self._tracer, self._prefix = tracer, prefix
        self._into, self._args = into, args
        self._open: Optional[_Span] = None
        self._name = ""

    def __enter__(self) -> "SpanChain":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close(exc)
        return False

    def switch(self, name: str) -> None:
        self.close()
        self._name = name
        self._open = self._tracer.span(self._prefix + name, **self._args)
        self._open.__enter__()

    def close(self, exc: Optional[BaseException] = None) -> None:
        sp = self._open
        if sp is None:
            return
        self._open = None
        if exc is None:
            sp.__exit__(None, None, None)
        else:
            sp.__exit__(type(exc), exc, exc.__traceback__)
        if self._into is not None:
            self._into[self._name] = (self._into.get(self._name, 0.0)
                                      + sp.ms)


class Tracer:
    """Process tracer: one trace id (minted or adopted), a bounded
    flight-recorder ring, counters, and an optional JSON-lines file
    sink.

    ``enabled`` gates what goes *beyond* recording locally: call sites
    building expensive args or adopting a sender's trace, and
    ``wire_context()`` handing senders a ``trace`` field. The local
    flight recorder has it off.

    Thread-safe: spans/events may be emitted from server threads (the
    control-plane handlers, the fence worker) as well as the main loop;
    the parent-span stack is thread-local so concurrent spans nest
    correctly per thread, and :meth:`attach` carries a parent across."""

    def __init__(self, service: str, path: Optional[str] = None,
                 # clonos: allow(wallclock): span timestamps, obs-only
                 trace_id: Optional[str] = None, clock=time.time,
                 buffer: int = DEFAULT_RING, enabled: bool = True):
        self.service = service
        self.trace_id = trace_id or _new_id()
        self.enabled = enabled
        self._path = path
        self._clock = clock
        # a caller that substitutes the wall clock (tests) gets its
        # durations on the same clock
        # clonos: allow(wallclock): identity test only, obs-only
        self._mono = time.monotonic if clock is time.time else clock
        self._file = None
        self._lock = threading.Lock()
        self._local = threading.local()
        #: span ids: a per-process counter, rendered ``<pid>-<n>`` (hex)
        #: only when a record leaves the ring
        self._ids = itertools.count(1)
        #: the flight recorder: most recent records (flat tuples), bounded
        self._ring: Deque[tuple] = collections.deque(maxlen=buffer)
        #: records evicted from the ring at overflow — a nonzero count
        #: means the in-memory timeline is TRUNCATED (the file sink, if
        #: any, still has everything). Surfaced as the
        #: ``trace.dropped-records`` counter in /metrics.json and top.
        self.dropped = 0
        self._counters: Dict[str, int] = {}
        # clonos: allow(entropy): trace metadata, never replayed data
        self._pid = os.getpid()

    # --- span stack (thread-local parents) -----------------------------------

    def _stack(self) -> List[SpanId]:
        try:
            return self._local.stack
        except AttributeError:
            st = self._local.stack = []
            return st

    def _render(self, sid: Optional[SpanId]) -> Optional[str]:
        if sid is None or isinstance(sid, str):
            return sid
        return f"{self._pid:x}-{sid:x}"

    def current_span(self) -> Optional[str]:
        st = self._stack()
        return self._render(st[-1]) if st else None

    def attach(self, parent: Optional[SpanId]) -> _Attach:
        """Context manager for a worker thread: spans opened inside are
        children of ``parent`` (a ``current_span()`` taken on the thread
        that started the work)."""
        return _Attach(self, parent)

    # --- recording -----------------------------------------------------------

    def _emit(self, ts: float, mono: float, name: str, ph: str,
              dur: float, span: SpanId, parent: Optional[SpanId],
              args: Optional[Dict[str, Any]]) -> None:
        rec = (ts, mono, name, ph, dur, span, parent,
               threading.get_ident() & 0xFFFF, args or None, self.trace_id)
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1      # eviction, not silence
            self._ring.append(rec)
            if self._path is not None:
                # One append-mode handle for the tracer's lifetime,
                # flushed per record: a SIGKILL loses at most the record
                # being written, never the buffered history.
                if self._file is None:
                    self._file = open(self._path, "a")
                self._file.write(
                    json.dumps(self._as_dict(rec), default=str) + "\n")
                self._file.flush()

    def _as_dict(self, rec: tuple) -> dict:
        ts, mono, name, ph, dur, span, parent, tid, args, trace = rec
        out = {"ts": ts, "mono": mono, "name": name, "ph": ph,
               "trace": trace, "service": self.service, "pid": self._pid,
               "tid": tid, "span": self._render(span),
               "parent": self._render(parent)}
        if ph != "i":
            out["ph"] = "X"
            out["dur"] = dur
            if ph == _BACKDATED:
                out["backdated"] = True
        if args:
            out["args"] = args
        return out

    def _instant(self, ts: float, mono: float, name: str, ph: str,
                 dur: float, args: Dict[str, Any]) -> None:
        st = self._stack()
        self._emit(ts, mono, name, ph, dur, next(self._ids),
                   st[-1] if st else None, args)

    def span(self, name: str, **args) -> _Span:
        """Context manager: records a complete span over the ``with``
        body, parented to the enclosing span of this thread."""
        return _Span(self, name, args)

    def chain(self, prefix: str, into: Optional[Dict[str, float]] = None,
              **args) -> SpanChain:
        """Consecutive phase spans named ``<prefix><phase>``
        (:class:`SpanChain`)."""
        return SpanChain(self, prefix, into, args)

    def event(self, name: str, **args) -> None:
        """Instant event at now."""
        self._instant(self._clock(), self._mono(), name, "i", 0.0, args)

    def complete(self, name: str, dur_s: float, **args) -> None:
        """Record an already-measured span ending now (the caller timed
        it; both stamps are back-dated so the timeline lays out
        correctly; the record says ``backdated``, since it may start
        before the span it was emitted under). For intervals no thread
        spent inside a ``with`` — a checkpoint's trigger-to-complete
        latency; otherwise use :meth:`span`, which stamps at entry."""
        self._instant(self._clock() - dur_s, self._mono() - dur_s, name,
                      _BACKDATED, dur_s, args)

    # --- counters ------------------------------------------------------------

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to a named counter (bytes, rows, dispatches)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    # --- context propagation -------------------------------------------------

    def wire_context(self) -> Optional[Dict[str, Any]]:
        """The ``trace`` field control-wire JSON headers carry (None on
        the local recorder: the wire stays as an untraced build's)."""
        if not self.enabled:
            return None
        return {"trace_id": self.trace_id, "span": self.current_span()}

    def adopt(self, ctx: Optional[Dict[str, Any]]) -> None:
        """Join the sender's trace: subsequent spans/events from this
        process land under the sender's trace id (idempotent)."""
        if ctx and ctx.get("trace_id"):
            self.trace_id = str(ctx["trace_id"])

    # --- flight recorder -----------------------------------------------------

    def records(self) -> List[dict]:
        with self._lock:
            ring = list(self._ring)
        return [self._as_dict(r) for r in ring]

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


# --- process-global tracer ---------------------------------------------------


def _local_recorder() -> Tracer:
    """The default: ring only, nothing on the wire, ``enabled`` False."""
    return Tracer("local", enabled=False)


_global_tracer: Any = _local_recorder()
_global_lock = threading.Lock()
_compile_listeners_on = False
_last_cache_event = threading.local()


def _on_jax_event(event: str, **kw) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is not None:
        _last_cache_event.kind = kind


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    tr = _global_tracer
    tr.count("compile.programs")
    tr.event("compile", fun_name=str(kw.get("fun_name")),
             seconds=float(duration),
             cache=getattr(_last_cache_event, "kind", None))
    _last_cache_event.kind = None


def install_compile_listener() -> None:
    """One ``compile`` instant (``fun_name``, seconds, cache hit or miss)
    and a ``compile.programs`` count per program JAX builds or fetches,
    into whatever tracer is current — so "which step recompiled" sits in
    the timeline beside the block it stalled. Idempotent; called by the
    executor, the first thing in a process that compiles."""
    global _compile_listeners_on
    with _global_lock:
        if _compile_listeners_on:
            return
        import jax
        jax.monitoring.register_event_listener(_on_jax_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _compile_listeners_on = True


def get_tracer():
    """The process tracer (the local flight recorder unless
    :func:`configure` ran)."""
    return _global_tracer


def configure(service: str, path: Optional[str] = None,
              trace_id: Optional[str] = None, **kw) -> Tracer:
    """Install the full process tracer — file sink, wire propagation,
    ``enabled`` True — replacing the previous one, which is closed."""
    global _global_tracer
    with _global_lock:
        old = _global_tracer
        _global_tracer = Tracer(service, path=path, trace_id=trace_id,
                                **kw)
        old.close()
        return _global_tracer


def reset() -> None:
    """Back to a fresh local flight recorder (tests; also closes the
    file)."""
    global _global_tracer
    with _global_lock:
        _global_tracer.close()
        _global_tracer = _local_recorder()
