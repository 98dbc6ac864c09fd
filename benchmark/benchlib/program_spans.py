"""The program's own spans, read from inside: what
``clonos_tpu.obs.get_tracer()`` recorded in this process while the run
went on (``clonos_tpu/obs/trace.py``; PERF.md section 3 lists the names).

Every record carries ``mono`` (``time.monotonic()`` at entry), the clock
of ``run.window``, ``run.recover_wall`` and the commit stamps, so a span
is placed in the run without any mapping. For the device's idle time the
spans go onto the profiler's clock through the one span both sides have:
``run.spans.spans["steady"]`` (monotonic, the harness's) against
``steady`` in ``run.events.host`` (the same span as the profiler saw it).

A program without such a recorder (the parent of the PR that added it:
its default tracer records nothing) yields no records, and every reader
built on this module then returns None: the metric is left out of the
line. So does a ring that evicted records inside what a reader reads.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import statistics
from typing import Dict, List, Optional, Sequence

from benchlib import trace_reduce

Span = Dict[str, object]           # a tracer record with ph "X"
#: name under which the harness's own sleep (open loop, waiting for an
#: epoch's due instant) enters the idle table: it is attributed, and not
#: to the program
WAIT_DUE = "bench:wait_due"
UNATTRIBUTED = "(none)"


@dataclasses.dataclass
class Program:
    """One snapshot of the recorder: complete spans stamped at entry
    (back-dated ``complete()`` records are no thread's interval and are
    left out), counters, and what the ring evicted."""
    spans: List[Span]
    counters: Dict[str, int]
    dropped: int
    #: ``mono`` of the oldest record still in the ring
    oldest: float

    def intact(self, lo: float) -> bool:
        """Whether the ring still holds everything from ``lo`` on."""
        return bool(self.spans) and (self.dropped == 0 or self.oldest <= lo)

    def inside(self, name: str, lo: float, hi: float) -> List[Span]:
        """Spans called ``name`` lying wholly inside ``[lo, hi]``
        (monotonic seconds), oldest first; empty if the ring dropped
        records in there."""
        if not self.intact(lo):
            return []
        return [s for s in self.spans if s["name"] == name
                and s["mono"] >= lo and s["mono"] + s["dur"] <= hi]

    def main_thread(self) -> Optional[int]:
        """The thread that drives the job: the one the ``epoch`` spans
        are on (the fence worker's spans overlap it and say nothing
        about why the device waits)."""
        tids = [s["tid"] for s in self.spans if s["name"] == "epoch"]
        return statistics.mode(tids) if tids else None


def snapshot(tracer) -> Program:
    records = tracer.records() if tracer is not None else []
    stamped = [r for r in records if r.get("mono") is not None]
    spans = [r for r in stamped if r.get("ph") == "X"
             and not r.get("backdated")]
    counters = getattr(tracer, "counters", dict)()
    return Program(spans, dict(counters), int(getattr(tracer, "dropped", 0)),
                   min((r["mono"] for r in stamped), default=0.0))


def of(run) -> Program:
    """The recorder's snapshot for this run, taken once (the readers of
    one result line all read the same records)."""
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        try:
            from clonos_tpu.obs import get_tracer
            tracer = get_tracer()
        except ImportError:
            tracer = None
        cached = run._program_spans = snapshot(tracer)
    return cached


# --- durations ---------------------------------------------------------------


def window_ms(run, name: str) -> List[float]:
    """Milliseconds of each ``name`` span inside the steady window."""
    return [s["dur"] * 1e3 for s in of(run).inside(name, *run.window)]


def mean_ms(run, name: str) -> Optional[float]:
    d = window_ms(run, name)
    return sum(d) / len(d) if d else None


def median_ms(run, name: str) -> Optional[float]:
    d = window_ms(run, name)
    return statistics.median(d) if d else None


def mean_arg(run, name: str, arg: str) -> Optional[float]:
    """Mean of a span's numeric arg over the window's ``name`` spans
    (a counter's increment, copied into the span that closed around it)."""
    v = [s["args"][arg] for s in of(run).inside(name, *run.window)
         if arg in (s.get("args") or {})]
    return sum(v) / len(v) if v else None


def recovery_span(run) -> Optional[Span]:
    """The kill phase's ``recovery`` span: the last one that is no drill
    and lies inside the harness's ``recover()`` wall."""
    found = [s for s in of(run).inside("recovery", *run.recover_wall)
             if not (s.get("args") or {}).get("drill")]
    return found[-1] if found else None


def recovery_phase_ms(run, phase: str) -> Optional[float]:
    """Milliseconds of ``recovery.<phase>`` inside the kill phase's
    ``recovery`` span, summed (a phase runs once per failed subtask)."""
    rec = recovery_span(run)
    if rec is None:
        return None
    d = [s["dur"] * 1e3 for s in of(run).spans
         if s["name"] == "recovery." + phase
         and s["parent"] == rec["span"]]
    return sum(d) if d else None


# --- onto the profiler's clock -----------------------------------------------


def clock_offset_ns(run) -> Optional[float]:
    """Profiler nanoseconds minus monotonic nanoseconds, from the start
    of the harness's ``steady`` span on both clocks."""
    if run.events is None or run.spans is None:
        return None
    mono = run.spans.spans.get("steady")
    prof = trace_reduce.span_window(run.events, "steady")
    if not mono or prof is None:
        return None
    return prof[0] - mono[-1][0] * 1e9


def leaves(spans: Sequence[Span]) -> List[Span]:
    """The spans no other span (of the same thread) is a child of."""
    parents = {(s["tid"], s["parent"]) for s in spans}
    return [s for s in spans if (s["tid"], s["span"]) not in parents]


def host_events(spans: Sequence[Span], offset_ns: float
                ) -> List[trace_reduce.Event]:
    return sorted(((s["name"], s["mono"] * 1e9 + offset_ns, s["dur"] * 1e9)
                   for s in spans), key=lambda e: e[1])


def idle_by_program_span(run, only_leaves: bool
                         ) -> Optional[Dict[str, float]]:
    """Seconds of the first chip's idle time inside the traced steady
    span, by the innermost program span of the driving thread under way
    (``only_leaves``: by leaf span, a parent's remainder then counting
    as ``(none)``); the harness's ``wait_due`` sleeps enter under
    :data:`WAIT_DUE`."""
    offset = clock_offset_ns(run)
    if offset is None or not run.events.ops:
        return None
    lo_hi = run.trace_window("steady")
    prog = of(run)
    steady = run.spans.spans["steady"][-1]
    if not prog.intact(steady[0]):
        return None
    tid = prog.main_thread()
    spans = [s for s in prog.spans if s["tid"] == tid
             and s["mono"] + s["dur"] > steady[0] and s["mono"] < steady[1]]
    if not spans:
        return None
    host = host_events(leaves(spans) if only_leaves else spans, offset)
    host += [(WAIT_DUE, a * 1e9 + offset, (b - a) * 1e9)
             for a, b in run.spans.spans.get("wait_due", ())]
    events = trace_reduce.Events(run.events.ops, run.events.modules, host)
    return trace_reduce.idle_by_span(events, min(run.events.ops), *lo_hi)


def idle_unattributed_pct(idle_by_leaf: Dict[str, float]) -> Optional[float]:
    total = sum(idle_by_leaf.values())
    if not total:
        return None
    return 100.0 * idle_by_leaf.get(UNATTRIBUTED, 0.0) / total


# --- the same spans as the profiler saw them ---------------------------------


def annotations(xplane: str, prefix: str = "clonos:"
                ) -> List[trace_reduce.Event]:
    """``(name, start_ns, dur_ns)`` of the program's spans as
    ``jax.profiler.TraceAnnotation`` events in an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name[len(prefix):], e.start_ns, e.duration_ns)
                        for e in line.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def clock_check(run, xplane: str, name: str = "epoch") -> Optional[dict]:
    """The recorder's ``name`` spans, mapped through ``mono``, against
    the ``clonos:<name>`` annotations of the same trace: how far start
    and end lie apart at most (ms), over the spans both hold."""
    offset = clock_offset_ns(run)
    if offset is None:
        return None
    seen = [(s, s + d) for n, s, d in annotations(xplane) if n == name]
    if not seen:
        return None
    lo, hi = seen[0][0], seen[-1][1]
    mine = [(s["mono"] * 1e9 + offset,
             (s["mono"] + s["dur"]) * 1e9 + offset)
            for s in of(run).spans if s["name"] == name]
    mine = [m for m in mine if m[0] >= lo - 1e6 and m[1] <= hi + 1e6]
    if len(mine) != len(seen):
        return {"spans": len(mine), "annotations": len(seen)}
    return {"spans": len(mine), "annotations": len(seen),
            "max_start_ms": max(abs(m[0] - a[0]) for m, a in
                                zip(mine, seen)) / 1e6,
            "max_end_ms": max(abs(m[1] - a[1]) for m, a in
                              zip(mine, seen)) / 1e6}


def inside_against_outside(run) -> Dict[str, float]:
    """The inside spans beside the outside wrappers that time the same
    layers in this run (ms): the sink tap's three parts against
    ``sink_absorb``, the ``fence`` span against ``fence-tail``, the
    ``recovery`` span against the harness's ``recover()`` wall — the
    rest of that wall is the harness's final sync on the carry."""
    out: Dict[str, float] = {}
    parts = [mean_ms(run, "block.sink." + p)
             for p in ("wait", "d2h", "shard")]
    absorb = run.spans.durations_ms("sink_absorb", *run.window)
    if all(p is not None for p in parts) and absorb:
        out["sink_parts_ms"] = sum(parts)
        out["sink_absorb_ms"] = sum(absorb) / len(absorb)
    fence = median_ms(run, "fence")
    if fence is not None and run.fence_tail_ms:
        out["fence_span_ms"] = fence
        out["fence_tail_ms"] = statistics.median(run.fence_tail_ms)
    rec = recovery_span(run)
    if rec is not None:
        out["recovery_span_ms"] = rec["dur"] * 1e3
        out["recover_wall_ms"] = (run.recover_wall[1]
                                  - run.recover_wall[0]) * 1e3
    return out


def newest_xplane(out_root: str) -> Optional[str]:
    """The trace of the run in progress (the harness keeps it under
    ``benchmark_out/run-*/trace`` until the result line is out)."""
    found = sorted(glob.glob(os.path.join(
        out_root, "run-*", "trace", "plugins", "profile", "*",
        "*.xplane.pb")), key=os.path.getmtime)
    return found[-1] if found else None
