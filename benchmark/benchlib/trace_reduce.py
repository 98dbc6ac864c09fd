"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers: device busy
time as the union of the intervals in which an operation ran, the
operations that took most time, the idle gaps by what the host was doing
in them, kernel and program times. Reads the file with nothing but JAX
(``ProfileData.from_file``); the arithmetic is on plain tuples so that it
can be checked on a small recorded trace (``tests/data``).

A trace is reduced to an :class:`Events` first:

- ``ops[device]``: ``(name, start_ns, dur_ns)`` of every event on the
  device plane's "XLA Ops" line — one per HLO operation executed, a
  ``while`` spanning the operations of its body;
- ``modules[device]``: the same for the "XLA Modules" line — one per
  program launched;
- ``host``: ``(name, start_ns, dur_ns)`` of every ``bench:*`` annotation
  (``spans.PREFIX``) on any host thread.

Device and host events share the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, dur_ns
Interval = Tuple[float, float]

#: prefix of every annotation the benchmark writes into the trace
PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Events:
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        def lines(m):
            return {int(k): [tuple(e) for e in v] for k, v in m.items()}
        return cls(lines(d["ops"]), lines(d["modules"]),
                   [tuple(e) for e in d["host"]])


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Events:
    """Read an ``.xplane.pb`` (or a ``.json`` / ``.json.gz`` written by
    :meth:`Events.to_json`, the form the recorded test trace is kept in)."""
    if path.endswith(".json") or path.endswith(".json.gz"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return Events.from_json(json.load(f))
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name[len(PREFIX):], e.start_ns,
                                     e.duration_ns))
    host.sort(key=lambda e: e[1])
    return Events(ops, modules, host)


# --- intervals ---------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_intervals(events: Sequence[Event], lo: float, hi: float
                   ) -> List[Interval]:
    return clip(union((s, s + d) for _, s, d in events), lo, hi)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``busy`` (disjoint, sorted) inside ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# --- reductions --------------------------------------------------------------


def device_busy_s(ev: Events, lo: float, hi: float) -> Dict[int, float]:
    """Seconds in which an operation ran, per device, inside the window."""
    return {dev: total(busy_intervals(ops, lo, hi)) / 1e9
            for dev, ops in ev.ops.items()}


def self_times(events: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """Seconds per operation name, counting each instant once: an
    operation that spans others (a ``while`` over its body) is charged
    only the time none of them covers."""
    out: Dict[str, float] = {}
    stack: List[List] = []                 # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return out


def short_name(name: str) -> str:
    """An operation's name and result shape without layouts and
    operands: ``%fusion.22 = s32[262144]``."""
    return name.split("{")[0].strip()[:80]


def top(named_seconds: Dict[str, float], n: int = 10
        ) -> List[List]:
    return [[short_name(k), v] for k, v in sorted(
        named_seconds.items(), key=lambda kv: -kv[1])[:n]]


def host_cover(host: Sequence[Event], lo: float, hi: float
               ) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut into segments labelled with the innermost host
    span that covers each (``"(none)"`` where none does)."""
    cuts = {lo, hi}
    for _, s, d in host:
        for t in (s, s + d):
            if lo < t < hi:
                cuts.add(t)
    edges = sorted(cuts)
    spans = [(s, s + d, n) for n, s, d in host if s + d > lo and s < hi]
    out = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        inner, width = "(none)", float("inf")
        for s, e, n in spans:
            if s <= mid < e and e - s < width:
                inner, width = n, e - s
        out.append((a, b, inner))
    return out


def idle_by_span(ev: Events, dev: int, lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds of device idle time inside the window, by the innermost
    host span under way."""
    idle = gaps(busy_intervals(ev.ops[dev], lo, hi), lo, hi)
    out: Dict[str, float] = {}
    gi = 0
    for a, b, name in host_cover(ev.host, lo, hi):
        while gi < len(idle) and idle[gi][1] <= a:
            gi += 1
        j = gi
        while j < len(idle) and idle[j][0] < b:
            ov = min(b, idle[j][1]) - max(a, idle[j][0])
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov / 1e9
            j += 1
    return out


def span_window(ev: Events, name: str, which: int = -1
                ) -> Optional[Interval]:
    """Start and end of the ``which``-th host span called ``name``."""
    found = [(s, s + d) for n, s, d in ev.host if n == name]
    return found[which] if found else None


def spans_inside(ev: Events, name: str, lo: float, hi: float
                 ) -> List[Interval]:
    """The host spans called ``name`` that lie wholly inside ``[lo, hi]``."""
    return [(s, s + d) for n, s, d in ev.host
            if n == name and s >= lo and s + d <= hi]


def matching(events: Sequence[Event], pattern: str, lo: float, hi: float
             ) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0]) and e[1] >= lo
            and e[1] + e[2] <= hi]


def shapes_of(name: str) -> List[Tuple[int, ...]]:
    """Every ``s32[a,b]``-like shape in an operation's name, in order."""
    return [tuple(int(x) for x in m.split(","))
            for m in re.findall(r"\b[a-z]+\d+\[([\d,]+)\]", name)]


# --- a look at a trace by hand ----------------------------------------------


def describe(path: str, limit: int = 12) -> dict:
    """Planes, lines, event counts and the first names of each: what to
    look at before writing code against a new kind of trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            by = {}
            for e in evs:
                by[e.name] = by.get(e.name, 0.0) + e.duration_ns
            sample = []
            for e in evs[:3]:
                try:
                    stats = {k: str(v)[:200] for k, v in e.stats}
                except Exception as err:          # a look, not a metric
                    stats = {"error": repr(err)}
                sample.append({"name": e.name, "start_ns": e.start_ns,
                               "dur_ns": e.duration_ns, "stats": stats})
            lines[line.name] = {
                "events": len(evs),
                "top": [[k[:160], v] for k, v in
                        sorted(by.items(), key=lambda kv: -kv[1])[:limit]],
                "sample": sample}
        out[plane.name] = lines
    return out


def clipped(ev: Events, lo: float, hi: float) -> Events:
    """The events that lie wholly inside ``[lo, hi]``."""
    def cut(events):
        return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]
    return Events({k: cut(v) for k, v in ev.ops.items()},
                  {k: cut(v) for k, v in ev.modules.items()}, cut(ev.host))


if __name__ == "__main__":
    # python3 benchmark/benchlib/trace_reduce.py <dir-or-xplane> \
    #     [description.json [one-epoch-events.json.gz]]
    src = sys.argv[1]
    src = find_xplane(src) if os.path.isdir(src) else src
    text = json.dumps(describe(src), indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
    if len(sys.argv) > 3:
        # the first whole epoch of the steady span: the recorded trace
        # that benchmark/tests checks the reduction on
        ev = load(src)
        epochs = spans_inside(ev, "epoch", *span_window(ev, "steady"))
        with gzip.open(sys.argv[3], "wt") as f:
            json.dump(clipped(ev, epochs[0][0] - 1e6,
                              epochs[0][1] + 1e6).to_json(), f)
