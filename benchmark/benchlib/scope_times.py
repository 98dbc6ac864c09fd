"""Device time of one program's launches by the program's own named scopes.

The device programs carry ``jax.named_scope`` names — ``vertex/<name>``,
``exchange``, ``causal-log``, ``inflight-ring``, ``hist`` and the parts
beneath them (:data:`PARTS`; the program's list is
``clonos_tpu/obs/scopes.py``, this file keeps a copy and imports nothing
of the program). XLA writes the scope path into every instruction's
``op_name`` and the profiler keeps it with the instruction: in an
``.xplane.pb`` each event of a device's "XLA Ops" line points at an
``XEventMetadata`` whose stats hold it. ``jax.profiler.ProfileData`` (what
``trace_reduce.load`` reads with) hands out an event's own stats only, not
its metadata's, so this file reads the device plane itself: a protobuf
wire reader of the few messages ``xplane.proto`` has, nothing more.

A trace is reduced to a :class:`Device` first — ``ops`` as
``(name, start_ns, dur_ns, op_name)``, ``modules`` as ``(name, start_ns,
dur_ns)``, on the clock of ``trace_reduce.Events`` — and everything after
that is arithmetic on plain tuples (``benchmark/tests``).

Time is **self time** with ``trace_reduce.self_times``' semantics (a
``while`` is charged only what its body does not cover) of the events that
lie inside launches of one program on the "XLA Modules" line — by its
name, ``jit_run_block``, not "the program with most device time" — that
lie wholly inside a window; per block = over the number of such launches.
A fusion is one instruction with one ``op_name`` (its root's): where the
compiler fused across a scope's edge the whole fusion is charged to the
root's scope.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import os
import re
import sys
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchlib import trace_reduce

Op = Tuple[str, float, float, str]        # name, start_ns, dur_ns, op_name
Scope = Tuple[str, ...]                   # () = under no scope

VERTEX = "vertex"
EXCHANGE = "exchange"
CAUSAL_LOG = "causal-log"
INFLIGHT_RING = "inflight-ring"
HIST = "hist"
#: layer -> the parts directly beneath it (beneath ``vertex/<name>`` for
#: the operators); ``hist`` is a leaf under any of them
PARTS: Dict[str, Tuple[str, ...]] = {
    VERTEX: ("lookup", "place", "segsum", "emit", "readback", "compact"),
    EXCHANGE: ("rank", "place", "plan"),
    CAUSAL_LOG: ("rows", "own", "replicas"),
    INFLIGHT_RING: (),
}
BLOCK_PROGRAM = "jit_run_block"
#: the stat of an instruction's metadata that carries its ``op_name``
#: (libtpu 0.0.34; ``trace_reduce.describe`` does not show it: it prints an
#: event's own stats)
SCOPE_STAT = "tf_op"


# --- the file ----------------------------------------------------------------


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of the message in ``buf[lo:hi]``: a
    varint's value, ``(start, end)`` of a length-delimited field, the
    raw bytes of a fixed one."""
    while lo < hi:
        key, lo = _varint(buf, lo)
        wire = key & 7
        if wire == 0:
            value, lo = _varint(buf, lo)
        elif wire == 2:
            n, lo = _varint(buf, lo)
            value, lo = (lo, lo + n), lo + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, lo = buf[lo:lo + n], lo + n
        else:
            raise ValueError(f"wire type {wire} at byte {lo}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stats(buf: bytes, spans, stat_names: Dict[int, str]) -> Dict[str, str]:
    """The string-valued stats among ``XStat`` messages, by name (a
    ``ref_value`` names another stat's metadata: its name is the value)."""
    out = {}
    for span in spans:
        mid, value = 0, None
        for f, v in _fields(buf, *span):
            if f == 1:
                mid = v
            elif f == 5:
                value = _text(buf, v)
            elif f == 7:
                value = stat_names.get(v)
        if value is not None:
            out[stat_names.get(mid, str(mid))] = value
    return out


@dataclasses.dataclass
class Device:
    """One device plane: its operations with the ``op_name`` each
    instruction carries (``""`` where it carries none), its program
    launches, and which stat the ``op_name`` came from (None: the plane
    has no such stat, so nothing can be said about scopes)."""
    ops: List[Op]
    modules: List[trace_reduce.Event]
    stat: Optional[str]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Device":
        return cls([tuple(e) for e in d["ops"]],
                   [tuple(e) for e in d["modules"]], d["stat"])


def device_planes(buf: bytes) -> Dict[int, Tuple[int, int]]:
    """Device number -> span of its ``XPlane`` in an ``XSpace``."""
    out = {}
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        for pf, v in _fields(buf, *span):
            if pf == 2:
                m = trace_reduce.DEVICE_PLANE.match(_text(buf, v))
                if m:
                    out[int(m.group(1))] = span
                break
    return out


def read_plane(buf: bytes, span: Tuple[int, int]) -> Device:
    lines, event_meta, stat_names = [], {}, {}
    for f, v in _fields(buf, *span):
        if f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.update([_map_entry(buf, v)])
        elif f == 5:
            key, ms = _map_entry(buf, v)
            stat_names[key] = next(
                (_text(buf, x) for g, x in _fields(buf, *ms) if g == 2), "")
    names: Dict[int, str] = {}
    stats: Dict[int, Dict[str, str]] = {}
    for mid, ms in event_meta.items():
        spans = []
        for f, v in _fields(buf, *ms):
            if f == 2:
                names[mid] = _text(buf, v)
            elif f == 5:
                spans.append(v)
        stats[mid] = _stats(buf, spans, stat_names)
    # the stat reads ``<op_name>:<op type>``, the type empty
    op_names = {mid: st[SCOPE_STAT].rpartition(":")[0] or st[SCOPE_STAT]
                for mid, st in stats.items() if SCOPE_STAT in st}
    ops: List[Op] = []
    modules: List[trace_reduce.Event] = []
    for span_l in lines:
        name, t0, events = "", 0, []
        for f, v in _fields(buf, *span_l):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        if name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        for at, end in events:
            # an XEvent's three varints, read in place: the trace holds
            # one event an operation executed, 10^6 and more
            mid = offset = dur = 0
            while at < end:
                key = buf[at]
                at += 1
                if key & 7 == 2:                     # its own stats: skipped
                    n, at = _varint(buf, at)
                    at += n
                    continue
                value = shift = 0
                while True:
                    b = buf[at]
                    at += 1
                    value |= (b & 0x7F) << shift
                    if b < 0x80:
                        break
                    shift += 7
                if key == 8:
                    mid = value
                elif key == 16:
                    offset = value
                elif key == 24:
                    dur = value
            start = t0 + offset / 1e3
            if name == trace_reduce.MODULES_LINE:
                modules.append((names.get(mid, ""), start, dur / 1e3))
            else:
                ops.append((names.get(mid, ""), start, dur / 1e3,
                            op_names.get(mid, "")))
    return Device(ops, modules, SCOPE_STAT if op_names else None)


def load(path: str, device: Optional[int] = None) -> Optional[Device]:
    """The plane of ``device`` (the lowest, if None) of an ``.xplane.pb``,
    or a ``.json`` / ``.json.gz`` written by :meth:`Device.to_json` (the
    form the recorded test trace is kept in); None when the trace holds
    no such plane."""
    if path.endswith(".json") or path.endswith(".json.gz"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return Device.from_json(json.load(f))
    with open(path, "rb") as f:
        buf = f.read()
    planes = device_planes(buf)
    if not planes:
        return None
    span = planes.get(min(planes) if device is None else device)
    return read_plane(buf, span) if span else None


# --- from an op_name to a scope ----------------------------------------------

_WRAPPED = re.compile(r"^[\w\-.]+\((.*)\)$")
_FUNCTION = re.compile(r"^p?jit\(")


def scope_of(op_name: str) -> Scope:
    """The vocabulary's components of an ``op_name`` path, in order:
    ``jit(run_block)/jit(main)/vertex/count/place/hist/jit(_hist)/dot`` ->
    ``("vertex", "count", "place", "hist")``. ``jit(f)`` names a
    function and is dropped; another transform wraps the component it
    was entered under (``vmap(hist)``) and is taken off; primitives,
    loops' ``while/body`` and any other name are skipped. A part counts
    directly beneath its layer only, ``hist`` ends a path, and a path
    starts at a layer: ``()`` is under no scope."""
    out: List[str] = []
    comps = op_name.split("/")
    i = 0
    while i < len(comps):
        c = comps[i]
        i += 1
        while not _FUNCTION.match(c):
            m = _WRAPPED.match(c)
            if not m:
                break
            c = m.group(1)
        if not out:
            if c == VERTEX and i < len(comps):
                out = [VERTEX, comps[i]]
                i += 1
            elif c in PARTS or c == HIST:
                out = [c]
        elif c == HIST:
            out.append(c)
        elif (c in PARTS.get(out[0], ())
              and len(out) == (2 if out[0] == VERTEX else 1)):
            out.append(c)
        if out and out[-1] == HIST:
            break
    return tuple(out)


# --- the reduction -----------------------------------------------------------


def launches(modules: Sequence[trace_reduce.Event], program: str,
             lo: float, hi: float) -> List[trace_reduce.Interval]:
    """The launches of ``program`` that lie wholly inside ``[lo, hi]``
    (a module's name is the program's, then ``(<id>)``)."""
    return sorted((s, s + d) for _, s, d in trace_reduce.matching(
        modules, "^" + re.escape(program) + r"(\W|$)", lo, hi))


def inside(ops: Sequence[Op], spans: Sequence[trace_reduce.Interval]
           ) -> List[Op]:
    """The operations that lie wholly inside one of ``spans`` (disjoint,
    sorted)."""
    out, at = [], 0
    for op in sorted(ops, key=lambda e: e[1]):
        while at < len(spans) and spans[at][1] <= op[1]:
            at += 1
        if (at < len(spans) and op[1] >= spans[at][0]
                and op[1] + op[2] <= spans[at][1]):
            out.append(op)
    return out


@dataclasses.dataclass
class ScopeTimes:
    """Self time of one program's launches, seconds: by scope (``()``:
    under none) and, for what is under none, by operation — its short
    name and, where it has one, its ``op_name`` (a copy of a parameter
    the compiler inserted carries the parameter's)."""
    launches: int
    by_scope: Dict[Scope, float]
    unscoped_ops: Dict[str, float]

    @property
    def total_s(self) -> float:
        return sum(self.by_scope.values())

    def under(self, *prefix: str) -> float:
        """Seconds under ``prefix`` and everything beneath it."""
        return sum(s for k, s in self.by_scope.items()
                   if k[:len(prefix)] == prefix and k)

    def leaf(self, leaf: str) -> float:
        """Seconds of the scopes that end in ``leaf``, wherever."""
        return sum(s for k, s in self.by_scope.items()
                   if k and k[-1] == leaf)

    def ms_per_block(self, seconds: float) -> float:
        return 1e3 * seconds / self.launches


def reduce(dev: Device, lo: float, hi: float,
           program: str = BLOCK_PROGRAM) -> Optional[ScopeTimes]:
    """Self time by scope of the launches of ``program`` wholly inside
    ``[lo, hi]``; None when there is none, or the plane names no
    ``op_name`` stat."""
    spans = launches(dev.modules, program, lo, hi)
    if not spans or dev.stat is None:
        return None
    ops = inside(dev.ops, spans)
    # self time per event: trace_reduce.self_times by an event's index
    own = trace_reduce.self_times(
        [(i, s, d) for i, (_, s, d, _) in enumerate(ops)], lo, hi)
    by_scope: Dict[Scope, float] = {}
    unscoped: Dict[str, float] = {}
    paths: Dict[str, Scope] = {}
    for i, seconds in own.items():
        name, _, _, op_name = ops[i]
        scope = paths.get(op_name)
        if scope is None:
            scope = paths[op_name] = scope_of(op_name)
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
        if not scope:
            label = trace_reduce.short_name(name) + (
                f" <{op_name}>" if op_name else "")
            unscoped[label] = unscoped.get(label, 0.0) + seconds
    return ScopeTimes(len(spans), by_scope, unscoped)


def table(st: ScopeTimes, unscoped_rows: int = 12) -> List[str]:
    """The scope table of a run, one line a scope: ms per block and share
    of the block's self time; a scope with scopes beneath it also has a
    line for the whole of it; then what is under none, by kind of
    operation and the largest operations one by one."""
    total = st.total_s
    rows: Dict[Scope, float] = {}      # a scope with everything beneath it
    for scope, s in st.by_scope.items():
        for n in range(2 if scope[:1] == (VERTEX,) else 1, len(scope) + 1):
            rows[scope[:n]] = rows.get(scope[:n], 0.0) + s

    def line(label: str, s: float) -> str:
        return (f"  {label:<44} {st.ms_per_block(s):10.3f} ms "
                f"{100 * s / total:6.2f} %")

    out = [f"scope table: {st.launches} launches of the block program, "
           f"{st.ms_per_block(total):.3f} ms of self time a block"]
    every_vertex_due = True
    for scope in sorted(rows):
        if scope[0] == VERTEX and every_vertex_due:     # above the first
            out.append(line(VERTEX + " (every vertex)", st.under(VERTEX)))
            every_vertex_due = False
        indent = "  " * (len(scope) - 1)
        out.append(line(indent + "/".join(scope), rows[scope]))
        own = st.by_scope.get(scope, 0.0)
        if 0 < own < rows[scope]:
            out.append(line(indent + "  (itself)", own))
    out.append(line("(under no scope)", st.by_scope.get((), 0.0)))
    kinds: Dict[str, float] = {}       # ``%copy.9 = s32[8]`` is a ``copy``
    for label, s in st.unscoped_ops.items():
        kind = re.match(r"%?([A-Za-z_\-]*)", label).group(1)
        kinds[kind] = kinds.get(kind, 0.0) + s
    largest = lambda d, n: sorted(d.items(), key=lambda kv: -kv[1])[:n]
    if kinds:
        out.append("    by kind of op, ms: " + ", ".join(
            f"{kind} {st.ms_per_block(s):.3f}" for kind, s in largest(kinds, 8)))
    for label, s in largest(st.unscoped_ops, unscoped_rows):
        out.append(line("    " + label, s))
    return out


# --- the run in progress -----------------------------------------------------

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark_out")


def of(run) -> Optional[ScopeTimes]:
    """The block program's scope times inside the traced steady span of
    this run, on the lowest device as every other device reader; read
    once (the readers of one result line share it) from the trace the
    harness still keeps under ``benchmark_out``. None on a run without a
    device plane (a CPU rehearsal), without the stat, or without a
    launch of the block program in the span."""
    if hasattr(run, "_scope_times"):
        return run._scope_times
    run._scope_times = None
    window = run.trace_window("steady")
    if window is None or not run.events.ops:
        return None
    from benchlib import program_spans
    xplane = program_spans.newest_xplane(OUT)
    if xplane is None:
        return None
    t0 = time.monotonic()
    dev = load(xplane, min(run.events.ops))
    if dev is None:
        return None
    run._scope_times = reduce(dev, *window)
    print(f"scopes: the device plane read again in "
          f"{time.monotonic() - t0:.2f} s ({len(dev.ops)} operations, "
          f"op_name under the stat {dev.stat!r})", flush=True)
    return run._scope_times


def ms_per_block(run, *prefix: str, leaf: Optional[str] = None
                 ) -> Optional[float]:
    """Per-block self time, ms, under ``prefix`` (and everything beneath)
    or of the scopes ending in ``leaf``; None with nothing to read."""
    st = of(run)
    if st is None:
        return None
    return st.ms_per_block(st.leaf(leaf) if leaf else st.under(*prefix))


if __name__ == "__main__":
    # (cd benchmark; python3 -m benchlib.scope_times <dir-or-xplane> \
    #     [one-block.json.gz]): the scope table of the trace's ``steady``
    # span (of the whole trace, where it has none), and the recorded trace
    # that benchmark/tests checks the reduction on: the span's second
    # launch of the block program, with its neighbours' edges
    src = sys.argv[1]
    src = trace_reduce.find_xplane(src) if os.path.isdir(src) else src
    window = (src.endswith(".pb") and trace_reduce.span_window(
        trace_reduce.load(src), "steady")) or (float("-inf"), float("inf"))
    dev = load(src)
    print("\n".join(table(reduce(dev, *window))))
    if len(sys.argv) > 2:
        a, b = launches(dev.modules, BLOCK_PROGRAM, *window)[1]
        cut = lambda events: [e for e in events
                              if e[1] + e[2] > a - 1e6 and e[1] < b + 1e6]
        with gzip.open(sys.argv[2], "wt") as f:
            json.dump(Device(cut(dev.ops), cut(dev.modules),
                             dev.stat).to_json(), f)
