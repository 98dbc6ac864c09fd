#!/usr/bin/env python
"""What the tracer costs, off and on (PERF.md section 6 reports the
numbers this prints on the chip's host).

    python tools/span_cost.py micro
        microseconds per span / event / count into the default local
        recorder, into one with a file sink, and under a profiler session

    python tools/span_cost.py run [--file-sink | --profiler] \\
            [--dump records.json.gz] -- <benchmark/run.py arguments>
        one ``--trace 0`` benchmark run in this process with the full
        tracer switched on (``obs.configure(path=...)``: JSON-lines file,
        wire context, ``enabled``) or under a ``jax.profiler`` session
        from the start of the window to the end of the run (keep
        ``--seconds`` short: a session holds every device event), and/or
        the recorder dumped when the run ends

The default recorder has no off switch, so "off" is the parent commit.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def _per_call_us(fn, n: int = 50_000) -> float:
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            fn(i)
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def micro() -> dict:
    import jax
    from clonos_tpu import obs

    def span_of(tr):
        def one(i):
            with tr.span("block.sink.d2h") as sp:
                sp.set(bytes=i)
        return one

    out = {}
    obs.reset()
    tr = obs.get_tracer()
    out["span_us"] = _per_call_us(span_of(tr))
    out["event_us"] = _per_call_us(lambda i: tr.event("compile", n=i))
    out["count_us"] = _per_call_us(lambda i: tr.count("sink.rows", i))
    work = tempfile.mkdtemp(prefix="span-cost-")
    try:
        full = obs.configure("cost", path=os.path.join(work, "t.jsonl"))
        out["span_file_sink_us"] = _per_call_us(span_of(full), n=20_000)
        obs.reset()
        tr = obs.get_tracer()
        with jax.profiler.trace(os.path.join(work, "prof")):
            out["span_profiler_session_us"] = _per_call_us(
                span_of(tr), n=20_000)
    finally:
        obs.reset()
        shutil.rmtree(work, ignore_errors=True)
    # what the ring holds per record (tracemalloc slows the loop, so this
    # is a pass of its own)
    import tracemalloc
    tr = obs.get_tracer()
    tracemalloc.start()
    before = tracemalloc.get_traced_memory()[0]
    one = span_of(tr)
    for i in range(10_000):
        one(i)
    out["ring_bytes_per_record"] = (
        tracemalloc.get_traced_memory()[0] - before) / 10_000
    tracemalloc.stop()
    obs.reset()
    return out


def run(args, bench_argv) -> int:
    import run as harness
    from clonos_tpu import obs

    work = tempfile.mkdtemp(prefix="span-cost-",
                            dir=os.path.join(ROOT, "benchmark_out")
                            if os.path.isdir(os.path.join(
                                ROOT, "benchmark_out")) else None)
    session = None
    try:
        if args.file_sink:
            obs.configure("bench", path=os.path.join(work, "trace.jsonl"))
        if args.profiler:
            import jax
            session = jax.profiler
            steady_window = harness.Harness.steady_window

            def traced_window(self, seconds, trace_dir):
                # the session opens with the window, not with set-up
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(work, "prof"),
                                         profiler_options=opts)
                return steady_window(self, seconds, trace_dir)
            harness.Harness.steady_window = traced_window
        rc = harness.main(bench_argv)
        if session is not None:
            t0 = time.monotonic()
            session.stop_trace()
            print(f"span_cost: profiler session stopped and written in "
                  f"{time.monotonic() - t0:.1f} s", flush=True)
        tr = obs.get_tracer()
        print(f"span_cost: recorder holds {len(tr.records())} records, "
              f"dropped {tr.dropped}, counters {tr.counters()}", flush=True)
        if args.file_sink:
            size = os.path.getsize(os.path.join(work, "trace.jsonl"))
            print(f"span_cost: file sink wrote {size} bytes", flush=True)
        if args.dump:
            keep = ("name", "ph", "mono", "dur", "span", "parent", "tid",
                    "args")
            os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                        exist_ok=True)
            with gzip.open(args.dump, "wt") as f:
                json.dump([{k: r[k] for k in keep if k in r}
                           for r in tr.records()], f, default=str)
        return rc
    finally:
        obs.reset()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("micro")
    r = sub.add_parser("run")
    r.add_argument("--file-sink", action="store_true")
    r.add_argument("--profiler", action="store_true")
    r.add_argument("--dump")
    r.add_argument("bench", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.cmd == "micro":
        print(json.dumps(micro()))
        return 0
    return run(args, [a for a in args.bench if a != "--"])


if __name__ == "__main__":
    sys.exit(main())
