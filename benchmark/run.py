#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Three phases in this order, then a check:

1. set-up (``setup_s``): the stream's table from the seed, the job and its
   ``ClusterRunner``, one warm epoch with a completed checkpoint,
   ``prewarm_recovery()``, one failover drill by the cell's own kill
   recipe, one more completed epoch;
2. a steady window of ``--seconds`` with no failure: rate and latency come
   from commit stamps that fall inside it, and from nothing else;
3. the kill phase, fixed work outside the window: the cell's kill recipe,
   ``recover()`` timed to ``block_until_ready`` on the carry
   (``time_to_resume_ms``), the rest of that epoch, one more epoch, drain;
4. the whole committed stream against the NumPy fold of the
   configuration's topology, and the count of compilations since set-up.

Before the result, every run prints the window by quarter
(``benchlib/quarters.py``, computed after the check from what the run
holds): each quarter's rate beside the host's spans per block — a
``--trace 1`` run with the harness's own wrappers over the whole window,
any run with the program's recorder as far back as its ring reaches.

The last line of stdout is the result object; its last key, ``checks``,
holds each number compared beside its limit, and the same lines are the
last on stderr. Exit code 0 means the run
reached that line; ``correct`` says whether it may be believed. Without a
TPU (or with fewer chips than the cell asks for) nothing is printed on
stdout and the exit code is 2.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

from benchlib import job, pacing, quarters, trace_reduce
from benchlib.byname import module_at
from benchlib.spans import Spans

#: seconds of the window's end that a ``--trace 1`` run reduces, and how
#: long before them the profiler starts (starting it stalls the host)
TRACE_STEADY_S = 3.0
TRACE_SETTLE_S = 2.0
#: JAX's event for a program built or fetched because it was not in the
#: process: none may fire after set-up
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    def __init__(self, bench_path: str, workload: str):
        self.bench = load_json(bench_path)
        self.root = os.path.dirname(os.path.abspath(bench_path))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in {bench_path}; "
                             f"there are {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        config = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.cfg = load_json(os.path.join(self.root, config["file"]))
        self.bench_dir = os.path.join(self.root, self.bench["paths"][0])
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        #: the plain reference of the configuration's topology
        self.reference = module_at(job.topology_file(self.cfg,
                                                     "reference.py"))

    def metrics(self, group: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


class Run:
    """Everything the metric readers may look at (``readers/*.py`` take
    one of these and return a number, or None when there is nothing for
    them to read)."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.cfg = cell.cfg
        self.traffic = cell.traffic
        self.reference = cell.reference
        #: epoch -> commit stamp (``time.monotonic()``), from the client's
        #: side of ``TransactionLog.committer``
        self.stamps: Dict[int, float] = {}
        #: epoch -> row arrays committed under it
        self.committed: Dict[int, List[np.ndarray]] = {}
        self.window = (0.0, 0.0)
        self.schedule: Optional[pacing.Schedule] = None
        #: (epoch, how late it started against its due instant)
        self.lateness_ms: List[tuple] = []
        self.first_window_epoch = 0
        self.last_window_epoch = -1
        self.report = None
        self.recover_wall = (0.0, 0.0)
        self.spans: Optional[Spans] = None
        self.fence_tail_ms: List[float] = []
        self.events: Optional[trace_reduce.Events] = None
        self.devices: List[Any] = []
        self.device_kind = ""
        #: ``bytes_in_use`` of each chip when the window closed
        self.held_bytes: List[int] = []

    @property
    def records_per_epoch(self) -> int:
        c = self.cfg
        return c["parallelism"] * c["batch"] * c["steps_per_epoch"]

    def trace_window(self, span: str):
        """``(lo, hi)`` of a host span on the profiler's clock."""
        return (trace_reduce.span_window(self.events, span)
                if self.events is not None else None)


# --- metrics: one reader file each ------------------------------------------


def read_metric(name: str, run: Run) -> Optional[float]:
    """``readers/<name>.py`` holds ``read(run)`` for the metric ``name``,
    end-to-end or per-layer; a later PR adds a metric by adding its file.
    One quantity split by the end-to-end metric its cells report
    (``<metric>.mesh``) is read by ``readers/<metric>.py`` unless the
    split name has a file of its own. A reader that finds nothing to
    read returns None."""
    path = os.path.join(HERE, "readers", name + ".py")
    if not os.path.isfile(path):
        path = os.path.join(HERE, "readers", name.split(".")[0] + ".py")
    return module_at(path).read(run)


# --- the run -----------------------------------------------------------------


class CompileCounter:
    """Counts JAX's compile events: programs built or fetched because
    they were not in the process, and how many of those the persistent
    cache served."""

    def __init__(self, jax):
        self.programs = self.hits = self.misses = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.programs += 1
            self.names.append(str(kw.get("fun_name")))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Harness:
    """The phases of one run, over one runner."""

    def __init__(self, jax, run: Run, runner, stream, trace: bool):
        self.jax, self.run, self.runner, self.stream = jax, run, runner, stream
        self.ex = runner.executor
        self.trace = trace
        self.cfg, self.kill = run.cfg, run.cfg["kill"]
        # a mix that names a rate is open loop; one that does not, closed
        self.paced = "rate_records_per_s" in run.traffic
        (txn,) = runner.txn_logs.values()
        txn.committer = self._committed
        if trace:
            self._install_spans()

    def _committed(self, epoch: int, rows: np.ndarray) -> None:
        self.run.stamps.setdefault(epoch, time.monotonic())
        self.run.committed.setdefault(epoch, []).append(rows)

    def _install_spans(self) -> None:
        spans = self.run.spans = Spans()
        ex, runner = self.ex, self.runner
        ex._pull_feeds = spans.wrap("feed_pull", ex._pull_feeds)
        ex.on_block_outputs = spans.wrap("sink_absorb", ex.on_block_outputs)
        runner.run_epoch = spans.wrap("epoch", runner.run_epoch)
        # the fence, where the runner has these (private) methods: without
        # them its idle gaps are charged to "epoch"
        for span, attr in (("fence_tail", "_run_fence_tail_inline"),
                           ("fence_begin", "_begin_fence_tail"),
                           ("fence_join", "_join_fence_tail")):
            if hasattr(runner, attr):
                setattr(runner, attr, spans.wrap(span, getattr(runner, attr)))

    def sync(self) -> None:
        self.jax.block_until_ready(self.ex.carry)

    def wait_due(self) -> None:
        """Open loop: the current epoch runs when its last record has
        been sent, or at once if that instant has passed."""
        run = self.run
        if run.schedule is None:
            return
        due = run.schedule.due(self.ex.epoch_id)
        now = time.monotonic()
        if now < due:
            if self.trace:            # so that this idle time has a name
                with run.spans.span("wait_due"):
                    time.sleep(due - now)
            else:
                time.sleep(due - now)
            now = time.monotonic()
        run.lateness_ms.append((self.ex.epoch_id, (now - due) * 1e3))

    def offer_epoch(self, complete: bool = True) -> None:
        self.wait_due()
        self.runner.run_epoch(complete_checkpoint=complete)
        phases = self.runner.last_fence_phases
        if self.trace and "fence-tail" in phases:
            self.run.fence_tail_ms.append(phases["fence-tail"])

    def into_kill_position(self) -> None:
        """The cell's kill recipe up to the kill: epochs whose
        checkpoints stay pending, then single steps into the next."""
        for _ in range(self.kill["uncompleted_epochs"]):
            self.offer_epoch(complete=False)
        if self.kill["steps_into_epoch"]:
            self.wait_due()
            for _ in range(self.kill["steps_into_epoch"]):
                self.runner.step()

    def finish_kill_epoch(self) -> None:
        if self.kill["steps_into_epoch"]:
            self.runner.run_epoch(complete_checkpoint=True)

    # 1 ------------------------------------------------------------------------

    def set_up(self, counter: CompileCounter) -> float:
        """Warm every program the window and the kill phase will use;
        returns ``setup_s``."""
        runner = self.runner
        stages = [("imports, table, job, runner", time.monotonic())]
        runner.run_epoch(complete_checkpoint=True)
        self.sync()
        stages.append(("warm epoch", time.monotonic()))
        runner.prewarm_recovery()
        stages.append(("prewarm", time.monotonic()))
        self.into_kill_position()
        stages.append(("into drill position", time.monotonic()))
        runner.drain_fence()    # as inject_failure does before a real kill
        runner.failover_drill(job_flats(runner, self.cfg["drill"]["victims"]))
        self.finish_kill_epoch()
        runner.run_epoch(complete_checkpoint=True)
        runner.drain_fence()
        self.sync()
        stages.append(("drill and the epochs after it", time.monotonic()))
        say(f"set-up: {self.ex.epoch_id} epochs, {counter.programs} programs "
            f"built or fetched ({counter.hits} from the compile cache, "
            f"{counter.misses} compiled); " + ", ".join(
                f"{name} {t - t_prev:.2f} s" for (name, t), t_prev in zip(
                    stages, [T_PROCESS_START] + [t for _, t in stages])))
        counter.programs = 0
        counter.names.clear()
        self.run.fence_tail_ms.clear()
        return time.monotonic() - T_PROCESS_START

    # 2 ------------------------------------------------------------------------

    def steady_window(self, seconds: float, trace_dir: str) -> bool:
        """``seconds`` of epochs with no failure. A traced run starts the
        profiler ``TRACE_STEADY_S + TRACE_SETTLE_S`` before the end and
        runs on until its ``steady`` span holds two epochs. Returns
        whether the profiler is running."""
        run, ex = self.run, self.ex
        t0 = time.monotonic()
        t1 = t0 + seconds
        run.window = (t0, t1)
        first_epoch = ex.epoch_id
        n_epochs = None
        if self.paced:
            run.schedule = pacing.Schedule(
                t0, run.traffic["rate_records_per_s"], run.records_per_epoch,
                self.cfg["steps_per_epoch"], first_epoch)
            n_epochs = int(seconds / run.schedule.period)
        tracing, steady, steady_from = False, None, 0
        while True:
            now = time.monotonic()
            done = (ex.epoch_id - first_epoch >= n_epochs if self.paced
                    else now >= t1)
            short = self.trace and (steady is None
                                    or ex.epoch_id - steady_from < 2)
            if done and not short:
                break
            if self.trace and not tracing and (
                    now >= t1 - TRACE_STEADY_S - TRACE_SETTLE_S):
                opts = self.jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                self.jax.profiler.start_trace(trace_dir,
                                              profiler_options=opts)
                tracing = True
            if tracing and steady is None and now >= t1 - TRACE_STEADY_S:
                steady = run.spans.span("steady")
                steady.__enter__()
                steady_from = ex.epoch_id
            self.offer_epoch()
        run.last_window_epoch = ex.epoch_id - 1
        run.first_window_epoch = first_epoch
        run.held_bytes = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                          for d in run.devices]
        if steady is not None:
            steady.__exit__(None, None, None)
        return tracing

    # 3 ------------------------------------------------------------------------

    def kill_phase(self) -> None:
        """Fixed work outside the window: the kill recipe, the kill,
        ``recover()`` timed to ``block_until_ready`` on the carry, the
        rest of that epoch, one more epoch, drain."""
        run, runner = self.run, self.runner
        self.into_kill_position()
        runner.inject_failure(job_flats(runner, self.kill["victims"]))
        self.sync()
        span = run.spans.span("recover") if self.trace else None
        if span is not None:
            span.__enter__()
        r0 = time.monotonic()
        run.report = runner.recover()
        self.sync()
        run.recover_wall = (r0, time.monotonic())
        if span is not None:
            span.__exit__(None, None, None)
        self.finish_kill_epoch()
        self.offer_epoch()
        runner.drain_fence()
        self.sync()
        if self.paced:
            late = [ms for e, ms in run.lateness_ms
                    if e <= run.last_window_epoch]
            after = [ms for e, ms in run.lateness_ms
                     if e > run.last_window_epoch]
            say(f"generator: {len(late)} epochs offered in the window, one "
                f"every {run.schedule.period * 1e3:.2f} ms; start lateness "
                f"median {statistics.median(late):.3f} ms, max "
                f"{max(late):.3f} ms (in the kill phase, which does not stop "
                f"the schedule: max {max(after):.1f} ms)")

    # 4 ------------------------------------------------------------------------

    def check(self, counter: CompileCounter, control: Optional[str]):
        """Hold the whole committed stream to the fold; print each number
        compared beside its limit. Returns (correct, epochs offered,
        epochs whose commit is missing or wrong, the numbers compared:
        as the result's ``checks`` and as the lines printed)."""
        run, cfg, ex, ref = self.run, self.cfg, self.ex, self.run.reference
        # before the check itself asks the program for anything new
        compiled, names = counter.programs, list(counter.names)
        c0 = time.monotonic()
        spe = cfg["steps_per_epoch"]
        epochs_offered, steps_run = ex.epoch_id, self.runner.global_step
        table = (cfg, self.stream.keys, self.stream.vals, epochs_offered)
        want = ref.expected(*table)
        committed = run.committed
        if control is not None:
            sound = ref.check(run.committed, want, cfg, epochs_offered)
            say(f"check program (before the control takes its place): "
                f"mismatched_rows={sound[0]} limit=0")
            committed = ref.committed_of(
                ref.expected(*table, control=control,
                             control_step=steps_run // 2),
                cfg, epochs_offered)
        mismatched, bad_epochs, compared = ref.check(
            committed, want, cfg, epochs_offered)
        overflow = ex.check_overflow()
        replayed = run.report.steps_replayed
        checks = [
            ("mismatched_rows", mismatched, "limit", 0, mismatched == 0),
            ("steps_replayed", replayed, "least", 1, replayed >= 1),
            ("overflow_messages", len(overflow), "limit", 0, not overflow),
            ("compilations_after_setup", compiled, "limit", 0,
             compiled == 0),
            ("steps_run", steps_run, "exactly", epochs_offered * spe,
             steps_run == epochs_offered * spe)]
        who = f"control {control}" if control else "program"
        in_window = run.last_window_epoch - run.first_window_epoch + 1
        say(f"check {who}: {compared} committed rows over {epochs_offered} "
            f"epochs ({in_window} in the window) against the NumPy fold, in "
            f"{time.monotonic() - c0:.3f} s")
        lines = [f"check {name}={got} {kind}={limit} "
                 f"{'ok' if ok else 'FAILED'}"
                 for name, got, kind, limit, ok in checks]
        for line in lines:
            say(line)
        if names:
            say(f"built or fetched after set-up: {names}")
        numbers = {name: {"value": got, kind: limit, "ok": ok}
                   for name, got, kind, limit, ok in checks}
        return (all(ok for *_, ok in checks), epochs_offered, bad_epochs,
                (numbers, lines))


def job_flats(runner, victims) -> List[int]:
    """``[[vertex, subtask], ...]`` of a kill recipe -> flat subtask ids."""
    return [runner.job.subtask_base(v) + s for v, s in victims]


def traced_metrics(run: Run, trace_dir: str, device: dict, result: dict
                   ) -> None:
    """Reduce the profile: ``busy_s``/``window_s`` into ``device``, the
    breakdown and the cell's per-layer metrics into ``result``."""
    l0 = time.monotonic()
    run.events = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    say(f"trace: read in {time.monotonic() - l0:.2f} s")
    lo_hi = run.trace_window("steady")
    if lo_hi is not None and run.events.ops:
        busy = trace_reduce.device_busy_s(run.events, *lo_hi)
        device["busy_s"] = sum(busy.values()) / len(busy)
        device["window_s"] = (lo_hi[1] - lo_hi[0]) / 1e9
        dev0 = min(run.events.ops)
        result["breakdown"] = {
            "device_ops": trace_reduce.top(trace_reduce.self_times(
                run.events.ops[dev0], *lo_hi)),
            "idle_gaps": trace_reduce.top(trace_reduce.idle_by_span(
                run.events, dev0, *lo_hi))}
    for m in run.cell.metrics("per_layer"):
        value = read_metric(m["name"], run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}


def untraced_metrics(run: Run, setup_s: float, result: dict) -> None:
    """The cell's end-to-end metrics; one with nothing to read makes the
    run not correct."""
    for m in run.cell.metrics("end_to_end"):
        value = (setup_s if m["name"] == "setup_s"
                 else read_metric(m["name"], run))
        if value is None:
            say(f"check {m['name']}: nothing to read FAILED")
            result["correct"] = False
            continue
        result["metrics"][m["name"]] = {"value": float(value),
                                        "unit": m["unit"]}


def say_by_quarter(run: Run) -> None:
    """The window by quarter, for the reader; it feeds no metric, so a
    fault in it may not cost a sound run its result."""
    try:
        parts = quarters.rounded(quarters.by_quarter(run))
    except Exception as e:      # noqa: BLE001 - a diagnostic, not a check
        say(f"by quarter not computed: {e!r}")
        return
    say(quarters.LINE + json.dumps(parts))


def run_cell(bench_path: str, workload: str, seed: int, seconds: float,
             trace: bool, control: Optional[str] = None,
             check_chip: bool = True, sabotage: Optional[Callable] = None,
             rate: Optional[float] = None) -> Optional[dict]:
    """Run one cell and return its result object (None: no chip, nothing
    run). ``control`` names a perturbed reference to put in the program's
    place for the comparison (the ``CONTROLS`` of the topology's
    reference). ``check_chip=False`` and ``sabotage(runner)`` are for
    ``benchmark/tests``: a CPU rehearsal, and a timed path broken
    underneath. ``rate`` stands in for an open-loop mix's rate, for the
    sweep that finds it."""
    cell = Cell(bench_path, workload)
    if control is not None and control not in cell.reference.CONTROLS:
        raise SystemExit(f"--control {control!r}: this cell's reference has "
                         f"{cell.reference.CONTROLS}")
    if rate is not None:
        cell.traffic["rate_records_per_s"] = rate

    from clonos_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    if check_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"benchmark: {workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} x {devs[0].platform} "
              f"({devs[0].device_kind}); nothing was run", file=sys.stderr)
        return None
    used = devs[:cell.chips]
    say(f"device: {used[0].device_kind} x{len(used)} (platform "
        f"{used[0].platform}); compile cache {cache_dir}")
    counter = CompileCounter(jax)

    out_root = os.path.join(ROOT, "benchmark_out")
    os.makedirs(out_root, exist_ok=True)
    work = tempfile.mkdtemp(prefix="run-", dir=out_root)
    trace_dir = os.path.join(work, "trace")
    try:
        run = Run(cell)
        run.devices, run.device_kind = used, used[0].device_kind
        stream = job.make_stream(cell.cfg, cell.traffic, seed)
        runner = job.make_runner(cell.cfg, stream, seed,
                                 os.path.join(work, "ckpt"), cell.chips)
        harness = Harness(jax, run, runner, stream, trace)
        if sabotage is not None:
            sabotage(runner)
        setup_s = harness.set_up(counter)
        tracing = harness.steady_window(seconds, trace_dir)
        harness.kill_phase()
        if tracing:
            jax.profiler.stop_trace()
        correct, attempted, bad_epochs, checks = harness.check(counter,
                                                               control)
        device = {"platform": used[0].platform, "kind": used[0].device_kind,
                  "count": len(used),
                  "memory_peak_bytes": max(
                      int((d.memory_stats() or {}).get(
                          "peak_bytes_in_use", 0)) for d in used)}
        result: Dict[str, Any] = {
            "correct": bool(correct), "attempted": int(attempted),
            "failed": len(bad_epochs), "metrics": {}, "device": device}
        if trace:
            traced_metrics(run, trace_dir, device, result)
        else:
            untraced_metrics(run, setup_s, result)
        say_by_quarter(run)
        result["checks"], check_lines = checks      # last in the line
        say(json.dumps(result))
        print("\n".join(check_lines), file=sys.stderr, flush=True)
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control",
                    help="put a perturbed reference in the program's place "
                         "for the comparison (a name from the CONTROLS of "
                         "the topology's reference.py): the result must be "
                         "correct=false")
    ap.add_argument("--rate", type=float,
                    help="records/s in place of an open-loop mix's own: for "
                         "the sweep that finds the rate to write into its "
                         "file")
    args = ap.parse_args(argv)
    result = run_cell(os.path.join(ROOT, "BENCHMARK.json"), args.workload,
                      args.seed, args.seconds, bool(args.trace),
                      control=args.control, rate=args.rate)
    return 2 if result is None else 0


if __name__ == "__main__":
    sys.exit(main())
