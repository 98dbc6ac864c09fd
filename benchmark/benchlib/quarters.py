"""A run's steady window by quarter: the rate of each quarter (the cut of
``pacing.rates_by_part``) beside what the host did in it, per block — so
that a run whose rate sits on two levels shows which spans carry them.

Computed once the window has closed, from what the run already holds;
nothing of it runs inside the window. Two sources, each left out of a
quarter where it has nothing:

- the harness's outside wrappers (``spans.Spans``; a ``--trace 1`` run
  has them over the whole window, not only under the profiler);
- the program's own spans (``program_spans``: the flight recorder's
  ring, in every run; a quarter the ring has evicted says ``evicted``
  and prints no span, never a zero).

A span counts into the quarter its start falls in; ``ms_per_block`` is
the quarter's total of a name over the blocks of the epochs committed in
it, so a span that runs once an epoch reads as its share of a block.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchlib import pacing, program_spans, trace_reduce

#: harness spans that are no layer's (the traced tail, the kill phase)
NOT_A_LAYER = ("steady", "recover")
DRAW = "block.causal-inputs"
#: what the line starts with; the rest of it is one JSON list
LINE = "by quarter: "


def rounded(x, digits: int = 4):
    """The line is for reading: every float to ``digits`` places."""
    if isinstance(x, float):
        return round(x, digits)
    if isinstance(x, dict):
        return {k: rounded(v, digits) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [rounded(v, digits) for v in x]
    return x


# --- the reduction -----------------------------------------------------------


def _per_block(named: Sequence[Tuple[str, float, float]], lo: float,
               hi: float, blocks: int) -> Dict[str, float]:
    """``(name, start, seconds)`` -> ms a block, of the spans that start
    inside ``[lo, hi)``."""
    total: Dict[str, float] = {}
    for name, start, dur in named:
        if lo <= start < hi:
            total[name] = total.get(name, 0.0) + dur
    return {n: s * 1e3 / blocks for n, s in sorted(total.items())}


def _draws_beside_others(prog: program_spans.Program, lo: float, hi: float
                         ) -> Optional[dict]:
    """The driving thread's draws (``block.causal-inputs``) that start in
    ``[lo, hi)``, apart by whether any span of another thread (the fence
    worker's) was under way during them: mean ms and count of each, and
    the mean ms of a draw that lay under the other thread."""
    tid = prog.main_thread()
    if tid is None:
        return None
    others = trace_reduce.union(
        (s["mono"], s["mono"] + s["dur"]) for s in prog.spans
        if s["tid"] != tid and s["mono"] + s["dur"] > lo and s["mono"] < hi)
    clear, beside, under = [], [], 0.0
    for s in prog.spans:
        if s["name"] == DRAW and s["tid"] == tid and lo <= s["mono"] < hi:
            c = trace_reduce.total(trace_reduce.clip(
                others, s["mono"], s["mono"] + s["dur"]))
            (beside if c > 0 else clear).append(s["dur"] * 1e3)
            under += c
    if not clear and not beside:
        return None
    return {"alone": {"n": len(clear), "mean_ms":
                      sum(clear) / len(clear) if clear else None},
            "beside_another_thread": {
                "n": len(beside), "mean_ms":
                sum(beside) / len(beside) if beside else None,
                "mean_overlap_ms": under * 1e3 / len(beside)
                if beside else None}}


def by_quarter(run, parts: int = 4) -> List[dict]:
    """One entry a quarter of the window; empty where
    ``pacing.rates_by_part`` is (too few commits inside)."""
    cuts = pacing.parts_of(run.stamps, *run.window, parts)
    cfg = run.cfg
    blocks_per_epoch = max(1, cfg["steps_per_epoch"] // cfg["block_steps"])
    prog = program_spans.of(run)
    out = []
    for q, (ea, eb) in enumerate(cuts, 1):
        lo, hi = run.stamps[ea], run.stamps[eb]
        blocks = (eb - ea) * blocks_per_epoch
        entry: dict = {
            "quarter": q, "epochs": eb - ea, "blocks": blocks,
            "seconds": hi - lo,
            "records_per_s": (eb - ea) * run.records_per_epoch / (hi - lo)}
        if run.spans is not None:
            entry["harness_ms_per_block"] = _per_block(
                [(n, a, b - a) for n, iv in run.spans.spans.items()
                 if n not in NOT_A_LAYER for a, b in iv], lo, hi, blocks)
        if not prog.spans:
            entry["program"] = "no recorder"
        elif not prog.intact(lo):
            entry["program"] = "evicted"
        else:
            entry["program"] = "whole"
            entry["program_ms_per_block"] = _per_block(
                [(s["name"], s["mono"], s["dur"]) for s in prog.spans],
                lo, hi, blocks)
            draws = _draws_beside_others(prog, lo, hi)
            if draws is not None:
                entry["draws"] = draws
        out.append(entry)
    return out
