"""Device: of the first chip's idle time inside the traced steady span,
the percentage that falls under no leaf span of the program's driving
thread (a parent's remainder counts as unattributed). Prints, before the
result line, that idle time by innermost program span — the table the
result's ``idle_gaps`` would be if it were taken from inside — the inside
spans beside the outside wrappers of the same layers, and how far the
recorder's ``epoch`` spans lie from their ``clonos:epoch`` annotations in
the same trace."""

import os

from benchlib import program_spans, trace_reduce

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark_out")


def read(run):
    by_leaf = program_spans.idle_by_program_span(run, only_leaves=True)
    if by_leaf is None:
        return None
    by_span = program_spans.idle_by_program_span(run, only_leaves=False)
    total = sum(by_span.values())
    print("idle by program span (s, share): " + ", ".join(
        f"{name} {s:.4f} {100 * s / total:.1f}%"
        for name, s in trace_reduce.top(by_span, n=24)), flush=True)
    print(f"inside against outside (ms): "
          f"{program_spans.inside_against_outside(run)}", flush=True)
    xplane = program_spans.newest_xplane(OUT)
    if xplane is not None:
        print(f"clock check, program epoch spans against clonos:epoch "
              f"annotations: {program_spans.clock_check(run, xplane)}",
              flush=True)
    return program_spans.idle_unattributed_pct(by_leaf)
