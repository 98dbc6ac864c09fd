"""Kernels: the keyed-histogram kernel's share of its memory roofline, in
percent — the bytes its calls must move (``kernel_cost.hist_bytes``, from
the shapes in each call's trace event) over the chip's HBM bandwidth
(``peaks``), divided by the device time of those calls in the traced
steady span."""

from benchlib import kernel_cost, peaks, trace_reduce


def read(run):
    w = run.trace_window("steady")
    if w is None or not run.events.ops:
        return None
    ops = run.events.ops[min(run.events.ops)]
    calls = trace_reduce.matching(ops, r"hist", *w)
    need = took = 0.0
    for name, _, dur in calls:
        # "%_hist_pallas.4 = (s32[512,8192], s32[512,8192]) custom-call(
        #  s32[512,512] %keys, s32[512,512] %vals), ..."
        results, _, operands = name.partition(" custom-call(")
        outs = trace_reduce.shapes_of(results)
        ins = trace_reduce.shapes_of(operands.split(")")[0])
        if not outs or not ins:
            continue
        (rows, lanes), (_, cols) = outs[0], ins[0]
        need += kernel_cost.hist_bytes(rows, cols, lanes, len(outs))
        took += dur / 1e9
    if not took:
        return None
    bandwidth = peaks.peaks_of(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / bandwidth / took
