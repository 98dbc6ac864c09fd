"""Operators, from inside: records the event-time windows dropped as late
(behind their watermark), per committed epoch over the whole run — the
program's ``window.late_records.<vertex>`` counters, which the fence adds
to from the operator state its health read brings back. 0 in a sound
run: the traffic's lag stays inside the bound the windows are given."""

from benchlib import program_spans


def read(run):
    late = [n for name, n in program_spans.of(run).counters.items()
            if name.startswith("window.late_records.")]
    if not late or not run.stamps:
        return None
    return sum(late) / len(run.stamps)
