"""End to end, host clock: records in the epochs committed between the
first and the last commit stamp inside the window, over the time between
those two stamps."""

from benchlib import pacing


def read(run):
    got = pacing.fence_aligned_rate(run.stamps, *run.window,
                                    run.records_per_epoch)
    if got is None:
        return None
    rate, epochs, span = got
    print(f"window: {epochs} epochs committed between the first and last "
          f"commit stamp inside it, {span:.4f} s apart; by quarter "
          + " ".join(f"{r:.0f}" for r in pacing.rates_by_part(
              run.stamps, *run.window, run.records_per_epoch))
          + " records/s", flush=True)
    return rate
