"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip."""


def read(run):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in run.devices]
    peaks = [p for p in peaks if p]
    return max(peaks) / 2**30 if peaks else None
