"""Device: ``memory_stats()["bytes_in_use"]`` of the fullest chip when the
window closed — what the deployment holds there (carry, logs, rings,
programs), without the transient of building it that ``hbm_peak_gib``
and the result line's ``memory_peak_bytes`` include."""


def read(run):
    held = [b for b in run.held_bytes if b]
    return max(held) / 2**30 if held else None
