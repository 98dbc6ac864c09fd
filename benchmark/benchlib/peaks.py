"""Published peaks, keyed by ``device_kind`` as JAX reports it. A device
that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s, per chip.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; add it to "
            f"benchmark/benchlib/peaks.py with its source")
    return PEAKS[device_kind]
