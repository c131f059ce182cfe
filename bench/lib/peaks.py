"""Published peaks, keyed by the ``device_kind`` JAX reports.  A kind that
is not here is an error, never a default.

TPU v5e ("TPU v5 lite" is the kind JAX reported on the chip, my chip runs,
PR 23): Google Cloud documentation, "TPU v5e" system architecture page —
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            "with its source to bench/lib/peaks.py")
    return PEAKS[device_kind]
