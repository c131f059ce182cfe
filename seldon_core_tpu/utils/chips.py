"""Chip spec table — advertised per-chip peaks.

The runtime performance observatory (``utils/perf.py``) normalizes live
per-dispatch MFU/roofline figures against these numbers (the chip
benchmark keeps its own, ``bench/lib/peaks.py``: it imports nothing of
the program).

Values are public spec-sheet figures; matching is by substring of
``device.device_kind`` (e.g. "TPU v5 lite").  A device kind that is not
in the table (CPU backend, a chip nobody has added) HAS NO PEAK: the
lookups return ``None`` and every figure normalized against a peak (MFU,
roofline share) is then absent/``null`` — never computed against some
other chip's numbers.
"""

from __future__ import annotations

from typing import Optional

__all__ = [
    "PEAK_BF16_TFLOPS",
    "PEAK_HBM_GBS",
    "chip_peak_tflops",
    "chip_peak_hbm_gbs",
]

#: advertised peak dense bf16 matmul throughput per chip, TFLOP/s (public
#: spec sheets; device_kind substring -> peak).  MFU divides by the bf16
#: peak even for int8 paths, so int8 "MFU" can legitimately exceed the
#: bf16-normalized number — ratio keys are the honest comparison.
PEAK_BF16_TFLOPS = (
    ("v6 lite", 918.0), ("v6e", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0), ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0), ("v2", 46.0),
)

#: advertised HBM bandwidth per chip, GB/s — the memory side of the
#: roofline.  Decode-shaped dispatches are bound by this, not by FLOPs.
PEAK_HBM_GBS = (
    ("v6 lite", 1640.0), ("v6e", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0), ("v5e", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0), ("v2", 700.0),
)

def _lookup(table, device_kind: str) -> Optional[float]:
    dk = (device_kind or "").lower()
    for frag, peak in table:
        if frag in dk:
            return peak
    return None


def chip_peak_tflops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 TFLOP/s for a device kind string; None when the
    kind is not in the table."""
    return _lookup(PEAK_BF16_TFLOPS, device_kind)


def chip_peak_hbm_gbs(device_kind: str) -> Optional[float]:
    """Peak HBM GB/s for a device kind string; None when the kind is not
    in the table."""
    return _lookup(PEAK_HBM_GBS, device_kind)
