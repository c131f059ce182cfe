"""Percentile, time-per-output-token and attainment arithmetic.  Pure."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default).  None for an empty set."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tpot_ms(t_first: float, t_last: float, n_out: int) -> Optional[float]:
    """Time per output token of one request, in ms: the stream delivers a
    chunk per decode round, so what is observable from outside is
    ``(t_last - t_first) / (n_out - 1)``.  None when ``n_out < 2``."""
    if n_out < 2:
        return None
    return 1e3 * (t_last - t_first) / (n_out - 1)


def attainment_pct(records: Sequence[dict], ttft_limit_ms: float,
                   tpot_limit_ms: float) -> Optional[float]:
    """Share of requests SENT that met both limits.  A record that failed,
    was refused or did not finish has ``ok`` False and counts as a miss; a
    request with a single output token has no TPOT and is judged on TTFT
    alone."""
    if not records:
        return None
    met = 0
    for r in records:
        if not r["ok"]:
            continue
        if r["ttft_ms"] > ttft_limit_ms:
            continue
        if r["tpot_ms"] is not None and r["tpot_ms"] > tpot_limit_ms:
            continue
        met += 1
    return 100.0 * met / len(records)
