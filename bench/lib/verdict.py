"""The verdict of the numerics comparison: a pure function of two arrays
(one number a judged row each) and the configuration's ``numerics``
section.  No JAX and no NumPy, so the tests call it on made-up arrays
without a device and the numerics child only gathers the arrays.

It is BY ROW because lost precision and a discrete choice that came out
differently fail differently.  A program that computes in a lower
precision than it states is off in every row.  A correct program whose
block makes discrete choices (a top-k router) is off by a whole logit's
rms in the few rows where a choice fell the other way — bf16 activations
against the float32 reference's move a score across the gap between the
8th and the 9th expert — and as close as a dense block everywhere else.
One number over all rows cannot tell the two apart; the share of rows
over the limit can.

``numerics`` of a configuration file:

  tolerance_rms            the prefill limit as a multiple of the reference
                           logits' rms; the decode limit is twice that
  discrete_share.prefill   the share of rows that may lie over the prefill
  discrete_share.decode    limit / the decode limit.  **0 where absent**: a
                           block without discrete choices has no row to
                           spare.  A configuration that states a share
                           states beside it why, and the two readings that
                           bracket it (PERF.md section 2)
"""

from __future__ import annotations

from statistics import median
from typing import Sequence


def _side(values: Sequence[float], limit: float, allowed: float) -> dict:
    # ``not <=``: a NaN is over the limit, never under it
    over = sum(1 for v in values if not v <= limit)
    share = over / len(values)
    finite = [v for v in values if v == v]
    return {"max": max(finite) if finite else float("nan"),
            "median": median(finite) if finite else float("nan"),
            "limit": limit, "over": over, "share": share,
            "allowed": allowed, "ok": share <= allowed}


def judge(prefill_err: Sequence[float], decode_margin: Sequence[float],
          numerics: dict, ref_rms: float = 1.0) -> dict:
    """``prefill_err[i]``: max |program - reference| over the logits after
    row i's last prompt token.  ``decode_margin[i]``: the worst, over the
    decode round's steps, of the reference's best logit minus its logit of
    the token the program chose.  Both in logit units; ``ref_rms`` is the
    reference logits' rms (1.0 where the arrays already are multiples of
    it)."""
    if not len(prefill_err) or len(prefill_err) != len(decode_margin):
        raise ValueError(
            f"{len(prefill_err)} prefill rows, {len(decode_margin)} decode "
            "rows: the verdict needs one of each for every judged row")
    tolerance = float(numerics["tolerance_rms"]) * float(ref_rms)
    share = numerics.get("discrete_share") or {}
    prefill = _side(prefill_err, tolerance, float(share.get("prefill", 0.0)))
    decode = _side(decode_margin, 2 * tolerance,
                   float(share.get("decode", 0.0)))
    return {"ok": prefill["ok"] and decode["ok"], "rows": len(prefill_err),
            "tolerance": tolerance, "prefill": prefill, "decode": decode}
