"""The small arithmetic a layer metric's data file may ask for over the
window delta of one of the engine's documents:

    {"num": [term, ...], "den": [term, ...], "scale": 100.0}

A term is ``{"path": "a.b.c"}`` (the delta of that number; a last segment
ending in ``*`` sums every key with that prefix), optionally with
``"sign": -1``, or ``{"harness": "<key>"}`` (a number the harness counted
itself).  A numerator path that is absent counts 0 (a cause that never
occurred); a denominator that is absent or 0 gives None."""

from __future__ import annotations

from typing import Optional


def lookup(doc: dict, path: str) -> Optional[float]:
    node = doc
    parts = path.split(".")
    for i, key in enumerate(parts):
        if not isinstance(node, dict):
            return None
        if key.endswith("*") and i == len(parts) - 1:
            vals = [v for k, v in node.items()
                    if k.startswith(key[:-1]) and isinstance(v, (int, float))]
            return float(sum(vals)) if vals else None
        if key not in node:
            return None
        node = node[key]
    return float(node) if isinstance(node, (int, float)) else None


def delta(before: dict, after: dict, path: str) -> Optional[float]:
    b, a = lookup(before, path), lookup(after, path)
    if a is None:
        return None
    return a - (b or 0.0)


def deltas(before: dict, after: dict) -> dict:
    """Every number of ``after`` less the same number of ``before`` (0
    where ``before`` lacks it), nested as ``after`` is; what is no number
    is left out."""
    out = {}
    for key, a in after.items():
        b = before.get(key) if isinstance(before, dict) else None
        if isinstance(a, dict):
            out[key] = deltas(b if isinstance(b, dict) else {}, a)
        elif isinstance(a, (int, float)) and not isinstance(a, bool):
            out[key] = a - (b if isinstance(b, (int, float)) else 0)
    return out


def _sum(terms, before, after, harness) -> Optional[float]:
    total, found = 0.0, False
    for t in terms:
        if "harness" in t:
            v = harness.get(t["harness"])
        else:
            v = delta(before, after, t["path"])
        if v is None:
            continue
        found = True
        total += t.get("sign", 1) * v
    return total if found else None


def evaluate(formula: dict, before: dict, after: dict,
             harness: dict) -> Optional[float]:
    num = _sum(formula["num"], before, after, harness)
    if "den" in formula:
        den = _sum(formula["den"], before, after, harness)
        if not den:
            return None
        return formula.get("scale", 1.0) * (num or 0.0) / den
    if num is None:
        return None
    return formula.get("scale", 1.0) * num
