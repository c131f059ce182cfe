"""Layer metrics that are a percentile of a CUMULATIVE histogram in ``GET
/genperf`` (``{"edges_ms": [e0 .. en], "counts": [c0 .. cn+1]}``: the
first bucket holds everything under ``e0``, bucket ``i`` holds ``e(i-1) <=
x < e(i)``, the last everything from ``en`` up), taken over the window
delta of the counts and linear inside a bucket.

    {"hist": "requests.ttft_ms_hist", "percentile": 90}

A program without that histogram (the parent of the PR that brought it),
edges that changed between the two documents, or an empty delta give
None."""

from __future__ import annotations

from typing import Optional


def _node(doc: dict, path: str):
    for key in path.split("."):
        doc = doc.get(key) if isinstance(doc, dict) else None
    return doc if isinstance(doc, dict) else None


def hist_percentile(edges: list, counts: list, q: float) -> Optional[float]:
    """The ``q``-th percentile of ``counts`` over ``edges``.  The bucket
    under the first edge is taken to start at 0; a percentile that falls
    into the open last bucket reads the last edge (its lower bound)."""
    total = sum(counts)
    if total <= 0 or len(counts) != len(edges) + 1:
        return None
    target = total * q / 100.0
    seen = 0.0
    for i, c in enumerate(counts):
        if c > 0 and seen + c >= target:
            if i == len(edges):
                return float(edges[-1])
            lo = edges[i - 1] if i else 0.0
            return lo + (edges[i] - lo) * max(target - seen, 0.0) / c
        seen += c
    return float(edges[-1])


def read(metric: dict, ctx: dict):
    f = metric["formula"]
    after = _node(ctx["genperf_after"], f["hist"])
    if not after:
        return None
    before = _node(ctx["genperf_before"], f["hist"]) or {}
    edges = after.get("edges_ms") or []
    counts = list(after.get("counts") or [])
    if before:
        if before.get("edges_ms") != edges or \
                len(before.get("counts") or []) != len(counts):
            return None
        counts = [a - b for a, b in zip(counts, before["counts"])]
    if any(c < 0 for c in counts):
        return None
    return hist_percentile(edges, counts, f["percentile"])
