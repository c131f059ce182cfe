"""Layer metrics over the engine's boot timeline: the ``boot`` block of
the ``GET /stats`` document read at the instant before the measured window
opens (``ctx["stats_before"]``), taken as it stands -- an account of the
set-up is absolute, not a window delta.

    {"num": [term, ...], "den": [term, ...], "scale": 100.0}

A term is one of

    {"path": "first_dispatch.loaded.host_s"}    a number of the block
    {"span": "load"}                            seconds of the spans so named
    {"sum": "programs", "field": "trace_s"}     a field summed over a list
    {"count": "programs"}                       a list's entries, all of them
    {"count": "programs", "unless": {"from_cache": true}}
                                                ... but those that say so

optionally with ``"sign": -1``.  What a term names and the block lacks
counts 0 (a span that never ran, a list that is empty); the numerator is
floored at 0; a denominator of 0 gives 0.0 (a share over no programs).  A
document without the block -- a program from before the timeline -- gives
None."""

from __future__ import annotations

from typing import Optional

from lib.formula import lookup


def _term(boot: dict, term: dict) -> float:
    if "path" in term:
        return lookup(boot, term["path"]) or 0.0
    if "span" in term:
        return float(sum(
            s["end_s"] - s["start_s"] for s in boot.get("spans", ())
            if s.get("name") == term["span"]))
    entries = boot.get(term.get("sum") or term["count"]) or ()
    if "sum" in term:
        return float(sum(e.get(term["field"]) or 0.0 for e in entries))
    unless = term.get("unless", {})
    return float(sum(
        1 for e in entries
        if not unless or any(e.get(k) != v for k, v in unless.items())))


def _sum(boot: dict, terms: list) -> float:
    return sum(t.get("sign", 1) * _term(boot, t) for t in terms)


def evaluate(formula: dict, boot: dict) -> float:
    num = max(_sum(boot, formula["num"]), 0.0)
    if "den" not in formula:
        return formula.get("scale", 1.0) * num
    den = _sum(boot, formula["den"])
    return formula.get("scale", 1.0) * num / den if den > 0 else 0.0


def read(metric: dict, ctx: dict) -> Optional[float]:
    boot = (ctx.get("stats_before") or {}).get("boot")
    if not isinstance(boot, dict):
        return None
    return float(evaluate(metric["formula"], boot))
