"""The batch the numerics child judges, planned off the chip: which rows,
at which lengths, over which blocks, and how the plain reference takes
them.  Pure arithmetic (the parent may not import JAX).

The rows are a batch the deployment really runs: as many as it has
``slots`` (at most ``MAX_ROWS``), their lengths the prompt lengths of the
window's own schedule — themselves the stratified quantiles of the cell's
mix (lib/traffic.py) — at evenly spaced ranks, the shortest and the
LONGEST among them, each cut so that prompt + one decode round stays
within the mix's ``max_positions``.  So a cell whose mix sends prompts of
four chunks is judged on a row of four chunks, whatever the seed."""

from __future__ import annotations

from typing import List, Sequence

from . import buckets

MAX_ROWS = 32
# the most one group of reference logits [rows, S, V] (float32) may take
GROUP_BYTES = 2 << 30
LONGEST = 4


def pick(prompts: Sequence[int], rows: int) -> List[int]:
    """``rows`` of ``prompts`` at evenly spaced ranks, ascending, with the
    shortest and the longest in it (the longest alone where rows is 1)."""
    s = sorted(prompts)
    if len(s) <= rows:
        return s
    if rows == 1:
        return s[-1:]
    return [s[round(i * (len(s) - 1) / (rows - 1))] for i in range(rows)]


def plan(prompts: Sequence[int], dep: dict, max_positions: int) -> dict:
    """The judged rows for a schedule's prompt lengths under a deployment.
    Block tables are disjoint and sized as the scheduler sizes them: a row
    holds the blocks of its prompt + one round of ``span``, block 0 is the
    scheduler's scratch.  Where the pool cannot hold every slot's row, the
    longest rows it holds are taken and ``offered`` says how many there
    were."""
    span, bs = dep["span"], dep["block_size"]
    lens = [max(1, min(p, max_positions - span))
            for p in pick(prompts, min(dep["slots"], MAX_ROWS))]
    offered = len(lens)
    need = [buckets.blocks(n + span, bs) for n in lens]
    while lens and sum(need) > dep["pool_blocks"] - 1:
        lens, need = lens[1:], need[1:]        # ascending: the shortest goes
    if not lens:
        raise ValueError(
            f"a pool of {dep['pool_blocks']} blocks of {bs} holds no row of "
            "the cell's mix")
    chunks = [-(-n // dep["prefill_chunk"]) for n in lens]
    return {"lens": lens, "offered": offered, "blocks": need,
            "chunks": [min(chunks), max(chunks)]}


def reference_groups(totals: Sequence[int], vocab: int) -> List[List[int]]:
    """Row indices in groups the reference takes one at a time, each
    right-padded to its longest row (a causal pass is unchanged before the
    pad), neighbours in length together.  Every group is one more shape
    the reference compiles (12-20 s apiece, cold), so there are as few as
    the two rules leave: the longest ``1 / LONGEST`` of the rows go apart
    (padding everything to them would multiply the work), and no group's
    logits [rows, S, V] in float32 pass ``GROUP_BYTES`` (one row alone
    may)."""
    order = sorted(range(len(totals)), key=lambda i: totals[i], reverse=True)
    most = -(-len(order) // LONGEST)
    out = []
    while order:
        fit = GROUP_BYTES // (totals[order[0]] * vocab * 4)
        k = max(1, min(most, fit))
        out.append(order[:k])
        order = order[k:]
        most = len(order)
    return out
