"""The batch the numerics child judges, planned off the chip: which rows,
at which lengths, over which blocks, and how the plain reference takes
the judged events of their round (lib/children.py says what an event is).
Pure arithmetic (the parent may not import JAX).

The rows are a batch the deployment really runs: as many as it has
``slots`` (at most ``MAX_ROWS``), their lengths the prompt lengths of the
window's own schedule — themselves the stratified quantiles of the cell's
mix (lib/traffic.py) — at evenly spaced ranks, the shortest and the
LONGEST among them, each cut so that prompt + one decode round stays
within the mix's ``max_positions``.  So a cell whose mix sends prompts of
four chunks is judged on a row of four chunks, whatever the seed."""

from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

from . import buckets

MAX_ROWS = 32
# the most one group of rows may hold inside the reference, by the
# architecture's own account of a row (archs/<arch>/reference.py row_bytes)
GROUP_BYTES = 2 << 30


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


def reference_groups(totals: Sequence[int], row_bytes: Callable[[int], int],
                     quantum: int) -> List[Tuple[int, List[int]]]:
    """``(length, row indices)`` groups the reference takes one at a time,
    the longest first; a row is one judged event's context.  A group's rows
    are right-padded to ``length``, the next multiple of ``quantum`` (the
    deployment's block size) at or over its longest row, so rows of nearby
    lengths share a compiled shape, whatever the schedule's exact lengths.
    Whether the pad is harmless is the reference's to see to: it is told
    each row's true length beside the positions asked for, and one whose
    mask lets a position look ahead keeps the pad out by it.  Rows of one
    length go together as far as ``GROUP_BYTES`` holds them by
    ``row_bytes(length)`` — what a row of the reference holds at that
    length, asked of the architecture, since a wide vocabulary, a long
    row's scores or an expert layer's hidden each dominate somewhere; one
    row alone may pass it."""
    by_length: dict = {}
    for i in sorted(range(len(totals)), key=lambda i: -totals[i]):
        by_length.setdefault(-(-totals[i] // quantum) * quantum, []).append(i)
    out = []
    for length in sorted(by_length, reverse=True):
        rows = by_length[length]
        fit = max(1, GROUP_BYTES // row_bytes(length))
        out += [(length, rows[k:k + fit]) for k in range(0, len(rows), fit)]
    return out
