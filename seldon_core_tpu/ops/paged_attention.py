"""Paged decode attention over the KV block pool IN PLACE — a Pallas TPU
kernel for the continuous-batching decode round (models/generate.py
``_paged_block``): one query a row (a decode step) or the ``W`` queries of
one diffusion block a row, which all see the same cache positions.

The XLA formulation of paged attention (``_paged_view`` + ``_attend_paged``)
gathers every row's blocks into a dense ``[B, KV, nblk*bs, hd]`` copy sized
by the PADDED row count times the LONGEST row's padded table, for every
layer of every step.  This kernel reads each row's K/V blocks from the pool
where they lie, through the block table, and stops at the row's length:

  * one invocation for the whole batch (no grid: the work is latency-bound,
    a grid step per row x head x block would cost more than the gather);
  * the LIVE rows only (``order[:count]``, compacted by ``decode_plan``), each
    walked in chunks of ``blocks_per_chunk`` blocks; a chunk's blocks are
    DMA'd HBM -> VMEM one descriptor each, all KV heads of a block in one
    descriptor, the next chunk (of this row or of the next live row) in
    flight while the current one is computed;
  * a block past a row's length costs no DMA and no compute; an inactive row
    costs nothing and yields zeros;
  * the pool keeps the layout XLA's scatter wants, ``[N, bs, KV, hd]``: a
    block arrives in VMEM with its KV heads interleaved position by
    position, so the kernel views the buffer as rows of 32-bit words
    ``[bs * KV * itemsize / 4, hd]`` (a no-op on the bytes), takes a head's
    rows by a strided load and, for a 16-bit pool, the head's half of each
    word by a shift or a mask (``_head_rows``);
  * online softmax in float32 across a row's chunks; scores accumulate in
    float32 from pool-dtype operands and ``p`` is cast to the model dtype
    before ``p @ V`` — the roundings of ``_grouped_qk`` / ``_grouped_pv``;
  * a row's ``W`` queries are folded into the query group of each KV head
    (``g * W`` query rows a head): there is no mask inside a block, so the
    one per-row length serves them all.  For a caller whose queries also
    see keys that are not in the pool (a diffusion block's own fresh K/V)
    the kernel returns the softmax's running max and sum beside the
    weighted sum (``stats``), and the caller joins the two parts in one
    softmax (generate._attend_pool_and_fresh).

Why not a pool laid out for the kernel (``[N, KV, bs, hd]``, a head's block
one plain tile): XLA's TPU scatter re-lays such a pool to ``[N, bs, KV, hd]``
around every ``_paged_write`` — two whole-pool copies per layer (PERF.md §6,
PR 25).

A head NARROWER than the 128 lanes (``hd`` 64: ``heads_per_row``) rides a
row with its neighbours.  A token's window is ``KV x hd`` values either way,
so the pool of such a model is made ``[N, bs, KV * hd / 128, 128]``
(``generate.init_block_pool``): row ``j`` of a position holds head ``2j`` in
lanes 0-63 and head ``2j + 1`` in lanes 64-127, and the kernel's body runs
unchanged over ``KV / 2`` heads of 128.  What changes is the wrapper's, on
operands of a few KB (``paged_decode_attention``):

  * the two heads' query groups fold into ONE group of ``2 * g * W`` rows a
    pool row; a query of head ``2j`` is its 64 values in lanes 0-63 and 0.0
    in 64-127, one of head ``2j + 1`` the other way round.  ``q_pad . k_row``
    is then exactly the 64-wide score — the other head's half contributes
    products with 0.0, which add nothing in float32 (stored K/V are finite)
    — so a row's softmax is its own head's, and of ``p @ v_row``
    ``[2 * g * W, 128]`` a row keeps its own half.  The scale stays
    ``1 / sqrt(hd)``: the model's head, not the row's lanes.  The MXU does
    twice the attention's FLOPs; the step is bound by the DMA of K/V, which
    is the same bytes.
  * the POOL has that shape from the start, and is never reshaped inside a
    program: a ``[N, bs, 8, 64]`` bfloat16 array does not lie on a TPU as
    its shape reads — the chip keeps it with the POSITIONS minor-most
    ((8, 128) tiles over hd x bs) and a program re-lays it, padded to 128
    lanes, around every scatter (PERF.md §6, PR 40: 134 MB of temporaries
    for one decode step's write into a 67-MB pool, 0 into the paired one)
    — so a ``reshape`` to ``[N, bs, 4, 128]`` there is a copy of the whole
    pool, around every layer.  The small operands meet the pool instead:
    ``generate._paged_write`` reshapes the fresh rows before the scatter,
    ``generate._paged_view`` the gathered copy after the gather (prefill
    and verify keep the gather path and its numerics).

Which formulation serves is decided by ONE pure function,
``inplace_supported``: the kernel on a TPU backend with a float pool, no
mesh (a Mosaic call does not partition under GSPMD), shapes the kernel
tiles (a head of 128 lanes or a multiple, or heads that fill rows of 128
together) and folded queries that fit its vector memory; the gather path
otherwise.  ``width`` there is the queries a row brings to a round's step,
all of ONE block: the causal widths of a prefill chunk or a verify pass
(``paged_forward``) do not ask and keep the gather path, which is also the
reference the kernel is tested against (tests/test_paged_attention.py).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["blocks_per_chunk", "decode_plan", "heads_per_row",
           "inplace_supported", "paged_decode_attention"]

_CHUNK_POSITIONS = 512          # K/V positions one compute step covers
_BUFFER_BYTES = 8 * 1024 * 1024  # both K and V chunks, double-buffered
_MASK = -1e30                   # generate._attend_paged's mask value
_LANES = 128                    # a vector register's lanes: a pool row
_STAT_LANES = 128               # one lane tile: the max in lane 0, the sum in 1..
# what the kernel may hold in vector memory: the chunk buffers, the whole
# batch's folded queries and outputs, the accumulators.  Mosaic's own limit
# on a v5e is 16 MiB; the rest is its scores and spills
_VMEM_BYTES = 12 * 1024 * 1024


def blocks_per_chunk(block_size: int, kv_heads: int, head_dim: int,
                     itemsize: int, nblk: int) -> int:
    """Blocks one compute step covers: as many as reach
    ``_CHUNK_POSITIONS`` positions, at most the table's width, halved until
    the four chunk buffers (K and V, two slots) fit ``_BUFFER_BYTES``.
    0 = even one block does not fit."""
    block_bytes = 4 * kv_heads * block_size * head_dim * itemsize
    c = max(1, min(_CHUNK_POSITIONS // block_size, nblk))
    while c > 1 and c * block_bytes > _BUFFER_BYTES:
        c //= 2
    return c if c * block_bytes <= _BUFFER_BYTES else 0


def heads_per_row(kv_heads: int, head_dim: int) -> int:
    """KV heads one row of the pool carries: 1 (the pool is ``[N, bs, KV,
    hd]``), or ``128 // head_dim`` for a head narrower than the 128 lanes
    whose KV heads fill whole rows (the pool is ``[N, bs, KV * hd / 128,
    128]``: the same bytes, a token's window still minor-most).  A matter of
    the model's shapes alone, because the pool is made before anybody knows
    which formulation will read it: ``generate.init_block_pool`` asks here
    for a float pool, and an int8 pool keeps a head a row (its scale planes
    are per head)."""
    pair = _LANES // head_dim if head_dim and _LANES % head_dim == 0 else 1
    return pair if kv_heads % pair == 0 else 1


def _query_rows(heads: int, kv_heads: int, width: int) -> int:
    """Query rows a pool row's heads serve, ``g * W`` a head, in whole
    float32 sublane tiles."""
    return -(-(heads // kv_heads) * width // 8) * 8


def inplace_supported(*, width: int, backend: str, pool_dtype: Any,
                      mesh: Optional[Any], block_size: int, kv_heads: int,
                      head_dim: int, heads: Optional[int] = None,
                      rows: int = 1) -> bool:
    """True where decode attention runs over the pool in place (this
    module's kernel), False where it takes the gather path.  Decided from
    what the caller can observe, never from a model's name or a switch.

    ``width`` is the queries a row brings to a step, all of one block (1: a
    decode step); ``heads`` and ``rows`` size the batch's folded queries,
    which the kernel holds whole in vector memory (a caller that does not
    say is answered for one row of one query head a KV head)."""
    if width < 1 or backend != "tpu" or mesh is not None:
        return False
    dt = jnp.dtype(pool_dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return False
    # heads narrower than a row ride it together: from here on the shape is
    # the pool's, ``kv_heads / pair`` heads of 128 (``heads`` stays: a row's
    # heads serve ``pair`` query groups)
    pair = heads_per_row(kv_heads, head_dim)
    heads = heads or kv_heads
    kv_heads, head_dim = kv_heads // pair, head_dim * pair
    # the 32-bit view: a position's KV heads are one memory tile of
    # 1, 2, 4 or 8 rows of 128 words, and a block is whole (8, 128) tiles
    words = kv_heads * dt.itemsize / 4
    if (dt.itemsize not in (2, 4) or words not in (1, 2, 4, 8)
            or head_dim % 128 or block_size * words % 8):
        return False
    chunk = blocks_per_chunk(block_size, kv_heads, head_dim, dt.itemsize,
                             max(1, _CHUNK_POSITIONS // block_size))
    if chunk == 0:
        return False
    group = kv_heads * _query_rows(heads, kv_heads, width) * 4
    held = (chunk * 4 * kv_heads * block_size * head_dim * dt.itemsize
            + 2 * rows * group * head_dim            # queries in, sums out
            + group * (head_dim + 2 * _STAT_LANES))  # the accumulators
    if width > 1:
        held += rows * group * _STAT_LANES           # the statistics out
    return held <= _VMEM_BYTES


def decode_plan(n_valid, active, capacity: int, fresh: int = 1):
    """The kernel's scalar operands for one decode step, shared by every
    layer: ``lengths`` [B] (``n_valid + fresh`` — ``fresh`` 1: the row's
    own new K/V is already in the pool and its query sees it; 0: the
    queries of a diffusion block that starts at ``n_valid`` see the cache
    before it and nothing of the pool from there on — 0 for an inactive
    row, at most the table's ``capacity``), ``order`` [B] (the live rows'
    indices first) and ``count`` [1] (how many are live: a row of length 0
    is not)."""
    B = n_valid.shape[0]
    lengths = jnp.where(active, jnp.minimum(n_valid + fresh, capacity), 0)
    lengths = lengths.astype(jnp.int32)
    seen = jnp.cumsum(lengths > 0)  # live rows up to and including b
    # the r-th live row's index = rows that come before it
    order = jnp.sum(seen[None, :] <= jnp.arange(B)[:, None], axis=1)
    order = jnp.minimum(order, B - 1).astype(jnp.int32)
    return lengths, order, seen[-1:].astype(jnp.int32)


def _head_rows(buf, h: int, dtype):
    """Head ``h``'s ``[T, hd]`` of a chunk buffer ``[C, bs, KV, hd]``.

    The buffer's bytes, position by position, are the KV heads' rows one
    after another; as 32-bit words that is ``KV * itemsize / 4`` rows per
    position.  A 32-bit pool: head h is every KV-th row from h.  A 16-bit
    pool: heads 2i and 2i+1 share a row, the even head in the low halves of
    its words — moved up (or masked in place) a half becomes the float32
    with the same value, and the cast back is exact."""
    C, bs, KV, hd = buf.shape
    itemsize = jnp.dtype(dtype).itemsize
    R = KV * itemsize // 4  # 32-bit rows a position
    if itemsize == 4:
        return buf.reshape(C * bs * R, hd)[pl.ds(h, C * bs, stride=R), :]
    w = buf.bitcast(jnp.uint32).reshape(C * bs * R, hd)[
        pl.ds(h // 2, C * bs, stride=R), :]
    w = (w & jnp.uint32(0xFFFF0000)) if h % 2 else (w << 16)
    return pltpu.bitcast(w, jnp.float32).astype(dtype)


def _first_lane(shape):
    """True in lane 0 of a statistics tile: the max's place, the sum fills
    the rest."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) == 0


def _kernel(len_ref, order_ref, count_ref, tbl_ref, q_ref, k_hbm, v_hbm,
            o_ref, *refs, nblk: int, p_dtype, scale: float):
    # ``stat_ref``: the second output, there only for a caller that asked
    # for the statistics
    *stat_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = refs
    B, KV, g, hd = q_ref.shape
    _, C, bs, _, _ = kbuf.shape
    T = C * bs
    scale = jnp.float32(scale)

    o_ref[...] = jnp.zeros_like(o_ref)
    for ref in stat_ref:
        # a row the walk never reaches: no weight in the caller's softmax
        ref[...] = jnp.where(_first_lane(ref.shape), _MASK, 0.0)
    if C > 1:
        # a chunk's blocks past the row's length are not fetched: whatever
        # VMEM held there meets p == 0, which must not be 0 * NaN
        vbuf[...] = jnp.zeros_like(vbuf)

    def each_copy(b, j, slot, fn):
        """``fn`` over the DMA descriptors of chunk ``j`` of row ``b``:
        one for K and one for V per block that holds a live position."""
        n = len_ref[b]
        for c in range(C):
            i = j * C + c

            @pl.when(i * bs < n)
            def _():
                blk = tbl_ref[b * nblk + i]
                fn(pltpu.make_async_copy(
                    k_hbm.at[blk], kbuf.at[slot, c], sem.at[0, slot]))
                fn(pltpu.make_async_copy(
                    v_hbm.at[blk], vbuf.at[slot, c], sem.at[1, slot]))

    def start(b, j, slot):
        each_copy(b, j, slot, lambda dma: dma.start())

    def wait(b, j, slot):
        each_copy(b, j, slot, lambda dma: dma.wait())

    count = count_ref[0]

    @pl.when(count > 0)
    def _():
        start(order_ref[0], 0, 0)

    def row(r, t):
        b = order_ref[r]
        n = len_ref[b]
        chunks = (n + T - 1) // T
        next_b = order_ref[jnp.minimum(r + 1, B - 1)]
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def chunk(j, t):
            slot = t % 2
            last = j + 1 >= chunks

            @pl.when(jnp.logical_not(last))
            def _():
                start(b, j + 1, 1 - slot)

            @pl.when(jnp.logical_and(last, r + 1 < count))
            def _():
                start(next_b, 0, 1 - slot)

            wait(b, j, slot)
            live = (j * T + jax.lax.broadcasted_iota(jnp.int32, (g, T), 1)
                    < n)
            for h in range(KV):
                k = _head_rows(kbuf.at[slot], h, kbuf.dtype)
                v = _head_rows(vbuf.at[slot], h, vbuf.dtype)
                s = jax.lax.dot_general(
                    q_ref[b, h].astype(k.dtype), k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(live, s, _MASK)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_scr[h] = alpha * l_scr[h] + p.sum(axis=-1, keepdims=True)
                acc_scr[h] = alpha * acc_scr[h] + jnp.dot(
                    p.astype(p_dtype).astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                m_scr[h] = m_new
            return t + 1

        t = jax.lax.fori_loop(0, chunks, chunk, t)
        o_ref[b] = acc_scr[...] / l_scr[...]
        for ref in stat_ref:
            ref[b] = jnp.where(_first_lane(ref.shape[1:]), m_scr[...],
                               l_scr[...])
        return t

    jax.lax.fori_loop(0, count, row, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret", "stats"))
def paged_decode_attention(q, k_pool, v_pool, tables, lengths, order, count,
                           *, interpret: bool = False, stats: bool = False):
    """Attention of every live row's ``W`` queries over the first
    ``lengths[b]`` positions of its own blocks, no mask among the queries.

    q [B, H, W, hd] in the model dtype; k_pool / v_pool
    ``[N, bs, KV, hd]``, or ``[N, bs, KV / pair, pair * hd]`` where ``pair``
    heads ride a row (the module docstring; told by the pool's last
    dimension against q's); tables [B, nblk] int32; ``lengths, order,
    count`` from ``decode_plan``.  Returns [B, H, W, hd] in q's dtype; a
    row that is
    not live (inactive, or of length 0) gives zeros.  Jitted so that a
    program's layers share one trace and one lowering of the kernel.

    ``stats`` (static) is for a caller whose queries see more keys than the
    pool holds for them: it returns ``(out, peak, mass)`` in float32 — the
    same weighted sum ``[B, KV, g, W, hd]``, the largest score
    ``[B, KV, g, W]`` and the sum of ``exp(score - peak)`` — to be joined
    with the other keys' part in one softmax; a row that is not live has
    mass 0."""
    B, H, W, hd = q.shape
    _, bs, KV, lanes = k_pool.shape     # the pool's rows: KV / pair of them
    pair = lanes // hd                  # model heads a pool row carries
    nblk = tables.shape[1]
    rows = H // KV * W                  # pair * g * W query rows a pool row
    gp = _query_rows(H, KV, W)
    C = blocks_per_chunk(bs, KV, lanes, k_pool.dtype.itemsize, nblk)
    if C == 0:
        raise ValueError(
            f"one block of {KV} x {bs} x {lanes} {k_pool.dtype} does not "
            "fit the kernel's chunk buffers; take the gather path "
            "(inplace_supported)")
    # float32 in and out: the model dtype's values exactly, in (8, 128)
    # tiles whatever the group size; cast back to the pool dtype in VMEM
    qg = q.reshape(B, KV, pair, rows // pair, hd).astype(jnp.float32)
    if pair > 1:
        # a head's queries in its own lanes of the row, 0.0 in the others'
        own = jnp.eye(pair, dtype=bool)[:, None, :, None]
        qg = jnp.where(own, qg[:, :, :, :, None, :], 0.0)
    qg = qg.reshape(B, KV, rows, lanes)
    if gp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rows), (0, 0)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    summed = jax.ShapeDtypeStruct((B, KV, gp, lanes), jnp.float32)
    stat = jax.ShapeDtypeStruct((B, KV, gp, _STAT_LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, nblk=nblk, p_dtype=q.dtype,
                          scale=1.0 / (hd ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                vmem,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(vmem, vmem) if stats else vmem,
            scratch_shapes=[
                pltpu.VMEM((2, C, bs, KV, lanes), k_pool.dtype),
                pltpu.VMEM((2, C, bs, KV, lanes), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((KV, gp, 1), jnp.float32),
                pltpu.VMEM((KV, gp, 1), jnp.float32),
                pltpu.VMEM((KV, gp, lanes), jnp.float32),
            ],
        ),
        out_shape=(summed, stat) if stats else summed,
        interpret=interpret,
    )(lengths, order, count, tables.reshape(-1), qg, k_pool, v_pool)
    out, stat = out if stats else (out, None)
    out = out[:, :, :rows].reshape(B, KV, pair, rows // pair, pair, hd)
    # of a row's sum over the wide values, the half that is its own head's
    out = jnp.stack([out[:, :, s, :, s] for s in range(pair)], axis=2)
    if not stats:
        return out.astype(q.dtype).reshape(B, H, W, hd)
    g = H // (KV * pair)
    stat = stat[:, :, :rows].reshape(B, KV * pair, g, W, _STAT_LANES)
    return (out.reshape(B, KV * pair, g, W, hd), stat[..., 0], stat[..., 1])
