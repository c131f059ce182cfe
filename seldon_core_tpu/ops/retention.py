"""Power retention of degree 2 (Buckman, Gelada, Zhang, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239) over a state that lives
in the block pool: the mixer of an ``LMConfig.layer_kinds`` "r" layer
(models/generate.py ``_retention`` brings the projections, the gate, the
head norms and the rotary embedding; this file is what happens between
them and ``W_o``).

For one KV head (width ``d``; ``G`` query heads read it) at position t,
``log g_t`` the gate's log-sigmoid (float32):

  attention form   ``a_tj = (q_t . k_j / sqrt(d))^2 * exp(sum_{l=j+1..t}
                   log g_l)`` for j <= t; ``y_t = sum_j a_tj v_j / (sum_j
                   a_tj + eps)``.  No softmax and no maximum to subtract:
                   an even power leaves no weight negative.
  recurrent form   ``phi(u)`` holds ``u_a u_b`` once for every a <= b, the
                   off-diagonal ones times sqrt(2), so that ``phi(q) .
                   phi(k) = (q . k)^2``.  ``S_t = g_t S_(t-1) + v_t
                   phi(k_t)^T`` [d, P] and ``z_t = g_t z_(t-1) + phi(k_t)``
                   [P] (the normaliser), from zero; ``y_t = (S_t phi(q_t) /
                   d) / (z_t . phi(q_t) / d + eps)`` -- the ``1/d`` is the
                   ``1/sqrt(d)`` of the attention form, squared, so ``eps``
                   weighs the same in both.

``retention`` runs the recurrent form for a call of one position a row (a
decode step: one rank-1 update and one read of the state a token) and the
CHUNK form otherwise (a prefill chunk): inside the chunk the attention form
over the chunk's own positions, from before it ``phi(q_t)`` against the
state the row carried in, decayed to t, numerators and denominators added
before the division; and the state the chunk leaves.  The two must agree
with each other and with the plain reference's attention form
(bench/archs/brumby/reference.py), which tests/test_brumby_block.py holds.

Which code runs which form, and where:

  ``_fused_chunk`` a call wider than one position where ``chunk_supported``
                   says so -- what ``step_supported`` asks, and a chunk of
                   whole sublane tiles whose ``phi``, a query head at a
                   time, fits vector memory beside the tiles -- decided
                   where the step's is (``generate.retention_fused`` with
                   the call's width: ``paged_forward`` that is not told,
                   and the scheduler): a Pallas TPU kernel, ONE call for
                   the live rows, over the step kernel's pipeline of tiles.
                   A tile of ``S`` comes in by DMA; ``phi`` of the keys and,
                   a query head at a time, of the queries is built for the
                   tile's lanes IN VECTOR MEMORY (a rotation, a product,
                   the cast); the MXU takes ``phi(q) . S^T`` into float32
                   accumulators ``[G * W, d]`` that stay there across the
                   tiles and ``[v; 1]^T phi(k)`` into the tile, which goes
                   back to the SAME entry decayed over the chunk.  The
                   normaliser's read-out is ``q^T M q`` with the carried
                   ``z`` set on the diagonals of ``M`` [d, d] (a 129th
                   column of the read-out would cost the MXU a second
                   pass); the attention form inside the chunk and the
                   division close a head.  The state is read once and
                   written once, and no ``[G * W, P]``, ``[W, P]`` or
                   state-shaped array exists outside the kernel.
  ``_chunk``       the same chunk in ``jax.numpy`` under ``retention``'s
                   loop over live rows: the CPU, the static lane, a mesh,
                   any shape the kernel refuses -- and the oracle of the
                   kernel's tests.
  ``_fused_step``  a call of one position a row where ``step_supported``
                   says so -- a TPU backend, a float32 state, a head of
                   whole 128-lane registers, ``P`` a whole number of tiles
                   that fit vector memory, no mesh -- decided by what the
                   caller can observe (``generate.retention_fused`` for a
                   decode round that is not told and for the scheduler,
                   which alone sees a mesh): a Pallas TPU kernel,
                   ONE call for the whole batch, that walks the live rows'
                   KV heads tile by tile along ``P`` (13 diagonal blocks:
                   ``[128, 1664]`` float32, 852 KB), DMAs a tile of ``S``
                   from the pool's entry, builds ``phi(k)`` and ``phi(q)``
                   of the head in vector memory from k and q (a rotation of
                   the lanes, a product, the weight: no ``[KV, G, P]`` array
                   is ever written), updates the tile, adds ``phi(q) . S'``
                   and ``phi(q) . z'`` into float32 accumulators from the
                   tile it still holds, and DMAs it back to the SAME entry
                   (``input_output_aliases``: the programs donate the pool).
                   The state is read once and written once.
  ``_step``        the same step in ``jax.numpy``, row by row: the CPU, the
                   static lane, any shape the kernel refuses -- and the
                   oracle of the kernel's tests (float32 both, equal to the
                   order of the sums).

Why both are kernels, and what binds each.  A step is bound by the state's
bytes (34 MB a row a layer against 43 MFLOP), its floor is one read and
one write, and XLA stays at 2.1 times that floor however the step is
written in ``jax.numpy`` -- as above (0.183 ms a row a layer), with the
read-out taken from the OLD state in the update's pass (0.169), as multiply
+ reduce in place of the dot (0.179) (PERF.md section 6, PR 41, call
``P41b``): the update is one fusion (read + write, 660 GB/s), the read-out
a second one that reads the new state AGAIN, and ``phi`` 65 small ones; the
compiler does not keep a 4 MB head in vector memory between two fusions.
The step kernel's time is its DMA's (the same with the arithmetic taken
out: PERF.md section 6, PR 42).  A chunk of 256 positions is bound by
compute: 27.7 GFLOP a row a layer at the published widths (0.14 ms of the
MXU) over the same one read and one write (0.084 ms).  ``_chunk`` took 0.6
ms, because under ``lax.map`` over KV heads XLA writes ``phi`` of a head's
1,280 queries to HBM -- ``[1280, 8320]`` bfloat16, 21 MB a head, 340 MB a
row a layer written and read back, 0.41 ms at the peak -- beside ``phi`` of
the keys, a bfloat16 copy of the state and some 65 small fusions for the
rotations: the matmuls were a quarter of its time.  The chunk kernel takes
0.25 ms (PERF.md section 6, PR 43): the read-out's matmul runs at the
MXU's peak (0.10 ms), and the rest is the vector unit's -- a rotation, a
product and a cast for each of ``phi``'s 10.6 M elements a KV head, one
query head's into one buffer while the MXU reads the other -- with the
state's DMA hidden behind both.

``phi``'s layout is by DIAGONALS: block s (s = 0 .. d/2) holds ``u_l *
u_((l + s) mod d)`` at lane l -- each unordered pair at circular distance s
once; the last block's second half would repeat its first and is zero.  So
``P = (d/2 + 1) * d`` (8,320 at d = 128, of which ``d (d + 1) / 2`` = 8,256
hold a product: what a 128-lane tile pads 8,256 to anyway), every block is
a whole number of lanes, and the expansion is rotations and products, no
gather.

The state is float32 (an accumulator over a whole row) and is found at
``slot`` = the row's FIRST block's id, as a short-convolution layer's is:
``{"s": [N, KV * d, P], "z": [N, KV, P]}``.  A row that starts at position
0 starts from zero whatever its entry holds (a reused block needs no reset
pass).  Rows are taken one after another, the LIVE ones only (``width`` >
0; the kernels take their indices and their count as scalar operands, as
ops/paged_attention.py takes ``order`` and ``count``): a padded row costs
nothing and touches nothing -- under the kernels not even the scratch entry
-- and the temporaries are one row's, not the batch's."""

from __future__ import annotations

import functools
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6

_HIGHEST = jax.lax.Precision.HIGHEST


def phi_width(d: int) -> int:
    return (d // 2 + 1) * d


def _diagonal_weights(d: int) -> np.ndarray:
    """[d/2 + 1, d]: 1 on the squares, sqrt(2) on every pair, 0 on the
    last block's repeated half."""
    w = np.full((d // 2 + 1, d), np.sqrt(2.0), np.float32)
    w[0] = 1.0
    w[d // 2, d // 2:] = 0.0
    return w


def phi(u, dtype=jnp.float32):
    """u [..., d] -> [..., P], products in float32, stored as ``dtype``."""
    d = u.shape[-1]
    u = u.astype(jnp.float32)
    twice = jnp.concatenate([u, u], axis=-1)
    turned = jnp.stack([twice[..., s:s + d] for s in range(d // 2 + 1)],
                       axis=-2)                         # [..., d/2+1, d]
    out = u[..., None, :] * turned * _diagonal_weights(d)
    return out.reshape(u.shape[:-1] + (phi_width(d),)).astype(dtype)


def _step(q, k, v, log_g, width, S, Z):
    """The recurrent form at one position: q [KV, G, 1, d], k, v [KV, 1,
    d], log_g [KV, 1], S [KV, d, P], Z [KV, P] -> (y [KV, G, 1, d], S',
    Z').  The read is of the state AFTER the update, in full precision: a
    step is bound by the state's bytes, not by these products."""
    del width                       # a live row's one position is valid
    d = q.shape[-1]
    g = jnp.exp(log_g[:, 0])
    fk = phi(k[:, 0])                                   # [KV, P]
    fq = phi(q[:, :, 0])                                # [KV, G, P]
    S = (g[:, None, None] * S
         + v[:, 0].astype(jnp.float32)[:, :, None] * fk[:, None, :])
    Z = g[:, None] * Z + fk
    num = jnp.einsum("kgp,kep->kge", fq, S, precision=_HIGHEST)
    den = jnp.einsum("kgp,kp->kg", fq, Z, precision=_HIGHEST)
    y = (num / d) / (den / d + EPS)[..., None]
    return y[:, :, None, :], S, Z


def _chunk(q, k, v, log_g, width, S, Z):
    """The chunk form: q [KV, G, W, d], k, v [KV, W, d], log_g [KV, W], the
    first ``width`` positions valid (the pad, to their right, enters
    neither a valid position's sums nor the state), S, Z the state carried
    in -> (y [KV, G, W, d] float32, S', Z').

    What touches ``phi`` runs ONE KV HEAD at a time (``lax.map``): ``phi``
    of a head's queries is 21 MB in bfloat16 at the published widths, of
    all eight 170 MB, and XLA takes four times as long over the whole as
    over the heads in turn (PERF.md section 6, PR 41)."""
    KV, G, W, d = q.shape
    f32 = jnp.float32
    here = jnp.arange(W)
    valid = here < width
    cum = jnp.cumsum(jnp.where(valid, log_g, 0.0), axis=-1)     # [KV, W]
    # inside the chunk: the attention form, masked before the exponential
    # (what lies above the diagonal would decay by a POSITIVE exponent)
    sees = (here[None, :] <= here[:, None]) & valid[None, :]    # [t, j]
    decay = jnp.exp(jnp.where(sees, cum[:, :, None] - cum[:, None, :], 0.0))
    dot = jnp.einsum("kgtd,kjd->kgtj", q, k, preferred_element_type=f32)
    a = jnp.where(sees, decay, 0.0)[:, None] * dot * dot / d
    num = jnp.einsum("kgtj,kje->kgte", a.astype(v.dtype), v,
                     preferred_element_type=f32)
    den = a.sum(-1)
    # what every valid position leaves in the state decays from there to
    # the chunk's end
    left = jnp.where(valid, jnp.exp(cum[:, -1:] - cum), 0.0)    # [KV, W]
    v1 = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)

    def head(of):
        """One KV head: its queries [G * W, d] against the state it carried
        in -- the normaliser as one more row of it, the state rounded to
        the activations' dtype for the read, the sums float32 -- and what
        its keys [W, d] add to the state, [d + 1, P] float32."""
        qh, kh, vh, lefth, Sh, Zh = of
        SZ = jnp.concatenate([Sh, Zh[None]], axis=0).astype(qh.dtype)
        carried = jnp.einsum("tp,ep->te", phi(qh, qh.dtype), SZ,
                             preferred_element_type=f32)
        fk = (phi(kh) * lefth[:, None]).astype(kh.dtype)
        return carried, jnp.einsum("je,jp->ep", vh, fk,
                                   preferred_element_type=f32)

    carried, add = jax.lax.map(
        head, (q.reshape(KV, G * W, d), k, v1, left, S, Z))
    # from before the chunk: decayed from the chunk's start to t
    carried = (carried.reshape(KV, G, W, d + 1)
               * (jnp.exp(cum) / d)[:, None, :, None])
    num = num + carried[..., :d]
    den = den + carried[..., d]
    y = num / (den + EPS)[..., None]
    # the state the chunk leaves: the carried one decayed over the whole
    # chunk, and what its positions added
    whole = jnp.exp(cum[:, -1])
    S = whole[:, None, None] * S + add[:, :d]
    Z = whole[:, None] * Z + add[:, d]
    return y, S, Z


# -- the recurrent form as ONE pass over the state: the kernel ---------------

_LANES = 128                    # a vector register's lanes
_TILE_BYTES = 1 << 20           # one tile of S in vector memory, at most
_SLOTS = 3                      # tiles in flight: read, compute, written
# the kernel takes the batch in whole groups of rows: a deployment's decode
# programs (1, 2, 4, 8, 16 rows) then bind ONE trace of it, and a padded
# row costs it nothing
_ROW_GROUP = 16
# what the kernel may hold in vector memory (ops/paged_attention.py allows
# itself the same): the tiles, a row's normalisers in and out, phi of one
# head's q and k, the read-out's accumulators, the batch's q, k, v and y
_VMEM_BYTES = 12 * 1024 * 1024


def blocks_per_tile(d: int) -> int:
    """Diagonal blocks (``d`` lanes each) in one tile of ``S`` ``[d, tile]``:
    the largest divisor of ``d/2 + 1`` whose tile is at most ``_TILE_BYTES``
    (13 of 65 at d = 128: five tiles of 852 KB).  0: not even one fits."""
    nb = d // 2 + 1
    fit = [c for c in range(1, nb + 1)
           if nb % c == 0 and 4 * d * d * c <= _TILE_BYTES]
    return max(fit, default=0)


def _rows_held(G: int) -> int:
    """Rows of the kernel's small operand a KV head: its G queries, its key
    and its value, in whole float32 sublane tiles."""
    return -(-(G + 2) // 8) * 8


def _tiles_supported(backend: str, state_dtype: Any, head_dim: int,
                     mesh: Optional[Any]) -> int:
    """What both kernels ask first: a TPU backend (Mosaic), no mesh (a
    Mosaic call does not partition under GSPMD), a float32 state (what
    the tiles and their DMA are laid out for), a head of whole 128-lane
    registers (a diagonal block of ``phi`` is a rotation of them).  ->
    ``blocks_per_tile``, 0 where any of them fails."""
    if backend != "tpu" or mesh is not None:
        return 0
    if jnp.dtype(state_dtype) != jnp.float32:
        return 0
    if head_dim < _LANES or head_dim % _LANES:
        return 0
    return blocks_per_tile(head_dim)


def step_supported(*, backend: str, state_dtype: Any, head_dim: int,
                   mesh: Optional[Any] = None, kv_heads: int = 1,
                   heads: Optional[int] = None, rows: int = 1) -> bool:
    """True where a call of one position a row runs the kernel
    (``_fused_step``), False where it runs ``_step`` row by row.  Decided
    from what the caller can observe, as ops.paged_attention
    .inplace_supported decides for attention: a TPU backend (Mosaic), a
    float32 state (what the kernel's tiles and its DMA are laid out for),
    a head of whole 128-lane registers (a diagonal block of ``phi`` is a
    rotation of them), ``P`` a whole number of tiles that fit vector
    memory beside the batch's q, k, v and y (``rows`` padded rows of
    ``heads`` query heads), and no mesh (a Mosaic call does not partition
    under GSPMD)."""
    tile = _tiles_supported(backend, state_dtype, head_dim, mesh)
    if tile == 0:
        return False
    G = (heads or kv_heads) // kv_heads
    R, P = _rows_held(G), phi_width(head_dim)
    rows = -(-rows // _ROW_GROUP) * _ROW_GROUP
    held = 4 * (_SLOTS * head_dim * head_dim * tile     # the tiles of S
                + 4 * kv_heads * P                      # Z in and out, x 2
                + 8 * (G + 1) * P                       # phi of q and k
                + (G + 1) * head_dim * head_dim         # accumulators, v
                + 2 * rows * kv_heads * R * head_dim)   # q, k, v in, y out
    return held <= _VMEM_BYTES


def _tiles_pipeline(slot_ref, order_ref, count, s_hbm, z_hbm, so_hbm,
                    zo_hbm, sbuf, zin, zout, sems, *, KV: int, d: int):
    """The DMA both kernels walk the live rows' states by: ``count * KV *
    nt`` steps, step tau = (row, KV head, tile) by ``at``; tile tau is read
    into slot tau % 3 while tau - 1 is computed and written back from where
    it lies -- ``s_hbm`` / ``so_hbm`` (and ``z_hbm`` / ``zo_hbm``) are the
    same pool entry, aliased: every tile is read once, before it is written
    once -- and a row's normalisers ``[KV, P]`` come and go whole, a row
    ahead and a row behind.  -> (``at``; ``s_copy(tau, write)``, tile tau's
    copy; ``z_copies(r, write, fn)``, ``fn`` over row r's; ``begin()``
    before the first step; ``advance(tau)`` at a step's start: the slot the
    next tile lands in is free, the next tile is on its way, a row's first
    step waits for its normalisers and sends for the next row's;
    ``drain()`` after the last step)."""
    nt, Pt = zin.shape[1], sbuf.shape[2]
    steps = count * (KV * nt)

    def at(tau):
        r = jax.lax.div(tau, KV * nt)
        rest = tau - r * (KV * nt)
        kv = jax.lax.div(rest, nt)
        return r, kv, rest - kv * nt

    def s_copy(tau, write: bool):
        r, kv, t = at(tau)
        c = jax.lax.rem(tau, _SLOTS)
        where = (slot_ref[order_ref[r]],
                 pl.ds(pl.multiple_of(kv * d, d), d),
                 pl.ds(pl.multiple_of(t * Pt, Pt), Pt))
        if write:
            return pltpu.make_async_copy(sbuf.at[c], so_hbm.at[where],
                                         sems.at[1, c])
        return pltpu.make_async_copy(s_hbm.at[where], sbuf.at[c],
                                     sems.at[0, c])

    def z_copies(r, write: bool, fn):
        blk, c = slot_ref[order_ref[r]], jax.lax.rem(r, 2)
        for t in range(nt):
            lanes = pl.ds(t * Pt, Pt)
            fn(pltpu.make_async_copy(zout.at[c, t], zo_hbm.at[blk, :, lanes],
                                     sems.at[3, c]) if write else
               pltpu.make_async_copy(z_hbm.at[blk, :, lanes], zin.at[c, t],
                                     sems.at[2, c]))

    def begin():
        @pl.when(count > 0)
        def _():
            s_copy(0, False).start()
            z_copies(0, False, lambda dma: dma.start())

    def advance(tau):
        r, kv, t = at(tau)

        @pl.when(tau >= 2)
        def _():    # the slot the next tile lands in has been written back
            s_copy(tau - 2, True).wait()

        @pl.when(tau + 1 < steps)
        def _():
            s_copy(tau + 1, False).start()

        @pl.when(jnp.logical_and(kv == 0, t == 0))
        def _():
            z_copies(r, False, lambda dma: dma.wait())

            @pl.when(r + 1 < count)
            def _():
                z_copies(r + 1, False, lambda dma: dma.start())

            @pl.when(r >= 2)
            def _():
                z_copies(r - 2, True, lambda dma: dma.wait())

    def drain():
        @pl.when(steps >= 2)
        def _():
            s_copy(steps - 2, True).wait()

        @pl.when(steps >= 1)
        def _():
            s_copy(steps - 1, True).wait()
            z_copies(count - 1, True, lambda dma: dma.wait())

        @pl.when(count >= 2)
        def _():
            z_copies(count - 2, True, lambda dma: dma.wait())

    return at, s_copy, z_copies, begin, advance, drain


def _step_kernel(slot_ref, order_ref, count_ref, fresh_ref, g_ref, x_ref,
                 s_hbm, z_hbm, y_ref, so_hbm, zo_hbm, sbuf, zin, zout, fphi,
                 vcol, acc, dacc, sems, *, G: int, tile: int):
    """Every live row's KV heads, tile by tile along ``P``, over
    ``_tiles_pipeline``.  At a head's first tile ``phi`` of its queries and
    its key is built whole (65 diagonal blocks of one register a row) and
    the read-out's accumulators are cleared; at its last, ``y`` is divided
    out.  The arithmetic hides behind the DMA (PERF.md section 6, PR 42:
    the same time with it taken out), so what is written here is written
    to be traced and lowered quickly -- five decode programs a deployment
    lower it at every boot -- not to save vector operations."""
    _, KV, _, d = x_ref.shape
    nt = zin.shape[1]
    Pt = tile * d
    f32 = jnp.float32
    root2 = np.float32(np.sqrt(2.0))
    count = count_ref[0]
    at, s_copy, z_copies, begin, advance, drain = _tiles_pipeline(
        slot_ref, order_ref, count, s_hbm, z_hbm, so_hbm, zo_hbm, sbuf, zin,
        zout, sems, KV=KV, d=d)

    y_ref[...] = jnp.zeros_like(y_ref)
    begin()

    def step(tau, carry):
        r, kv, t = at(tau)
        b = order_ref[r]
        c, zc = jax.lax.rem(tau, _SLOTS), jax.lax.rem(r, 2)
        g = g_ref[b * KV + kv]
        advance(tau)

        @pl.when(t == 0)
        def _():
            # phi of this head's G queries and its key, block by diagonal
            # block: a rotation of the lanes, a product, the weight -- the
            # operations of ``phi``, in its order.  A row of it is kept a
            # (1, d) tile of its own, so that the queries' rows meet a
            # block of S as ONE broadcast
            x = x_ref[b, kv]                                # [R, d]
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

            def diagonal(s):
                blk = x * pltpu.roll(x, jnp.where(s == 0, 0, d - s), 1)
                # 1 on the squares, sqrt(2) on every pair, 0 on the last
                # block's repeated half (``_diagonal_weights``)
                w = jnp.where(s == 0, f32(1.0), root2)
                w = jnp.where(
                    jnp.logical_and(s == d // 2, lane >= d // 2), f32(0.0), w)
                blk = blk * w
                fphi[s] = blk[:G + 1].reshape(G + 1, 1, d)

            def diagonals(i, carry):
                # a tile's diagonals an iteration: one after another the
                # rotations wait for each other (a rolled loop of 65 takes
                # longer than a tile's DMA)
                for j in range(tile):
                    diagonal(i * tile + j)
                return carry

            jax.lax.fori_loop(0, nt, diagonals, 0)
            # the value down the sublanes, the same in every lane
            vcol[...] = jnp.broadcast_to(x[G + 1:G + 2, :], (d, d)).T
            acc[...] = jnp.zeros_like(acc)
            dacc[...] = jnp.zeros_like(dacc)

        s_copy(tau, False).wait()
        mine = jax.lax.broadcasted_iota(jnp.int32, (KV, d), 0) == kv

        @pl.when(fresh_ref[b] != 0)
        def _():    # a row that starts at 0: zeros, whatever the entry holds
            sbuf[c] = jnp.zeros((d, Pt), f32)
            zin[zc, t] = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (KV, Pt), 0) == kv,
                f32(0.0), zin[zc, t])

        def block(j, carry):
            lanes = pl.ds(pl.multiple_of(j * d, d), d)
            f = fphi[t * tile + j]                          # [G + 1, 1, d]
            fk = f[G]                                       # [1, d]
            S = g * sbuf[c, :, lanes] + vcol[...] * fk      # [d, d]
            sbuf[c, :, lanes] = S
            acc[...] += S[None] * f[:G]
            # this head's row of the normalisers [KV, d]: picked and put
            # back by a mask (Mosaic loads no single row at a dynamic one)
            Z = g * jnp.sum(jnp.where(mine, zin[zc, t, :, lanes], f32(0.0)),
                            axis=0, keepdims=True) + fk     # [1, d]
            zout[zc, t, :, lanes] = jnp.where(mine, Z, zout[zc, t, :, lanes])
            dacc[...] += f * Z[None]
            return carry

        # traced once, unrolled where it is lowered: the lane offsets are
        # constants again and Mosaic schedules block against block (rolled,
        # the loop takes longer than the tile's DMA)
        jax.lax.fori_loop(0, tile, block, 0, unroll=True)
        s_copy(tau, True).start()

        @pl.when(t == nt - 1)
        def _():
            for h in range(G):
                num = acc[h].T.sum(axis=0, keepdims=True)   # [1, d]
                den = dacc[h].sum(axis=1, keepdims=True)    # [1, 1]
                y_ref[b, kv, h:h + 1, :] = (num / d) / (den / d + EPS)

            @pl.when(kv == KV - 1)
            def _():
                z_copies(r, True, lambda dma: dma.start())

        return carry

    jax.lax.fori_loop(0, count * (KV * nt), step, 0)
    drain()


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _fused_step(q, k, v, log_g, s, z, slot, fresh, order, count, *,
                interpret: bool = False, tile: Optional[int] = None):
    """The recurrent form at one position for the ``count`` live rows
    ``order[:count]`` of the batch, over the pool's entries IN PLACE: q [B,
    KV, G, d], k, v [B, KV, d], log_g [B, KV]; row b's state is entry
    ``slot[b]`` of ``s`` / ``z``, zero where ``fresh[b]`` -> (y [B, KV, G,
    d] float32, zero for a row that is not live; s', z').  ``tile`` is the
    diagonal blocks a tile holds (``blocks_per_tile``).  Jitted so that a
    program's layers share one trace and one lowering of the kernel."""
    B, KV, G, d = q.shape
    tile = tile or blocks_per_tile(d)
    if not tile or (d // 2 + 1) % tile:
        raise ValueError(
            f"{d // 2 + 1} diagonal blocks of {d} lanes are no whole "
            f"number of tiles of {tile}; take the row-by-row form "
            "(step_supported)")
    nt, R = (d // 2 + 1) // tile, _rows_held(G)
    f32 = jnp.float32
    # a head's queries, its key and its value: one small operand, whole in
    # vector memory, in (8, 128) tiles whatever the group size
    x = jnp.concatenate([q.astype(f32), k.astype(f32)[:, :, None],
                         v.astype(f32)[:, :, None]], axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, R - G - 2), (0, 0)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, s, z = pl.pallas_call(
        functools.partial(_step_kernel, G=G, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, hbm, hbm],
            out_specs=(vmem, hbm, hbm),
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, d, tile * d), f32),     # tiles of S
                pltpu.VMEM((2, nt, KV, tile * d), f32),     # a row's Z in
                pltpu.VMEM((2, nt, KV, tile * d), f32),     # ... and out
                pltpu.VMEM((d // 2 + 1, G + 1, 1, d), f32),  # phi(q), phi(k)
                pltpu.VMEM((d, d), f32),                    # v, a column
                pltpu.VMEM((G, d, d), f32),                 # phi(q) . S'
                pltpu.VMEM((G + 1, 1, d), f32),             # phi(q) . Z'
                pltpu.SemaphoreType.DMA((4, _SLOTS)),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct((B, KV, R, d), f32),
                   jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)),
        # operands 6 and 7 (after the four scalar ones, g and x) are the
        # pool's entries: updated where they lie
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )(slot.astype(jnp.int32), order.astype(jnp.int32),
      count.astype(jnp.int32).reshape(1), fresh.astype(jnp.int32),
      jnp.exp(log_g).astype(f32).reshape(B * KV), x, s, z)
    return y[:, :, :G], s, z


# -- the chunk form as ONE pass over the state: the kernel -------------------

# phi's sqrt(2) on every pair, as a factor of BOTH operands of the product:
# (c u_a) (c u_b) = sqrt(2) u_a u_b.  The squares (block 0) then carry
# sqrt(2) where 1 belongs and the last block's second half a repeat where 0
# belongs: put right on the small side, a tile's lanes of the state
# (``fix`` in the kernel), never on ``phi``'s [W, P]
_ROOT4_2 = float(2.0 ** 0.25)


def _value_rows(d: int) -> int:
    """Rows of the kernel's value operand a KV head: v transposed [d, W],
    a row of ones (the normaliser's) and zeros up to whole sublane tiles
    of either activation dtype."""
    return d + 16


def chunk_supported(*, backend: str, state_dtype: Any, head_dim: int,
                    width: int, mesh: Optional[Any] = None,
                    kv_heads: int = 1, heads: Optional[int] = None,
                    act_dtype: Any = jnp.bfloat16) -> bool:
    """True where a call wider than one position runs the kernel
    (``_fused_chunk``), False where it runs ``_chunk`` row by row.  Decided
    from what the caller can observe, as ``step_supported`` decides for
    the step: a TPU backend, a float32 state, a head of whole 128-lane
    registers, no mesh, a chunk ``width`` of whole sublane tiles of the
    activations' dtype, and tiles, ``phi`` of one query head's chunk and
    of the keys', the head's q, k, v and y and the accumulators within the
    vector memory the file allows itself -- nothing of which grows with the
    batch."""
    tile = _tiles_supported(backend, state_dtype, head_dim, mesh)
    act = jnp.dtype(act_dtype).itemsize
    if tile == 0 or width < 2 or width % (32 // act):
        return False
    d, W, Pt = head_dim, width, tile * head_dim
    G = (heads or kv_heads) // kv_heads
    held = (4 * _SLOTS * d * Pt                     # the tiles of S
            + 4 * 4 * kv_heads * phi_width(d)       # Z in and out, x 2
            + act * (d + 3 * W) * Pt        # S rounded, phi k, phi q x 2
            + 4 * 8 * Pt                            # this head's z
            + 2 * act * G * W * d * 2               # q in, y out, x 2
            + 2 * (4 + act) * W * d                 # k scaled and plain, x 2
            + 2 * act * _value_rows(d) * W + 2 * 4 * 8 * W      # v, cum, x 2
            + 4 * G * W * d                         # the accumulators
            + 4 * (W * W + W * d + d * d)           # decay, e, M
            + 4 * _value_rows(d) * Pt)              # what the keys add
    return held <= _VMEM_BYTES


def _chunk_kernel(slot_ref, order_ref, count_ref, fresh_ref, whole_ref,
                  q_hbm, k_hbm, ks_hbm, v_hbm, cum_hbm, s_hbm, z_hbm,
                  y_hbm, so_hbm, zo_hbm, sbuf, zin, zout, qbuf, kbuf, ksbuf,
                  vbuf, cbuf, ybuf, acc, sbf, fk, fq, zf, mref, dref, eref,
                  sems,
                  *, G: int, tile: int):
    """Every live row's KV heads, tile by tile along ``P``, over
    ``_tiles_pipeline`` and, a head ahead and a head behind, the head's
    small operands in and its ``y`` out.

    A tile's work: the state rounded to the activations' dtype for the
    read (``sbf``); ``phi`` of the keys, weighted by what each leaves at
    the chunk's end, into ``fk`` [W, tile] and ``[v; 1]^T fk`` on the MXU
    into the tile, which goes back decayed over the whole chunk; the
    normaliser's blocks set on their diagonals of ``M`` [d, d] (``q^T M q
    = phi(q) . z``: the 129th column of the read-out would cost the MXU a
    second pass); then, a query head at a time, ``phi`` of its W queries
    into ``fq`` and ``fq . sbf^T`` added into the head's accumulators
    [G * W, d].  At a head's last tile the attention form inside the chunk
    (``q k^T`` [W, W] a query head), the carried sums decayed to each
    position and the division; ``y`` leaves in the activations' dtype."""
    _, KV, GW, d = q_hbm.shape
    W = GW // G
    nt = zin.shape[1]
    Pt = tile * d
    f32 = jnp.float32
    act = sbf.dtype
    count = count_ref[0]
    heads = count * KV
    nt_dims = (((1,), (1,)), ((), ()))      # a . b^T
    at, s_copy, z_copies, begin, advance, drain = _tiles_pipeline(
        slot_ref, order_ref, count, s_hbm, z_hbm, so_hbm, zo_hbm, sbuf, zin,
        zout, sems, KV=KV, d=d)

    def head_of(h):
        r = jax.lax.div(h, KV)
        return order_ref[r], h - r * KV, jax.lax.rem(h, 2)

    def head_copies(h, fn):
        b, kv, c = head_of(h)
        for src, dst in ((q_hbm, qbuf), (k_hbm, kbuf), (ks_hbm, ksbuf),
                         (v_hbm, vbuf), (cum_hbm, cbuf)):
            fn(pltpu.make_async_copy(src.at[b, kv], dst.at[c],
                                     sems.at[4, c]))

    def y_copy(h):
        b, kv, c = head_of(h)
        return pltpu.make_async_copy(ybuf.at[c], y_hbm.at[b, kv],
                                     sems.at[5, c])

    begin()

    @pl.when(count > 0)
    def _():
        head_copies(0, lambda dma: dma.start())

    def step(tau, carry):
        r, kv, t = at(tau)
        h = r * KV + kv
        b = order_ref[r]
        c, zc, hc = jax.lax.rem(tau, _SLOTS), jax.lax.rem(r, 2), \
            jax.lax.rem(h, 2)
        whole = whole_ref[b * KV + kv]
        fresh = fresh_ref[b] != 0
        advance(tau)

        @pl.when(t == 0)
        def _():
            head_copies(h, lambda dma: dma.wait())

            @pl.when(h + 1 < heads)
            def _():
                head_copies(h + 1, lambda dma: dma.start())

            @pl.when(h >= 2)
            def _():    # the y that left from this buffer has arrived
                y_copy(h - 2).wait()

            acc[...] = jnp.zeros_like(acc)
            mref[...] = jnp.zeros_like(mref)

        s_copy(tau, False).wait()

        @pl.when(fresh)
        def _():    # a row that starts at 0: zeros, whatever the entry holds
            sbuf[c] = jnp.zeros((d, Pt), f32)

        # the squares' sqrt(2) taken back and the last block's repeated
        # half struck out (``_ROOT4_2``), on this tile's lanes of the state
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, Pt), 1)
        fix = jnp.where(jnp.logical_and(t == 0, lane < d),
                        f32(np.sqrt(0.5)), f32(1.0))
        fix = jnp.where(jnp.logical_and(t == nt - 1, lane >= Pt - d // 2),
                        f32(0.0), fix)
        # this head's row of the normalisers [KV, Pt]: picked and put back
        # by a mask (Mosaic loads no single row at a dynamic one)
        mine = jax.lax.broadcasted_iota(jnp.int32, (KV, Pt), 0) == kv
        zrow = jnp.sum(jnp.where(mine, zin[zc, t], f32(0.0)), axis=0,
                       keepdims=True)
        zrow = jnp.where(fresh, f32(0.0), zrow)             # [1, Pt]
        sbf[...] = (sbuf[c] * fix).astype(act)

        def shift(j):
            s = t * tile + j
            return jnp.where(s == 0, 0, d - s)

        def lanes_of(j):
            return pl.ds(pl.multiple_of(j * d, d), d)

        # what the keys leave: phi(k) weighted (``ks`` carries the weight's
        # root on both factors), [v; 1]^T against it on the MXU
        ks = ksbuf[hc]                                      # [W, d] f32

        def key_block(j, carry):
            fk[:, lanes_of(j)] = (
                ks * pltpu.roll(ks, shift(j), 1)).astype(act)
            return carry

        jax.lax.fori_loop(0, tile, key_block, 0, unroll=True)
        add = jnp.dot(vbuf[hc], fk[...], preferred_element_type=f32) * fix
        sbuf[c] = whole * sbuf[c] + add[:d]
        zout[zc, t] = jnp.where(mine, whole * zrow + add[d:d + 1],
                                zout[zc, t])
        s_copy(tau, True).start()

        # the normaliser the head carried in, block s on the diagonal
        # (row - col) mod d = s of M
        zf[...] = jnp.broadcast_to(zrow * fix, zf.shape)
        delta = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (d, d), 1) + d, d)

        def m_block(j, M):
            return jnp.where(delta == t * tile + j,
                             jnp.broadcast_to(zf[0:1, lanes_of(j)], (d, d)),
                             M)

        mref[...] = jax.lax.fori_loop(0, tile, m_block, mref[...],
                                      unroll=True)

        def rows_of(i):
            return pl.ds(pl.multiple_of(i * W, W), W)

        def query_head(i, carry):
            qs = qbuf[hc, rows_of(i), :].astype(f32) * f32(_ROOT4_2)
            buf = fq.at[jax.lax.rem(i, 2)]

            def query_block(j, carry):
                buf[:, lanes_of(j)] = (
                    qs * pltpu.roll(qs, shift(j), 1)).astype(act)
                return carry

            jax.lax.fori_loop(0, tile, query_block, 0, unroll=True)
            acc[rows_of(i), :] += jax.lax.dot_general(
                buf[...], sbf[...], nt_dims, preferred_element_type=f32)
            return carry

        # traced once, unrolled where it is lowered, phi of one query head
        # into one buffer while the MXU reads the other: in one basic block
        # Mosaic overlaps a head's expansion with the head before's matmul
        # (rolled: 0.317 ms a row a layer where 0.248; PERF.md section 6)
        jax.lax.fori_loop(0, G, query_head, 0, unroll=True)

        @pl.when(t == nt - 1)
        def _():
            # inside the chunk: the attention form, masked before the
            # exponential; cum along the lanes, and turned, down the rows
            cj = jnp.broadcast_to(cbuf[hc, 0:1, :], (W, W))
            ct = cj.T
            sees = (jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
                    <= jax.lax.broadcasted_iota(jnp.int32, (W, W), 0))
            dref[...] = jnp.where(
                sees, jnp.exp(jnp.where(sees, ct - cj, f32(0.0))),
                f32(0.0)) * f32(1.0 / d)
            # what was carried in decays from the chunk's start to t
            eref[...] = jnp.broadcast_to(
                jnp.exp(ct[:, 0:1]) * f32(1.0 / d), (W, d))
            M = mref[...]

            def read(i, carry):
                rows = rows_of(i)
                q = qbuf[hc, rows, :]
                dot = jax.lax.dot_general(q, kbuf[hc], nt_dims,
                                          preferred_element_type=f32)
                a = dref[...] * dot * dot                   # [W, W]
                num = jax.lax.dot_general(
                    a.astype(act), vbuf[hc, 0:d, :], nt_dims,
                    preferred_element_type=f32)             # [W, d]
                den = jnp.sum(a, axis=1, keepdims=True)
                qs = q.astype(f32) * f32(_ROOT4_2)
                carried = jnp.sum(
                    jnp.dot(qs, M, preferred_element_type=f32,
                            precision=_HIGHEST) * qs, axis=1, keepdims=True)
                e = eref[...]
                num = num + acc[rows, :] * e
                den = den + carried * e[:, 0:1]
                ybuf[hc, rows, :] = (num / (den + EPS)).astype(ybuf.dtype)
                return carry

            jax.lax.fori_loop(0, G, read, 0)
            y_copy(h).start()

            @pl.when(kv == KV - 1)
            def _():
                z_copies(r, True, lambda dma: dma.start())

        return carry

    jax.lax.fori_loop(0, heads * nt, step, 0)
    drain()

    @pl.when(heads >= 1)
    def _():
        y_copy(heads - 1).wait()

    @pl.when(heads >= 2)
    def _():
        y_copy(heads - 2).wait()


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _fused_chunk(q, k, v, log_g, width, s, z, slot, fresh, order, count, *,
                 interpret: bool = False, tile: Optional[int] = None):
    """The chunk form for the ``count`` live rows ``order[:count]`` of the
    batch, over the pool's entries IN PLACE: q [B, KV, G, W, d], k, v [B,
    KV, W, d], log_g [B, KV, W], the first ``width[b]`` positions of a row
    valid; row b's state is entry ``slot[b]`` of ``s`` / ``z``, zero where
    ``fresh[b]`` -> (y [B, KV, G, W, d] in q's dtype, WHATEVER for a row
    that is not live: the caller masks; s', z').  What ``jax.numpy`` does
    around the call is the small part: the cumulative decay, the weights
    the keys leave by, v turned.  Jitted so that a program's layers share
    one trace and one lowering of the kernel."""
    B, KV, G, W, d = q.shape
    tile = tile or blocks_per_tile(d)
    if not tile or (d // 2 + 1) % tile:
        raise ValueError(
            f"{d // 2 + 1} diagonal blocks of {d} lanes are no whole "
            f"number of tiles of {tile}; take the row-by-row form "
            "(chunk_supported)")
    nt, R1 = (d // 2 + 1) // tile, _value_rows(d)
    f32, act = jnp.float32, q.dtype
    valid = (jnp.arange(W) < width[:, None])[:, None, :]        # [B, 1, W]
    cum = jnp.cumsum(jnp.where(valid, log_g, 0.0), axis=-1)     # [B, KV, W]
    # what a valid position leaves in the state decays from there to the
    # chunk's end; its root on both factors of phi(k), with sqrt(2)'s
    root = jnp.where(valid, jnp.exp(0.5 * (cum[..., -1:] - cum)), 0.0)
    ks = k.astype(f32) * (_ROOT4_2 * root)[..., None]
    vt = jnp.concatenate(
        [jnp.swapaxes(v, 2, 3), jnp.ones((B, KV, 1, W), v.dtype),
         jnp.zeros((B, KV, R1 - d - 1, W), v.dtype)], axis=2)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, s, z = pl.pallas_call(
        functools.partial(_chunk_kernel, G=G, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [hbm] * 7,
            out_specs=(hbm, hbm, hbm),
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, d, tile * d), f32),     # tiles of S
                pltpu.VMEM((2, nt, KV, tile * d), f32),     # a row's Z in
                pltpu.VMEM((2, nt, KV, tile * d), f32),     # ... and out
                pltpu.VMEM((2, G * W, d), act),             # a head's q
                pltpu.VMEM((2, W, d), act),                 # ... k
                pltpu.VMEM((2, W, d), f32),                 # ... k, weighted
                pltpu.VMEM((2, R1, W), act),                # ... [v; 1]^T
                pltpu.VMEM((2, 8, W), f32),                 # ... cum
                pltpu.VMEM((2, G * W, d), act),             # ... y
                pltpu.VMEM((G * W, d), f32),                # phi(q) . S^T
                pltpu.VMEM((d, tile * d), act),             # S, rounded
                pltpu.VMEM((W, tile * d), act),             # phi(k)
                pltpu.VMEM((2, W, tile * d), act),          # phi(q), a head
                pltpu.VMEM((8, tile * d), f32),             # z, this head's
                pltpu.VMEM((d, d), f32),                    # M
                pltpu.VMEM((W, W), f32),                    # decay
                pltpu.VMEM((W, d), f32),                    # e
                pltpu.SemaphoreType.DMA((6, _SLOTS)),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct((B, KV, G * W, d), act),
                   jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)),
        # operands 10 and 11 (after the four scalar ones, the decay over
        # the chunk and the head's five small ones) are the pool's entries
        input_output_aliases={10: 1, 11: 2},
        interpret=interpret,
    )(slot.astype(jnp.int32), order.astype(jnp.int32),
      count.astype(jnp.int32).reshape(1), fresh.astype(jnp.int32),
      jnp.exp(cum[..., -1]).astype(f32).reshape(B * KV),
      q.reshape(B, KV, G * W, d), k, ks, vt,
      jnp.broadcast_to(cum[:, :, None, :], (B, KV, 8, W)), s, z)
    return y.reshape(B, KV, G, W, d), s, z


def retention(q, k, v, log_g, state, slot, start, width, fused=False):
    """q [B, KV, G, W, d], k, v [B, KV, W, d], log_g [B, KV, W] float32
    over ``state`` = {"s", "z"} (the module's text): row b's state is entry
    ``slot[b]``, taken as zero where ``start[b]`` is 0, and its first
    ``width[b]`` positions are valid -- a row of width 0 is skipped: its
    ``y`` is zero and no entry is written.  -> (y [B, KV, G, W, d] in q's
    dtype, state').

    ``fused``: True takes the kernels (``_fused_step`` for a call of one
    position a row, ``_fused_chunk`` for any other width), "interpret" the
    kernels in Pallas interpret mode (tests on the CPU), False ``_step`` /
    ``_chunk`` row by row.  The caller asks ``step_supported`` /
    ``chunk_supported`` for the width it brings (models/generate.py
    ``retention_fused``, as ``decode_inplace`` asks for attention: only a
    program's caller sees the backend it is lowered for and the mesh)."""
    B, KV, G, W, d = q.shape
    live = width > 0
    order = jnp.argsort(~live, stable=True)             # the live rows first
    if W == 1 and fused:
        def rows(a):    # the batch in whole groups: ``_ROW_GROUP``
            return jnp.pad(a, [(0, -B % _ROW_GROUP)] + [(0, 0)] * (a.ndim - 1))

        y, s, z = _fused_step(
            rows(q[:, :, :, 0]), rows(k[:, :, 0]), rows(v[:, :, 0]),
            rows(log_g[:, :, 0]), state["s"], state["z"], rows(slot),
            rows(start == 0), rows(order), jnp.sum(live),
            interpret=fused == "interpret")
        return y[:B, :, :, None].astype(q.dtype), {"s": s, "z": z}
    if fused:
        y, s, z = _fused_chunk(
            q, k, v, log_g, width, state["s"], state["z"], slot, start == 0,
            order, jnp.sum(live), interpret=fused == "interpret")
        return (jnp.where(live[:, None, None, None, None], y, 0),
                {"s": s, "z": z})
    form = _step if W == 1 else _chunk

    def row(i, carry):
        s, z, y = carry
        r = order[i]

        def mine(a):
            return jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False)

        at = mine(slot)
        fresh = mine(start) == 0
        S = jax.lax.dynamic_index_in_dim(s, at, 0, keepdims=False)
        Z = jax.lax.dynamic_index_in_dim(z, at, 0, keepdims=False)
        out, S, Z = form(mine(q), mine(k), mine(v), mine(log_g), mine(width),
                         jnp.where(fresh, 0.0, S).reshape(KV, d, -1),
                         jnp.where(fresh, 0.0, Z))
        s = jax.lax.dynamic_update_index_in_dim(
            s, S.reshape(KV * d, -1), at, 0)
        z = jax.lax.dynamic_update_index_in_dim(z, Z, at, 0)
        y = jax.lax.dynamic_update_index_in_dim(y, out.astype(y.dtype), r, 0)
        return s, z, y

    s, z, y = jax.lax.fori_loop(
        0, jnp.sum(live), row,
        (state["s"], state["z"], jnp.zeros(q.shape, q.dtype)))
    return y, {"s": s, "z": z}
