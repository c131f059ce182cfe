"""The Mamba-2 state-space recurrence, from a carried state and leaving one.

For one sequence, ``H`` heads of width ``P``, a state of ``N`` a head, ``G``
groups of B and C (head ``h`` reads group ``h // (H / G)``), with a step
size ``dt_t`` [H] > 0 and ``A`` [H] < 0, both float32:

    h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t          [H, P, N], float32
    y_t = h_t C_t + D x_t                                 [H, P]

in two forms that give the same numbers:

  * ``ssm_step``: one position a row (a decode step) -- the recurrence as
    written, element by element in float32 (and ``ssm_step_pool``, the same
    step over the pool's entries where they lie: below);
  * ``ssm_chunk``: ``W`` positions a row at once (a prefill call), the
    chunked ("SSD") form.  With ``c_t = sum_(s <= t) dt_s A`` the recurrence
    unrolls to ``h_t = exp(c_t) h_0 + sum_(s <= t) exp(c_t - c_s) dt_s x_s
    (x) B_s``, so

        y_t = sum_(s <= t) exp(c_t - c_s) (C_t . B_s) dt_s x_s   (one masked
              [W, W] matrix a head against x: matmuls)
              + exp(c_t) C_t . h_0                               (the carried
              state's part) + D x_t
        h_W = exp(c_W) h_0 + sum_s exp(c_W - c_s) dt_s x_s (x) B_s

    A call wider than ``CHUNK`` positions goes chunk by chunk (a
    ``lax.scan`` over whole chunks, the state carried between them), so the
    [W, W] matrices stay ``CHUNK`` square whatever the call's width.

A position whose ``dt`` is 0 is no position: its decay is 1 and its update
0, so it leaves the state as it was and adds to no later sum.  That is how
a caller keeps pad positions (to the right of a row's valid ones) and empty
rows out: ``dt = 0`` there (models/generate.py ``_ssm``).

``ssm_step`` and ``ssm_chunk`` are plain ``jax.numpy`` over states the
caller gathered, and the caller scatters what they return.  Every decay,
the cumulative sums and the state are float32; ``x``, ``B`` and ``C`` enter
the matmuls in the dtype they come in (the model's), accumulated in
float32.

``ssm_step_pool`` is the step over the POOL: ``h`` [num_blocks, H, P, N],
row b's state at entry ``slot[b]``.  Where ``step_supported`` says so -- a
TPU backend, a float32 state, ``N`` whole 128-lane registers and ``P``
whole sublane tiles, tiles that fit the vector memory the kernel allows
itself, no mesh; decided where the pool's owner sees the mesh
(models/generate.py ``ssm_fused``) -- it is a Pallas TPU kernel
(``_fused_step``), ONE call a layer a step for the whole batch, that walks
the LIVE rows only: a row's entry comes in by DMA tile by tile (a quarter
of it at the published widths, contiguous in the entry), ``h <- exp(dt A)
h + (dt x) (x) B`` and ``y = h C`` are taken from the tile it holds, and
the tile goes back to the SAME entry (``input_output_aliases``: the
programs donate the pool), the next two tiles on their way meanwhile.  A
row's state is read once and written once; a padded or inactive row costs
nothing and touches nothing, not even the scratch entry; a row that starts
at position 0 is zero-filled in vector memory whatever its entry holds.  A step is bound by the state's
bytes (2 MiB a row a layer against 2.6 MFLOP): the kernel's time is its
DMA's (PERF.md section 6, PR 47).  Elsewhere (the CPU, the static lane, a
bfloat16 state, a mesh) ``ssm_step`` over gathered rows serves, and is the
oracle of the kernel's tests.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_step", "ssm_step_pool", "step_supported", "ssm_chunk",
           "ssm_scan", "CHUNK"]

#: positions a chunk of the chunked form holds: the masked [CHUNK, CHUNK]
#: matrix a head and a row is float32, 16 rows x 64 heads of it 268 MB at
#: 256, which is also the widest prefill call the benchmark's cells make
CHUNK = 256


def _by_group(t, groups: int):
    """[B, H, ...] -> [B, G, H / G, ...]: heads beside the group they read."""
    return t.reshape(t.shape[0], groups, t.shape[1] // groups, *t.shape[2:])


def ssm_step(x, dt, A, Bm, Cm, D, h):
    """One position a row.  x [B, H, P]; dt [B, H] float32; A, D [H]
    float32; Bm, Cm [B, G, N]; h [B, H, P, N] float32 -> (y [B, H, P]
    float32, h' [B, H, P, N])."""
    f32 = jnp.float32
    G = Bm.shape[1]
    decay = jnp.exp(dt * A)                                   # [B, H]
    xg = _by_group(x.astype(f32) * dt[..., None], G)          # [B,G,H/G,P]
    hg = _by_group(h, G) * _by_group(decay, G)[..., None, None]
    hg = hg + xg[..., None] * Bm.astype(f32)[:, :, None, None, :]
    y = jnp.sum(hg * Cm.astype(f32)[:, :, None, None, :], axis=-1)
    h = hg.reshape(h.shape)
    return y.reshape(x.shape) + D[:, None] * x.astype(f32), h


# -- the step over the pool's entries where they lie: the kernel -------------

_LANES = 128                    # a vector register's lanes
_SUBLANES = 8                   # ... and its float32 sublanes
_TILE_BYTES = 512 << 10         # one tile of h in vector memory, at most
_AHEAD = 2                      # tiles on their way in while one is computed
_SLOTS = _AHEAD + 2             # ... and one on its way back
_CHUNKS_ABREAST = 4             # chunks of rows an iteration of a tile's loop
# the kernel takes the batch in whole groups of rows: a deployment's decode
# programs (1, 2, 4, 8, 16 rows) then bind ONE trace of it, and a padded
# row costs it nothing (ops/retention.py ``_ROW_GROUP`` is the precedent)
_ROW_GROUP = 16
# what the kernel may hold in vector memory (ops/retention.py allows itself
# the same): the tiles, and the batch's dt x, B, C in and y out
_VMEM_BYTES = 12 * 1024 * 1024


def heads_per_tile(heads: int, head_dim: int, groups: int, state: int) -> int:
    """Heads in one tile of ``h`` ``[tile, P, N]``: a whole number of tiles
    an entry and of ``_update_tile``'s iterations a tile
    (``_rows_abreast``), the largest of at most ``_TILE_BYTES`` -- a
    quarter of the entry, 16 heads, at the published widths: a larger tile
    leaves the first read and the last write of a call longer in the open
    (a call of 9 live rows: 63.9 us, halves 64.6, whole entries 68.0 with
    one tile on its way in, where the DMA alone takes 63.4: PERF.md
    section 6, PR 47).  0: none fits."""
    rows = _rows_abreast(head_dim, heads // groups)
    fit = [c for c in range(1, heads + 1)
           if heads % c == 0 and c * head_dim % rows == 0
           and 4 * c * head_dim * state <= _TILE_BYTES]
    return max(fit, default=0)


def step_supported(*, backend: str, state_dtype: Any, heads: int,
                   head_dim: int, groups: int, state: int,
                   mesh: Optional[Any] = None, rows: int = 1) -> bool:
    """True where a call of one position a row runs the kernel
    (``ssm_step_pool``), False where ``ssm_step`` over gathered rows
    serves.  Decided from what the caller can observe, as
    ops.retention.step_supported decides for a retention state: a TPU
    backend (Mosaic), no mesh (a Mosaic call does not partition under
    GSPMD), a float32 state (what the tiles and their DMA are laid out
    for), ``N`` whole 128-lane registers and ``P`` whole sublane tiles (a
    head ``[P, N]`` is whole registers), an entry of whole chunks of rows
    (``_chunked``), and tiles that fit vector memory beside ``rows`` padded
    rows' small operands."""
    if backend != "tpu" or mesh is not None:
        return False
    if jnp.dtype(state_dtype) != jnp.float32:
        return False
    if state % _LANES or head_dim % _SUBLANES or not _chunked(
            heads, head_dim, groups):
        return False
    tile = heads_per_tile(heads, head_dim, groups, state)
    rows = -(-rows // _ROW_GROUP) * _ROW_GROUP
    held = 4 * (_SLOTS * tile * head_dim * state        # the tiles of h
                + 2 * rows * heads * head_dim           # dt x in, y out
                + 2 * rows * max(groups, _SUBLANES) * state)    # B, C
    return tile > 0 and held <= _VMEM_BYTES


def _chunked(heads: int, head_dim: int, groups: int) -> bool:
    """Whether an entry ``[H P, N]`` is whole chunks of ``_chunk_rows``
    rows that each read one group and whole heads or a part of one."""
    if heads % groups:
        return False
    R = _chunk_rows(head_dim, heads // groups)
    return (heads // groups * head_dim % R == 0
            and (R % head_dim == 0 or head_dim % R == 0))


def _chunk_rows(head_dim: int, per: int) -> int:
    """Rows of the entry as a matrix ``[H P, N]`` that ``_update_tile``
    takes at once: a register's 128 lanes of them (two heads of 64), or a
    whole group's where that is fewer (a toy)."""
    return min(_LANES, per * head_dim)


def _rows_abreast(head_dim: int, per: int) -> int:
    """Rows an iteration of ``_update_tile``'s loop takes: a few chunks of
    ONE group, side by side."""
    R = _chunk_rows(head_dim, per)
    return R * math.gcd(per * head_dim // R, _CHUNKS_ABREAST)


def _update_tile(tile_ref, t, fresh, decay, xrow, Bs, Cs, *, per: int):
    """Tile ``t`` of one row's entry, ``tile_ref`` [tile, P, N], updated
    where it stands in vector memory, in chunks of ``R`` rows of the matrix
    ``[tile P, N]`` (``_chunk_rows``: they read one group's row of ``Bs`` /
    ``Cs`` [G, N]), a few chunks an iteration of a rolled loop.  What
    varies down the rows -- ``dt x`` -- comes lane-dense (``xrow`` [H P /
    R, R], a chunk a row) and is TURNED: a row laid under itself N times
    and transposed holds each of its values along one row's lanes; the
    read-out goes the other way, the products ``h C`` transposed and
    summed down the sublanes into a lane-dense row of ``y``.  Two
    transposes a chunk cost the vector unit less than a lane's broadcast
    and a sum over the lanes a register (5.0 against 7.9 us a row where
    the DMA takes 6.4: PERF.md section 6, PR 47).  ``decay(head)`` is a
    scalar; ``fresh``: the row starts at 0, so zeros stand for whatever
    the entry holds.  -> this tile's chunks of ``y`` in an array of
    ``xrow``'s shape, zero elsewhere."""
    tile, P, N = tile_ref.shape
    R = xrow.shape[1]
    f32 = jnp.float32
    whole = jax.lax.broadcasted_iota(jnp.int32, xrow.shape, 0)
    pick = jax.lax.broadcasted_iota(jnp.int32, Bs.shape, 0)

    def row_of(rows, index, i):
        """Row ``i`` of ``rows`` [.., W] -> [1, W], by a mask: Mosaic
        loads no single sublane row at a dynamic index."""
        return jnp.sum(jnp.where(index == i, rows, f32(0.0)), axis=0,
                       keepdims=True)

    def chunk(i, y, B_g, C_g):
        first = t * (tile * P) + i * R          # its first row in the entry
        k, head = jax.lax.div(first, R), jax.lax.div(first, P)
        if P <= R:      # whole heads, one decay each
            heads = R // P
            at = (pl.ds(pl.multiple_of(i * heads, heads), heads),)
            d = jnp.concatenate(
                [jnp.full((P, 1), decay(head + u), f32)
                 for u in range(heads)], axis=0)            # [R, 1]
        else:           # a part of one head
            at = (jax.lax.div(i * R, P),
                  pl.ds(pl.multiple_of(jax.lax.rem(i * R, P), R), R))
            d = decay(head)
        old = tile_ref[at]
        S = jnp.where(fresh, f32(0.0), old.reshape(R, N))
        x = jnp.broadcast_to(row_of(xrow, whole, k), (N, R)).T  # [R, N]
        S = d * S + x * B_g
        tile_ref[at] = S.reshape(old.shape)
        yk = jnp.sum((S * C_g).T, axis=0, keepdims=True)
        return jnp.where(whole == k, yk, y)

    # a few chunks of ONE group an iteration, side by side: one chunk's
    # transposes run while the next one's products do (a chunk an
    # iteration waits for its own: 11.8 us a row where 5.0)
    each = _rows_abreast(P, per) // R

    def chunks(j, y):
        g = jax.lax.div(t * (tile * P) + j * (each * R), per * P)
        B_g, C_g = row_of(Bs, pick, g), row_of(Cs, pick, g)
        for u in range(each):
            y = chunk(j * each + u, y, B_g, C_g)
        return y

    return jax.lax.fori_loop(0, tile * P // (each * R), chunks,
                             jnp.zeros(xrow.shape, f32))


def _step_kernel(slot_ref, order_ref, count_ref, fresh_ref, decay_ref, x_ref,
                 b_ref, c_ref, h_hbm, y_ref, ho_hbm, buf, sems, *, per: int):
    """Every live row's entry, tile by tile: ``count * nt`` steps, step tau
    = (row, tile); tile tau is computed (``_update_tile``) in slot tau % 4
    and written back from where it lies while tau + 1 and tau + 2 are on
    their way in (with one on its way the DMA waits for the arithmetic
    between tiles: 71.5 us a call of 9 live rows where 63.9) --
    ``h_hbm`` / ``ho_hbm`` are the same pool, aliased: every tile is read
    once, before it is written once.  The arithmetic hides behind the DMA
    (PERF.md section 6, PR 47), and both loops are rolled: five decode
    programs a deployment lower this body at every boot."""
    H, tile = h_hbm.shape[1], buf.shape[1]
    nt = H // tile
    count = count_ref[0]
    steps = count * nt

    def at(tau):
        r = jax.lax.div(tau, nt)
        return r, tau - r * nt

    def copy(tau, write: bool):
        r, t = at(tau)
        c = jax.lax.rem(tau, _SLOTS)
        where = (slot_ref[order_ref[r]],
                 pl.ds(pl.multiple_of(t * tile, tile), tile))
        if write:
            return pltpu.make_async_copy(buf.at[c], ho_hbm.at[where],
                                         sems.at[1, c])
        return pltpu.make_async_copy(h_hbm.at[where], buf.at[c],
                                     sems.at[0, c])

    y_ref[...] = jnp.zeros_like(y_ref)
    for ahead in range(_AHEAD):
        @pl.when(ahead < steps)
        def _():
            copy(ahead, False).start()

    def step(tau, carry):
        r, t = at(tau)
        b = order_ref[r]

        @pl.when(tau >= 2)
        def _():    # the slot the next tile lands in has been written back
            copy(tau - 2, True).wait()

        @pl.when(tau + _AHEAD < steps)
        def _():
            copy(tau + _AHEAD, False).start()

        copy(tau, False).wait()
        y_ref[b] += _update_tile(
            buf.at[jax.lax.rem(tau, _SLOTS)], t, fresh_ref[b] != 0,
            lambda head: decay_ref[b * H + head], x_ref[b], b_ref[b],
            c_ref[b], per=per)
        copy(tau, True).start()
        return carry

    jax.lax.fori_loop(0, steps, step, 0)

    @pl.when(steps >= 2)
    def _():
        copy(steps - 2, True).wait()

    @pl.when(steps >= 1)
    def _():
        copy(steps - 1, True).wait()


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _fused_step(x, dt, A, Bm, Cm, h, slot, fresh, order, count, *,
                interpret: bool = False, tile: Optional[int] = None):
    """The step (without the skip) for the ``count`` live rows
    ``order[:count]`` of the batch, over the pool's entries IN PLACE: x [B,
    H, P]; dt [B, H] float32; A [H]; Bm, Cm [B, G, N]; row b's state is
    entry ``slot[b]`` of ``h`` [num_blocks, H, P, N], zero where
    ``fresh[b]`` -> (y [B, H, P] float32, zero for a row that is not live;
    h').  ``tile`` is the heads a tile holds (``heads_per_tile``).  Jitted
    so that a program's layers share one trace and one lowering of the
    kernel."""
    B, H, P = x.shape
    G, N = Bm.shape[1:]
    tile = tile or heads_per_tile(H, P, G, N)
    if (not _chunked(H, P, G) or not tile or H % tile
            or tile * P % _rows_abreast(P, H // G)):
        raise ValueError(
            f"{H} heads of {P} in {G} groups are no whole number of tiles "
            f"of {tile} heads in whole chunks of rows; take ssm_step over "
            "gathered rows (step_supported)")
    f32 = jnp.float32
    R = _chunk_rows(P, H // G)
    # dt x lane-dense, ``R`` rows of the entry's matrix a row
    xdt = (x.astype(f32) * dt[..., None]).reshape(B, H * P // R, R)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, h = pl.pallas_call(
        functools.partial(_step_kernel, per=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem,
                      vmem, hbm],
            out_specs=(vmem, hbm),
            scratch_shapes=[
                pltpu.VMEM((_SLOTS, tile, P, N), f32),      # tiles of h
                pltpu.SemaphoreType.DMA((2, _SLOTS)),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct(xdt.shape, f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)),
        # operand 8 (after the four scalar ones, the decays, dt x, B and C)
        # is the pool's entries: updated where they lie
        input_output_aliases={8: 1},
        interpret=interpret,
    )(slot.astype(jnp.int32), order.astype(jnp.int32),
      count.astype(jnp.int32).reshape(1), fresh.astype(jnp.int32),
      jnp.exp(dt * A).astype(f32).reshape(B * H), xdt, Bm.astype(f32),
      Cm.astype(f32), h)
    return y.reshape(B, H, P), h


def ssm_step_pool(x, dt, A, Bm, Cm, D, h, slot, start, live,
                  interpret: bool = False):
    """One position a row over the POOL (the module's text): x [B, H, P];
    dt [B, H] float32; A, D [H] float32; Bm, Cm [B, G, N]; row b's state
    is entry ``slot[b]`` of ``h`` [num_blocks, H, P, N] float32, taken as
    zero where ``start[b]`` is 0; a row that is not ``live`` is skipped:
    no entry is read or written for it -> (y [B, H, P] float32, h').  The
    caller asks ``step_supported`` first (models/generate.py ``ssm_fused``:
    only a program's caller sees the backend it is lowered for and the
    mesh); ``interpret`` runs the kernel in Pallas interpret mode (tests
    on the CPU)."""
    B = x.shape[0]
    order = jnp.argsort(~live, stable=True)             # the live rows first

    def rows(a):        # the batch in whole groups: ``_ROW_GROUP``
        return jnp.pad(a, [(0, -B % _ROW_GROUP)] + [(0, 0)] * (a.ndim - 1))

    y, h = _fused_step(
        rows(x), rows(dt), A, rows(Bm), rows(Cm), h, rows(slot),
        rows(start == 0), rows(order), jnp.sum(live), interpret=interpret)
    return y[:B] + D[:, None] * x.astype(jnp.float32), h


def _chunk(x, dt, A, Bm, Cm, h):
    """The chunked form over ONE chunk (without the skip): x [B, W, H, P];
    dt [B, W, H] float32; Bm, Cm [B, W, G, N]; h [B, H, P, N] float32 ->
    (y [B, W, H, P] float32, h')."""
    f32 = jnp.float32
    Bsz, W, H, P = x.shape
    G = Bm.shape[2]
    cum = jnp.cumsum(dt * A, axis=1)                          # [B, W, H] <= 0
    cum = cum.transpose(0, 2, 1)                              # [B, H, W]
    # the masked decay from position s to position t >= s, a head
    causal = jnp.arange(W)[:, None] >= jnp.arange(W)[None, :]
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                      # [B, H, t, s]
    scores = jnp.einsum("btgn,bsgn->bgts", Cm, Bm,
                        preferred_element_type=f32)           # [B, G, t, s]
    mix = (_by_group(decay, G) * scores[:, :, None]).reshape(Bsz, H, W, W)
    xdt = x.astype(f32) * dt[..., None]                       # [B, W, H, P]
    y = jnp.einsum("bhts,bshp->bthp", mix.astype(x.dtype),
                   xdt.astype(x.dtype), preferred_element_type=f32)
    # the carried state's part: exp(c_t) C_t . h_0
    hg = _by_group(h, G)                                      # [B,G,H/G,P,N]
    y0 = jnp.einsum("btgn,bgkpn->btgkp", Cm.astype(f32), hg,
                    preferred_element_type=f32).reshape(Bsz, W, H, P)
    y = y + y0 * jnp.exp(cum).transpose(0, 2, 1)[..., None]
    # the state the chunk leaves
    last = cum[..., -1:]                                      # [B, H, 1]
    left = jnp.exp(last - cum).transpose(0, 2, 1)             # [B, W, H]
    xg = (xdt * left[..., None]).reshape(Bsz, W, G, H // G, P)
    new = jnp.einsum("bsgkp,bsgn->bgkpn", xg.astype(x.dtype), Bm,
                     preferred_element_type=f32)
    h = h * jnp.exp(last)[..., None] + new.reshape(h.shape)
    return y, h


def ssm_chunk(x, dt, A, Bm, Cm, D, h, chunk: int = CHUNK):
    """``W`` positions a row at once.  x [B, W, H, P]; dt [B, W, H]
    float32, 0 at a position that is none; A, D [H] float32; Bm, Cm
    [B, W, G, N]; h [B, H, P, N] float32 -> (y [B, W, H, P] float32, h')."""
    W = x.shape[1]
    if W <= chunk:
        y, h = _chunk(x, dt, A, Bm, Cm, h)
    else:
        pad = -W % chunk            # positions of dt 0: no positions at all

        def chunks(t):
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return jnp.moveaxis(
                t.reshape(t.shape[0], -1, chunk, *t.shape[2:]), 1, 0)

        def one(h, part):
            y, h = _chunk(*part[:2], A, *part[2:], h)
            return h, y

        h, y = jax.lax.scan(one, h, tuple(map(chunks, (x, dt, Bm, Cm))))
        y = jnp.moveaxis(y, 0, 1).reshape(x.shape[0], -1, *x.shape[2:])[:, :W]
    return y + D[:, None] * x.astype(jnp.float32), h


def ssm_scan(x, dt, A, Bm, Cm, D, h):
    """The recurrence position by position (a ``lax.scan`` of
    ``ssm_step``): what both forms above are tested against.  Arguments and
    results as ``ssm_chunk``'s."""

    def one(h, part):
        y, h = ssm_step(part[0], part[1], A, part[2], part[3], D, h)
        return h, y

    h, y = jax.lax.scan(one, h, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), h
