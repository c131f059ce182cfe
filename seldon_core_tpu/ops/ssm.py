"""The Mamba-2 state-space recurrence, from a carried state and leaving one.

For one sequence, ``H`` heads of width ``P``, a state of ``N`` a head, ``G``
groups of B and C (head ``h`` reads group ``h // (H / G)``), with a step
size ``dt_t`` [H] > 0 and ``A`` [H] < 0, both float32:

    h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t          [H, P, N], float32
    y_t = h_t C_t + D x_t                                 [H, P]

in two forms that give the same numbers:

  * ``ssm_step``: one position a row (a decode step) -- the recurrence as
    written, element by element in float32;
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

Plain ``jax.numpy``: the state is gathered, updated and scattered by the
caller.  Every decay, the cumulative sums and the state are float32; ``x``,
``B`` and ``C`` enter the matmuls in the dtype they come in (the model's),
accumulated in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssm_step", "ssm_chunk", "ssm_scan", "CHUNK"]

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
