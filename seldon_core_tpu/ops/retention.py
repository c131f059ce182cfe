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
0): a padded row costs nothing and touches nothing, and the temporaries are
one row's, not the batch's."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

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


def retention(q, k, v, log_g, state, slot, start, width):
    """q [B, KV, G, W, d], k, v [B, KV, W, d], log_g [B, KV, W] float32
    over ``state`` = {"s", "z"} (the module's text): row b's state is entry
    ``slot[b]``, taken as zero where ``start[b]`` is 0, and its first
    ``width[b]`` positions are valid -- a row of width 0 is skipped: its
    ``y`` is zero and no entry is written.  -> (y [B, KV, G, W, d] in q's
    dtype, state')."""
    B, KV, G, W, d = q.shape
    form = _step if W == 1 else _chunk
    live = width > 0
    order = jnp.argsort(~live, stable=True)             # the live rows first

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
