"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

Long sequences are sharded along the sequence dimension, one block per chip.
Each chip keeps its Q block resident and the K/V blocks rotate around the
ring via ``lax.ppermute`` (neighbour-to-neighbour ICI hops, overlapping
compute with transfer); softmax is accumulated online flash-style
(running max ``m``, normaliser ``l``, weighted sum ``o``), so the full
[S, S] score matrix never materialises and memory stays O(S_local * d).

The reference has no sequence models (SURVEY.md §2.7: SP/CP absent —
pre-LLM serving), but long-context serving is first-class here: any graph
node whose unit calls ``ring_attention`` can span a pod slice's ``sp`` axis.

Causality across blocks uses global position offsets: chip i holds positions
[i*S_local, (i+1)*S_local); a rotated K/V block is masked per-element by
(q_pos >= k_pos).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from seldon_core_tpu.parallel.mesh import shard_map as compat_shard_map

__all__ = ["ring_attention", "ring_attention_sharded"]

_NEG_INF = -1e30


def _block_attend(q, k, v, q_offset, k_offset, causal: bool):
    """Scores of one (Q block, K/V block) pair plus flash-style stats.

    q: [B, H, Sq, D], k/v: [B, H, Sk, D] -> (m, l, o) partials."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale  # [B,H,Sq,Sk]
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])[:, None]
        k_pos = k_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    # fully-masked rows (causal, block entirely in the future): zero them
    p = jnp.where(m[..., None] <= _NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)  # noqa: E741
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.where(m1 <= _NEG_INF / 2, 0.0, jnp.exp(m1 - m))
    a2 = jnp.where(m2 <= _NEG_INF / 2, 0.0, jnp.exp(m2 - m))
    l = a1 * l1 + a2 * l2  # noqa: E741
    o = a1[..., None] * o1 + a2[..., None] * o2
    return m, l, o


def ring_attention(
    q, k, v, axis_name: str, causal: bool = True
):
    """Attention over a sequence sharded on ``axis_name``.

    Call INSIDE shard_map/pjit with q/k/v local blocks of shape
    [B, H, S_local, D].  Returns the local output block [B, H, S_local, D].
    """
    n_blocks = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_offset = my_idx * s_local

    # start with my own block
    m, l, o = _block_attend(q, k, v, q_offset, my_idx * s_local, causal)

    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    def step(i, carry):
        m, l, o, k_blk, v_blk, k_idx = carry
        # rotate K/V to the next chip (neighbour ICI hop)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        k_idx = jax.lax.ppermute(k_idx, axis_name, perm)
        m2, l2, o2 = _block_attend(q, k_blk, v_blk, q_offset, k_idx * s_local, causal)
        m, l, o = _merge(m, l, o, m2, l2, o2)
        return m, l, o, k_blk, v_blk, k_idx

    m, l, o, _, _, _ = jax.lax.fori_loop(
        0, n_blocks - 1, step, (m, l, o, k, v, my_idx)
    )
    return o / jnp.maximum(l, 1e-30)[..., None]


def ring_attention_sharded(
    mesh: Mesh, axis: str = "sp", causal: bool = True
):
    """Standalone sharded attention: [B, H, S, D] global arrays, S sharded
    over ``axis``.  For use outside an enclosing shard_map."""

    @partial(
        compat_shard_map,
        mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
    )
    def fn(q, k, v):
        return ring_attention(q, k, v, axis, causal=causal)

    return fn
