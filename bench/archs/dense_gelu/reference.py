"""The plain reference of the ``dense_gelu`` block: what a configuration
file that names ``"arch": "dense_gelu"`` DECLARES, in straightforward
``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no cache, no paging, no
batching tricks, no code of the program under test.  Sizes come from the
configuration file (its published keys), never from the program's own
``cfg``.

The declared block ("<model>'s sizes through the repo's block"):
token embedding -> N x [ pre-RMSNorm (no bias, eps 1e-6) -> fused bias-free
QKV -> rotary embedding over the whole head (half-split convention, base
``rope_theta``) -> grouped-query causal attention -> bias-free output
projection -> residual -> pre-RMSNorm -> two-matrix tanh-GELU FFN ->
residual ] -> final RMSNorm -> tied unembedding (``x @ embed.T``).
Departures from the published StarCoder2 block are listed in each
configuration file under ``departures``; they are not implemented here
either, because the reference follows what is declared.

Weights are read layer by layer (``params["l<i>"]`` with ``ln1``, ``wqkv``,
``wo``, ``ln2``, ``w1``, ``w2``; ``embed``; ``ln_f``) and cast to float32 one
layer at a time, so a full-width model fits beside its bf16 weights."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rmsnorm(x, w, eps=1e-6):
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * w


def _rope(x, positions, theta):
    """x [B, S, H, hd]; rotate pairs (i, i + hd/2) by position * theta^(-i/(hd/2))."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # [S, half]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta"))
def layer(lp, x, *, n_heads: int, n_kv: int, theta: float):
    """One declared block on x [B, S, D] (float32), full causal attention."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        B, S, D = x.shape
        hd = D // n_heads
        h = _rmsnorm(x, lp["ln1"])
        qkv = h @ lp["wqkv"]
        q = qkv[..., :D].reshape(B, S, n_heads, hd)
        k = qkv[..., D:D + n_kv * hd].reshape(B, S, n_kv, hd)
        v = qkv[..., D + n_kv * hd:].reshape(B, S, n_kv, hd)
        pos = jnp.arange(S)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = n_heads // n_kv
        k = jnp.repeat(k, g, axis=2)   # head h reads KV head h // g
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        causal = pos[:, None] >= pos[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, D)
        x = x + a @ lp["wo"]
        h = _rmsnorm(x, lp["ln2"])
        u = jax.nn.gelu(h @ lp["w1"], approximate=True)
        return x + u @ lp["w2"]


@jax.jit
def head(embed, ln_f, x, at):
    """The final norm and the tied unembedding at the positions ``at``
    [B, A] of x [B, S, D] only: nothing of [B, S, V] is ever held."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        x = _rmsnorm(x, ln_f.astype(jnp.float32))
        return x @ embed.astype(jnp.float32).T


def forward(params, tokens, config: dict, at, lengths=None):
    """tokens [B, S] int32, at [B, A] int32 -> the logits after the
    positions ``at`` of each row, [B, A, V] float32, at the sizes ``config``
    (a configuration file's document) publishes.  ``lengths`` [B], each
    row's true length before the pad, is not read: under a causal mask no
    judged position sees the pad."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        x = layer(params[f"l{i}"], x,
                  n_heads=config["num_attention_heads"],
                  n_kv=config["num_key_value_heads"],
                  theta=float(config["rope_theta"]))
    return head(params["embed"], params["ln_f"], x, at)


def row_bytes(config: dict, S: int, judged: int) -> int:
    """What one row of ``S`` positions holds at its fullest, inside
    ``layer`` (float32): the scores and their softmax [H, S, S]; the
    stream, its norm, q and the repeated k and v, the attention's output
    [S, D] each; qkv; the FFN's hidden [S, F] before and after the GELU —
    and the ``judged`` positions' logits.  lib/sample.py sizes a group of
    rows by it."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    hd = D // H
    F, V = config["intermediate_size"], config["vocab_size"]
    qkv = D + 2 * config["num_key_value_heads"] * hd
    return 4 * (2 * H * S * S + S * (6 * D + qkv + 2 * F) + judged * V)
