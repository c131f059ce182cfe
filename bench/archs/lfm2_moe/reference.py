"""The plain reference of the ``lfm2_moe`` block (LiquidAI LFM2-8B-A1B,
``model_type`` ``lfm2_moe``: gated short-convolution layers and GQA layers
by ``layer_types``, two leading dense layers, then experts chosen by a
sigmoid router with a selection bias) in straightforward ``jax.numpy``,
float32 under ``jax.default_matmul_precision("highest")`` — no cache, no
state, no chunking, no sorting of tokens by expert, no code of the program
under test.  Sizes and the layer pattern come from the configuration
file's published keys (``layer_types`` is the file's LIST, not the string
the unit was given), never from the program's ``cfg``.

For the hidden state ``x`` [S, D], ``eps`` = ``norm_eps``, ``rms(t, w) = t
* rsqrt(mean(t^2) + eps) * w``, no bias anywhere (``conv_bias`` false):

  * layer i: ``h = x + mixer_i(rms(x, ln1))``; ``x = h + ffn_i(rms(h,
    ln2))``;
  * ``mixer`` where ``layer_types[i] == "conv"``: ``[b | c | u] = t
    W_in`` (D -> 3D, split in that order); ``z = b * u``; ``y_t = sum_j
    w[j] * z_(t - (K-1) + j)`` for ``j = 0 .. K-1``, ``K`` =
    ``conv_L_cache`` — a depthwise causal convolution written as an
    explicit sum of shifted copies, ``z`` before position 0 being zero;
    ``(c * y) W_out``;
  * ``mixer`` where ``"full_attention"``: ``q = t Wq`` ->
    ``num_attention_heads`` heads of ``hidden_size //
    num_attention_heads``; ``k``, ``v`` -> ``num_key_value_heads`` heads;
    ``q = rms(q, q_norm)``, ``k = rms(k, k_norm)`` over each head;
    half-split rotary embedding at ``rope_theta``; causal scores ``q k^T /
    sqrt(head)``, a query head reading KV head ``h // (H / KV)``; ``(P v)
    Wo``;
  * ``ffn`` for ``i < num_dense_layers``: ``(silu(t W1) * (t W3)) W2``,
    width ``intermediate_size``;
  * ``ffn`` otherwise: ``s = sigmoid(t Wr)`` over ``num_experts``; the
    ``num_experts_per_tok`` largest of ``s + expert_bias``
    (``use_expert_bias``); weights ``s`` at the chosen — the UNBIASED
    scores — divided by ``(their sum + 1e-6)`` (``norm_topk_prob``), times
    ``routed_scaling_factor``; ``sum_e w_e (silu(t W_gate,e) * (t
    W_up,e)) W_down,e``; no shared expert;
  * after the last layer ``rms(x, ln_f) embed^T`` (the embedding tied) —
    at the judged positions only.

The causal mask and the causal convolution keep a padded group's pad out
of every real position, so ``lengths`` is not read.  The router scores in
float32 here and in the program (the published module scores in the
model's dtype; the configuration file notes it).  The expert layer is a
loop over the experts (a ``lax.scan``): each expert in turn on every
token, weighted by the token's weight for it, 0 where it was not chosen.

Weights are read as the program holds them (``params["l<i>"]``: ``ln1``,
``ln2``; a conv layer's ``conv_in`` [D, 3D], ``conv_w`` [K, D] with the
LAST tap on the position itself, ``conv_out`` [D, D]; an attention
layer's ``wqkv`` = q | k | v side by side, ``q_norm``, ``k_norm``, ``wo``;
a dense layer's ``w1``, ``w3`` [D, F], ``w2`` [F, D]; a routed layer's
``router`` [D, E], ``expert_bias`` [E], ``e_gate_up`` [E, D, 2F] = gate |
up side by side, ``e_down`` [E, F, D]; ``embed``, ``ln_f``) and cast to
float32 a layer — the experts an expert — at a time."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, hd]: pairs (i, i + hd/2) turned by position *
    theta^(-i / (hd/2))."""
    half = x.shape[-1] // 2
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _conv(lp, t):
    """The gated short convolution on t [B, S, D]."""
    f32 = jnp.float32
    S = t.shape[1]
    b, c, u = jnp.split(t @ lp["conv_in"].astype(f32), 3, axis=-1)
    z = b * u
    taps = lp["conv_w"].astype(f32)                     # [K, D]
    K = taps.shape[0]
    y = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j                 # tap j reads the position t - back
        shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :S]
        y = y + taps[j] * shifted
    return (c * y) @ lp["conv_out"].astype(f32)


def _attention(lp, t, H, KV, theta, eps):
    f32 = jnp.float32
    B, S, D = t.shape
    hd = D // H
    qkv = t @ lp["wqkv"].astype(f32)
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + KV) * hd].reshape(B, S, KV, hd)
    v = qkv[..., (H + KV) * hd:].reshape(B, S, KV, hd)
    q = _rope(_rms(q, lp["q_norm"].astype(f32), eps), theta)
    k = _rope(_rms(k, lp["k_norm"].astype(f32), eps), theta)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    here = jnp.arange(S)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(here[None, :] <= here[:, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(B, S, H * hd) @ lp["wo"].astype(f32)


def _experts(lp, t, top: int, norm: bool, scale: float, biased: bool):
    """t [T, D] -> the routed experts' sum [T, D]."""
    f32 = jnp.float32
    score = jax.nn.sigmoid(t @ lp["router"].astype(f32))        # [T, E]
    chooser = score + lp["expert_bias"].astype(f32) if biased else score
    _, idx = jax.lax.top_k(chooser, top)
    best = jnp.take_along_axis(score, idx, axis=-1)
    if norm:
        best = best / (best.sum(-1, keepdims=True) + 1e-6)
    best = best * scale
    E = score.shape[-1]
    # [T, E]: a token's weight for each expert, 0 where it was not chosen
    weight = jnp.einsum("tk,tke->te", best, jax.nn.one_hot(idx, E, dtype=f32))

    def one(y, expert):
        gate_up, down, w = expert
        gate, up = jnp.split(gate_up.astype(f32), 2, axis=-1)
        out = (jax.nn.silu(t @ gate) * (t @ up)) @ down.astype(f32)
        return y + w[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(t),
                        (lp["e_gate_up"], lp["e_down"], weight.T))
    return y


@functools.partial(jax.jit, static_argnames=(
    "mixer", "dense", "H", "KV", "theta", "eps", "top", "norm", "scale",
    "biased"))
def layer(lp, x, *, mixer, dense, H, KV, theta, eps, top, norm, scale,
          biased):
    """One published layer on x [B, S, D] (float32)."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        B, S, D = x.shape
        t = _rms(x, lp["ln1"].astype(f32), eps)
        if mixer == "conv":
            x = x + _conv(lp, t)
        elif mixer == "full_attention":
            x = x + _attention(lp, t, H, KV, theta, eps)
        else:
            raise ValueError(f"layer_types names {mixer!r}")
        t = _rms(x, lp["ln2"].astype(f32), eps)
        if dense:
            y = ((jax.nn.silu(t @ lp["w1"].astype(f32))
                  * (t @ lp["w3"].astype(f32))) @ lp["w2"].astype(f32))
        else:
            y = _experts(lp, t.reshape(B * S, D), top, norm, scale,
                         biased).reshape(B, S, D)
        return x + y


@functools.partial(jax.jit, static_argnames=("eps",))
def head(embed, ln_f, x, at, *, eps):
    """The final norm and the tied unembedding at the positions ``at``
    [B, A] of x [B, S, D] only: nothing of [B, S, V] is ever held."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return (_rms(x, ln_f.astype(jnp.float32), eps)
                @ embed.astype(jnp.float32).T)


def forward(params, tokens, config: dict, at, lengths):
    """tokens [B, S] int32, at [B, A], lengths [B] (not read: every mask
    here is causal) -> the logits after the positions ``at`` of each row,
    [B, A, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        x = layer(params[f"l{i}"], x,
                  mixer=config["layer_types"][i],
                  dense=i < config["num_dense_layers"],
                  H=config["num_attention_heads"],
                  KV=config["num_key_value_heads"],
                  theta=float(config["rope_theta"]),
                  eps=float(config["norm_eps"]),
                  top=config["num_experts_per_tok"],
                  norm=bool(config["norm_topk_prob"]),
                  scale=float(config["routed_scaling_factor"]),
                  biased=bool(config["use_expert_bias"]))
    return head(params["embed"], params["ln_f"], x, at,
                eps=float(config["norm_eps"]))


def row_bytes(config: dict, S: int, judged: int) -> int:
    """What one row of ``S`` positions holds at its fullest inside
    ``layer`` (float32): an attention layer's scores and their softmax
    [H, S, S] with q, the repeated k and v and the output [S, 4 * D]; the
    stream, its norm and a running sum [S, 3 * D]; a conv layer's b | c |
    u, z, a shifted copy and y [S, 6 * D] (less than the attention's); a
    dense layer's hidden [S, 3 * I] or ONE expert's [S, 3 * F] (the loop
    holds one at a time) with the scores, the weights and the one-hot of
    the chosen [S, (2 + k) * E] — and the ``judged`` positions' logits.
    lib/sample.py sizes a group of rows by it."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    E, F = config["num_experts"], config["moe_intermediate_size"]
    I, k = config["intermediate_size"], config["num_experts_per_tok"]
    return 4 * (2 * H * S * S
                + S * (9 * D + max(3 * I, 3 * F + (2 + k) * E))
                + judged * config["vocab_size"])
