"""The plain reference of the ``sdar_moe`` block (SDAR-30B-A3B-Chat,
``model_type`` ``sdar_moe``: the Qwen3-MoE layer under a block-causal mask)
in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")`` — no cache, no paging, no
sorting of tokens by expert, no code of the program under test.  Sizes
come from the configuration file's published keys, never from the
program's ``cfg``.

For the hidden state ``x`` [S, D], ``eps`` = ``rms_norm_eps``,
``rms(t, w) = t * rsqrt(mean(t^2) + eps) * w``:

  * ``h = rms(x, ln1)``; ``q = h Wq`` -> ``num_attention_heads`` heads of
    ``head_dim``; ``k = h Wk``, ``v = h Wv`` -> ``num_key_value_heads``
    heads; no biases (``attention_bias`` false);
  * ``q = rms(q, q_norm)``, ``k = rms(k, k_norm)`` over each head's
    ``head_dim``, one weight vector each a layer; half-split rotary
    embedding at ``rope_theta`` on the whole head;
  * scores ``q k^T / sqrt(head_dim)``, a query head reading KV head
    ``h // (H / KV)``; position ``i`` sees key ``j`` iff ``j < (i // L + 1)
    * L`` (``L`` = ``block_length``: up to the END of its own block, the
    later positions of that block too) and ``j`` is a position the row
    really holds (``lengths``: a padded group's pad is no key); softmax;
    ``x = x + (P v) Wo``;
  * ``h = rms(x, ln2)``; ``g = softmax(h Wr)`` over all ``num_experts``;
    the ``num_experts_per_tok`` largest; ``w_e = g_e / sum of the chosen``
    (``norm_topk_prob``); ``y = sum_e w_e (silu(h W_gate,e) * (h W_up,e))
    W_down,e``; ``x = x + y``.  Every layer is routed (``decoder_sparse_step``
    1, ``mlp_only_layers`` []), there is no shared expert;
  * after the last layer ``rms(x, ln_f) W_head``, ``W_head`` its own matrix
    (``tie_word_embeddings`` false) — at the judged positions only.

The expert layer is a loop over the experts (a ``lax.scan``): each expert
in turn on every token, weighted by the token's weight for it, which is 0
where the token did not choose it.  Sixteen times the arithmetic of the
chosen eight, and nothing to get wrong.

Weights are read as the program holds them (``params["l<i>"]``: ``ln1``,
``wqkv`` = q | k | v side by side, ``q_norm``, ``k_norm``, ``wo``, ``ln2``,
``router`` [D, E], ``e_gate_up`` [E, D, 2F] = gate | up side by side,
``e_down`` [E, F, D]; ``embed``, ``ln_f``, ``lm_head`` [D, V]) and cast to
float32 a layer — the experts an expert — at a time, so a full-width model
fits beside its bf16 weights."""

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


def _experts(h, lp, top: int, norm: bool):
    """h [T, D] -> the routed experts' sum [T, D]."""
    f32 = jnp.float32
    gates = jax.nn.softmax(h @ lp["router"].astype(f32), axis=-1)  # [T, E]
    best, idx = jax.lax.top_k(gates, top)
    if norm:
        best = best / best.sum(-1, keepdims=True)
    E = gates.shape[-1]
    # [T, E]: a token's weight for each expert, 0 where it was not chosen
    weight = jnp.einsum("tk,tke->te", best, jax.nn.one_hot(idx, E, dtype=f32))

    def one(y, expert):
        gate_up, down, w = expert
        gate, up = jnp.split(gate_up.astype(f32), 2, axis=-1)
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down.astype(f32)
        return y + w[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (lp["e_gate_up"], lp["e_down"], weight.T))
    return y


@functools.partial(jax.jit, static_argnames=(
    "H", "KV", "hd", "theta", "eps", "block", "top", "norm"))
def layer(lp, x, lengths, *, H, KV, hd, theta, eps, block, top, norm):
    """One published layer on x [B, S, D] (float32)."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        B, S, D = x.shape
        h = _rms(x, lp["ln1"].astype(f32), eps)
        qkv = h @ lp["wqkv"].astype(f32)
        q = qkv[..., :H * hd].reshape(B, S, H, hd)
        k = qkv[..., H * hd:(H + KV) * hd].reshape(B, S, KV, hd)
        v = qkv[..., (H + KV) * hd:].reshape(B, S, KV, hd)
        q = _rope(_rms(q, lp["q_norm"].astype(f32), eps), theta)
        k = _rope(_rms(k, lp["k_norm"].astype(f32), eps), theta)
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        here = jnp.arange(S)
        seen = here[None, :] < ((here // block + 1) * block)[:, None]
        seen = seen[None] & (here[None, None, :] < lengths[:, None, None])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(seen[:, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + a.reshape(B, S, H * hd) @ lp["wo"].astype(f32)
        h = _rms(x, lp["ln2"].astype(f32), eps)
        y = _experts(h.reshape(B * S, D), lp, top, norm)
        return x + y.reshape(B, S, D)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(lm_head, ln_f, x, at, *, eps):
    """The final norm and the untied unembedding at the positions ``at``
    [B, A] of x [B, S, D] only: nothing of [B, S, V] is ever held."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return (_rms(x, ln_f.astype(jnp.float32), eps)
                @ lm_head.astype(jnp.float32))


def forward(params, tokens, config: dict, at, lengths):
    """tokens [B, S] int32 (a mask id where a pass saw one), at [B, A],
    lengths [B] (each row's true length before the right pad) -> the
    logits after the positions ``at`` of each row, [B, A, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        x = layer(params[f"l{i}"], x, lengths,
                  H=config["num_attention_heads"],
                  KV=config["num_key_value_heads"], hd=config["head_dim"],
                  theta=float(config["rope_theta"]),
                  eps=float(config["rms_norm_eps"]),
                  block=config["block_length"],
                  top=config["num_experts_per_tok"],
                  norm=bool(config["norm_topk_prob"]))
    return head(params["lm_head"], params["ln_f"], x, at,
                eps=float(config["rms_norm_eps"]))


def row_bytes(config: dict, S: int, judged: int) -> int:
    """What one row of ``S`` positions holds at its fullest inside
    ``layer`` (float32): the scores and their softmax [H, S, S]; the
    stream, its norm and the experts' running sum [S, D]; q, the repeated
    k and v and the attention's output [S, H * hd]; the gates, the weights
    and the one-hot of the chosen [S, (2 + k) * E]; ONE expert's hidden
    [S, 3 * F] (the loop holds one at a time) — and the ``judged``
    positions' logits.  lib/sample.py sizes a group of rows by it."""
    D, H, hd = (config["hidden_size"], config["num_attention_heads"],
                config["head_dim"])
    E, F = config["num_experts"], config["moe_intermediate_size"]
    k = config["num_experts_per_tok"]
    return 4 * (2 * H * S * S
                + S * (5 * D + 5 * H * hd + (2 + k) * E + 3 * F)
                + judged * config["vocab_size"])
