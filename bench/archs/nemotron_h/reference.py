"""The plain reference of the ``nemotron_h`` block (NVIDIA-Nemotron-3-Nano-
30B-A3B, ``model_type`` ``nemotron_h``: ONE sub-layer a block by
``hybrid_override_pattern`` -- ``M`` a Mamba-2 state-space mixer, ``E`` an
expert layer with a shared expert, ``*`` attention without a rotary
embedding) in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")`` -- no cache, no carried state,
no chunks, no sorting of tokens by expert, no code of the program under
test.  Sizes and the layer pattern come from the configuration file's
published keys, never from the program's ``cfg``.

For the hidden state ``x`` [S, D], ``eps`` = ``layer_norm_epsilon``,
``rms(t, w) = t * rsqrt(mean(t^2) + eps) * w``, no bias but the
convolution's:

  * block i: ``x = x + sublayer_i(rms(x, norm_i))``, the sub-layer by
    letter ``i`` of ``hybrid_override_pattern`` (the first
    ``num_hidden_layers`` of it);
  * ``M``: ``H`` = ``mamba_num_heads``, ``P`` = ``mamba_head_dim``, ``G`` =
    ``n_groups``, ``N`` = ``ssm_state_size``; ``[z | xBC | dt] = t W_in``
    (D -> H P + (H P + 2 G N) + H, split in that order); ``xBC =
    silu(conv(xBC) + b)``, a depthwise causal convolution of
    ``conv_kernel`` taps written as an explicit sum of shifted copies,
    ``xBC`` before position 0 being zero; ``[x | B | C] = xBC``; ``dt =
    softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one a head; position by
    position (a ``lax.scan``) ``h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x)
    B_t`` from ``h = 0``, head ``h`` reading group ``h // (H / G)``; ``y_t =
    h_t C_t + D x_t``; ``y = rms_groups(y * silu(z), w)``, the norm over
    each of the ``G`` groups of ``H P / G``; ``y W_out``;
  * ``*``: ``q = t Wq`` -> ``num_attention_heads`` heads of ``head_dim``;
    ``k``, ``v`` -> ``num_key_value_heads`` heads; NO rotary embedding
    (the published module has none); causal scores ``q k^T / sqrt(head)``,
    a query head reading KV head ``h // (H / KV)``; ``(P v) Wo``;
  * ``E``: ``s = sigmoid(t Wr)`` over ALL the router's experts; the
    ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
    (``n_group`` 1: no grouping); weights ``s`` at the chosen -- the
    UNBIASED scores -- divided by ``(their sum + 1e-20)``
    (``norm_topk_prob``), times ``routed_scaling_factor``; ``sum_e w_e
    relu(t W_up,e)^2 W_down,e`` over THE EXPERTS HELD -- the file's
    ``n_routed_experts`` of the router's width, ids ``experts_first`` (0
    where the file does not say) onward: the chip's share, given to the
    reference as it is to the program; what the absent experts would add
    is left out of both -- plus the shared expert ``relu(t S_up)^2
    S_down``, unweighted;
  * after the last block ``rms(x, norm_f) lm_head`` -- at the judged
    positions only.

Every mask here is causal and the recurrence runs left to right, so a
padded group's pad stays out of every real position and ``lengths`` is not
read.  The expert layer is a loop over the held experts (a ``lax.scan``):
each expert in turn on every token, weighted by the token's weight for it,
0 where it was not chosen.

Weights are read as the program holds them (``params["l<i>"]``: a mixer's
norm ``ln1``, an expert layer's ``ln2``; ``M``: ``ssm_in``, ``conv_w``
[K, C] with the LAST tap on the position itself, ``conv_b``, ``A_log``,
``dt_bias``, ``ssm_D``, ``ssm_norm``, ``ssm_out``; ``*``: ``wqkv`` = q | k
| v side by side, ``wo``; ``E``: ``router`` [D, E], ``expert_bias`` [E],
``e_up`` [held, F, D] (each up matrix output-major), ``e_down`` [held, F,
D], ``s_up`` [D, Fs], ``s_down``;
``embed``, ``ln_f``, ``lm_head``) and cast to float32 a layer -- the
experts an expert -- at a time."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mamba(lp, t, H, P, G, N, eps):
    """The Mamba-2 mixer on t [B, S, D]."""
    f32 = jnp.float32
    B, S, _ = t.shape
    inner = H * P
    u = t @ lp["ssm_in"].astype(f32)
    z, xbc, dt = u[..., :inner], u[..., inner:-H], u[..., -H:]
    taps = lp["conv_w"].astype(f32)                       # [K, C]
    K = taps.shape[0]
    conv = jnp.zeros_like(xbc)
    for j in range(K):
        back = K - 1 - j                 # tap j reads the position t - back
        conv = conv + taps[j] * jnp.pad(
            xbc, ((0, 0), (back, 0), (0, 0)))[:, :S]
    xbc = jax.nn.silu(conv + lp["conv_b"].astype(f32))
    x = xbc[..., :inner].reshape(B, S, H, P)
    Bm = jnp.repeat(xbc[..., inner:inner + G * N].reshape(B, S, G, N),
                    H // G, axis=2)                       # [B, S, H, N]
    Cm = jnp.repeat(xbc[..., inner + G * N:].reshape(B, S, G, N),
                    H // G, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))  # [B, S, H]
    A = -jnp.exp(lp["A_log"].astype(f32))                 # [H]

    def position(h, at):
        x_t, b_t, c_t, dt_t = at
        h = (h * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    _, y = jax.lax.scan(
        position, jnp.zeros((B, H, P, N), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + lp["ssm_D"].astype(f32)[:, None] * x
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    y = y.reshape(B, S, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(B, S, inner) * lp["ssm_norm"].astype(f32)
    return y @ lp["ssm_out"].astype(f32)


def _attention(lp, t, H, KV, hd):
    f32 = jnp.float32
    B, S, _ = t.shape
    qkv = t @ lp["wqkv"].astype(f32)
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + KV) * hd].reshape(B, S, KV, hd)
    v = qkv[..., (H + KV) * hd:].reshape(B, S, KV, hd)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    here = jnp.arange(S)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(here[None, :] <= here[:, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(B, S, H * hd) @ lp["wo"].astype(f32)


def _relu2(t, up, down):
    f32 = jnp.float32
    return jnp.square(jax.nn.relu(t @ up.astype(f32))) @ down.astype(f32)


def _experts(lp, t, top: int, norm: bool, scale: float, first: int):
    """t [T, D] -> the held routed experts' sum and the shared expert's."""
    f32 = jnp.float32
    score = jax.nn.sigmoid(t @ lp["router"].astype(f32))        # [T, E]
    _, idx = jax.lax.top_k(score + lp["expert_bias"].astype(f32), top)
    best = jnp.take_along_axis(score, idx, axis=-1)
    if norm:
        best = best / (best.sum(-1, keepdims=True) + 1e-20)
    best = best * scale
    E = score.shape[-1]
    # [T, E]: a token's weight for each expert, 0 where it was not chosen
    weight = jnp.einsum("tk,tke->te", best, jax.nn.one_hot(idx, E, dtype=f32))
    held = lp["e_up"].shape[0]
    weight = weight[:, first:first + held]

    def one(y, expert):
        up, down, w = expert
        # (the program stores a routed expert's up matrix output-major)
        return y + w[:, None] * _relu2(t, up.T, down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(t),
                        (lp["e_up"], lp["e_down"], weight.T))
    return y + _relu2(t, lp["s_up"], lp["s_down"])


@functools.partial(jax.jit, static_argnames=(
    "letter", "H", "KV", "hd", "mH", "mP", "G", "N", "eps", "top", "norm",
    "scale", "first"))
def layer(lp, x, *, letter, H, KV, hd, mH, mP, G, N, eps, top, norm, scale,
          first):
    """One published block on x [B, S, D] (float32)."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        B, S, D = x.shape
        if letter == "M":
            return x + _mamba(lp, _rms(x, lp["ln1"].astype(f32), eps),
                              mH, mP, G, N, eps)
        if letter == "*":
            return x + _attention(lp, _rms(x, lp["ln1"].astype(f32), eps),
                                  H, KV, hd)
        if letter == "E":
            t = _rms(x, lp["ln2"].astype(f32), eps).reshape(B * S, D)
            return x + _experts(lp, t, top, norm, scale,
                                first).reshape(B, S, D)
        raise ValueError(f"hybrid_override_pattern names {letter!r}")


@functools.partial(jax.jit, static_argnames=("eps",))
def head(lm_head, ln_f, x, at, *, eps):
    """The final norm and the untied unembedding at the positions ``at``
    [B, A] of x [B, S, D] only: nothing of [B, S, V] is ever held."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return (_rms(x, ln_f.astype(jnp.float32), eps)
                @ lm_head.astype(jnp.float32))


def forward(params, tokens, config: dict, at, lengths):
    """tokens [B, S] int32, at [B, A], lengths [B] (not read: every mask
    here is causal) -> the logits after the positions ``at`` of each row,
    [B, A, V] float32."""
    x = params["embed"][tokens].astype(jnp.float32)
    eps = float(config["layer_norm_epsilon"])
    for i in range(config["num_hidden_layers"]):
        x = layer(params[f"l{i}"], x,
                  letter=config["hybrid_override_pattern"][i],
                  H=config["num_attention_heads"],
                  KV=config["num_key_value_heads"], hd=config["head_dim"],
                  mH=config["mamba_num_heads"], mP=config["mamba_head_dim"],
                  G=config["n_groups"], N=config["ssm_state_size"], eps=eps,
                  top=config["num_experts_per_tok"],
                  norm=bool(config["norm_topk_prob"]),
                  scale=float(config["routed_scaling_factor"]),
                  first=int(config.get("experts_first", 0)))
    return head(params["lm_head"], params["ln_f"], x, at, eps=eps)


def row_bytes(config: dict, S: int, judged: int) -> int:
    """What one row of ``S`` positions holds at its fullest inside
    ``layer`` (float32): an attention block's scores and their softmax
    [H, S, S] with q, the repeated k and v and the output [S, 4 * H * hd];
    a Mamba-2 block's z | xBC | dt, the convolution and its shifted copy,
    x, the repeated B and C, y and the gated y [S, 6 * inner + 2 * H * N +
    3 * C] and its state [H, P, N] twice; an expert block's ONE expert
    [S, 2 * F] (the loop holds one at a time) or the shared expert's
    [S, 2 * Fs], with the scores, the weights and the one-hot of the chosen
    [S, (2 + k) * E]; the stream, its norm and a running sum [S, 3 * D] --
    and the ``judged`` positions' logits.  lib/sample.py sizes a group of
    rows by it."""
    D, H, hd = (config["hidden_size"], config["num_attention_heads"],
                config["head_dim"])
    mH, mP = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    inner = mH * mP
    conv = inner + 2 * G * N
    E = config.get("published", {}).get("n_routed_experts",
                                        config["n_routed_experts"])
    F = max(config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"])
    k = config["num_experts_per_tok"]
    attention = 2 * H * S * S + S * 4 * H * hd
    mamba = S * (6 * inner + 2 * mH * N + 3 * conv) + 2 * mH * mP * N
    experts = S * (2 * F + (2 + k) * E)
    return 4 * (max(attention, mamba, experts) + S * 3 * D
                + judged * config["vocab_size"])
