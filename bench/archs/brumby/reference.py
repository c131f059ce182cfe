"""The plain reference of the ``brumby`` block (manifestai Brumby-14B-Base,
``model_type`` ``brumby``: a Qwen3-shaped decoder whose every attention
layer was retrained as a POWER-RETENTION layer, Buckman, Gelada, Zhang,
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239) in
straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")`` -- no cache, no state, no
chunk form, no expansion of a head into its 8,256 products, no code of the
program under test.  Sizes come from the configuration file's published
keys, never from the program's ``cfg``.

It is the ATTENTION form of the layer on purpose: the program runs the
recurrent form (a decode step) and the chunk form (a prefill chunk), and
both are held against this third one.

For the hidden state ``x`` [S, D], ``eps`` = ``rms_norm_eps``, ``rms(t, w)
= t * rsqrt(mean(t^2) + eps) * w``, no bias but the gate's
(``attention_bias`` false):

  * layer: ``h = x + Wo ret(rms(x, ln1))``; ``x = h + W2 (silu(W1 u) * W3
    u)``, ``u = rms(h, ln2)``, width ``intermediate_size``;
  * ``ret(t)``: ``q = t Wq`` -> ``num_attention_heads`` heads of
    ``head_dim``; ``k``, ``v`` -> ``num_key_value_heads`` heads; ``q =
    rms(q, q_norm)``, ``k = rms(k, k_norm)`` over each head; half-split
    rotary embedding at ``rope_theta`` on both; ``log g = logsigmoid(t Wg +
    bg)``, one a KV head and a position; a query head h reads KV head ``h
    // (H / KV)``:

        a_tj = (q_t . k_j / sqrt(head_dim)) ** retention_degree
               * exp(sum_{l = j+1 .. t} log g_l)            for j <= t
        y_t  = sum_j a_tj v_j / (sum_j a_tj + retention_eps)

    no softmax and no maximum subtracted (the degree is even: no weight is
    negative);
  * after the last layer ``rms(x, ln_f) lm_head`` (the head untied) -- at
    the judged positions only.

The decay between j and t is ``exp(c_t - c_j)`` with ``c`` the running sum
of ``log g`` over the row, masked BEFORE the exponential (above the
diagonal the exponent is positive).  A row of 12,800 positions does not fit
its scores whole (40 heads x 12,800^2 float32 are 26 GB): a layer takes the
row's queries in blocks of ``_block(S)`` positions (at most 256), each
against all S keys, and the same blocks go through ``Wo`` and the FFN;
``row_bytes`` counts that.  The causal mask keeps a padded group's pad out
of every real position, so ``lengths`` is not read.

Weights are read as the program holds them (``params["l<i>"]``: ``ln1``,
``ln2``, ``wqkv`` = q | k | v side by side, ``q_norm``, ``k_norm``, ``wo``,
``ret_gate`` [D, KV], ``ret_gate_b`` [KV] float32, ``w1``, ``w3`` [D, F],
``w2`` [F, D]; ``embed``, ``ln_f``, ``lm_head`` [D, V]) and cast to
float32 a layer at a time."""

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


def _block(S: int) -> int:
    """The most query positions a step takes: the largest divisor of S
    that is at most 256."""
    return max(b for b in range(1, min(S, 256) + 1) if S % b == 0)


@functools.partial(jax.jit, static_argnames=(
    "H", "KV", "hd", "theta", "eps", "degree", "ret_eps"))
def layer(lp, x, *, H, KV, hd, theta, eps, degree, ret_eps):
    """One published layer on x [B, S, D] (float32)."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        B, S, D = x.shape
        Q = _block(S)
        t = _rms(x, lp["ln1"].astype(f32), eps)
        qkv = t @ lp["wqkv"].astype(f32)
        q = qkv[..., :H * hd].reshape(B, S, H, hd)
        k = qkv[..., H * hd:(H + KV) * hd].reshape(B, S, KV, hd)
        v = qkv[..., (H + KV) * hd:].reshape(B, S, KV, hd)
        q = _rope(_rms(q, lp["q_norm"].astype(f32), eps), theta)
        k = _rope(_rms(k, lp["k_norm"].astype(f32), eps), theta)
        log_g = jax.nn.log_sigmoid(
            t @ lp["ret_gate"].astype(f32) + lp["ret_gate_b"].astype(f32))
        c = jnp.cumsum(log_g, axis=1)                       # [B, S, KV]
        wo, w1, w2, w3 = (lp[n].astype(f32) for n in ("wo", "w1", "w2", "w3"))
        ln2 = lp["ln2"].astype(f32)
        keys = jnp.arange(S)

        def some(block):
            """Query positions ``first .. first + Q`` through the layer."""
            first, xq, qq, cq = block
            qq = qq.reshape(B, Q, KV, H // KV, hd)
            dot = jnp.einsum("bqkgd,bjkd->bkgqj", qq, k) / math.sqrt(hd)
            sees = keys[None, :] <= (first + jnp.arange(Q))[:, None]  # [Q,S]
            span = jnp.where(sees[None, None],
                             cq.transpose(0, 2, 1)[..., None]
                             - c.transpose(0, 2, 1)[:, :, None, :], 0.0)
            a = jnp.where(sees[None, None], jnp.exp(span), 0.0)[:, :, None] \
                * dot ** degree                             # [B,KV,G,Q,S]
            y = (jnp.einsum("bkgqj,bjkd->bqkgd", a, v)
                 / (a.sum(-1).transpose(0, 3, 1, 2)[..., None] + ret_eps))
            h = xq + y.reshape(B, Q, H * hd) @ wo
            u = _rms(h, ln2, eps)
            return h + (jax.nn.silu(u @ w1) * (u @ w3)) @ w2

        def blocks(a):
            return a.reshape((B, S // Q, Q) + a.shape[2:]).swapaxes(0, 1)

        out = jax.lax.map(some, (jnp.arange(0, S, Q), blocks(x), blocks(q),
                                 blocks(c)))
        return out.swapaxes(0, 1).reshape(B, S, D)


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
    for i in range(config["num_hidden_layers"]):
        x = layer(params[f"l{i}"], x,
                  H=config["num_attention_heads"],
                  KV=config["num_key_value_heads"],
                  hd=config["head_dim"],
                  theta=float(config["rope_theta"]),
                  eps=float(config["rms_norm_eps"]),
                  degree=int(config["retention_degree"]),
                  ret_eps=float(config["retention_eps"]))
    return head(params["lm_head"], params["ln_f"], x, at,
                eps=float(config["rms_norm_eps"]))


def row_bytes(config: dict, S: int, judged: int) -> int:
    """What one row of ``S`` positions holds at its fullest inside
    ``layer`` (float32): the stream, its norm and the layer's output [S, 3
    * D]; q, k, v [S, (H + 2 * KV) * hd] and q, k again once normed and
    turned; for ONE block of ``Q = _block(S)`` queries the products, the
    weights and what the sum over keys reads [3, H, Q, S] with the decay's
    exponent and its exponential [2, KV, Q, S], and the FFN's hidden [Q, 3
    * I] -- and the ``judged`` positions' logits.  lib/sample.py sizes a
    group of rows by it: at 12,800 positions one row is over the 2 GiB a
    group may hold, so every row goes alone."""
    D, H, KV = (config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"])
    hd, I = config["head_dim"], config["intermediate_size"]
    Q = _block(S)
    return 4 * (S * (3 * D + 2 * (H + 2 * KV) * hd)
                + Q * S * (3 * H + 2 * KV) + Q * 3 * I
                + judged * config["vocab_size"])
