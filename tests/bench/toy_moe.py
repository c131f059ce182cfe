"""The program side of the tests' second toy architecture (``toymoe``,
bench_paths.add_toy_moe): a test-local generator unit with its own paged
programs, shaped like what a draw of public models brings and the repo's
block is not — ``head_dim`` apart from hidden // heads, an untied head, a
float32 sigmoid top-k router over the PUBLISHED number of experts of which
this "chip" holds the first few, a shared expert, leading dense layers,
sliding-window layers with rotary embedding and full layers without.

It adds nothing to ``seldon_core_tpu/``: the tests put ``init_block_pool``,
``paged_forward`` and ``paged_decode_round`` in the place of
``models/generate.py``'s, and the numerics child (lib/children.py) drives
them as it drives the program's — chunk by chunk over a block pool, then
one decode round.  It is written apart from the plain reference
(bench_paths.TOYMOE_REFERENCE): a cache, per-row offsets, the chosen
experts' weights gathered a token; the layer pattern DERIVED from the
published scalars, where the reference reads the file's list."""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    vocab: int
    d_model: int
    head_dim: int
    n_heads: int
    n_kv_heads: int
    n_layers: int
    n_dense_layers: int
    d_ff: int
    d_expert: int
    n_experts: int          # held here
    router_width: int       # published: the router scores every expert
    experts_per_tok: int
    window: int
    full_every: int
    rope_base: float
    norm_eps: float
    route_scale: float

    def sliding(self, layer: int) -> bool:
        return (layer + 1) % self.full_every != 0


class ToyMoEGenerator:
    def __init__(self, *, max_new_tokens: int, seed: int, temperature: float,
                 eos_token: int, dtype: str, **sizes):
        self.cfg = ToyConfig(**sizes)
        self.max_new_tokens, self.seed = max_new_tokens, seed
        self.temperature, self.top_k, self.top_p = temperature, 0, 0.0
        self.eos_token, self.dtype = eos_token, jnp.dtype(dtype)

    def init_state(self, _):
        c = self.cfg
        keys = iter(jax.random.split(jax.random.key(self.seed), 256))

        def w(*shape, scale=1.0):
            return (jax.random.normal(next(keys), shape) * scale
                    / math.sqrt(shape[-2])).astype(self.dtype)

        def ones(n):
            return jnp.ones((n,), self.dtype)

        D, A, KV = c.d_model, c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        params = {"embed": w(c.vocab, D, scale=math.sqrt(c.vocab)),
                  "ln_f": ones(D), "lm_head": w(D, c.vocab)}
        for i in range(c.n_layers):
            F = c.d_ff if i < c.n_dense_layers else c.d_expert
            lp = {"ln1": ones(D), "wq": w(D, A), "wk": w(D, KV),
                  "wv": w(D, KV), "wo": w(A, D), "ln2": ones(D),
                  "w_gate": w(D, F), "w_up": w(D, F), "w_down": w(F, D)}
            if i >= c.n_dense_layers:
                E = c.n_experts
                lp.update(router=w(D, c.router_width, scale=3.0),
                          e_gate=w(E, D, F), e_up=w(E, D, F),
                          e_down=w(E, F, D))
            params[f"l{i}"] = lp
        return {"params": params}


def init_block_pool(cfg: ToyConfig, num_blocks: int, block_size: int):
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32)}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, base):
    half = x.shape[-1] // 2
    ang = (pos.astype(jnp.float32)[..., None, None]
           * base ** (-jnp.arange(half, dtype=jnp.float32) / half))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _routed(h, lp, cfg: ToyConfig):
    """The part of the expert layer this chip's experts give: the router
    scores all ``router_width`` experts, the chosen ones that are not held
    here add nothing (they live on the other chips of the deployment)."""
    score = jax.nn.sigmoid((h @ lp["router"]).astype(jnp.float32))
    top, idx = jax.lax.top_k(score, cfg.experts_per_tok)
    weight = top / top.sum(-1, keepdims=True) * cfg.route_scale
    weight = jnp.where(idx < cfg.n_experts, weight, 0.0)
    idx = jnp.minimum(idx, cfg.n_experts - 1)
    hk = h[..., None, None, :]                              # [B, W, 1, 1, D]
    act = (jax.nn.silu(hk @ lp["e_gate"][idx]) * (hk @ lp["e_up"][idx])
           ) @ lp["e_down"][idx]                            # [B, W, K, 1, D]
    return (act[..., 0, :] * weight[..., None]).sum(-2)


@functools.partial(jax.jit, static_argnames=("cfg", "last_only"))
def paged_forward_jit(params, tokens, pool, tables, start, width, *,
                      cfg: ToyConfig, last_only: bool = True):
    assert last_only
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        B, W = tokens.shape
        bs, nblk = pool["k"].shape[2], tables.shape[1]
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        pos = start[:, None] + jnp.arange(W)[None]
        valid = jnp.arange(W)[None] < width[:, None]
        blk = jnp.take_along_axis(tables, jnp.minimum(pos // bs, nblk - 1), 1)
        blk, slot = jnp.where(valid, blk, 0), pos % bs      # pad -> scratch
        kpos = jnp.arange(nblk * bs)[None, None]
        x = p["embed"][tokens]
        for i in range(cfg.n_layers):
            lp = p[f"l{i}"]
            h = _norm(x, lp["ln1"], cfg.norm_eps)
            q = (h @ lp["wq"]).reshape(B, W, H, hd)
            k = (h @ lp["wk"]).reshape(B, W, KV, hd)
            v = (h @ lp["wv"]).reshape(B, W, KV, hd)
            seen = kpos <= pos[..., None]
            if cfg.sliding(i):
                q, k = _rope(q, pos, cfg.rope_base), _rope(k, pos,
                                                           cfg.rope_base)
                seen &= kpos > pos[..., None] - cfg.window
            pk = pool["k"][i].at[blk, slot].set(k)
            pv = pool["v"][i].at[blk, slot].set(v)
            pool = {"k": pool["k"].at[i].set(pk), "v": pool["v"].at[i].set(pv)}
            kk = jnp.repeat(pk[tables].reshape(B, nblk * bs, KV, hd),
                            H // KV, axis=2)
            vv = jnp.repeat(pv[tables].reshape(B, nblk * bs, KV, hd),
                            H // KV, axis=2)
            s = jnp.einsum("bwhd,bkhd->bhwk", q, kk) / math.sqrt(hd)
            s = jnp.where(seen[:, None], s, -1e30)
            a = jnp.einsum("bhwk,bkhd->bwhd", jax.nn.softmax(s, -1), vv)
            x = x + a.reshape(B, W, H * hd) @ lp["wo"]
            h = _norm(x, lp["ln2"], cfg.norm_eps)
            y = _gated(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            if i >= cfg.n_dense_layers:
                y = y + _routed(h, lp, cfg)
            x = x + y
        last = jnp.take_along_axis(
            x, jnp.maximum(width - 1, 0)[:, None, None], axis=1)[:, 0]
        return _norm(last, p["ln_f"], cfg.norm_eps) @ p["lm_head"], pool


def paged_decode_round_jit(params, pool, tables, token, n_valid, active,
                           seen_eos, keys, cfg: ToyConfig, *, span: int,
                           **sampling):
    """``span`` greedy steps: the token at position ``n_valid`` in, the next
    one out."""
    out, one = [], jnp.ones_like(n_valid)
    for j in range(span):
        logits, pool = paged_forward_jit(params, token[:, None], pool, tables,
                                         n_valid + j, one, cfg=cfg)
        token = logits.argmax(-1).astype(jnp.int32)
        out.append(token)
    return jnp.stack(out, 1), pool
