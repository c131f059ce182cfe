"""The program side of the tests' third toy architecture (``toyblockdiff``,
bench_paths.add_toy_blockdiff): a test-local generator whose round is NOT
one token a step.  It generates by diffusion over blocks: a block of
``block_length`` positions starts as mask ids (the first one of a row
behind the remainder of its prompt, which is not a whole block), attends to
the cache of the earlier blocks and to itself IN BOTH DIRECTIONS, and is
passed through the model ``denoising_steps`` times; each pass unmasks the
``block_length / denoising_steps`` positions it is most confident of, and
once the block is finished it is passed once more, whole, to write its
K/V.  A prefill runs under the same block-causal mask and chooses no token.

It adds nothing to ``seldon_core_tpu/``: the tests put ``init_block_pool``,
``paged_forward_jit`` and ``paged_decode_round_jit`` in the place of
``models/generate.py``'s; the numerics child (lib/children.py) prefills
chunk by chunk as for any program, and the architecture's own
``drive.py`` (bench_paths.TOYBLOCKDIFF_DRIVE) calls the round and says
what of it is judged.  Written apart from the plain reference
(bench_paths.TOYBLOCKDIFF_REFERENCE): a cache, per-row offsets, and the
ids of a row kept in the pool beside its K/V, which is where a round finds
the prompt's remainder.  Nothing here is jitted: the tests break one
function at a time underneath (test_bench_arch.py)."""

from __future__ import annotations

import dataclasses
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
    d_ff: int
    rope_base: float
    norm_eps: float
    block_length: int
    denoising_steps: int
    mask_id: int


class ToyBlockDiffGenerator:
    def __init__(self, *, max_new_tokens: int, seed: int, temperature: float,
                 eos_token: int, dtype: str, **sizes):
        self.cfg = ToyConfig(**sizes)
        self.max_new_tokens, self.seed = max_new_tokens, seed
        self.temperature, self.top_k, self.top_p = temperature, 0, 0.0
        self.eos_token, self.dtype = eos_token, jnp.dtype(dtype)

    def init_state(self, _):
        c = self.cfg
        keys = iter(jax.random.split(jax.random.key(self.seed), 64))

        def w(*shape, scale=1.0):
            return (jax.random.normal(next(keys), shape) * scale
                    / math.sqrt(shape[-2])).astype(self.dtype)

        def ones(n):
            return jnp.ones((n,), self.dtype)

        D, A, KV = c.d_model, c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        params = {"embed": w(c.vocab, D, scale=math.sqrt(c.vocab)),
                  "ln_f": ones(D), "lm_head": w(D, c.vocab)}
        for i in range(c.n_layers):
            # peaked attention (scores three times as wide) and values
            # three times as large: what one wrong key does to the logits
            # is then no rounding (test_bench_arch.py: a block's K/V written
            # from a pass that still saw masks)
            params[f"l{i}"] = {
                "ln1": ones(D), "wq": w(D, A), "wk": w(D, KV),
                "wv": w(D, KV, scale=3.0),
                "q_norm": 3.0 * ones(c.head_dim), "k_norm": ones(c.head_dim),
                "wo": w(A, D), "ln2": ones(D), "w_gate": w(D, c.d_ff),
                "w_up": w(D, c.d_ff), "w_down": w(c.d_ff, D)}
        return {"params": params}


def init_block_pool(cfg: ToyConfig, num_blocks: int, block_size: int):
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32),
            "ids": jnp.zeros((num_blocks, block_size), jnp.int32)}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, base):
    half = x.shape[-1] // 2
    ang = (pos.astype(jnp.float32)[..., None, None]
           * base ** (-jnp.arange(half, dtype=jnp.float32) / half))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _visible(pos, kpos, block_length: int):
    """Block-causal: a position sees every key up to the END of its own
    diffusion block, the later positions of that block among them."""
    return kpos < (pos // block_length + 1) * block_length


def _forward(params, tokens, pool, tables, start, width, cfg: ToyConfig):
    """``tokens`` [B, W] at positions ``start + arange(W)`` (the first
    ``width`` of a row real) over the cache: the hidden states after the
    last layer, and the pool with these positions' K/V and ids written —
    the caller keeps it or, for a denoising pass, drops it."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        B, W = tokens.shape
        bs, nblk = pool["ids"].shape[1], tables.shape[1]
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        pos = start[:, None] + jnp.arange(W)[None]
        valid = jnp.arange(W)[None] < width[:, None]
        blk = jnp.take_along_axis(tables, jnp.minimum(pos // bs, nblk - 1), 1)
        blk, slot = jnp.where(valid, blk, 0), pos % bs      # pad -> scratch
        kpos = jnp.arange(nblk * bs)[None, None]
        # nothing past what the row holds so far: those slots are stale
        seen = (_visible(pos[..., None], kpos, cfg.block_length)
                & (kpos < (start + width)[:, None, None]))
        pool = {**pool, "ids": pool["ids"].at[blk, slot].set(tokens)}
        x = p["embed"][tokens]
        for i in range(cfg.n_layers):
            lp = p[f"l{i}"]
            h = _norm(x, lp["ln1"], cfg.norm_eps)
            q = _norm((h @ lp["wq"]).reshape(B, W, H, hd), lp["q_norm"],
                      cfg.norm_eps)
            k = _norm((h @ lp["wk"]).reshape(B, W, KV, hd), lp["k_norm"],
                      cfg.norm_eps)
            v = (h @ lp["wv"]).reshape(B, W, KV, hd)
            q, k = _rope(q, pos, cfg.rope_base), _rope(k, pos, cfg.rope_base)
            pk = pool["k"][i].at[blk, slot].set(k)
            pv = pool["v"][i].at[blk, slot].set(v)
            pool = {**pool, "k": pool["k"].at[i].set(pk),
                    "v": pool["v"].at[i].set(pv)}
            kk = jnp.repeat(pk[tables].reshape(B, nblk * bs, KV, hd),
                            H // KV, axis=2)
            vv = jnp.repeat(pv[tables].reshape(B, nblk * bs, KV, hd),
                            H // KV, axis=2)
            s = jnp.einsum("bwhd,bkhd->bhwk", q, kk) / math.sqrt(hd)
            s = jnp.where(seen[:, None], s, -1e30)
            a = jnp.einsum("bhwk,bkhd->bwhd", jax.nn.softmax(s, -1), vv)
            x = x + a.reshape(B, W, H * hd) @ lp["wo"]
            h = _norm(x, lp["ln2"], cfg.norm_eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
                     ) @ lp["w_down"]
        return x, pool


def _logits(params, x, cfg: ToyConfig):
    with jax.default_matmul_precision("highest"):
        return (_norm(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
                @ params["lm_head"].astype(jnp.float32))


def paged_forward_jit(params, tokens, pool, tables, start, width, *,
                      cfg: ToyConfig, last_only: bool = True):
    """A prefill chunk under the block-causal mask: the K/V of every real
    position written, the logits after each row's last one (they choose no
    token: the round starts on masks)."""
    assert last_only
    x, pool = _forward(params, tokens, pool, tables, start, width, cfg)
    last = jnp.take_along_axis(
        x, jnp.maximum(width - 1, 0)[:, None, None], axis=1)[:, 0]
    return _logits(params, last, cfg), pool


def paged_decode_round_jit(params, pool, tables, token, n_valid, active,
                           seen_eos, keys, cfg: ToyConfig, *, span: int,
                           **sampling):
    """``span / block_length`` blocks a row, greedy.  A row's first block
    starts where the last whole block of its ``n_valid`` positions ends:
    the prompt's remainder (its ids are in the pool) stands in the block
    unmasked and is written again with it.  ``token`` is not read: a
    prefill chose none.  Returns the finished blocks [B, span] — a row's
    NEW tokens are those from its ``n_valid`` on — the pool, and every
    denoising pass as the host needs it to judge one: ``block``, what the
    pass ``saw`` [B, L], which positions it ``picked`` and what it
    ``chose`` for them."""
    L, steps = cfg.block_length, cfg.denoising_steps
    assert span % L == 0 and L % steps == 0
    bs, nblk = pool["ids"].shape[1], tables.shape[1]
    full = jnp.full_like(n_valid, L)
    out, passes = [], []
    for b in range(span // L):
        start = n_valid - n_valid % L + b * L
        pos = start[:, None] + jnp.arange(L)[None]
        held = pool["ids"][jnp.take_along_axis(
            tables, jnp.minimum(pos // bs, nblk - 1), 1), pos % bs]
        masked = pos >= n_valid[:, None]
        x = jnp.where(masked, cfg.mask_id, held)
        for _ in range(steps):
            hidden, _ = _forward(params, x, pool, tables, start, full, cfg)
            logits = _logits(params, hidden, cfg)
            # the mask id is never an answer
            logits = logits.at[..., cfg.mask_id].set(-jnp.inf)
            chose = logits.argmax(-1).astype(jnp.int32)
            sure = jnp.where(masked, jax.nn.softmax(logits, -1).max(-1), -1.0)
            # the most confident of the positions still masked, so many a pass
            rank = jnp.argsort(jnp.argsort(-sure, axis=-1), axis=-1)
            picked = masked & (rank < L // steps)
            passes.append({"block": b, "saw": x, "picked": picked,
                           "chose": chose})
            x = jnp.where(picked, chose, x)
            masked = masked & ~picked
        # the finished block, whole, writes its K/V
        _, pool = _forward(params, x, pool, tables, start, full, cfg)
        out.append(x)
    return jnp.concatenate(out, 1), pool, passes
