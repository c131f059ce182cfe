"""Autoregressive decoding over a paged KV pool — LLM-style serving through
the same graph engine.

The reference predates sequence models entirely (SURVEY.md §5); this module
makes generation a first-class graph workload: ``TransformerGenerator`` is
a MODEL unit whose ``predict`` maps prompt token rows to generated token
rows, so a deployment JSON serves text continuation over the identical
REST/gRPC data plane as every other model.

One KV layout, one decoder block, three programs:
  * the cache is a POOL of fixed-size blocks (``init_block_pool``:
    ``[num_blocks, block_size, KV, hd]`` per attention layer, int8 with
    per-position scales under ``LMConfig.kv_quant``) and a row reaches its
    blocks through a block table; a gated short-convolution layer
    (``LMConfig.layer_kinds``) holds no K/V but a fixed-size state a
    sequence, ``[num_blocks, conv_kernel - 1, D]``, found by the row's
    FIRST block, a power-retention layer likewise a float32 matrix
    state a KV head (ops/retention.py) and a Mamba-2 state-space layer its
    convolution's taps and a float32 matrix state a head (ops/ssm.py),
    beside the attention layers' K/V in one pool; ``_paged_block`` is the
    only decoder block that reads or writes any of them;
  * ``paged_forward`` (a prompt, a prompt chunk or a verify pass),
    ``paged_decode_round`` (``span`` single-token steps as ONE ``lax.scan``
    inside jit — no per-token dispatch, no host round trip between steps;
    for a generator by diffusion over blocks, ``LMConfig.block_length`` > 1,
    ``span / block_length`` blocks of denoising passes, ``_denoising_round``)
    and ``paged_spec_round`` (draft + verify) are the device programs.

Two lanes drive those programs and differ only in who owns the pool and
the tables:
  * the CONTINUOUS lane (runtime/genserver.py) owns one process-wide pool,
    allocates blocks per sequence and co-schedules requests of every age;
  * the STATIC lane (``generate`` / ``stream_chunks`` below, and
    models/speculative.py) gives each request a private pool with identity
    tables, prefills the whole prompt in one ``paged_forward`` and decodes
    in one round (``generate``) or one round per client chunk
    (``stream_chunks``).  It traces under ``jit``, so it is what serves
    where the scheduler cannot: a generator whose experts are capacity-
    routed (``moe_every``: the capacity couples co-batched rows; dropless
    experts, ``d_expert``, do not and ride the scheduler), a generator
    inside a graph of several units, and ``SELDON_TPU_GEN_CONTINUOUS=0``.

The cache-free forward (training, ``lm_apply``) is models/transformer.py.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from seldon_core_tpu.graph.units import Unit, UnitAux, register_unit
from seldon_core_tpu.utils.telemetry import RECORDER

_stream_counter = itertools.count()  # per-process sampled-stream key source
from seldon_core_tpu.models.served import served
from seldon_core_tpu.models.transformer import (
    LMConfig,
    _ffn,
    _rmsnorm,
    apply_rope,
    lm_init,
)


def _eager(x) -> bool:
    """True when ``x`` is a concrete array — i.e. we are executing, not
    being traced into someone's jit.  Telemetry must only record on
    execution: a traced ``time.perf_counter()`` would bake trace-time
    constants into the program."""
    return not isinstance(x, jax.core.Tracer)

__all__ = ["generate", "stream_chunks", "sample_token", "mask_after_eos",
           "init_block_pool", "private_pool", "decode_inplace",
           "retention_fused", "ssm_fused", "experts_fused",
           "paged_forward", "paged_decode_round", "paged_spec_round",
           "paged_copy_block", "TransformerGenerator"]


def _quantize_kv(t):
    """t [B, KV, S, hd] float -> (int8 values, f32 scales [B, KV, S]).

    Symmetric per-token-per-head absmax — one scale per cache position, so
    the score/PV dots recover it as a rank-1 broadcast over the length
    axis (no per-element dequant tensor ever materialises)."""
    t32 = t.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(t32), axis=-1)
    scales = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(
        jnp.round(t32 / scales[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scales


def _heads(t, B, S, H, hd):
    return t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)


def sanitize_prompt(X, vocab: int):
    """Float wire rows -> int32 token ids in [0, vocab).

    nan_to_num then clip in float space BEFORE the cast: float->int32 of
    NaN or out-of-range values is implementation-defined in XLA (wrap vs
    saturate varies by backend); after this chain the cast input is always
    a finite value in range."""
    return jnp.clip(jnp.nan_to_num(X), 0, vocab - 1).astype(jnp.int32)


def _grouped_qk(q, cache_k, k_s=None):
    """q [B,H,S,hd] x cache_k [B,KV,L,hd] -> scores [B,KV,g,S,L] f32.

    The group axis folds into the dot_general row axis so K streams from
    HBM once at its stored (grouped) size — decode is HBM-bound on exactly
    this stream, and with GQA it is n_heads/n_kv_heads smaller.  Reads use
    the stored dtype with f32 accumulation via ``preferred_element_type``;
    an explicit .astype(f32) would materialise a second, larger copy of
    the cache every step.  Int8 caches (``k_s`` [B,KV,L] scales) cast
    inside the dot — XLA fuses the convert into the weight-side read, the
    dequant_matmul trick — and the per-position scale multiplies the f32
    SCORES (a rank-1 broadcast over L), never the cache."""
    B, H, S, hd = q.shape
    KV, L = cache_k.shape[1], cache_k.shape[2]
    g = H // KV
    scale = jnp.float32(1.0 / (hd ** 0.5))
    k = cache_k.astype(q.dtype) if cache_k.dtype == jnp.int8 else cache_k
    s = jax.lax.dot_general(
        q.reshape(B, KV, g * S, hd), k,
        (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) * scale
    s = s.reshape(B, KV, g, S, L)
    if k_s is not None:
        s = s * k_s[:, :, None, None, :]
    return s


def _grouped_pv(p, cache_v, out_shape, out_dtype, v_s=None):
    """p [B,KV,g,S,L] x cache_v [B,KV,L,hd] -> [B,H,S,hd] ``out_dtype``.

    Int8 caches fold the per-position scale into p BEFORE the dot
    (out = (p * v_s) @ v_q): p is [*, L]-shaped so the scale is a cheap
    broadcast there, while scaling V would rebuild a full-size float
    cache copy."""
    B, KV, g, S, L = p.shape
    if v_s is not None:
        p = p * v_s[:, :, None, None, :]
    v = (cache_v.astype(out_dtype)
         if cache_v.dtype == jnp.int8 else cache_v)
    out = jax.lax.dot_general(
        p.astype(out_dtype).reshape(B, KV, g * S, L), v,
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)
    return out.reshape(out_shape)


def sample_token(logits, key, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0):
    """[B, V] f32 logits -> [B] int32 next-token ids.

    All knobs are STATIC python values (jit caches one executable per
    sampling config): temperature <= 0 is greedy argmax; otherwise
    temperature-scaled sampling, optionally truncated to the ``top_k``
    highest logits and/or the top-p nucleus (the smallest set of tokens
    whose cumulative probability reaches ``top_p`` — always at least
    one).  Nucleus filtering sorts the [B, V] logits per step (~17
    bitonic passes over the row at V=32k — measurable but small next to
    the decode step's cache stream); top-k alone uses lax.top_k."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = (logits / temperature).astype(jnp.float32)
    if top_k and top_k > 0:
        # clamp: a deployment's top_k may exceed a small model's vocab,
        # and lax.top_k would raise at trace time inside the scan
        kk = min(int(top_k), logits.shape[-1])
        kth = jax.lax.top_k(logits, kk)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and 0.0 < top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]      # descending
        probs = jax.nn.softmax(srt, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        keep = mass_before < top_p                       # >= 1 token
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def mask_after_eos(toks, eos_token: int):
    """Force every position strictly AFTER a row's first ``eos_token``
    to eos: fixed-shape scans keep decoding past a stop token, so the
    serving contract is 'output is eos-padded after the stop'.  No-op
    when eos_token < 0 (disabled)."""
    if eos_token < 0:
        return toks
    is_eos = toks == eos_token
    after = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
             - is_eos.astype(jnp.int32)) > 0
    return jnp.where(after, jnp.int32(eos_token), toks)


# ---------------------------------------------------------------------------
# Paged KV-block cache — the one cache both lanes decode over
# (runtime/genserver.py drives these for the continuous lane; see
# docs/operations.md "tuning the generation scheduler")
# ---------------------------------------------------------------------------
#
# Continuous batching co-schedules sequences of different ages in one decode
# batch, so the cache is a POOL of fixed-size blocks ([num_blocks,
# block_size, KV, hd] per layer; [num_blocks, block_size, KV * hd / 128,
# 128] where heads narrower than 128 lanes fill rows together --
# init_block_pool) and each sequence carries a BLOCK TABLE
# mapping its logical block i to a physical pool block.  The scheduler's
# allocation/free/eviction and occupancy accounting are host-side
# (runtime/genserver.py BlockAllocator); the static lane's private pool
# needs none (private_pool: identity tables).  The device side below is
# three programs:
#
#   * paged_forward      — W tokens of one-or-more rows at per-row offsets
#                          (chunked prefill AND the speculative verify pass)
#   * paged_decode_round — `span` single-token steps for the whole
#                          in-flight batch as ONE lax.scan (per-row
#                          positions, per-row sampling keys, on-device
#                          after-eos latch)
#   * paged_spec_round   — draft k+1 paged steps + one (k+1)-wide target
#                          verify + greedy acceptance (speculative decoding
#                          on the serving path)
#
# Reads come in two formulations of one contract (attention of each row over
# its own blocks, positions <= the query's): the GATHER path builds a
# position-ordered dense view (pool[tables], pure XLA: _paged_view +
# _attend_paged) and serves every width, dtype, backend and mesh; the
# IN-PLACE path (ops/paged_attention.py, a Pallas TPU kernel) reads a row's
# blocks where they lie and serves a decode round's step — one query a row,
# or the queries of one diffusion block a row, which see the cache before
# the block through the kernel and the block's own fresh K/V beside it —
# where ops.paged_attention.inplace_supported says so.  The gather path is
# the reference the kernel is tested against.  Writes SCATTER fresh K/V at
# (table[pos // bs], pos % bs) — the vLLM reshape_and_cache shape — and the
# pool's layout is the one XLA's TPU scatter wants (a token's KV x hd
# window minor-most): any other makes XLA re-lay the whole pool around
# every write.  Block 0 is a reserved SCRATCH block: masked rows and pad
# positions write there, so inactive slots never need a branch.


def init_block_pool(cfg: LMConfig, num_blocks: int, block_size: int,
                    mesh=None) -> Dict[str, Any]:
    """One entry a layer, by the layer's mixer (``LMConfig.kind``).  An
    attention layer's is {k, v[, k_s, v_s]} shaped ``[num_blocks,
    block_size, KV, hd]``; int8 pools carry per-position f32 scale planes
    (``[num_blocks, block_size, KV]``, ~6% over the values at hd=64).

    A float pool whose head is narrower than the 128 lanes of a row is
    ``[num_blocks, block_size, KV * hd / 128, 128]`` where the KV heads
    fill whole rows (ops.paged_attention.heads_per_row: hd 64 with an even
    KV count -- row j holds head 2j in lanes 0-63 and head 2j+1 in 64-127):
    the same KV x hd values a token, in the same order, under the shape
    the in-place decode kernel reads.  A pool its caller will shard over a
    ``mesh`` keeps a head a row: the kernel does not serve it
    (``inplace_supported``) and its KV heads are what is sharded
    (runtime/servingmesh.py shard_gen_pool).  It is decided HERE, from the
    model's shapes and on every backend, and never by a reshape of the pool
    inside a program: a ``[N, bs, 8, 64]`` bfloat16 array lies on a TPU
    with its positions minor-most, not its window, and a program re-lays
    it, padded to 128 lanes, around every scatter (PERF.md section 6, PR
    40: what the gather path of such a model paid), so a reshape there is
    a copy of the whole pool around every layer.  The small operands meet
    the pool instead: ``_paged_write`` reshapes the fresh rows before the
    scatter, ``_paged_view`` the gathered copy after the gather, and nobody
    else may read KV or hd off the pool's shape (they are ``cfg``'s).

    A gated short-convolution layer's is {conv}: ``[num_blocks,
    conv_kernel - 1, D]`` in the pool's dtype, the gated input at a
    sequence's last positions, kept at the id of the sequence's FIRST
    block -- unique to a live sequence, freed and copied with the block --
    and no K/V is allocated for it.  Block 0 is the scratch block — the
    allocator (runtime/genserver.py) hands out ids >= 1 — and entry 0 of a
    state the scratch state.

    A power-retention layer's is {s, z} (ops/retention.py): ``s`` ``[num_
    blocks, KV * hd, P]`` and the normaliser ``z`` ``[num_blocks, KV, P]``,
    float32 on every backend, found like a conv state at the sequence's
    first block's id.  An entry is 34 MB at the published widths whatever
    the row's length, and there is one a BLOCK: such a generator (every
    layer is one, ``LMConfig``) is deployed a block a row -- a block size
    that holds the longest row, as many blocks as rows and the scratch one
    -- so that the pool IS the table of states and holds nothing else.
    (``s`` is three-dimensional, rows of (KV head, value lane):
    bench/tools/rehearse_aot.py describes every four-dimensional float32
    array of a pool as the chip's bfloat16 K/V.)

    A Mamba-2 state-space layer's is {conv, h} (``_ssm``; ops/ssm.py):
    ``conv`` ``[num_blocks, conv_kernel - 1, conv_dim]`` in the pool's
    dtype, the convolution's input x | B | C at a sequence's last
    positions, and ``h`` ``[num_blocks, heads, head_dim, state]``, float32
    on every backend, both at the sequence's first block's id as a conv
    state is: 2.1 MB a block at the published widths whatever the block
    holds, beside the attention layers' K/V in the same pool -- so such a
    generator is deployed with few, large blocks (runtime/genserver.py
    refuses a pool whose state entries do not fit).  A block with no mixer
    (an FFN alone) keeps nothing: its entry is empty."""
    from seldon_core_tpu.ops.paged_attention import heads_per_row
    from seldon_core_tpu.ops.retention import phi_width

    hd = cfg.hd
    kv = cfg.kv_heads
    pair = heads_per_row(kv, hd) if mesh is None else 1
    # XLA:CPU has no native bf16 scatter: a bf16 pool pays TWO whole-pool
    # converts (bf16 -> f32 scatter -> bf16) around EVERY write, which
    # scales step cost with POOL size instead of batch size (measured:
    # 211 ms vs 6 ms per decode round at 1024 blocks).  CPU backends
    # store the pool f32; TPU/GPU keep the configured dtype (bf16 native,
    # half the HBM) — same degradation pattern as the quality observatory.
    dtype = cfg.dtype
    if dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        dtype = jnp.float32

    def layer(mixer):
        if mixer is None:
            return {}
        if mixer == "ssm":
            return {"conv": jnp.zeros(
                (num_blocks, cfg.conv_kernel - 1, cfg.ssm_conv_dim), dtype),
                    "h": jnp.zeros((num_blocks, cfg.ssm_heads,
                                    cfg.ssm_head_dim, cfg.ssm_state),
                                   jnp.float32)}
        if mixer == "conv":
            return {"conv": jnp.zeros(
                (num_blocks, cfg.conv_kernel - 1, cfg.d_model), dtype)}
        if mixer == "ret":
            return {"s": jnp.zeros((num_blocks, kv * hd, phi_width(hd)),
                                   jnp.float32),
                    "z": jnp.zeros((num_blocks, kv, phi_width(hd)),
                                   jnp.float32)}
        if cfg.kv_quant == "int8":
            return {
                "k": jnp.zeros((num_blocks, block_size, kv, hd), jnp.int8),
                "v": jnp.zeros((num_blocks, block_size, kv, hd), jnp.int8),
                "k_s": jnp.zeros((num_blocks, block_size, kv), jnp.float32),
                "v_s": jnp.zeros((num_blocks, block_size, kv), jnp.float32),
            }
        rows = (num_blocks, block_size, kv // pair, hd * pair)
        return {"k": jnp.zeros(rows, dtype), "v": jnp.zeros(rows, dtype)}

    return {f"l{i}": layer(cfg.kind(i)[0]) for i in range(cfg.n_layers)}


def _pool_kv(pool):
    """The first attention layer's entry of ``pool`` (every attention layer
    has its shapes and dtype), or None where no layer holds K/V."""
    return next((layer for layer in pool.values() if "k" in layer), None)


def _paged_view(layer, tables, head_dim=None):
    """Gather one layer's blocks into a dense position-ordered cache view:
    pool [N, bs, KV, hd] + tables [B, nblk] -> {k, v[, k_s, v_s]} with k/v
    [B, KV, nblk*bs, hd] — the _grouped_qk/_grouped_pv layout, so paged
    attention reuses the exact dot formulations the dense caches use.
    ``head_dim`` is the model's head width where the pool's rows may carry
    several heads (``init_block_pool``; None: a row is a head): the copy,
    not the pool, is cut back into heads."""
    out = {}
    for name in ("k", "v"):
        g = layer[name][tables]  # [B, nblk, bs, KV, hd]
        B, nblk, bs = g.shape[:3]
        hd = head_dim or g.shape[4]
        if hd != g.shape[4]:
            # the copy is cut into heads as a value of its own: fused with
            # the gather, XLA's TPU layout assignment picks the POOL's
            # layout for this reshape and re-lays the whole pool around
            # the chunk's scatter (PERF.md section 6, PR 40)
            g = jax.lax.optimization_barrier(g)
        g = g.reshape(B, nblk, bs, -1, hd)
        out[name] = g.transpose(0, 3, 1, 2, 4).reshape(B, -1, nblk * bs, hd)
    for name in ("k_s", "v_s"):
        if name in layer:
            g = layer[name][tables]  # [B, nblk, bs, KV]
            B, nblk, bs, KV = g.shape
            out[name] = g.transpose(0, 3, 1, 2).reshape(B, KV, nblk * bs)
    return out


def _paged_write(layer, tables, pos, valid, k_new, v_new):
    """Scatter fresh K/V (``[B, KV, W, hd]``) into the pool at per-token
    (block, offset) targets: ``pos`` [B, W] global positions, resolved
    through each row's table.  ``valid`` [B, W] False routes the write to
    the scratch block 0 (masked rows / pad positions) — garbage lands in
    scratch, never in a live sequence's blocks.  int8 pools quantize here
    (per-token absmax, _quantize_kv) and scatter the scale planes too.  The
    fresh rows take the shape of the pool's rows (``init_block_pool``: a
    token's KV x hd values, in that order, whichever way they are cut)."""
    bs = layer["k"].shape[1]
    nblk = tables.shape[1]
    idx = jnp.clip(pos // bs, 0, nblk - 1)
    blk = jnp.take_along_axis(tables, idx, axis=1)  # [B, W]
    blk = jnp.where(valid, blk, 0)
    off = pos % bs
    out = dict(layer)
    if layer["k"].dtype == jnp.int8:
        k_q, k_s = _quantize_kv(k_new)
        v_q, v_s = _quantize_kv(v_new)
        out["k"] = layer["k"].at[blk, off].set(k_q.transpose(0, 2, 1, 3))
        out["v"] = layer["v"].at[blk, off].set(v_q.transpose(0, 2, 1, 3))
        out["k_s"] = layer["k_s"].at[blk, off].set(k_s.transpose(0, 2, 1))
        out["v_s"] = layer["v_s"].at[blk, off].set(v_s.transpose(0, 2, 1))
    else:
        for name, new in (("k", k_new), ("v", v_new)):
            rows = new.transpose(0, 2, 1, 3).reshape(
                blk.shape + layer[name].shape[2:])
            out[name] = layer[name].at[blk, off].set(
                rows.astype(layer[name].dtype))
    return out


def _attend_paged(q, view, start, block_length: int = 1, limit=None):
    """q [B, H, W, hd] over a dense paged view; query i of row b sees
    positions <= start[b] + i (its own fresh K/V is already in the pool).
    Per-row ``start`` is what separates this from _attend_cached_causal:
    co-scheduled rows sit at different sequence lengths.  W == 1 with
    start == n_valid is exactly the cached decode mask (kpos <= n_valid).

    ``block_length`` > 1 is the BLOCK-CAUSAL mask of generation by
    diffusion over blocks: a position sees every key up to the END of its
    own block of ``block_length``, the later ones of that block too — as
    far as the row really holds them, ``limit`` [B] (= start + the row's
    valid width): what lies past it in the pool is stale."""
    s = _grouped_qk(q, view["k"], view.get("k_s"))  # [B, KV, g, W, L]
    L = view["k"].shape[2]
    W = q.shape[2]
    qpos = start[:, None] + jnp.arange(W)[None, :]          # [B, W]
    if block_length > 1:
        ends = jnp.minimum((qpos // block_length + 1) * block_length,
                           limit[:, None])
        allowed = jnp.arange(L)[None, None, :] < ends[:, :, None]
    else:
        allowed = jnp.arange(L)[None, None, :] <= qpos[:, :, None]  # [B,W,L]
    s = jnp.where(allowed[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_pv(p, view["v"], q.shape, q.dtype, view.get("v_s"))


def _fresh_mask(s, block_length, valid=None):
    """Scores ``s`` [B, KV, g, W, W] of a pass's queries against its own
    fresh keys under the block-causal mask by block: a query sees its own
    block of ``block_length`` whole and the blocks before it.  A pass of
    one block (``block_length`` None: all of it is one) has nothing to
    mask.  In a pass over several, a position that is not the row's
    (``valid`` [B, W] False: the first block of a row that brings none,
    ``_denoising_round``) is nobody's key: what stands there lies in the
    row's cache."""
    W = s.shape[-1]
    if W <= (block_length or W):
        return s
    block = jnp.arange(W) // block_length
    s = jnp.where(block[None, :] > block[:, None], -1e30, s)
    return jnp.where(valid[:, None, None, None, :], s, -1e30)


def _attend_view_and_fresh(q, view, start, k_new, v_new, block_length=None,
                           valid=None):
    """q [B, H, W, hd] — one diffusion block of a row, at positions
    ``start[b] ..`` — over a dense paged view taken BEFORE the block was
    begun, and the block's own fresh K/V ``k_new`` / ``v_new``
    [B, KV, W, hd]: every query sees the view's positions before ``start``
    (the earlier blocks; what the view holds from there on is stale) and
    the whole block, later positions too (the block-causal mask), in ONE
    softmax over both.  The fresh K/V never pass through the pool, so a
    view gathered once serves every pass over the block.

    A pass over SEVERAL blocks of ``block_length`` (``W`` is wider than
    one) sees the same of the view, and of the fresh keys what
    ``_fresh_mask`` leaves under the positions' ``valid`` [B, W]: a row
    whose leading positions are not valid has them in its cache, which ends
    where its valid positions begin."""
    s_old = _grouped_qk(q, view["k"], view.get("k_s"))      # [B,KV,g,W,L]
    L = view["k"].shape[2]
    if q.shape[2] > (block_length or q.shape[2]):
        start = start + jnp.argmax(valid, axis=1)
    earlier = jnp.arange(L)[None, :] < start[:, None]       # [B, L]
    s_old = jnp.where(earlier[:, None, None, None, :], s_old, -1e30)
    p = jax.nn.softmax(
        jnp.concatenate(
            [s_old, _fresh_mask(_grouped_qk(q, k_new), block_length, valid)],
            axis=-1), axis=-1)
    return (_grouped_pv(p[..., :L], view["v"], q.shape, q.dtype,
                        view.get("v_s"))
            + _grouped_pv(p[..., L:], v_new, q.shape, q.dtype))


def _attend_pool_and_fresh(q, pool_layer, tables, plan, k_new, v_new,
                           interpret: bool = False, block_length=None,
                           valid=None):
    """``_attend_view_and_fresh`` with the pool read in place: the kernel
    (ops.paged_attention, ``plan`` = ``decode_plan(start, active, capacity,
    fresh=0)``) gives each query's weighted sum over the row's cache before
    ``start`` with that softmax's largest score and mass, and the block's
    own fresh keys join here, in float32: ONE softmax over both parts, by
    the larger of the two maxima.  A row whose block starts at 0 has no
    mass in the pool and takes the fresh part alone.

    For a pass over several blocks ``plan`` is a sequence, a plan a block
    of ``q``, each to where that block's cache ends in each row (the
    fresh keys go by the positions' ``valid``, ``_fresh_mask``): the kernel
    serves one block's queries a call (a row that is
    not live in a block's plan is not walked for it), the calls one after
    another as a ``lax.map`` -- ONE place in the program that holds the
    kernel, as a pass of one block has: a second costs a program's trace
    and lowering a third of a second (PERF.md section 6, PR 48)."""
    from seldon_core_tpu.ops.paged_attention import paged_decode_attention

    def cache(q, plan):
        return paged_decode_attention(
            q, pool_layer["k"], pool_layer["v"], tables, *plan,
            interpret=interpret, stats=True)             # [B, KV, g, W(, hd)]

    L = block_length or q.shape[2]
    if q.shape[2] <= L:
        old, peak, mass = cache(q, plan)
    else:
        n = len(plan)
        blocks = q.reshape(q.shape[:2] + (n, L) + q.shape[3:])
        old, peak, mass = (
            jnp.moveaxis(part, 0, 3).reshape(
                part.shape[1:4] + (n * L,) + part.shape[5:])
            for part in jax.lax.map(
                lambda each: cache(*each),
                (jnp.moveaxis(blocks, 2, 0),
                 jax.tree.map(lambda *a: jnp.stack(a), *plan))))
    B, KV, g, W, hd = old.shape
    s = _fresh_mask(_grouped_qk(q, k_new), block_length, valid)  # [B,KV,g,W,W]
    top = jnp.maximum(peak, s.max(axis=-1))
    p = jnp.exp(s - top[..., None])
    mass = mass * jnp.exp(peak - top)
    fresh = jax.lax.dot_general(
        p.astype(v_new.dtype).reshape(B, KV, g * W, W),
        v_new, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32).reshape(old.shape)
    a = ((old * mass[..., None] + fresh)
         / (mass + p.sum(axis=-1))[..., None])
    return a.astype(q.dtype).reshape(q.shape)


def _short_conv(lp, x, pool_layer, tables, start, valid, cfg: LMConfig):
    """The gated short-convolution mixer on x [B, W, D] -> (x', pool
    layer'): ``[b | c | u] = in_proj(norm(x))``; ``z = b * u``; ``y_t =
    sum_j w[j] * z_(t - (K-1) + j)``, a depthwise causal convolution of
    ``K = cfg.conv_kernel`` taps; ``x + out_proj(c * y)``.

    ``z`` before a row's first position of this call is the row's state,
    ``pool_layer["conv"][tables[b, 0]]`` -- ``z`` at the K-1 positions
    before ``start[b]`` -- and zero for a row that starts at 0, whatever the
    entry holds (a reused block needs no reset).  The state left is ``z`` at
    the last K-1 of the row's valid positions: a call of one valid token
    leaves ``[.., old z_-1, new z_0]``, pad positions (``valid`` False, to
    the right of the valid ones) enter neither a valid position's sum nor
    the state, and a row with no valid position (an empty slot) writes the
    scratch entry 0."""
    from seldon_core_tpu.ops.quant import lm_matmul

    B, W, D = x.shape
    K = cfg.conv_kernel
    state = pool_layer["conv"]
    with jax.named_scope("conv_in"):
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        bcu = lm_matmul(lp, "conv_in", h, out_dtype=x.dtype)
    with jax.named_scope("conv"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        z = b * u
        width = jnp.sum(jnp.broadcast_to(valid, (B, W)), axis=1)
        zz, state = _carried_taps(state, z, tables, start, width)
        taps = lp["conv_w"].astype(jnp.float32)
        y = sum(taps[j] * zz[:, j:j + W].astype(jnp.float32)
                for j in range(K))
        y = c * y.astype(x.dtype)
    with jax.named_scope("conv_out"):
        x = x + lm_matmul(lp, "conv_out", y, out_dtype=x.dtype)
    return x, {"conv": state}


def _carried_taps(state, zz, tables, start, width):
    """What a causal convolution of ``K`` taps reads before a row's first
    position of this call, and what it leaves: ``state`` [N, K-1, C] holds
    a sequence's input at its last K-1 positions at the id of its first
    block.  Returns (the input with the carried positions in front,
    [B, K-1+W, C] -- zeros for a row that starts at 0, whatever the entry
    holds -- and ``state`` with each row's last K-1 VALID positions
    written back, the scratch entry 0 for a row with none)."""
    slot = tables[:, 0]
    old = jnp.where((start > 0)[:, None, None], state[slot], 0)
    zz = jnp.concatenate([old.astype(zz.dtype), zz], axis=1)
    # zz index of a row's valid position i is K-1+i: its last K-1 valid
    # positions (reaching into the old state where it has fewer)
    keep = width[:, None, None] + jnp.arange(state.shape[1])[None, :, None]
    left = jnp.take_along_axis(zz, keep, axis=1)
    return zz, state.at[jnp.where(width > 0, slot, 0)].set(
        left.astype(state.dtype))


def _ssm(lp, x, pool_layer, tables, start, valid, cfg: LMConfig,
         fused=False):
    """The Mamba-2 state-space mixer on x [B, W, D] -> (x', pool layer'):
    ``[z | xBC | dt] = in_proj(norm(x))``; ``xBC = silu(conv1d(xBC) +
    bias)``, depthwise and causal over ``cfg.conv_kernel`` taps; ``[x | B |
    C] = xBC`` (x as heads of ``ssm_head_dim``, B and C as ``ssm_groups``
    groups of ``ssm_state``); ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)``, float32; the recurrence of ops/ssm.py -- its step for a
    call of one position a row, its chunked form otherwise; ``y =
    norm_groups(y * silu(z))`` with a weight, an RMSNorm over each of the
    ``ssm_groups`` groups of the inner width; ``x + out_proj(y)``.

    A row's state is ``pool_layer`` at ``tables[b, 0]``: ``conv``, the
    convolution's input at the K-1 positions before ``start[b]``, and
    ``h``, the float32 matrix state -- both zero for a row that starts at
    0, whatever the entry holds (a reused block needs no reset).  Pad
    positions (``valid`` False, to the right of the valid ones) enter
    neither a sum nor the state -- their ``dt`` is 0, which the recurrence
    reads as no position -- and a row with no valid position (an empty
    slot) writes the scratch entry 0.

    ``fused`` has a call of one position a row (a decode round's step) work
    on each live row's ``h`` where it lies in the pool: the kernel of
    ops/ssm.py (``ssm_step_pool``; "interpret": in Pallas interpret mode),
    which reads and writes a live row's entry once and touches no other,
    not even the scratch entry.  Without it, and for any wider call, the
    rows' states are gathered, ``ssm_step`` / ``ssm_chunk`` update the
    copy and the rows are written back one by one.  It rides the call only
    where it says so: the call of seven is what the benchmark's fault
    injectors wrap (tests/bench/test_bench_nemotron.py)."""
    from seldon_core_tpu.ops.quant import lm_matmul
    from seldon_core_tpu.ops.ssm import ssm_chunk, ssm_step, ssm_step_pool

    B, W, D = x.shape
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    inner, K = cfg.ssm_inner, cfg.conv_kernel
    f32 = jnp.float32
    valid = jnp.broadcast_to(valid, (B, W))
    width = jnp.sum(valid, axis=1)
    with jax.named_scope("ssm_in"):
        u = lm_matmul(lp, "ssm_in", _rmsnorm(x, lp["ln1"], cfg.norm_eps),
                      out_dtype=x.dtype)
        z, xbc, dt = jnp.split(u, [inner, inner + cfg.ssm_conv_dim], axis=-1)
    with jax.named_scope("ssm_conv"):
        zz, taps_left = _carried_taps(pool_layer["conv"], xbc, tables, start,
                                      width)
        taps = lp["conv_w"].astype(f32)
        xbc = sum(taps[j] * zz[:, j:j + W].astype(f32) for j in range(K))
        xbc = jax.nn.silu(xbc + lp["conv_b"].astype(f32)).astype(x.dtype)
    with jax.named_scope("ssm"):
        xs, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        xs = xs.reshape(B, W, H, P)
        Bm, Cm = Bm.reshape(B, W, G, N), Cm.reshape(B, W, G, N)
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        dt = jnp.where(valid[..., None], dt, 0.0)             # [B, W, H]
        A = -jnp.exp(lp["A_log"].astype(f32))
        skip = lp["ssm_D"].astype(f32)
        slot = tables[:, 0]
        state = pool_layer["h"]
        if W == 1 and fused:
            y, state = ssm_step_pool(
                xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], skip, state, slot,
                start, width > 0, interpret=fused == "interpret")
            y = y[:, None]
        else:
            h = jnp.where((start > 0)[:, None, None, None], state[slot], 0.0)
            if W == 1:
                y, h = ssm_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                skip, h)
                y = y[:, None]
            else:
                y, h = ssm_chunk(xs, dt, A, Bm, Cm, skip, h)
            # a row's state goes back where it lies, row by row (an empty
            # slot's to the scratch entry 0: what lands there last stays)
            for b, entry in enumerate(jnp.where(width > 0, slot, 0)):
                state = jax.lax.dynamic_update_slice(
                    state, h[b:b + 1].astype(state.dtype), (entry, 0, 0, 0))
    with jax.named_scope("ssm_out"):
        y = y.reshape(B, W, G, inner // G) * jax.nn.silu(
            z.astype(f32)).reshape(B, W, G, inner // G)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (y.reshape(B, W, inner).astype(x.dtype) * lp["ssm_norm"])
        x = x + lm_matmul(lp, "ssm_out", y, out_dtype=x.dtype)
    return x, {"conv": taps_left, "h": state}


def _project_qkv(lp, x, positions, cfg: LMConfig, scope=jax.named_scope):
    """What an attention layer and a retention layer both begin with, on x
    [B, W, D]: the layer's first norm, the fused ``wqkv``, heads apart (q
    [B, H, W, hd]; k, v [B, KV, W, hd]), the per-head norms on q and k under
    ``cfg.qk_norm`` and the rotary embedding at ``positions`` [B, W] under
    ``cfg.rope``.  Returns (the normed input, q, k, v).  ``scope`` names the
    three stages for the trace; a caller that books them under a scope of
    its own passes one that names nothing."""
    from seldon_core_tpu.ops.quant import lm_matmul

    B, W, _ = x.shape
    hd, kv_h = cfg.hd, cfg.kv_heads
    q_out = cfg.n_heads * hd
    with scope("qkv"):
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        qkv = lm_matmul(lp, "wqkv", h, out_dtype=x.dtype)
        q, k, v = jnp.split(qkv, [q_out, q_out + kv_h * hd], axis=-1)
        q = _heads(q, B, W, cfg.n_heads, hd)
        k = _heads(k, B, W, kv_h, hd)
        v = _heads(v, B, W, kv_h, hd)
    if cfg.qk_norm:
        with scope("qk_norm"):
            q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
            k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.rope:
        with scope("rope"):
            q = apply_rope(q, positions, cfg.rope_base)
            k = apply_rope(k, positions, cfg.rope_base)
    return h, q, k, v


def _retention(lp, x, pool_layer, tables, start, valid, cfg: LMConfig,
               fused=False):
    """The power-retention mixer on x [B, W, D] -> (x', pool layer'):
    ``q, k, v`` as attention projects them (``wqkv``; the per-head norms
    under ``cfg.qk_norm``, the rotary embedding under ``cfg.rope``) and
    ``log g = logsigmoid(norm(x) ret_gate + ret_gate_b)``, one a KV head, in
    float32; ops/retention.py between them and ``wo`` -- the recurrent form
    for a call of one position a row, the chunk form otherwise.  A row's
    state is ``pool_layer`` at ``tables[b, 0]``, zero for a row that starts
    at 0; pad positions (``valid`` False, to the right of the valid ones)
    enter nothing, and a row with no valid position is skipped whole.
    ``fused`` is ``retention``'s: whether the call works on the state
    where it lies (the kernels of ops/retention.py: the step for one
    position a row, the chunk form for any other width).  It rides the
    calls -- this function's and ``retention``'s -- only where it says so:
    the call of seven here and of eight there is what the benchmark's
    fault injectors wrap (tests/bench/test_bench_brumby.py)."""
    from seldon_core_tpu.ops.quant import lm_matmul
    from seldon_core_tpu.ops.retention import retention

    B, W, D = x.shape
    hd, kv_h, H = cfg.hd, cfg.kv_heads, cfg.n_heads
    q_out = H * hd
    with jax.named_scope("ret_in"):
        # one scope for the whole of it: the innermost known scope is the
        # one a device op is booked under (bench/lib/trace_scopes.py)
        h, q, k, v = _project_qkv(
            lp, x, start[:, None] + jnp.arange(W)[None, :], cfg,
            scope=lambda name: contextlib.nullcontext())
        log_g = jax.nn.log_sigmoid(
            jnp.einsum("bwd,dk->bkw", h, lp["ret_gate"],
                       preferred_element_type=jnp.float32)
            + lp["ret_gate_b"].astype(jnp.float32)[None, :, None])
    with jax.named_scope("retention"):
        width = jnp.sum(jnp.broadcast_to(valid, (B, W)), axis=1)
        y, pool_layer = retention(
            q.reshape(B, kv_h, H // kv_h, W, hd), k, v, log_g, pool_layer,
            tables[:, 0], start, width, **({"fused": fused} if fused else {}))
        y = y.reshape(B, H, W, hd).transpose(0, 2, 1, 3).reshape(B, W, q_out)
    with jax.named_scope("ret_out"):
        x = x + lm_matmul(lp, "wo", y, out_dtype=x.dtype)
    return x, pool_layer


# A ``jit`` of its own inside the programs that call it: a program traces and
# lowers the block once per distinct (shapes, static arguments) -- a dense
# program once, one whose layers are of several kinds (``kind``) once a kind,
# a round of denoising passes three times (a pass that writes
# nothing, the pass that writes a block's K/V, that pass's last layer) -- and
# every other layer is a
# cached bind that lowers to a ``call`` of one private function, so trace,
# lowering and the module's text no longer grow with depth (PERF.md section
# 6, PR 37).  XLA inlines the calls before it optimises.  Nothing is donated
# here: the programs donate the pool.  ``_paged_block.__wrapped__`` is the
# plain body.
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "interpret", "kv_only", "write", "kind",
                     "fused", "experts"))
def _paged_block(lp, x, pool_layer, tables, start, valid, cfg: LMConfig,
                 plan=None, interpret: bool = False, limit=None,
                 kv_only: bool = False, view=None,
                 write: Optional[int] = None, kind=None,
                 fused: bool = False, experts: Optional[str] = None):
    """One decoder block over the paged pool: K/V written at per-row
    positions start[b] + i (scratch-routed where ``valid`` is False),
    attention over each row's own blocks.  x [B, W, D] -> (x', pool layer',
    the FFN's aux: the experts read by a dropless expert layer, else 0).

    ``kind`` is the layer's ``(mixer, ffn)`` (``LMConfig.kind(i)``; None: an
    attention layer whose FFN is read off its weights): a "conv" layer
    takes ``_short_conv`` in the attention's place, a "ret" layer
    ``_retention`` and an "ssm" layer ``_ssm`` -- its pool entry is the
    state, and what follows about K/V does not concern it; a block of one
    sub-layer is a mixer with no FFN (``ffn`` None) or an FFN with no mixer
    (``mixer`` None: its pool entry is empty); ``fused`` has it work on
    the state where it lies in the pool (the kernels of ops/retention.py,
    a decode round's step and a prefill call's chunk, and of ops/ssm.py, a
    decode round's step -- under ``interpret`` in Pallas interpret mode)
    -- and the FFN is ``transformer._ffn``'s of that kind, a layer of
    dropless experts by ``experts`` (``moe_dropless``'s ``impl``: what
    ``_experts_impl`` makes of a program's ``experts_fused``).

    ``plan`` (ops.paged_attention.decode_plan) selects the in-place
    formulation: attention reads the row's blocks from the pool where they
    lie — one query a row over its cache and its own fresh K/V, or, for
    ``cfg.block_length`` > 1, a diffusion block's queries over the cache
    before ``start`` with the block's fresh K/V joined outside the kernel
    (``_attend_pool_and_fresh``).  None takes the gather path.  ``limit``
    [B] is how far each row really holds positions, for the mask of
    ``cfg.block_length`` > 1 (``_attend_paged``).  ``kv_only`` stops once
    the K/V are written: the last layer of a pass whose hidden states
    nobody reads (``_denoising_round``: the pass that writes a finished
    block's K/V).  ``view`` is a dense view of this layer's blocks gathered
    before a diffusion block was begun: attention then goes over it and the
    fresh K/V together (``_attend_view_and_fresh``) and gathers nothing.
    ``write`` is how many of the call's LEADING positions' K/V the pool
    keeps: None all of them, 0 none (a denoising pass: its K/V are not
    kept), a block's length of a call over two blocks (``W //
    cfg.block_length`` says so) for the pass that writes a finished block's
    K/V with the next block's first denoising pass riding it -- the fresh
    keys are then masked by block and by ``valid`` (``_fresh_mask``: a row
    that brings no finished block has that half not valid, and its cache
    reaches up to the second), the view or the pool serves each row's
    cache to both blocks, and ``plan`` is a plan a block."""
    from seldon_core_tpu.ops.paged_attention import paged_decode_attention
    from seldon_core_tpu.ops.quant import lm_matmul

    B, W, D = x.shape
    hd = cfg.hd
    q_out = cfg.n_heads * hd
    mixer, ffn = kind or ("attn", None)
    # a block of one sub-layer (``kind`` says so; without it the FFN is
    # read off the weights) lacks one half
    lone = kind is not None and ffn is None

    def alone(x, pool_layer, aux):
        """What a block of one sub-layer hands on, behind a barrier: XLA's
        TPU compiler, left to schedule fourteen such blocks as one stretch,
        made a prefill program that never ended on the chip for block
        tables whose ids do not ascend over the call's rows (found by
        bisection, PERF.md section 6, PR 46: the same layers end behind a
        barrier a block, and with the K/V rows written in ascending
        order); behind it each block's writes are whole before the next
        block is scheduled."""
        x, pool_layer = jax.lax.optimization_barrier((x, pool_layer))
        return x, pool_layer, aux

    def feed_forward(x):
        if lone:
            return x, jnp.int32(0)
        with jax.named_scope("ffn"):
            h = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
            y, aux = _ffn(lp, h, cfg, mesh=None,
                          valid=jnp.broadcast_to(valid, (B, W))
                          if cfg.d_expert else None, kind=ffn,
                          experts=experts)
            return x + y, aux

    if mixer is None:
        x, aux = feed_forward(x)
        return alone(x, pool_layer, aux)
    if mixer in ("conv", "ret", "ssm"):
        how = {}
        if mixer in ("ret", "ssm") and fused:
            how["fused"] = "interpret" if interpret else True
        x, pool_layer = {"conv": _short_conv, "ret": _retention,
                         "ssm": _ssm}[mixer](
            lp, x, pool_layer, tables, start, valid, cfg, **how)
        x, aux = feed_forward(x)
        return alone(x, pool_layer, aux) if lone else (x, pool_layer, aux)
    # the stages below are jax.named_scope's: op metadata only (same
    # programs, same numerics), read back from a profile window's device
    # ops by bench/lib/trace_scopes.py — keep the names stable
    positions = start[:, None] + jnp.arange(W)[None, :]  # [B, W] per-row
    _, q, k, v = _project_qkv(lp, x, positions, cfg)
    kept = W if write is None else write
    if kept:
        with jax.named_scope("kv_write"):
            fresh = (positions, valid, k, v)
            if kept < W:
                fresh = (positions[:, :kept], valid[:, :kept],
                         k[:, :, :kept], v[:, :, :kept])
            pool_layer = _paged_write(pool_layer, tables, *fresh)
    if kv_only:
        return x, pool_layer, jnp.int32(0)
    gathered = view is not None
    if plan is None and not gathered:
        with jax.named_scope("kv_gather"):
            view = _paged_view(pool_layer, tables, hd)
    with jax.named_scope("attn"):
        if gathered:
            a = _attend_view_and_fresh(q, view, start, k, v,
                                       cfg.block_length, valid)
        elif plan is None:
            a = _attend_paged(q, view, start, cfg.block_length, limit)
        elif cfg.block_length > 1:
            a = _attend_pool_and_fresh(q, pool_layer, tables, plan, k, v,
                                       interpret, cfg.block_length, valid)
        else:
            a = paged_decode_attention(
                q, pool_layer["k"], pool_layer["v"], tables, *plan,
                interpret=interpret)
        a = a.transpose(0, 2, 1, 3).reshape(B, W, q_out)
    with jax.named_scope("wo"):
        x = x + lm_matmul(lp, "wo", a, out_dtype=x.dtype)
    x, aux = feed_forward(x)
    return alone(x, pool_layer, aux) if lone else (x, pool_layer, aux)


def _experts_counted(read, cfg: LMConfig):
    """What the expert layers of a program counted (``_ffn``'s aux, summed
    over layers and passes) by the names the scheduler reads it under:
    ``experts_read`` and, where a layer holds a share of the experts it
    routes over (``cfg.experts_held``: the aux is a pair), the picks that
    fell on held experts, ``expert_slots_held``.  ``read`` None gives the
    sum's zero."""
    if read is None:
        return (jnp.zeros((2,), jnp.int32) if cfg.experts_held
                else jnp.int32(0))
    if cfg.experts_held:
        return {"experts_read": read[0], "expert_slots_held": read[1]}
    return {"experts_read": read}


def _head(params, cfg: LMConfig):
    """The unembedding matrix [D, V]: the tied embedding, or ``lm_head``
    where the configuration unties it."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def paged_forward(params, tokens, pool, tables, start, width,
                  cfg: LMConfig, last_only: bool = True, head: bool = True,
                  fused=None, experts_fused=None):
    """Forward W tokens per row at per-row offsets over the paged pool —
    chunked prefill (one prompt chunk at a time, decode never stalls for
    the whole prompt) and the speculative verify pass share this program.

    tokens [B, W] int32; start [B] per-row global offset of token 0;
    width [B] valid token count per row (positions past it are pad: their
    K/V go to scratch, their logits are garbage nobody reads).  Returns
    (logits, pool'): logits [B, V] at each row's LAST valid position when
    ``last_only`` (prefill needs only the next-token distribution — the
    unembed is ~20% of prefill FLOPs at real vocab sizes), else [B, W, V]
    for every position (the verify pass scores all of them).

    ``head`` False is for a caller that reads no logits (a generator by
    diffusion over blocks chooses no token from its prompt): the final norm
    and the unembedding are left out, and in the logits' place comes the
    experts the call's expert layers read, an int32 (0 without experts).

    ``fused`` concerns a generator of retention layers alone: whether a
    chunk works on each live row's state where it lies in the pool (the
    chunk kernel of ops/retention.py).  None decides by
    ``retention_fused(pool, width=W)`` for this batch, as
    ``paged_decode_round(inplace=None)`` does (a caller with a mesh passes
    its own answer); True / False force the kernel / ``jax.numpy`` row by
    row; "interpret" runs the kernel in Pallas interpret mode (tests on
    the CPU).  ``experts_fused`` concerns layers of dropless experts, with
    the same four answers: whether an expert's whole feed-forward is one
    kernel (parallel/moe.py ``_experts_fused``) or two grouped matmuls;
    None decides by ``experts_fused(cfg, dtype=...)``."""
    B, W = tokens.shape
    experts = _experts_impl(experts_fused, params, cfg)
    if fused is None:
        fused = retention_fused(pool, heads=cfg.n_heads, rows=B, width=W,
                                dtype=params["embed"].dtype)
    valid = jnp.arange(W)[None, :] < width[:, None]  # [B, W]
    # block diffusion: a prompt's short last block sees itself and no
    # further; a chunk is whole blocks (the scheduler's chunk is a multiple
    # of block_length), so no position asks for a key a later chunk brings
    limit = start + width if cfg.block_length > 1 else None
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    read = jnp.int32(0)
    for i in range(cfg.n_layers):
        x, pool[f"l{i}"], aux = _paged_block(
            params[f"l{i}"], x, pool[f"l{i}"], tables, start, valid, cfg,
            limit=limit, kind=cfg.kind(i), fused=bool(fused),
            interpret=fused == "interpret", experts=experts,
        )
        if cfg.d_expert:
            read = read + aux
    if not head:
        return _experts_counted(read, cfg)["experts_read"], pool
    with jax.named_scope("unembed"):
        if last_only:
            idx = jnp.clip(width - 1, 0, W - 1)
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(idx[:, None, None], (B, 1, x.shape[2])),
                axis=1,
            )  # [B, 1, D] — before the (positionwise) norm: same numerics
        x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = (x @ _head(params, cfg)).astype(jnp.float32)
    return (logits[:, 0, :] if last_only else logits), pool


def decode_inplace(pool, mesh=None, width: int = 1, heads=None,
                   rows: int = 1, head_dim=None) -> bool:
    """Whether a decode round over ``pool`` attends in place (the Pallas
    kernel) or through the gather path: ops.paged_attention
    .inplace_supported over what is observable here — the backend, the
    pool's dtype and shapes, the caller's mesh, the queries a row brings
    to a step (``width``: 1, or a diffusion block's ``block_length``) and
    the query ``heads`` and padded ``rows`` of the widest batch the caller
    will bring, which the kernel holds whole.  ``head_dim`` is the model's
    head width (``cfg.hd``): a pool row may carry several heads
    (``init_block_pool``), so the KV heads are the row's values over it; a
    caller that does not say is answered for a head a row."""
    from seldon_core_tpu.ops.paged_attention import inplace_supported

    kv = _pool_kv(pool)
    if kv is None:
        return False        # no layer attends: nothing to read in place
    k = kv["k"]
    head_dim = head_dim or k.shape[3]
    return inplace_supported(
        width=width, backend=jax.default_backend(), pool_dtype=k.dtype,
        mesh=mesh, block_size=k.shape[1],
        kv_heads=k.shape[2] * k.shape[3] // head_dim, head_dim=head_dim,
        heads=heads, rows=rows)


def retention_fused(pool, mesh=None, heads=None, rows: int = 1,
                    width: int = 1, dtype=None) -> bool:
    """Whether a call of ``width`` positions a row of a generator of
    retention layers works on each live row's state where it lies in
    ``pool`` (the Pallas kernels of ops/retention.py: the step for a
    decode round's one position, the chunk form for a prefill call's
    ``width``) or row by row in ``jax.numpy``: ops.retention
    .step_supported / .chunk_supported over what is observable here, as
    ``decode_inplace`` asks for attention -- the backend, the state's dtype
    and shapes, the caller's mesh, the query ``heads`` and padded ``rows``
    of the widest batch the caller will bring and, for a chunk, the
    activations' ``dtype``.  False for a pool without such layers."""
    from seldon_core_tpu.ops.retention import chunk_supported, step_supported

    state = next((e for e in pool.values() if "s" in e), None)
    if state is None:
        return False
    kv_heads = state["z"].shape[1]
    seen = dict(
        backend=jax.default_backend(), state_dtype=state["s"].dtype,
        head_dim=state["s"].shape[1] // kv_heads, mesh=mesh,
        kv_heads=kv_heads, heads=heads)
    if width == 1:
        return step_supported(rows=rows, **seen)
    return chunk_supported(width=width, act_dtype=dtype or jnp.bfloat16,
                           **seen)


def ssm_fused(pool, mesh=None, rows: int = 1) -> bool:
    """Whether a decode round's step of a generator with Mamba-2
    state-space layers updates each live row's ``h`` where it lies in
    ``pool`` (the Pallas kernel of ops/ssm.py) or over gathered rows in
    ``jax.numpy``: ops.ssm.step_supported over what is observable here, as
    ``retention_fused`` asks for a retention state -- the backend, the
    state's dtype and shapes, the caller's mesh and the padded ``rows`` of
    the widest batch the caller will bring.  B and C are the taps' channels
    beside x (``conv`` [.., H P + 2 G N]), which gives the groups.  False
    for a pool without such layers."""
    from seldon_core_tpu.ops.ssm import step_supported

    entry = next((e for e in pool.values() if "h" in e), None)
    if entry is None:
        return False
    _, H, P, N = entry["h"].shape
    return step_supported(
        backend=jax.default_backend(), state_dtype=entry["h"].dtype,
        heads=H, head_dim=P, groups=(entry["conv"].shape[2] - H * P)
        // (2 * N), state=N, mesh=mesh, rows=rows)


def experts_fused(cfg: LMConfig, mesh=None, dtype=None) -> bool:
    """Whether the layers of dropless experts of a generator of ``cfg``
    take an expert's whole feed-forward as one kernel (parallel/moe.py
    ``_experts_fused``) or as two grouped matmuls with the activation
    between them: parallel.moe.fused_supported over what is observable
    here, as ``ssm_fused`` asks for a state-space layer -- the backend, the
    caller's mesh, the experts' ``dtype`` (the configuration's where the
    caller does not say), the model's and an expert's width and whether an
    expert has a gate.  False for a generator without such layers."""
    from seldon_core_tpu.parallel.moe import fused_supported

    if not cfg.d_expert:
        return False
    return fused_supported(
        backend=jax.default_backend(), dtype=dtype or cfg.dtype, mesh=mesh,
        d_model=cfg.d_model, d_expert=cfg.d_expert,
        gated=cfg.expert_act == "silu")


def _experts_impl(answer, params, cfg: LMConfig) -> Optional[str]:
    """``moe_dropless``'s ``impl`` for a program's ``experts_fused``: None
    asks ``experts_fused`` for this program's parameters, "interpret" runs
    the kernel in Pallas interpret mode (tests on the CPU), True forces it,
    False leaves the two grouped matmuls to the platform."""
    if answer is None:
        answer = experts_fused(cfg, dtype=params["embed"].dtype)
    if not answer:
        return None
    return "fused_interpret" if answer == "interpret" else "fused"


def paged_decode_round(params, pool, tables, token, n_valid, active,
                       seen_eos, keys, cfg: LMConfig, *, span: int,
                       temperature: float, top_k: int, top_p: float,
                       eos_token: int, inplace=None, ssm_inplace=None,
                       experts_fused=None, trace_passes: bool = False):
    """``span`` cached decode steps for the whole in-flight batch as ONE
    lax.scan — the scheduler's unit of work between admission points.
    (``cfg.block_length`` > 1: ``span`` positions as blocks of denoising
    passes, ``_denoising_round`` below, with the same operands and results.)

    ``inplace``: None decides by ``decode_inplace(pool, width=...)`` for
    this batch (a caller that shards the pool over a mesh passes its own
    answer: a traced program cannot see shardings); True / False force the
    kernel / the gather path; "interpret" runs the kernel in Pallas
    interpret mode (tests on the CPU).  For a generator of retention
    layers the kernel is the state's (``retention_fused`` decides, False is
    the row-by-row step of ops/retention.py): either way the answer says
    whether the step works on the pool where it lies.  ``ssm_inplace`` is the
    same question of the Mamba-2 state-space layers, which stand BESIDE
    attention layers in one generator, so their answer is apart: None
    decides by ``ssm_fused(pool)``, True / False force the kernel of
    ops/ssm.py / ``ssm_step`` over gathered rows, "interpret" runs the
    kernel in Pallas interpret mode.  ``experts_fused`` is the same
    question of the layers of dropless experts (one kernel an expert's
    feed-forward, or two grouped matmuls): None decides by
    ``experts_fused(cfg, dtype=...)``.

    token [B] pending tokens (diffusion blocks: [B, L], a row's first block
    as the round finds it -- the prompt's remainder, or the block the round
    before left to this one, and ``token'`` is this round's); n_valid [B]
    per-row cache length (diffusion blocks: the positions FIXED; the pool
    of a row that brings a block lags it by that block); active [B]
    masks empty slots (their writes go to scratch, their samples are
    forced to 0); seen_eos [B] is the device-side after-eos latch (rows
    past their stop keep riding the scan but emit eos — the generate()
    output contract — until the host retires them at the round boundary);
    keys [B] per-ROW PRNG keys (sampled decoding must not couple co-batched
    requests the way a shared batch key does).  Returns
    (toks [B, span], pool', token', n_valid', seen_eos', keys') and, for a
    configuration with dropless experts, a seventh: ``{"experts_read":
    int32}``, the experts the round's expert layers read, summed over its
    layers and steps."""
    from seldon_core_tpu.ops.paged_attention import decode_plan

    if cfg.block_length > 1:
        return _denoising_round(
            params, pool, tables, token, n_valid, active, seen_eos, keys,
            cfg, span=span, temperature=temperature, eos_token=eos_token,
            inplace=inplace, experts_fused=experts_fused,
            trace_passes=trace_passes)
    if inplace is None:
        inplace = (decode_inplace(pool, heads=cfg.n_heads,
                                  rows=n_valid.shape[0], head_dim=cfg.hd)
                   or retention_fused(pool, heads=cfg.n_heads,
                                      rows=n_valid.shape[0]))
    if ssm_inplace is None:
        ssm_inplace = ssm_fused(pool, rows=n_valid.shape[0])
    experts = _experts_impl(experts_fused, params, cfg)
    kv = _pool_kv(pool)     # (a plan is made only where a layer attends)
    capacity = tables.shape[1] * kv["k"].shape[1] if kv else 0

    def step(carry, _):
        pool, token, n_valid, seen_eos, keys, *read = carry
        # the kernel's scalar operands: once a step, shared by the layers
        plan = (decode_plan(n_valid, active, capacity)
                if inplace and kv else None)
        with jax.named_scope("embed"):
            x = params["embed"][token][:, None, :]
        for i in range(cfg.n_layers):
            # a state-space layer works in place by its own answer
            how = ssm_inplace if cfg.kind(i)[0] == "ssm" else inplace
            x, pool[f"l{i}"], aux = _paged_block(
                params[f"l{i}"], x, pool[f"l{i}"], tables, n_valid,
                active[:, None], cfg, plan=plan,
                interpret=how == "interpret", kind=cfg.kind(i),
                fused=bool(how), experts=experts,
            )
            read = [r + aux for r in read]
        with jax.named_scope("unembed"):
            x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
            logits = (x[:, 0, :] @ _head(params, cfg)).astype(jnp.float32)
        with jax.named_scope("sample"):
            if temperature <= 0.0:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                split = jax.vmap(jax.random.split)(keys)  # [B, 2] keys
                keys = split[:, 0]
                nxt = jax.vmap(
                    lambda lg, kk: sample_token(
                        lg[None, :], kk, temperature, top_k, top_p
                    )[0]
                )(logits, split[:, 1])
            if eos_token >= 0:
                nxt = jnp.where(seen_eos, jnp.int32(eos_token), nxt)
                seen_eos = seen_eos | (nxt == eos_token)
            nxt = jnp.where(active, nxt, 0)
            n_valid = n_valid + active.astype(jnp.int32)
        return (pool, nxt, n_valid, seen_eos, keys, *read), nxt

    # the experts read ride the carry only where there are experts: a dense
    # configuration's program is the one it always was
    read = (_experts_counted(None, cfg),) if cfg.d_expert else ()
    (pool, token, n_valid, seen_eos, keys, *read), toks = jax.lax.scan(
        step, (pool, token, n_valid, seen_eos, keys, *read), None,
        length=span
    )
    out = (toks.T, pool, token, n_valid, seen_eos, keys)
    return out + (_experts_counted(read[0], cfg),) if read else out


def _denoising_round(params, pool, tables, token, n_valid, active, seen_eos,
                     keys, cfg: LMConfig, *, span: int, temperature: float,
                     eos_token: int, inplace=None, experts_fused=None,
                     trace_passes: bool = False):
    """A decode round of a generator by diffusion over blocks: ``span /
    block_length`` blocks a row, one after another (a ``lax.scan``), greedy.

    The sequence is cut into blocks of ``L = cfg.block_length`` at
    multiples of ``L``.  For ``cfg.denoising_steps`` passes the whole block
    goes through the model over the cache of the earlier blocks under the
    block-causal mask; the logits at its masked places give a candidate
    (the argmax over every id but the mask id) and a confidence (that id's
    softmax probability), and the ``L / denoising_steps`` masked places of
    highest confidence are fixed (``fix``).  Then the block goes through
    once more, whole: that pass's K/V is what the cache keeps, it needs no
    logits, and its last layer stops at its K/V.

    **That pass is never a pass of the device of its own.**  The pass that
    writes block b's K/V and the first denoising pass of block b + 1 follow
    each other over the same rows, the second reads what the first has just
    written, and every weight -- a dropless expert layer takes a token
    alone -- is position-wise, so the two are ONE pass of the layers over
    ``[B, 2 L]`` (``shared``) under the block-causal mask: block b's
    positions see the cache before b and themselves; block b + 1's, masked,
    see the cache before b, block b's fresh K/V and themselves.  The
    weights, the experts the two passes chose among them, are read once.
    The pool keeps block b's K/V alone.  In the last layer block b stops at
    its K/V and block b + 1 goes through whole, as two calls: the K/V
    pass's last layer on block b, then a denoising pass's layer on block
    b + 1 at its own start, which finds block b's K/V in the cache (the
    layer's ``wqkv`` is read twice, nothing else).  A round boundary
    changes nothing in this: **the round's LAST block leaves its K/V pass
    to the next round's first pass**, so every block of every round comes
    with its first pass made and runs ``denoising_steps - 1`` of its own --
    ``blocks * denoising_steps`` passes of the device a round -- and a
    row's last block, which nobody will read, gets no K/V pass at all.

    **What a round takes and hands on.**  ``token`` [B, L] is a row's first
    block as the round finds it.  A row in its first round since its
    prefill brings the prompt's remainder: those ids (>= 0) at the block's
    first ``n_valid % L`` places (a ``[B]`` token is taken for every place:
    the prefill chose none, and what stands at a masked place is not
    read); the first half of its shared pass is not valid (no expert
    picked, no cache walked, nothing written), and its second half's cache
    reaches up to the block.  A row that rode the round before BRINGS that
    round's last block, fixed and not yet in the pool, as the complement
    of its ids (``~ids``, < 0: what ``token'`` holds): ``n_valid`` counts
    its positions (so ``n_valid % L == 0``: no row both brings a block and
    holds a remainder) while the pool holds K/V up to ``n_valid - L``
    only.  **The pool of a row that brings a block lags ``n_valid`` by
    that block**; the caller hands ``token'`` on untouched (the static
    lane, ``_denoising_lane``) or by slot (``GenServer``, models/served.py
    ``Served.held``).

    A denoising pass's K/V are NOT stored: every pass attends over the
    cache before the block's start and its own fresh K/V in ONE softmax,
    and only a shared pass's first half writes the pool.  ``inplace`` (as
    for ``paged_decode_round``: None decides by ``decode_inplace(pool,
    width=L)``) says how the cache is read: in place, the block's ``L``
    queries a row folded into the kernel's query group, the fresh part
    joined outside it (``_attend_pool_and_fresh``), nothing gathered; or
    on the gather path, each layer's blocks gathered into a dense view once
    a block, after the shared pass's write (``_attend_view_and_fresh``):
    it serves the block's own passes and the next shared pass.
    ``_paged_block`` stays the one block both kinds of round run.

    Returns what ``paged_decode_round`` returns: the finished blocks
    [B, span] (a row's NEW tokens are those from its ``n_valid`` on; after
    a generated ``eos_token`` a row emits eos, the latch ``seen_eos``
    carried on), the pool, ``token'`` [B, L] (the round's last block as
    the next round takes it, ``~ids``: the ids the passes fixed, whatever
    the latch makes of them; 0 for an empty slot), ``n_valid'`` (the
    round's end), ``seen_eos'``, ``keys``; then ``{"experts_read":
    int32}`` where the configuration has experts (a shared pass counts the
    experts it read, once); then, under ``trace_passes`` (the benchmark's
    driver, archs/<arch>/drive.py), what every denoising pass ``saw``,
    ``picked`` and ``chose``, each [blocks, steps, B, L] (a block's step 0
    is the shared pass's second half)."""
    from seldon_core_tpu.ops.paged_attention import decode_plan

    L, steps = cfg.block_length, cfg.denoising_steps
    if span % L:
        raise ValueError(f"span={span} is no whole number of blocks of {L}")
    if temperature > 0.0:
        raise ValueError("a round of denoising passes decodes greedily")
    B, blocks = n_valid.shape[0], span // L
    if inplace is None:
        inplace = decode_inplace(pool, width=L, heads=cfg.n_heads, rows=B,
                                 head_dim=cfg.hd)
    experts = _experts_impl(experts_fused, params, cfg)
    capacity = tables.shape[1] * _pool_kv(pool)["k"].shape[1]
    if token.ndim == 1:
        token = jnp.broadcast_to(token[:, None], (B, L))
    brings = active & (token[:, 0] < 0)
    token = jnp.where(token < 0, ~token, token)
    base = n_valid - n_valid % L
    valid = jnp.broadcast_to(active[:, None], (B, L))
    head, last = _head(params, cfg), cfg.n_layers - 1

    def layer(i, h, entry, start, valid, plan, view, write, kv_only=False):
        """Layer ``i`` over ``h`` (``_paged_block``; one spelling of the
        call, so that equal shapes bind one trace)."""
        return _paged_block(
            params[f"l{i}"], h, entry, tables, start, valid, cfg, plan=plan,
            interpret=inplace == "interpret", view=view, write=write,
            kv_only=kv_only, kind=cfg.kind(i), experts=experts)

    def through(pool, plan, views, x, start, valid, write: int,
                upto: int = cfg.n_layers):
        """The ids ``x`` [B, W] at ``start`` through the first ``upto``
        layers (all of them: a whole pass), each row over its cache -- by
        the kernel's ``plan``, or ``views``, the layers' caches gathered at
        the block's start; the pool keeps the K/V of the first ``write``
        positions."""
        read = _experts_counted(None, cfg)
        with jax.named_scope("embed"):
            h = params["embed"][x]
        for i in range(upto):
            h, entry, aux = layer(
                i, h, pool[f"l{i}"], start, valid, plan, views and views[i],
                write)
            if write:
                pool[f"l{i}"] = entry
            if cfg.d_expert:
                read = read + aux
        return h, pool, read

    # how a block's passes read the cache before its start, made once a
    # block: the kernel's scalar operands, or each layer's view
    def planned(start, live=active):
        return decode_plan(start, live, capacity, fresh=0) if inplace else None

    def gathered(pool, upto: int = cfg.n_layers):
        if inplace:
            return None
        with jax.named_scope("kv_gather"):
            return [_paged_view(pool[f"l{i}"], tables, cfg.hd)
                    for i in range(upto)]

    # (two places run it, the shared pass and a block's own passes: one
    # trace and one lowering for them)
    @jax.jit
    def fix(h, x, masked):
        """A pass's hidden states ``h`` of a block ``x``: the block with
        its surest masked places fixed, what stays masked, and what the
        pass saw, picked and chose."""
        with jax.named_scope("unembed"):
            h = _rmsnorm(h, params["ln_f"], cfg.norm_eps)
            logits = (h @ head).astype(jnp.float32)           # [B, L, V]
        with jax.named_scope("sample"):
            # the mask id is never an answer
            logits = logits.at[..., cfg.mask_id].set(-jnp.inf)
            chose = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sure = jnp.exp(jnp.max(logits, axis=-1)
                           - jax.nn.logsumexp(logits, axis=-1))
            sure = jnp.where(masked, sure, -1.0)
            rank = jnp.argsort(jnp.argsort(-sure, axis=-1), axis=-1)
            picked = masked & (rank < L // steps)
        return (jnp.where(picked, chose, x), masked & ~picked,
                (x, picked, chose))

    def latch(x, pos, seen_eos):
        """The ids ``x`` at ``pos`` as the round emits them: eos after a
        generated eos, 0 for an empty slot; and the latch."""
        out = x
        if eos_token >= 0:
            # only a GENERATED eos stops a row: the prompt's remainder may
            # hold the id
            hit = (x == eos_token) & (pos >= n_valid[:, None])
            hits = hit.astype(jnp.int32)
            after = (jnp.cumsum(hits, axis=1) - hits) > 0
            out = jnp.where(seen_eos[:, None] | after,
                            jnp.int32(eos_token), x)
            seen_eos = seen_eos | jnp.any(hit, axis=1)
        return jnp.where(active[:, None], out, 0), seen_eos

    def block(carry, b):
        """Block ``b`` of the round: the pass that writes the K/V of the
        block ``before`` it -- the round's own or, ahead of the round's
        first, the one a row ``came`` with -- with ``b``'s first denoising
        pass riding it, then ``b``'s other passes."""
        pool, read, before, came, views = carry
        start = base + b * L
        # the block as its first pass finds it: the prompt's remainder,
        # which only a row's first block holds, then the mask id
        masked = start[:, None] + jnp.arange(L)[None, :] >= n_valid[:, None]
        x = jnp.where(masked, jnp.int32(cfg.mask_id), token)
        kept, writing = valid & came[:, None], planned(start - L, came)
        with jax.named_scope("shared"):
            # both blocks through every layer but the last, as one pass; a
            # row that came with no block has the first half not valid and
            # what lies before ``start`` in its cache
            h, pool, r = through(
                pool, (writing, planned(jnp.where(came, start - L, start)))
                if inplace else None, views,
                jnp.concatenate([before, x], axis=1), start - L,
                jnp.concatenate([kept, valid], axis=1), L, upto=last)
            # the last layer: the block before stops at its K/V, and block
            # b goes through it as a denoising pass's block does, over a
            # cache that now holds them
            _, entry, _ = layer(
                last, h[:, :L], pool[f"l{last}"], start - L, kept, writing,
                views and views[last], L, True)
            pool[f"l{last}"], view = entry, None
            if not inplace:
                with jax.named_scope("kv_gather"):
                    view = _paged_view(entry, tables, cfg.hd)
            plan = planned(start)
            h, _, aux = layer(last, h[:, L:], entry, start, valid, plan,
                              view, 0)
            if cfg.d_expert:
                r = r + aux
            x, masked, saw = fix(h, x, masked)
            # (the last layer's view is the one just gathered)
            views = view and gathered(pool, last) + [view]

        def denoise(c, _):
            x, masked, read = c
            with jax.named_scope("denoise"):
                h, _, r = through(pool, plan, views, x, start, valid, 0)
                x, masked, saw = fix(h, x, masked)
            return (x, masked, read + r), saw

        (x, _, read), seen = jax.lax.scan(
            denoise, (x, masked, read + r), None, length=steps - 1)
        seen = jax.tree.map(lambda one, rest: jnp.concatenate(
            [one[None], rest]), saw, seen)
        return (pool, read, x, active, views), (x, seen)

    (pool, read, x, _, _), (toks, seen) = jax.lax.scan(
        block, (pool, _experts_counted(None, cfg), token, brings,
                gathered(pool)), jnp.arange(blocks))
    toks, seen_eos = latch(
        toks.transpose(1, 0, 2).reshape(B, span),
        base[:, None] + jnp.arange(span)[None, :], seen_eos)
    n_valid = jnp.where(active, base + span, n_valid)
    out = (toks, pool, jnp.where(active[:, None], ~x, 0), n_valid, seen_eos,
           keys)
    if cfg.d_expert:
        out += (_experts_counted(read, cfg),)
    if trace_passes:
        out += (dict(zip(("saw", "picked", "chose"), seen)),)
    return out


def paged_spec_round(t_params, d_params, t_pool, d_pool, t_tables,
                     d_tables, token, n_valid, active, t_cfg: LMConfig,
                     d_cfg: LMConfig, *, k: int):
    """One speculative draft/verify round over paged pools — speculative
    decoding composed with continuous batching (greedy, float KV, the
    speculative.py constraints).

    The paged layout makes this SIMPLER than speculative.py's round-
    aligned holes: pools are mutable buffers donated across rounds, so
    rejected candidates' K/V are just stale slots past ``n_valid`` that
    the next round overwrites before anything can attend them (attention
    masks at n_valid).  Draft runs k+1 single-token paged steps (the +1
    writes the last proposal's K/V so a fully-accepted round leaves no
    draft-cache hole — same trick as speculative.py), target verifies all
    k+1 positions in one paged_forward, and greedy acceptance takes the
    longest matched prefix plus the corrected token.  Returns
    (new_toks [B, k+1], gained [B], corrected [B], t_pool', d_pool'):
    row b's round output is new_toks[b, :gained[b]], its next pending
    token is corrected[b]."""
    if set(t_cfg.layer_kinds + d_cfg.layer_kinds) & set("crm"):
        raise ValueError(
            "speculative decoding cannot serve a gated short-convolution, "
            "retention or state-space layer: a rejected draft would have to "
            "roll the "
            "layer's state back, and the state keeps no history to roll "
            "back to")
    B = token.shape[0]
    W = k + 1

    def dstep(carry, _):
        d_pool, tok, nv = carry
        x = d_params["embed"][tok][:, None, :]
        for i in range(d_cfg.n_layers):
            x, d_pool[f"l{i}"], _ = _paged_block(
                d_params[f"l{i}"], x, d_pool[f"l{i}"], d_tables, nv,
                active[:, None], d_cfg, kind=d_cfg.kind(i),
            )
        x = _rmsnorm(x, d_params["ln_f"])
        logits = (x[:, 0, :] @ d_params["embed"].T).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (d_pool, nxt, nv + 1), tok

    (d_pool, _, _), seg = jax.lax.scan(
        dstep, (d_pool, token, n_valid), None, length=W
    )
    seg = seg.transpose(1, 0)  # [B, W] = [pending, d1 .. dk]
    widths = jnp.where(active, jnp.int32(W), jnp.int32(0))
    # (expert layers as the draft's steps above run them: this round is
    # given no answer of the pool's owner, who may hold a mesh)
    t_logits, t_pool = paged_forward(
        t_params, seg, t_pool, t_tables, n_valid, widths, t_cfg,
        last_only=False, experts_fused=False,
    )
    t_argmax = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B, W]
    draft = seg[:, 1:]  # [B, k]
    match = draft == t_argmax[:, :k]
    a = jnp.argmin(
        jnp.concatenate([match, jnp.zeros((B, 1), bool)], axis=1), axis=1
    )  # first mismatch; k if all matched
    corrected = jnp.take_along_axis(t_argmax, a[:, None], axis=1)[:, 0]
    padded = jnp.concatenate([draft, jnp.zeros((B, 1), jnp.int32)], axis=1)
    new_toks = jnp.where(
        jnp.arange(W)[None, :] < a[:, None], padded, corrected[:, None]
    )
    gained = jnp.where(active, a + 1, 0).astype(jnp.int32)
    return new_toks, gained, corrected, t_pool, d_pool


def paged_copy_block(pool, src, dst):
    """Copy block ``src`` onto block ``dst`` in every layer, pool to pool
    (a short-convolution or state-space layer's state at that id with it).
    A shared prefix's full blocks are written once and SHARED by block-table
    reference across every sequence (pinned in the allocator); the partly
    filled boundary block must be private, because the sequence's own
    tokens continue into it — admission copies it into the row's first
    block.  Offsets past the prefix's tail carry whatever the pinned block
    holds; the row's own prefill overwrites them before anything attends
    there (attention masks at n_valid)."""
    return {
        li: {name: buf.at[dst].set(buf[src]) for name, buf in layer.items()}
        for li, layer in pool.items()
    }


# pools are DONATED through every paged program: the scheduler owns exactly
# one live pool pytree per model and rebinds it after each dispatch, so XLA
# mutates the blocks in place instead of copying the whole pool per step
paged_forward_jit = jax.jit(
    paged_forward,
    static_argnames=("cfg", "last_only", "head", "fused", "experts_fused"),
    donate_argnums=(2,)
)
paged_decode_round_jit = jax.jit(
    paged_decode_round,
    static_argnames=("cfg", "span", "temperature", "top_k", "top_p",
                     "eos_token", "inplace", "ssm_inplace", "experts_fused",
                     "trace_passes"),
    donate_argnums=(1,),
)
paged_spec_round_jit = jax.jit(
    paged_spec_round, static_argnames=("t_cfg", "d_cfg", "k"),
    donate_argnums=(2, 3),
)
paged_copy_block_jit = jax.jit(paged_copy_block, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# The static lane: one request, its own pool, the same programs
# ---------------------------------------------------------------------------

#: positions per KV block — the scheduler's default block size and the
#: static lane's (ROADMAP C9 owns the number for both)
BLOCK_SIZE = 16


def private_pool(cfg: LMConfig, rows: int, positions: int, mesh=None):
    """A request's own pool and tables: ``rows`` rows of ``positions``
    positions, ``1 + rows * ceil(positions / BLOCK_SIZE)`` blocks in all.
    Row b owns blocks ``1 + b*n .. b*n + n`` in order (identity tables —
    nothing to allocate, free or evict); block 0 is the scratch block.  A
    generator of retention layers keeps nothing by position and a state a
    BLOCK (``init_block_pool``): its rows get one block each."""
    n = 1 if "r" in cfg.layer_kinds else -(-positions // BLOCK_SIZE)
    pool = init_block_pool(cfg, 1 + rows * n, BLOCK_SIZE, mesh)
    tables = 1 + jnp.arange(rows * n, dtype=jnp.int32).reshape(rows, n)
    return pool, tables


def _begin(params, prompt, cfg: LMConfig, max_new_tokens: int,
           temperature: float, rng, top_k: int, top_p: float,
           eos_token: int, mesh):
    """Prefill a request over its private pool and draw each row's first
    token.  Returns (first [B], carry, tables, knobs): ``carry`` is what
    paged_decode_round threads (pool, pending token, n_valid, seen_eos,
    per-row keys) and ``knobs`` its static keywords."""
    B, S = prompt.shape
    pool, tables = private_pool(cfg, B, S + max_new_tokens, mesh)
    knobs = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                 eos_token=eos_token,
                 inplace=decode_inplace(pool, mesh, heads=cfg.n_heads,
                                        rows=B, head_dim=cfg.hd),
                 ssm_inplace=ssm_fused(pool, mesh, rows=B),
                 experts_fused=experts_fused(cfg, mesh,
                                             params["embed"].dtype))
    # prefill sees the prompt's own blocks only: its attention would
    # otherwise span (masked) the blocks the decode round has yet to fill
    logits, pool = paged_forward_jit(
        params, prompt, pool, tables[:, :-(-S // BLOCK_SIZE)],
        jnp.zeros((B,), jnp.int32), jnp.full((B,), S, jnp.int32), cfg=cfg,
        last_only=True, experts_fused=knobs["experts_fused"])
    # per-ROW keys, as the round draws: a row's stream must not depend on
    # the rows it happens to be stacked with
    keys = jax.random.split(
        jax.random.key(0) if rng is None else rng, B)
    if temperature <= 0.0:
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        split = jax.vmap(jax.random.split)(keys)  # [B, 2] keys
        keys = split[:, 0]
        first = jax.vmap(
            lambda lg, kk: sample_token(
                lg[None, :], kk, temperature, top_k, top_p)[0]
        )(logits, split[:, 1])
    # the device-side after-eos latch the round carries (eos_token < 0
    # never matches a token id, so the latch stays open)
    seen_eos = first == eos_token
    n_valid = jnp.full((B,), S, jnp.int32)
    return first, (pool, first, n_valid, seen_eos, keys), tables, knobs


def _decode(params, carry, tables, cfg: LMConfig, n: int, knobs):
    """``n`` decode steps for every row as one paged_decode_round.
    Returns (tokens [B, n], carry')."""
    pool, token, n_valid, seen_eos, keys = carry
    toks, *carry = paged_decode_round_jit(
        params, pool, tables, token, n_valid,
        jnp.ones(token.shape, bool), seen_eos, keys, cfg, span=n, **knobs)
    # (a configuration with experts returns their count too: not carried)
    return toks, tuple(carry[:5])


def _denoising_lane(params, prompt, cfg: LMConfig, max_new_tokens: int,
                    chunk: Optional[int], temperature: float,
                    eos_token: int, mesh=None):
    """The static lane of a generator by diffusion over blocks
    (``cfg.block_length`` > 1): the whole prompt prefilled (it chooses no
    token), then rounds of whole blocks over the request's private pool —
    ONE round for everything (``chunk`` None: ``generate``) or one a client
    chunk, ``chunk`` rounded up to whole blocks (``stream_chunks``).  The
    first round takes the prompt's remainder into its first block and
    yields so many tokens fewer; every later one takes the block the round
    before it handed on (``_denoising_round``: the pool lags by it).
    Yields [B, n] token arrays whose
    concatenation is [B, max_new_tokens]; both drive the round the
    scheduler drives, so their answers are its answers."""
    B, S = prompt.shape
    L = cfg.block_length
    rem = S % L
    whole = -(-(rem + max_new_tokens) // L) * L     # positions to generate
    pool, tables = private_pool(cfg, B, S - rem + whole, mesh)
    inplace = decode_inplace(pool, mesh, width=L, heads=cfg.n_heads, rows=B,
                             head_dim=cfg.hd)
    fused = experts_fused(cfg, mesh, params["embed"].dtype)
    _, pool = paged_forward_jit(
        params, prompt, pool, tables[:, :-(-S // BLOCK_SIZE)],
        jnp.zeros((B,), jnp.int32), jnp.full((B,), S, jnp.int32), cfg=cfg,
        last_only=True, experts_fused=fused)
    token = jnp.zeros((B, L), jnp.int32).at[:, :rem].set(prompt[:, S - rem:])
    n_valid = jnp.full((B,), S, jnp.int32)
    seen = jnp.zeros((B,), bool)
    keys = jnp.zeros((B,), jnp.uint32)      # greedy: never read
    per = whole if chunk is None else -(-int(chunk) // L) * L
    done, skip = 0, rem
    while done < max_new_tokens:
        span = min(per, -(-(skip + max_new_tokens - done) // L) * L)
        # (``token'``: the round's last block, which the next one writes)
        toks, pool, token, n_valid, seen, keys, *_ = paged_decode_round_jit(
            params, pool, tables, token, n_valid, jnp.ones((B,), bool),
            seen, keys, cfg, span=span, temperature=temperature, top_k=0,
            top_p=0.0, eos_token=eos_token, inplace=inplace,
            experts_fused=fused)
        new = toks[:, skip:skip + max_new_tokens - done]
        yield new
        done, skip = done + new.shape[1], 0


def generate(
    params,
    prompt,
    cfg: LMConfig,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_token: int = -1,
    mesh=None,
) -> jax.Array:
    """prompt [B, S] int32 -> generated [B, max_new_tokens] int32.

    Greedy when temperature == 0 (a static python branch), else sampled
    (optionally top-k / nucleus truncated — sample_token) from per-row
    keys split off ``rng``; rows that emit ``eos_token`` are eos-padded
    afterwards (the round's latch — the mask_after_eos contract).

    One ``paged_forward`` over the whole prompt, then ONE
    ``paged_decode_round`` of ``max_new_tokens - 1`` steps (the first token
    came from prefill; no final forward whose logits nobody reads), over a
    private pool sized for exactly this request.  ``mesh`` is the mesh the
    caller sharded ``params`` over, if any: a traced program cannot see
    shardings, and decode_inplace needs it to pick the attention
    formulation — the same answer GenServer gives for its own pool.

    Telemetry (eager calls only — traced calls skip; see _eager):
    time-to-first-token and whole-call tokens/sec land in the flight
    recorder (``seldon_tpu_ttft_seconds`` /
    ``seldon_tpu_decode_tokens_per_second``).  TTFT costs ONE host sync
    at the prefill boundary — the decode round depends on the first token
    anyway, so no device idle is added, only the host-side enqueue
    overlap of one dispatch."""
    B, S = prompt.shape
    if cfg.block_length > 1:
        # blocks of denoising passes: no first token from the prefill, one
        # round for the whole answer (_denoising_lane)
        return jnp.concatenate(list(_denoising_lane(
            params, prompt, cfg, max_new_tokens, None, temperature,
            eos_token, mesh)), axis=1)
    eager = _eager(prompt)
    t0 = time.perf_counter() if eager else 0.0
    first, carry, tables, knobs = _begin(
        params, prompt, cfg, max_new_tokens, temperature, rng, top_k,
        top_p, eos_token, mesh)
    if eager:
        # the decode round depends on `first` anyway — blocking here adds
        # no device idle, just surfaces the true prefill latency
        jax.block_until_ready(first)
        RECORDER.observe_ttft(time.perf_counter() - t0)
        RECORDER.set_kv_slots(active=B * S, reserved=B * max_new_tokens)
    result = first[:, None]
    if max_new_tokens > 1:
        toks, _ = _decode(params, carry, tables, cfg, max_new_tokens - 1,
                          knobs)
        result = jnp.concatenate([result, toks], axis=1)  # [B, max_new]
    if eager:
        # block before timing: serving callers materialize next anyway
        jax.block_until_ready(result)
        elapsed = time.perf_counter() - t0
        if elapsed > 0:
            RECORDER.observe_decode_rate(B * max_new_tokens / elapsed)
    return result


def stream_chunks(params, prompt, cfg: LMConfig, max_new_tokens: int,
                  chunk: int = 8, temperature: float = 0.0,
                  rng: Optional[jax.Array] = None, top_k: int = 0,
                  top_p: float = 0.0, eos_token: int = -1, mesh=None):
    """Incremental decoding: yields token arrays [B, <=chunk] whose
    concatenation equals ``generate(...)`` token-for-token (same
    sampling semantics, same per-row PRNG streams, same eos padding).

    The host loop exists ONLY to surface tokens early — each iteration is
    one ``paged_decode_round`` of ``chunk`` steps over the request's
    private pool (one executable per distinct span: the first chunk's
    ``chunk - 1``, ``chunk``, and a shorter tail), so the first token
    arrives after prefill + (chunk-1) steps instead of after
    max_new_tokens steps.

    With ``eos_token`` set the after-eos latch is the round's own, on the
    device; the host reads back one scalar per chunk — have all rows
    stopped — and once they have, the remaining chunks are host-made eos
    padding with no further device work.  Yielded chunks stay device
    arrays, so the consumer decides when to pay the readback.

    Telemetry (flight recorder): TTFT recorded at the first sampled
    token (one host sync at the prefill boundary — the first round
    depends on that token anyway), tokens/sec over the whole stream at
    exhaustion."""
    B = prompt.shape[0]
    t0 = time.perf_counter()
    chunk = int(chunk)
    if cfg.block_length > 1:
        # one round of whole blocks a chunk (``chunk`` rounded up to them)
        yield from _denoising_lane(params, prompt, cfg, max_new_tokens, chunk,
                                   temperature, eos_token, mesh)
        return
    first, carry, tables, knobs = _begin(
        params, prompt, cfg, max_new_tokens, temperature, rng, top_k,
        top_p, eos_token, mesh)
    jax.block_until_ready(first)  # the first round depends on it anyway
    RECORDER.observe_ttft(time.perf_counter() - t0)

    # first chunk: the prefill token + (chunk-1) decoded steps
    head = first[:, None]
    n_first = min(chunk - 1, max_new_tokens - 1)
    if n_first > 0:
        toks, carry = _decode(params, carry, tables, cfg, n_first, knobs)
        head = jnp.concatenate([head, toks], axis=1)
    yield head
    done = 1 + n_first
    decoded = done  # device-decoded tokens only (host eos pads excluded)
    while done < max_new_tokens:
        n = min(chunk, max_new_tokens - done)
        if eos_token >= 0 and bool(jnp.all(carry[3])):
            # every row is finished: pad from the host, skip the device
            yield jnp.full((B, n), jnp.int32(eos_token))
        else:
            toks, carry = _decode(params, carry, tables, cfg, n, knobs)
            yield toks
            decoded += n
        done += n
    elapsed = time.perf_counter() - t0
    if elapsed > 0:
        # rate counts only device-decoded tokens — an early-stopped
        # stream's host-padded filler must not inflate the SLO histogram
        RECORDER.observe_decode_rate(B * decoded / elapsed)


@register_unit("TransformerGenerator")
class TransformerGenerator(Unit):
    """Serving unit: prompt token rows in, generated token rows out, over
    the standard data plane.  Generation length and temperature are graph
    parameters, so a deployment JSON fully describes the decode behavior.

    Input contract: prompt values are truncated to int32 and CLAMPED to
    [0, vocab) — jit-compiled programs cannot reject data-dependent values
    per-request, so out-of-range ids degrade deterministically instead of
    hitting XLA's unspecified out-of-bounds gather.

    Sampling: temperature>0 threads a request counter through unit state,
    so repeated identical prompts draw fresh continuations (a fixed key
    would make sampling a worse greedy); the counter update rides the
    normal state write-back."""

    pure = True
    class_names = None

    def __init__(self, vocab: int = 256, d_model: int = 128, n_heads: int = 4,
                 n_layers: int = 2, d_ff: int = 512, seed: int = 0,
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0, eos_token: int = -1,
                 prefix_tokens: str = "",
                 dtype: str = "bfloat16", moe_every: int = 0,
                 n_experts: int = 8, moe_k: int = 2, mesh=None,
                 quant: str = "none", kv_quant: str = "none",
                 n_kv_heads: int = 0, weights_path: str = "",
                 rope: bool = True, rope_base: float = 10000.0,
                 head_dim: int = 0, qk_norm: bool = False,
                 norm_eps: float = 1e-6, tie_embeddings: bool = True,
                 d_expert: int = 0, moe_norm_topk: bool = True,
                 block_length: int = 1, denoising_steps: int = 1,
                 mask_id: int = -1, layer_kinds: str = "",
                 conv_kernel: int = 3, dense_layers: int = 0,
                 router: str = "softmax", ssm_heads: int = 0,
                 ssm_head_dim: int = 0, ssm_groups: int = 1,
                 ssm_state: int = 0, expert_act: str = "silu",
                 d_shared: int = 0, router_scale: float = 1.0,
                 router_eps: float = 1e-6, experts_held: int = 0,
                 experts_first: int = 0):
        # mesh (from the binding's mesh_axes, e.g. {"tp": 4}): params are
        # laid out with the LM's tp shardings and GSPMD partitions the
        # whole prefill+decode program across the mesh — one generator
        # graph node spans multiple chips through the deployment JSON
        self.mesh = mesh
        self.cfg = LMConfig(
            vocab=int(vocab), d_model=int(d_model), n_heads=int(n_heads),
            n_layers=int(n_layers), d_ff=int(d_ff),
            dtype=jnp.dtype(dtype).type,
            moe_every=int(moe_every), n_experts=int(n_experts),
            moe_k=int(moe_k), quant=str(quant),
            kv_quant=str(kv_quant),
            n_kv_heads=int(n_kv_heads),
            rope=bool(rope), rope_base=float(rope_base),
            head_dim=int(head_dim), qk_norm=bool(qk_norm),
            norm_eps=float(norm_eps), tie_embeddings=bool(tie_embeddings),
            d_expert=int(d_expert), moe_norm_topk=bool(moe_norm_topk),
            block_length=int(block_length),
            denoising_steps=int(denoising_steps), mask_id=int(mask_id),
            # one letter a layer ("ccacccac..": a deployment document
            # carries scalars, so the pattern comes as ONE string)
            layer_kinds=str(layer_kinds), conv_kernel=int(conv_kernel),
            dense_layers=int(dense_layers), router=str(router),
            ssm_heads=int(ssm_heads), ssm_head_dim=int(ssm_head_dim),
            ssm_groups=int(ssm_groups), ssm_state=int(ssm_state),
            expert_act=str(expert_act), d_shared=int(d_shared),
            router_scale=float(router_scale), router_eps=float(router_eps),
            experts_held=int(experts_held), experts_first=int(experts_first),
        )
        # the lanes it cannot take, in the scheduler's words (served.py)
        served(self.cfg).refuse(
            prefix=bool(str(prefix_tokens).strip()),
            sampled=float(temperature) > 0.0, mesh=mesh is not None)
        self.weights_path = str(weights_path)
        self.seed = int(seed)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token = int(eos_token)
        # shared system-prompt prefix ("1,2,3" token ids).  The scheduler
        # computes its K/V ONCE, into pinned pool blocks every sequence's
        # table references; the static lane prepends the ids to each row
        self.prefix_ids = [
            int(t) for t in str(prefix_tokens).replace(" ", "").split(",")
            if t != ""
        ]
        for t in self.prefix_ids:
            if not 0 <= t < self.cfg.vocab:
                raise ValueError(
                    f"prefix token {t} outside vocab [0, {self.cfg.vocab})")
        # sampled decoding keys each row by its index in the stacked batch,
        # and capacity-routed experts (moe_every) couple rows (shared
        # capacity over the flattened token stream) — either way, coalescing
        # other callers' rows would change this caller's answer; dropless
        # experts (d_expert) route a token by that token alone and couple
        # nothing.  The request counter in state additionally varies the
        # sampling key per request.
        self.batch_coupled = (
            self.temperature > 0.0 or self.cfg.moe_every > 0
        )
        self.updates_state_on_predict = self.temperature > 0.0

    def init_state(self, rng):
        from seldon_core_tpu.models.transformer import load_lm_weights

        if rng is None:
            rng = jax.random.key(self.seed)
        params = lm_init(jax.random.fold_in(rng, self.seed), self.cfg)
        params = load_lm_weights(params, self.weights_path)
        if self.cfg.quant == "int8":
            from seldon_core_tpu.ops.quant import quantize_lm_params

            params = quantize_lm_params(params)
        if self.mesh is not None:
            from seldon_core_tpu.models.transformer import param_shardings

            params = jax.device_put(
                params, param_shardings(self.mesh, params)
            )
        state = {"params": params, "requests": jnp.zeros((), jnp.int32)}
        if self.prefix_ids:
            state["prefix_ids"] = jnp.asarray(self.prefix_ids, jnp.int32)
        return state

    def _prompt(self, state, X):
        """Sanitized prompt rows, the shared prefix's ids in front."""
        prompt = sanitize_prompt(X, self.cfg.vocab)
        ids = state.get("prefix_ids")
        if ids is None:
            return prompt
        return jnp.concatenate(
            [jnp.broadcast_to(ids, (prompt.shape[0],) + ids.shape), prompt],
            axis=1)

    def predict(self, state, X):
        key = jax.random.fold_in(jax.random.key(self.seed),
                                 state["requests"])
        y = generate(
            state["params"], self._prompt(state, X), self.cfg,
            max_new_tokens=self.max_new_tokens,
            temperature=self.temperature,
            rng=key,
            top_k=self.top_k, top_p=self.top_p,
            eos_token=self.eos_token,
            mesh=self.mesh,
        ).astype(jnp.float32)
        if self.temperature > 0.0:
            # preserve EVERY state key (prefix_ids!) — only the
            # request counter advances
            new_state = {**state, "requests": state["requests"] + 1}
            return y, UnitAux(state=new_state)
        return y

    def continuous_spec(self, state):
        """Scheduler contract for the continuous-batching generation lane
        (runtime/genserver.py): everything the per-step scheduler needs to
        run this unit's decoding — params, config, sampling knobs, the
        shared prefix's ids.  Returns None when the unit cannot be
        continuously scheduled: capacity-routed experts (``moe_every``)
        couple co-batched rows through the shared expert-capacity reduction,
        so co-scheduling other requests' rows would change this request's
        answer.  Dropless experts (``d_expert``) drop nothing and route a
        token by that token alone: such a unit is scheduled like a dense
        one, as is a generator by diffusion over blocks."""
        if self.cfg.moe_every > 0:
            return None
        return {
            "params": state["params"],
            "cfg": self.cfg,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "top_p": self.top_p,
            "eos_token": self.eos_token,
            "max_new_tokens": self.max_new_tokens,
            "prefix_ids": state.get("prefix_ids"),
            "seed": self.seed,
            # tensor-parallel dispatch (runtime/servingmesh.py): the
            # scheduler lays its paged KV pool out over the same mesh
            # the params are sharded on, so prefill/decode programs
            # compile SPMD across the chips
            "mesh": self.mesh,
        }

    def stream_tokens(self, state, X, chunk: int = 8):
        """Incremental serving: yields [B, <=chunk] int32 arrays; the
        concatenation equals ``predict``'s output for greedy decoding
        (streaming bypasses the batcher and state write-back, so sampled
        streams draw a fresh key per call instead of threading the request
        counter — same quality, different stream)."""
        if self.temperature > 0.0:
            key = jax.random.fold_in(
                jax.random.key(self.seed), next(_stream_counter)
            )
        else:
            key = jax.random.fold_in(jax.random.key(self.seed), 0)
        yield from stream_chunks(
            state["params"], self._prompt(state, jnp.asarray(X)), self.cfg,
            max_new_tokens=self.max_new_tokens, chunk=int(chunk),
            temperature=self.temperature, rng=key,
            top_k=self.top_k, top_p=self.top_p,
            eos_token=self.eos_token,
            mesh=self.mesh,
        )
