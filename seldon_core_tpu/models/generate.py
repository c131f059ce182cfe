"""Autoregressive decoding with a KV cache — LLM-style serving through the
same graph engine.

The reference predates sequence models entirely (SURVEY.md §5); this module
makes generation a first-class graph workload: ``TransformerGenerator`` is
a MODEL unit whose ``predict`` maps prompt token rows to generated token
rows, so a deployment JSON serves text continuation over the identical
REST/gRPC data plane as every other model.

TPU-shaped decoding:
  * the whole decode loop is ONE ``lax.scan`` inside jit — no Python
    per-token dispatch, no host round-trips between steps;
  * TWO-TIER KV cache: the prompt's K/V live in a read-only MAIN cache
    (``[B, KV, S, hd]``, grouped heads), new tokens write a chunk-sized
    buffer, and attention softmaxes over the concatenated scores.
    Measured motivation (v5e, B=256): mutating a large cache inside the
    scan cost ~200 us per ``dynamic_update_slice`` plus ~2 ms/step of
    layout copies — XLA cannot keep a big while-loop carry in place —
    while the two-tier step runs the same attention at ~1/3 the time;
  * chunks fold into main at most once per ``GEN/STREAM_CHUNK_CAP``
    tokens via a donated (in-place) bulk merge; generations that fit one
    chunk keep main PROMPT-SIZED and never mask or merge at all;
  * optional int8 cache (``LMConfig.kv_quant``): per-token-per-head
    scales, convert fused into the score/PV dot reads;
  * greedy (temperature=0) or sampled decoding via ``jax.random`` keys
    threaded through the scan carry.
"""

from __future__ import annotations

import itertools
import logging
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seldon_core_tpu.graph.units import Unit, UnitAux, register_unit
from seldon_core_tpu.utils.telemetry import RECORDER

logger = logging.getLogger(__name__)

_stream_counter = itertools.count()  # per-process sampled-stream key source
from seldon_core_tpu.models.transformer import (
    LMConfig,
    _attention,
    _ffn,
    _rmsnorm,
    apply_rope,
    lm_init,
)

_warned_prefix_flash = False  # one-time flash-vs-prefix warning latch


def _resolve_prefix_flash(prefix, use_flash: bool) -> bool:
    """The shared-prefix path has no flash kernel: the suffix prefill is a
    causal SEGMENT (mid-sequence offsets + cache-wide attention) the fused
    kernel cannot mask.  Rather than warning and letting the caller think
    flash applied, resolve the EFFECTIVE flash setting here: with a prefix
    active, warn once and return False — the safe unfused segment path —
    so every downstream site (plain prefill included) branches on one
    answer instead of re-deriving the hazard.  Decode is unaffected either
    way (the two-tier/paged paths never use flash)."""
    if prefix is None or not use_flash:
        return use_flash
    global _warned_prefix_flash
    if not _warned_prefix_flash:
        _warned_prefix_flash = True
        logger.warning(
            "prefix cache active with use_flash=True: falling back to the "
            "unfused causal-segment suffix prefill (no flash kernel for "
            "causal segments); long suffixes pay O((P+S)*S) unfused "
            "attention"
        )
    return False


def _eager(x) -> bool:
    """True when ``x`` is a concrete array — i.e. we are executing, not
    being traced into someone's jit.  Telemetry must only record on
    execution: a traced ``time.perf_counter()`` would bake trace-time
    constants into the program."""
    return not isinstance(x, jax.core.Tracer)

__all__ = ["init_cache", "init_chunk", "prefill", "decode_step",
           "generate", "stream_chunks", "sample_token", "mask_after_eos",
           "build_prefix_main",
           "init_block_pool", "decode_inplace", "paged_forward",
           "paged_decode_round",
           "paged_spec_round", "paged_write_prefix_blocks",
           "paged_write_prefix_tail",
           "TransformerGenerator"]


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    # K/V stored at the GROUPED head count (cfg.kv_heads): with GQA the
    # cache — the HBM stream every decode step pays for — shrinks by
    # n_heads/n_kv_heads.  Allocated at EXACTLY max_len: padding would
    # bill every decode step for masked slots.
    # kv_quant="int8" stores int8 values + per-token-per-head f32 scales
    # ([B, KV, L] — ~6% size overhead at hd=64), halving the stream.
    hd = cfg.d_model // cfg.n_heads
    kv = cfg.kv_heads

    def layer():
        if cfg.kv_quant == "int8":
            return {
                "k": jnp.zeros((batch, kv, max_len, hd), jnp.int8),
                "v": jnp.zeros((batch, kv, max_len, hd), jnp.int8),
                "k_s": jnp.zeros((batch, kv, max_len), jnp.float32),
                "v_s": jnp.zeros((batch, kv, max_len), jnp.float32),
            }
        return {
            "k": jnp.zeros((batch, kv, max_len, hd), cfg.dtype),
            "v": jnp.zeros((batch, kv, max_len, hd), cfg.dtype),
        }

    return {f"l{i}": layer() for i in range(cfg.n_layers)}


def init_chunk(cfg: LMConfig, batch: int, cap: int) -> Dict[str, Any]:
    """Decode chunk buffer — same layout as init_cache, named for the
    role.  Round-5 restructures (stacked all-layer buffers, position-
    major scales, unrolled sub-scans with straight-line merges, a Pallas
    aliased writer) all measured SLOWER than this layout; see
    scripts/probe_step_profile.py and docs/benchmarking.md for the
    numbers and the while-carry dus serialization analysis."""
    return init_cache(cfg, batch, cap)


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


def _pv_f32(p, cache_v, v_s=None):
    """p [B,KV,g,S,L] x cache_v [B,KV,L,hd] -> f32 [B,KV,g*S,hd] partial
    attention output (un-cast so two-tier partials add exactly).

    The dot's input dtype follows the CACHE dtype: bf16 only for bf16 or
    int8 caches — an f32-dtype model keeps f32 weights so its greedy
    ties break identically to prefill/naive decode."""
    B, KV, g, S, L = p.shape
    if v_s is not None:
        p = p * v_s[:, :, None, None, :]
    ct = (jnp.bfloat16 if cache_v.dtype in (jnp.int8, jnp.bfloat16)
          else cache_v.dtype)
    v = cache_v.astype(ct) if cache_v.dtype == jnp.int8 else cache_v
    return jax.lax.dot_general(
        p.astype(ct).reshape(B, KV, g * S, L), v,
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )


def _attend_two_tier(q, main_layer, chunk_layer, n_main, n_chunk,
                     main_full: bool = False):
    """q [B,H,1,hd] over (frozen main cache)[:n_main] + (chunk
    buffer)[:n_chunk]: one softmax over the concatenated scores, partial
    PV dots summed in f32.

    THE decode-hot-loop formulation: profiling the single-tier scan on
    v5e showed ~half of every step going to dynamic_update_slice on the
    big cache plus ~2 ms/step of layout copies — XLA cannot keep a
    mutated while-loop carry in place at this size.  Keeping the big
    cache READ-ONLY inside the scan and writing only a chunk-sized
    buffer measured 144 us/layer-step vs ~960 us (B=256, L=640; see
    scripts/probe_dus.py and docs/benchmarking.md).

    ``main_full`` (static): caller guarantees every main slot is valid
    (n_main == main length) — skips the validity select, which profiling
    showed streaming the whole f32 score tensor twice per layer
    (bitcast_select_fusion, ~1.2 ms/step at B=256).  The single-chunk
    serving path (prompt-sized main) always qualifies.

    Two score-stream economies (profiled round 5, B=256 — together
    bf16 4.18 -> 3.98 ms/step, int8kv 3.29 -> 3.10):
      * validity masks are ADDED (0 / -1e30) instead of selected —
        jnp.where materialised as its own fusion re-streaming the f32
        chunk scores (~22 us/layer), an add joins the exp chain;
      * the softmax normalisation happens AFTER the PV dots: partial PV
        runs on unnormalised exp weights (globally max-shifted, so in
        [0, 1] like p) and the division by the sum touches only the
        [B, H, 1, hd] output — dividing p re-streamed the full score
        tensor per layer (divide_convert fusions, ~8 us/layer)."""
    sm = _grouped_qk(q, main_layer["k"], main_layer.get("k_s"))
    sc = _grouped_qk(q, chunk_layer["k"], chunk_layer.get("k_s"))
    C = chunk_layer["k"].shape[2]
    if not main_full:
        Lm = main_layer["k"].shape[2]
        sm = sm + jnp.where(jnp.arange(Lm) < n_main, 0.0, -1e30
                            ).astype(jnp.float32)[None, None, None, None, :]
    sc = sc + jnp.where(jnp.arange(C) < n_chunk, 0.0, -1e30
                        ).astype(jnp.float32)[None, None, None, None, :]
    m = jnp.maximum(jnp.max(sm, axis=-1), jnp.max(sc, axis=-1))
    em = jnp.exp(sm - m[..., None])
    ec = jnp.exp(sc - m[..., None])
    l = jnp.sum(em, axis=-1) + jnp.sum(ec, axis=-1)  # [B,KV,g,S]
    om = _pv_f32(em, main_layer["v"], main_layer.get("v_s"))
    oc = _pv_f32(ec, chunk_layer["v"], chunk_layer.get("v_s"))
    B, KV, g, S = m.shape
    out = (om + oc) / l.reshape(B, KV, g * S)[..., None]
    return out.astype(q.dtype).reshape(q.shape)


def _block_two_tier(lp, x, main_layer, chunk_layer, n_main, n_chunk,
                    cfg: LMConfig, main_full: bool = False):
    """One decoder block for a single cached step: K/V written into the
    CHUNK buffer at slot ``n_chunk`` (the big cache is never touched),
    attention over main[:n_main] + chunk[:n_chunk+1].  Global position of
    this token is n_main + n_chunk."""
    from seldon_core_tpu.ops.quant import lm_matmul

    B, S, D = x.shape  # S == 1
    hd = cfg.d_model // cfg.n_heads
    kv_h = cfg.kv_heads
    h = _rmsnorm(x, lp["ln1"])
    qkv = lm_matmul(lp, "wqkv", h, out_dtype=x.dtype)
    q, k, v = jnp.split(qkv, [D, D + kv_h * hd], axis=-1)
    q = _heads(q, B, S, cfg.n_heads, hd)
    k = _heads(k, B, S, kv_h, hd)
    v = _heads(v, B, S, kv_h, hd)
    if cfg.rope:
        positions = n_main + n_chunk + jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
    if chunk_layer["k"].dtype == jnp.int8:
        k_w, k_sw = _quantize_kv(k)
        v_w, v_sw = _quantize_kv(v)
        new_chunk = {
            "k": jax.lax.dynamic_update_slice(
                chunk_layer["k"], k_w, (0, 0, n_chunk, 0)),
            "v": jax.lax.dynamic_update_slice(
                chunk_layer["v"], v_w, (0, 0, n_chunk, 0)),
            "k_s": jax.lax.dynamic_update_slice(
                chunk_layer["k_s"], k_sw, (0, 0, n_chunk)),
            "v_s": jax.lax.dynamic_update_slice(
                chunk_layer["v_s"], v_sw, (0, 0, n_chunk)),
        }
    else:
        new_chunk = {
            "k": jax.lax.dynamic_update_slice(
                chunk_layer["k"], k.astype(chunk_layer["k"].dtype),
                (0, 0, n_chunk, 0)),
            "v": jax.lax.dynamic_update_slice(
                chunk_layer["v"], v.astype(chunk_layer["v"].dtype),
                (0, 0, n_chunk, 0)),
        }
    a = _attend_two_tier(q, main_layer, new_chunk, n_main, n_chunk + 1,
                         main_full)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, D)
    x = x + lm_matmul(lp, "wo", a, out_dtype=x.dtype)
    h = _rmsnorm(x, lp["ln2"])
    y, _lb = _ffn(lp, h, cfg, mesh=None)
    return x + y, new_chunk


def decode_step_two_tier(params, token, main, chunk, n_main, n_chunk,
                         cfg: LMConfig, main_full: bool = False):
    """One cached step against (frozen main, growing chunk).  token [B]
    -> (logits [B, V], chunk')."""
    x = params["embed"][token][:, None, :]
    for i in range(cfg.n_layers):
        x, chunk[f"l{i}"] = _block_two_tier(
            params[f"l{i}"], x, main[f"l{i}"], chunk[f"l{i}"],
            n_main, n_chunk, cfg, main_full,
        )
    x = _rmsnorm(x, params["ln_f"])
    return (x[:, 0, :] @ params["embed"].T).astype(jnp.float32), chunk


def merge_chunk(main, chunk, n_main, cfg: LMConfig):
    """Fold a (full or partial) chunk buffer into the main cache at
    position ``n_main``.  Callers jit this with the main (and chunk)
    buffers DONATED — measured in-place on v5e, i.e. dispatch-cost only;
    run OUTSIDE the decode scan, once per chunk."""
    out = {}
    for i in range(cfg.n_layers):
        ml, cl = main[f"l{i}"], chunk[f"l{i}"]
        layer = {
            "k": jax.lax.dynamic_update_slice(
                ml["k"], cl["k"].astype(ml["k"].dtype), (0, 0, n_main, 0)),
            "v": jax.lax.dynamic_update_slice(
                ml["v"], cl["v"].astype(ml["v"].dtype), (0, 0, n_main, 0)),
        }
        if "k_s" in ml:
            layer["k_s"] = jax.lax.dynamic_update_slice(
                ml["k_s"], cl["k_s"], (0, 0, n_main))
            layer["v_s"] = jax.lax.dynamic_update_slice(
                ml["v_s"], cl["v_s"], (0, 0, n_main))
        out[f"l{i}"] = layer
    return out


def _attend_cached(q, cache_layer, n_valid):
    """q [B,H,1,hd] against the (possibly grouped, possibly int8) cache
    layer {k, v, k_s?, v_s?}; positions >= n_valid (scalar) masked.

    Deliberately the grouped-XLA formulation: over a DENSE cache XLA runs
    the whole batch as a few large batched dots, and a (B*KV, L/128)
    kernel grid that serialized tiny per-step dots measured 1.6-2.3x
    slower.  (The PAGED pool is another matter: see _paged_block.)"""
    s = _grouped_qk(q, cache_layer["k"], cache_layer.get("k_s"))
    valid = jnp.arange(cache_layer["k"].shape[2]) < n_valid  # [L]
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_pv(p, cache_layer["v"], q.shape, q.dtype,
                       cache_layer.get("v_s"))


def _attend_cached_causal(q, cache_layer, start):
    """q [B,H,S,hd] for global positions start..start+S-1 over the cache:
    query i may see cache positions <= start + i (speculative segments)."""
    S = q.shape[2]
    s = _grouped_qk(q, cache_layer["k"], cache_layer.get("k_s"))
    qpos = start + jnp.arange(S)[:, None]
    kpos = jnp.arange(cache_layer["k"].shape[2])[None, :]
    mask = kpos <= qpos  # [S, L]
    s = jnp.where(mask[None, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_pv(p, cache_layer["v"], q.shape, q.dtype,
                       cache_layer.get("v_s"))


def _block_cached(lp, x, cache_layer, start, n_valid, cfg: LMConfig,
                  use_flash: bool = False, segment: bool = False):
    """One decoder block writing K/V into the cache at ``start`` and
    attending over cache[:n_valid].  x [B,S,D]; returns (x', cache_layer').
    S > 1 with ``segment=False`` means prefill from position 0; with
    ``segment=True`` a mid-sequence continuation at traced offset ``start``
    attending causally over the cache; S == 1 is a cached decode step."""
    from seldon_core_tpu.ops.quant import lm_matmul

    B, S, D = x.shape
    hd = cfg.d_model // cfg.n_heads
    kv_h = cfg.kv_heads
    h = _rmsnorm(x, lp["ln1"])
    qkv = lm_matmul(lp, "wqkv", h, out_dtype=x.dtype)
    q, k, v = jnp.split(qkv, [D, D + kv_h * hd], axis=-1)
    q = _heads(q, B, S, cfg.n_heads, hd)
    k = _heads(k, B, S, kv_h, hd)
    v = _heads(v, B, S, kv_h, hd)
    if cfg.rope:
        # rotate with GLOBAL positions before the cache write, so stored
        # keys are final and cached attention needs no re-rotation
        positions = start + jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
    whole = (not segment and S == cache_layer["k"].shape[2])
    if cache_layer["k"].dtype == jnp.int8:
        k_w, k_sw = _quantize_kv(k)
        v_w, v_sw = _quantize_kv(v)
        if whole:
            # prompt-sized cache (single-chunk serving): the fresh K/V ARE
            # the cache — a dus into same-sized zeros is a pure copy, and
            # dus on large buffers measured ~200 us each on v5e
            new_cache = {"k": k_w, "v": v_w, "k_s": k_sw, "v_s": v_sw}
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache_layer["k"], k_w, (0, 0, start, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache_layer["v"], v_w, (0, 0, start, 0)),
                "k_s": jax.lax.dynamic_update_slice(
                    cache_layer["k_s"], k_sw, (0, 0, start)),
                "v_s": jax.lax.dynamic_update_slice(
                    cache_layer["v_s"], v_sw, (0, 0, start)),
            }
    elif whole:
        new_cache = {"k": k.astype(cache_layer["k"].dtype),
                     "v": v.astype(cache_layer["v"].dtype)}
    else:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(
                cache_layer["k"], k.astype(cache_layer["k"].dtype),
                (0, 0, start, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache_layer["v"], v.astype(cache_layer["v"].dtype),
                (0, 0, start, 0)),
        }
    if segment:
        # mid-sequence continuation (speculative draft/verify): causal over
        # the whole cache with global position offsets (any S, traced start)
        a = _attend_cached_causal(q, new_cache, start)
    elif S > 1:
        # prefill: causal attention over the fresh k/v only — the cache
        # tail past S is all-masked zeros, no need to attend over it.
        # Reuses the LM's _attention (flash kernel when available, same
        # fallback numerics as lm_apply) so the two paths cannot drift;
        # int8 caches still prefill from the EXACT pre-quantization k/v.
        a = _attention(q, k, v, None, causal=True, use_flash=use_flash)
    else:
        a = _attend_cached(q, new_cache, n_valid)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, D)
    x = x + lm_matmul(lp, "wo", a, out_dtype=x.dtype)
    h = _rmsnorm(x, lp["ln2"])
    y, _lb = _ffn(lp, h, cfg, mesh=None)  # dense or MoE FFN
    x = x + y
    return x, new_cache


def segment_forward(params, tokens, cache, start, cfg: LMConfig,
                    use_flash: bool = False, segment: bool = True,
                    last_only: bool = False):
    """Forward S tokens at global positions start.. over the cache
    (filling it); returns (logits [B, S, V] for EVERY position, cache').
    ``segment=False`` is the prefill special case (start must be 0).
    ``last_only`` unembeds ONLY the final position (returns [B, 1, V]):
    the unembed is ~20% of prefill FLOPs at real vocab sizes and a
    [B, S, V] f32 write besides — generation never reads the rest."""
    x = params["embed"][tokens]
    for i in range(cfg.n_layers):
        x, cache[f"l{i}"] = _block_cached(
            params[f"l{i}"], x, cache[f"l{i}"], start, tokens.shape[1], cfg,
            use_flash, segment,
        )
    if last_only:
        x = x[:, -1:, :]  # before the (positionwise) norm: same numerics
    x = _rmsnorm(x, params["ln_f"])
    return (x @ params["embed"].T).astype(jnp.float32), cache


def prefill(params, tokens, cache, cfg: LMConfig, use_flash: bool = False):
    """Consume the prompt in one pass, filling the cache.

    tokens [B, S_prompt] -> (last-position logits [B, V], cache')."""
    logits, cache = segment_forward(
        params, tokens, cache, 0, cfg, use_flash, segment=False,
        last_only=True,
    )
    return logits[:, -1, :], cache


def decode_step(params, token, cache, pos, cfg: LMConfig):
    """One cached step.  token [B] int32, pos scalar -> (logits [B,V],
    cache')."""
    x = params["embed"][token][:, None, :]  # [B,1,D]
    for i in range(cfg.n_layers):
        x, cache[f"l{i}"] = _block_cached(
            params[f"l{i}"], x, cache[f"l{i}"], pos, pos + 1, cfg
        )
    x = _rmsnorm(x, params["ln_f"])
    return (x[:, 0, :] @ params["embed"].T).astype(jnp.float32), cache


def build_prefix_main(prefix_cache, batch: int, total_len: int,
                      cfg: LMConfig):
    """Batched main cache [B, KV, total_len, hd] whose first P slots are
    a shared B=1 PREFIX cache broadcast across the batch — the serving
    trick for common system prompts: the prefix's K/V are computed once
    per deployment (init_state), so each request prefills only its
    suffix (prefill FLOPs drop by the prefix's share of S², which at
    long prefixes is most of them)."""
    out = {}
    for li, layer in prefix_cache.items():
        new_layer = {}
        for kk, vv in layer.items():
            P = vv.shape[2]
            pad_shape = list(vv.shape)
            pad_shape[0] = batch
            pad_shape[2] = total_len - P
            pref = jnp.broadcast_to(vv, (batch,) + vv.shape[1:])
            new_layer[kk] = jnp.concatenate(
                [pref, jnp.zeros(pad_shape, vv.dtype)], axis=2)
        out[li] = new_layer
    return out


#: generation chunk-buffer capacity: generations up to this length run
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


def _chunk_eos_mask(toks, seen_eos, eos_token: int):
    """Per-chunk after-eos masking with a carried latch — the DEVICE-side
    form of mask_after_eos for streaming: rows already stopped
    (``seen_eos`` [B] bool) are forced to eos wholesale, within-chunk
    positions after a fresh eos are forced to eos, and the latch is
    updated.  Returns (masked [B, n], seen_eos', all_done scalar).  The
    caller reads back ONLY the scalar ``all_done`` flag to drive the
    early-stop branch — the token chunk itself stays on device (the old
    host-side masking forced a full [B, n] readback per chunk, serializing
    the stream's device/host overlap)."""
    eos = jnp.int32(eos_token)
    t = jnp.where(seen_eos[:, None], eos, toks)
    is_eos = t == eos
    after = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
             - is_eos.astype(jnp.int32)) > 0
    t = jnp.where(after, eos, t)
    seen2 = seen_eos | is_eos.any(axis=1)
    return t, seen2, jnp.all(seen2)


_chunk_eos_mask_jit = jax.jit(_chunk_eos_mask, static_argnames=("eos_token",))


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


#: with a prompt-sized main cache and ZERO merges; longer ones merge the
#: chunk into main once per CAP tokens (a donated-in-place bulk write)
GEN_CHUNK_CAP = 256


def generate(
    params,
    prompt,
    cfg: LMConfig,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    use_flash: bool = False,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_token: int = -1,
    prefix: Optional[Dict[str, Any]] = None,
) -> jax.Array:
    """prompt [B, S] int32 -> generated [B, max_new_tokens] int32.

    Greedy when temperature == 0 (a static python branch), else sampled
    (optionally top-k / nucleus truncated — sample_token); rows that
    emit ``eos_token`` are eos-padded afterwards (mask_after_eos).

    ``prefix``: optional B=1 prefix KV cache (build it once with
    prefill at B=1; its length is its own shape).  The request then
    prefills only its suffix (``prompt`` holds the suffix tokens)
    against the broadcast prefix via the causal segment path; decode is
    unchanged.  Positions are global, so outputs equal generating over
    the concatenated sequence EXACTLY for float caches; with
    ``kv_quant="int8"`` the prefix is read back quantized where a full
    prefill attends pre-quantization k/v, so near-tie argmaxes may
    differ (same class as every int8-KV read-back).  NOTE: prefix mode
    DISABLES flash for the suffix prefill — the causal-segment attend
    (mid-sequence offsets over the whole cache) has no flash kernel, so
    ``use_flash=True`` is ignored there with a one-time warning; plain
    (no-prefix) prefill still uses the flash kernel when available.

    Telemetry (eager calls only — traced calls skip; see _eager):
    time-to-first-token and whole-call tokens/sec land in the flight
    recorder (``seldon_tpu_ttft_seconds`` /
    ``seldon_tpu_decode_tokens_per_second``).  TTFT costs ONE host sync
    at the prefill boundary — the decode scan depends on the first token
    anyway, so no device idle is added, only the host-side enqueue
    overlap of one dispatch.
    Decode runs the TWO-TIER cache: the prefilled main cache is read-only
    inside the scan (mutating a large while-loop carry measured ~10x the
    logical write cost in dus + layout copies — see _attend_two_tier),
    new K/V land in a chunk buffer, merged into main between scans only
    when max_new_tokens exceeds GEN_CHUNK_CAP."""
    B, S = prompt.shape
    P = 0 if prefix is None else prefix["l0"]["k"].shape[2]
    eager = _eager(prompt)
    t0 = time.perf_counter() if eager else 0.0
    use_flash = _resolve_prefix_flash(prefix, use_flash)
    chunked = max_new_tokens - 1 > GEN_CHUNK_CAP
    # single-chunk generations never merge, so main holds ONLY the prompt
    # — decode then streams P+S cache slots, not P+S+max_new masked ones
    main_len = P + S + max_new_tokens if chunked else P + S
    if prefix is None:
        main = init_cache(cfg, B, main_len)
        logits, main = prefill(params, prompt, main, cfg, use_flash)
    else:
        # suffix-prefill against a cache sized EXACTLY P+S (the causal
        # segment dots stream the whole buffer, so pre-sizing to
        # main_len would bill every suffix position for max_new dead
        # slots); chunked mode pads up to main_len afterwards, once
        main = build_prefix_main(prefix, B, P + S, cfg)
        logits, main = segment_forward(
            params, prompt, main, P, cfg, segment=True, last_only=True)
        logits = logits[:, -1, :]
        if main_len > P + S:
            main = {
                li: {
                    kk: jnp.concatenate(
                        [vv, jnp.zeros(
                            vv.shape[:2] + (main_len - P - S,)
                            + vv.shape[3:], vv.dtype)], axis=2)
                    for kk, vv in layer.items()
                }
                for li, layer in main.items()
            }
    if rng is None:
        rng = jax.random.key(0)

    key0, rng = jax.random.split(rng)
    first = sample_token(logits, key0, temperature, top_k, top_p)
    if eager:
        # the decode scan depends on `first` anyway — blocking here adds
        # no device idle, just surfaces the true prefill latency
        jax.block_until_ready(first)
        RECORDER.observe_ttft(time.perf_counter() - t0)
        RECORDER.set_kv_slots(
            active=B * (P + S), reserved=B * (main_len - P - S)
        )

    def scan_steps(main, n_main, token, key, n, cap):
        # n_main is a python int here: slice the valid prefix statically,
        # so the scan neither streams nor masks the unwritten tail and
        # the validity select disappears (main_full)
        if main["l0"]["k"].shape[2] > n_main:
            main = {
                li: {kk: vv[:, :, :n_main] for kk, vv in layer.items()}
                for li, layer in main.items()
            }
        chunk = init_chunk(cfg, B, cap)
        # one scan body for one-shot and streamed decoding — the
        # stream-equals-generate contract rests on this delegation
        toks, (token, chunk, _, key) = _chunk_step(
            params, token, main, chunk, jnp.int32(n_main), jnp.int32(0),
            key, cfg, n, temperature, main_full=True,
            top_k=top_k, top_p=top_p,
        )
        return toks, chunk, token, key

    # first token came from prefill; the scans emit the remaining N-1 (no
    # wasted final forward whose logits would be discarded)
    out = [first[:, None]]
    token, key = first, rng
    n_main, remaining = P + S, max_new_tokens - 1
    while remaining > 0:
        n = min(remaining, GEN_CHUNK_CAP) if chunked else remaining
        toks, chunk, token, key = scan_steps(
            main, n_main, token, key, n, GEN_CHUNK_CAP if chunked else n
        )
        out.append(toks)
        remaining -= n
        if remaining > 0:  # fold the finished chunk in before the next
            main = merge_chunk(main, chunk, n_main, cfg)
            n_main += n
    result = mask_after_eos(
        jnp.concatenate(out, axis=1), eos_token)  # [B, max_new]
    if eager:
        # block before timing: serving callers materialize next anyway
        jax.block_until_ready(result)
        elapsed = time.perf_counter() - t0
        if elapsed > 0:
            RECORDER.observe_decode_rate(B * max_new_tokens / elapsed)
    return result


def _chunk_step(params, token, main, chunk_buf, n_main, used, key,
                cfg: LMConfig, n: int, temperature: float,
                main_full: bool = False, top_k: int = 0,
                top_p: float = 0.0):
    """n cached decode steps as ONE jitted scan over the two-tier cache:
    main is READ-ONLY (see _attend_two_tier), new K/V go to ``chunk_buf``
    slots used..used+n-1.  Returns (tokens [B, n], (token, chunk_buf,
    used', key)).  The per-(B, n) executable is cached by jit, so a
    stream costs ceil(max_new/chunk) device dispatches regardless of
    length."""

    def step(carry, _):
        token, chunk_buf, used, key = carry
        key, sub = jax.random.split(key)
        logits, chunk_buf = decode_step_two_tier(
            params, token, main, chunk_buf, n_main, used, cfg, main_full
        )
        nxt = sample_token(logits, sub, temperature, top_k, top_p)
        return (nxt, chunk_buf, used + 1, key), nxt

    (token, chunk_buf, used, key), toks = jax.lax.scan(
        step, (token, chunk_buf, used, key), None, length=n
    )
    return toks.T, (token, chunk_buf, used, key)  # [B, n]


# chunk buffer DONATED across chunk dispatches (each SSE chunk would
# otherwise copy it in and out of the program); main is NOT donated — it
# is read-only and stays resident across every dispatch of a stream.
# Callers must treat the passed chunk_buf as consumed — stream_chunks
# reassigns it every iteration.
_chunk_step_jit = jax.jit(
    _chunk_step,
    static_argnames=("cfg", "n", "temperature", "main_full", "top_k",
                     "top_p"),
    donate_argnums=(3,),
)

def grow_merge(main, chunk, cfg: LMConfig, used: int):
    """Concatenate chunk[:used] onto main along the length axis, returning
    a main cache that is EXACTLY full (every slot valid).

    Streams use this instead of a dus into a max_new-sized preallocation:
    a big mostly-empty main would make every decode step pay the QK dot
    and validity select over unwritten slots (the bitcast_select_fusion
    cost, ~1.2 ms/step at B=256, the two-tier design exists to remove).
    The full-buffer copy here runs once per STREAM_CHUNK_CAP tokens —
    ~2 decode-steps' worth of HBM traffic amortised over 128 steps — and
    buys ``main_full=True`` on every step of arbitrarily long streams.

    Costs, stated plainly:
      * each merge grows main's length, so the NEXT chunk-scan is a new
        shape — one XLA compile per merge point.  Merge offsets are fixed
        for a given (B, S, chunk, cap), the serving engine pins max_new
        per deployment, and the persistent compile cache keeps them
        across restarts, so this is a one-time cost per deployment shape
        (the one-shot ``generate`` path has sliced main to n_main per
        chunk since round 4 — same shape-per-chunk property).  The
        steady-state alternative (fixed max_new-sized main) pays the
        mostly-empty select ~1.2 ms/EVERY step at B=256 instead;
      * concat cannot donate, so a merge transiently holds old+new main
        (~2x cache HBM) before GC frees the old one.  Streams whose KV
        cache approaches half of free HBM should lower max_new or batch
        instead of relying on this path."""
    out = {}
    for i in range(cfg.n_layers):
        ml, cl = main[f"l{i}"], chunk[f"l{i}"]
        layer = {
            "k": jnp.concatenate(
                [ml["k"], cl["k"][:, :, :used].astype(ml["k"].dtype)], axis=2),
            "v": jnp.concatenate(
                [ml["v"], cl["v"][:, :, :used].astype(ml["v"].dtype)], axis=2),
        }
        if "k_s" in ml:
            layer["k_s"] = jnp.concatenate(
                [ml["k_s"], cl["k_s"][:, :, :used]], axis=2)
            layer["v_s"] = jnp.concatenate(
                [ml["v_s"], cl["v_s"][:, :, :used]], axis=2)
        out[f"l{i}"] = layer
    return out


# shape-changing, so donation cannot alias outputs to inputs; freeing the
# old buffers immediately after is the caller's job (Python GC suffices)
_grow_merge_jit = jax.jit(grow_merge, static_argnames=("cfg", "used"))

#: stream chunk-buffer capacity (slots between merges)
STREAM_CHUNK_CAP = 128


def stream_chunks(params, prompt, cfg: LMConfig, max_new_tokens: int,
                  chunk: int = 8, temperature: float = 0.0,
                  rng: Optional[jax.Array] = None,
                  use_flash: bool = False, top_k: int = 0,
                  top_p: float = 0.0, eos_token: int = -1,
                  prefix=None):
    """Incremental decoding: yields token arrays [B, <=chunk] whose
    concatenation equals ``generate(...)`` token-for-token (same
    sampling semantics, same PRNG stream, same eos padding, same
    optional shared-prefix cache).

    With ``eos_token`` set, once EVERY row has emitted it the remaining
    chunks are host-generated eos padding — no further device work —
    and within-stream tokens after a row's first eos are masked to eos
    (the generate() contract).

    The host loop exists ONLY to surface tokens early — each iteration is
    one jitted scan over ``chunk`` two-tier cached steps, so the device
    work is the same one-scan-per-chunk shape serving wants; first token
    arrives after prefill + (chunk-1) steps instead of after
    max_new_tokens steps.  When the chunk buffer fills
    (STREAM_CHUNK_CAP), the host grows the main cache by the buffered
    tokens (grow_merge — main stays exactly full, so every step of a
    long stream decodes over valid slots only) and continues.

    With ``eos_token`` set, after-eos masking runs ON DEVICE
    (_chunk_eos_mask: a carried ``seen_eos`` latch jitted with the mask)
    and the host reads back only a scalar all-done flag per chunk to
    drive the early-stop branch — yielded chunks stay device arrays, so
    the consumer decides when to pay the readback.

    Telemetry (flight recorder): TTFT recorded at the first sampled
    token (one host sync at the prefill boundary — the first scan
    depends on that token anyway), tokens/sec over the whole stream at
    exhaustion, KV slot occupancy per merge."""
    B, S = prompt.shape
    t0 = time.perf_counter()
    cap = STREAM_CHUNK_CAP
    # a per-dispatch scan may not outgrow the chunk buffer: a larger
    # request would dus past the buffer (clamped to the last slot =
    # silent KV corruption).  Engine clients may ask up to 256.
    chunk = min(int(chunk), cap)
    # main starts prompt-sized and GROWS at each merge (grow_merge), so
    # it is exactly full at every decode step — long streams never pay
    # the mostly-empty-buffer QK dot + validity select
    P = 0 if prefix is None else prefix["l0"]["k"].shape[2]
    use_flash = _resolve_prefix_flash(prefix, use_flash)
    if prefix is None:
        main = init_cache(cfg, B, S)
        logits, main = prefill(params, prompt, main, cfg, use_flash)
    else:
        main = build_prefix_main(prefix, B, P + S, cfg)
        logits, main = segment_forward(
            params, prompt, main, P, cfg, segment=True, last_only=True)
        logits = logits[:, -1, :]
    if rng is None:
        rng = jax.random.key(0)
    key0, rng = jax.random.split(rng)
    first = sample_token(logits, key0, temperature, top_k, top_p)
    jax.block_until_ready(first)  # the first scan depends on it anyway
    RECORDER.observe_ttft(time.perf_counter() - t0)

    token, key = first, rng
    chunk_buf = init_chunk(cfg, B, cap)
    n_main, used = P + S, 0
    done = 0
    # per-row "has emitted eos" latch — DEVICE-side; the host sees only
    # the scalar all_done flag (one tiny readback per chunk instead of
    # the whole [B, chunk] token array)
    seen_eos = jnp.zeros((B,), bool)
    all_done = False

    def finalize(toks):
        nonlocal seen_eos, all_done
        if eos_token < 0:
            return toks
        toks, seen_eos, flag = _chunk_eos_mask_jit(
            toks, seen_eos, eos_token=eos_token
        )
        all_done = bool(flag)  # scalar readback drives the early stop
        return toks

    def emit(n):
        nonlocal token, key, chunk_buf, main, n_main, used
        if used + n > cap:  # grow main by the buffered tokens, continue
            main = _grow_merge_jit(main, chunk_buf, cfg=cfg, used=used)
            n_main += used
            chunk_buf = init_chunk(cfg, B, cap)
            used = 0
            RECORDER.set_kv_slots(
                active=B * n_main, reserved=B * cap
            )
        toks, (token, chunk_buf, _, key) = _chunk_step_jit(
            params, token, main, chunk_buf, jnp.int32(n_main),
            jnp.int32(used), key, cfg=cfg, n=n, temperature=temperature,
            # grow_merge keeps main exactly full at every step
            main_full=True, top_k=top_k, top_p=top_p,
        )
        used += n
        return toks

    # first chunk: the prefill token + (chunk-1) scanned steps
    n_first = min(chunk - 1, max_new_tokens - 1)
    if n_first > 0:
        yield finalize(jnp.concatenate([first[:, None], emit(n_first)],
                                       axis=1))
    else:
        yield finalize(first[:, None])
    done = 1 + n_first
    decoded = done  # device-decoded tokens only (host eos pads excluded)
    while done < max_new_tokens:
        n = min(chunk, max_new_tokens - done)
        if eos_token >= 0 and all_done:
            # every row is finished: pad from the host, skip the device
            yield jnp.full((B, n), jnp.int32(eos_token))
        else:
            yield finalize(emit(n))
            decoded += n
        done += n
    elapsed = time.perf_counter() - t0
    if elapsed > 0:
        # rate counts only device-decoded tokens — an early-stopped
        # stream's host-padded filler must not inflate the SLO histogram
        RECORDER.observe_decode_rate(B * decoded / elapsed)


# ---------------------------------------------------------------------------
# Paged KV-block cache — the continuous-batching serving lane
# (runtime/genserver.py drives these; see docs/operations.md "tuning the
# generation scheduler")
# ---------------------------------------------------------------------------
#
# The dense caches above are per-REQUEST: one [B, KV, L, hd] buffer sized
# for one request's batch and lifetime.  Continuous batching co-schedules
# sequences of different ages in one decode batch, so the cache becomes a
# process-wide POOL of fixed-size blocks ([num_blocks, block_size, KV, hd]
# per layer) and each sequence carries a BLOCK TABLE mapping its logical
# block i to a physical pool block.  Allocation/free/eviction and
# occupancy accounting are host-side (runtime/genserver.py BlockAllocator);
# the device side below is three programs:
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
# blocks where they lie and serves the width-1 decode step where
# ops.paged_attention.inplace_supported says so.  The gather path is the
# reference the kernel is tested against.  Writes SCATTER fresh K/V at
# (table[pos // bs], pos % bs) — the vLLM reshape_and_cache shape — and the
# pool's layout is the one XLA's TPU scatter wants (a token's KV x hd
# window minor-most): any other makes XLA re-lay the whole pool around
# every write.  Block 0 is a reserved SCRATCH block: masked rows and pad
# positions write there, so inactive slots never need a branch.


def init_block_pool(cfg: LMConfig, num_blocks: int, block_size: int
                    ) -> Dict[str, Any]:
    """Per-layer {k, v[, k_s, v_s]} pools shaped
    ``[num_blocks, block_size, KV, hd]``.  Block 0 is the scratch block —
    the allocator (runtime/genserver.py) hands out ids >= 1.  int8 pools
    carry per-position scale planes exactly like init_cache."""
    hd = cfg.d_model // cfg.n_heads
    kv = cfg.kv_heads
    # XLA:CPU has no native bf16 scatter: a bf16 pool pays TWO whole-pool
    # converts (bf16 -> f32 scatter -> bf16) around EVERY write, which
    # scales step cost with POOL size instead of batch size (measured:
    # 211 ms vs 6 ms per decode round at 1024 blocks).  CPU backends
    # store the pool f32; TPU/GPU keep the configured dtype (bf16 native,
    # half the HBM) — same degradation pattern as the quality observatory.
    dtype = cfg.dtype
    if dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        dtype = jnp.float32

    def layer():
        if cfg.kv_quant == "int8":
            return {
                "k": jnp.zeros((num_blocks, block_size, kv, hd), jnp.int8),
                "v": jnp.zeros((num_blocks, block_size, kv, hd), jnp.int8),
                "k_s": jnp.zeros((num_blocks, block_size, kv), jnp.float32),
                "v_s": jnp.zeros((num_blocks, block_size, kv), jnp.float32),
            }
        return {
            "k": jnp.zeros((num_blocks, block_size, kv, hd), dtype),
            "v": jnp.zeros((num_blocks, block_size, kv, hd), dtype),
        }

    return {f"l{i}": layer() for i in range(cfg.n_layers)}


def _paged_view(layer, tables):
    """Gather one layer's blocks into a dense position-ordered cache view:
    pool [N, bs, KV, hd] + tables [B, nblk] -> {k, v[, k_s, v_s]} with k/v
    [B, KV, nblk*bs, hd] — the _grouped_qk/_grouped_pv layout, so paged
    attention reuses the exact dot formulations the dense caches use."""
    out = {}
    for name in ("k", "v"):
        g = layer[name][tables]  # [B, nblk, bs, KV, hd]
        B, nblk, bs, KV, hd = g.shape
        out[name] = g.transpose(0, 3, 1, 2, 4).reshape(B, KV, nblk * bs, hd)
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
    (per-token absmax, _quantize_kv) and scatter the scale planes too."""
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
        out["k"] = layer["k"].at[blk, off].set(
            k_new.transpose(0, 2, 1, 3).astype(layer["k"].dtype))
        out["v"] = layer["v"].at[blk, off].set(
            v_new.transpose(0, 2, 1, 3).astype(layer["v"].dtype))
    return out


def _attend_paged(q, view, start):
    """q [B, H, W, hd] over a dense paged view; query i of row b sees
    positions <= start[b] + i (its own fresh K/V is already in the pool).
    Per-row ``start`` is what separates this from _attend_cached_causal:
    co-scheduled rows sit at different sequence lengths.  W == 1 with
    start == n_valid is exactly the cached decode mask (kpos <= n_valid)."""
    s = _grouped_qk(q, view["k"], view.get("k_s"))  # [B, KV, g, W, L]
    L = view["k"].shape[2]
    W = q.shape[2]
    qpos = start[:, None] + jnp.arange(W)[None, :]          # [B, W]
    allowed = jnp.arange(L)[None, None, :] <= qpos[:, :, None]  # [B, W, L]
    s = jnp.where(allowed[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_pv(p, view["v"], q.shape, q.dtype, view.get("v_s"))


def _paged_block(lp, x, pool_layer, tables, start, valid, cfg: LMConfig,
                 plan=None, interpret: bool = False):
    """One decoder block over the paged pool: K/V written at per-row
    positions start[b] + i (scratch-routed where ``valid`` is False),
    attention over each row's own blocks.  x [B, W, D].

    ``plan`` (ops.paged_attention.decode_plan, width 1 only) selects the
    in-place formulation: attention reads the row's blocks from the pool
    where they lie.  None takes the gather path."""
    from seldon_core_tpu.ops.paged_attention import paged_decode_attention
    from seldon_core_tpu.ops.quant import lm_matmul

    B, W, D = x.shape
    hd = cfg.d_model // cfg.n_heads
    kv_h = cfg.kv_heads
    # the stages below are jax.named_scope's: op metadata only (same
    # programs, same numerics), read back from a profile window's device
    # ops by bench/lib/trace_scopes.py — keep the names stable
    with jax.named_scope("qkv"):
        h = _rmsnorm(x, lp["ln1"])
        qkv = lm_matmul(lp, "wqkv", h, out_dtype=x.dtype)
        q, k, v = jnp.split(qkv, [D, D + kv_h * hd], axis=-1)
        q = _heads(q, B, W, cfg.n_heads, hd)
        k = _heads(k, B, W, kv_h, hd)
        v = _heads(v, B, W, kv_h, hd)
    positions = start[:, None] + jnp.arange(W)[None, :]  # [B, W] per-row
    if cfg.rope:
        with jax.named_scope("rope"):
            q = apply_rope(q, positions, cfg.rope_base)
            k = apply_rope(k, positions, cfg.rope_base)
    with jax.named_scope("kv_write"):
        pool_layer = _paged_write(pool_layer, tables, positions, valid, k, v)
    if plan is None:
        with jax.named_scope("kv_gather"):
            view = _paged_view(pool_layer, tables)
    with jax.named_scope("attn"):
        if plan is None:
            a = _attend_paged(q, view, start)
        else:
            a = paged_decode_attention(
                q, pool_layer["k"], pool_layer["v"], tables, *plan,
                interpret=interpret)
        a = a.transpose(0, 2, 1, 3).reshape(B, W, D)
    with jax.named_scope("wo"):
        x = x + lm_matmul(lp, "wo", a, out_dtype=x.dtype)
    with jax.named_scope("ffn"):
        h = _rmsnorm(x, lp["ln2"])
        y, _lb = _ffn(lp, h, cfg, mesh=None)
        x = x + y
    return x, pool_layer


def paged_forward(params, tokens, pool, tables, start, width,
                  cfg: LMConfig, last_only: bool = True):
    """Forward W tokens per row at per-row offsets over the paged pool —
    chunked prefill (one prompt chunk at a time, decode never stalls for
    the whole prompt) and the speculative verify pass share this program.

    tokens [B, W] int32; start [B] per-row global offset of token 0;
    width [B] valid token count per row (positions past it are pad: their
    K/V go to scratch, their logits are garbage nobody reads).  Returns
    (logits, pool'): logits [B, V] at each row's LAST valid position when
    ``last_only`` (prefill needs only the next-token distribution — the
    unembed is ~20% of prefill FLOPs at real vocab sizes), else [B, W, V]
    for every position (the verify pass scores all of them)."""
    B, W = tokens.shape
    valid = jnp.arange(W)[None, :] < width[:, None]  # [B, W]
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    for i in range(cfg.n_layers):
        x, pool[f"l{i}"] = _paged_block(
            params[f"l{i}"], x, pool[f"l{i}"], tables, start, valid, cfg
        )
    with jax.named_scope("unembed"):
        if last_only:
            idx = jnp.clip(width - 1, 0, W - 1)
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(idx[:, None, None], (B, 1, x.shape[2])),
                axis=1,
            )  # [B, 1, D] — before the (positionwise) norm: same numerics
        x = _rmsnorm(x, params["ln_f"])
        logits = (x @ params["embed"].T).astype(jnp.float32)
    return (logits[:, 0, :] if last_only else logits), pool


def decode_inplace(pool, mesh=None) -> bool:
    """Whether a decode round over ``pool`` attends in place (the Pallas
    kernel) or through the gather path: ops.paged_attention
    .inplace_supported over what is observable here — the backend, the
    pool's dtype and shapes, and the caller's mesh."""
    from seldon_core_tpu.ops.paged_attention import inplace_supported

    k = pool["l0"]["k"]
    return inplace_supported(
        width=1, backend=jax.default_backend(), pool_dtype=k.dtype,
        mesh=mesh, block_size=k.shape[1], kv_heads=k.shape[2],
        head_dim=k.shape[3])


def paged_decode_round(params, pool, tables, token, n_valid, active,
                       seen_eos, keys, cfg: LMConfig, *, span: int,
                       temperature: float, top_k: int, top_p: float,
                       eos_token: int, inplace=None):
    """``span`` cached decode steps for the whole in-flight batch as ONE
    lax.scan — the scheduler's unit of work between admission points.

    ``inplace``: None decides by ``decode_inplace(pool)`` (a caller that
    shards the pool over a mesh passes its own answer: a traced program
    cannot see shardings); True / False force the kernel / the gather
    path; "interpret" runs the kernel in Pallas interpret mode (tests on
    the CPU).

    token [B] pending tokens; n_valid [B] per-row cache length; active [B]
    masks empty slots (their writes go to scratch, their samples are
    forced to 0); seen_eos [B] is the device-side after-eos latch (rows
    past their stop keep riding the scan but emit eos — the generate()
    output contract — until the host retires them at the round boundary);
    keys [B] per-ROW PRNG keys (sampled decoding must not couple co-batched
    requests the way a shared batch key does).  Returns
    (toks [B, span], pool', token', n_valid', seen_eos', keys')."""
    from seldon_core_tpu.ops.paged_attention import decode_plan

    if inplace is None:
        inplace = decode_inplace(pool)
    capacity = tables.shape[1] * pool["l0"]["k"].shape[1]

    def step(carry, _):
        pool, token, n_valid, seen_eos, keys = carry
        # the kernel's scalar operands: once a step, shared by the layers
        plan = decode_plan(n_valid, active, capacity) if inplace else None
        with jax.named_scope("embed"):
            x = params["embed"][token][:, None, :]
        for i in range(cfg.n_layers):
            x, pool[f"l{i}"] = _paged_block(
                params[f"l{i}"], x, pool[f"l{i}"], tables, n_valid,
                active[:, None], cfg, plan=plan,
                interpret=inplace == "interpret",
            )
        with jax.named_scope("unembed"):
            x = _rmsnorm(x, params["ln_f"])
            logits = (x[:, 0, :] @ params["embed"].T).astype(jnp.float32)
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
        return (pool, nxt, n_valid, seen_eos, keys), nxt

    (pool, token, n_valid, seen_eos, keys), toks = jax.lax.scan(
        step, (pool, token, n_valid, seen_eos, keys), None, length=span
    )
    return toks.T, pool, token, n_valid, seen_eos, keys


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
    B = token.shape[0]
    W = k + 1

    def dstep(carry, _):
        d_pool, tok, nv = carry
        x = d_params["embed"][tok][:, None, :]
        for i in range(d_cfg.n_layers):
            x, d_pool[f"l{i}"] = _paged_block(
                d_params[f"l{i}"], x, d_pool[f"l{i}"], d_tables, nv,
                active[:, None], d_cfg,
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
    t_logits, t_pool = paged_forward(
        t_params, seg, t_pool, t_tables, n_valid, widths, t_cfg,
        last_only=False,
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


def paged_write_prefix_tail(pool, prefix, blk, cfg: LMConfig, *, p0: int):
    """Copy the shared-prefix TAIL (positions p0..P-1, the part that does
    not fill a whole block) into one private pool block ``blk`` at offsets
    0..r-1.  Full prefix blocks are written once and SHARED by block-table
    reference across every sequence (pinned in the allocator); the
    partially-filled boundary block must be private because the sequence's
    own tokens continue into it."""
    out = {}
    for li, layer in pool.items():
        pl = prefix[li]
        new = dict(layer)
        r = pl["k"].shape[2] - p0
        new["k"] = layer["k"].at[blk, 0:r].set(
            pl["k"][0, :, p0:, :].transpose(1, 0, 2).astype(
                layer["k"].dtype))
        new["v"] = layer["v"].at[blk, 0:r].set(
            pl["v"][0, :, p0:, :].transpose(1, 0, 2).astype(
                layer["v"].dtype))
        if "k_s" in layer:
            new["k_s"] = layer["k_s"].at[blk, 0:r].set(
                pl["k_s"][0, :, p0:].transpose(1, 0))
            new["v_s"] = layer["v_s"].at[blk, 0:r].set(
                pl["v_s"][0, :, p0:].transpose(1, 0))
        out[li] = new
    return out


def paged_write_prefix_blocks(pool, prefix, blocks, cfg: LMConfig):
    """Write the full-block part of a shared prefix into pool blocks
    ``blocks`` (a python list of block ids, len = P // block_size) — run
    ONCE per deployment; every admitted sequence then references these
    blocks through its table without copying."""
    bs = pool["l0"]["k"].shape[1]
    out = pool
    for j, blk in enumerate(blocks):
        seg = {}
        for li, layer in out.items():
            pl = prefix[li]
            new = dict(layer)
            lo = j * bs
            new["k"] = layer["k"].at[blk, 0:bs].set(
                pl["k"][0, :, lo:lo + bs, :].transpose(1, 0, 2).astype(
                    layer["k"].dtype))
            new["v"] = layer["v"].at[blk, 0:bs].set(
                pl["v"][0, :, lo:lo + bs, :].transpose(1, 0, 2).astype(
                    layer["v"].dtype))
            if "k_s" in layer:
                new["k_s"] = layer["k_s"].at[blk, 0:bs].set(
                    pl["k_s"][0, :, lo:lo + bs].transpose(1, 0))
                new["v_s"] = layer["v_s"].at[blk, 0:bs].set(
                    pl["v_s"][0, :, lo:lo + bs].transpose(1, 0))
            seg[li] = new
        out = seg
    return out


# pools are DONATED through every paged program: the scheduler owns exactly
# one live pool pytree per model and rebinds it after each dispatch, so XLA
# mutates the blocks in place instead of copying the whole pool per step
paged_forward_jit = jax.jit(
    paged_forward, static_argnames=("cfg", "last_only"), donate_argnums=(2,)
)
paged_decode_round_jit = jax.jit(
    paged_decode_round,
    static_argnames=("cfg", "span", "temperature", "top_k", "top_p",
                     "eos_token", "inplace"),
    donate_argnums=(1,),
)
paged_spec_round_jit = jax.jit(
    paged_spec_round, static_argnames=("t_cfg", "d_cfg", "k"),
    donate_argnums=(2, 3),
)
paged_write_prefix_tail_jit = jax.jit(
    paged_write_prefix_tail, static_argnames=("cfg", "p0"),
    donate_argnums=(0,),
)
# blocks is a STATIC tuple: the loop unrolls into one fused scatter program
# compiled once per deployment (the prefix is written exactly once)
paged_write_prefix_blocks_jit = jax.jit(
    paged_write_prefix_blocks, static_argnames=("cfg", "blocks"),
    donate_argnums=(0,),
)


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
                 quant: str = "none", attention: str = "auto",
                 kv_quant: str = "none",
                 n_kv_heads: int = 0, weights_path: str = "",
                 rope: bool = True, rope_base: float = 10000.0):
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
        )
        from seldon_core_tpu.models.transformer import resolve_flash

        self.use_flash = resolve_flash(str(attention), mesh)
        self.weights_path = str(weights_path)
        self.seed = int(seed)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token = int(eos_token)
        # shared system-prompt prefix ("1,2,3" token ids): its KV cache
        # is computed ONCE in init_state and reused by every request
        self.prefix_ids = [
            int(t) for t in str(prefix_tokens).replace(" ", "").split(",")
            if t != ""
        ]
        for t in self.prefix_ids:
            if not 0 <= t < self.cfg.vocab:
                raise ValueError(
                    f"prefix token {t} outside vocab [0, {self.cfg.vocab})")
        # sampled decoding draws per-row noise from one key, so a row's
        # tokens depend on its position in the stacked batch; MoE capacity
        # routing likewise couples rows (shared capacity over the flattened
        # token stream) — either way, coalescing other callers' rows would
        # change this caller's answer.  The request counter in state
        # additionally varies the sampling key per request.
        self.batch_coupled = (
            self.temperature > 0.0 or self.cfg.moe_every > 0
        )
        self.updates_state_on_predict = self.temperature > 0.0

    def _prefix(self, state):
        return state.get("prefix_cache")

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
            pc = init_cache(self.cfg, 1, len(self.prefix_ids))
            _, pc = prefill(
                params, jnp.asarray([self.prefix_ids], jnp.int32), pc,
                self.cfg, self.use_flash,
            )
            state["prefix_cache"] = pc
        return state

    def predict(self, state, X):
        prompt = sanitize_prompt(X, self.cfg.vocab)
        key = jax.random.fold_in(jax.random.key(self.seed),
                                 state["requests"])
        y = generate(
            state["params"], prompt, self.cfg,
            max_new_tokens=self.max_new_tokens,
            temperature=self.temperature,
            rng=key,
            use_flash=self.use_flash,
            top_k=self.top_k, top_p=self.top_p,
            eos_token=self.eos_token,
            prefix=self._prefix(state),
        ).astype(jnp.float32)
        if self.temperature > 0.0:
            # preserve EVERY state key (prefix_cache!) — only the
            # request counter advances
            new_state = {**state, "requests": state["requests"] + 1}
            return y, UnitAux(state=new_state)
        return y

    def continuous_spec(self, state):
        """Scheduler contract for the continuous-batching generation lane
        (runtime/genserver.py): everything the per-step scheduler needs to
        run this unit's decoding — params, config, sampling knobs, the
        shared-prefix cache.  Returns None when the unit cannot be
        continuously scheduled: MoE capacity routing couples co-batched
        rows through the shared expert-capacity reduction, so co-scheduling
        other requests' rows would change this request's answer."""
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
            "prefix_cache": state.get("prefix_cache"),
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
        prompt = sanitize_prompt(jnp.asarray(X), self.cfg.vocab)
        if self.temperature > 0.0:
            key = jax.random.fold_in(
                jax.random.key(self.seed), next(_stream_counter)
            )
        else:
            key = jax.random.fold_in(jax.random.key(self.seed), 0)
        yield from stream_chunks(
            state["params"], prompt, self.cfg,
            max_new_tokens=self.max_new_tokens, chunk=int(chunk),
            temperature=self.temperature, rng=key,
            use_flash=self.use_flash,
            top_k=self.top_k, top_p=self.top_p,
            eos_token=self.eos_token,
            prefix=self._prefix(state),
        )


