"""Decode-regime attribution probe — where does a cached decode step's
time go, and how close is it to the HBM roofline?

Decode is HBM-bound: every step streams the matmul weights plus the whole
KV cache.  MFU is the wrong axis for that regime (the FLOPs are trivial);
the honest roofline is bytes/step vs MEASURED achievable HBM bandwidth.
This probe breaks a step into its components on the real chip:

  * measured achievable HBM bandwidth (chained large-array reductions —
    the practical ceiling, not the spec sheet);
  * full decode step at B and B_MAX, bf16 cache vs int8 KV cache;
  * attention-only (one layer's ``_attend_cached`` over a live-size
    cache, chained) — isolates the cache stream;
  * layer-count slope (n_layers=2 vs 12) — separates per-layer cost from
    per-step fixed overhead (embed/unembed/argmax/scan plumbing).

Methodology matches bench.py's MFU probe: chained data-dependent reps
inside ONE dispatch, measured dispatch floor subtracted.  Prints one JSON
line; run it standalone on the TPU box (`python scripts/probe_decode.py
[--smoke]`).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

# script lives in scripts/ — put the repo root on the path (sys.path
# insertion, NOT the PYTHONPATH env var: the latter set at interpreter
# startup breaks this environment's TPU backend registration)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp





def _dispatch_floor():
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.zeros((1, 8), jnp.float32)
    np.asarray(f(x))
    lat = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(f(x))
        lat.append(time.perf_counter() - t0)
    return float(np.percentile(lat, 50))


def _timed(fn, *args, floor_s=0.0, n=1):
    """Compile, then time one dispatch; returns seconds per rep."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    raw = time.perf_counter() - t0
    return max(raw - floor_s, 0.05 * raw) / n


def measure_hbm_bw(floor_s: float, gib: float = 1.0, reps: int = 8):
    """Achievable HBM read bandwidth: chained full reads of a large bf16
    array.  ``max(arr + alpha)`` with a carry-dependent alpha defeats
    loop-invariant hoisting without adding measurable compute."""
    n = int(gib * (1 << 30) // 2)  # bf16 elements
    arr = jnp.ones((n,), jnp.bfloat16)

    @jax.jit
    def chain(a):
        def body(alpha, _):
            m = jnp.max(a + alpha)
            return m * jnp.bfloat16(1e-3), m
        _, ms = jax.lax.scan(body, jnp.bfloat16(0), None, length=reps)
        return ms

    t = _timed(chain, arr, floor_s=floor_s, n=reps)
    return (n * 2) / t  # bytes/s


def decode_bytes_per_step(cfg, batch: int, cache_len: int) -> int:
    """HBM bytes a cached decode step must stream: every matmul'd weight
    (at its serving dtype) + the whole KV cache read (+ scales when
    int8)."""
    d, ff, v, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    hd = d // cfg.n_heads
    kv = cfg.kv_heads
    qkv_out = d + 2 * kv * hd
    wbytes_el = 1 if cfg.quant == "int8" else np.dtype(cfg.dtype).itemsize
    per_layer_w = (d * qkv_out + d * d + 2 * d * ff) * wbytes_el
    unembed = d * v * np.dtype(cfg.dtype).itemsize  # tied head, not quantized
    kv_el = 1 if cfg.kv_quant == "int8" else np.dtype(cfg.dtype).itemsize
    kv_read = 2 * batch * kv * cache_len * hd * kv_el
    kv_scales = (2 * batch * kv * cache_len * 4
                 if cfg.kv_quant == "int8" else 0)
    return L * (per_layer_w + kv_read + kv_scales) + unembed


def decode_step_time(params, cfg, B, S, NEW, toks0, floor_s):
    from seldon_core_tpu.models.generate import _chunk_step, init_cache, init_chunk, prefill

    btoks = toks0[:1].repeat(B, axis=0) if toks0.shape[0] != B else toks0
    main = init_cache(cfg, B, S)
    logits, main = jax.jit(
        lambda p, t, c: prefill(p, t, c, cfg)
    )(params, btoks, main)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    chunk = init_chunk(cfg, B, NEW)
    carry = (first, main, chunk, jnp.int32(S), jnp.int32(0),
             jax.random.key(0))
    step = jax.jit(
        lambda p, tok, m, c, nm, used, key: _chunk_step(
            p, tok, m, c, nm, used, key, cfg, NEW, 0.0, main_full=True)
    )
    return _timed(step, params, *carry, floor_s=floor_s, n=NEW)


def attention_only_time(cfg, B, cache_len, floor_s, reps, kv_quant="none"):
    """One layer's cached attention, chained: q_{i+1} derived from out_i."""
    from seldon_core_tpu.models.generate import _attend_cached, _quantize_kv

    hd = cfg.d_model // cfg.n_heads
    kv = cfg.kv_heads
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, kv, cache_len, hd)), cfg.dtype)
    v = jnp.asarray(rng.normal(size=(B, kv, cache_len, hd)), cfg.dtype)
    if kv_quant == "int8":
        k_q, k_s = _quantize_kv(k)
        v_q, v_s = _quantize_kv(v)
        layer = {"k": k_q, "v": v_q, "k_s": k_s, "v_s": v_s}
    else:
        layer = {"k": k, "v": v}
    q0 = jnp.asarray(rng.normal(size=(B, cfg.n_heads, 1, hd)), cfg.dtype)

    @jax.jit
    def chain(layer, q):
        def body(qc, _):
            out = _attend_cached(qc, layer, cache_len - 1)
            return (qc * 0.5 + out * 0.5).astype(qc.dtype), ()
        qf, _ = jax.lax.scan(body, q, None, length=reps)
        return qf

    return _timed(chain, layer, q0, floor_s=floor_s, n=reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    from seldon_core_tpu.models.transformer import LMConfig, lm_init
    from seldon_core_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    floor_s = _dispatch_floor()
    out = {"dispatch_floor_ms": round(floor_s * 1e3, 2)}

    if args.smoke:
        cfg = LMConfig(vocab=1024, d_model=256, n_heads=8, n_layers=2,
                       d_ff=1024, n_kv_heads=4)
        B, B_MAX, S, NEW = 4, 8, 128, 16
        bw_gib = 0.125
    else:
        cfg = LMConfig(vocab=32768, d_model=1024, n_heads=16, n_layers=12,
                       d_ff=4096, n_kv_heads=4)
        B, B_MAX, S, NEW = 32, 256, 512, 64
        bw_gib = 1.0

    bw = measure_hbm_bw(floor_s, gib=bw_gib)
    out["hbm_bw_measured_gbs"] = round(bw / 1e9, 1)

    params = lm_init(jax.random.key(0), cfg)
    toks0 = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, size=(B, S)),
        jnp.int32,
    )
    total_len = S + NEW

    for b in (B, B_MAX):
        t = decode_step_time(params, cfg, b, S, NEW, toks0, floor_s)
        nbytes = decode_bytes_per_step(cfg, b, total_len)
        out[f"step_ms_b{b}"] = round(t * 1e3, 3)
        out[f"tok_s_b{b}"] = round(b / t, 1)
        out[f"bytes_per_step_mb_b{b}"] = round(nbytes / 1e6, 1)
        out[f"bw_util_pct_b{b}"] = round(100 * nbytes / t / bw, 1)

    # int8 KV cache
    cfg_q = dataclasses.replace(cfg, kv_quant="int8")
    for b in (B, B_MAX):
        t = decode_step_time(params, cfg_q, b, S, NEW, toks0, floor_s)
        nbytes = decode_bytes_per_step(cfg_q, b, total_len)
        out[f"step_ms_b{b}_int8kv"] = round(t * 1e3, 3)
        out[f"tok_s_b{b}_int8kv"] = round(b / t, 1)
        out[f"bw_util_pct_b{b}_int8kv"] = round(100 * nbytes / t / bw, 1)

    # attention-only: one layer's cache stream, chained
    for b in (B, B_MAX):
        for kvq in ("none", "int8"):
            t = attention_only_time(cfg, b, total_len, floor_s,
                                    reps=64 if not args.smoke else 8,
                                    kv_quant=kvq)
            hd = cfg.d_model // cfg.n_heads
            el = 1 if kvq == "int8" else 2
            nbytes = 2 * b * cfg.kv_heads * total_len * hd * el
            tag = "" if kvq == "none" else "_int8"
            out[f"attn_ms_b{b}{tag}"] = round(t * 1e3, 3)
            out[f"attn_bw_util_pct_b{b}{tag}"] = round(
                100 * nbytes / t / bw, 1)

    # layer slope: per-layer vs fixed per-step cost
    cfg2 = dataclasses.replace(cfg, n_layers=2)
    p2 = lm_init(jax.random.key(0), cfg2)
    t2 = decode_step_time(p2, cfg2, B_MAX, S, NEW, toks0, floor_s)
    t12 = out[f"step_ms_b{B_MAX}"] / 1e3
    per_layer = (t12 - t2) / (cfg.n_layers - 2)
    out["step_ms_2layer_bmax"] = round(t2 * 1e3, 3)
    out["per_layer_ms_bmax"] = round(per_layer * 1e3, 3)
    out["fixed_overhead_ms_bmax"] = round(
        (t12 - per_layer * cfg.n_layers) * 1e3, 3)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
