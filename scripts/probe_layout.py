"""Cache-layout A/B probe — is the decode attention stream paying minor-
dim padding?

TPU tiling pads the minor (lane) dimension to 128: a KV cache stored
[B, KV, L, hd] with hd=64 physically occupies — and streams — 2x its
logical bytes.  Storing K/V transposed ([B, KV, hd, L], L on the lane
axis, padded only L->ceil(L/128)) removes that.  This probe times, with
enough chained reps to bury dispatch-floor variance:

  * a trustworthy HBM bandwidth ceiling (max(abs(arr - alpha)) defeats
    the algebraic hoisting that inflated the first attempt);
  * cached decode attention in both layouts (bf16 and int8);
  * the cache dynamic_update_slice write in isolation (copy-bound scans
    would show per-step cost scaling with L);
  * full decode step at two cache lengths (L-dependence attribution).

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

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
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    raw = time.perf_counter() - t0
    return max(raw - floor_s, 0.05 * raw) / n


def measure_hbm_bw(floor_s, gib=1.0, reps=16):
    n = int(gib * (1 << 30) // 2)
    arr = jnp.ones((n,), jnp.bfloat16)

    @jax.jit
    def chain(a):
        def body(alpha, _):
            m = jnp.max(jnp.abs(a - alpha))  # not factorable out of the loop
            return m * jnp.bfloat16(1e-3), m
        _, ms = jax.lax.scan(body, jnp.bfloat16(0), None, length=reps)
        return ms

    t = _timed(chain, arr, floor_s=floor_s, n=reps)
    return (n * 2) / t


def attn_time(B, KV, G, hd, L, floor_s, reps, layout, dtype):
    """Chained cached-attention reps; layout 'nt' stores K/V as
    [B, KV, hd, L] (L on lanes), 'nn' the current [B, KV, L, hd]."""
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(B, KV, L, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, KV, L, hd)), dtype)
    if dtype == jnp.int8:
        k = jnp.asarray(
            rng.integers(-127, 127, size=(B, KV, L, hd)), jnp.int8)
        v = jnp.asarray(
            rng.integers(-127, 127, size=(B, KV, L, hd)), jnp.int8)
    if layout == "nt":
        k = k.transpose(0, 1, 3, 2)  # [B,KV,hd,L]
        v = v.transpose(0, 1, 3, 2)
    q0 = jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.bfloat16)

    def attend(q, k, v):
        ct = jnp.bfloat16
        if layout == "nt":
            kk = k.astype(ct) if k.dtype == jnp.int8 else k
            vv = v.astype(ct) if v.dtype == jnp.int8 else v
            s = jax.lax.dot_general(
                q, kk, (((3,), (2,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32,
            )  # [B,KV,G,L]
            p = jax.nn.softmax(s * (hd ** -0.5), axis=-1).astype(ct)
            o = jax.lax.dot_general(
                vv, p, (((3,), (3,)), ((0, 1), (0, 1))),
                preferred_element_type=jnp.float32,
            )  # [B,KV,hd,G]
            return o.transpose(0, 1, 3, 2).astype(ct)
        kk = k.astype(ct) if k.dtype == jnp.int8 else k
        vv = v.astype(ct) if v.dtype == jnp.int8 else v
        s = jax.lax.dot_general(
            q, kk, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )  # [B,KV,G,L]
        p = jax.nn.softmax(s * (hd ** -0.5), axis=-1).astype(ct)
        return jax.lax.dot_general(
            p, vv, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        ).astype(ct)  # [B,KV,G,hd]

    @jax.jit
    def chain(k, v, q):
        def body(qc, _):
            out = attend(qc, k, v)
            if layout == "nt":
                nxt = qc * 0.5 + out * 0.5
            else:
                nxt = qc * 0.5 + out * 0.5
            return nxt.astype(qc.dtype), ()
        qf, _ = jax.lax.scan(body, q, None, length=reps)
        return qf

    return _timed(chain, k, v, q0, floor_s=floor_s, n=reps)


def dus_time(B, KV, hd, L, floor_s, reps, dtype):
    """Isolated cache write: chained dynamic_update_slice on a carried
    buffer — per-rep cost >> slice size means the scan is copying."""
    buf = jnp.zeros((B, KV, L, hd), dtype)
    blk = jnp.ones((B, KV, 1, hd), dtype)

    @jax.jit
    def chain(buf, blk):
        def body(c, i):
            b, pos = c
            b = jax.lax.dynamic_update_slice(b, blk, (0, 0, pos % L, 0))
            return (b, pos + 1), ()
        (bf, _), _ = jax.lax.scan(
            body, (buf, jnp.int32(0)), jnp.arange(reps))
        return bf

    return _timed(chain, buf, blk, floor_s=floor_s, n=reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    from seldon_core_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    floor_s = _dispatch_floor()
    out = {"dispatch_floor_ms": round(floor_s * 1e3, 2)}

    bw = measure_hbm_bw(floor_s, gib=0.125 if args.smoke else 1.0)
    out["hbm_bw_measured_gbs"] = round(bw / 1e9, 1)

    if args.smoke:
        B, KV, G, hd, L = 4, 4, 4, 64, 128
        reps = 16
    else:
        B, KV, G, hd, L = 256, 4, 4, 64, 640  # L a lane multiple
        reps = 512

    for layout in ("nn", "nt"):
        for dt, tag in ((jnp.bfloat16, "bf16"), (jnp.int8, "int8")):
            t = attn_time(B, KV, G, hd, L, floor_s, reps, layout, dt)
            el = 1 if dt == jnp.int8 else 2
            nbytes = 2 * B * KV * L * hd * el
            out[f"attn_ms_{layout}_{tag}"] = round(t * 1e3, 4)
            out[f"attn_gbs_{layout}_{tag}"] = round(nbytes / t / 1e9, 1)

    for dt, tag in ((jnp.bfloat16, "bf16"), (jnp.int8, "int8")):
        t = dus_time(B, KV, hd, L, floor_s, reps, dt)
        out[f"dus_us_{tag}"] = round(t * 1e6, 2)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
