"""Why does a 131 KB cache write cost ~200 us? — dynamic_update_slice
scaling probe.

probe_layout.py measured ~198 us per single-position dus into a
[256, 4, 640, 64] bf16 cache carried through a scan: ~40x the bytes
written even counting tile read-modify-write.  A decode step does
n_layers x 2 of these, which the layer-slope measurement says is the
dominant per-layer cost.  This probe pins the scaling law (buffer length,
batch, dtype, position axis), and times the candidate fix: a TWO-TIER
cache — the scan writes a chunk-sized ring buffer, attention reads
main-cache + chunk (concatenated scores), and the big buffer takes ONE
bulk write per chunk outside the scan.

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


def dus_chain(B, KV, hd, L, dtype, reps, floor_s):
    buf = jnp.zeros((B, KV, L, hd), dtype)
    blk = jnp.ones((B, KV, 1, hd), dtype)

    @jax.jit
    def chain(buf, blk):
        def body(c, _):
            b, pos = c
            b = jax.lax.dynamic_update_slice(b, blk, (0, 0, pos % L, 0))
            return (b, pos + 1), ()
        (bf, _), _ = jax.lax.scan(body, (buf, jnp.int32(0)), None,
                                  length=reps)
        return bf

    return _timed(chain, buf, blk, floor_s=floor_s, n=reps)


def dus_multi_chain(B, KV, hd, L, dtype, n_bufs, reps, floor_s):
    """n_bufs caches updated per iteration — the real decode shape (one
    k and one v per layer)."""
    bufs = [jnp.zeros((B, KV, L, hd), dtype) for _ in range(n_bufs)]
    blk = jnp.ones((B, KV, 1, hd), dtype)

    @jax.jit
    def chain(bufs, blk):
        def body(c, _):
            bs, pos = c
            bs = [
                jax.lax.dynamic_update_slice(b, blk, (0, 0, pos % L, 0))
                for b in bs
            ]
            return (bs, pos + 1), ()
        (bf, _), _ = jax.lax.scan(body, (bufs, jnp.int32(0)), None,
                                  length=reps)
        return bf[0]

    return _timed(chain, bufs, blk, floor_s=floor_s, n=reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    from seldon_core_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    floor_s = _dispatch_floor()
    out = {"dispatch_floor_ms": round(floor_s * 1e3, 2)}
    reps = 16 if args.smoke else 256
    KV, hd = 4, 64

    # scaling in L (buffer bytes) and B
    for B, L in ((256, 160), (256, 640), (256, 1280), (32, 640)):
        if args.smoke and (B, L) != (256, 640):
            continue
        t = dus_chain(B, KV, hd, L, jnp.bfloat16, reps, floor_s)
        out[f"dus_us_b{B}_L{L}"] = round(t * 1e6, 2)

    # many buffers per iteration (decode reality: 24 buffers)
    if not args.smoke:
        t = dus_multi_chain(256, KV, hd, 640, jnp.bfloat16, 8, reps, floor_s)
        out["dus8_us_each"] = round(t * 1e6 / 8, 2)

    # chunk-tier simulation: same write stream into a 64-slot ring buffer
    t = dus_chain(256 if not args.smoke else 8, KV, hd, 64, jnp.bfloat16,
                  reps, floor_s)
    out["dus_us_chunk64"] = round(t * 1e6, 2)

    # bulk merge cost: one 64-wide dus into the big cache (per chunk, so
    # amortized /64 per step)
    if not args.smoke:
        B, L = 256, 640
        buf = jnp.zeros((B, KV, L, hd), jnp.bfloat16)
        blk = jnp.ones((B, KV, 64, hd), jnp.bfloat16)

        @jax.jit
        def bulk(buf, blk, pos):
            return jax.lax.dynamic_update_slice(buf, blk, (0, 0, pos, 0))

        t = _timed(bulk, buf, blk, jnp.int32(512), floor_s=floor_s, n=1)
        out["bulk_merge_us"] = round(t * 1e6, 2)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
