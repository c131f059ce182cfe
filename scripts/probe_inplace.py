"""Can a scan-carried KV chunk be updated IN PLACE? — dus vs Pallas
aliased write.

Every decode-step formulation tried so far pays a full copy of any
scan-carried buffer it mutates (~26 us per 8.4 MB per-layer chunk at
B=256, 0.4-1.0 ms/step across layers): XLA double-buffers while-loop
carries rather than proving the dynamic-update-slice dead-write-free.
This probe times three candidate escape hatches on the real chip, all
as `scan(64 steps)` over a [256, 4, 64, 64] bf16 buffer:

  a. baseline: read a slice of the buffer, then lax.dynamic_update_slice
     one slot (the serving pattern: attend over prefix, append);
  b. write-only: the dus without any read — does dead-read analysis
     alone unlock in-place?
  c. pallas: a one-slot writer kernel declared with
     input_output_aliases={0: 0} — explicit aliasing XLA cannot miss.

Prints one JSON line with us/step per variant.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp



from jax.experimental import pallas as pl


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


def _write_kernel(pos_ref, val_ref, buf_ref, out_ref, sem):
    # DMA val into the aliased output at column pos — the rest of the
    # buffer is untouched (in-place intent via input_output_aliases)
    t = pos_ref[0]
    from jax.experimental.pallas import tpu as pltpu

    copy = pltpu.make_async_copy(
        val_ref, out_ref.at[:, :, pl.dslice(t, 1), :], sem
    )
    copy.start()
    copy.wait()


def _pallas_write(buf, val, pos):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        _write_kernel,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
        input_output_aliases={2: 0},
    )(jnp.reshape(pos, (1,)).astype(jnp.int32), val, buf)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=64)
    args = ap.parse_args()
    floor_s = _dispatch_floor()

    B, KV, C, hd = 256, 4, 64, 64
    buf0 = jnp.zeros((B, KV, C, hd), jnp.bfloat16)
    val = jnp.ones((B, KV, 1, hd), jnp.bfloat16)
    q = jnp.ones((B, KV, 1, hd), jnp.bfloat16)

    def run(body):
        @jax.jit
        def prog(buf, q):
            def step(carry, t):
                buf, acc = carry
                buf, out = body(buf, q, t)
                return (buf, acc + out), ()
            (buf, acc), _ = jax.lax.scan(
                step, (buf, jnp.zeros((), jnp.float32)),
                jnp.arange(args.steps))
            return buf, acc
        jax.block_until_ready(prog(buf0, q))
        raws = []
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(prog(buf0, q))
            raws.append(time.perf_counter() - t0)
        raw = min(raws)
        return max(raw - floor_s, 0.05 * raw) / args.steps * 1e6

    def read_of(buf, q, t):
        # a data-dependent read over the buffer prefix (like attention)
        s = jnp.sum((buf * q).astype(jnp.float32))
        return s

    def a_read_dus(buf, q, t):
        out = read_of(buf, q, t)
        buf = jax.lax.dynamic_update_slice(
            buf, val + out.astype(jnp.bfloat16) * 0, (0, 0, t, 0))
        return buf, out

    def b_dus_only(buf, q, t):
        buf = jax.lax.dynamic_update_slice(buf, val, (0, 0, t, 0))
        return buf, jnp.float32(0)

    def c_pallas(buf, q, t):
        out = read_of(buf, q, t)
        buf = _pallas_write(buf, val + out.astype(jnp.bfloat16) * 0, t)
        return buf, out

    res = {
        "buffer_mb": round(buf0.size * 2 / 1e6, 1),
        "a_read_then_dus_us": round(run(a_read_dus), 1),
        "b_dus_only_us": round(run(b_dus_only), 1),
    }
    try:
        res["c_pallas_aliased_us"] = round(run(c_pallas), 1)
    except Exception as e:  # pallas lowering may reject this formulation
        res["c_pallas_error"] = str(e)[:300]
    print(json.dumps(res))


if __name__ == "__main__":
    main()
