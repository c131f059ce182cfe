"""Per-op attribution of one max-batch decode step — where do the
milliseconds actually go, per quant mode?

Round 4's two-tier cache fixed the carry-mutation pathology, but the
bench still shows only ~47% HBM-bandwidth utilization at B=256 bf16 and
the int8-KV path captures ~1.2x of a theoretical ~1.6x stream cut.  The
open question is the residual: ~half of every step is NOT the cache
stream.  This probe answers it with the device profiler: trace one dispatch of the NEW-step decode scan per mode
(bf16 / int8 KV / int8 weights+KV), aggregate TPU op durations by
fusion name, and print the top ops per step.

Output: one JSON object with, per mode, total step ms and the top-N ops
as (name, us_per_step, pct).  Run on the TPU box:
    python scripts/probe_step_profile.py [--smoke] [--top 30]
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import gzip
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp





def _trace_events(trace_dir: str):
    """Load the newest perfetto trace under ``trace_dir`` and yield
    (name, dur_us, bytes_accessed, hlo_category, long_name) for complete
    events on TPU device tracks."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise RuntimeError(f"no trace written under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # device tracks: pid whose process_name metadata mentions the TPU
    device_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = e.get("args", {}).get("name", "")
            if "TPU" in name or "/device:" in name:
                device_pids.add(e.get("pid"))
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            a = e.get("args", {})
            yield (e.get("name", "?"), float(e.get("dur", 0.0)),
                   int(a.get("bytes_accessed", 0) or 0),
                   a.get("hlo_category", ""), a.get("long_name", ""))


def _aggregate(events, top):
    totals = {}
    for name, dur, nbytes, cat, long_name in events:
        # container spans (whole-program, while-loop bodies) nest the
        # leaf fusions on the same track — counting them double-bills
        if name.startswith("jit_") or name.startswith("while"):
            continue
        t = totals.setdefault(
            name, {"us": 0.0, "n": 0, "bytes": 0, "cat": cat, "hlo": ""})
        t["us"] += dur
        t["n"] += 1
        t["bytes"] += nbytes
        if long_name and not t["hlo"]:
            t["hlo"] = long_name[:220]
    items = sorted(totals.items(), key=lambda kv: -kv[1]["us"])
    grand = sum(t["us"] for t in totals.values())
    grand_bytes = sum(t["bytes"] for t in totals.values())
    return grand, grand_bytes, [
        {"op": k, "us": round(t["us"], 1), "n": t["n"],
         "mb": round(t["bytes"] / 1e6, 2), "cat": t["cat"],
         "pct": round(100 * t["us"] / grand, 1), "hlo": t["hlo"]}
        for k, t in items[:top]
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--modes", default="bf16,int8kv,int8both")
    args = ap.parse_args()

    from seldon_core_tpu.models.generate import (
        _chunk_step, init_cache, init_chunk, prefill)
    from seldon_core_tpu.models.transformer import LMConfig, lm_init
    from seldon_core_tpu.ops.quant import quantize_lm_params

    if args.smoke:
        cfg = LMConfig(vocab=1024, d_model=256, n_heads=8, n_layers=4,
                       d_ff=1024)
        B, S, NEW = 8, 128, 16
    else:
        cfg = LMConfig(vocab=32768, d_model=1024, n_heads=16, n_layers=12,
                       d_ff=4096, n_kv_heads=4)
        B, S, NEW = 256, 512, 64

    params = lm_init(jax.random.key(0), cfg)
    qparams = quantize_lm_params(params)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, size=(B, S)),
        jnp.int32,
    )

    out = {"B": B, "S": S, "NEW": NEW, "modes": {}}
    for mode in args.modes.split(","):
        mcfg = {
            "bf16": cfg,
            "int8kv": dataclasses.replace(cfg, kv_quant="int8"),
            "int8both": dataclasses.replace(cfg, quant="int8",
                                            kv_quant="int8"),
        }[mode]
        ps = qparams if mcfg.quant == "int8" else params
        main = init_cache(mcfg, B, S)
        logits, main = jax.jit(
            lambda p, t, c, _c=mcfg: prefill(p, t, c, _c, use_flash=True)
        )(ps, toks, main)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        chunk = init_chunk(mcfg, B, NEW)
        carry = (first, main, chunk, jnp.int32(S), jnp.int32(0),
                 jax.random.key(0))
        step = jax.jit(
            lambda p, tok, m, c, nm, used, key, _c=mcfg: _chunk_step(
                p, tok, m, c, nm, used, key, _c, NEW, 0.0, main_full=True,
            )
        )
        jax.block_until_ready(step(ps, *carry))  # compile outside trace
        tdir = tempfile.mkdtemp(prefix=f"prof_{mode}_")
        t0 = time.perf_counter()
        with jax.profiler.trace(tdir):
            jax.block_until_ready(step(ps, *carry))
        wall = time.perf_counter() - t0
        grand_us, grand_bytes, top_ops = _aggregate(
            _trace_events(tdir), args.top)
        for op in top_ops:
            op["us_per_step"] = round(op.pop("us") / NEW, 1)
            op["mb_per_step"] = round(op.pop("mb") / NEW, 2)
        out["modes"][mode] = {
            "wall_ms": round(wall * 1e3, 1),
            "device_ms_total": round(grand_us / 1e3, 2),
            "device_ms_per_step": round(grand_us / 1e3 / NEW, 3),
            "bytes_per_step_mb": round(grand_bytes / 1e6 / NEW, 1),
            "top_ops": top_ops,
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
