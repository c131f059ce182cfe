#!/usr/bin/env python3
"""Time the expert layer's grouped matmul (``parallel/moe.py _gmm``, the
megablox kernel under the repo's tiles) ON THE CHIP at the sorted-pick
counts a decode program brings, under each row tile: the table beside
``_ROW_TILE`` / ``_ROW_TILE_WIDE`` / ``_WIDE_FROM`` in that file is made
from this.

    python3 bench/tools/gmm_tiles.py --config sdar-30b-a3b \
        --shapes 2048:144,1024:72,1024:128,512:64 --tiles 64,128 \
        --out chiprun_out/gmm_tiles.json

A shape is ``M:tokens``: ``M`` sorted picks a call brings (padded rows x
positions x picks a token) of which ``tokens`` real tokens' are real, each
token's picks distinct experts drawn uniformly from the seed; the other
picks sort behind every group, as a pad's do.  For each shape and row tile
it prints the milliseconds of the gate|up and of the down matmul (the
median of ``--reps`` timed batches of ``--calls`` calls dispatched back to
back, the host waiting once a batch) beside the least time the chip could
take for the bytes of the experts hit.  Nothing here is part of a run, and
it fails off the chip: a CPU timing is no device metric."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--shapes", required=True, help="M:tokens,...")
    ap.add_argument("--tiles", default="64,128")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib.manifest import Manifest, arch_module
    from lib.peaks import peaks_for
    from seldon_core_tpu.parallel import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs the chip, found {dev.platform}")
    peak = peaks_for(dev.device_kind)
    doc = Manifest().config(args.config)
    # the expert stack's sizes are the architecture's to name
    size = arch_module(BENCH, doc, "needs").sizes(doc)
    D, F, E, k = (size[n] for n in "DFEk")
    key = jax.random.key(args.seed)
    mats = {"gate_up": jax.random.normal(key, (E, D, 2 * F), jnp.bfloat16),
            "down": jax.random.normal(key, (E, F, D), jnp.bfloat16)}
    rng = np.random.default_rng(args.seed)
    table = []
    for shape in args.shapes.split(","):
        M, tokens = (int(n) for n in shape.split(":"))
        picks = np.full((M,), E, np.int64)
        for t in range(tokens):
            picks[t * k:(t + 1) * k] = rng.choice(E, k, replace=False)
        sizes = jnp.asarray(np.bincount(picks, minlength=E + 1)[:E],
                            jnp.int32)
        hit = int(np.count_nonzero(np.asarray(sizes)))
        for tm in (int(t) for t in args.tiles.split(",")):
            # the row tile the kernel takes, forced whatever M
            moe._ROW_TILE_WIDE, moe._WIDE_FROM = tm, 0
            line = {"M": M, "tokens": tokens, "experts_hit": hit, "tm": tm}
            for name, w in mats.items():
                x = jax.random.normal(key, (M, w.shape[1]), jnp.bfloat16)
                fn = jax.jit(lambda x, w, s: moe._gmm(x, w, s))
                fn(x, w, sizes).block_until_ready()
                took = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    for _ in range(args.calls):
                        out = fn(x, w, sizes)
                    out.block_until_ready()
                    took.append((time.perf_counter() - t0) / args.calls)
                least = hit * w[0].size * 2 / (peak["hbm_bytes_per_s"])
                line[name + "_ms"] = statistics.median(took) * 1e3
                line[name + "_least_ms"] = least * 1e3
            print(json.dumps(line), flush=True)
            table.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "config": args.config,
                       "table": table}, f, indent=1)


if __name__ == "__main__":
    main()
