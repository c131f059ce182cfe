#!/usr/bin/env python3
"""Time ONE expert layer's feed-forward ON THE CHIP both ways it can be
had (``parallel/moe.py``): the two grouped matmuls with the activation
between them (``_gmm`` -> ``_act`` -> ``_gmm``, three device ops) against
the one fused call (``_experts_fused``), at the sorted-pick counts a
program brings.  The table in ``moe.py`` beside ``fused_supported`` is
made from this; ``bench/tools/gmm_tiles.py`` times the two matmuls alone.

    python3 scripts/experts_fused_time.py \
        --form sdar-30b-a3b=128,128,2048,768,8,silu \
        --shapes 2048:144,2048:256,1024:72,1024:128 \
        --out chiprun_out/experts_fused.json

A form is ``name=E,held,D,F,k,act``: the router's ``E`` experts of which
``held`` are here, ``D`` x ``F`` matrices, ``k`` picks a token, ``silu``
(gate and up side by side) or ``relu2`` (no gate, the up matrix stored [F,
D]).  A shape is ``M:tokens`` as in gmm_tiles: ``M`` sorted picks of which
``tokens`` real tokens' are real, each token's picks distinct experts
drawn uniformly from the seed; a pick of an expert that is not held and
the picks of the other rows sort behind every group.  For each it prints
the milliseconds of either way (the median of ``--reps`` timed batches of
``--calls`` calls dispatched back to back, the host waiting once a batch),
the least time for the bytes of the experts hit, and how far the two
results lie apart over the real rows (rms of the difference over rms).
Fails off the chip: a CPU timing is no device metric."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "bench"))    # lib.peaks: the one table


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--form", action="append", required=True,
                    help="name=E,held,D,F,k,act")
    ap.add_argument("--shapes", action="append", required=True,
                    help="M:tokens,... (one a --form, in order)")
    ap.add_argument("--tiles", default="",
                    help="row tiles to force, e.g. 64,128 (default: the "
                    "program's own choice)")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib.peaks import peaks_for
    from seldon_core_tpu.parallel import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs the chip, found {dev.platform}")
    bandwidth = peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    key = jax.random.key(args.seed)
    rng = np.random.default_rng(args.seed)
    tiles = ("_ROW_TILE", "_ROW_TILE_WIDE", "_WIDE_FROM", "_FUSED_ROW_TILE",
             "_FUSED_ROW_TILE_NARROW")
    own = {knob: getattr(moe, knob) for knob in tiles}
    table = []

    def timed(fn, *operands):
        fn(*operands).block_until_ready()
        took = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = fn(*operands)
            out.block_until_ready()
            took.append((time.perf_counter() - t0) / args.calls)
        return statistics.median(took) * 1e3, out

    for form, shapes in zip(args.form, args.shapes):
        name, spec = form.split("=")
        E, held, D, F, k = (int(n) for n in spec.split(",")[:5])
        gated = spec.split(",")[5] == "silu"
        bf16 = jnp.bfloat16
        w_up = (jax.random.normal(
            key, (held, D, 2 * F) if gated else (held, F, D), bf16)
            * jnp.asarray(D ** -0.5, bf16))
        w_down = (jax.random.normal(jax.random.fold_in(key, 1),
                                    (held, F, D), bf16)
                  * jnp.asarray(F ** -0.5, bf16))
        expert_bytes = (w_up[0].size + w_down[0].size) * 2
        for shape in shapes.split(","):
            M, tokens = (int(n) for n in shape.split(":"))
            picks = np.full((M,), held, np.int64)
            for t in range(tokens):
                picks[t * k:(t + 1) * k] = np.minimum(
                    rng.choice(E, k, replace=False), held)
            sizes = jnp.asarray(np.bincount(picks, minlength=held + 1)[:held],
                                jnp.int32)
            real, hit = int(sizes.sum()), int(np.count_nonzero(sizes))
            x = jax.random.normal(jax.random.fold_in(key, 2), (M, D), bf16)
            for tm in [int(t) for t in args.tiles.split(",") if t] or [0]:
                # the row tile of either way forced, whatever M, or their own
                for knob in tiles:
                    setattr(moe, knob, (0 if knob == "_WIDE_FROM" else tm)
                            if tm else own[knob])

                def pair(x, w_up, w_down, sizes):
                    up = moe._gmm(x, w_up, sizes, transposed=not gated)
                    return moe._gmm(moe._act(up, gated).astype(x.dtype),
                                    w_down, sizes)

                def fused(x, w_up, w_down, sizes):
                    return moe._experts_fused(x, w_up, w_down, sizes,
                                              gated=gated)

                line = {"form": name, "M": M, "tokens": tokens,
                        "real_picks": real, "experts_hit": hit,
                        "tm": tm or moe._fused_rows(M),
                        "least_ms": hit * expert_bytes / bandwidth * 1e3}
                line["pair_ms"], a = timed(jax.jit(pair), x, w_up, w_down,
                                           sizes)
                line["fused_ms"], b = timed(jax.jit(fused), x, w_up, w_down,
                                            sizes)
                line["fused_over_pair"] = line["fused_ms"] / line["pair_ms"]
                a, b = np.asarray(a[:real]), np.asarray(b[:real])
                line["apart_rms"] = float(
                    np.sqrt(np.mean((a - b) ** 2) / np.mean(a ** 2)))
                print(json.dumps(line), flush=True)
                table.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "table": table}, f,
                      indent=1)


if __name__ == "__main__":
    main()
