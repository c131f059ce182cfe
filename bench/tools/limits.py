#!/usr/bin/env python3
"""The readings a numerics limit is set from (PERF.md section 2: the PR
that sets or changes a limit brings them): for a cell, the judged batch of
a run of ``--seconds`` through the program over ``--seeds`` — every row's
``prefill_err`` and ``decode_margin`` — and, over ``--control-seeds``, the
control's: the plain reference on weights rounded to fp8 e4m3, one scale
a matrix, in the program's place.  ONE process holds the chip for all of
it (``lib/children.py limits``); this parent stays off JAX.

    python3 bench/tools/limits.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] --out chiprun_out/limits.<cell>.json

A limit belongs above the program's largest and below the control's
smallest, with room on both sides; a share of rows a configuration
allows over the limit (``numerics.discrete_share``) belongs above the
program's largest share, and the control's smallest has to read 1.0.

Not part of a run: it writes nothing the benchmark reads."""

from __future__ import annotations

import argparse
import json
import os
import sys
from statistics import median

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run as bench_run  # noqa: E402
from lib.engine import unit_spec  # noqa: E402


def summary(doc: dict, numerics: dict) -> dict:
    """Over all seeds and rows, as multiples of each seed's reference rms:
    the program's largest and the control's smallest, and the shares of
    rows over the limits."""
    out = {"rows": len(doc["lens"]), "chunks": doc["chunks"],
           "tolerance_rms": numerics["tolerance_rms"]}
    for side in ("program", "control"):
        seeds = [s for s in doc["seeds"] if side in s]
        if not seeds:
            continue
        rel = {k: [v / s["ref_logit_rms"] for s in seeds
                   for v in s[side][k]]
               for k in ("prefill_err", "decode_margin")}
        shares = {k: [s[side + "_verdict"][k]["share"] for s in seeds]
                  for k in ("prefill", "decode")}
        out[side] = {
            "seeds": len(seeds),
            **{k: {"min": min(v), "median": median(v), "max": max(v)}
               for k, v in rel.items()},
            **{k + "_share_over": [min(v), max(v)]
               for k, v in shares.items()}}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None,
                    help="default: every seed")
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=os.path.dirname(BENCH_DIR))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    args.seed, args.trace = seeds[0], 0
    run = bench_run.Run(args)
    if args.seconds is None:
        args.seconds = float(run.man.doc["run_seconds"])
    spec = run.numerics_spec()
    spec.update(
        seeds=seeds,
        control_seeds=(seeds if args.control_seeds is None else
                       [int(s) for s in args.control_seeds.split(",")]),
        units=[unit_spec(run.config, run.dep, s, run.caps["max_out"])
               for s in seeds])
    doc = run.child("limits", spec, timeout=3300)
    doc["summary"] = summary(doc, run.config["numerics"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f)
    print(json.dumps({"device": doc["device"], **doc["summary"]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
