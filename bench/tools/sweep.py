#!/usr/bin/env python3
"""Find an ``.r80`` cell's knee and rate: ONE engine, the cell's warm-up
ladder, then every offered rate once per seed (the schedule is the same,
the token ids differ: repeats), each for ``--seconds``.

    python3 bench/tools/sweep.py --workload <cell> --rates 3,3.5,4,4.5,5 \
        --seeds 11,12 --seconds 40 --out chiprun_out/sweep.<cell>.json

Rule: the knee is the highest rate at which, under EVERY seed, every
measured request finished inside the step and the in-flight count was not
still growing at the end; it is searched upward and stops at the first
rate that fails.  The cell file then takes 0.8 x knee (two significant
digits).  Tails are over all requests sent, a failed one charged at the
step's end (lib/client.py), so a rate that loses requests shows it.

Not part of a run: it writes nothing the benchmark reads."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run as bench_run  # noqa: E402
from lib.arith import percentile  # noqa: E402
from lib.engine import compile_counters  # noqa: E402


def growing(samples: list, span: float) -> bool:
    """In flight over the last two fifths of the arrival span against the
    two fifths before (the first fifth is the ramp from an idle engine):
    still growing if it rose by more than 30% and two rows.  (A rule on
    quarters and a fifth called 9.9 rows against 9.6 growth, my chip run,
    PR 23.)"""
    early = [n for t, n in samples if 0.2 * span <= t < 0.6 * span]
    late = [n for t, n in samples if 0.6 * span <= t < span]
    if not early or not late:
        return False
    a, b = sum(early) / len(early), sum(late) / len(late)
    return b > 1.3 * a + 2.0


async def sweep(run, rates: list, seeds: list, seconds: float) -> dict:
    await run.read_device()
    await run.ladder()
    await run.soak()
    table = []
    for rate in rates:
        run.cell = {**run.cell, "arrivals": {"kind": "open", "rate": rate}}
        for seed in seeds:
            await run.wait_idle()
            c0 = compile_counters(await run.get("/stats"))
            samples: list = []
            recs = await run.offer(run.plan(seed, seconds), seconds, samples)
            c1 = compile_counters(await run.get("/stats"))
            meas = [r for r in recs if r["measured"]]
            span = seconds - min(run.cell["drain_s"], seconds / 2)
            mid = [n for t, n in samples if 0.25 * span <= t < span]
            row = {"rate": rate, "seed": seed, "sent": len(meas),
                   "finished": sum(r["ok"] for r in meas),
                   "inflight_mean": sum(mid) / max(len(mid), 1),
                   "inflight_growing": growing(samples, span),
                   "late_p95_ms": percentile(
                       [r["late_ms"] for r in meas
                        if r["late_ms"] is not None], 95),
                   "compiles_in_step": c1["compiles"] - c0["compiles"]}
            for series in ("ttft_ms", "tpot_ms"):
                values = [r[series] for r in meas if r[series] is not None]
                for q in (50, 90, 99):
                    row[f"{series[:4]}_p{q}"] = percentile(values, q)
            table.append(row)
            bench_run.say("[sweep] " + json.dumps(row))
        if any(r["finished"] < r["sent"] or r["inflight_growing"]
               for r in table if r["rate"] == rate):
            break    # the first rate that fails ends the search
    knee = None
    for rate in rates:
        rows = [r for r in table if r["rate"] == rate]
        if all(r["finished"] == r["sent"] and not r["inflight_growing"]
               for r in rows):
            knee = rate
        else:
            break
    genperf = await run.get("/genperf")
    return {"cell": run.cell_name, "device": run.device, "knee": knee,
            "seconds": seconds, "seeds": seeds, "table": table,
            "deployment": run.dep,
            "scheduler": {k: v for k, v in (genperf.get("scheduler") or {}
                                            ).items()
                          if k != "sequence_ledger"}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="11,12")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=os.path.dirname(BENCH_DIR))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    args.seed, args.trace = 0, 9   # run directory <cell>.0.t9
    run = bench_run.Run(args)
    try:
        run.boot()
        result = asyncio.run(sweep(
            run, sorted(float(r) for r in args.rates.split(",")),
            [int(s) for s in args.seeds.split(",")], args.seconds))
    finally:
        if run.engine is not None:
            run.engine.stop()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: result[k] for k in ("cell", "device", "knee")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
