"""Performance-observatory demo: a 3-node compiled ensemble graph served
under load, its ``GET /perf`` table dumped as a CI artifact.

Boots one engine over an AVERAGE_COMBINER of two MnistClassifier members
(3 graph nodes, one fused XLA program), drives a batch mix through the
REST handler so several batch-bucket executables compile and dispatch,
then writes:

    <out>/perf.json     the full /perf document — per-executable cost
                        features (FLOPs, bytes), compile time, latency
                        percentiles, MFU, roofline bound, HBM watermarks
    <out>/stats.json    the /stats snapshot (perf block included)

and prints a compact per-executable table.  Run via ``make perf-demo``
(CI uploads the artifact from a non-blocking lane, mirroring
``trace-demo``).  Everything is local and deterministic — no TPU
required; on the CPU backend the table is exactly the degraded-but-
honest shape operators see without a real chip (tiny MFU, bound:
overhead, ``memory_stats: null``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os

import numpy as np


def deployment() -> dict:
    return {
        "spec": {
            "name": "perf-demo",
            "predictors": [{
                "name": "p",
                "graph": {
                    "name": "ens",
                    "type": "COMBINER",
                    "implementation": "AVERAGE_COMBINER",
                    "children": [
                        {"name": "m0", "type": "MODEL"},
                        {"name": "m1", "type": "MODEL"},
                    ],
                },
                "components": [
                    {
                        "name": f"m{i}",
                        "runtime": "inprocess",
                        "class_path": "MnistClassifier",
                        "parameters": [
                            {"name": "hidden", "value": "64", "type": "INT"},
                            {"name": "seed", "value": str(i), "type": "INT"},
                        ],
                    }
                    for i in range(2)
                ],
            }],
        }
    }


async def run_demo(out_dir: str, n_requests: int) -> dict:
    from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
    from seldon_core_tpu.runtime.engine import EngineService
    from seldon_core_tpu.utils.tracing import TRACER

    TRACER.enable()  # dispatch traces feed the histogram exemplars
    spec = SeldonDeploymentSpec.from_json_dict(deployment())
    engine = EngineService(spec, max_batch=64, max_wait_ms=1.0)
    engine.prewarm([784])

    rng = np.random.default_rng(0)
    for i in range(n_requests):
        rows = int(rng.choice((1, 2, 4, 8)))
        payload = json.dumps(
            {"data": {"ndarray": rng.normal(size=(rows, 784)).tolist()}}
        )
        text, status = await engine.predict_json(payload)
        assert status == 200, text

    doc = engine.perf_document()
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "perf.json"), "w") as f:
        json.dump(doc, f, indent=1)
    with open(os.path.join(out_dir, "stats.json"), "w") as f:
        json.dump(engine.stats(), f, indent=1)
    await engine.close()
    return doc


def print_table(doc: dict) -> None:
    dev = doc["device"]
    if dev["peak_bf16_tflops"] is None:
        peak = "no peak in utils/chips.py (MFU/roofline columns empty)"
    else:
        peak = "peak %.0f TFLOP/s bf16, %.0f GB/s HBM" % (
            dev["peak_bf16_tflops"], dev["peak_hbm_gbs"])
    print("device: %s (%s)  %s"
          % (dev["device_kind"] or "?", dev["platform"] or "?", peak))
    cols = ("executable", "calls", "p50_ms", "p99_ms", "compile_s",
            "gflops", "mfu", "pred/meas", "bound")
    print(("%-28s %6s %8s %8s %9s %8s %10s %9s %9s") % cols)
    for r in doc["executables"]:
        print("%-28s %6d %8.3f %8.3f %9s %8s %10s %9s %9s" % (
            r["executable"][:28], r["calls"],
            r["latency_ms"]["p50"], r["latency_ms"]["p99"],
            "-" if r.get("compile_s") is None else "%.3f" % r["compile_s"],
            "-" if not r.get("flops") else "%.3f" % (r["flops"] / 1e9),
            "-" if r.get("mfu") is None else "%.2e" % r["mfu"],
            "-" if r.get("predicted_vs_measured") is None
            else "%.3g" % r["predicted_vs_measured"],
            r.get("bound") or "-",
        ))
    for h in doc.get("hbm", []):
        if h.get("memory_stats", "x") is None:
            print(f"hbm {h['device']}: no memory_stats (CPU backend)")
        else:
            print(
                "hbm %s: %.1f / %.1f GB in use (peak %.1f)"
                % (h["device"], h["bytes_in_use"] / 1e9,
                   h["bytes_limit"] / 1e9, h["peak_bytes_in_use"] / 1e9)
            )
    if "batching" in doc:
        print("pad overhead: %.2f%%" % doc["batching"]["pad_overhead_pct"])


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default="perf_demo")
    parser.add_argument("--requests", type=int, default=64)
    args = parser.parse_args(argv)
    doc = asyncio.run(run_demo(args.out, args.requests))
    print_table(doc)
    print(f"\nfull table: {args.out}/perf.json "
          f"(the GET /perf body; docs/operations.md runbook)")


if __name__ == "__main__":
    main()
