#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the engine a user runs (``python -m
seldon_core_tpu.runtime.engine_main --file <deployment>``) as a child, at
the cell's configuration and scheduler settings, drives the cell's traffic
over the engine's own HTTP lane (SSE on ``/api/v0.1/generate/stream``) and
prints ONE JSON object as the last line of stdout (bench/README.md says
what it holds).  This parent never imports JAX: the chip belongs to the
engine, then to the numerics child.  With no accelerator the command
fails; it does not fall back.

``--root`` (a directory holding BENCHMARK.json and bench/) and
``--allow-cpu`` exist for the CPU rehearsal and the tests only: a line
from such a run names ``platform: cpu`` and is no device measurement."""

from __future__ import annotations

T_PROCESS_START = __import__("time").monotonic()

import argparse
import asyncio
import glob
import importlib
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from lib import buckets, client, sample, traffic  # noqa: E402
from lib.arith import percentile  # noqa: E402
from lib.engine import (  # noqa: E402
    Engine,
    EngineFailure,
    cache_env,
    compile_counters,
    deployment_doc,
    engine_env,
    run_child,
    unit_spec,
)
from lib.manifest import Manifest, reserved_ids  # noqa: E402

PROBE_LEN, PROBE_NEW = 40, 16


_DEVICE = ["device not read yet"]


def say(msg: str) -> None:
    """Every line names the device it is about."""
    print(f"{msg}  [{_DEVICE[0]}]" if msg.startswith("[") else msg,
          flush=True)


class Run:
    """Everything one invocation knows: the cell's data files, where it
    writes, and the engine once it is up."""

    def __init__(self, args):
        self.args = args
        self.man = Manifest(args.root)
        self.repo = self.man.root
        if not os.path.isdir(os.path.join(self.repo, "seldon_core_tpu")):
            raise SystemExit(
                f"{self.repo} holds no seldon_core_tpu/: the benchmark "
                "drives the program, it does not contain it")
        self.cell_name = args.workload
        self.cell = self.man.cell(args.workload)
        self.config = self.man.config(self.cell["config"])
        self.mix = self.man.mix(self.cell["mix"])
        self.dep = self.man.deployment(self.cell, self.config)
        self.caps = buckets.caps(self.mix)
        self.vocab = self.config["vocab_size"]
        self.reserved = reserved_ids(self.config)
        self.out_dir = os.path.join(self.man.bench, "out")
        self.run_dir = os.path.join(
            self.out_dir, f"{self.cell_name}.{args.seed}.t{args.trace}")
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        self.platforms = ["tpu"] + (["cpu"] if args.allow_cpu else [])
        self.notes: list = []       # reasons a run is not correct
        self.engine = None
        self.device = None

    # -- files and children -------------------------------------------------

    def write(self, name: str, doc) -> str:
        path = os.path.join(self.run_dir, name)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return path

    def child_spec(self, **extra) -> dict:
        return {"repo": self.repo, "platforms": self.platforms,
                "bench_dir": self.man.bench, "config": self.config,
                "unit": unit_spec(self.config, self.dep, self.args.seed,
                                  self.caps["max_out"]),
                "deployment": self.dep, **extra}

    def numerics_spec(self) -> dict:
        """What the numerics child is handed: the judged batch
        (lib/sample.py) at the prompt lengths of this window's measured
        requests, token ids from the seed."""
        reqs = self.requests(self.args.seconds)
        prompts = [r.prompt_len for r in reqs if r.measured]
        return self.child_spec(
            sample=sample.plan(prompts or [r.prompt_len for r in reqs],
                               self.dep, self.caps["max_positions"]),
            sample_seed=self.args.seed % 9973)

    def child(self, which: str, spec: dict, timeout: float) -> dict:
        path = self.write(f"{which}_spec.json", spec)
        return run_child(
            self.repo,
            [os.path.join(BENCH_DIR, "lib", "children.py"), which, path],
            cache_env(self.repo), timeout)

    def boot(self) -> None:
        dep_path = self.write("deployment.json", deployment_doc(
            self.config, self.dep, self.args.seed, self.caps["max_out"]))
        self.profile_dir = os.path.join(self.run_dir, "profile")
        self.engine = Engine(
            self.repo, dep_path,
            {**engine_env(self.dep, self.profile_dir),
             **cache_env(self.repo)},
            os.path.join(self.run_dir, "engine.log"))
        say(f"[setup] {self.engine.up_line} ({self.engine.boot_s:.1f}s)")

    # -- engine documents ----------------------------------------------------

    async def get(self, path: str) -> dict:
        status, doc = await client.http_json(self.engine.port, "GET", path)
        if status != 200 or not isinstance(doc, dict):
            raise EngineFailure(f"GET {path} -> {status}: {str(doc)[:300]}")
        return doc

    async def read_device(self) -> None:
        perf = await self.get("/perf")
        self.device = {"platform": perf["device"]["platform"],
                       "kind": perf["device"]["device_kind"],
                       "count": len(perf.get("hbm") or []) or 1}
        _DEVICE[0] = "{platform} / {kind} / {count}".format(**self.device)
        if self.device["platform"] not in self.platforms:
            raise EngineFailure(
                f"the engine runs on {self.device}: no accelerator")
        if (self.device["platform"] == "tpu"
                and self.device["count"] != self.cell["chips"]):
            raise EngineFailure(
                f"the cell needs {self.cell['chips']} chip(s), the engine "
                f"sees {self.device['count']}")

    async def wait_idle(self, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            g = (await self.get("/stats"))["genserver"]
            if g["inflight_sequences"] + g["waiting_sequences"] == 0:
                return
            await asyncio.sleep(0.05)
        raise EngineFailure("the engine did not go idle")

    # -- set-up traffic ------------------------------------------------------

    async def ladder(self) -> None:
        """One request per (row count, ladder length): between them they
        take every (rows, chunk, blocks) bucket the cell can reach."""
        rows = buckets.ladder_rows(self.dep, self.caps)
        n = 0
        for b in buckets.row_buckets(self.dep["slots"]):
            for length, max_new in rows:
                body = client.rows_body(
                    [self.tokens(7, n * 64 + r, length)
                     for r in range(b)], max_new, self.dep["span"])
                rec = await client.stream_once(
                    self.engine.port, body, time.monotonic, 900.0)
                toks = rec["tokens"] or []
                if not (rec["done"] and len(toks) == b
                        and all(len(t) == max_new for t in toks)):
                    raise EngineFailure(
                        f"ladder request {b}x{length} failed: {rec['error']}"
                        f" status {rec['status']}")
                n += 1
        say(f"[setup] ladder: {n} requests")

    async def probe(self) -> list:
        body = client.stream_body(
            self.tokens(11, 0, PROBE_LEN),
            PROBE_NEW, self.dep["span"])
        rec = await client.stream_once(self.engine.port, body,
                                       time.monotonic, 300.0)
        if not rec["done"]:
            raise EngineFailure(f"probe request failed: {rec}")
        return rec["tokens"][0]

    def tokens(self, seed: int, index: int, length: int) -> list:
        return traffic.prompt_tokens(seed, index, length, self.vocab,
                                     self.reserved)

    def bodies(self, requests: list, seed: int) -> list:
        return [client.stream_body(
            self.tokens(seed, r.index, r.prompt_len),
            r.out_len, self.dep["span"]) for r in requests]

    def requests(self, seconds: float) -> list:
        return traffic.open_loop(
            self.mix, self.cell["arrivals"]["rate"], seconds,
            min(self.cell["drain_s"], seconds / 2))

    def plan(self, seed: int, seconds: float) -> dict:
        """The cell's schedule for ``seconds`` with token ids from ``seed``,
        bodies encoded ahead of the window so the generator does little
        inside it."""
        reqs = self.requests(seconds)
        return {"requests": reqs, "bodies": self.bodies(reqs, seed)}

    async def offer(self, plan: dict, seconds: float, samples=None) -> list:
        return await client.run_open_loop(
            self.engine.port, plan["requests"], plan["bodies"],
            self.vocab, time.monotonic(), seconds, samples, self.reserved)

    async def soak(self) -> None:
        """The cell's own traffic from a warm-up seed until the compile
        counters stand still over a whole stretch."""
        for attempt in range(4):
            before = compile_counters(await self.get("/stats"))
            await self.offer(
                self.plan(1000003 + attempt, self.cell["soak_s"]),
                self.cell["soak_s"])
            await self.wait_idle()
            after = compile_counters(await self.get("/stats"))
            if after["compiles"] == before["compiles"]:
                return
            say(f"[setup] soak {attempt}: "
                f"{after['compiles'] - before['compiles']} compiles, again")
        self.notes.append("compile counters never stood still in the soak")

    # -- the traced span -----------------------------------------------------

    async def trace_start(self, t0: float, seconds: float, box: dict
                          ) -> None:
        """Open the profiler window for the last ``trace_s`` seconds of the
        measured window.  Load never stops, so the end of the window is as
        loaded as its middle; and stopping a trace freezes the engine for
        several times the traced span (19 s after 3 s, my chip run, PR 23),
        so the stop comes after the window, where it spoils no request."""
        trace_s = float(self.cell.get("trace_s", 1.5))
        await asyncio.sleep(
            max(t0 + seconds - trace_s - time.monotonic(), 0))
        status, doc = await client.http_json(
            self.engine.port, "POST", "/profile/start",
            {"duration_s": trace_s + 60.0, "logdir": "window"})
        if status != 200:
            self.notes.append(f"/profile/start -> {status}: {doc}")
            return
        box["start"] = time.monotonic() - t0

    async def trace_stop(self, t0: float, seconds: float, box: dict) -> None:
        if "start" not in box:
            return
        box["stop"] = time.monotonic() - t0
        await client.http_json(self.engine.port, "POST", "/profile/stop",
                               {}, timeout=180.0)
        box["stopped"] = time.monotonic() - t0

    def traced_account(self, records: list, box: dict) -> dict:
        """What the harness saw in flight during the traced span: mean
        decoding rows and their live positions, and the prompt tokens
        prefilled inside it."""
        a, b = box["start"], box["stop"]
        rows = live = 0.0
        steps = 100
        for k in range(steps):
            t = a + (b - a) * (k + 0.5) / steps
            for r in records:
                if r["t_first"] is None or r["t_last"] is None:
                    continue
                # a stream the window's end cut was still decoding then
                last = b if r["n_out"] < r["out_len"] else r["t_last"]
                if r["t_first"] <= t < last:
                    frac = (t - r["t_first"]) / (last - r["t_first"])
                    rows += 1
                    live += r["prompt_len"] + frac * r["n_out"]
        # a prompt's chunks run between its send and its first token: it
        # counts for the share of that stretch that lies inside the span
        # (a request with no first token yet is left out: undercounted)
        pre_tok = pre_att = 0.0
        for r in records:
            if r["t_first"] is None or r["t_sent"] is None:
                continue
            over = min(b, r["t_first"]) - max(a, r["t_sent"])
            if over > 0:
                f = over / max(r["t_first"] - r["t_sent"], 1e-9)
                pre_tok += f * r["prompt_len"]
                pre_att += f * r["prompt_len"] * (r["prompt_len"] + 1) / 2
        return {
            "decode_rows_mean": rows / steps,
            "decode_live_positions_mean": live / steps,
            "prefill_tokens": pre_tok, "prefill_attended": pre_att,
        }

    def reduce_trace(self) -> dict:
        found = glob.glob(os.path.join(self.profile_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            self.notes.append("the profile window left no trace file")
            return {}
        return run_child(
            self.repo,
            [os.path.join(BENCH_DIR, "lib", "trace_reduce.py"), found[0]],
            {"JAX_PLATFORMS": "cpu"}, 300.0)


def tails(sent: list) -> dict:
    """Medians and tails of TTFT and TPOT over every request SENT (a
    failed or unfinished one is charged at the window's end,
    lib/client.py); the manifest names the ones a cell is judged on."""
    out = {}
    for series in ("ttft", "tpot"):
        values = [r[series + "_ms"] for r in sent
                  if r[series + "_ms"] is not None]
        for q in (50, 90, 95):
            out[f"{series}_p{q}_ms"] = percentile(values, q)
    return out


def end_to_end(run: Run, records: list, seconds: float) -> dict:
    """The cell's end-to-end numbers from the load generator's records.
    ``out_tok_s`` (every output token delivered inside the window, per
    second of it) is recorded, not judged: PERF.md section 2 says why."""
    sent = [r for r in records if r["measured"]]
    ok = [r for r in sent if r["ok"]]
    out = tails(sent)
    out["out_tok_s"] = sum(r["n_out"] for r in records) / seconds
    return {"values": out, "attempted": len(sent),
            "failed": len(sent) - len(ok)}


async def drive(run: Run) -> dict:
    args = run.args
    await run.read_device()
    c_boot = compile_counters(await run.get("/stats"))
    phases = {"boot": run.engine.boot_s}
    t = time.monotonic()
    await run.ladder()
    phases["ladder"] = time.monotonic() - t
    probe_before = await run.probe()
    t = time.monotonic()
    await run.soak()
    await run.wait_idle()
    phases["soak"] = time.monotonic() - t
    stats_before = await run.get("/stats")
    genperf_before = await run.get("/genperf")
    c0 = compile_counters(stats_before)
    say(f"[setup] compiles before the window: {c0} (at boot {c_boot}); "
        f"seconds by phase: {({k: round(v, 1) for k, v in phases.items()})}")
    box: dict = {}
    samples: list = []
    plan = run.plan(args.seed, args.seconds)
    t_window = time.monotonic()
    setup_s = t_window - T_PROCESS_START
    tracer = None
    if args.trace:
        tracer = asyncio.ensure_future(
            run.trace_start(t_window, args.seconds, box))
    records = await run.offer(plan, args.seconds, samples)
    if tracer is not None:
        await tracer
    stats_after = await run.get("/stats")
    genperf_after = await run.get("/genperf")
    await run.trace_stop(t_window, args.seconds, box)
    c1 = compile_counters(stats_after)
    if c1["compiles"] != c0["compiles"]:
        run.notes.append(
            f"{c1['compiles'] - c0['compiles']} backend compiles inside "
            "the measured window")
    await run.wait_idle()
    probe_after = await run.probe()
    probe_moved = int(probe_after != probe_before)
    if probe_moved:
        run.notes.append("the probe request's tokens changed over the window")
    perf = await run.get("/perf")
    hbm_peak = max((row.get("peak_bytes_in_use", 0)
                    for row in perf.get("hbm") or []), default=0)
    return {
        "records": records, "setup_s": setup_s, "box": box,
        "samples": samples, "stats_before": stats_before,
        "stats_after": stats_after, "genperf_before": genperf_before,
        "genperf_after": genperf_after, "hbm_peak": hbm_peak,
        "compiles": {"boot": c_boot, "before": c0, "after": c1},
        "probe_moved": probe_moved,
        "phases": phases,
        "chunk": genperf_after.get("adaptive_chunk"),
    }


def layer_metrics(run: Run, res: dict, trace: dict) -> tuple:
    """The cell's per-layer metrics, each from the reader its data file
    names; a reader with nothing to read leaves its metric out."""

    ctx = {
        "records": res["records"], "config": run.config,
        "bench_dir": run.man.bench,
        "deployment": run.dep, "device": run.device, "cell": run.cell,
        "genperf_before": res["genperf_before"],
        "genperf_after": res["genperf_after"],
        "stats_before": res["stats_before"],
        "stats_after": res["stats_after"],
        "trace": trace if trace.get("busy_s") else None,
        "traced": (run.traced_account(res["records"], res["box"])
                   if "stop" in res["box"] else None),
        "harness": {
            "prompt_tokens_first_token_in_window": float(sum(
                r["prompt_len"] for r in res["records"]
                if r["t_first"] is not None)),
        },
    }
    out = {}
    for entry in run.man.metrics_for(run.cell_name, "per_layer"):
        metric = run.man.layer_metric(entry["name"])
        reader = importlib.import_module(f"readers.{metric['reader']}")
        value = reader.read(metric, ctx)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out, ctx.get("bounds", {})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=os.path.dirname(BENCH_DIR))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    run = Run(args)
    try:
        run.boot()
        res = asyncio.run(drive(run))
    except (EngineFailure, OSError, asyncio.TimeoutError) as e:
        sys.stderr.write(f"run failed: {type(e).__name__}: {e}\n")
        if run.engine is not None:
            sys.stderr.write(run.engine.log_tail() + "\n")
        return 3
    finally:
        if run.engine is not None:
            run.engine.stop()
    assert "jax" not in sys.modules, "the parent imported JAX"
    t_after = time.monotonic()
    try:
        num = run.child("numerics", run.numerics_spec(), timeout=600)
        res["phases"]["numerics"] = time.monotonic() - t_after
        trace = run.reduce_trace() if args.trace else {}
        res["phases"]["after_engine"] = time.monotonic() - t_after
    except EngineFailure as e:
        sys.stderr.write(f"run failed: {e}\n")
        return 3
    if not num["verdict"]["ok"]:
        run.notes.append(f"numerics child: {num['verdict']}")
    if num["reserved_emitted"]:
        run.notes.append(f"numerics child: the round emitted "
                         f"{num['reserved_emitted']} ids the configuration "
                         "reserves")
    if num["device"] != run.device:
        run.notes.append(f"numerics ran on {num['device']}, the engine on "
                         f"{run.device}")
    e2e = end_to_end(run, res["records"], args.seconds)
    e2e["values"]["setup_s"] = res["setup_s"]
    bad_answers = [r for r in res["records"]
                   if r["status"] == 200 and not r["ok"]
                   and r["error"] is None]
    if bad_answers:
        run.notes.append(f"{len(bad_answers)} answers of the wrong length "
                         "or with ids out of range or reserved")
    # every number ``correct`` rests on, beside its limit: the benchmark's
    # contract asks every run to print them (read in the driver's run logs)
    pre, dec = num["verdict"]["prefill"], num["verdict"]["decode"]
    compared = "[correct] " + json.dumps({
        "rows_judged": [num["verdict"]["rows"], num["rows_offered"]],
        "chunks_per_row": num["chunks"],
        "prefill_max_abs_err": [pre["max"], pre["limit"]],
        "prefill_median_err": [pre["median"], pre["limit"]],
        "prefill_share_over": [pre["share"], pre["allowed"]],
        "decode_max_margin": [dec["max"], dec["limit"]],
        "decode_median_margin": [dec["median"], dec["limit"]],
        "decode_share_over": [dec["share"], dec["allowed"]],
        "compiles_in_window": [res["compiles"]["after"]["compiles"]
                               - res["compiles"]["before"]["compiles"], 0],
        "probe_moved": [res["probe_moved"], 0],
        "answers_wrong": [len(bad_answers), 0],
        # compared only where the configuration reserves an id
        **({"reserved_emitted": [num["reserved_emitted"], 0]}
           if run.reserved else {})})
    say(compared)
    device = {**run.device,
              "memory_peak_bytes": max(res["hbm_peak"],
                                       num["memory_peak_bytes"])}
    line = {"correct": not run.notes, "attempted": e2e["attempted"],
            "failed": e2e["failed"], "device": device}
    side = {"cell": run.cell_name, "seed": args.seed,
            "seconds": args.seconds, "device": device, "notes": run.notes,
            "end_to_end": e2e["values"], "numerics": num,
            "compiles": res["compiles"], "chunk": res["chunk"],
            "setup_phases_s": res["phases"],
            "inflight": res["samples"][-8:]}
    if args.trace:
        metrics, bounds = layer_metrics(run, res, trace)
        line["metrics"] = metrics
        if trace.get("busy_s"):
            line["device"]["busy_s"] = trace["busy_s"]
            line["device"]["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
        side.update(layer=metrics, bounds=bounds, trace=trace,
                    traced_span=res["box"])
    else:
        wanted = run.man.metrics_for(run.cell_name, "end_to_end")
        line["metrics"] = {
            m["name"]: {"value": e2e["values"][m["name"]], "unit": m["unit"]}
            for m in wanted if e2e["values"].get(m["name"]) is not None}
    with open(os.path.join(
            run.out_dir, f"{run.cell_name}.{args.seed}.json"), "w") as f:
        json.dump(side, f, indent=1)
    say(json.dumps({"device": device, "notes": run.notes,
                    "numerics_s": res["phases"]["numerics"],
                    "recorded": {k: v for k, v in e2e["values"].items()
                                 if k not in line["metrics"]}}))
    say(json.dumps(line))
    # and as the last line of standard error: a record that keeps only the
    # end of that still shows what a run that is not correct rested on
    sys.stderr.write(compared + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
