#!/usr/bin/env python3
"""chip_smoke.py — the serving path, end to end, on a directly attached TPU.

The quickest proof that the system still starts on the chip: it drives the
main path once through the entry point a user runs,

    python -m seldon_core_tpu.runtime.engine_main --file <deployment>

as its own process, over HTTP, from this parent — which never imports JAX
(a chip belongs to one process at a time; the parent asserts it on exit).
One engine process at a time; every process started here is stopped here.

Phases (any failure: non-zero exit, the engine's log tail on stderr, no
result line):

  1. LM     examples/lm_d1024_deployment.json — the widest generator the
            repo serves, full width (d1024 L12 H16 kv4 ff4096 vocab 32768,
            bf16, seeded random weights) through engine -> GenLane ->
            GenServer -> BlockAllocator -> paged_forward /
            paged_decode_round.  One row of 37 tokens; one request of
            4 x 512; eight single-row requests of seeded lengths in
            [16, 700], each sent once the previous one is decoding (so
            later ones are admitted into a running decode batch); then,
            engine idle, the first request again and one SSE stream of it.
  2. graph  examples/ensemble4_deployment.json (4-member MNIST
            AVERAGE_COMBINER, the BASELINE.json workload): JSON
            predictions and one application/x-seldon-tensor frame over the
            native C++ lane with the Pallas fused-MLP kernel.
  3. numerics (a child process that owns the chip after both engines are
            gone): the bf16 paged pool against the dense forward at the LM
            phase's widths, flash_attention compiled against
            gqa_attention at B4 H16 KV4 S512 hd64, the fused MLP against
            XLA, and that jax.block_until_ready really fences.

``--tp N`` runs the LM phase only, with ``mesh_axes {"tp": N}`` added to
the deployment, on N chips.  Nothing else changes what is run.

What the engines are given explicitly, and why:

  ENGINE_DISPATCH_TIMEOUT_S=900   on a cold compile cache every new
      (rows, chunk, blocks) program compiles on the scheduler thread
      INSIDE a request; the 30 s default 504 budget would time those out.
  SELDON_TPU_GEN_PREFILL_CHUNK_MAX=128   pins the prefill chunk at its
      default floor.  The adaptive chunk ({128, 256, 512}) moves on
      measured tick walls, which would make the compiled program set —
      and, in bf16 over seeded random weights, the sampled tokens —
      depend on timing; the repeated request must take the SAME programs
      as the first to be comparable.
  ENGINE_PREWARM_WIDTHS=37 (LM) / 784 (graph), ENGINE_MAX_BATCH=64 (graph)
      exercise boot-time prewarm (a failed probe stops the boot) and
      bound the graph's pad buckets to 1..64.
  Everything else is the default: block 16, 1024 blocks, 64 slots, span 8.

The program set a cold run may compile (the scheduler pads rows and block
tables to powers of two; chunk is pinned to 128):

  prefill (B, 128, nblk):  (1,128,{1..64})  (4,128,{8,16,32})
  decode  (B, nblk):       (1,{2..64}) (2,{2..64}) (4,{2..64}) (8,{2..64})

— at most 7 + 3 + 4 x 6 = 34, of which a run takes roughly twenty.

Last stdout line on success, and only then:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chip_smoke_out")
LM_DEPLOYMENT = os.path.join(REPO, "examples", "lm_d1024_deployment.json")
GRAPH_DEPLOYMENT = os.path.join(
    REPO, "examples", "ensemble4_deployment.json")
SEED = 21

_ENGINES: list = []  # every engine process started; reaped at exit


class SmokeFailure(Exception):
    """A phase failed; the run exits non-zero."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------

def probe_device() -> dict:
    """The device as JAX reports it — asked of a CHILD that exits (and so
    releases the chip) before any engine starts."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    if out.returncode != 0:
        raise SmokeFailure(
            f"JAX found no usable device:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Engine:
    """One ``engine_main`` process, its log under chip_smoke_out/."""

    def __init__(self, name: str, deployment_path: str, env: dict,
                 boot_timeout_s: float = 900.0):
        self.name = name
        self.port = free_port()
        self.log_path = os.path.join(OUT_DIR, f"engine_{name}.log")
        self._log = open(self.log_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.runtime.engine_main",
             "--file", deployment_path, "--host", "127.0.0.1",
             "--rest-port", str(self.port),
             "--grpc-port", str(free_port())],
            stdout=self._log, stderr=subprocess.STDOUT, cwd=REPO,
            env={**os.environ, **env}, start_new_session=True,
        )
        _ENGINES.append(self)
        self.up_line = ""
        deadline = self.t_spawn + boot_timeout_s
        while True:
            for line in self.log_text().splitlines():
                if line.startswith("engine up:"):
                    self.up_line = line
            if self.up_line:
                break
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"engine {name!r} exited at boot "
                    f"(code {self.proc.returncode})")
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"engine {name!r} not up after {boot_timeout_s:.0f}s")
            time.sleep(0.2)
        self.boot_s = time.monotonic() - self.t_spawn

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def up_fields(self) -> dict:
        """``key=value`` fields of the ``engine up:`` line."""
        return dict(f.split("=", 1) for f in self.up_line.split()[2:]
                    if "=" in f)

    # -- HTTP ---------------------------------------------------------------

    def request(self, method: str, path: str, body: bytes = b"",
                ctype: str = "application/json", timeout: float = 900.0):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body or None,
                         headers={"Content-Type": ctype} if body else {})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path: str) -> dict:
        status, raw = self.request("GET", path, timeout=60.0)
        check(status == 200, f"GET {path} -> {status}: {raw[:300]!r}")
        return json.loads(raw)

    def predict(self, rows) -> "tuple[list, float]":
        """POST rows as a JSON ndarray; returns (answer rows, seconds)."""
        body = json.dumps({"data": {"ndarray": rows}}).encode()
        t0 = time.perf_counter()
        status, raw = self.request("POST", "/api/v0.1/predictions", body)
        dt = time.perf_counter() - t0
        check(status == 200,
              f"predict -> {status}: {raw[:400]!r}")
        return json.loads(raw)["data"]["ndarray"], dt

    def stream(self, row, chunk: int = 8) -> list:
        """One SSE generation stream; returns the concatenated tokens."""
        body = json.dumps({"data": {"ndarray": [row]},
                           "chunk": chunk}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=900.0)
        tokens: list = []
        try:
            conn.request("POST", "/api/v0.1/generate/stream", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            check(resp.status == 200, f"stream -> {resp.status}")
            done = False
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                event = json.loads(line[len("data: "):])
                check("error" not in event, f"stream error event: {event}")
                if event.get("done"):
                    done = True
                    break
                tokens.extend(event["tokens"][0])
            check(done, "stream ended without its done event")
        finally:
            conn.close()
        return tokens

    # -- lifetime -----------------------------------------------------------

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.send_signal(signal.SIGTERM)  # skip the drain
                try:
                    self.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
        try:  # the whole session: nothing the engine started outlives it
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()


def stop_all() -> None:
    for eng in _ENGINES:
        eng.stop()


def log_tails(n: int = 60) -> str:
    parts = []
    for eng in _ENGINES:
        tail = "\n".join(eng.log_text().splitlines()[-n:])
        parts.append(f"--- engine {eng.name!r} log tail "
                     f"({eng.log_path}) ---\n{tail}")
    return "\n".join(parts)


# ---------------------------------------------------------------------------
# helpers over the engine's own documents
# ---------------------------------------------------------------------------

def compile_counters(stats: dict) -> dict:
    """Backend compiles so far: count and total seconds
    (``seldon_tpu_compile_seconds``) plus the persistent-cache hit/miss
    counts (``compile_cache_events``)."""
    tel = stats["telemetry"]
    cs = tel["perf"]["compile_s"]
    ev = tel["compile_cache_events"]
    return {"compiles": cs["count"],
            "compile_s": round(cs["count"] * cs["mean"], 2),
            "cache_hits": ev.get("hit", 0), "cache_misses": ev.get("miss", 0),
            "cache_enabled": ev.get("enabled", 0)}


def delta(after: dict, before: dict) -> dict:
    return {k: round(after[k] - before[k], 2) for k in after
            if k != "cache_enabled"}


def as_tokens(rows, n_rows: int, max_new: int, vocab: int, what: str):
    """Validate one generation answer: [n_rows, max_new] integer ids in
    [0, vocab)."""
    check(len(rows) == n_rows and all(len(r) == max_new for r in rows),
          f"{what}: expected [{n_rows}, {max_new}], got "
          f"[{len(rows)}, {sorted({len(r) for r in rows})}]")
    out = []
    for r in rows:
        check(all(float(t) == int(t) and 0 <= int(t) < vocab for t in r),
              f"{what}: token ids outside [0, {vocab}) or non-integer")
        out.append([int(t) for t in r])
    return out


def deployment_params(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    comp = doc["spec"]["predictors"][0]["components"][0]
    return {p["name"]: p["value"] for p in comp["parameters"]}


# ---------------------------------------------------------------------------
# phase 1: the LM through GenServer
# ---------------------------------------------------------------------------

def lm_phase(deployment_path: str, device: dict, tp: int) -> dict:
    from seldon_core_tpu.runtime.compilecache import compile_cache_dir

    params = deployment_params(deployment_path)
    vocab, max_new = int(params["vocab"]), int(params["max_new_tokens"])
    if tp > 1:
        with open(deployment_path) as f:
            doc = json.load(f)
        doc["spec"]["predictors"][0]["components"][0]["mesh_axes"] = {
            "tp": tp}
        deployment_path = os.path.join(OUT_DIR, f"lm_tp{tp}_deployment.json")
        with open(deployment_path, "w") as f:
            json.dump(doc, f, indent=1)
    # with the cache placed from outside, the in-checkout default must
    # stay exactly as it was
    default_cache = os.path.join(REPO, ".xla_cache")
    default_before = (sorted(os.listdir(default_cache))
                      if os.path.isdir(default_cache) else None)
    rng = random.Random(SEED)

    def prompt(n):
        return [float(rng.randrange(vocab)) for _ in range(n)]

    one = [prompt(37)]
    four = [prompt(512) for _ in range(4)]
    lengths = rng.sample(range(16, 701), 8)
    eight = [[prompt(n)] for n in lengths]

    eng = Engine(f"lm_tp{tp}", deployment_path, {
        "ENGINE_DISPATCH_TIMEOUT_S": "900",
        "SELDON_TPU_GEN_PREFILL_CHUNK_MAX": "128",
        "ENGINE_PREWARM_WIDTHS": "37",
    })
    up = eng.up_fields()
    say(f"[lm] {eng.up_line}  ({eng.boot_s:.1f}s from process start)")
    check(up.get("http") == "fast",
          f"a generator graph serves on the fast lane, engine says: {up}")
    log = eng.log_text()
    check("unavailable" not in log,
          "the lane was announced through an 'unavailable' warning")
    check(f"compile cache: {compile_cache_dir()}" in log,
          f"engine did not report the compile cache at "
          f"{compile_cache_dir()}")
    at_boot = compile_counters(eng.get_json("/stats"))

    # -- the requests -------------------------------------------------------
    ans_one, rtt_cold = eng.predict(one)
    tok_one = as_tokens(ans_one, 1, max_new, vocab, "1x37")
    ans_four, _ = eng.predict(four)
    tok_four = as_tokens(ans_four, 4, max_new, vocab, "4x512")

    # eight single-row requests: request i+1 is sent once request i has
    # produced its first token (it is decoding) — so each later prompt is
    # admitted and prefilled while earlier ones sit in the decode batch
    results: dict = {}

    def send(i):
        try:
            results[i] = eng.predict(eight[i])[0]
        except BaseException as e:  # noqa: BLE001 - re-raised by the parent
            results[i] = e

    threads = []
    joined_running = 0  # requests seen decoding BESIDE an earlier one
    for i, n in enumerate(lengths):
        t = threading.Thread(target=send, args=(i,), daemon=True)
        t.start()
        threads.append(t)
        deadline = time.monotonic() + 900
        while t.is_alive() and time.monotonic() < deadline:
            ledger = eng.get_json("/stats")["genserver"]["sequence_ledger"]
            if any(s["prompt_len"] == n and s["emitted"] >= 1
                   for s in ledger):
                joined_running += any(
                    s["prompt_len"] != n and s["state"] == "running"
                    for s in ledger)
                break
            time.sleep(0.005)
    for t in threads:
        t.join(timeout=900)
        check(not t.is_alive(), "a staggered request never returned")
    tok_eight = []
    for i, n in enumerate(lengths):
        if isinstance(results[i], BaseException):
            raise results[i]
        tok_eight.append(
            as_tokens(results[i], 1, max_new, vocab, f"1x{n}")[0])

    # engine idle: the same programs as the first request are taken again
    for _ in range(200):
        gs = eng.get_json("/stats")["genserver"]
        if not gs["inflight_sequences"] and not gs["waiting_sequences"]:
            break
        time.sleep(0.05)
    gp0 = eng.get_json("/genperf")
    ans_again, rtt_warm = eng.predict(one)
    gp1 = eng.get_json("/genperf")
    check(as_tokens(ans_again, 1, max_new, vocab, "1x37 again") == tok_one,
          "the repeated request returned different tokens")
    streamed = eng.stream(one[0])
    check(streamed == tok_one[0],
          "the SSE stream's concatenation differs from the unary answer: "
          f"{streamed} != {tok_one[0]}")

    # -- the engine's own account -------------------------------------------
    stats = eng.get_json("/stats")
    perf = eng.get_json("/perf")
    genperf = eng.get_json("/genperf")
    dev = perf["device"]
    check(dev["platform"] == device["platform"] == "tpu",
          f"engine device platform is {dev['platform']!r}, not 'tpu'")
    check(dev["device_kind"] == device["kind"],
          f"engine saw {dev['device_kind']!r}, the probe {device['kind']!r}")
    check(dev.get("peak_bf16_tflops") and dev.get("peak_hbm_gbs")
          and "peak_assumed" not in dev,
          f"no peak for this device kind in utils/chips.py: {dev}")
    gs = stats["genserver"]
    check(gs is not None and gs["mode"] == "decode",
          f"no continuous-batching scheduler serving: {gs}")
    # the boot-time prewarm probe is one sequence too
    sent = 1 + 1 + 4 + 8 + 1 + 1
    check(gs["tick_errors_total"] == 0,
          f"scheduler tick errors: {gs['tick_errors_total']}")
    check(gs["admitted_total"] == sent,
          f"admitted {gs['admitted_total']} sequences, sent {sent}")
    check(sum(gs["retired_total"].values()) == sent,
          f"retired {gs['retired_total']}, sent {sent}")
    check(gs["tokens_emitted_total"] >= (sent - 1) * max_new,
          f"tokens emitted {gs['tokens_emitted_total']} < "
          f"{(sent - 1) * max_new}")
    check(joined_running >= 1,
          "none of the eight staggered requests was admitted while an "
          "earlier one was decoding")
    end = compile_counters(stats)
    check(end["cache_enabled"] == 1, "compile cache not reported enabled")
    cache_dir = compile_cache_dir()
    check(os.path.isdir(cache_dir) and os.listdir(cache_dir),
          f"compile cache directory {cache_dir} is empty")
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        default_after = (sorted(os.listdir(default_cache))
                         if os.path.isdir(default_cache) else None)
        check(default_after == default_before,
              f"JAX_COMPILATION_CACHE_DIR is set but {default_cache} was "
              "written too")

    hbm = perf["hbm"]
    record = {
        "engine_up_s": round(eng.boot_s, 1),
        "compiles_before_requests": {
            k: v for k, v in at_boot.items() if k != "cache_enabled"},
        "compiles_inside_requests": delta(end, at_boot),
        "compile_cache_dir": cache_dir,
        "one_row_round_trip_ms": {"first": round(rtt_cold * 1e3, 1),
                                  "repeat_idle": round(rtt_warm * 1e3, 1)},
        "tick_wall_p50_ms": {
            k: v["p50"] for k, v in genperf["tick_wall_ms"].items()},
        "repeat_request_window": tick_window(gp0, gp1),
        "ticks": genperf["ticks"],
        "staggered_admitted_beside_running": f"{joined_running} of 7",
        "hbm": hbm,
        "kv_high_water_blocks": gs["kv_blocks"].get("high_water"),
        "first_tokens": [r[0] for r in tok_one + tok_four + tok_eight],
    }
    if tp > 1:
        record.update(tp_checks(tp, gs, hbm, record["first_tokens"]))
    eng.stop()
    say(f"[lm] {json.dumps(record)}")
    return record


def tick_window(gp0: dict, gp1: dict) -> dict:
    """Scheduler walls over ONE idle-engine request whose programs are
    already compiled: per decode tick, the host wall around the tick and
    the fenced device wall inside it (``/genperf`` deltas)."""
    def d(path):
        a, b = gp0, gp1
        for k in path:
            a, b = a.get(k, {}), b.get(k, {})
        return (b or 0) - (a or 0)

    decode_ticks = d(["ticks", "decode"])
    out = {"decode_ticks": decode_ticks,
           # a lone request's first tick prefills AND decodes: "mixed"
           "mixed_ticks": d(["ticks", "mixed"]),
           "device_s": round(d(["accounting", "device_s"]), 4),
           "host_s": round(d(["accounting", "host_s"]), 4)}
    if decode_ticks > 0:
        dev = d(["phases", "device_s", "decode/decode"])
        host = d(["phases", "host_s", "decode/decode"])
        out["decode_tick_device_ms"] = round(1e3 * dev / decode_ticks, 3)
        out["decode_tick_host_phase_ms"] = round(
            1e3 * host / decode_ticks, 3)
    return out


def tp_checks(tp: int, gs: dict, hbm: list, first_tokens: list) -> dict:
    check(gs["mesh"] == {"tp": tp}, f"genserver.mesh is {gs['mesh']}")
    rows = [h for h in hbm if h.get("memory_stats", 0) is not None]
    check(len(rows) == tp, f"expected {tp} hbm rows, got {hbm}")
    used = [h["bytes_in_use"] for h in rows]
    # roughly equal shares: sharded matmul weights and KV heads split tp
    # ways, the embedding and norms replicate
    check(min(used) > 0 and max(used) <= 1.5 * min(used),
          f"per-device bytes_in_use are not roughly equal shares: {used}")
    out = {"tp": tp, "first_token_match_share": None}
    ref_path = os.path.join(OUT_DIR, "record_tp1.json")
    if os.path.exists(ref_path):
        with open(ref_path) as f:
            ref = json.load(f)["lm"]["first_tokens"]
        out["first_token_match_share"] = round(
            sum(a == b for a, b in zip(first_tokens, ref)) / len(ref), 3)
    else:
        out["first_token_match_reason"] = (
            f"no one-chip record at {ref_path} (run chip_smoke.py on one "
            "chip first and keep its chip_smoke_out/)")
    return out


# ---------------------------------------------------------------------------
# phase 2: the graph through the native lane
# ---------------------------------------------------------------------------

def graph_phase(deployment_path: str, device: dict) -> dict:
    import numpy as np

    from seldon_core_tpu.runtime import wire

    eng = Engine("graph", deployment_path, {
        "ENGINE_PREWARM_WIDTHS": "784",
        "ENGINE_MAX_BATCH": "64",
    })
    up = eng.up_fields()
    say(f"[graph] {eng.up_line}  ({eng.boot_s:.1f}s from process start)")
    check(up.get("mode") == "fused", f"graph is not fused: {up}")
    check(up.get("http") == "native" and up.get("grpc-lane") == "native",
          f"the native C++ lane is not serving: {up}")
    check("fused_mlp" in up.get("kernels", ""),
          f"the Pallas fused-MLP kernel is not in use: {up}")
    rng = np.random.default_rng(SEED)
    answers = {}
    for rows in (1, 5, 32):
        x = rng.random((rows, 784), dtype=np.float32)
        y, _ = eng.predict(x.astype(float).tolist())
        y = np.asarray(y, dtype=np.float64)
        check(y.shape == (rows, 10) and np.isfinite(y).all(),
              f"graph answer shape {y.shape}")
        check(np.allclose(y.sum(axis=1), 1.0, atol=1e-3),
              f"rows do not sum to 1: {y.sum(axis=1)}")
        answers[rows] = (x, y)
    x, y_json = answers[5]
    status, raw = eng.request(
        "POST", "/api/v0.1/predictions",
        wire.join_parts(wire.encode_frame(x)), ctype=wire.WIRE_CONTENT_TYPE)
    check(status == 200, f"binary frame -> {status}: {raw[:200]!r}")
    y_bin = np.asarray(wire.decode_frame(raw).array)
    check(y_bin.shape == (5, 10), f"binary answer shape {y_bin.shape}")
    check(np.array_equal(y_bin.astype(np.float32),
                         y_json.astype(np.float32)),
          "binary answer differs from the JSON answer: max abs diff "
          f"{np.abs(y_bin - y_json).max()}")
    stats = eng.get_json("/stats")
    perf = eng.get_json("/perf")
    check(stats["engine"]["mode"] == "fused",
          f"/stats mode {stats['engine']['mode']!r}")
    check(perf["device"]["platform"] == device["platform"] == "tpu",
          f"engine device platform is {perf['device']['platform']!r}")
    record = {"engine_up_s": round(eng.boot_s, 1),
              "compiles": compile_counters(stats),
              "lanes": {k: up.get(k) for k in ("http", "grpc-lane",
                                               "kernels", "mode")}}
    eng.stop()
    say(f"[graph] {json.dumps(record)}")
    return record


# ---------------------------------------------------------------------------
# phase 3: numerics (child process; the only one here that imports jax)
# ---------------------------------------------------------------------------

def numerics_phase() -> dict:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_numerics"],
        capture_output=True, text=True, cwd=REPO, timeout=900,
    )
    sys.stderr.write(out.stderr[-4000:] if out.returncode else "")
    check(out.returncode == 0,
          f"numerics child failed (code {out.returncode}):\n"
          f"{out.stdout[-2000:]}")
    record = json.loads(out.stdout.strip().splitlines()[-1])
    say(f"[numerics] {json.dumps(record)}")
    return record


def numerics_main() -> None:
    """Runs in the child.  Tolerances are fixed here from the dtype, not
    from what the chip happened to produce:

    bf16 keeps 8 significant bits, so one rounding is a relative error of
    at most 2^-9.  The paged and the dense forward round at the same ~6
    points per layer but contract in different orders, so over 12 layers
    their difference behaves like a random walk of ~72 such roundings:
    sqrt(72) * 2^-9 ~= 1.7% of the activation scale, which the final
    norm carries into the logits as ~1.7% of their RMS.  The MAX over
    B*S*V ~= 3M logits sits ~5 sigma out: 0.10 * rms(logits) bounds the
    max, 0.03 * rms the mean.  A wrong block table, a dropped write or a
    mis-cast pool is an O(1) * rms error and fails both.

    Attention outputs are convex averages of V (|v| <~ 4 for unit
    normals), rounded once to bf16 on the way out of either path, after a
    bf16 cast of the probabilities: 3e-2 absolute covers 8 * 2^-9 * 4
    with room for the differing accumulation order.  The fused MLP and
    XLA both emit f32 softmax rows from bf16 matmuls: 2e-2 absolute."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.generate import (
        init_block_pool,
        paged_decode_round_jit,
        paged_forward_jit,
    )
    from seldon_core_tpu.models.mnist import mlp_apply, mlp_init
    from seldon_core_tpu.models.transformer import (
        LMConfig,
        gqa_attention,
        lm_apply,
        lm_init,
    )
    from seldon_core_tpu.ops.flash_attention import flash_attention
    from seldon_core_tpu.ops.fused_mlp import fused_mlp_softmax
    from seldon_core_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        raise SystemExit(f"numerics needs the chip, found {d0.platform}")
    out = {"platform": d0.platform, "device_kind": d0.device_kind,
           "device_count": len(jax.devices())}
    p = deployment_params(LM_DEPLOYMENT)
    cfg = LMConfig(
        vocab=int(p["vocab"]), d_model=int(p["d_model"]),
        n_heads=int(p["n_heads"]), n_layers=int(p["n_layers"]),
        d_ff=int(p["d_ff"]), n_kv_heads=int(p["n_kv_heads"]),
        dtype=jnp.dtype(p["dtype"]).type)
    params = lm_init(jax.random.key(0), cfg)
    rs = np.random.default_rng(SEED)

    # -- paged prefill + one decode round vs the dense forward ---------------
    B, S, span, bs, nblk = 2, 48, 8, 16, 4
    toks = jnp.asarray(rs.integers(0, cfg.vocab, (B, S)), jnp.int32)
    pool = init_block_pool(cfg, 16, bs)
    out["pool_dtype"] = str(pool["l0"]["k"].dtype)
    tables = jnp.asarray(
        [[1 + r * nblk + j for j in range(nblk)] for r in range(B)],
        jnp.int32)
    start = jnp.zeros((B,), jnp.int32)
    width = jnp.full((B,), S, jnp.int32)
    paged, pool = paged_forward_jit(params, toks, pool, tables, start,
                                    width, cfg=cfg, last_only=False)
    dense = jax.jit(lambda ps, t: lm_apply(ps, t, cfg))(params, toks)
    paged, dense = np.asarray(paged), np.asarray(dense)
    rms = float(np.sqrt(np.mean(dense ** 2)))
    diff = np.abs(paged - dense)
    out["prefill_logits"] = {
        "rms": round(rms, 4), "max_abs_diff": round(float(diff.max()), 4),
        "mean_abs_diff": round(float(diff.mean()), 5),
        "tol_max": round(0.10 * rms, 4), "tol_mean": round(0.03 * rms, 5)}
    if not np.isfinite(paged).all():
        raise SystemExit("paged prefill logits are not finite")
    if diff.max() > 0.10 * rms or diff.mean() > 0.03 * rms:
        raise SystemExit(f"paged prefill != dense: {out['prefill_logits']}")
    first = jnp.asarray(dense[:, -1].argmax(-1), jnp.int32)
    round_toks, pool, *_ = paged_decode_round_jit(
        params, pool, tables, first, jnp.full((B,), S, jnp.int32),
        jnp.ones((B,), bool), jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.uint32), cfg, span=span, temperature=0.0,
        top_k=0, top_p=0.0, eos_token=-1)
    round_toks = np.asarray(round_toks)
    # teacher-forced dense logits over prompt + what the round emitted:
    # each emitted token must sit within the tolerance of the dense max
    full = jnp.concatenate(
        [toks, first[:, None], jnp.asarray(round_toks[:, :-1])], axis=1)
    dense2 = np.asarray(
        jax.jit(lambda ps, t: lm_apply(ps, t, cfg))(params, full))
    gaps = [
        float(dense2[b, S + i].max() - dense2[b, S + i, round_toks[b, i]])
        for b in range(B) for i in range(span)]
    out["decode_round"] = {
        "max_gap_to_dense_argmax": round(max(gaps), 4),
        "exact_argmax_share": round(
            float(np.mean([g == 0.0 for g in gaps])), 3),
        "tol": round(0.10 * rms, 4)}
    if max(gaps) > 0.10 * rms:
        raise SystemExit(f"decode round left the dense argmax: "
                         f"{out['decode_round']}")

    # -- flash attention, compiled, at the shape attention=auto sends it ----
    Bq, H, KV, Sq, hd = 4, 16, 4, 512, 64
    kq, kk, kv_ = jax.random.split(jax.random.key(SEED), 3)
    q = jax.random.normal(kq, (Bq, H, Sq, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (Bq, KV, Sq, hd), jnp.bfloat16)
    v = jax.random.normal(kv_, (Bq, KV, Sq, hd), jnp.bfloat16)
    fl = np.asarray(jax.jit(
        lambda a, b, c: flash_attention(a, b, c, True, False)
    )(q, k, v).astype(jnp.float32))
    ref = np.asarray(jax.jit(
        lambda a, b, c: gqa_attention(a, b, c, True)
    )(q, k, v).astype(jnp.float32))
    fdiff = float(np.abs(fl - ref).max())
    out["flash_vs_gqa"] = {"shape": [Bq, H, KV, Sq, hd],
                           "max_abs_diff": round(fdiff, 5), "tol": 3e-2}
    if not np.isfinite(fl).all() or fdiff > 3e-2:
        raise SystemExit(f"flash_attention != gqa_attention: "
                         f"{out['flash_vs_gqa']}")

    # -- fused MLP, compiled, against XLA ------------------------------------
    mlp = mlp_init(jax.random.key(0))
    mdiffs = {}
    for rows in (1, 8, 256):
        x = jnp.asarray(rs.random((rows, 784)), jnp.float32)
        got = np.asarray(jax.jit(fused_mlp_softmax)(mlp, x))
        want = np.asarray(jax.jit(
            lambda ps, a: jax.nn.softmax(mlp_apply(ps, a), axis=-1))(mlp, x))
        mdiffs[rows] = round(float(np.abs(got - want).max()), 5)
        if got.shape != (rows, 10) or mdiffs[rows] > 2e-2:
            raise SystemExit(f"fused MLP != XLA at {rows} rows: {mdiffs}")
    out["fused_mlp_vs_xla_max_abs_diff"] = mdiffs

    # -- jax.block_until_ready is the fence ----------------------------------
    # one program, timed twice: under block_until_ready and under a host
    # fetch of its (small) output.  24 chained 4096^3 matmuls are 3.3
    # TFLOP, so no chip in utils/chips.py can finish under flops / peak —
    # a fence that returns early shows up against that bound
    from seldon_core_tpu.utils.chips import chip_peak_tflops

    n, reps = 4096, 24
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(m):
        def body(c, _):
            return (c @ m) * jnp.bfloat16(1e-4), None
        return jax.lax.scan(body, m, None, length=reps)[0][:8, :128]

    np.asarray(chain(a))
    t0 = time.perf_counter()
    jax.block_until_ready(chain(a))
    fence_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    np.asarray(chain(a))
    fetch_ms = (time.perf_counter() - t0) * 1e3
    floor_ms = reps * 2 * n ** 3 / (
        chip_peak_tflops(d0.device_kind) * 1e12) * 1e3
    out["fence"] = {"block_until_ready_ms": round(fence_ms, 2),
                    "host_fetch_ms": round(fetch_ms, 2),
                    "flops_over_peak_ms": round(floor_ms, 2)}
    if fence_ms < floor_ms or fence_ms < 0.5 * fetch_ms:
        raise SystemExit(f"block_until_ready returned early: {out['fence']}")
    print(json.dumps(out))


# ---------------------------------------------------------------------------

def run(tp: int) -> dict:
    check(os.path.isdir(os.path.join(REPO, "seldon_core_tpu")),
          f"{REPO} holds chip_smoke.py but not the seldon_core_tpu package")
    os.makedirs(OUT_DIR, exist_ok=True)
    device = probe_device()
    check(device["platform"] == "tpu",
          f"JAX found no accelerator here: {device} — chip_smoke.py only "
          "passes on the chip")
    check(device["count"] >= tp,
          f"--tp {tp} needs {tp} chips, JAX reports {device['count']}")
    say(f"[device] platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']}")
    record = {"device": device, "lm": lm_phase(LM_DEPLOYMENT, device, tp)}
    if tp == 1:
        record["graph"] = graph_phase(GRAPH_DEPLOYMENT, device)
        record["numerics"] = numerics_phase()
    with open(os.path.join(OUT_DIR, f"record_tp{tp}.json"), "w") as f:
        json.dump(record, f, indent=1)
    return device


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--tp", type=int, default=1,
                        help="LM phase only, tensor-parallel over N chips")
    parser.add_argument("--_numerics", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args._numerics:
        numerics_main()
        return 0
    t0 = time.monotonic()
    try:
        device = run(args.tp)
    except BaseException as e:
        stop_all()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}\n{log_tails()}",
              file=sys.stderr, flush=True)
        if isinstance(e, SmokeFailure):
            return 1
        raise  # a bug in the smoke itself: the traceback is the report
    stop_all()
    # one process per chip: this parent must never have opened a backend
    if "jax" in sys.modules:
        print("chip_smoke FAILED: the parent imported jax", file=sys.stderr)
        return 1
    say(f"[done] all phases passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
