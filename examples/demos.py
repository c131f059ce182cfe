"""Guided end-to-end walkthroughs — the role the reference's notebook
suite played (notebooks/advanced_graphs.ipynb, epsilon_greedy_gcp.ipynb,
canary examples/istio/canary_update/canary.ipynb,
benchmark_simple_model.ipynb), as runnable scripts:

  canary    two predictors, one gateway: replica-weighted traffic split,
            then a canary promotion shifts the split live
  ensemble  8-member AVERAGE_COMBINER: one request fans out on-device,
            metrics + trace prove a single batched dispatch
  mab       epsilon-greedy ROUTER trained by /feedback until it prefers
            the rewarded branch (the reference's MAB notebook flow)
  stream    SSE token generation THROUGH the gateway (auth + canary pick
            + proxied event stream)

    python examples/demos.py [canary|ensemble|mab|stream|all] [--tpu]

Engines run on host CPU by default (JAX_PLATFORMS=cpu): every scenario
starts SEVERAL engine processes on one host, and a chip belongs to one
process at a time — nothing here assigns chips to processes yet.  --tpu
leaves the platform to JAX and only makes sense for a one-engine scenario.
Exits non-zero on any failed assertion; `make demos` runs all four.
"""

from __future__ import annotations

import argparse
import base64
import collections
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")

ENGINE_A, ENGINE_B = 18820, 18821
GW_REST, GW_GRPC = 18828, 18829

FORCE_CPU = True  # --tpu clears this


# -- process helpers ---------------------------------------------------------


def _reap_at_exit(proc) -> None:
    """atexit backstop: a demo killed mid-boot (Ctrl-C in wait_for,
    assertion in the driver) must not leave an engine process running —
    PR 8 found exactly such strays skewing later bench runs.  Orderly
    teardown still goes through the finally/stop() paths; this only
    fires for processes still alive at interpreter exit."""
    import atexit

    def _kill():
        if proc.poll() is None:
            proc.kill()

    atexit.register(_kill)


def wait_for(url: str, timeout_s: float, proc=None) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(f"process exited rc={proc.returncode}")
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except (urllib.error.URLError, OSError):
            time.sleep(0.5)
    raise RuntimeError(f"timeout waiting for {url}")


def post(url: str, body: str, headers=None, timeout=60) -> dict:
    req = urllib.request.Request(
        url, data=body.encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class Stack:
    """Engines + optional gateway, torn down on exit."""

    def __init__(self):
        self.procs = []
        self.tmp = tempfile.mkdtemp(prefix="seldon-demo-")

    def engine(self, deployment: dict, port: int, predictor=None,
               env_extra=None) -> None:
        path = os.path.join(self.tmp, f"dep-{port}.json")
        with open(path, "w") as f:
            json.dump(deployment, f)
        env = dict(os.environ)
        if FORCE_CPU:
            env["JAX_PLATFORMS"] = "cpu"
        env.update(env_extra or {})
        cmd = [sys.executable, "-m", "seldon_core_tpu.runtime.engine_main",
               "--file", path, "--host", "127.0.0.1",
               "--rest-port", str(port), "--grpc-port", str(port + 100)]
        if predictor:
            cmd += ["--predictor", predictor]
        self.procs.append(subprocess.Popen(env=env, cwd=REPO, args=cmd))
        _reap_at_exit(self.procs[-1])
        wait_for(f"http://127.0.0.1:{port}/ready", 300, self.procs[-1])

    def gateway(self, deployment: dict, url_map=None, template=None) -> None:
        spec_dir = os.path.join(self.tmp, "specs")
        os.makedirs(spec_dir, exist_ok=True)
        with open(os.path.join(spec_dir, "dep.json"), "w") as f:
            json.dump(deployment, f)
        env = dict(
            os.environ,
            GATEWAY_REST_PORT=str(GW_REST),
            GATEWAY_GRPC_PORT=str(GW_GRPC),
            GATEWAY_FIREHOSE_DIR=os.path.join(self.tmp, "firehose"),
        )
        if url_map:
            env["GATEWAY_ENGINE_URL_MAP"] = json.dumps(url_map)
        if template:
            env["GATEWAY_ENGINE_URL_TEMPLATE"] = template
        self.procs.append(subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.gateway.gateway_main",
             "--spec-dir", spec_dir, "--host", "127.0.0.1"],
            env=env, cwd=REPO,
        ))
        _reap_at_exit(self.procs[-1])
        wait_for(f"http://127.0.0.1:{GW_REST}/ready", 60, self.procs[-1])

    def token(self, key: str, secret: str) -> str:
        basic = base64.b64encode(f"{key}:{secret}".encode()).decode()
        return post(f"http://127.0.0.1:{GW_REST}/oauth/token", "",
                    {"Authorization": f"Basic {basic}"})["access_token"]

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
                p.send_signal(signal.SIGTERM)  # second: skip the drain
        deadline = time.monotonic() + 20
        for p in self.procs:
            try:
                p.wait(timeout=max(1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(self.tmp, ignore_errors=True)


def load_example(name: str) -> dict:
    with open(os.path.join(EXAMPLES, name)) as f:
        return json.load(f)


def step(msg: str) -> None:
    print(f"  -> {msg}", flush=True)


# -- scenario 1: canary ------------------------------------------------------


def demo_canary() -> None:
    """Replica-weighted canary split, then a live promotion — the flow the
    reference demonstrated with istio routing (canary.ipynb), here native
    to the gateway's predictor weighting."""
    print("[canary] two predictors (main x3, canary x1), one gateway")
    doc = load_example("canary_deployment.json")
    stack = Stack()
    try:
        step("engine per predictor (:18820 main, :18821 canary)")
        stack.engine(doc, ENGINE_A, predictor="main")
        stack.engine(doc, ENGINE_B, predictor="canary")
        step("gateway with per-predictor URL map")
        stack.gateway(doc, url_map={
            "mnist-canary/main": f"http://127.0.0.1:{ENGINE_A}",
            "mnist-canary/canary": f"http://127.0.0.1:{ENGINE_B}",
        })
        tok = stack.token("canary-key", doc["spec"]["oauth_secret"])
        auth = {"Authorization": f"Bearer {tok}"}

        def split(n):
            served = collections.Counter()
            payload = json.dumps({"data": {"ndarray": [[0.0] * 784]}})
            for _ in range(n):
                r = post(f"http://127.0.0.1:{GW_REST}/api/v0.1/predictions",
                         payload, auth)
                assert r["status"]["status"] == "SUCCESS", r
                served[r["meta"]["requestPath"]["predictor"]] += 1
            return served

        n = 80
        served = split(n)
        step(f"traffic over {n} requests: {dict(served)} (want ~3:1)")
        assert served["main"] > served["canary"] > 0, served

        step("promote: canary replicas 1 -> 12 (live spec refresh)")
        doc2 = json.loads(json.dumps(doc))
        doc2["spec"]["predictors"][1]["replicas"] = 12
        with open(os.path.join(stack.tmp, "specs", "dep.json"), "w") as f:
            json.dump(doc2, f)
        time.sleep(6.5)  # gateway spec-dir poll interval is 5 s
        served = split(n)
        step(f"traffic after promotion: {dict(served)} (want canary-heavy)")
        assert served["canary"] > served["main"], served
        print("[canary] OK — split followed replica weights live\n")
    finally:
        stack.stop()


# -- scenario 2: ensemble ----------------------------------------------------


def demo_ensemble() -> None:
    """8-member AVERAGE_COMBINER ensemble: the graph fans out in ONE
    compiled dispatch; metrics + trace make that visible (the reference's
    advanced_graphs.ipynb combiner demo, plus on-device evidence)."""
    print("[ensemble] 8-member AVERAGE_COMBINER through one engine")
    members = 8
    doc = {
        "spec": {
            "name": "demo-ens",
            "predictors": [{
                "name": "main",
                "graph": {
                    "name": "ens", "type": "COMBINER",
                    "implementation": "AVERAGE_COMBINER",
                    "children": [
                        {"name": f"m{i}", "type": "MODEL"}
                        for i in range(members)
                    ],
                },
                "components": [
                    {
                        "name": f"m{i}", "runtime": "inprocess",
                        "class_path": "MnistClassifier",
                        "parameters": [
                            {"name": "hidden", "value": "64", "type": "INT"},
                            {"name": "seed", "value": str(i), "type": "INT"},
                        ],
                    }
                    for i in range(members)
                ],
            }],
        }
    }
    stack = Stack()
    try:
        step("engine with the 8-member graph (compiled mode)")
        # Python fast lane: the request/dispatch tracer spans this demo
        # inspects are recorded there (the C++ lane keeps its own stats
        # and surfaces them via /prometheus instead)
        stack.engine(doc, ENGINE_A, env_extra={
            "ENGINE_PREWARM_WIDTHS": "784", "ENGINE_HTTP_IMPL": "fast",
        })
        base = f"http://127.0.0.1:{ENGINE_A}"
        urllib.request.urlopen(f"{base}/trace/enable", timeout=10).read()
        payload = json.dumps({"data": {"ndarray": [[0.1] * 784]}})
        t0 = time.perf_counter()
        n = 50
        for _ in range(n):
            r = post(f"{base}/api/v0.1/predictions", payload)
            assert len(r["data"]["ndarray"][0]) == 10
        dt = time.perf_counter() - t0
        step(f"{n} requests, {members}-member mean: "
             f"{1e3 * dt / n:.1f} ms/req avg")

        with urllib.request.urlopen(
            f"{base}/trace?limit=200", timeout=10
        ) as r:
            spans = json.loads(r.read())["spans"]
        dispatches = [s for s in spans if s["kind"] == "dispatch"]
        requests = [s for s in spans if s["kind"] == "request"]
        step(f"trace: {len(requests)} requests -> {len(dispatches)} device "
             f"dispatches (fan-out is INSIDE the compiled graph)")
        assert dispatches and len(dispatches) <= len(requests) + 2

        with urllib.request.urlopen(f"{base}/prometheus", timeout=10) as r:
            metrics = r.read().decode()
        assert "seldon_api_engine_server_requests_duration_seconds" in metrics
        step("prometheus: engine server histogram present")
        print("[ensemble] OK — one dispatch per request at any width\n")
    finally:
        stack.stop()


# -- scenario 3: epsilon-greedy feedback -------------------------------------


def demo_mab() -> None:
    """Multi-armed-bandit router converging on the rewarded branch via the
    /feedback path — the reference's epsilon_greedy_gcp.ipynb loop."""
    print("[mab] epsilon-greedy router trained by feedback")
    doc = load_example("epsilon_greedy_deployment.json")
    stack = Stack()
    try:
        step("engine with ROUTER graph (eg-router over mnist-a, mnist-b)")
        stack.engine(doc, ENGINE_A)
        base = f"http://127.0.0.1:{ENGINE_A}"
        payload = json.dumps({"data": {"ndarray": [[0.05] * 784]}})

        def routed_counts(n):
            counts = collections.Counter()
            responses = []
            for _ in range(n):
                r = post(f"{base}/api/v0.1/predictions", payload)
                assert r["status"]["status"] == "SUCCESS", r
                branch = list(r["meta"]["routing"].values())[0]
                counts[branch] += 1
                responses.append(r)
            return counts, responses

        before, responses = routed_counts(40)
        step(f"routing before training: {dict(before)}")

        step("reward ONLY branch 1 through /feedback (60 rounds)")
        for _ in range(60):
            r = post(f"{base}/api/v0.1/predictions", payload)
            branch = list(r["meta"]["routing"].values())[0]
            post(f"{base}/api/v0.1/feedback", json.dumps({
                "request": {"data": {"ndarray": [[0.05] * 784]}},
                "response": r,
                "reward": 1.0 if branch == 1 else 0.0,
            }))

        after, _ = routed_counts(40)
        step(f"routing after training: {dict(after)}")
        assert after[1] > after[0], (
            f"router did not converge to the rewarded branch: {dict(after)}"
        )
        print("[mab] OK — feedback shifted routing to the rewarded arm\n")
    finally:
        stack.stop()


# -- scenario 4: SSE generation through the gateway --------------------------


def demo_stream() -> None:
    """Token streaming end-to-end: OAuth at the gateway, canary predictor
    pick, SSE proxied from the engine's Python fast lane (beyond-reference:
    the reference predates sequence models)."""
    print("[stream] SSE generation through the gateway")
    doc = load_example("generator_deployment.json")
    stack = Stack()
    try:
        step("engine on the Python fast lane (SSE lives there)")
        stack.engine(doc, ENGINE_A, env_extra={"ENGINE_HTTP_IMPL": "fast"})
        step("gateway proxying the event stream")
        stack.gateway(doc, url_map={
            "generator-deployment/main": f"http://127.0.0.1:{ENGINE_A}",
        })
        tok = stack.token("gen-key", doc["spec"]["oauth_secret"])
        req = urllib.request.Request(
            f"http://127.0.0.1:{GW_REST}/api/v0.1/generate/stream",
            data=json.dumps({
                "data": {"ndarray": [[1.0, 2.0, 3.0, 4.0]]}, "chunk": 4,
            }).encode(),
            headers={"Content-Type": "application/json",
                     "Authorization": f"Bearer {tok}"},
        )
        t0 = time.perf_counter()
        ttft = None
        events = []
        with urllib.request.urlopen(req, timeout=300) as r:
            assert r.headers["Content-Type"].startswith("text/event-stream")
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                if ttft is None:
                    ttft = time.perf_counter() - t0
                events.append(json.loads(line[len("data: "):]))
        total = time.perf_counter() - t0
        tokens = sum(len(e["tokens"][0]) for e in events if "tokens" in e)
        assert events and events[-1].get("done") is True
        assert tokens == 16, f"expected 16 tokens, got {tokens}"
        step(f"{len(events)} SSE events, {tokens} tokens; first chunk after "
             f"{1e3 * ttft:.0f} ms, total {1e3 * total:.0f} ms")
        # unauthenticated request is refused at the gateway
        bad = urllib.request.Request(
            f"http://127.0.0.1:{GW_REST}/api/v0.1/generate/stream",
            data=b'{"data":{"ndarray":[[1.0]]}}',
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(bad, timeout=30)
            raise AssertionError("unauthenticated stream was not refused")
        except urllib.error.HTTPError as e:
            assert e.code == 401, e.code
        step("unauthenticated stream refused with 401")
        print("[stream] OK — authenticated SSE proxied end-to-end\n")
    finally:
        stack.stop()


DEMOS = {
    "canary": demo_canary,
    "ensemble": demo_ensemble,
    "mab": demo_mab,
    "stream": demo_stream,
}


def main() -> int:
    global FORCE_CPU
    parser = argparse.ArgumentParser()
    parser.add_argument("scenario", nargs="?", default="all",
                        choices=[*DEMOS, "all"])
    parser.add_argument("--tpu", action="store_true",
                        help="run engines on the real accelerator")
    args = parser.parse_args()
    FORCE_CPU = not args.tpu
    names = list(DEMOS) if args.scenario == "all" else [args.scenario]
    for name in names:
        DEMOS[name]()
    print(f"all demos OK: {', '.join(names)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
