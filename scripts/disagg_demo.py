"""Disaggregated prefill/decode demo — 1 prefill + 2 decode CPU
replicas with streamed KV handoffs, vs the unified kill-switch arm.

What it proves (and asserts):

1. a generator served by a prefill replica + two decode replicas over
   the UDS relay's OP_KVSTREAM lane answers EXACTLY the tokens the
   unified single-replica path answers (token-identical handoff);
2. the handoffs are VISIBLE: the prefill replica's /stats
   ``genserver.disagg`` block counts them (with latency + bytes/token)
   and the firehose carries one ``kv_handoff`` line per handoff;
3. both decode replicas imported (the free-KV-block p2c spreads load);
4. a client request aimed straight at a decode replica answers a typed
   503 role misconfig;
5. ``SELDON_TPU_DISAGG=0`` (the kill switch) serves the same traffic
   unified — zero handoffs, same tokens.

Artifact: ``<out>/disagg.json``.  Run via ``make disagg-demo``.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEPLOYMENT = {
    "spec": {
        "name": "disagg-demo",
        "predictors": [{
            "name": "main",
            "graph": {"name": "gen", "type": "MODEL"},
            "components": [{
                "name": "gen", "runtime": "inprocess",
                "class_path": "TransformerGenerator",
                "parameters": [
                    {"name": "vocab", "value": "128", "type": "INT"},
                    {"name": "d_model", "value": "64", "type": "INT"},
                    {"name": "n_heads", "value": "4", "type": "INT"},
                    {"name": "n_layers", "value": "2", "type": "INT"},
                    {"name": "d_ff", "value": "128", "type": "INT"},
                    {"name": "max_new_tokens", "value": "24",
                     "type": "INT"},
                    {"name": "dtype", "value": "float32",
                     "type": "STRING"},
                ],
            }],
        }],
    }
}

_SPAWNED = []


def _reap():
    for p in _SPAWNED:
        if p.poll() is None:
            p.kill()


class Replica:
    def __init__(self, port, role="unified", uds_path="",
                 decode_peers="", audit_dir=""):
        self.tmp = tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False)
        json.dump(DEPLOYMENT, self.tmp)
        self.tmp.flush()
        self.log = tempfile.NamedTemporaryFile(
            "w+", suffix=".log", delete=False)
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "ENGINE_HTTP_IMPL": "fast", "ENGINE_GRPC_IMPL": "fast",
            "ENGINE_MAX_BATCH": "32", "ENGINE_BATCH_WAIT_MS": "0.5",
        })
        if role != "unified":
            env["ENGINE_GEN_ROLE"] = role
        if uds_path:
            env["ENGINE_UDS_PATH"] = uds_path
        if decode_peers:
            env["ENGINE_DECODE_PEERS"] = decode_peers
        if audit_dir:
            env["SELDON_TPU_AUDIT"] = "1"
            env["SELDON_TPU_AUDIT_DIR"] = audit_dir
        self.port = port
        self.role = role
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "seldon_core_tpu.runtime.engine_main",
             "--file", self.tmp.name, "--host", "127.0.0.1",
             "--rest-port", str(port), "--grpc-port", str(port + 1000)],
            stdout=self.log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
        )
        _SPAWNED.append(self.proc)

    def wait_up(self, timeout_s=180.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with open(self.log.name) as f:
                text = f.read()
            if "engine up" in text:
                return
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.role} replica died at boot:\n{text}")
            time.sleep(0.5)
        raise RuntimeError(f"{self.role} replica boot timed out")

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        os.unlink(self.tmp.name)

    def predict(self, prompt):
        body = json.dumps({"data": {"ndarray": [prompt]}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/api/v0.1/predictions",
            data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def stats(self):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.port}/stats", timeout=10
        ) as r:
            return json.loads(r.read())


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="disagg_demo")
    args = parser.parse_args()
    os.makedirs(args.out, exist_ok=True)
    import atexit

    atexit.register(_reap)

    prompts = [
        [(i * 7 + j) % 97 + 1 for j in range(40)] for i in range(6)
    ]
    doc = {"checks": {}}
    uds_dir = tempfile.mkdtemp(prefix="disagg-demo-")
    audit_dir = os.path.join(args.out, "firehose")
    os.makedirs(audit_dir, exist_ok=True)
    socks = [os.path.join(uds_dir, f"d{i}.sock") for i in range(2)]

    # -- unified reference ------------------------------------------------
    print("== booting unified reference replica", flush=True)
    unified = Replica(19740)
    unified.wait_up()
    try:
        want = [unified.predict(p) for p in prompts]
        assert all(s == 200 for s, _ in want)
        want_tokens = [b["data"]["ndarray"] for _, b in want]
    finally:
        unified.stop()

    # -- 1 prefill + 2 decode over the relay ------------------------------
    print("== booting 1 prefill + 2 decode mesh", flush=True)
    d0 = Replica(19741, role="decode", uds_path=socks[0])
    d1 = Replica(19742, role="decode", uds_path=socks[1])
    p0 = Replica(19743, role="prefill",
                 decode_peers=f"uds:{socks[0]},uds:{socks[1]}",
                 audit_dir=audit_dir)
    try:
        for r in (d0, d1, p0):
            r.wait_up()
        got = [p0.predict(p) for p in prompts]
        assert all(s == 200 for s, _ in got), [s for s, _ in got]
        got_tokens = [b["data"]["ndarray"] for _, b in got]
        doc["checks"]["token_identical"] = got_tokens == want_tokens
        assert doc["checks"]["token_identical"], \
            "disaggregated tokens differ from unified!"

        # handoffs visible in /stats
        gs = p0.stats()["genserver"]
        disagg = gs["disagg"]
        doc["prefill_stats"] = {
            "role": gs["role"],
            "handoffs": disagg["handoffs"],
            "handoff_ms_p50": disagg["handoff_ms_p50"],
            "bytes_per_tok": disagg["bytes_per_tok"],
            "peer_free_blocks": disagg["peer_free_blocks"],
        }
        doc["checks"]["handoffs_in_stats"] = (
            disagg["handoffs"].get("ok", 0) == len(prompts))
        imports = [r.stats()["genserver"]["imports"] for r in (d0, d1)]
        doc["decode_imports"] = imports
        doc["checks"]["both_decodes_imported"] = all(
            i["committed_total"] > 0 for i in imports)
        doc["checks"]["zero_reclaims"] = all(
            i["reclaimed_total"] == 0 for i in imports)

        # handoffs visible in the firehose
        lines = []
        for fn in os.listdir(audit_dir):
            with open(os.path.join(audit_dir, fn)) as f:
                lines += [json.loads(ln) for ln in f if ln.strip()]
        handoff_lines = [
            ln for ln in lines if ln.get("method") == "kv_handoff"]
        doc["checks"]["handoffs_in_firehose"] = (
            len(handoff_lines) == len(prompts))
        doc["firehose_handoff_sample"] = (
            handoff_lines[0] if handoff_lines else None)

        # role misconfig: a client request at a decode replica
        status, body = d0.predict(prompts[0])
        doc["checks"]["decode_direct_typed_503"] = (
            status == 503
            and "decode-only" in (body.get("status") or {}).get(
                "info", ""))
    finally:
        p0.stop()
        d0.stop()
        d1.stop()

    # -- kill switch: SELDON_TPU_DISAGG=0 ---------------------------------
    print("== kill-switch arm (SELDON_TPU_DISAGG=0)", flush=True)
    os.environ["SELDON_TPU_DISAGG"] = "0"
    killed = Replica(19744, role="prefill",
                     decode_peers=f"uds:{socks[0]}")
    try:
        killed.wait_up()
        k = [killed.predict(p) for p in prompts]
        assert all(s == 200 for s, _ in k)
        doc["checks"]["kill_switch_token_identical"] = (
            [b["data"]["ndarray"] for _, b in k] == want_tokens)
        gs = killed.stats()["genserver"]
        doc["checks"]["kill_switch_role_unified"] = gs["role"] == "unified"
    finally:
        killed.stop()
        del os.environ["SELDON_TPU_DISAGG"]

    failed = {k: v for k, v in doc["checks"].items() if not v}
    doc["ok"] = not failed
    out = os.path.join(args.out, "disagg.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc["checks"], indent=1))
    print(f"artifact: {out}")
    if failed:
        print(f"FAILED checks: {sorted(failed)}", file=sys.stderr)
        sys.exit(3)
    print("disagg demo: ALL CHECKS PASSED")


if __name__ == "__main__":
    main()
