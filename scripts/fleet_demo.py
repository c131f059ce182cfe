"""Fleet observability demo — the mesh-wide single pane end to end.

What it proves (and asserts):

1. a disaggregated generation (in-process prefill engine -> real UDS
   relay -> decode engine) is traced END TO END: the gateway's
   federated ``/trace`` assembly returns ONE causal tree containing the
   gateway ingress, the prefill dispatch, the ``kv_handoff`` wire
   segment and the decode process's ``kv_import``/``decode`` spans,
   with critical-path segments summing exactly to the root duration;
2. a replica set with one injected-slow replica (+30 ms
   testing/faults.FaultyEngine) surfaces THAT replica as the outlier on
   ``GET /fleet`` (worse-than-median ratio on the gateway EWMA) and in
   the ``seldon_tpu_fleet_outlier_ratio`` gauge;
3. a coordinated profile window opens on the deployment's engines
   simultaneously, collects the artifact paths into one manifest, and
   REFUSES an overlapping window (409);
4. ``SELDON_TPU_FLEET=0`` (the kill switch) answers every surface from
   local data only.

Artifacts: ``<out>/fleet.json`` (the check table), ``<out>/trace.json``
(the federated tree), ``<out>/trace_perfetto.json`` (per-process
tracks — load in Perfetto), ``<out>/profile_manifest.json``.
Run via ``make fleet-demo``.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["SELDON_TPU_TRACE"] = "1"

from seldon_core_tpu.gateway.apife import ApiGateway, DeploymentStore  # noqa: E402
from seldon_core_tpu.gateway import fleet  # noqa: E402
from seldon_core_tpu.graph.spec import SeldonDeploymentSpec  # noqa: E402
from seldon_core_tpu.messages import SeldonMessage  # noqa: E402
from seldon_core_tpu.runtime.engine import EngineService  # noqa: E402
from seldon_core_tpu.runtime.udsrelay import serve_uds  # noqa: E402
from seldon_core_tpu.testing.faults import FaultSpec, FaultyEngine  # noqa: E402
from seldon_core_tpu.utils.tracing import TRACER  # noqa: E402


def _gen_spec(name):
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": name, "predictors": [{
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
        }]}
    })


def _iris_spec(name):
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": name, "predictors": [{
            "name": "main",
            "graph": {"name": "m", "type": "MODEL"},
            "components": [{
                "name": "m", "runtime": "inprocess",
                "class_path": "IrisClassifier",
            }],
        }]}
    })


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="fleet_demo")
    args = parser.parse_args()
    os.makedirs(args.out, exist_ok=True)
    os.environ.setdefault(
        "SELDON_TPU_PROFILE_DIR", os.path.join(args.out, "profiles"))
    doc = {"checks": {}}
    checks = doc["checks"]
    TRACER.enable()

    # -- arm 1: federated trace of a disaggregated generation -------------
    print("== arm 1: federated trace across the prefill/decode mesh",
          flush=True)
    sock = os.path.join(tempfile.mkdtemp(prefix="fleet-demo-"),
                        "decode.sock")
    decode_engine = EngineService(_gen_spec("gen"), gen_role="decode")
    relay_loop = asyncio.new_event_loop()
    threading.Thread(target=relay_loop.run_forever, daemon=True).start()
    server = asyncio.run_coroutine_threadsafe(
        serve_uds(decode_engine, sock), relay_loop).result(30)
    prefill_engine = EngineService(
        _gen_spec("gen"), gen_role="prefill", decode_peers=[f"uds:{sock}"])
    gen_store = DeploymentStore()
    gen_store.register(_gen_spec("gen"), {"main": prefill_engine})
    gen_gw = ApiGateway(gen_store, require_auth=False)
    prompt = [(i * 7) % 97 + 1 for i in range(40)]
    msg = SeldonMessage.from_json(
        json.dumps({"data": {"ndarray": [prompt]}}))

    async def trace_arm():
        resp = await gen_gw.predict(msg)
        assert resp.status is None or resp.status.status == "SUCCESS"
        puid = resp.meta.puid
        trace_id = ""
        for _ in range(100):
            spans = TRACER.trace(puid)
            trace_id = next((s.trace_id for s in spans if s.trace_id), "")
            names = {s.name for s in TRACER.by_trace(trace_id)} \
                if trace_id else set()
            if {"kv_handoff", "decode", "kv_import"} <= names:
                break
            await asyncio.sleep(0.1)
        tdoc = await fleet.federated_trace_document(
            gen_gw, trace_id=trace_id)
        export = await fleet.federated_export_document(
            gen_gw, trace_id=trace_id)
        await gen_gw.close()
        return tdoc, export

    try:
        tdoc, export = asyncio.run(trace_arm())
    finally:
        asyncio.run_coroutine_threadsafe(
            server.stop(), relay_loop).result(30)
        relay_loop.call_soon_threadsafe(relay_loop.stop)
        for e in (decode_engine, prefill_engine):
            asyncio.run(e.close())
    names = {(s["name"], s["kind"]) for s in tdoc["spans"]}
    checks["federated_tree_has_all_legs"] = {
        ("gateway", "request"), ("prefill", "dispatch"),
        ("kv_handoff", "kv_handoff"), ("kv_import", "kv_import"),
        ("decode", "dispatch"),
    } <= names
    cp_total = sum(c["self_ms"] for c in tdoc["critical_path"])
    checks["critical_path_sums_to_root"] = (
        abs(cp_total - tdoc["root_duration_ms"]) < 0.01)
    checks["one_tree_not_partial"] = (
        len(tdoc["tree"]) == 1 and not tdoc["partial"])
    checks["relay_lane_federated"] = any(
        r["lane"] == "relay" and not r["error"] for r in tdoc["sources"])
    tracks = {e["args"]["name"] for e in export["traceEvents"]
              if e.get("name") == "process_name"}
    checks["perfetto_per_process_tracks"] = {
        "prefill replica", "decode replica"} <= tracks
    doc["trace_summary"] = {
        "root_ms": tdoc["root_duration_ms"],
        "phases": tdoc["phases"],
        "critical_path": tdoc["critical_path"],
        "sources": tdoc["sources"],
    }
    with open(os.path.join(args.out, "trace.json"), "w") as f:
        json.dump(tdoc, f, indent=1)
    with open(os.path.join(args.out, "trace_perfetto.json"), "w") as f:
        json.dump(export, f)

    # -- arm 2: the slow replica surfaces on /fleet ------------------------
    print("== arm 2: /fleet outlier (one +30ms replica)", flush=True)
    spec = _iris_spec("fleet")
    fast = EngineService(spec)
    slow = FaultyEngine(EngineService(spec), FaultSpec(delay_s=0.03))
    store = DeploymentStore()
    store.register(spec, {"main": [fast, slow]})
    gw = ApiGateway(store, require_auth=False)
    imsg = SeldonMessage.from_json(
        json.dumps({"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}}))

    async def fleet_arm():
        await fast.predict(imsg)        # pay compile OFF the EWMAs
        await slow.inner.predict(imsg)
        for _ in range(80):
            await gw.predict(imsg)
        fdoc = await fleet.fleet_document(gw)
        # overlap-refusal + manifest on the same gateway
        status1, manifest = await fleet.profile_start(gw, duration_s=3.0)
        status2, _refused = await fleet.profile_start(gw, duration_s=1.0)
        status3, closed = await fleet.profile_stop(gw)
        killed_fleet = None
        os.environ["SELDON_TPU_FLEET"] = "0"
        try:
            killed_fleet = await fleet.fleet_document(gw)
            killed_trace = await fleet.federated_trace_document(
                gw, trace_id="ab" * 16)
        finally:
            del os.environ["SELDON_TPU_FLEET"]
        await gw.close()
        return (fdoc, status1, manifest, status2, status3, closed,
                killed_fleet, killed_trace)

    try:
        (fdoc, st1, manifest, st2, st3, closed, killed_fleet,
         killed_trace) = asyncio.run(fleet_arm())
    finally:
        asyncio.run(fast.close())
        asyncio.run(slow.inner.close())
    dep = fdoc["deployments"]["fleet/main"]
    outliers = dep["outliers"]
    doc["fleet_rollup"] = {
        "replicas": {
            k: {kk: v.get(kk) for kk in
                ("role", "ewma_ms", "picks", "staleness_s")}
            for k, v in dep["replicas"].items()
        },
        "median": dep["median"],
        "outliers": outliers,
    }
    checks["slow_replica_is_the_outlier"] = bool(
        outliers and outliers[0]["replica"] == "inprocess-1"
        and outliers[0]["ratio"] >= 1.5)
    checks["profile_manifest_written"] = (
        st1 == 200
        and any("artifact" in s for s in manifest["sources"]))
    checks["overlapping_window_refused"] = st2 == 409
    checks["profile_stop_finalizes"] = (
        st3 == 200 and closed["state"] == "closed")
    with open(os.path.join(args.out, "profile_manifest.json"), "w") as f:
        json.dump(closed, f, indent=1)
    checks["kill_switch_local_only"] = (
        killed_fleet["enabled"] is False
        and killed_trace["federated"] is False)

    failed = {k: v for k, v in checks.items() if not v}
    doc["ok"] = not failed
    out = os.path.join(args.out, "fleet.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(checks, indent=1))
    print(f"artifact: {out}")
    if failed:
        print(f"FAILED checks: {sorted(failed)}", file=sys.stderr)
        sys.exit(3)
    print("fleet demo: ALL CHECKS PASSED")


if __name__ == "__main__":
    main()
