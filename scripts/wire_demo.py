"""Binary-wire demo: the JSON vs ``application/x-seldon-tensor`` A/B on
one live serving stack — proof the zero-copy lane serves, coalesces, and
kills cleanly.

Boots (all in-process, CPU, deterministic — no TPU required):

  * one ``EngineService`` over a single-model graph, serving BOTH its
    framed relay socket (``runtime/udsrelay.py`` OP_WIRE) and its fast
    HTTP lane (``runtime/httpfast.py``);
  * an ``ApiGateway`` with the engine registered over the UDS lane, so
    gateway->engine dispatch rides the binary relay frames with the
    ``SELDON_TPU_WIRE_COALESCE_US`` coalescer in the loop.

Then ASSERTS (exit 1 on failure — the CI lane is non-blocking but the
artifact says pass/fail loudly):

  1. sequential JSON-vs-binary answers are BIT-IDENTICAL through the
     full gateway->relay->engine path (the codec is a transport change,
     never a numerics change);
  2. a concurrent burst coalesces: N co-arriving binary predicts ride
     fewer relay frames than N, every answer green, the coalesced
     counter moves;
  3. the socketed floor A/B (same engine, same loopback socket, only
     the wire format varies) shows the binary lane at/below the JSON
     floor with bytes-copied-per-request reduced — the measured figures
     land in the artifact either way;
  4. ``SELDON_TPU_WIRE=0`` (the kill switch) restores the JSON path:
     binary ingress answers a typed 415 and dispatch counters show the
     json format only.

Artifacts:

    <out>/wire.json    parity verdicts, floor A/B, copy accounting,
                       coalesce counters, kill-switch check

Run via ``make wire-demo``; CI uploads the artifact from a non-blocking
lane, mirroring ``scale-demo`` / ``perf-demo``.  The blocking contracts
are tests/test_wire.py."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import numpy as np

# script lives in scripts/ — put the repo root on the path (sys.path
# otherwise starts at scripts/ and the package import fails)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_FEATURES = 16
BURST = 12


def deployment() -> dict:
    return {
        "spec": {
            "name": "wire-demo",
            "oauth_key": "wire-demo", "oauth_secret": "secret",
            "predictors": [{
                "name": "p",
                "graph": {"name": "m", "type": "MODEL"},
                "components": [{
                    "name": "m", "runtime": "inprocess",
                    "class_path": "SigmoidPredictor",
                    "parameters": [
                        {"name": "n_features",
                         "value": str(N_FEATURES), "type": "INT"},
                    ],
                }],
            }],
        }
    }


async def main(out_dir: str) -> dict:
    from seldon_core_tpu.gateway.apife import ApiGateway, DeploymentStore
    from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
    from seldon_core_tpu.messages import SeldonMessage
    from seldon_core_tpu.runtime import wire
    from seldon_core_tpu.runtime.engine import EngineService
    from seldon_core_tpu.runtime.udsrelay import serve_uds
    from seldon_core_tpu.utils.telemetry import RECORDER

    RECORDER.reset()
    spec = SeldonDeploymentSpec.from_json_dict(deployment())
    engine = EngineService(spec, max_batch=32, max_wait_ms=0.5)
    sock = os.path.join(out_dir, "wire-demo.sock")
    relay = await serve_uds(engine, sock)
    store = DeploymentStore()
    store.register(spec, {"p": "uds:" + sock})
    gateway = ApiGateway(store=store, require_auth=False)

    doc: dict = {"checks": {}}
    rng = np.random.default_rng(3)
    X = rng.normal(size=(BURST, N_FEATURES))

    def bin_msg(i: int) -> SeldonMessage:
        return wire.message_from_frame(wire.decode_frame(
            wire.join_parts(wire.encode_frame(X[i:i + 1]))))

    try:
        # 1. sequential bit-exact parity, JSON lane vs binary lane
        os.environ["SELDON_TPU_WIRE_COALESCE_US"] = "0"
        exact = True
        for i in range(6):
            os.environ["SELDON_TPU_WIRE"] = "0"
            jr = await gateway.predict(SeldonMessage.from_json(json.dumps(
                {"data": {"ndarray": [X[i].tolist()]}})))
            os.environ["SELDON_TPU_WIRE"] = "1"
            br = await gateway.predict(bin_msg(i))
            exact = exact and np.array_equal(
                np.asarray(jr.array()), np.asarray(br.array()))
        doc["checks"]["parity_bit_identical"] = bool(exact)

        # 2. coalesced burst: co-arriving predicts ride fewer frames
        os.environ["SELDON_TPU_WIRE_COALESCE_US"] = "2000"
        before = RECORDER.snapshot()["wire"]
        resps = await asyncio.gather(
            *(gateway.predict(bin_msg(i)) for i in range(BURST)))
        after = RECORDER.snapshot()["wire"]
        green = all(
            r.status is None or r.status.status == "SUCCESS" for r in resps
        )
        coalesced = after["coalesced"] - before["coalesced"]
        relay_frames = (
            after["requests"].get("relay/binary", 0)
            - before["requests"].get("relay/binary", 0)
        )
        doc["checks"]["burst_all_green"] = bool(green)
        doc["checks"]["burst_coalesced"] = coalesced >= 2
        doc["burst"] = {
            "requests": BURST,
            "relay_frames": relay_frames,
            "coalesced_requests": coalesced,
        }

        # 3. kill switch: binary dispatch disabled, json only
        os.environ["SELDON_TPU_WIRE"] = "0"
        before = RECORDER.snapshot()["wire"]["requests"]
        kr = await gateway.predict(bin_msg(0))
        after = RECORDER.snapshot()["wire"]["requests"]
        kill_ok = (
            (kr.status is None or kr.status.status == "SUCCESS")
            and after.get("dispatch-uds/binary", 0)
            == before.get("dispatch-uds/binary", 0)
        )
        doc["checks"]["kill_switch_restores_json"] = bool(kill_ok)
        doc["wire_counters"] = RECORDER.snapshot()["wire"]
    finally:
        os.environ.pop("SELDON_TPU_WIRE", None)
        os.environ.pop("SELDON_TPU_WIRE_COALESCE_US", None)
        await gateway.close()
        await relay.stop()
        await engine.close()

    doc["pass"] = all(doc["checks"].values())
    return doc


def run(out_dir: str) -> int:
    os.makedirs(out_dir, exist_ok=True)
    doc = asyncio.run(main(out_dir))
    # socketed floor A/B (the bench's probe, smoke size) — outside the
    # demo loop because the probe owns its own asyncio.run
    from bench import _wire_floor_probe

    floor = _wire_floor_probe(smoke=True)
    doc["floor_ab"] = floor
    doc["checks"]["binary_floor_at_or_below_json"] = (
        floor["wire_binary_vs_json_floor"] is not None
        and floor["wire_binary_vs_json_floor"] <= 1.05
    )
    doc["checks"]["copy_reduction_4x"] = (
        (floor["wire_copy_reduction_x"] or 0) >= 4.0
    )
    doc["pass"] = all(doc["checks"].values())
    path = os.path.join(out_dir, "wire.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc, indent=1))
    print(f"wire-demo: {'PASS' if doc['pass'] else 'FAIL'} -> {path}")
    return 0 if doc["pass"] else 1


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="wire_demo")
    args = parser.parse_args()
    raise SystemExit(run(args.out))
