"""Safe traffic lifecycle: shadow mirroring (gateway/shadow.py), firehose
replay (runtime/replay.py), and canary rollouts with automatic rollback
(operator/rollouts.py) — including the canary_deployment.json example end
to end through the operator materializer and the gateway's weighted
split."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from seldon_core_tpu.gateway.apife import ApiGateway, DeploymentStore
from seldon_core_tpu.gateway.firehose import Firehose
from seldon_core_tpu.gateway.shadow import (
    ShadowConfig,
    shadow_config_from_spec,
)
from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import SeldonMessage, prediction_delta
from seldon_core_tpu.operator.rollouts import (
    GatewaySignals,
    RolloutController,
    RolloutGates,
    RolloutPlan,
    plan_from_annotations,
)
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.replay import (
    ReplayGates,
    load_firehose_events,
    replay_events,
    replay_file,
)
from seldon_core_tpu.testing.faults import FaultSpec, FaultyNodeRuntime
from seldon_core_tpu.utils.quality import QUALITY
from seldon_core_tpu.utils.telemetry import RECORDER

N_FEATURES = 8


def _predictor(name, seed, replicas, annotations=None, node=None):
    node = node or f"clf-{name}"
    return {
        "name": name,
        "replicas": replicas,
        "annotations": annotations or {},
        "graph": {"name": node, "type": "MODEL"},
        "components": [{
            "name": node, "runtime": "inprocess",
            "class_path": "SigmoidPredictor",
            "parameters": [
                {"name": "n_features", "value": str(N_FEATURES),
                 "type": "INT"},
                {"name": "seed", "value": str(seed), "type": "INT"},
            ],
        }],
    }


def _spec(name="life-dep", shadow=True, sample="1.0", extra_ann=None,
          cand_seed=1, node=None):
    """``node`` names both predictors' unit: a unit's init key is derived
    from its NAME as well as its seed (graph/interpreter.py unit_rngs), so
    "the same weights" needs both to agree."""
    ann = {"seldon.io/shadow-sample": sample,
           "seldon.io/shadow-budget-per-s": "10000"}
    ann.update(extra_ann or {})
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {
            "name": name, "oauth_key": "k", "oauth_secret": "s",
            "annotations": ann,
            "predictors": [
                _predictor("main", 0, 3, node=node),
                _predictor(
                    "cand", cand_seed, 1,
                    {"seldon.io/shadow": "true"} if shadow else None,
                    node=node,
                ),
            ],
        }
    })


def _msg(rng, shift=0.0, rows=1):
    return SeldonMessage.from_array(
        rng.normal(shift, 1.0, size=(rows, N_FEATURES)).astype(np.float64)
    )


async def _gateway(spec, firehose=None, engines=None, seed=7):
    store = DeploymentStore()
    engines = engines or {
        p.name: EngineService(spec, p.name, max_batch=16, max_wait_ms=0.5)
        for p in spec.predictors
    }
    store.register(spec, engines)
    gw = ApiGateway(store=store, firehose=firehose, seed=seed)
    token = store.issue_token("k", "s")
    return gw, store, engines, token


# ---------------------------------------------------------------------------
# shadow mirroring
# ---------------------------------------------------------------------------


def test_shadow_config_from_spec_and_weight_zero_registration():
    spec = _spec(extra_ann={
        "seldon.io/shadow-deadline-ms": "750",
        "seldon.io/shadow-max-concurrency": "3",
    })
    cfg = shadow_config_from_spec(spec)
    assert cfg == ShadowConfig(predictor="cand", sample=1.0,
                               max_concurrency=3, budget_per_s=10000.0,
                               deadline_ms=750.0)
    store = DeploymentStore()
    store.register(spec, {"main": "http://a", "cand": "http://b"})
    reg = store._by_key["k"]
    assert {n: w for n, w, _ in reg.engines} == {"main": 3, "cand": 0}
    assert reg.shadow == cfg
    # no annotation -> no shadow, replica weights untouched
    store.register(_spec(shadow=False), {"main": "http://a",
                                         "cand": "http://b"})
    reg = store._by_key["k"]
    assert {n: w for n, w, _ in reg.engines} == {"main": 3, "cand": 1}
    assert reg.shadow is None


def test_shadow_mirrors_and_diffs_live_traffic():
    async def run():
        # identical candidate — same unit name, same seed, so the same
        # trained weights: zero divergence
        spec = _spec(cand_seed=0, node="clf")
        gw, store, engines, token = await _gateway(spec)
        rng = np.random.default_rng(0)
        for _ in range(20):
            resp = await gw.predict(_msg(rng), token)
            assert resp.meta.requestPath["predictor"] == "main"
        await gw.shadow.drain()
        row = gw.shadow.document()["deployments"]["life-dep"]
        assert row["mirrored"] + row["capped"] == 20  # sample 1.0
        assert row["mirrored"] > 0
        assert row["disagreement"]["mean"] == 0.0  # same weights, same answer
        assert row["error_delta"] == {
            "live": 0, "shadow": 0, "live_rate": 0.0, "shadow_rate": 0.0,
        }
        # surfaces: /stats block + recorder mirrors + metric families
        assert gw.stats()["shadow"]["deployments"]["life-dep"][
            "mirrored"] == row["mirrored"]
        snap = RECORDER.snapshot()["traffic_lifecycle"]
        assert snap["shadow"].get("mirrored", 0) >= row["mirrored"]
        await gw.close()

    asyncio.run(run())


def test_shadow_divergent_candidate_scores_disagreement():
    async def run():
        spec = _spec(cand_seed=1)
        gw, store, engines, token = await _gateway(spec)
        rng = np.random.default_rng(1)
        for _ in range(60):
            await gw.predict(_msg(rng, rows=4), token)
            if gw.shadow.document()["deployments"].get(
                "life-dep", {}
            ).get("inflight", 0) >= 6:
                await gw.shadow.drain()  # keep under the concurrency cap
        await gw.shadow.drain()
        rate = gw.shadow.disagreement_rate("life-dep")
        assert rate is not None and rate > 0.0
        await gw.close()

    asyncio.run(run())


def test_shadow_never_on_the_live_response_path():
    """A shadow predictor 300 ms slower than live must not move live
    latency: the mirror is scheduled after the live answer exists."""

    class SlowEngine:
        def __init__(self, inner):
            self.inner = inner
            self.calls = 0

        async def predict(self, msg):
            self.calls += 1
            await asyncio.sleep(0.3)
            return await self.inner.predict(msg)

    async def run():
        spec = _spec()
        engines = {
            "main": EngineService(spec, "main"),
            "cand": SlowEngine(EngineService(spec, "cand")),
        }
        gw, store, _, token = await _gateway(spec, engines=engines)
        rng = np.random.default_rng(2)
        # warm the live engine first: the initial jit compile must not be
        # charged to the latency comparison
        await engines["main"].predict(_msg(rng))
        t0 = time.perf_counter()
        for _ in range(5):
            resp = await gw.predict(_msg(rng), token)
            assert resp.status is None or resp.status.status == "SUCCESS"
        live_wall = time.perf_counter() - t0
        # 5 sequential live requests vs 5 mirrored 300 ms hops: if the
        # mirror were on the response path the wall would exceed 1.5 s
        assert live_wall < 1.0, live_wall
        await gw.shadow.drain(timeout_s=5.0)
        assert engines["cand"].calls == 5
        await gw.close()

    asyncio.run(run())


def test_shadow_concurrency_cap_drops_instead_of_queueing():
    class HangingEngine:
        def __init__(self):
            self.started = 0
            self.release = asyncio.Event()

        async def predict(self, msg):
            self.started += 1
            await self.release.wait()
            return SeldonMessage.from_array(np.zeros((1, 2)))

    async def run():
        spec = _spec(extra_ann={"seldon.io/shadow-max-concurrency": "2"})
        hanging = HangingEngine()
        engines = {"main": EngineService(spec, "main"), "cand": hanging}
        gw, store, _, token = await _gateway(spec, engines=engines)
        rng = np.random.default_rng(3)
        for _ in range(10):
            await gw.predict(_msg(rng), token)
            await asyncio.sleep(0)  # let mirror tasks start
        row = gw.shadow.document()["deployments"]["life-dep"]
        assert row["inflight"] == 2  # the cap
        assert row["capped"] == 8   # the rest dropped, never queued
        hanging.release.set()
        await gw.shadow.drain()
        await gw.close()

    asyncio.run(run())


def test_shadow_deadline_clamps_a_wedged_shadow_predictor():
    class WedgedEngine:
        async def predict(self, msg):
            await asyncio.sleep(30)
            return SeldonMessage.from_array(np.zeros((1, 2)))

    async def run():
        spec = _spec(extra_ann={"seldon.io/shadow-deadline-ms": "50"})
        engines = {"main": EngineService(spec, "main"),
                   "cand": WedgedEngine()}
        gw, store, _, token = await _gateway(spec, engines=engines)
        rng = np.random.default_rng(4)
        await gw.predict(_msg(rng), token)
        t0 = time.perf_counter()
        await gw.shadow.drain(timeout_s=10.0)
        assert time.perf_counter() - t0 < 5.0  # clamped, not 30 s
        row = gw.shadow.document()["deployments"]["life-dep"]
        assert row["mirrored"] == 1
        # the wedged mirror accounts as a shadow error, live side clean
        assert row["error_delta"]["shadow"] == 1
        assert row["error_delta"]["live"] == 0
        await gw.close()

    asyncio.run(run())


def test_shadow_kill_switch(monkeypatch):
    async def run():
        spec = _spec()
        gw, store, engines, token = await _gateway(spec)
        monkeypatch.setenv("SELDON_TPU_SHADOW", "0")
        rng = np.random.default_rng(5)
        for _ in range(6):
            await gw.predict(_msg(rng), token)
        await gw.shadow.drain()
        assert gw.shadow.document()["deployments"] == {}
        assert gw.shadow.document()["enabled"] is False
        # flip back on without restart
        monkeypatch.delenv("SELDON_TPU_SHADOW")
        await gw.predict(_msg(rng), token)
        await gw.shadow.drain()
        assert gw.shadow.document()["deployments"]["life-dep"][
            "mirrored"] + gw.shadow.document()["deployments"]["life-dep"][
            "capped"] == 1
        await gw.close()

    asyncio.run(run())


def test_shadow_http_route():
    async def run():
        import aiohttp
        from aiohttp import web

        from seldon_core_tpu.gateway.apife import make_gateway_app

        spec = _spec()
        gw, store, engines, token = await _gateway(spec)
        app = make_gateway_app(gw)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        rng = np.random.default_rng(6)
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{port}/api/v0.1/predictions",
                data=_msg(rng).to_json(),
                headers={"Authorization": f"Bearer {token}"},
            ) as r:
                assert r.status == 200
            await gw.shadow.drain()
            async with s.get(f"http://127.0.0.1:{port}/shadow") as r:
                assert r.status == 200
                doc = await r.json()
                assert "life-dep" in doc["deployments"]
            async with s.get(f"http://127.0.0.1:{port}/rollouts") as r:
                assert r.status == 404  # no controller attached
            gw.rollouts = RolloutController(store, lambda plan: {})
            async with s.get(f"http://127.0.0.1:{port}/rollouts") as r:
                assert r.status == 200
                assert (await r.json())["rollouts"] == {}
        await runner.cleanup()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# firehose replay
# ---------------------------------------------------------------------------


async def _record_firehose(tmp_path, n=16, cand_seed=1):
    spec = _spec(shadow=False, cand_seed=cand_seed)
    fh = Firehose(base_dir=str(tmp_path))
    gw, store, engines, token = await _gateway(spec, firehose=fh)
    fh.start()
    rng = np.random.default_rng(7)
    for _ in range(n):
        await gw.predict(_msg(rng, rows=2), token)
    await fh.stop()
    await gw.close()
    return os.path.join(str(tmp_path), "life-dep.jsonl"), engines


def test_replay_identical_candidate_passes(tmp_path):
    async def run():
        path, engines = await _record_firehose(tmp_path)
        # most traffic went to 'main' (3:1); replay against main = parity
        doc = await replay_file(path, engines["main"])
        # a handful of recorded lines were served by 'cand' (weight 1):
        # those disagree — filter them out via a permissive gate instead
        # of pretending the mix is identical
        assert doc["counts"]["replayed"] == 16
        assert doc["candidate_latency_ms"]["count"] == 16
        assert doc["disagreement"]["count"] == 16
        # strict parity check: replay against the engines that served
        disagree_free = await replay_events(
            [e for e in load_firehose_events(path)
             if e["response"]["meta"]["requestPath"].get("predictor")
             == "main"],
            engines["main"],
        )
        assert disagree_free["verdict"] == "pass", disagree_free["reasons"]
        assert disagree_free["disagreement"]["mean"] == 0.0
        assert disagree_free["prediction_psi"] is not None
        assert disagree_free["prediction_psi"] < 0.05

    asyncio.run(run())


def test_replay_flags_divergent_candidate(tmp_path):
    async def run():
        path, engines = await _record_firehose(tmp_path)
        spec2 = _spec(shadow=False, cand_seed=9)
        drifted = EngineService(spec2, "cand")
        doc = await replay_file(path, drifted)
        assert doc["verdict"] == "fail"
        assert any(r.startswith("disagreement") for r in doc["reasons"])
        await drifted.close()

    asyncio.run(run())


def test_replay_flags_error_rate_regression(tmp_path):
    """A candidate whose graph node hard-fails (testing/faults.py at
    100% error rate) fails the vet on the error-rate gate."""

    async def run():
        path, engines = await _record_firehose(tmp_path)
        from seldon_core_tpu.graph.defaulting import default_and_validate
        from seldon_core_tpu.graph.interpreter import GraphExecutor

        spec2 = _spec(shadow=False)
        default_and_validate(spec2)
        executor = GraphExecutor(spec2.predictor("cand"))
        executor.runtimes["clf-cand"] = FaultyNodeRuntime(
            executor.runtimes["clf-cand"], FaultSpec(error_rate=1.0),
        )
        broken = EngineService(
            spec2, "cand", extra_runtimes=executor.runtimes,
        )
        doc = await replay_file(path, broken)
        assert doc["verdict"] == "fail"
        assert doc["error_rate"]["candidate"] == 1.0
        assert any(r.startswith("error_rate") for r in doc["reasons"])
        await broken.close()

    asyncio.run(run())


def test_replay_recorded_pace_honors_time_warp():
    async def run():
        class Instant:
            async def predict(self, msg):
                return SeldonMessage.from_array(np.zeros((1, 2)))

        base = 1000.0
        events = [
            {"ts": base + i * 0.08,
             "request": SeldonMessage.from_array(
                 np.zeros((1, 2))).to_json_dict(),
             "response": SeldonMessage.from_array(
                 np.zeros((1, 2))).to_json_dict()}
            for i in range(5)
        ]
        gates = ReplayGates(min_requests=0)
        t0 = time.perf_counter()
        await replay_events(events, Instant(), pace="recorded", speed=1.0,
                            gates=gates)
        paced = time.perf_counter() - t0
        assert paced >= 0.3  # 4 gaps x 80 ms
        t0 = time.perf_counter()
        await replay_events(events, Instant(), pace="recorded", speed=8.0,
                            gates=gates)
        warped = time.perf_counter() - t0
        assert warped < paced / 2  # the time-warp knob works

    asyncio.run(run())


def test_replay_skips_control_plane_events(tmp_path):
    path = tmp_path / "dep.jsonl"
    req = SeldonMessage.from_array(np.zeros((1, 2))).to_json_dict()
    lines = [
        {"puid": "", "deployment": "dep", "ts": 1.0, "event": "rollback",
         "reason": "drift"},
        {"puid": "x", "deployment": "dep", "ts": 2.0,
         "request": req, "response": req},
        {"puid": "y", "deployment": "other", "ts": 3.0,
         "request": req, "response": req},
    ]
    with open(path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
        f.write('{"torn": ')  # producer mid-write
    events = load_firehose_events(str(path), deployment="dep")
    assert len(events) == 1 and events[0]["puid"] == "x"


# ---------------------------------------------------------------------------
# rollout controller
# ---------------------------------------------------------------------------


def _store_with(name="dep"):
    spec = SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": name, "oauth_key": name, "predictors": [
            _predictor("main", 0, 99), _predictor("cand", 1, 1),
        ]}})
    store = DeploymentStore()
    store.register(spec, {"main": "http://a", "cand": "http://b"})
    return store, spec


def _weights(store, key="dep"):
    return {n: w for n, w, _ in store._by_key[key].engines}


def test_set_weights_in_memory_store():
    store, _ = _store_with()
    store.set_weights("dep", {"cand": 25, "main": 75})
    assert _weights(store) == {"main": 75, "cand": 25}
    with pytest.raises(KeyError):
        store.set_weights("dep", {"nope": 1})
    with pytest.raises(KeyError):
        store.set_weights("ghost-dep", {"cand": 1})


def test_sqlite_store_set_weights_and_shadow_roundtrip(tmp_path):
    from seldon_core_tpu.gateway.state import SqliteDeploymentStore

    store = SqliteDeploymentStore(str(tmp_path / "gw.db"))
    spec = _spec()
    store.register(spec, {"main": "http://a", "cand": "http://b"})
    reg = store._registration("k")
    assert {n: w for n, w, _ in reg.engines} == {"main": 3, "cand": 0}
    assert reg.shadow is not None and reg.shadow.predictor == "cand"
    rev = store.revision()
    store.set_weights("life-dep", {"cand": 5, "main": 95})
    assert store.revision() > rev  # other gateway replicas see the shift
    reg = store._registration("k")
    assert {n: w for n, w, _ in reg.engines} == {"main": 95, "cand": 5}
    assert reg.shadow is not None  # the shift must not drop the policy
    with pytest.raises(KeyError):
        store.set_weights("life-dep", {"nope": 1})
    store.close()


def test_rollout_staged_promotion_and_stage_gating():
    store, _ = _store_with()
    clock = [0.0]
    sig = {"requests": 0, "errors": 0, "drift": 0.0}
    ctrl = RolloutController(store, lambda plan: dict(sig),
                             clock=lambda: clock[0])
    plan = RolloutPlan("dep", "cand", "main", stages=(1, 5, 25, 100),
                       hold_s=10.0, config_hash="h1",
                       gates=RolloutGates(min_requests=5))
    sig["requests"] = 40  # pre-rollout traffic: stage deltas must ignore it
    ctrl.apply(plan)
    assert ctrl.tick()[0]["decision"] == "advance"
    assert _weights(store) == {"main": 99, "cand": 1}
    # held: not enough time (plenty of traffic)
    clock[0] += 5
    sig["requests"] = 90
    assert ctrl.tick()[0]["decision"] == "hold"
    # held: enough time but not enough candidate traffic SINCE the stage
    # entered (90 - 40-at-entry = 50... reset to prove the delta rule)
    clock[0] += 6
    sig["requests"] = 43  # 3 since entry < min_requests 5
    assert ctrl.tick()[0]["decision"] == "hold"
    assert _weights(store) == {"main": 99, "cand": 1}
    # both satisfied -> next stage
    sig["requests"] = 50
    assert ctrl.tick()[0]["decision"] == "advance"
    assert _weights(store) == {"main": 95, "cand": 5}
    for _ in range(4):
        clock[0] += 11
        sig["requests"] += 50
        ctrl.tick()
    st = ctrl.status_block("dep")
    assert st["state"] == "promoted" and st["stage_percent"] == 100
    assert _weights(store) == {"main": 0, "cand": 100}


class _ListFirehose:
    def __init__(self):
        self.events = []

    def publish_event(self, deployment, kind, **fields):
        self.events.append({"deployment": deployment, "event": kind,
                            **fields})


def test_rollout_rollback_quarantine_and_surfaces():
    store, _ = _store_with()
    clock = [0.0]
    sig = {"requests": 100, "errors": 0, "drift": 0.0}
    fh = _ListFirehose()
    ctrl = RolloutController(store, lambda plan: dict(sig), firehose=fh,
                             clock=lambda: clock[0])
    plan = RolloutPlan("dep", "cand", "main", hold_s=0.0, config_hash="h1",
                       gates=RolloutGates(min_requests=0))
    ctrl.apply(plan)
    ctrl.tick()
    assert _weights(store) == {"main": 99, "cand": 1}
    before = dict(RECORDER.rollbacks)
    sig["drift"] = 0.9
    clock[0] += 1
    decision = ctrl.tick()[0]
    assert decision["decision"] == "rollback"
    assert decision["reason"] == "drift"
    # ONE step: weights snapped all the way back, not to a lower stage
    assert _weights(store) == {"main": 100, "cand": 0}
    # counter + firehose event + status surfaces
    assert RECORDER.rollbacks.get("drift", 0) == before.get("drift", 0) + 1
    assert [e for e in fh.events if e["event"] == "rollback"]
    assert ctrl.snapshot()["rollouts"]["dep"]["state"] == "rolled_back"
    assert ctrl.document()["quarantined"] == {"dep": ["h1"]}
    # quarantine: the same hash never rolls out again...
    ctrl.apply(plan)
    clock[0] += 100
    assert ctrl.tick() == []
    assert _weights(store) == {"main": 100, "cand": 0}
    # ...but a CHANGED spec does
    sig["drift"] = 0.0
    plan2 = RolloutPlan("dep", "cand", "main", hold_s=0.0,
                        config_hash="h2", gates=RolloutGates(min_requests=0))
    ctrl.apply(plan2)
    assert ctrl.tick()[0]["decision"] == "advance"
    assert _weights(store)["cand"] == 1
    # flip-flop guard: h2 also rolls back; re-applying the OLD bad hash
    # h1 must stay quarantined (the history is a set, not last-one-wins)
    sig["drift"] = 0.9
    clock[0] += 1
    assert ctrl.tick()[0]["decision"] == "rollback"
    ctrl.apply(plan)  # h1 again
    clock[0] += 100
    assert ctrl.tick() == []
    assert ctrl.status_block("dep")["state"] == "rolled_back"
    assert ctrl.document()["quarantined"] == {"dep": ["h1", "h2"]}
    assert _weights(store) == {"main": 100, "cand": 0}


def test_rollout_error_rate_gate_with_injected_faults():
    """The error-rate gate fed by REAL gateway traffic accounting: the
    candidate's graph node hard-fails via testing/faults.py, failures
    surface as FAILURE answers at the gateway, the stage rolls back."""

    async def run():
        from seldon_core_tpu.graph.defaulting import default_and_validate
        from seldon_core_tpu.graph.interpreter import GraphExecutor

        spec = _spec(shadow=False)
        default_and_validate(spec)
        executor = GraphExecutor(spec.predictor("cand"))
        executor.runtimes["clf-cand"] = FaultyNodeRuntime(
            executor.runtimes["clf-cand"], FaultSpec(error_rate=1.0),
        )
        engines = {
            "main": EngineService(spec, "main"),
            "cand": EngineService(spec, "cand",
                                  extra_runtimes=executor.runtimes),
        }
        gw, store, _, token = await _gateway(spec, engines=engines)
        ctrl = RolloutController(store, GatewaySignals(gw))
        plan = RolloutPlan(
            "life-dep", "cand", "main", stages=(50, 100), hold_s=0.0,
            config_hash="h1",
            gates=RolloutGates(max_error_rate=0.1, max_drift=None,
                               min_requests=8),
        )
        ctrl.apply(plan)
        ctrl.tick()  # stage 1: candidate at 50%
        rng = np.random.default_rng(8)
        rolled_back = None
        for _ in range(6):
            for _ in range(16):
                await gw.predict(_msg(rng), token)
            decisions = ctrl.tick()
            if decisions and decisions[0]["decision"] == "rollback":
                rolled_back = decisions[0]
                break
        assert rolled_back is not None
        assert rolled_back["reason"] == "error_rate"
        assert _weights(store, "k") == {"main": 100, "cand": 0}
        # baseline kept serving the whole time
        count, errors = gw.predictor_traffic("life-dep", "main")
        assert count > 0 and errors == 0
        await gw.close()

    asyncio.run(run())


def test_shadow_contract_break_reads_as_maximal_disagreement():
    """A candidate that changes the output SHAPE must score disagree=1.0
    in the mirror window, not silently fall out of it — the rollout's
    shadow gate would otherwise be blind to a contract break."""

    class WrongShapeEngine:
        async def predict(self, msg):
            return SeldonMessage.from_array(np.zeros((1, 7)))

    async def run():
        spec = _spec()
        engines = {"main": EngineService(spec, "main"),
                   "cand": WrongShapeEngine()}
        gw, store, _, token = await _gateway(spec, engines=engines)
        rng = np.random.default_rng(9)
        for _ in range(4):
            await gw.predict(_msg(rng), token)
        await gw.shadow.drain()
        assert gw.shadow.disagreement_rate("life-dep") == 1.0
        await gw.close()

    asyncio.run(run())


def test_replay_flags_contract_break(tmp_path):
    class WrongShapeEngine:
        async def predict(self, msg):
            return SeldonMessage.from_array(np.zeros((1, 7)))

    async def run():
        path, _engines = await _record_firehose(tmp_path, n=12)
        doc = await replay_file(path, WrongShapeEngine())
        assert doc["verdict"] == "fail"
        assert doc["disagreement"]["mean"] == 1.0
        assert doc["counts"]["incomparable"] == 12

    asyncio.run(run())


def test_rollout_scrape_outage_at_stage_entry_backfills_baseline():
    """A one-tick signal outage while advancing must not zero the stage
    entry counters: the first good read becomes the baseline and the
    stage clock restarts, so min_requests means THIS stage's traffic."""
    store, _ = _store_with()
    clock = [0.0]
    state = {"fail": True, "requests": 10_000, "errors": 0}

    def signals(plan):
        if state["fail"]:
            raise ConnectionError("scrape down")
        return {"requests": state["requests"], "errors": state["errors"]}

    ctrl = RolloutController(store, signals, clock=lambda: clock[0])
    plan = RolloutPlan("dep", "cand", "main", stages=(5, 100), hold_s=5.0,
                       config_hash="h1",
                       gates=RolloutGates(min_requests=20,
                                          max_error_rate=0.05))
    ctrl.apply(plan)
    ctrl.tick()  # advance; entry read fails -> entry counters None
    state["fail"] = False
    clock[0] += 100  # ages past hold_s — but the clock must restart
    assert ctrl.tick()[0]["decision"] == "hold"  # backfilled, 0 new reqs
    # 100 new requests at this stage, 50 of them errors: without the
    # backfill this would read 50/10100 = 0.5% and promote
    clock[0] += 6
    state["requests"] += 100
    state["errors"] += 50
    decision = ctrl.tick()[0]
    assert decision["decision"] == "rollback"
    assert decision["reason"] == "error_rate"
    assert _weights(store) == {"main": 100, "cand": 0}


def test_rollout_rolls_back_when_signals_unavailable():
    store, _ = _store_with()

    def broken(plan):
        raise ConnectionError("scrape target down")

    ctrl = RolloutController(store, broken, clock=lambda: 0.0)
    plan = RolloutPlan("dep", "cand", "main", hold_s=0.0, config_hash="h1")
    ctrl.apply(plan)
    ctrl.tick()  # advance to stage 1
    decision = ctrl.tick()[0]
    assert decision["decision"] == "rollback"
    assert decision["reason"] == "signals_unavailable"
    assert _weights(store) == {"main": 100, "cand": 0}


def test_rollout_kill_switch(monkeypatch):
    store, _ = _store_with()
    ctrl = RolloutController(store, lambda plan: {"requests": 100})
    plan = RolloutPlan("dep", "cand", "main", hold_s=0.0, config_hash="h1")
    ctrl.apply(plan)
    monkeypatch.setenv("SELDON_TPU_ROLLOUTS", "0")
    assert ctrl.tick() == []
    assert ctrl.tick_deployment("dep") is None
    assert _weights(store) == {"main": 99, "cand": 1}
    monkeypatch.delenv("SELDON_TPU_ROLLOUTS")
    assert ctrl.tick()[0]["decision"] == "advance"


def test_rollout_plan_validation():
    with pytest.raises(ValueError):
        RolloutPlan("d", "cand", "cand")  # candidate == baseline
    with pytest.raises(ValueError):
        RolloutPlan("d", "c", "m", stages=(5, 1))  # not increasing
    with pytest.raises(ValueError):
        RolloutPlan("d", "c", "m", stages=(0, 100))  # 0% stage
    plan = RolloutPlan("d", "c", "m", stages=(1, 5))
    assert plan.stages == (1, 5, 100)  # terminal 100 appended


def test_plan_from_annotations_contract():
    spec = _spec(shadow=False, extra_ann={
        "seldon.io/canary": "cand",
        "seldon.io/canary-stages": "2,20",
        "seldon.io/canary-hold-s": "7",
        "seldon.io/canary-max-drift": "0.5",
        "seldon.io/canary-max-shadow-disagreement": "none",
        "seldon.io/canary-min-requests": "3",
    })
    plan = plan_from_annotations(spec, config_hash="h")
    assert plan.candidate == "cand" and plan.baseline == "main"
    assert plan.stages == (2, 20, 100)
    assert plan.hold_s == 7.0
    assert plan.gates.max_drift == 0.5
    assert plan.gates.max_shadow_disagreement is None
    assert plan.gates.min_requests == 3
    assert plan.config_hash == "h"
    # no annotation -> no plan
    assert plan_from_annotations(_spec(shadow=False), "h") is None
    # unknown predictor -> typed error
    bad = _spec(shadow=False, extra_ann={"seldon.io/canary": "ghost"})
    with pytest.raises(ValueError):
        plan_from_annotations(bad, "h")


def test_reconciler_drives_rollout_from_cr_annotations():
    from seldon_core_tpu.operator.reconciler import FakeKubeApi, Reconciler

    cr = {
        "apiVersion": "machinelearning.seldon.io/v1alpha2",
        "kind": "SeldonDeployment",
        "metadata": {"name": "dep", "annotations": {
            "seldon.io/canary": "cand",
            "seldon.io/canary-hold-s": "0",
            "seldon.io/canary-min-requests": "0",
            "seldon.io/canary-stages": "5,100",
        }},
        "spec": {"name": "dep", "predictors": [
            _predictor("main", 0, 3), _predictor("cand", 1, 1),
        ]},
    }
    store, _ = _store_with()
    sig = {"requests": 100, "errors": 0, "drift": 0.0}
    ctrl = RolloutController(store, lambda plan: dict(sig))
    api = FakeKubeApi()
    rec = Reconciler(api, rollouts=ctrl)
    api.create(cr)
    for _ in range(3):
        rec.run_once()
    status = api.get("SeldonDeployment", "default", "dep")["status"]
    assert status["rollout"]["state"] == "promoted"
    assert _weights(store) == {"main": 0, "cand": 100}
    # edit the spec (new config hash) with sick signals: stage 1 then
    # rollback, quarantined across further reconciles
    api.objects[("SeldonDeployment", "default", "dep")]["spec"][
        "annotations"] = {"note": "v2"}
    sig["drift"] = 2.0
    rec.run_once()
    rec.run_once()
    status = api.get("SeldonDeployment", "default", "dep")["status"]
    assert status["rollout"]["state"] == "rolled_back"
    assert status["rollout"]["rollback_reason"] == "drift"
    assert _weights(store) == {"main": 100, "cand": 0}
    rec.run_once()
    assert api.get("SeldonDeployment", "default", "dep")["status"][
        "rollout"]["state"] == "rolled_back"
    # CR deletion clears the rollout AND the quarantine
    api.delete("SeldonDeployment", "default", "dep")
    rec.run_once()
    assert ctrl.status_block("dep") is None
    assert ctrl.document()["quarantined"] == {}


def test_reconciler_surfaces_invalid_canary_annotation():
    from seldon_core_tpu.operator.reconciler import FakeKubeApi, Reconciler

    cr = {
        "apiVersion": "machinelearning.seldon.io/v1alpha2",
        "kind": "SeldonDeployment",
        "metadata": {"name": "dep", "annotations": {
            "seldon.io/canary": "ghost",
        }},
        "spec": {"name": "dep", "predictors": [
            _predictor("main", 0, 3), _predictor("cand", 1, 1),
        ]},
    }
    store, _ = _store_with()
    ctrl = RolloutController(store, lambda plan: {})
    api = FakeKubeApi()
    rec = Reconciler(api, rollouts=ctrl)
    api.create(cr)
    rec.run_once()
    status = api.get("SeldonDeployment", "default", "dep")["status"]
    assert status["rollout"]["state"] == "invalid"
    assert "ghost" in status["rollout"]["error"]
    assert _weights(store) == {"main": 99, "cand": 1}  # untouched


# ---------------------------------------------------------------------------
# the canary example, end to end
# ---------------------------------------------------------------------------


def test_canary_deployment_example_end_to_end(tmp_path):
    """examples/canary_deployment.json through the REAL pipeline:
    operator materialization -> two weighted predictors registered at the
    gateway -> 3:1 traffic split honored -> staged rollout -> rollback on
    injected drift -> weights snapped back, event in the firehose."""
    from seldon_core_tpu.operator.materializer import Materializer

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "canary_deployment.json")
    with open(path) as f:
        doc = json.load(f)

    async def run():
        QUALITY.reset()
        spec = SeldonDeploymentSpec.from_json_dict(doc)
        mat = Materializer(spawn_units=False)
        md = mat.apply(spec)
        assert set(md.engines) == {"main", "canary"}
        fh = Firehose(base_dir=str(tmp_path))
        gw = ApiGateway(store=mat.store, firehose=fh, seed=11)
        fh.start()
        token = mat.store.issue_token("canary-key", "canary-secret")
        rng = np.random.default_rng(0)

        async def drive(shift, n):
            served, failures = [], 0
            for _ in range(n):
                msg = SeldonMessage.from_array(
                    rng.normal(shift, 1.0, (1, 784)).astype(np.float64))
                resp = await gw.predict(msg, token)
                if resp.status is not None and \
                        resp.status.status == "FAILURE":
                    failures += 1
                served.append(resp.meta.requestPath["predictor"])
            return served, failures

        # the example's 75/25 replica-weighted split is honored
        served, failures = await drive(0.0, 80)
        assert failures == 0
        counts = {p: served.count(p) for p in set(served)}
        assert counts.get("main", 0) > counts.get("canary", 0) > 0
        # freeze the healthy window as the drift reference
        QUALITY.reference_control("freeze")

        # staged rollout of the canary, gated on drift
        ctrl = RolloutController(mat.store, GatewaySignals(gw),
                                 firehose=fh)
        gw.rollouts = ctrl
        plan = RolloutPlan(
            "mnist-canary", "canary", "main", stages=(5, 25, 100),
            hold_s=0.0, config_hash="v2",
            gates=RolloutGates(max_drift=0.25,
                               max_shadow_disagreement=None,
                               min_requests=4),
        )
        ctrl.apply(plan)
        assert ctrl.tick()[0]["decision"] == "advance"
        # injected drift: the live inputs shift away from the reference
        decision = None
        for _ in range(6):
            _, failures2 = await drive(3.0, 24)
            assert failures2 == 0  # rollback machinery never breaks live
            decisions = ctrl.tick()
            decision = decisions[0] if decisions else None
            if decision and decision["decision"] == "rollback":
                break
        assert decision is not None and \
            decision["decision"] == "rollback", decision
        assert decision["reason"] == "drift"
        reg_weights = {
            n: w for n, w, _ in mat.store._by_key["canary-key"].engines
        }
        assert reg_weights == {"main": 100, "canary": 0}
        assert ctrl.status_block("mnist-canary")["state"] == "rolled_back"
        # the rollback event landed in the firehose next to the traffic
        await fh.stop()
        events = load_firehose_events(
            os.path.join(str(tmp_path), "mnist-canary.jsonl"))
        assert events  # the request stream
        with open(os.path.join(str(tmp_path), "mnist-canary.jsonl")) as f:
            raw = [json.loads(x) for x in f if x.strip()]
        assert any(e.get("event") == "rollback" for e in raw)
        # /stats carries the rollout + rollback surfaces
        stats = gw.stats()
        assert stats["rollouts"]["rollouts"]["mnist-canary"][
            "state"] == "rolled_back"
        assert stats["telemetry"]["traffic_lifecycle"]["rollbacks"].get(
            "drift", 0) >= 1
        mat.delete("mnist-canary")
        await gw.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# prediction_delta (the shared disagreement rule)
# ---------------------------------------------------------------------------


def test_prediction_delta_rules():
    a = SeldonMessage.from_array(np.array([[0.1, 0.9], [0.8, 0.2]]))
    b = SeldonMessage.from_array(np.array([[0.2, 0.8], [0.4, 0.6]]))
    # row 2 flips argmax, row 1 doesn't: 50% disagreement
    assert prediction_delta(a, b)["disagree"] == 0.5
    assert prediction_delta(a, a) == {
        "comparable": True, "disagree": 0.0, "mean_abs_delta": 0.0}
    # scalar outputs: elementwise tolerance
    c = SeldonMessage.from_array(np.array([[1.0], [2.0]]))
    d = SeldonMessage.from_array(np.array([[1.0], [2.5]]))
    assert prediction_delta(c, d)["disagree"] == 0.5
    # one-sided failure disagrees maximally; matched failure agrees
    f = SeldonMessage.failure("boom")
    assert prediction_delta(a, f)["disagree"] == 1.0
    assert prediction_delta(f, SeldonMessage.failure("x"))["disagree"] == 0.0
    # shape mismatch is incomparable-and-divergent
    e = SeldonMessage.from_array(np.zeros((3, 2)))
    assert prediction_delta(a, e) == {
        "comparable": False, "disagree": 1.0, "mean_abs_delta": None}
