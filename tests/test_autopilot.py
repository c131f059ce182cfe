"""Learned cost-model autopilot (runtime/autopilot.py): hand-computed
predictor updates (seed-from-cost, online correction, outlier
robustness), goodput-optimal flush sizing, deadline-aware admission
shedding, p2c score blending, router branch demotion, the kill switch,
and the seldon_tpu_autopilot_* metric families."""

import asyncio
import json
from collections import deque

import jax
import numpy as np
import pytest

from seldon_core_tpu.gateway.balancer import ReplicaEndpoint, ReplicaSet
from seldon_core_tpu.graph.interpreter import GraphExecutor
from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import SeldonMessage
from seldon_core_tpu.runtime.autopilot import (
    AUTOPILOT,
    Autopilot,
    autopilot_enabled,
    branch_key,
    pad_bucket,
)
from seldon_core_tpu.runtime.batching import MicroBatcher
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.resilience import Deadline, deadline_scope
from seldon_core_tpu.utils.perf import PerfObservatory, executable_key
from seldon_core_tpu.utils.telemetry import RECORDER, TPU_METRIC_FAMILIES


def deployment(graph, components=None):
    return SeldonDeploymentSpec.from_json_dict(
        {
            "spec": {
                "name": "d",
                "predictors": [
                    {"name": "p", "graph": graph,
                     "components": components or []}
                ],
            }
        }
    )


def run(coro):
    return asyncio.get_event_loop_policy().new_event_loop().run_until_complete(
        coro
    )


# ---------------------------------------------------------------------------
# predictor: seed-from-cost, online correction, outlier robustness
# ---------------------------------------------------------------------------


def test_pad_bucket_and_branch_key():
    assert [pad_bucket(n) for n in (1, 2, 3, 4, 5, 127, 128)] == [
        1, 2, 4, 4, 8, 128, 128,
    ]
    assert branch_key("r", 1, 5) == "branch:r/1[8]"
    assert branch_key("r", 0, None) == "branch:r/0[1]"


@pytest.mark.usefixtures("v5e_peaks")
def test_seed_prior_is_overhead_adjusted_roofline():
    """Before any measurement the prediction is the perf observatory's
    overhead-adjusted roofline — hand-computed from the cost features."""
    obs = PerfObservatory(enabled=True)
    key = executable_key("predict", (8, 16), np.float32)
    flops, nbytes = 2.0 * 8 * 16 * 4, 4.0 * (8 * 16 + 16 * 4)
    obs.record_compile(
        key, {"flops": flops, "bytes_accessed": nbytes}, None
    )
    peaks = obs.peaks()
    roofline = max(
        flops / (peaks["peak_bf16_tflops"] * 1e12),
        nbytes / (peaks["peak_hbm_gbs"] * 1e9),
    )
    ap = Autopilot(lr=0.3, min_samples=4)
    ap.seed_fn = obs.seed_predicted_s
    assert ap.predict_s(key) == pytest.approx(roofline * obs.overhead_x)
    # one measured dispatch calibrates the seed: the adjusted roofline
    # scaled by the key's measured calibration ratio equals the wall
    obs.observe_dispatch(key, 0.004)
    assert obs.seed_predicted_s(key) == pytest.approx(0.004, rel=1e-3)


def test_online_correction_blends_seed_then_trusts_measurements():
    ap = Autopilot(lr=0.5, min_samples=4)
    ap.seed_fn = lambda key: 0.1  # a (bad) 100 ms prior
    key = "predict[4x8/float32]"
    assert ap.predict_s(key) == pytest.approx(0.1)  # pure seed, no samples
    ap.observe(key, 0.02)
    # 1 of 4 samples: w=0.25 toward the learned 20 ms estimate
    assert ap.predict_s(key) == pytest.approx(0.25 * 0.02 + 0.75 * 0.1)
    for _ in range(3):
        ap.observe(key, 0.02)
    # min_samples reached: the learned estimate stands alone
    assert ap.predict_s(key) == pytest.approx(0.02)
    # sustained shift converges at the learning rate: est += lr*resid
    # (resid clipped at 4 scales; scale here is 10 ms, so 40 ms passes)
    before = ap.predict_s(key)
    ap.observe(key, 0.04)
    m = ap._models[key]
    assert m.est_s > before  # moved toward the new regime


def test_outlier_robustness_hand_computed():
    """One 10 s straggler among 10 ms dispatches moves the estimate by at
    most lr * OUTLIER_K * scale — the model cannot be yanked."""
    ap = Autopilot(lr=0.5, min_samples=3)
    key = "predict[8x8/float32]"
    for _ in range(10):
        ap.observe(key, 0.010)
    m = ap._models[key]
    scale_before = m.scale_s  # decayed toward 0 on identical samples
    est_before = m.est_s
    ap.observe(key, 10.0)  # 1000x straggler
    max_step = ap.lr * ap.OUTLIER_K * max(scale_before, 1e-9)
    assert m.est_s - est_before <= max_step + 1e-12
    assert ap.predict_s(key) == pytest.approx(0.010, rel=0.05)
    # and the misprediction landed in the auditing reservoir
    assert ap.mispredict_pct.snapshot()["max"] > 1000.0


def test_bounded_model_table():
    ap = Autopilot()
    for i in range(ap.MAX_KEYS + 50):
        ap.observe(f"k{i}", 0.001)
    assert len(ap._models) == ap.MAX_KEYS


# ---------------------------------------------------------------------------
# predictive micro-batch sizing
# ---------------------------------------------------------------------------


def _entry(rows, width=3, deadline=None):
    return (np.zeros((rows, width)), None, 0.0, None, deadline)


async def _noop_batch(stacked):
    return stacked, {}


def test_flush_plan_picks_goodput_optimal_pad_bucket():
    """Constructed workload: 3+1 rows fill the 4-bucket exactly
    (predicted 10 ms -> 400 rows/s); adding a 5th row pads to the
    8-bucket (predicted 40 ms -> 125 rows/s).  The planner flushes the
    zero-waste prefix and leaves the tail for the next slot."""
    costs = {4: 0.010, 8: 0.040}
    mb = MicroBatcher(
        _noop_batch, max_batch=64,
        predict_s_fn=lambda padded, x: costs.get(padded),
    )
    bucket = deque([_entry(3), _entry(1), _entry(1)])
    k, predicted = mb._plan_flush(bucket)
    assert k == 2
    assert predicted == pytest.approx(0.010)

    # flat predicted cost: bigger is always better goodput -> take all
    mb2 = MicroBatcher(
        _noop_batch, max_batch=64, predict_s_fn=lambda p, x: 0.010,
    )
    k, predicted = mb2._plan_flush(deque([_entry(3), _entry(1), _entry(1)]))
    assert k == 3
    assert predicted == pytest.approx(0.010)


def test_flush_plan_respects_tightest_deadline():
    """A candidate whose predicted wall blows the included requests'
    tightest remaining deadline is dropped when a smaller prefix fits:
    16 rows at 25 ms is the better goodput (640 > 400 rows/s) and wins
    without deadline pressure, but under a 22 ms budget the planner
    flushes the 8-row prefix that can still answer in time."""
    costs = {8: 0.020, 16: 0.025}
    mb = MicroBatcher(
        _noop_batch, max_batch=64,
        predict_s_fn=lambda padded, x: costs.get(padded),
    )
    # no deadline pressure: the 16-bucket's higher goodput wins
    bucket = deque([_entry(8), _entry(8)])
    k, predicted = mb._plan_flush(bucket)
    assert k == 2
    assert predicted == pytest.approx(0.025)
    clock = [100.0]
    tight = Deadline(100.0 + 0.022, clock=lambda: clock[0])
    bucket = deque([_entry(8, deadline=tight), _entry(8, deadline=tight)])
    k, predicted = mb._plan_flush(bucket)
    assert k == 1
    assert predicted == pytest.approx(0.020)


def test_flush_plan_kill_switch_and_no_model_restore_legacy(monkeypatch):
    costs = {4: 0.010, 8: 0.040}
    mb = MicroBatcher(
        _noop_batch, max_batch=64,
        predict_s_fn=lambda padded, x: costs.get(padded),
    )
    bucket = deque([_entry(3), _entry(1), _entry(1)])
    monkeypatch.setenv("SELDON_TPU_AUTOPILOT", "0")
    assert mb._plan_flush(bucket) == (3, None)  # legacy take-all
    monkeypatch.delenv("SELDON_TPU_AUTOPILOT")
    # an unmodelled pad bucket anywhere in the candidate set: legacy
    mb.predict_s_fn = lambda padded, x: None
    assert mb._plan_flush(bucket) == (3, None)
    # no hook at all (engines without compiled graphs): legacy
    mb.predict_s_fn = None
    assert mb._plan_flush(bucket) == (3, None)


def test_predicted_latency_s_hand_computed():
    mb = MicroBatcher(
        _noop_batch, max_batch=64, coalesce_ms=0.5, max_wait_ms=2.0,
        predict_s_fn=lambda padded, x: {1: 0.004, 4: 0.007}.get(padded),
    )
    x = np.zeros((1, 3))
    # idle batcher: dispatch + coalesce window, no slot wait
    assert mb.predicted_latency_s(x) == pytest.approx(0.004 + 0.0005)
    # with 3 rows already queued the request lands in the 4-bucket
    # (bucket keys carry the latency tier — runtime/qos.py — and
    # default traffic is all-interactive)
    from seldon_core_tpu.runtime.qos import TIER_INTERACTIVE

    mb._buckets[(x.shape[1:], x.dtype, TIER_INTERACTIVE)] = \
        deque([_entry(3)])
    assert mb.predicted_latency_s(x) == pytest.approx(0.007 + 0.0005)


# ---------------------------------------------------------------------------
# deadline-aware admission control
# ---------------------------------------------------------------------------


def _model_engine(**kw):
    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )
    return EngineService(spec, **kw)


def _prime_slow_model(engine, rows=1, width=4, seconds=5.0):
    """Teach the autopilot that this engine's pad bucket is slow."""
    x = np.zeros((rows, width))
    key = executable_key(
        "predict", (pad_bucket(rows),) + x.shape[1:], x.dtype
    )
    for _ in range(AUTOPILOT.min_samples + 1):
        AUTOPILOT.observe(key, seconds)
    return key


def test_admission_sheds_on_exhausted_predicted_budget():
    AUTOPILOT.reset()
    engine = _model_engine()
    assert engine.batcher is not None
    _prime_slow_model(engine, seconds=5.0)
    before = dict(RECORDER.autopilot_sheds)
    payload = json.dumps({"data": {"ndarray": [[0.0] * 4]}})

    async def go():
        # 50 ms of budget against a predicted ~5 s dispatch: typed 503
        # BEFORE any dispatch happens
        with deadline_scope(0.05):
            return await engine.predict_json(payload)

    text, status = run(go())
    assert status == 503
    doc = json.loads(text)
    assert doc["status"]["status"] == "FAILURE"
    assert "load shed" in doc["status"]["info"]
    got = RECORDER.autopilot_sheds.get("admission", 0)
    assert got == before.get("admission", 0) + 1
    AUTOPILOT.reset()


def test_admission_does_not_shed_when_budget_suffices():
    AUTOPILOT.reset()
    engine = _model_engine()
    _prime_slow_model(engine, seconds=0.001)  # predicted ~1 ms

    async def go():
        with deadline_scope(30.0):
            return await engine.predict_json(
                json.dumps({"data": {"ndarray": [[0.0] * 4]}})
            )

    text, status = run(go())
    assert status == 200, text
    AUTOPILOT.reset()


def test_admission_kill_switch_restores_prior_behavior(monkeypatch):
    """SELDON_TPU_AUTOPILOT=0: a doomed-looking request is NOT shed —
    exactly the pre-autopilot reactive path (and on this fast CPU model
    the dispatch actually makes the deadline, proving a shed would have
    been wrong to force)."""
    AUTOPILOT.reset()
    engine = _model_engine()
    _prime_slow_model(engine, seconds=5.0)  # model CLAIMS 5 s
    monkeypatch.setenv("SELDON_TPU_AUTOPILOT", "0")

    async def go():
        with deadline_scope(5.0):
            return await engine.predict_json(
                json.dumps({"data": {"ndarray": [[0.0] * 4]}})
            )

    text, status = run(go())
    assert status == 200, text
    AUTOPILOT.reset()


# ---------------------------------------------------------------------------
# cost-aware routing: p2c score blending + router branch demotion
# ---------------------------------------------------------------------------


def test_p2c_score_blends_shape_aware_latency():
    ep = ReplicaEndpoint("http://e1:1")
    # global EWMA says 5 ms; the 128-bucket has learned 50 ms
    ep.ewma_ms = 5.0
    for _ in range(ReplicaEndpoint.SHAPE_MIN_SAMPLES):
        ep.inflight += 1
        ep.complete(0.050, ok=True, rows=100)
    # unknown shape / no rows: the shape-blind EWMA (which the 50 ms
    # completions also fed) — bit-for-bit the legacy input
    assert ep.predicted_ms(None) == ep.ewma_ms
    # the 100-row request prices at its own bucket, not the blind EWMA
    assert ep.predicted_ms(100) == pytest.approx(50.0, rel=1e-6)
    assert ep.predicted_ms(1) == ep.ewma_ms  # no 1-bucket model yet
    now = 0.0
    assert ep.score(now, 1e9, rows=100) == pytest.approx(
        (ep.inflight + ep.scraped_inflight + 1) * 50.0
    )


def test_p2c_blend_below_min_samples_hand_computed():
    ep = ReplicaEndpoint("http://e1:1")
    ep.ewma_ms = 5.0
    for _ in range(2):  # 2 of 5 samples at 50 ms
        ep.inflight += 1
        ep.complete(0.050, ok=True, rows=100)
    ewma_after = ep.ewma_ms  # the completions moved the global EWMA too
    w = 2 / ReplicaEndpoint.SHAPE_MIN_SAMPLES
    assert ep.predicted_ms(100) == pytest.approx(
        w * 50.0 + (1 - w) * ewma_after
    )


def test_p2c_pick_steers_by_request_shape():
    """Replica A is fast for small rows, B for big ones: the same set
    routes a 1-row request to A and a 128-row request to B."""
    import random

    rs = ReplicaSet(["http://a:1", "http://b:1"], rng=random.Random(0))
    a, b = rs.endpoints
    a.ewma_ms = b.ewma_ms = 10.0
    a.shape_ms = {1: [1.0, 9], 128: [80.0, 9]}
    b.shape_ms = {1: [30.0, 9], 128: [8.0, 9]}
    picks_small = {rs.pick(rows=1)[0].name for _ in range(8)}
    picks_big = {rs.pick(rows=128)[0].name for _ in range(8)}
    assert picks_small == {"http://a:1"}
    assert picks_big == {"http://b:1"}


def test_p2c_kill_switch_restores_blind_ewma(monkeypatch):
    ep = ReplicaEndpoint("http://e1:1")
    ep.ewma_ms = 5.0
    ep.shape_ms = {128: [50.0, 9]}
    monkeypatch.setenv("SELDON_TPU_AUTOPILOT", "0")
    assert ep.predicted_ms(100) == 5.0
    assert ep.score(0.0, 1e9, rows=100) == ep.score(0.0, 1e9)


def test_router_branch_demotion_under_deadline():
    """The router picks branch 0 (argmax of rewards); the autopilot has
    learned branch 0 takes ~5 s and branch 1 ~1 ms.  Under a 100 ms
    budget the request is demoted to branch 1 — recorded in
    meta.routing (feedback trains the branch that served) and tagged.
    Without a deadline the router's choice stands."""
    AUTOPILOT.reset()
    g = {
        "name": "r",
        "type": "ROUTER",
        "children": [
            {"name": "s1", "type": "MODEL"},
            {"name": "s2", "type": "MODEL"},
        ],
    }
    comps = [
        {"name": "r", "runtime": "inprocess",
         "class_path": "test.CountingRouter"},
        {"name": "s1", "runtime": "inprocess", "class_path": "test.Scale"},
        {"name": "s2", "runtime": "inprocess", "class_path": "test.Scale"},
    ]
    import tests.test_graph_exec  # noqa: F401 - registers test.* units

    ex = GraphExecutor(deployment(g, comps).predictor())
    for _ in range(AUTOPILOT.min_samples + 1):
        AUTOPILOT.observe(branch_key("r", 0, 1), 5.0)
        AUTOPILOT.observe(branch_key("r", 1, 1), 0.001)

    resp = run(ex.predict(SeldonMessage.from_array(np.ones((1, 2)))))
    assert resp.meta.routing["r"] == 0  # no deadline: untouched

    async def bounded():
        with deadline_scope(0.1):
            return await ex.predict(SeldonMessage.from_array(np.ones((1, 2))))

    resp = run(bounded())
    assert resp.meta.routing["r"] == 1
    assert resp.meta.tags["seldon.autopilot.reroute.r"] == 1
    AUTOPILOT.reset()


# ---------------------------------------------------------------------------
# learning rides the telemetry spine; surfaces; metric families
# ---------------------------------------------------------------------------


def test_dispatches_train_model_through_spine_and_autopilot_page():
    AUTOPILOT.reset()
    engine = _model_engine()
    payload = json.dumps({"data": {"ndarray": [[0.0] * 4]}})

    async def go():
        for _ in range(8):
            text, status = await engine.predict_json(payload)
            assert status == 200, text

    run(go())
    doc = engine.autopilot_document()
    assert doc["engine"]["deployment"] == "d"
    trained = [k for k in doc["keys"] if k["samples"] > 0]
    assert trained, doc
    assert trained[0]["predicted_ms"] > 0
    assert doc["knobs"]["kill_switch"] == "SELDON_TPU_AUTOPILOT"
    # /stats carries the compact health block
    assert engine.stats()["autopilot"]["keys"] >= 1
    AUTOPILOT.reset()


def test_gateway_does_not_blame_replicas_for_sheds():
    """A predictive shed is the engine deciding, not the replica dying:
    the gateway must neither feed fail-degradation (a shedding replica
    would blackhole) nor the latency EWMA (a ~1 ms refusal would make
    it look fast) — while real transport 503s still count as faults."""
    from seldon_core_tpu.gateway.apife import ApiGateway
    from seldon_core_tpu.messages import LoadShedError
    from seldon_core_tpu.runtime.autopilot import SHED_INFO_PREFIX

    shed = SeldonMessage.failure(
        f"{SHED_INFO_PREFIX}: predicted 12.0 ms exceeds 4.0 ms", code=503
    )
    transport = SeldonMessage.failure("bad gateway", code=503)
    assert ApiGateway._is_autopilot_shed(shed)
    assert not ApiGateway._is_autopilot_shed(transport)
    assert not ApiGateway._replica_fault(shed)
    assert ApiGateway._replica_fault(transport)
    # the engine's raise site really does produce the recognized prefix
    assert str(LoadShedError(f"{SHED_INFO_PREFIX}: x")).startswith(
        SHED_INFO_PREFIX
    )


def test_flush_plan_shorter_prefix_same_bucket_feasible():
    """Two prefixes landing in the SAME pad bucket differ only in their
    tightest deadline — the shorter, feasible one must not be shadowed
    by the longer, infeasible one (both pad to 8; the second request's
    5 ms budget cannot fit the 20 ms wall, the first alone can)."""
    mb = MicroBatcher(
        _noop_batch, max_batch=64, predict_s_fn=lambda p, x: 0.020,
    )
    clock = [0.0]
    wide = Deadline(0.050, clock=lambda: clock[0])
    tight = Deadline(0.005, clock=lambda: clock[0])
    bucket = deque([
        _entry(5, deadline=wide), _entry(2, deadline=tight),
    ])
    k, predicted = mb._plan_flush(bucket)
    assert k == 1
    assert predicted == pytest.approx(0.020)


def test_autopilot_metric_families_exported():
    for family in (
        "seldon_tpu_autopilot_decisions_total",
        "seldon_tpu_autopilot_shed_total",
        "seldon_tpu_autopilot_mispredict_pct",
        "seldon_tpu_autopilot_keys",
    ):
        assert family in TPU_METRIC_FAMILIES
    before_shed = dict(RECORDER.autopilot_sheds)
    before_dec = dict(RECORDER.autopilot_decisions)
    RECORDER.record_autopilot_shed("admission")
    RECORDER.record_autopilot_decision("flush")
    RECORDER.set_autopilot_model(mispredict_p50_pct=12.5, keys=3)
    snap = RECORDER.snapshot()["autopilot"]
    assert snap["sheds"]["admission"] == before_shed.get("admission", 0) + 1
    assert snap["decisions"]["flush"] == before_dec.get("flush", 0) + 1
    assert snap["mispredict_p50_pct"] == 12.5
    assert snap["keys"] == 3
    if RECORDER.registry is not None:
        from seldon_core_tpu.utils.metrics import MetricsRegistry

        text = MetricsRegistry(deployment_name="t").exposition().decode()
        for family in (
            "seldon_tpu_autopilot_shed_total",
            "seldon_tpu_autopilot_mispredict_pct",
            "seldon_tpu_autopilot_keys",
        ):
            assert family in text
