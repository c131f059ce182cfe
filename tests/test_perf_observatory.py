"""Performance observatory (utils/perf.py): per-executable cost-feature
capture, MFU/roofline math, the GET /perf surface on both REST lanes,
OpenMetrics trace_id exemplars, anomaly detection, and HBM-gauge
degradation on backends without memory stats."""

import asyncio
import json

import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.graph.units import Unit, register_unit
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.utils.perf import (
    OBSERVATORY,
    PerfObservatory,
    executable_key,
    extract_cost_features,
)
from seldon_core_tpu.utils.telemetry import RECORDER
from seldon_core_tpu.utils.tracing import TRACER


@register_unit("test.PureMatmul")
class PureMatmulUnit(Unit):
    """One dense matmul with a known analytic FLOP count (2*M*K*N)."""

    K, N = 32, 16

    def __init__(self):
        self.w = jnp.arange(self.K * self.N, dtype=jnp.float32).reshape(
            self.K, self.N
        ) / (self.K * self.N)

    def predict(self, state, X):
        return X @ self.w


def matmul_deployment():
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "perf-dep", "predictors": [{
            "name": "p",
            "graph": {"name": "mm", "type": "MODEL"},
            "components": [{
                "name": "mm", "runtime": "inprocess",
                "class_path": "test.PureMatmul",
            }],
        }]}
    })


def drive(engine, rows, width, n=12):
    payload = json.dumps(
        {"data": {"ndarray": np.ones((rows, width)).tolist()}}
    )

    async def run():
        for _ in range(n):
            text, status = await engine.predict_json(payload)
            assert status == 200, text

    asyncio.run(run())


# ---------------------------------------------------------------------------
# cost-feature capture + MFU math
# ---------------------------------------------------------------------------


@pytest.mark.usefixtures("v5e_peaks")
def test_cost_features_captured_on_compiled_model():
    """A served matmul graph lands in the observatory with non-zero FLOPs
    (bounded below by the analytic 2*M*K*N), bytes accessed, a measured
    compile duration, and dispatch-derived MFU/roofline figures."""
    OBSERVATORY.reset()
    B = 4
    engine = EngineService(matmul_deployment())
    drive(engine, B, PureMatmulUnit.K)
    doc = engine.perf_document()
    assert doc["engine"]["mode"] == "compiled"
    rows = [r for r in doc["executables"] if str(B) in r["executable"]]
    assert rows, doc["executables"]
    row = rows[0]
    analytic = 2 * B * PureMatmulUnit.K * PureMatmulUnit.N
    assert row["calls"] >= 12
    assert row["flops"] >= analytic, (row, analytic)
    assert row["bytes_accessed"] > 0
    assert row["compile_s"] > 0
    assert row["mfu"] > 0
    assert row["predicted_vs_measured"] > 0
    assert row["bound"] in ("compute", "memory", "overhead")
    assert row["latency_ms"]["p50"] > 0


def test_unknown_device_kind_has_no_peak_and_perf_prints_null():
    """A device kind with no row in utils/chips.py has NO peak: /perf
    states null for the peak-normalized fields instead of quoting some
    other chip's MFU (this CPU backend used to report a v5e's)."""
    from seldon_core_tpu.utils.chips import (
        chip_peak_hbm_gbs,
        chip_peak_tflops,
    )

    assert chip_peak_tflops("TPU v5 lite") == 197.0
    assert chip_peak_hbm_gbs("TPU v5 lite") == 819.0
    assert chip_peak_tflops("cpu") is None
    assert chip_peak_hbm_gbs("TPU v9x") is None
    OBSERVATORY.reset()
    engine = EngineService(matmul_deployment())
    drive(engine, 4, PureMatmulUnit.K, n=3)
    doc = json.loads(json.dumps(engine.perf_document()))
    dev = doc["device"]
    assert dev["platform"] == "cpu"
    assert dev["peak_bf16_tflops"] is None and dev["peak_hbm_gbs"] is None
    assert "peak_assumed" not in dev
    row = next(r for r in doc["executables"] if "4x" in r["executable"])
    assert row["flops"] > 0 and row["achieved_tflops"] > 0
    assert row["mfu"] is None
    assert row["predicted_vs_measured"] is None and row["bound"] is None
    # no peak -> no roofline prior: the autopilot waits for measurements
    assert OBSERVATORY.seed_predicted_s(row["executable"]) is None


@pytest.mark.usefixtures("v5e_peaks")
def test_mfu_math_against_hand_computed_flops():
    """observe_dispatch derives exactly flops/seconds/peak — checked with
    a hand-computed matmul FLOP count against the observatory's own
    device-kind-matched peaks (the shared utils/chips.py table)."""
    obs = PerfObservatory(enabled=True)
    M, K, N = 8, 128, 64
    flops = 2.0 * M * K * N
    nbytes = 4.0 * (M * K + K * N + M * N)
    key = executable_key("predict", (M, K), np.float32)
    obs.record_compile(key, {"flops": flops, "bytes_accessed": nbytes}, 0.25)
    seconds = 0.02
    d = obs.observe_dispatch(key, seconds, rows=M)
    peaks = obs.peaks()
    peak_flops_s = peaks["peak_bf16_tflops"] * 1e12
    peak_bytes_s = peaks["peak_hbm_gbs"] * 1e9
    assert d["mfu"] == pytest.approx(flops / seconds / peak_flops_s)
    assert d["achieved_tflops"] == pytest.approx(flops / seconds / 1e12)
    assert d["achieved_gbs"] == pytest.approx(nbytes / seconds / 1e9)
    assert d["arithmetic_intensity"] == pytest.approx(flops / nbytes)
    predicted = max(flops / peak_flops_s, nbytes / peak_bytes_s)
    assert d["predicted_s"] == pytest.approx(predicted)
    # reads in name order: predicted over measured, 1.0 = wall time at
    # the OVERHEAD-ADJUSTED roofline — the same adjusted time the
    # overhead-bound classification judges and the autopilot seeds from
    adjusted = predicted * obs.overhead_x
    assert d["adjusted_predicted_s"] == pytest.approx(adjusted)
    assert d["predicted_vs_measured"] == pytest.approx(adjusted / seconds)
    # 20 ms of wall for sub-microsecond predicted device work: overhead
    assert d["bound"] == "overhead"
    # the per-executable /perf row reports the same figures, plus the
    # per-pad-bucket calibration ratio (measured / adjusted roofline)
    row = obs.document()["executables"][0]
    assert row["executable"] == key
    assert row["mfu"] == pytest.approx(d["mfu"], abs=1e-6)
    assert row["compile_s"] == pytest.approx(0.25)
    assert row["calibration_ratio"] == pytest.approx(
        seconds / adjusted, rel=1e-3
    )
    # the autopilot seed prior agrees with the page: adjusted roofline
    # scaled by the key's own measured calibration = measured wall
    assert obs.seed_predicted_s(key) == pytest.approx(seconds, rel=1e-3)


def test_extract_cost_features_tolerates_odd_shapes():
    assert extract_cost_features(None) is None
    assert extract_cost_features([]) is None
    assert extract_cost_features({}) is None
    assert extract_cost_features({"flops": -1.0}) is None  # unknown marker
    got = extract_cost_features([{"flops": 10.0, "bytes accessed": 5.0}])
    assert got == {"flops": 10.0, "bytes_accessed": 5.0}
    got = extract_cost_features(
        {"flops": 2.0, "bytes accessedout{}": 7.0}
    )
    assert got["output_bytes"] == 7.0


def test_degrades_to_latency_only_rows_without_cost_features():
    """Backends where cost_analysis() yields nothing still get calls +
    latency percentiles on /perf — no crash, no fabricated MFU."""
    obs = PerfObservatory(enabled=True)
    obs.record_compile("predict[2x4/float32]", None, 0.1)
    for _ in range(3):
        d = obs.observe_dispatch("predict[2x4/float32]", 0.005, rows=2)
    assert d == {} or "mfu" not in d
    row = obs.document()["executables"][0]
    assert row["calls"] == 3
    assert row["latency_ms"]["p50"] > 0
    assert "flops" not in row and "mfu" not in row


# ---------------------------------------------------------------------------
# anomaly detection
# ---------------------------------------------------------------------------


def test_anomaly_counter_fires_on_injected_slow_dispatch():
    before = dict(RECORDER.perf_anomalies)
    obs = PerfObservatory(enabled=True, anomaly_factor=3.0, min_calls=5)
    key = "predict[8x16/float32]"
    for _ in range(6):
        d = obs.observe_dispatch(key, 0.004)
        assert "anomaly" not in d
    d = obs.observe_dispatch(key, 0.4)  # 100x the rolling p50
    assert d.get("anomaly") == "slow_dispatch"
    assert obs.document()["executables"][0]["anomalies"] == 1
    got = RECORDER.perf_anomalies.get("slow_dispatch", 0)
    assert got == before.get("slow_dispatch", 0) + 1


@pytest.mark.usefixtures("v5e_peaks")
def test_ratio_drift_anomaly():
    """With cost features present, drift is judged on measured/predicted —
    a dispatch whose ratio blows past its own rolling baseline fires
    kind=ratio_drift even below the absolute slow_dispatch floor."""
    obs = PerfObservatory(enabled=True, anomaly_factor=3.0, min_calls=4)
    key = "predict[4x8/float32]"
    obs.record_compile(key, {"flops": 1e9, "bytes_accessed": 1e6}, 0.1)
    for _ in range(5):
        obs.observe_dispatch(key, 0.0002)
    d = obs.observe_dispatch(key, 0.0011)  # ~5x ratio, <1ms over p50
    assert d.get("anomaly") == "ratio_drift"


# ---------------------------------------------------------------------------
# HBM watermarks
# ---------------------------------------------------------------------------


def test_overflow_entry_stays_latency_only():
    """Past MAX_EXECUTABLES distinct shapes, dispatches aggregate under
    one overflow entry — which must never mix one shape's cost features
    into another's MFU, and never fires anomalies (its baselines span
    unrelated shapes)."""
    obs = PerfObservatory(enabled=True, min_calls=2)
    for i in range(obs.MAX_EXECUTABLES):
        obs.observe_dispatch(f"predict[{i}x8/float32]", 0.001)
    # the 65th shape lands on the shared overflow entry
    obs.record_compile("predict[999x8/float32]", {"flops": 1e12}, 0.1)
    for s in (0.001, 0.001, 0.001, 5.0):
        d = obs.observe_dispatch("predict[999x8/float32]", s)
    assert "mfu" not in d and "anomaly" not in d
    rows = {r["executable"]: r for r in obs.document()["executables"]}
    over = rows[obs.OVERFLOW_KEY]
    assert over["calls"] == 4
    assert "flops" not in over and over["anomalies"] == 0


def test_hbm_gauges_tolerate_cpu_backend():
    """CPU devices return no memory_stats(); the watermark poll reports
    ``memory_stats: null`` rows, sets no gauges, and never raises."""
    obs = PerfObservatory(enabled=True)
    rows = obs.hbm_watermarks(force=True)
    assert rows, "expected one row per jax device"
    for row in rows:
        assert "device" in row
        if row.get("memory_stats", "present") is None:
            assert "bytes_in_use" not in row
        else:
            assert row["bytes_in_use"] >= 0
    # a second (throttled) poll serves the cached reading without error
    assert obs.hbm_watermarks() == rows


def test_hbm_gauges_set_when_backend_reports(monkeypatch):
    """A backend WITH memory stats lands in seldon_tpu_hbm_* gauges."""
    obs = PerfObservatory(enabled=True)

    class FakeDev:
        platform = "tpu"
        id = 0

        def memory_stats(self):
            return {"bytes_in_use": 123, "peak_bytes_in_use": 456,
                    "bytes_limit": 1000}

    import jax as jax_mod

    monkeypatch.setattr(jax_mod, "devices", lambda: [FakeDev()])
    rows = obs.hbm_watermarks(force=True)
    assert rows == [{"device": "tpu:0", "bytes_in_use": 123,
                     "peak_bytes_in_use": 456, "bytes_limit": 1000}]
    assert RECORDER.hbm["tpu:0"]["bytes_in_use"] == 123


def test_prometheus_exposition_refreshes_hbm_gauges(monkeypatch):
    """A Prometheus-only deployment (nobody polls /perf) still gets live
    HBM watermarks: the exposition path triggers the throttled poll."""
    import jax as jax_mod

    class FakeDev:
        platform = "tpu"
        id = 7

        def memory_stats(self):
            return {"bytes_in_use": 11, "peak_bytes_in_use": 22,
                    "bytes_limit": 33}

    monkeypatch.setattr(jax_mod, "devices", lambda: [FakeDev()])
    OBSERVATORY._hbm_last_poll = 0.0  # defeat the throttle for the test
    RECORDER.exposition()
    assert RECORDER.hbm["tpu:7"] == {
        "bytes_in_use": 11, "peak_bytes_in_use": 22, "bytes_limit": 33}


# ---------------------------------------------------------------------------
# GET /perf on both REST lanes + OpenMetrics exemplars
# ---------------------------------------------------------------------------


@pytest.mark.usefixtures("v5e_peaks")
def test_perf_endpoint_and_exemplars_aiohttp_lane():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.runtime.rest import make_engine_app

    OBSERVATORY.reset()
    engine = EngineService(matmul_deployment())
    was_enabled = TRACER.enabled
    TRACER.enable()

    async def run():
        try:
            app = make_engine_app(engine)
            async with TestClient(TestServer(app)) as client:
                payload = json.dumps({
                    "data": {"ndarray": np.ones((2, PureMatmulUnit.K)).tolist()}
                })
                for _ in range(8):
                    r = await client.post(
                        "/api/v0.1/predictions", data=payload,
                        headers={"Content-Type": "application/json"},
                    )
                    assert r.status == 200
                r = await client.get("/perf")
                assert r.status == 200
                doc = await r.json()
                assert doc["engine"]["deployment"] == "perf-dep"
                assert doc["executables"], doc
                row = doc["executables"][0]
                assert row["flops"] > 0 and row["mfu"] > 0
                assert isinstance(doc["hbm"], list)
                # /stats carries the compact observatory block
                r = await client.get("/stats")
                stats = await r.json()
                assert stats["perf"]["executables"] >= 1
                assert stats["perf"]["dispatches"] >= 8
                # OpenMetrics exposition via Accept negotiation carries
                # trace_id exemplars on dispatch-histogram buckets
                r = await client.get(
                    "/prometheus",
                    headers={"Accept": "application/openmetrics-text"},
                )
                assert "openmetrics-text" in r.headers["Content-Type"]
                text = await r.text()
                assert text.rstrip().endswith("# EOF")
                assert text.count("# EOF") == 1
                exemplar_lines = [
                    ln for ln in text.splitlines()
                    if "seldon_tpu_dispatch_seconds_bucket" in ln
                    and 'trace_id="' in ln
                ]
                assert exemplar_lines, "no exemplars in OpenMetrics body"
                # classic exposition still serves (no exemplars there)
                r = await client.get("/prometheus")
                assert "seldon_tpu_dispatch_seconds" in await r.text()
        finally:
            if not was_enabled:
                TRACER.disable()

    asyncio.run(run())


@pytest.mark.usefixtures("v5e_peaks")
def test_perf_endpoint_and_exemplars_fast_lane():
    import aiohttp

    from seldon_core_tpu.runtime.httpfast import serve_fast

    OBSERVATORY.reset()
    engine = EngineService(matmul_deployment())
    was_enabled = TRACER.enabled
    TRACER.enable()

    async def run():
        server = await serve_fast(engine, "127.0.0.1", 0)
        base = f"http://127.0.0.1:{server.port}"
        try:
            async with aiohttp.ClientSession() as sess:
                payload = json.dumps({
                    "data": {"ndarray": np.ones((2, PureMatmulUnit.K)).tolist()}
                })
                for _ in range(8):
                    async with sess.post(
                        base + "/api/v0.1/predictions", data=payload,
                    ) as r:
                        assert r.status == 200
                async with sess.get(base + "/perf") as r:
                    assert r.status == 200
                    doc = await r.json()
                assert doc["executables"]
                assert doc["executables"][0]["flops"] > 0
                assert doc["executables"][0]["mfu"] > 0
                # fast-lane handlers don't see headers: OpenMetrics is
                # query-negotiated
                async with sess.get(
                    base + "/prometheus", params={"format": "openmetrics"}
                ) as r:
                    assert "openmetrics-text" in r.headers["Content-Type"]
                    text = await r.text()
                assert any(
                    "seldon_tpu_dispatch_seconds_bucket" in ln
                    and 'trace_id="' in ln
                    for ln in text.splitlines()
                ), "no exemplars on the fast lane's OpenMetrics body"
        finally:
            if not was_enabled:
                TRACER.disable()
            await server.stop()

    asyncio.run(run())


def test_perf_endpoint_on_unit_app():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.runtime.microservice import build_runtime
    from seldon_core_tpu.runtime.rest import make_unit_app

    runtime = build_runtime("SIMPLE_MODEL", "MODEL", unit_name="u")

    async def run():
        async with TestClient(TestServer(make_unit_app(runtime))) as client:
            r = await client.get("/perf")
            assert r.status == 200
            doc = await r.json()
            assert doc["unit"]["name"] == "u"
            assert "executables" in doc and "hbm" in doc

    asyncio.run(run())


# ---------------------------------------------------------------------------
# compile-cache listener degradation (satellite)
# ---------------------------------------------------------------------------


def test_compile_cache_listener_degrades_without_jax_monitoring(monkeypatch):
    """install_compile_cache_listener() returns False and registers
    nothing when jax.monitoring is unimportable — serving boots fine."""
    import sys

    import seldon_core_tpu.utils.telemetry as telemetry

    monkeypatch.setattr(telemetry, "_compile_listener_installed", False)
    # a None sys.modules entry makes `import jax.monitoring` raise
    monkeypatch.setitem(sys.modules, "jax.monitoring", None)
    assert telemetry.install_compile_cache_listener() is False
    assert telemetry._compile_listener_installed is False


def test_compile_durations_recorded():
    """The AOT capture records compile wall time into the
    seldon_tpu_compile_seconds mirror (and histogram when prometheus is
    present)."""
    before = RECORDER.compile_seconds.snapshot()["count"]
    OBSERVATORY.reset()
    engine = EngineService(matmul_deployment())
    drive(engine, 3, PureMatmulUnit.K, n=2)
    after = RECORDER.compile_seconds.snapshot()["count"]
    assert after > before


def test_observatory_disabled_is_inert(monkeypatch):
    obs = PerfObservatory(enabled=False)
    assert obs.observe_dispatch("k", 0.1) == {}
    obs.record_compile("k", {"flops": 1.0}, 0.1)
    obs.note_padding(2, 4)
    assert obs.document()["executables"] == []
