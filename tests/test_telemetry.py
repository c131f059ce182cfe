"""Serving flight recorder: TPU metric families, /stats snapshots, and the
request-audit firehose (bounded queue, non-blocking, counted drops)."""

import asyncio
import json

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import SeldonMessage
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.rest import make_engine_app, serve_app
from seldon_core_tpu.utils.metrics import MetricsRegistry
from seldon_core_tpu.utils.telemetry import (
    RECORDER,
    AuditLog,
    FlightRecorder,
    Reservoir,
    TPU_METRIC_FAMILIES,
)


def deployment(graph, name="dep"):
    return SeldonDeploymentSpec.from_json_dict(
        {"spec": {"name": name,
                  "predictors": [{"name": "p", "graph": graph}]}}
    )


SIMPLE = {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}

GEN_SPEC = {
    "spec": {"name": "gen-dep", "predictors": [{
        "name": "p",
        "graph": {"name": "g", "type": "MODEL"},
        "components": [{
            "name": "g", "runtime": "inprocess",
            "class_path": "TransformerGenerator",
            "parameters": [
                {"name": "vocab", "value": "32", "type": "INT"},
                {"name": "d_model", "value": "16", "type": "INT"},
                {"name": "n_heads", "value": "2", "type": "INT"},
                {"name": "n_layers", "value": "1", "type": "INT"},
                {"name": "d_ff", "value": "32", "type": "INT"},
                {"name": "max_new_tokens", "value": "6", "type": "INT"},
                {"name": "dtype", "value": "float32", "type": "STRING"},
            ],
        }],
    }]}
}


async def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _fresh_recorder():
    RECORDER.reset()
    yield
    RECORDER.reset()


# ---------------------------------------------------------------------------
# Reservoir + recorder primitives
# ---------------------------------------------------------------------------


def test_reservoir_percentiles_and_bound():
    r = Reservoir(capacity=100)
    for v in range(1, 1001):  # keeps the last 100: 901..1000
        r.observe(float(v))
    snap = r.snapshot()
    assert snap["count"] == 1000  # lifetime count survives the window
    assert len(r) == 100
    assert 940 <= snap["p50"] <= 960
    assert snap["p99"] >= 990
    assert snap["max"] == 1000.0


def test_reservoir_empty_snapshot():
    snap = Reservoir().snapshot()
    assert snap == {"count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
                    "p99": 0.0, "max": 0.0}


def test_recorder_snapshot_shape_and_exposition():
    rec = FlightRecorder()
    rec.observe_batch(8, queue_wait_s=0.002)
    rec.set_inflight(3)
    rec.observe_ttft(0.05)
    rec.observe_decode_rate(1234.0)
    rec.observe_accept_ratio(0.6)
    rec.set_kv_slots(active=512, reserved=128)
    rec.record_compile_cache("hit")
    snap = rec.snapshot()
    assert snap["batch"]["occupancy"]["count"] == 1
    assert snap["batch"]["inflight_dispatches"] == 3
    assert snap["generation"]["kv_cache_slots"] == {
        "active": 512, "reserved": 128}
    assert snap["compile_cache_events"] == {"hit": 1}
    json.dumps(snap)  # /stats body must be JSON-safe
    text = rec.exposition().decode()
    for family in TPU_METRIC_FAMILIES:
        assert family in text, f"{family} missing from exposition"


def test_metrics_registry_merges_tpu_families():
    """Every /prometheus scrape target carries the process-level families."""
    RECORDER.observe_batch(4)
    reg = MetricsRegistry(deployment_name="d", predictor_name="p")
    text = reg.exposition().decode()
    assert "seldon_api_engine_server_requests_duration_seconds" in text
    assert "seldon_tpu_batch_occupancy" in text
    assert frozenset(TPU_METRIC_FAMILIES) <= MetricsRegistry.family_names()


def test_request_latency_key_space_bounded():
    rec = FlightRecorder()
    for i in range(200):
        rec.request_latency(f"svc{i}", 0.001)
    assert len(rec.snapshot()["request_latency_s"]) <= 64


# ---------------------------------------------------------------------------
# Engine instrumentation
# ---------------------------------------------------------------------------


def test_engine_predicts_feed_batch_telemetry():
    async def run():
        engine = EngineService(deployment(SIMPLE))
        assert engine.mode == "compiled"
        msg = SeldonMessage.from_array(np.ones((3, 2), np.float64))
        await engine.predict(msg)
        await asyncio.gather(*[
            engine.predict(SeldonMessage.from_array(
                np.ones((1, 2), np.float64)))
            for _ in range(4)
        ])
        # let the dispatch tasks' done-callbacks (inflight gauge) fire
        await asyncio.sleep(0.05)
    asyncio.run(run())
    snap = RECORDER.snapshot()
    occ = snap["batch"]["occupancy"]
    assert occ["count"] >= 2  # at least the 3-row and one coalesced stack
    assert occ["max"] >= 3
    assert snap["batch"]["queue_wait_s"]["count"] >= 5  # per request
    # the dispatch slot picture returned to 0 after the burst
    assert snap["batch"]["inflight_dispatches"] == 0
    # request latency percentiles for the predictions service
    assert snap["request_latency_s"]["server:predictions"]["count"] >= 5


def test_engine_stats_endpoint():
    async def run():
        engine = EngineService(deployment(SIMPLE))
        await engine.predict(SeldonMessage.from_array(
            np.ones((2, 2), np.float64)))
        await asyncio.sleep(0.05)  # inflight gauge done-callbacks
        port = await _free_port()
        runner = await serve_app(make_engine_app(engine), "127.0.0.1", port)
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{port}/stats") as r:
                    assert r.status == 200
                    doc = json.loads(await r.text())
        finally:
            await runner.cleanup()
        return doc
    doc = asyncio.run(run())
    assert doc["engine"]["mode"] == "compiled"
    assert doc["batcher"]["max_inflight"] >= 1
    assert doc["batcher"]["inflight_dispatches"] == 0
    assert doc["telemetry"]["batch"]["occupancy"]["count"] >= 1
    assert "server:predictions" in doc["telemetry"]["request_latency_s"]
    assert doc["telemetry"]["request_latency_s"]["server:predictions"][
        "p99"] >= 0
    assert doc["tracer"] == {"enabled": False} or doc["tracer"]["enabled"] in (
        True, False)
    assert doc["audit"]["enabled"] is False  # env-off default


def test_gateway_stats_endpoint():
    from seldon_core_tpu.gateway.apife import ApiGateway, make_gateway_app
    from seldon_core_tpu.gateway.firehose import Firehose

    async def run():
        engine = EngineService(deployment(SIMPLE, name="d1"))
        gw = ApiGateway(require_auth=False, firehose=Firehose(max_queue=16))
        gw.store.register(engine.deployment, {"p": engine})
        await gw.predict(SeldonMessage.from_array(np.ones((1, 2))))
        port = await _free_port()
        runner = await serve_app(make_gateway_app(gw), "127.0.0.1", port)
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{port}/stats") as r:
                    assert r.status == 200
                    return json.loads(await r.text())
        finally:
            await runner.cleanup()
    doc = asyncio.run(run())
    assert doc["gateway"]["deployments"] == ["d1"]
    assert doc["firehose"]["max_queue"] == 16
    assert doc["firehose"]["dropped"] == 0
    assert "ingress:predictions" in doc["telemetry"]["request_latency_s"]


def test_generation_records_ttft_and_decode_rate():
    """Eager generate() and stream_chunks() feed the generation SLO
    families; the jit-traced serving path must NOT record trace-time
    constants (tested via jit below)."""
    from seldon_core_tpu.models.generate import generate, stream_chunks
    from seldon_core_tpu.models.transformer import LMConfig, lm_init

    cfg = LMConfig(vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                   dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)

    generate(params, prompt, cfg, max_new_tokens=5)
    snap = RECORDER.snapshot()
    assert snap["generation"]["ttft_s"]["count"] == 1
    assert snap["generation"]["decode_tokens_per_s"]["count"] == 1
    assert snap["generation"]["decode_tokens_per_s"]["max"] > 0

    for _ in stream_chunks(params, prompt, cfg, max_new_tokens=5, chunk=2):
        pass
    snap = RECORDER.snapshot()
    assert snap["generation"]["ttft_s"]["count"] == 2
    assert snap["generation"]["decode_tokens_per_s"]["count"] == 2

    # traced: the telemetry guard must keep trace-time wall clocks out
    RECORDER.reset()
    jitted = jax.jit(lambda p, t: generate(p, t, cfg, max_new_tokens=5))
    np.asarray(jitted(params, prompt))
    snap = RECORDER.snapshot()
    assert snap["generation"]["ttft_s"]["count"] == 0


def test_speculative_records_accept_ratio():
    from seldon_core_tpu.models.speculative import SpeculativeGenerator

    unit = SpeculativeGenerator(
        vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_new_tokens=6, k=2)
    state = unit.init_state(None)
    from seldon_core_tpu.models.speculative import speculative_generate

    toks, rounds = speculative_generate(
        state["target"], state["draft"],
        jnp.asarray([[1, 2, 3]], jnp.int32),
        unit.target_cfg, unit.draft_cfg, max_new_tokens=6, k=2)
    assert np.asarray(toks).shape == (1, 6)
    snap = RECORDER.snapshot()
    assert snap["generation"]["speculative_accept_ratio"]["count"] == 1
    ratio = snap["generation"]["speculative_accept_ratio"]["max"]
    assert 0.0 <= ratio <= 1.0


def test_speculative_max_rounds_caps_cache():
    """max_rounds caps the round-aligned cache; when the cap covers the
    actual rounds used, outputs are bit-identical to the uncapped run."""
    from seldon_core_tpu.models.speculative import speculative_generate
    from seldon_core_tpu.models.transformer import LMConfig, lm_init

    cfg = LMConfig(vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                   dtype=jnp.float32)
    kt, kd = jax.random.split(jax.random.key(7))
    tp, dp = lm_init(kt, cfg), lm_init(kd, cfg)
    prompt = jnp.asarray([[4, 5, 6, 7]], jnp.int32)
    ref, rounds = speculative_generate(tp, dp, prompt, cfg, cfg,
                                       max_new_tokens=8, k=2)
    used = int(np.asarray(rounds)[0])
    got, _ = speculative_generate(tp, dp, prompt, cfg, cfg,
                                  max_new_tokens=8, k=2,
                                  max_rounds=max(used, 1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Request-audit firehose
# ---------------------------------------------------------------------------


def test_audit_disabled_by_default_records_nothing():
    log = AuditLog()
    assert log.enabled is False
    assert log.record(puid="x") is False
    assert log.snapshot()["recorded"] == 0


def test_audit_drop_accounting_when_queue_full():
    """record() must never block: with no drain running, a full queue
    counts drops and returns immediately."""
    log = AuditLog(sink=lambda ev: None, max_queue=8)
    assert log.enabled is True
    accepted = sum(log.record(puid=f"p{i}") for i in range(20))
    assert accepted == 8
    snap = log.snapshot()
    assert snap["recorded"] == 8
    assert snap["dropped"] == 12
    assert snap["queued"] == 8
    # the prometheus-side accounting mirrors the drops
    text = RECORDER.exposition().decode()
    assert 'seldon_tpu_audit_events_total{outcome="dropped"}' in text


def test_audit_drains_to_jsonl(tmp_path):
    path = str(tmp_path / "audit.jsonl")

    async def run():
        log = AuditLog(path=path, max_queue=64)
        for i in range(5):
            log.record(puid=f"p{i}", method="predict", status=200)
        await log.flush()
        await log.stop()
    asyncio.run(run())
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert [e["puid"] for e in lines] == [f"p{i}" for i in range(5)]
    assert all("ts" in e for e in lines)


def test_engine_audits_unary_and_streaming_requests():
    """puid-correlated audit entries for both request kinds, with the
    serving telemetry fields (graph path, rows, latency, tokens)."""
    events = []

    async def run():
        audit = AuditLog(sink=events.append, max_queue=256)
        engine = EngineService(
            SeldonDeploymentSpec.from_json_dict(GEN_SPEC), audit=audit)
        assert engine.mode == "compiled" and engine.can_stream()
        msg = SeldonMessage.from_array(np.asarray([[1.0, 2.0, 3.0]]))
        msg.meta.puid = "unary-puid-000000000000000000"
        await engine.predict(msg)
        raw = json.dumps({"data": {"ndarray": [[1, 2, 3]]},
                          "meta": {"puid": "stream-puid-00000000000000000"}})
        async for _ in engine.generate_stream(raw, chunk=3):
            pass
        await audit.flush()
        await audit.stop()
    asyncio.run(run())

    unary = [e for e in events if e["method"] == "predict"]
    stream = [e for e in events if e["method"] == "generate_stream"]
    assert len(unary) == 1 and len(stream) == 1
    assert unary[0]["puid"] == "unary-puid-000000000000000000"
    assert unary[0]["graph"] == "g"
    assert unary[0]["rows"] == 1
    assert unary[0]["status"] == 200
    assert unary[0]["latency_ms"] > 0
    assert stream[0]["puid"] == "stream-puid-00000000000000000"
    assert stream[0]["tokens"] == 6  # max_new_tokens
    assert stream[0]["ttft_ms"] > 0
    assert stream[0]["tokens_per_s"] > 0
    # the stream fed the SLO families exactly once (stream_chunks is the
    # canonical recorder; the engine edge must not double-count)
    snap = RECORDER.snapshot()
    assert snap["generation"]["ttft_s"]["count"] == 1
    assert snap["generation"]["decode_tokens_per_s"]["count"] == 1


def test_engine_audits_abandoned_stream():
    """A client that drops the SSE connection mid-stream must still leave
    a puid-correlated audit entry (status 499) — failed streams consumed
    device work and are exactly the requests operators investigate."""
    events = []

    async def run():
        audit = AuditLog(sink=events.append, max_queue=64)
        engine = EngineService(
            SeldonDeploymentSpec.from_json_dict(GEN_SPEC), audit=audit)
        raw = json.dumps({"data": {"ndarray": [[1, 2, 3]]},
                          "meta": {"puid": "abandoned-puid-000000000000"}})
        agen = engine.generate_stream(raw, chunk=2)
        await agen.__anext__()  # first chunk only, then hang up
        await agen.aclose()
        await audit.flush()
        await audit.stop()
    asyncio.run(run())
    stream = [e for e in events if e["method"] == "generate_stream"]
    assert len(stream) == 1
    assert stream[0]["puid"] == "abandoned-puid-000000000000"
    assert stream[0]["status"] == 499
    assert stream[0]["ttft_ms"] > 0


@pytest.fixture
def _restore_jax_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_by_env_sets_no_dir_in_code(
        monkeypatch, tmp_path, _restore_jax_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: JAX owns the placement — the
    program configures no directory of its own (and creates none)."""
    from seldon_core_tpu.runtime import compilecache

    placed = str(tmp_path / "xla")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setattr(compilecache, "_DEFAULT_DIR",
                        str(tmp_path / "never"))
    assert compilecache.compile_cache_dir() == placed
    assert compilecache.enable_compile_cache() is True
    assert jax.config.jax_compilation_cache_dir == _restore_jax_cache_dir
    assert not (tmp_path / "never").exists()
    assert RECORDER.snapshot()["compile_cache_events"].get("enabled") == 1
    monkeypatch.setenv("SELDON_COMPILE_CACHE", "0")
    assert compilecache.enable_compile_cache() is False
    assert RECORDER.snapshot()["compile_cache_events"].get("disabled") == 1


def test_compile_cache_default_is_fixed_dir_inside_checkout(
        monkeypatch, _restore_jax_cache_dir):
    """Unset: one fixed git-ignored directory next to the package —
    never ~, a temp name, a pid or a time (the path keys the cache)."""
    import os

    from seldon_core_tpu.runtime import compilecache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".xla_cache")
    assert compilecache.compile_cache_dir() == want
    assert compilecache.enable_compile_cache() is True
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".xla_cache/" in f.read().split()
