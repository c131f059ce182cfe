"""Causal distributed tracing: span trees, W3C propagation, sampling,
critical path, export.

The acceptance scenario (ISSUE 3): a request through a COMBINER graph
with one injected transient failure yields ONE trace tree containing the
root request span, per-node child spans, a retry-attempt event with
backoff + ``deadline_remaining_ms``, a batching queue-wait span, and a
critical path whose summed durations are within 10% of the root span
duration; ``/trace/export`` validates as Chrome trace-event JSON.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from seldon_core_tpu.graph.spec import ComponentBinding, SeldonDeploymentSpec
from seldon_core_tpu.messages import Feedback, SeldonMessage
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.utils.tracing import (
    TRACER,
    TraceContext,
    Tracer,
    chrome_trace,
    critical_path,
    current_trace_context,
    export_document,
    parse_traceparent,
    trace_document,
    trace_scope,
    traceparent_header_value,
)


def deployment(graph, components=None):
    return SeldonDeploymentSpec.from_json_dict(
        {
            "spec": {
                "name": "d",
                "predictors": [
                    {"name": "p", "graph": graph, "components": components or []}
                ],
            }
        }
    )


@pytest.fixture(autouse=True)
def _clean_tracer():
    TRACER.clear()
    TRACER.disable()
    TRACER.sample = 1.0
    yield
    TRACER.clear()
    TRACER.disable()
    TRACER.sample = 1.0


# ---------------------------------------------------------------------------
# Core tracer semantics
# ---------------------------------------------------------------------------


def test_nested_spans_build_a_tree():
    t = Tracer(enabled=True)
    with t.span("p1", "outer", kind="request", method="predict"):
        with t.span("p1", "middle", method="predict"):
            with t.span("p1", "leaf", kind="client", method="predict"):
                pass
        with t.span("p1", "sibling", method="route"):
            pass
    spans = {s.name: s for s in t.trace("p1")}
    assert len(spans) == 4
    outer = spans["outer"]
    assert outer.parent_span_id == ""
    assert {s.trace_id for s in spans.values()} == {outer.trace_id}
    assert spans["middle"].parent_span_id == outer.span_id
    assert spans["leaf"].parent_span_id == spans["middle"].span_id
    assert spans["sibling"].parent_span_id == outer.span_id
    # by_trace returns the same set, via the trace_id index
    assert len(t.by_trace(outer.trace_id)) == 4


def test_traceparent_roundtrip_and_malformed():
    t = Tracer(enabled=True)
    with t.span("p1", "root"):
        hdr = traceparent_header_value()
        ctx = current_trace_context()
        assert hdr == f"00-{ctx.trace_id}-{ctx.span_id}-01"
        parsed = parse_traceparent(hdr)
        assert parsed.trace_id == ctx.trace_id
        assert parsed.span_id == ctx.span_id
        assert parsed.sampled is True
    assert traceparent_header_value() is None  # no active trace
    for bad in (None, "", "garbage", "00-short-span-01",
                "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace id
                "ff-" + "1" * 32 + "-" + "1" * 16 + "-01",  # forbidden version
                "00-" + "x" * 32 + "-" + "1" * 16 + "-01"):  # non-hex
        assert parse_traceparent(bad) is None
    off = parse_traceparent("00-" + "a" * 32 + "-" + "b" * 16 + "-00")
    assert off is not None and off.sampled is False


def test_remote_context_adoption_parents_next_span():
    t = Tracer(enabled=True)
    remote = TraceContext(trace_id="c" * 32, span_id="d" * 16, puid="pX")
    with trace_scope(remote):
        with t.span("", "local", kind="server"):
            pass
    (span,) = t.by_trace("c" * 32)
    assert span.parent_span_id == "d" * 16
    assert span.puid == "pX"  # inherited from the adopted context


def test_head_sampling_zero_records_nothing_including_children():
    t = Tracer(enabled=True, sample=0.0)
    with t.span("p1", "root", kind="request") as sp:
        assert sp is None
        ctx = current_trace_context()
        assert ctx is not None and ctx.sampled is False
        with t.span("p1", "child"):
            pass
        # the decision also rides the wire: a remote hop adopting this
        # context records nothing either
        hdr = traceparent_header_value()
        assert hdr is not None and hdr.endswith("-00")
        with trace_scope(parse_traceparent(hdr)):
            with t.span("p1", "remote-side"):
                pass
    assert t.recent(100) == []
    assert t.sampled_out_total == 1


def test_events_attach_to_active_span():
    t = Tracer(enabled=True)
    assert t.event("orphan") is False  # no active span
    with t.span("p1", "call", kind="client"):
        assert t.event("retry", attempt=1, backoff_ms=5.0) is True
    (span,) = t.trace("p1")
    assert span.events[0]["name"] == "retry"
    assert span.events[0]["attrs"]["backoff_ms"] == 5.0
    json.dumps(span.to_json_dict())  # events stay JSON-safe


def test_index_stays_correct_across_eviction():
    t = Tracer(capacity=10, enabled=True)
    for i in range(50):
        with t.span(f"p{i % 4}", "n"):
            pass
    assert len(t.recent(1000)) == 10
    # index agrees with the ring exactly (no stale evicted entries)
    by_scan = {}
    for s in t.recent(1000):
        by_scan.setdefault(s.puid, []).append(s)
    for puid in ("p0", "p1", "p2", "p3"):
        assert t.trace(puid) == sorted(
            by_scan.get(puid, []), key=lambda s: s.start_s
        )
    # internal: no index key holds more than the ring can
    assert sum(len(v) for v in t._by_puid.values()) == 10
    assert sum(len(v) for v in t._by_trace.values()) == 10


def test_critical_path_sums_to_root_and_clips_children():
    t = Tracer(enabled=True)
    t0 = 1000.0
    root = _span(t, "root", "request", t0, 100.0)
    a = _span(t, "a", "node", t0 + 0.010, 30.0, parent=root)
    _span(t, "a-call", "client", t0 + 0.015, 20.0, parent=a)
    _span(t, "b", "node", t0 + 0.050, 45.0, parent=root)
    spans = t.by_trace(root.trace_id)
    r, segments = critical_path(spans)
    assert r.span_id == root.span_id
    total = sum(ms for _, ms in segments)
    assert total == pytest.approx(100.0, rel=1e-6)
    names_on_path = {sp.name for sp, _ in segments}
    assert "b" in names_on_path  # latest-ending child gates the root


def _span(t, name, kind, start_s, duration_ms, parent=None):
    from seldon_core_tpu.utils.tracing import Span, new_span_id, new_trace_id

    s = Span(
        puid="pc", name=name, kind=kind, method="m",
        start_s=start_s, duration_ms=duration_ms,
        trace_id=parent.trace_id if parent else new_trace_id(),
        span_id=new_span_id(),
        parent_span_id=parent.span_id if parent else "",
    )
    t.add(s)
    return s


def test_critical_path_clips_skewed_child_to_parent_window():
    """A child whose clock-skewed start precedes its parent's must not
    leak time outside the root duration — segments still sum exactly."""
    t = Tracer(enabled=True)
    t0 = 1000.0
    root = _span(t, "root", "request", t0, 100.0)
    # starts 50ms BEFORE the root (cross-host skew), ends inside it
    _span(t, "skewed", "client", t0 - 0.050, 90.0, parent=root)
    _, segments = critical_path(t.by_trace(root.trace_id))
    total = sum(ms for _, ms in segments)
    assert total == pytest.approx(100.0, rel=1e-6)


def test_chrome_trace_export_shape():
    t = Tracer(enabled=True)
    with t.span("p1", "root", kind="request"):
        t.event("retry", attempt=1)
    doc = chrome_trace(t.trace("p1"))
    json.loads(json.dumps(doc))  # serializable
    events = doc["traceEvents"]
    assert any(e["ph"] == "X" for e in events)
    assert any(e["ph"] == "i" and e["name"] == "retry" for e in events)
    assert any(e["ph"] == "M" for e in events)  # lane names
    for e in events:
        assert "name" in e and "ph" in e and "pid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0


# ---------------------------------------------------------------------------
# Engine integration: queue-wait spans, audit trace ids
# ---------------------------------------------------------------------------


def test_queue_wait_span_parented_under_request():
    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )

    async def run():
        TRACER.enable()
        engine = EngineService(spec)
        assert engine.batcher is not None
        msg = SeldonMessage.from_array(np.ones((2, 3), np.float64))
        resp = await engine.predict(msg)
        spans = TRACER.trace(resp.meta.puid)
        request = next(s for s in spans if s.kind == "request")
        queue = next(s for s in spans if s.kind == "queue")
        assert queue.trace_id == request.trace_id
        assert queue.parent_span_id == request.span_id
        assert queue.attrs["rows"] == 2
        # the stacked flush span exists but stands alone (multi-request)
        assert any(s.kind == "batch" for s in TRACER.recent(200))

    asyncio.run(run())


def test_audit_records_carry_trace_id():
    from seldon_core_tpu.utils.telemetry import AuditLog

    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )
    events = []

    async def run():
        TRACER.enable()
        engine = EngineService(spec, audit=AuditLog(sink=events.append))
        msg = SeldonMessage.from_array(np.ones((1, 3), np.float64))
        resp = await engine.predict(msg)
        await engine.audit.flush()
        return resp

    resp = asyncio.run(run())
    assert events, "audit sink saw no events"
    (ev,) = [e for e in events if e["puid"] == resp.meta.puid]
    spans = TRACER.trace(resp.meta.puid)
    assert ev["trace_id"] == spans[0].trace_id


# ---------------------------------------------------------------------------
# Cross-process propagation + acceptance scenario
# ---------------------------------------------------------------------------


def _flaky(times: int):
    """aiohttp middleware: first ``times`` /predict calls answer a
    retryable 503 — the injected transient failure."""
    from aiohttp import web

    left = {"n": times}

    @web.middleware
    async def mw(request, handler):
        if request.path == "/predict" and left["n"] > 0:
            left["n"] -= 1
            return web.Response(status=503, text="injected transient")
        return await handler(request)

    return mw


def test_combiner_trace_acceptance_rest():
    """The ISSUE 3 acceptance criterion, REST lane: host-mode COMBINER
    over two remote engines (served as MODEL leaves via /predict), one
    transient 503 injected, under a request deadline."""
    from aiohttp.test_utils import TestServer

    from seldon_core_tpu.runtime.client import RestNodeRuntime
    from seldon_core_tpu.runtime.resilience import deadline_scope
    from seldon_core_tpu.runtime.rest import make_engine_app

    leaf_spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )
    outer_spec = deployment(
        {
            "name": "ens",
            "implementation": "AVERAGE_COMBINER",
            "type": "COMBINER",
            "children": [
                {"name": "a", "type": "MODEL"},
                {"name": "b", "type": "MODEL"},
            ],
        }
    )

    async def run():
        TRACER.enable()
        inner_a = EngineService(leaf_spec)
        inner_b = EngineService(leaf_spec)
        assert inner_a.batcher is not None  # queue-wait spans exist
        app_a = make_engine_app(inner_a)
        app_a.middlewares.append(_flaky(1))
        srv_a, srv_b = TestServer(app_a), TestServer(make_engine_app(inner_b))
        await srv_a.start_server()
        await srv_b.start_server()
        try:
            nodes = {
                n.name: n
                for n in outer_spec.predictor("p").graph.walk()
            }
            outer = EngineService(
                outer_spec,
                force_host=True,
                extra_runtimes={
                    "a": RestNodeRuntime(
                        nodes["a"],
                        ComponentBinding(name="a", runtime="rest",
                                         host="127.0.0.1", port=srv_a.port),
                    ),
                    "b": RestNodeRuntime(
                        nodes["b"],
                        ComponentBinding(name="b", runtime="rest",
                                         host="127.0.0.1", port=srv_b.port),
                    ),
                },
            )
            msg = SeldonMessage.from_array(np.ones((1, 3), np.float64))
            msg.meta.puid = "acceptance-puid"
            with deadline_scope(10.0):
                resp = await outer.predict(msg)
            assert resp.status is None or resp.status.status == "SUCCESS"
            await outer.close()
        finally:
            await srv_a.close()
            await srv_b.close()

    asyncio.run(run())

    doc = trace_document(TRACER, puid="acceptance-puid")
    spans = doc["spans"]
    # ONE trace id across the outer engine, both node clients, and both
    # inner engines' request + queue spans
    trace_ids = {s["trace_id"] for s in spans if s.get("trace_id")}
    assert len(trace_ids) == 1, f"expected one trace, got {trace_ids}"
    kinds = {(s["kind"], s["name"]) for s in spans}
    assert ("request", "request") in kinds          # root + inner engines
    assert ("client", "a") in kinds and ("client", "b") in kinds
    assert ("queue", "batch_queue") in kinds        # micro-batch wait
    # the injected 503 shows up as a retry event with backoff and the
    # remaining deadline budget
    retry_events = [
        e
        for s in spans
        for e in s.get("events", [])
        if e["name"] == "retry"
    ]
    assert retry_events, "no retry event recorded for the injected 503"
    attrs = retry_events[0]["attrs"]
    assert attrs["backoff_ms"] >= 0
    assert attrs["deadline_remaining_ms"] > 0
    # assembled tree: a single root whose subtree covers the client hops
    roots = doc["tree"]
    root_nodes = [r for r in roots if r["kind"] == "request"]
    assert root_nodes, "no request root in the assembled tree"
    # critical path accounts for the root's duration within 10%
    total = sum(seg["self_ms"] for seg in doc["critical_path"])
    assert total == pytest.approx(doc["root_duration_ms"], rel=0.10)
    # per-phase decomposition covers the same wall clock
    assert doc["phases"]["total_ms"] == pytest.approx(total, abs=0.05)
    assert doc["phases"]["network_ms"] > 0
    # export validates as Chrome trace-event JSON
    export = export_document(TRACER, puid="acceptance-puid")
    parsed = json.loads(json.dumps(export))
    assert isinstance(parsed["traceEvents"], list) and parsed["traceEvents"]
    for e in parsed["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e


def test_grpc_lane_propagates_trace_context():
    """Engine-as-MODEL-leaf over gRPC: the client span and the remote
    engine's request/queue spans share one trace id via traceparent
    metadata."""
    grpc = pytest.importorskip("grpc")  # noqa: F841

    from seldon_core_tpu.runtime.client import GrpcNodeRuntime
    from seldon_core_tpu.runtime.grpc_server import make_engine_grpc_server

    leaf_spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )

    async def run():
        TRACER.enable()
        inner = EngineService(leaf_spec)
        server = make_engine_grpc_server(inner, "127.0.0.1", 0)
        port = server.add_insecure_port("127.0.0.1:0")
        await server.start()
        try:
            node = leaf_spec.predictor("p").graph
            rt = GrpcNodeRuntime(
                node,
                ComponentBinding(name="m", runtime="grpc",
                                 host="127.0.0.1", port=port),
            )
            msg = SeldonMessage.from_array(np.ones((1, 3), np.float64))
            msg.meta.puid = "grpc-trace-puid"
            with TRACER.span("grpc-trace-puid", "caller", kind="request",
                             method="predict"):
                resp = await rt.predict(msg)
            assert resp.status is None or resp.status.status == "SUCCESS"
            await rt.close()
        finally:
            await server.stop(None)

    asyncio.run(run())
    spans = TRACER.trace("grpc-trace-puid")
    trace_ids = {s.trace_id for s in spans if s.trace_id}
    assert len(trace_ids) == 1
    kinds = {s.kind for s in spans}
    assert "client" in kinds, "gRPC client span missing (REST parity)"
    assert "request" in kinds
    client = next(s for s in spans if s.kind == "client")
    remote_request = next(
        s for s in spans if s.kind == "request" and s.name == "request"
    )
    assert remote_request.parent_span_id == client.span_id


def test_client_feedback_and_aggregate_puid_correlation():
    """Satellite: feedback spans fall back to the request's puid when the
    response is absent; aggregate uses the active trace context instead
    of guessing from msgs[0]."""
    from aiohttp.test_utils import TestServer

    from seldon_core_tpu.runtime.client import RestNodeRuntime
    from seldon_core_tpu.runtime.microservice import build_runtime
    from seldon_core_tpu.runtime.rest import make_unit_app

    async def run():
        TRACER.enable()
        runtime = build_runtime("AVERAGE_COMBINER", "COMBINER", unit_name="u")
        srv = TestServer(make_unit_app(runtime))
        await srv.start_server()
        try:
            node = runtime.node
            rt = RestNodeRuntime(
                node,
                ComponentBinding(name="u", runtime="rest",
                                 host="127.0.0.1", port=srv.port),
            )
            # feedback with NO response message but a request puid
            req = SeldonMessage.from_array(np.ones((1, 2), np.float64))
            req.meta.puid = "fb-req-puid"
            await rt.send_feedback(Feedback(request=req, reward=1.0), -1)
            # aggregate inside an active trace: ctx puid wins over msgs[0]
            m1 = SeldonMessage.from_array(np.ones((1, 2), np.float64))
            m2 = SeldonMessage.from_array(np.ones((1, 2), np.float64))
            with TRACER.span("ctx-puid", "request", kind="request"):
                await rt.aggregate([m1, m2])
            await rt.close()
        finally:
            await srv.close()

    asyncio.run(run())
    fb_spans = TRACER.trace("fb-req-puid")
    assert any(
        s.kind == "client" and s.method == "send-feedback" for s in fb_spans
    )
    agg_spans = TRACER.trace("ctx-puid")
    assert any(
        s.kind == "client" and s.method == "aggregate" for s in agg_spans
    )


# ---------------------------------------------------------------------------
# Admin surface
# ---------------------------------------------------------------------------


def test_trace_admin_is_post_only():
    """The PR-3 GET-alias deprecation window is closed: mutation via GET
    now answers 405 (the POST route exists, the GET does not) and flips
    nothing."""
    from seldon_core_tpu.runtime.rest import make_engine_app

    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )

    async def run():
        from aiohttp.test_utils import TestClient, TestServer

        engine = EngineService(spec)
        async with TestClient(TestServer(make_engine_app(engine))) as client:
            r = await client.post("/trace/enable")
            assert r.status == 200 and "Deprecation" not in r.headers
            assert TRACER.enabled
            r = await client.post("/trace/disable")
            assert r.status == 200
            assert not TRACER.enabled
            # deprecation window closed: GET mutation is gone
            r = await client.get("/trace/enable")
            assert r.status in (404, 405)
            assert not TRACER.enabled
            await client.post("/trace/enable")
            r = await client.get("/trace/disable")
            assert r.status in (404, 405)
            assert TRACER.enabled
            await client.post("/trace/disable")

    asyncio.run(run())


def test_rest_trace_export_endpoint():
    from seldon_core_tpu.runtime.rest import make_engine_app

    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )

    async def run():
        from aiohttp.test_utils import TestClient, TestServer

        engine = EngineService(spec)
        async with TestClient(TestServer(make_engine_app(engine))) as client:
            await client.post("/trace/enable")
            body = json.dumps({"meta": {"puid": "exp-puid"},
                               "data": {"ndarray": [[1.0, 2.0, 3.0]]}})
            r = await client.post(
                "/api/v0.1/predictions", data=body,
                headers={"Content-Type": "application/json"},
            )
            assert r.status == 200
            r = await client.get("/trace/export", params={"puid": "exp-puid"})
            doc = await r.json()
            assert doc["traceEvents"]
            # /stats carries the tracer health block
            r = await client.get("/stats")
            stats = await r.json()
            assert stats["tracer"]["enabled"] is True
            assert stats["tracer"]["sample"] == 1.0
            assert stats["tracer"]["spans"] >= 1

    asyncio.run(run())


def test_httpfast_trace_routes_and_post_admin():
    from seldon_core_tpu.runtime.httpfast import serve_fast

    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )

    async def run():
        import aiohttp

        engine = EngineService(spec)
        server = await serve_fast(engine, "127.0.0.1", 0)
        base = f"http://127.0.0.1:{server.port}"
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.post(base + "/trace/enable") as r:
                    assert r.status == 200
                body = json.dumps({"meta": {"puid": "fast-puid"},
                                   "data": {"ndarray": [[1.0, 2.0, 3.0]]}})
                # traceparent adoption on the fast lane
                parent = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
                async with sess.post(
                    base + "/api/v0.1/predictions", data=body,
                    headers={"Content-Type": "application/json",
                             "traceparent": parent},
                ) as r:
                    assert r.status == 200
                async with sess.get(
                    base + "/trace", params={"puid": "fast-puid"}
                ) as r:
                    doc = await r.json()
                assert any(
                    s.get("trace_id") == "a" * 32 for s in doc["spans"]
                ), "fast lane did not adopt the traceparent"
                async with sess.get(
                    base + "/trace/export", params={"puid": "fast-puid"}
                ) as r:
                    export = await r.json()
                assert export["traceEvents"]
                async with sess.post(base + "/trace/disable") as r:
                    assert r.status == 200
                    assert "Deprecation" not in r.headers
                # deprecation window closed: GET mutation gone (lane
                # parity with the aiohttp app's 405)
                async with sess.get(base + "/trace/enable") as r:
                    assert r.status in (404, 405)
                assert not TRACER.enabled
                async with sess.get(base + "/trace/disable") as r:
                    assert r.status in (404, 405)
        finally:
            await server.stop()

    asyncio.run(run())


def test_gateway_feedback_adopts_traceparent():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.gateway.apife import ApiGateway, make_gateway_app

    spec = deployment(
        {"name": "m", "implementation": "SIMPLE_MODEL", "type": "MODEL"}
    )

    async def run():
        TRACER.enable()
        engine = EngineService(spec)
        gw = ApiGateway(require_auth=False)
        gw.store.register(spec, {"p": engine})
        parent = "00-" + "e" * 32 + "-" + "f" * 16 + "-01"
        fb = {"reward": 1.0,
              "response": {"meta": {"puid": "gw-fb-puid"}}}
        async with TestClient(TestServer(make_gateway_app(gw))) as client:
            r = await client.post(
                "/api/v0.1/feedback", data=json.dumps(fb),
                headers={"Content-Type": "application/json",
                         "traceparent": parent},
            )
            assert r.status == 200
        await engine.close()

    asyncio.run(run())
    spans = TRACER.by_trace("e" * 32)
    assert spans, "gateway feedback did not join the caller's trace"
    gw_span = next(s for s in spans if s.name == "gateway")
    assert gw_span.parent_span_id == "f" * 16
    assert gw_span.puid == "gw-fb-puid"


# ---------------------------------------------------------------------------
# Profile windows (no real profiler session: jax.profiler is a stand-in)
# ---------------------------------------------------------------------------


class _FakeProfiler:
    """start/stop counters; ``stop_trace`` takes ``stop_s`` like the real
    one takes seconds to write a chip trace."""

    def __init__(self, stop_s=0.0):
        import jax

        self.ProfileOptions = jax.profiler.ProfileOptions
        self.stop_s = stop_s
        self.started, self.stopped, self.options = 0, 0, []

    def start_trace(self, logdir, profiler_options=None):
        assert self.started == self.stopped, "profiler already active"
        self.started += 1
        self.options.append(profiler_options)

    def stop_trace(self):
        time.sleep(self.stop_s)
        self.stopped += 1


def test_profile_window_keeps_the_host_tracer_and_drops_the_python_one(
        monkeypatch, tmp_path):
    import jax

    import seldon_core_tpu.utils.tracing as tracing

    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    res = tracing.profile_window_start(str(tmp_path / "w"), 30.0)
    try:
        assert res["active"] is True
        with pytest.raises(tracing.ProfileBusyError):
            tracing.profile_window_start(str(tmp_path / "w2"), 1.0)
    finally:
        out = tracing.profile_window_stop()
    assert out["last"]["artifact"].endswith("w")
    (opts,) = fake.options
    # the scheduler's own TraceAnnotations need the host tracer; the
    # Python tracer (an event per Python call) is what made a stop slow
    assert opts.python_tracer_level == 0 and opts.host_tracer_level >= 1
    assert (fake.started, fake.stopped) == (1, 1)


def test_profile_window_stop_answers_only_when_no_stop_is_in_flight(
        monkeypatch, tmp_path):
    """The auto-stop timer (or a first stop) may still be writing the
    trace when a second stop arrives: the second one WAITS, so that its
    answer means "the trace is on disk and a start is accepted" — it used
    to answer at once while the profile lock was still held, and the next
    start was refused with 409."""
    import threading

    import jax

    import seldon_core_tpu.utils.tracing as tracing

    fake = _FakeProfiler(stop_s=0.4)
    monkeypatch.setattr(jax, "profiler", fake)
    tracing.profile_window_start(str(tmp_path / "w"), 30.0)
    first = threading.Thread(target=tracing.profile_window_stop)
    first.start()
    deadline = time.monotonic() + 5
    while tracing.profile_window_status()["active"] \
            and time.monotonic() < deadline:
        time.sleep(0.005)            # the first stop is now inside stop_trace
    assert fake.stopped == 0
    out = tracing.profile_window_stop()          # the second stop
    assert fake.stopped == 1, "the second stop answered before the first"
    assert out["active"] is False and out["last"]["artifact"].endswith("w")
    # and a start is accepted at once
    again = tracing.profile_window_start(str(tmp_path / "w3"), 30.0)
    assert again["active"] is True
    tracing.profile_window_stop()
    first.join(timeout=5)
    assert (fake.started, fake.stopped) == (2, 2)
