"""REST servers (aiohttp) — external prediction API + internal microservice API.

External (per-predictor engine, mirroring engine RestClientController.java):
  POST /api/v0.1/predictions   JSON body or form field ``json=``
  POST /api/v0.1/feedback
  GET  /ping /ready /pause /unpause (admin drain,
       engine RestClientController.java:57-99)
  GET  /prometheus             metric exposition
  GET  /stats                  flight-recorder JSON snapshot (batcher,
       latency percentiles, generation telemetry — utils/telemetry.py)

Internal (single-unit microservice, mirroring wrappers/python/
model_microservice.py REST routes):
  POST /predict /transform-input /transform-output /route /aggregate
       /send-feedback

Both accept the reference's form-encoded ``json=`` convention
(engine InternalPredictionService.java:240-242) as well as a plain JSON body.
"""

from __future__ import annotations

import json
from typing import Optional

from aiohttp import web

from seldon_core_tpu.graph.interpreter import InProcessNodeRuntime
from seldon_core_tpu.graph.spec import GraphSpecError
from seldon_core_tpu.messages import (
    Feedback,
    SeldonMessage,
    SeldonMessageError,
    SeldonMessageList,
)
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.resilience import (
    DEADLINE_HEADER,
    current_deadline,
    deadline_ms_header,
    maybe_deadline_scope,
)
from seldon_core_tpu.utils.metrics import CONTENT_TYPE_LATEST
from seldon_core_tpu.utils.tracing import (
    TRACEPARENT_HEADER,
    parse_traceparent,
    trace_scope,
)

__all__ = ["make_engine_app", "make_unit_app", "serve_app"]

#: binary tensor wire contract (runtime/wire.py)
_WIRE_CTYPE = "application/x-seldon-tensor"


async def _payload_text(request: web.Request) -> str:
    """JSON body or form-encoded ``json=`` field.  curl sends
    ``application/x-www-form-urlencoded`` by default even for raw JSON
    bodies, so a form without a ``json`` field falls back to the raw body."""
    body = await request.read()
    ctype = request.content_type or ""
    if "form" in ctype:
        from urllib.parse import parse_qs

        form = parse_qs(body.decode("utf-8", "replace"), keep_blank_values=True)
        if "json" in form:
            return form["json"][0]
    return body.decode("utf-8", "replace")


def _msg_response(msg: SeldonMessage, status: int = 200) -> web.Response:
    return web.Response(
        text=msg.to_json(), status=status, content_type="application/json"
    )


def _error_response(info: str, code: int = 400) -> web.Response:
    return _msg_response(SeldonMessage.failure(info, code=code), status=code)


def _request_budget_s(request: web.Request) -> Optional[float]:
    """Deadline budget from the ``Seldon-Deadline-Ms`` header (None when
    absent/malformed — resilience layer, gRPC-style deadline
    propagation)."""
    return deadline_ms_header(request.headers.get(DEADLINE_HEADER))


def _request_trace_scope(request: web.Request):
    """Adopt the caller's W3C ``traceparent`` context (None/malformed →
    fresh trace) so this process's spans join the caller's tree."""
    return trace_scope(parse_traceparent(request.headers.get(TRACEPARENT_HEADER)))


def _request_qos_scope(request: web.Request):
    """Adopt the caller's tenant/tier identity (``Seldon-Tenant`` /
    ``Seldon-Tier`` — the gateway forwards both) so engine-side
    admission, the brownout ladder and the genserver's tier lanes see
    the same QoS identity the ingress resolved."""
    from seldon_core_tpu.runtime.qos import (
        TENANT_HEADER,
        TIER_HEADER,
        qos_scope,
    )

    return qos_scope(request.headers.get(TENANT_HEADER),
                     request.headers.get(TIER_HEADER))


async def _quality_reference(request: web.Request) -> web.Response:
    """POST /quality/reference — freeze/reset the drift reference window
    (one handler shared by the engine and unit apps; the fast lane
    adapts the same parse in httpfast.py)."""
    from seldon_core_tpu.utils.quality import QUALITY, parse_reference_action

    try:
        action, node = parse_reference_action(
            await request.read(),
            request.query.get("action"), request.query.get("node"),
        )
    except ValueError as e:
        return _error_response(str(e))
    return web.json_response(QUALITY.reference_control(action, node=node))


# ---------------------------------------------------------------------------
# Engine app
# ---------------------------------------------------------------------------


def make_engine_app(engine: EngineService) -> web.Application:
    app = web.Application(client_max_size=256 * 1024 * 1024)

    async def predictions(request: web.Request) -> web.Response:
        if (request.content_type or "") == _WIRE_CTYPE:
            return await predictions_wire(request)
        try:
            with _request_trace_scope(request), \
                    maybe_deadline_scope(_request_budget_s(request)), \
                    _request_qos_scope(request):
                text, status = await engine.predict_json(
                    await _payload_text(request)
                )
        except SeldonMessageError as e:
            return _error_response(str(e), code=e.http_code)
        return web.Response(
            text=text, status=status or 200, content_type="application/json"
        )

    async def predictions_wire(request: web.Request) -> web.Response:
        """``Content-Type: application/x-seldon-tensor`` — the binary
        tensor wire contract (runtime/wire.py): frame in, frame out, no
        JSON round trip.  Header-bound deadline/trace/QoS still apply
        (the frame sidecar tightens/joins them, never loosens)."""
        from seldon_core_tpu.runtime import wire
        from seldon_core_tpu.utils.telemetry import RECORDER

        if not wire.wire_enabled():
            return _error_response(
                "binary wire lane disabled (SELDON_TPU_WIRE=0)", code=415
            )
        body = await request.read()
        RECORDER.record_wire_request("rest", "binary")
        wire.account_copy(len(body))
        try:
            with _request_trace_scope(request), \
                    maybe_deadline_scope(_request_budget_s(request)), \
                    _request_qos_scope(request):
                status, parts = await engine.predict_wire(body)
        except wire.WireError as e:
            # unparseable bytes answer as JSON the peer can always read
            return _error_response(str(e), code=e.http_code)
        except SeldonMessageError as e:
            return _error_response(str(e), code=e.http_code)
        return web.Response(
            body=wire.join_parts(parts), status=status,
            content_type=_WIRE_CTYPE,
        )

    async def predict_alias(request: web.Request) -> web.Response:
        # internal-API alias: an engine IS a model from a parent graph's
        # perspective (the gRPC lane's Model/Predict alias, grpc_server.py)
        # — POST /predict lets a RestNodeRuntime dial an engine as a MODEL
        # leaf of a larger cross-process graph
        return await predictions(request)

    async def feedback(request: web.Request) -> web.Response:
        try:
            with _request_trace_scope(request), \
                    maybe_deadline_scope(_request_budget_s(request)):
                fb = Feedback.from_json(await _payload_text(request))
                ack = await engine.send_feedback(fb)
        except SeldonMessageError as e:
            return _error_response(str(e), code=e.http_code)
        status = 200 if ack.status is None or ack.status.status == "SUCCESS" else ack.status.code
        return _msg_response(ack, status=status or 200)

    async def ping(_): return web.Response(text="pong")

    async def ready(_):
        if not engine.ready():
            return web.Response(text="paused", status=503)
        open_breakers = engine.open_breakers()
        if open_breakers:
            # still ready (the graph serves, degraded) but the condition is
            # surfaced where orchestration probes look first
            return web.Response(
                text="ready (breakers open: %s)" % ",".join(open_breakers)
            )
        return web.Response(text="ready")

    async def pause(_):
        engine.pause()
        return web.Response(text="paused")

    async def unpause(_):
        engine.unpause()
        return web.Response(text="unpaused")

    async def prometheus(request: web.Request):
        # CONTENT_TYPE_LATEST carries the exposition-format version parameter;
        # aiohttp's content_type= kwarg rejects parameters, so set the header.
        # OpenMetrics (Accept-negotiated, or ?format=openmetrics for lane
        # parity with httpfast) carries the trace_id exemplars on
        # seldon_tpu_dispatch_seconds buckets
        openmetrics = (
            "application/openmetrics-text" in request.headers.get("Accept", "")
            or request.query.get("format") == "openmetrics"
        )
        from seldon_core_tpu.utils.metrics import OPENMETRICS_CONTENT_TYPE

        return web.Response(
            body=engine.metrics.exposition(openmetrics=openmetrics),
            headers={"Content-Type": (
                OPENMETRICS_CONTENT_TYPE if openmetrics else CONTENT_TYPE_LATEST
            )},
        )

    async def stats(_):
        # flight-recorder snapshot: batcher/bucket state, latency
        # percentiles, generation SLO telemetry — zero-dependency JSON
        return web.json_response(engine.stats())

    async def perf(_):
        # performance observatory: per-executable cost/MFU/roofline table
        # + HBM watermarks (utils/perf.py; docs/operations.md runbook)
        return web.json_response(engine.perf_document())

    async def genperf(_):
        # generation-lane flight recorder: per-tick latency percentiles,
        # host/device phase splits, bubble ledger, served decode MFU,
        # KV-block residency (utils/genperf.py; docs/operations.md
        # "reading the /genperf page" runbook)
        return web.json_response(engine.genperf_document())

    async def quality(_):
        # prediction-quality observatory: per-node drift table, feedback
        # reward/accuracy, outlier bridge, SLO burn rates
        # (utils/quality.py; docs/operations.md runbook)
        return web.json_response(engine.quality_document())

    async def overhead(_):
        # telemetry overhead budget: per-subsystem framework-time
        # decomposition from the fused hop records (utils/hotrecord.py;
        # docs/operations.md "telemetry overhead budget" runbook)
        return web.json_response(engine.overhead_document())

    async def autopilot(_):
        # learned cost-model autopilot: per-executable/pad-bucket latency
        # model table, knobs, misprediction distribution, shed counters
        # (runtime/autopilot.py; docs/operations.md runbook)
        return web.json_response(engine.autopilot_document())

    async def corpus(_):
        # durable perf corpus: per-key quantile sketches + segment state
        # (utils/perfcorpus.py; docs/operations.md runbook)
        return web.json_response(engine.corpus_document())

    async def costs(_):
        # resource-attribution ledger: per-tenant/deployment/phase
        # device-seconds, pad tax, KV-block-seconds, capacity
        # (utils/costledger.py; docs/operations.md runbook)
        return web.json_response(engine.costs_document())

    async def postmortems(request: web.Request) -> web.Response:
        # tail-sampled worst-request exemplars with automatic explainers
        # (utils/postmortem.py); ?puid= returns one full document
        return web.json_response(engine.postmortems_document(
            puid=request.query.get("puid", "")))

    async def trace(request: web.Request) -> web.Response:
        from seldon_core_tpu.utils.tracing import TRACER, trace_document

        return web.json_response(trace_document(
            TRACER,
            puid=request.query.get("puid", ""),
            trace_id=request.query.get("trace_id", ""),
            limit=int(request.query.get("limit", "100")),
        ))

    async def trace_export(request: web.Request) -> web.Response:
        # Chrome trace-event JSON — load in Perfetto / chrome://tracing.
        # The process track is named replica/role so exports merged
        # across the mesh (the gateway's federated export) read legibly
        from seldon_core_tpu.utils.tracing import TRACER, export_document

        return web.json_response(export_document(
            TRACER,
            puid=request.query.get("puid", ""),
            trace_id=request.query.get("trace_id", ""),
            limit=int(request.query.get("limit", "1000")),
            process_name=engine.process_track_name(),
        ))

    async def trace_enable(_):
        from seldon_core_tpu.utils.tracing import TRACER

        TRACER.enable()
        return web.Response(text="tracing enabled")

    async def trace_disable(_):
        from seldon_core_tpu.utils.tracing import TRACER

        TRACER.disable()
        return web.Response(text="tracing disabled")

    async def profile_start(request: web.Request) -> web.Response:
        # the per-engine half of a coordinated fleet profile window
        # (gateway/fleet.py): open a bounded jax.profiler trace in THIS
        # process; overlapping windows answer 409, never queue
        from seldon_core_tpu.utils.tracing import (
            ProfileBusyError,
            profile_window_start_request,
        )

        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 - empty body = defaults
            body = {}
        if not isinstance(body, dict):
            body = {}
        try:
            return web.json_response(profile_window_start_request(body))
        except ProfileBusyError as e:
            return web.json_response({"error": str(e)}, status=409)

    async def profile_stop(_):
        from seldon_core_tpu.utils.tracing import profile_window_stop

        # on the loop, as on the fast lane (httpfast._profile_stop says why)
        return web.json_response(profile_window_stop())

    async def profile_get(_):
        from seldon_core_tpu.utils.tracing import profile_window_status

        return web.json_response(profile_window_status())

    async def generate_stream(request: web.Request):
        """SSE token streaming (beyond-reference; see engine.generate_stream).
        Payload = SeldonMessage prompt + optional top-level ``chunk``."""
        try:  # full validation BEFORE any bytes: problems are a plain 400
            text, chunk = engine.prepare_stream_request(
                await _payload_text(request)
            )
        except SeldonMessageError as e:
            return _error_response(str(e))
        # tier rides task-locally for the stream's lifetime so the
        # genserver admits it on the right lane (runtime/qos.py)
        from seldon_core_tpu.runtime.qos import (
            TENANT_HEADER,
            TIER_HEADER,
            bind_qos,
        )

        bind_qos(request.headers.get(TENANT_HEADER),
                 request.headers.get(TIER_HEADER))
        agen = engine.generate_stream(text, chunk=chunk)
        # prime the generator BEFORE the 200 goes out: genserver
        # admission sheds (brownout tier shed, SELDON_TPU_GEN_MAX_WAITING
        # bound) raise on the first __anext__, and the shed contract
        # promises a typed retryable 503 — not a 200 with an in-band
        # error frame that status-code retry logic can never see
        first = None
        try:
            first = await agen.__anext__()
        except StopAsyncIteration:
            pass
        except SeldonMessageError as e:
            await agen.aclose()
            return _error_response(str(e), code=e.http_code)
        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache"},
        )
        await resp.prepare(request)
        try:
            if first is not None:
                await resp.write(b"data: " + first.encode() + b"\n\n")
            async for event in agen:
                await resp.write(b"data: " + event.encode() + b"\n\n")
        except Exception as e:  # mid-stream: terminal error frame
            import json as _json

            await resp.write(
                b'data: {"done": true, "error": %s}\n\n'
                % _json.dumps(str(e)).encode()
            )
        finally:
            await agen.aclose()
        await resp.write_eof()
        return resp

    async def events(_):
        # documented external surface, stubbed exactly like the reference
        # (engine RestClientController.java:177-180 returns "Not
        # Implemented" with 200 on any method)
        return web.Response(text="Not Implemented")

    app.router.add_post("/api/v0.1/predictions", predictions)
    app.router.add_post("/predict", predict_alias)
    app.router.add_post("/api/v0.1/feedback", feedback)
    app.router.add_post("/api/v0.1/generate/stream", generate_stream)
    app.router.add_route("*", "/api/v0.1/events", events)
    app.router.add_get("/ping", ping)
    app.router.add_get("/ready", ready)
    app.router.add_get("/pause", pause)
    app.router.add_get("/unpause", unpause)
    app.router.add_get("/prometheus", prometheus)
    app.router.add_get("/stats", stats)
    app.router.add_get("/perf", perf)
    app.router.add_get("/genperf", genperf)
    app.router.add_get("/quality", quality)
    app.router.add_get("/overhead", overhead)
    app.router.add_get("/autopilot", autopilot)
    app.router.add_get("/corpus", corpus)
    app.router.add_get("/costs", costs)
    app.router.add_get("/postmortems", postmortems)
    app.router.add_post("/quality/reference", _quality_reference)
    app.router.add_get("/trace", trace)
    app.router.add_get("/trace/export", trace_export)
    # POST-only: the PR-3 deprecation window for the GET mutation aliases
    # is closed — GET /trace/enable|disable now answers 405
    app.router.add_post("/trace/enable", trace_enable)
    app.router.add_post("/trace/disable", trace_disable)
    app.router.add_get("/profile", profile_get)
    app.router.add_post("/profile/start", profile_start)
    app.router.add_post("/profile/stop", profile_stop)
    return app


# ---------------------------------------------------------------------------
# Unit (microservice) app
# ---------------------------------------------------------------------------


def make_unit_app(runtime: InProcessNodeRuntime) -> web.Application:
    """Serve one unit over the internal microservice API — what
    ``microservice.py <UserClass> REST`` builds in the reference."""
    app = web.Application(client_max_size=256 * 1024 * 1024)

    def handler(method_name):
        async def handle(request: web.Request) -> web.Response:
            import time as _time

            from seldon_core_tpu.utils.telemetry import RECORDER

            t0 = _time.perf_counter()
            try:
                # deadline propagation: the engine's node client forwards the
                # remaining request budget; nested work in this unit (and a
                # unit that is itself an engine facade) draws from it.  The
                # traceparent metadata makes this unit's spans children of
                # the engine's client span — one tree across processes
                with _request_trace_scope(request), \
                        maybe_deadline_scope(_request_budget_s(request)):
                    dl = current_deadline()
                    if dl is not None and dl.expired:
                        return _error_response(
                            "request deadline exhausted on arrival", code=504
                        )
                    return await _dispatch(method_name, request)
            except (SeldonMessageError, GraphSpecError) as e:
                return _error_response(str(e), code=getattr(e, "http_code", 400))
            except NotImplementedError as e:
                return _error_response(str(e), code=501)
            finally:
                RECORDER.request_latency(
                    f"unit:{method_name}", _time.perf_counter() - t0
                )

        return handle

    async def _dispatch(method_name: str, request: web.Request) -> web.Response:
        from seldon_core_tpu.utils.tracing import TRACER, current_trace_puid

        text = await _payload_text(request)
        if method_name == "aggregate":
            msgs = SeldonMessageList.from_json(text)
            puid = current_trace_puid() or (
                msgs.messages[0].meta.puid if msgs.messages else ""
            )
            with TRACER.span(puid, runtime.node.name, kind="server",
                             method=method_name):
                resp = await runtime.aggregate(msgs.messages)
        elif method_name == "send_feedback":
            fb = Feedback.from_json(text)
            routing = (
                fb.response.meta.routing if fb.response is not None else {}
            )
            branch = int(routing.get(runtime.node.name, -1))
            with TRACER.span(fb.puid() or current_trace_puid(),
                             runtime.node.name,
                             kind="server", method=method_name):
                await runtime.send_feedback(fb, branch)
            resp = SeldonMessage()
        elif method_name == "route":
            msg = SeldonMessage.from_json(text)
            with TRACER.span(msg.meta.puid, runtime.node.name, kind="server",
                             method=method_name) as sp:
                branch = await runtime.route(msg)
                if isinstance(sp, dict):
                    sp["branch"] = branch
            # branch wrapped as 1x1 tensor like the reference wrapper
            # (wrappers/python/router_microservice.py:39-56)
            import numpy as np

            resp = msg.with_array(np.array([[branch]], dtype=np.float64))
        else:
            msg = SeldonMessage.from_json(text)
            with TRACER.span(msg.meta.puid, runtime.node.name, kind="server",
                             method=method_name):
                resp = await getattr(runtime, method_name)(msg)
        return _msg_response(resp)

    app.router.add_post("/predict", handler("predict"))
    app.router.add_post("/transform-input", handler("transform_input"))
    app.router.add_post("/transform-output", handler("transform_output"))
    app.router.add_post("/route", handler("route"))
    app.router.add_post("/aggregate", handler("aggregate"))
    app.router.add_post("/send-feedback", handler("send_feedback"))

    async def ping(_): return web.Response(text="pong")

    async def stats(_):
        # unit pods carry the process-level flight recorder too (compile
        # cache, generation telemetry of in-unit generators)
        from seldon_core_tpu.utils.telemetry import RECORDER

        return web.json_response({
            "unit": {"name": runtime.node.name,
                     "type": getattr(runtime.node.type, "name", None)},
            "telemetry": RECORDER.snapshot(),
        })

    async def perf(_):
        # unit pods own a TPU runtime too: whatever this process compiled
        # and dispatched shows up in its process-global observatory
        from seldon_core_tpu.utils.perf import OBSERVATORY

        return web.json_response({
            "unit": {"name": runtime.node.name,
                     "type": getattr(runtime.node.type, "name", None)},
            **OBSERVATORY.document(),
        })

    async def quality(_):
        # per-node drift windows recorded by InProcessNodeRuntime.predict
        # land in the process-global quality observatory
        from seldon_core_tpu.utils.quality import QUALITY

        return web.json_response({
            "unit": {"name": runtime.node.name,
                     "type": getattr(runtime.node.type, "name", None)},
            **QUALITY.document(),
        })

    async def overhead(_):
        # unit pods carry the process-global telemetry spine too
        from seldon_core_tpu.utils.hotrecord import SPINE

        return web.json_response({
            "unit": {"name": runtime.node.name,
                     "type": getattr(runtime.node.type, "name", None)},
            **SPINE.overhead_document(),
        })

    async def autopilot(_):
        # whatever this unit process dispatched trains the process-global
        # cost model; its table is inspectable on unit pods too
        from seldon_core_tpu.runtime.autopilot import AUTOPILOT
        from seldon_core_tpu.utils.hotrecord import SPINE

        SPINE.drain()
        return web.json_response({
            "unit": {"name": runtime.node.name,
                     "type": getattr(runtime.node.type, "name", None)},
            **AUTOPILOT.document(),
        })

    app.router.add_get("/ping", ping)
    app.router.add_get("/stats", stats)
    app.router.add_get("/perf", perf)
    app.router.add_get("/quality", quality)
    app.router.add_get("/overhead", overhead)
    app.router.add_get("/autopilot", autopilot)
    app.router.add_post("/quality/reference", _quality_reference)
    return app


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


async def serve_app(app: web.Application, host: str, port: int):
    """Start an app; returns the runner (caller is responsible for cleanup)."""
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    return runner
