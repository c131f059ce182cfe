"""Fast HTTP/1.1 engine front — asyncio.Protocol, zero per-request framework.

The aiohttp app (runtime/rest.py) stays the full-featured surface; this
module serves the same engine routes straight off an ``asyncio.Protocol``
for the data plane.  Rationale: on a single-core host the HTTP stack is
the serving bottleneck — an echo benchmark on this class of machine puts
aiohttp server+client at ~4k req/s while a raw protocol pair sustains
~40k req/s.  The reference engine leans on Tomcat NIO + Jackson for the
same reason (engine RestClientController.java); this is the TPU-serving
equivalent: terminate HTTP cheaply, spend the cycles on batching and
device dispatch.

Semantics match ``rest.py:make_engine_app`` route for route:

  POST /api/v0.1/predictions   JSON body or form field ``json=``
  POST /predict                internal-API alias (engine as MODEL leaf)
  POST /api/v0.1/feedback
  POST /trace/enable /trace/disable (POST-only: the PR-3 GET-alias
       deprecation window is closed; GET now answers 404)
  POST /quality/reference      freeze/reset the drift reference window
  GET  /ping /ready /pause /unpause /prometheus /stats
  GET  /perf                   performance observatory (utils/perf.py)
  GET  /genperf                generation-lane flight recorder
                               (utils/genperf.py)
  GET  /quality                prediction-quality observatory
                               (utils/quality.py)
  GET  /overhead               telemetry overhead budget
                               (utils/hotrecord.py)
  GET  /autopilot              learned cost-model table
                               (runtime/autopilot.py)
  GET  /corpus                 durable perf corpus
                               (utils/perfcorpus.py)
  GET  /trace /trace/export

``GET /prometheus?format=openmetrics`` serves the OpenMetrics exposition
(trace_id exemplars on ``seldon_tpu_dispatch_seconds`` buckets) — query
negotiation, because fast-lane handlers don't see request headers.

Protocol scope (documented contract, tested in tests/test_httpfast.py):
HTTP/1.1 with keepalive and Content-Length bodies.  Pipelined requests
are answered in order (each request's handler runs concurrently; a
per-connection writer drains responses FIFO).  ``Transfer-Encoding:
chunked`` is declined with 501 — every client in scope (loadtest rig,
aiohttp, curl, the gateway's pooled client) sends Content-Length.
"""

from __future__ import annotations

import asyncio
import time
from typing import Awaitable, Callable, Dict, Optional, Tuple
from urllib.parse import parse_qs

from seldon_core_tpu.graph.spec import GraphSpecError
from seldon_core_tpu.messages import (
    Feedback,
    SeldonMessage,
    SeldonMessageError,
)
from seldon_core_tpu.runtime.resilience import (
    deadline_ms_header,
    deadline_scope,
)
from seldon_core_tpu.utils.metrics import CONTENT_TYPE_LATEST
from seldon_core_tpu.utils.tracing import parse_traceparent, trace_scope

__all__ = ["FastHttpServer", "serve_fast"]

_JSON = "application/json"
_WIRE_CTYPE = "application/x-seldon-tensor"  # runtime/wire.py contract
_MAX_BODY = 256 * 1024 * 1024  # matches rest.py client_max_size
_MAX_HEAD = 64 * 1024

# handler result: (status, body bytes, content-type) — an optional 4th
# element carries extra response header lines (bytes, CRLF-terminated)
Result = Tuple[int, bytes, str]
Handler = Callable[[bytes, str, str], Awaitable[Result]]


class StreamResult:
    """Handler result for streaming routes: the writer sends a chunked
    response, one SSE ``data:`` frame per async-generator item."""

    __slots__ = ("status", "ctype", "agen")

    def __init__(self, status: int, ctype: str, agen):
        self.status = status
        self.ctype = ctype
        self.agen = agen

_STATUS_LINE = {
    code: f"HTTP/1.1 {code} {text}\r\n".encode()
    for code, text in {
        200: "OK", 400: "Bad Request", 404: "Not Found",
        405: "Method Not Allowed", 411: "Length Required",
        413: "Payload Too Large", 415: "Unsupported Media Type",
        500: "Internal Server Error",
        501: "Not Implemented", 503: "Service Unavailable",
        504: "Gateway Timeout",
    }.items()
}


def _json_str(s: str) -> bytes:
    import json as _json

    return _json.dumps(s).encode()


def _payload_text(body: bytes, ctype: str) -> str:
    """JSON body or form-encoded ``json=`` field (rest.py:_payload_text)."""
    if "form" in ctype:
        form = parse_qs(body.decode("utf-8", "replace"), keep_blank_values=True)
        if "json" in form:
            return form["json"][0]
    return body.decode("utf-8", "replace")


class _EngineRoutes:
    """The engine route table shared by every fast connection."""

    def __init__(self, engine):
        self.engine = engine
        self.post: Dict[bytes, Handler] = {
            b"/api/v0.1/predictions": self._predictions,
            # internal-API alias: engines compose as MODEL leaves of larger
            # cross-process graphs (rest.py predict_alias)
            b"/predict": self._predictions,
            b"/api/v0.1/feedback": self._feedback,
            b"/api/v0.1/generate/stream": self._generate_stream,
            b"/api/v0.1/events": self._events,
            b"/trace/enable": self._trace_enable,
            b"/trace/disable": self._trace_disable,
            b"/quality/reference": self._quality_reference,
            b"/profile/start": self._profile_start,
            b"/profile/stop": self._profile_stop,
        }
        self.get: Dict[bytes, Handler] = {
            b"/ping": self._ping,
            b"/ready": self._ready,
            b"/pause": self._pause,
            b"/unpause": self._unpause,
            b"/prometheus": self._prometheus,
            b"/stats": self._stats,
            b"/perf": self._perf,
            b"/genperf": self._genperf,
            b"/quality": self._quality,
            b"/overhead": self._overhead,
            b"/autopilot": self._autopilot,
            b"/corpus": self._corpus,
            b"/costs": self._costs,
            b"/postmortems": self._postmortems,
            b"/trace": self._trace,
            b"/trace/export": self._trace_export,
            # NB: no GET /trace/enable|disable — the PR-3 deprecation
            # window for mutation-via-GET is closed (POST-only now)
            b"/api/v0.1/events": self._events,
            b"/profile": self._profile,
        }

    async def _events(self, body, ctype, query) -> Result:
        # stubbed external surface, reference-exact
        # (engine RestClientController.java:177-180)
        return 200, b"Not Implemented", "text/plain"

    async def _predictions(self, body, ctype, query) -> Result:
        if ctype.startswith(_WIRE_CTYPE):
            return await self._predictions_wire(body)
        try:
            text, status = await self.engine.predict_json(
                _payload_text(body, ctype)
            )
        except SeldonMessageError as e:
            code = e.http_code
            return (
                code,
                SeldonMessage.failure(str(e), code=code).to_json().encode(),
                _JSON,
            )
        return status or 200, text.encode(), _JSON

    async def _predictions_wire(self, body) -> Result:
        """Binary tensor frame in, binary tensor frame out (runtime/
        wire.py) — no JSON round trip.  The request tensor is a
        frombuffer view over ``body`` (the ONE copy this lane pays is the
        receive-buffer materialization, accounted); the response parts
        ride the writer as separate buffers, framed straight from the
        device readback array.  A torn/over-length frame answers a typed
        400/413 through the same FIFO writer every response rides — the
        connection keeps serving (or closes AFTER the queued responses
        drain, never before)."""
        from seldon_core_tpu.runtime import wire
        from seldon_core_tpu.utils.telemetry import RECORDER

        if not wire.wire_enabled():
            return (
                415,
                SeldonMessage.failure(
                    "binary wire lane disabled (SELDON_TPU_WIRE=0)",
                    code=415,
                ).to_json().encode(),
                _JSON,
            )
        RECORDER.record_wire_request("fast", "binary")
        wire.account_copy(len(body))
        try:
            status, parts = await self.engine.predict_wire(body)
        except wire.WireError as e:
            # unparseable bytes: the peer may not even decode frames —
            # the typed failure goes back as JSON it can always read
            return (
                e.http_code,
                SeldonMessage.failure(
                    str(e), code=e.http_code
                ).to_json().encode(),
                _JSON,
            )
        return status, parts, _WIRE_CTYPE

    async def _generate_stream(self, body, ctype, query):
        """SSE token streaming (beyond-reference: the reference predates
        sequence models).  Payload = a SeldonMessage with the prompt plus
        an optional top-level ``chunk`` (tokens per event)."""
        t_recv = time.perf_counter()  # /genperf requests.stage_s.lane_in
        try:  # every problem surfaces as a plain 400 BEFORE streaming
            text, chunk = self.engine.prepare_stream_request(
                _payload_text(body, ctype)
            )
        except SeldonMessageError as e:
            return 400, SeldonMessage.failure(str(e)).to_json().encode(), _JSON
        return StreamResult(
            200, "text/event-stream",
            self.engine.generate_stream(text, chunk=chunk, t_recv=t_recv),
        )

    async def _feedback(self, body, ctype, query) -> Result:
        try:
            fb = Feedback.from_json(_payload_text(body, ctype))
        except SeldonMessageError as e:
            return 400, SeldonMessage.failure(str(e)).to_json().encode(), _JSON
        ack = await self.engine.send_feedback(fb)
        ok = ack.status is None or ack.status.status == "SUCCESS"
        status = 200 if ok else (ack.status.code or 200)
        return status or 200, ack.to_json().encode(), _JSON

    async def _ping(self, body, ctype, query) -> Result:
        return 200, b"pong", "text/plain"

    async def _ready(self, body, ctype, query) -> Result:
        if self.engine.ready():
            open_breakers = self.engine.open_breakers()
            if open_breakers:
                return (
                    200,
                    b"ready (breakers open: "
                    + ",".join(open_breakers).encode() + b")",
                    "text/plain",
                )
            return 200, b"ready", "text/plain"
        return 503, b"paused", "text/plain"

    async def _pause(self, body, ctype, query) -> Result:
        self.engine.pause()
        return 200, b"paused", "text/plain"

    async def _unpause(self, body, ctype, query) -> Result:
        self.engine.unpause()
        return 200, b"unpaused", "text/plain"

    async def _prometheus(self, body, ctype, query) -> Result:
        # ?format=openmetrics serves the exemplar-carrying OpenMetrics
        # exposition (fast-lane handlers don't see Accept headers)
        if parse_qs(query).get("format", [""])[0] == "openmetrics":
            from seldon_core_tpu.utils.metrics import OPENMETRICS_CONTENT_TYPE

            return (
                200,
                self.engine.metrics.exposition(openmetrics=True),
                OPENMETRICS_CONTENT_TYPE,
            )
        return 200, self.engine.metrics.exposition(), CONTENT_TYPE_LATEST

    async def _stats(self, body, ctype, query) -> Result:
        import json as _json

        return 200, _json.dumps(self.engine.stats()).encode(), _JSON

    async def _perf(self, body, ctype, query) -> Result:
        import json as _json

        return 200, _json.dumps(self.engine.perf_document()).encode(), _JSON

    async def _genperf(self, body, ctype, query) -> Result:
        import json as _json

        return (
            200,
            _json.dumps(self.engine.genperf_document()).encode(),
            _JSON,
        )

    async def _quality(self, body, ctype, query) -> Result:
        import json as _json

        return 200, _json.dumps(self.engine.quality_document()).encode(), _JSON

    async def _overhead(self, body, ctype, query) -> Result:
        import json as _json

        return (
            200,
            _json.dumps(self.engine.overhead_document()).encode(),
            _JSON,
        )

    async def _autopilot(self, body, ctype, query) -> Result:
        import json as _json

        return (
            200,
            _json.dumps(self.engine.autopilot_document()).encode(),
            _JSON,
        )

    async def _corpus(self, body, ctype, query) -> Result:
        import json as _json

        return (
            200,
            _json.dumps(self.engine.corpus_document()).encode(),
            _JSON,
        )

    async def _costs(self, body, ctype, query) -> Result:
        import json as _json

        return (
            200,
            _json.dumps(self.engine.costs_document()).encode(),
            _JSON,
        )

    async def _postmortems(self, body, ctype, query) -> Result:
        import json as _json

        q = parse_qs(query)
        doc = self.engine.postmortems_document(
            puid=q.get("puid", [""])[0])
        return 200, _json.dumps(doc).encode(), _JSON

    async def _quality_reference(self, body, ctype, query) -> Result:
        import json as _json

        from seldon_core_tpu.utils.quality import (
            QUALITY,
            parse_reference_action,
        )

        q = parse_qs(query)
        try:
            action, node = parse_reference_action(
                body, q.get("action", [None])[0], q.get("node", [None])[0]
            )
        except ValueError as e:
            return 400, SeldonMessage.failure(str(e)).to_json().encode(), _JSON
        return (
            200,
            _json.dumps(QUALITY.reference_control(action, node=node)).encode(),
            _JSON,
        )

    async def _trace(self, body, ctype, query) -> Result:
        import json as _json

        from seldon_core_tpu.utils.tracing import TRACER, trace_document

        q = parse_qs(query)
        doc = trace_document(
            TRACER,
            puid=q.get("puid", [""])[0],
            trace_id=q.get("trace_id", [""])[0],
            limit=int(q.get("limit", ["100"])[0]),
        )
        return 200, _json.dumps(doc).encode(), _JSON

    async def _trace_export(self, body, ctype, query) -> Result:
        import json as _json

        from seldon_core_tpu.utils.tracing import TRACER, export_document

        q = parse_qs(query)
        doc = export_document(
            TRACER,
            puid=q.get("puid", [""])[0],
            trace_id=q.get("trace_id", [""])[0],
            limit=int(q.get("limit", ["1000"])[0]),
            process_name=self.engine.process_track_name(),
        )
        return 200, _json.dumps(doc).encode(), _JSON

    async def _profile_start(self, body, ctype, query) -> Result:
        # the per-engine half of a coordinated fleet profile window
        # (gateway/fleet.py): bounded jax.profiler window, 409 on overlap
        import json as _json

        from seldon_core_tpu.utils.tracing import (
            ProfileBusyError,
            profile_window_start_request,
        )

        try:
            payload = _json.loads(body.decode("utf-8", "replace") or "{}")
        except ValueError:
            payload = {}
        if not isinstance(payload, dict):
            payload = {}
        try:
            doc = profile_window_start_request(payload)
        except ProfileBusyError as e:
            return 409, _json.dumps({"error": str(e)}).encode(), _JSON
        return 200, _json.dumps(doc).encode(), _JSON

    async def _profile_stop(self, body, ctype, query) -> Result:
        import json as _json

        from seldon_core_tpu.utils.tracing import profile_window_stop

        # ON the loop, although it blocks every stream for the seconds the
        # trace takes to write: from an executor thread, beside a live
        # loop, the same stop took three times as long (30-34 s against
        # 9-13 s, chip runs, PERF.md section 6, PR 24)
        return 200, _json.dumps(profile_window_stop()).encode(), _JSON

    async def _profile(self, body, ctype, query) -> Result:
        import json as _json

        from seldon_core_tpu.utils.tracing import profile_window_status

        return 200, _json.dumps(profile_window_status()).encode(), _JSON

    async def _trace_enable(self, body, ctype, query) -> Result:
        from seldon_core_tpu.utils.tracing import TRACER

        TRACER.enable()
        return 200, b"tracing enabled", "text/plain"

    async def _trace_disable(self, body, ctype, query) -> Result:
        from seldon_core_tpu.utils.tracing import TRACER

        TRACER.disable()
        return 200, b"tracing disabled", "text/plain"


_MAX_INFLIGHT = 128  # per-connection pipelined requests before pause_reading


async def _with_deadline(coro, budget_s: float):
    """Run a route handler under a request deadline budget (the scope must
    be entered INSIDE the handler task so child awaits inherit it)."""
    with deadline_scope(budget_s):
        return await coro


async def _with_trace(coro, ctx):
    """Run a route handler under an adopted remote trace context (same
    inside-the-task requirement as ``_with_deadline``)."""
    with trace_scope(ctx):
        return await coro


async def _with_qos(coro, tenant, tier):
    """Run a route handler under the caller's tenant/tier identity
    (Seldon-Tenant / Seldon-Tier — runtime/qos.py), same
    inside-the-task requirement as the deadline/trace wrappers."""
    from seldon_core_tpu.runtime.qos import qos_scope

    with qos_scope(tenant, tier):
        return await coro


def _header_value(lower: bytes, name: bytes) -> Optional[bytes]:
    """Value of ``name`` (lower-case, colon included) anchored at a line
    start — an unanchored substring search would match inside other header
    names (X-Content-Length) or values."""
    j = lower.find(b"\r\n" + name)
    if j < 0:
        return None
    start = j + 2 + len(name)
    stop = lower.find(b"\r", start)
    return lower[start: stop if stop > 0 else None].strip()


class _FastHttpProtocol(asyncio.Protocol):
    def __init__(self, routes: _EngineRoutes, protocols: Optional[set] = None):
        self.routes = routes
        self.protocols = protocols
        self.buf = bytearray()
        self.body_need = -1  # >= 0: header parsed, waiting for body bytes
        self.scan_from = 0   # resume point for the \r\n\r\n scan
        self.transport: Optional[asyncio.Transport] = None
        self.queue: "asyncio.Queue" = asyncio.Queue()
        self.writer_task: Optional[asyncio.Task] = None
        self.closing = False
        self.paused_read = False
        self._can_write = asyncio.Event()
        self._can_write.set()

    # -- connection lifecycle ------------------------------------------------

    def connection_made(self, transport):
        self.transport = transport
        transport.set_write_buffer_limits(high=1 << 20)
        if self.protocols is not None:
            self.protocols.add(self)
        self.writer_task = asyncio.get_running_loop().create_task(
            self._writer()
        )

    def connection_lost(self, exc):
        self.closing = True
        if self.protocols is not None:
            self.protocols.discard(self)
        if self.writer_task is not None:
            self.writer_task.cancel()

    def pause_writing(self):
        self._can_write.clear()

    def resume_writing(self):
        self._can_write.set()

    def _maybe_pause_reading(self):
        """Backpressure: a connection may pipeline at most _MAX_INFLIGHT
        requests; beyond that the socket stops being read until the writer
        drains the queue."""
        if (
            not self.paused_read
            and self.queue.qsize() > _MAX_INFLIGHT
            and self.transport is not None
        ):
            self.paused_read = True
            self.transport.pause_reading()

    async def _writer(self):
        """Drain handler results in request order (pipelining-safe)."""
        while True:
            task, close = await self.queue.get()
            try:
                result = await task
            except (SeldonMessageError, GraphSpecError) as e:
                result = (
                    400, SeldonMessage.failure(str(e)).to_json().encode(), _JSON
                )
            except asyncio.CancelledError:
                raise
            except Exception as e:  # unexpected: 500, keep serving
                result = (
                    500,
                    SeldonMessage.failure(str(e), code=500).to_json().encode(),
                    _JSON,
                )
            if isinstance(result, StreamResult):
                await self._write_stream(result)
                if close and self.transport is not None:
                    self.transport.close()
                continue
            extra = b""
            if len(result) == 4:
                status, body, ctype, extra = result
            else:
                status, body, ctype = result
            if not self._can_write.is_set():
                await self._can_write.wait()  # transport buffer full
            self._write_response(status, body, ctype, close, extra)
            if (
                self.paused_read
                and self.queue.qsize() <= _MAX_INFLIGHT // 2
                and self.transport is not None
            ):
                self.paused_read = False
                self.transport.resume_reading()
            if close and self.transport is not None:
                self.transport.close()

    async def _write_stream(self, result: "StreamResult"):
        """Chunked transfer encoding, one SSE data: frame per event.  A
        mid-stream failure can't change the already-sent status — the
        stream ends with an SSE error event and the connection closes."""
        if self.transport is None or self.transport.is_closing():
            return
        self.transport.write(
            b"HTTP/1.1 %d OK\r\nContent-Type: %s\r\n"
            b"Cache-Control: no-cache\r\n"
            b"Transfer-Encoding: chunked\r\n\r\n"
            % (result.status, result.ctype.encode())
        )
        try:
            async for event in result.agen:
                if self.transport is None or self.transport.is_closing():
                    return  # client went away; finally closes the generator
                frame = b"data: " + event.encode() + b"\n\n"
                self.transport.write(
                    b"%x\r\n" % len(frame) + frame + b"\r\n"
                )
                if not self._can_write.is_set():
                    await self._can_write.wait()
        except asyncio.CancelledError:
            raise
        except Exception as e:
            if self.transport is not None and not self.transport.is_closing():
                err = (b'data: {"done": true, "error": %s}\n\n'
                       % _json_str(str(e)))
                self.transport.write(b"%x\r\n" % len(err) + err + b"\r\n")
                self.transport.write(b"0\r\n\r\n")
                self.transport.close()  # stream integrity unknown
            return
        finally:
            # a disconnect mid-stream must not leave the generator (and
            # its KV caches / open metric+trace spans) suspended until GC
            await result.agen.aclose()
        if self.transport is not None and not self.transport.is_closing():
            self.transport.write(b"0\r\n\r\n")

    def _write_response(self, status, body, ctype, close, extra=b""):
        if self.transport is None or self.transport.is_closing():
            return
        # body may be a LIST of buffer parts (the binary wire lane's
        # header + device-readback payload view): written sequentially,
        # no concatenation copy — the transport coalesces into writev
        parts = body if isinstance(body, (list, tuple)) else None
        blen = sum(len(p) for p in parts) if parts is not None else len(body)
        head = (
            _STATUS_LINE.get(status) or f"HTTP/1.1 {status} X\r\n".encode()
        ) + (
            b"Content-Length: %d\r\nContent-Type: %s\r\n%s%s\r\n"
            % (
                blen,
                ctype.encode(),
                extra,
                b"Connection: close\r\n" if close else b"",
            )
        )
        if parts is not None:
            self.transport.write(head)
            for p in parts:
                self.transport.write(p)
            return
        self.transport.write(head + body)

    # -- parsing -------------------------------------------------------------

    def data_received(self, data):
        # bytearray append + one prefix trim per chunk: O(chunk + leftover),
        # never O(total^2) on large bodies arriving in many TCP segments
        self.buf += data
        consumed = 0
        while not self.closing:
            if self.body_need >= 0:
                # mid-body: wait for the rest without rescanning headers
                if len(self.buf) - consumed < self._head_len + self.body_need:
                    break
                start = consumed + self._head_len
                # one copy out of the receive buffer (a bytearray slice
                # would copy twice: slice then bytes); the view is a
                # temporary, gone before the prefix trim below
                body = bytes(memoryview(self.buf)[start: start + self.body_need])
                consumed = start + self.body_need
                self.body_need = -1
                self._dispatch(self._head, self._lower, body)
                continue
            end = self.buf.find(b"\r\n\r\n", max(consumed, self.scan_from))
            if end < 0:
                if len(self.buf) - consumed > _MAX_HEAD:
                    self._reject(413, b"headers too large", close=True)
                # resume the scan where it left off (minus the 3 bytes a
                # split terminator could span)
                self.scan_from = max(consumed, len(self.buf) - 3)
                break
            head = bytes(self.buf[consumed:end])
            lower = head.lower()
            # RFC 7230: Transfer-Encoding wins over Content-Length; a request
            # carrying both must not be framed by Content-Length (smuggling)
            if _header_value(lower, b"transfer-encoding:") is not None:
                self._reject(501, b"chunked bodies not supported", close=True)
                break
            clen = 0
            clv = _header_value(lower, b"content-length:")
            if clv is not None:
                # digits only: int() would accept "-5" (consumed moves
                # backwards -> phantom pipelined request) and "1_0"
                if not clv.isdigit():
                    self._reject(400, b"bad content-length", close=True)
                    break
                clen = int(clv)
            if clen > _MAX_BODY:
                self._reject(413, b"body too large", close=True)
                break
            if len(self.buf) - consumed < end - consumed + 4 + clen:
                # body incomplete: remember the parse so the next chunk
                # resumes in state BODY
                self._head, self._lower = head, lower
                self._head_len = end - consumed + 4
                self.body_need = clen
                break
            start = end + 4
            body = bytes(memoryview(self.buf)[start: start + clen])
            consumed = start + clen
            self._dispatch(head, lower, body)
        if consumed:
            del self.buf[:consumed]
            self.scan_from = 0
        self._maybe_pause_reading()

    def _reject(self, status, text, close=False):
        self.closing = self.closing or close
        fut = asyncio.get_running_loop().create_future()
        fut.set_result((status, text, "text/plain"))
        self.queue.put_nowait((fut, close))

    def _dispatch(self, head: bytes, lower: bytes, body: bytes):
        line_end = head.find(b"\r\n")
        request_line = head[: line_end if line_end > 0 else len(head)]
        try:
            method, target, _ = request_line.split(b" ", 2)
        except ValueError:
            self._reject(400, b"malformed request line", close=True)
            return
        qpos = target.find(b"?")
        path, query = (
            (target[:qpos], target[qpos + 1:]) if qpos >= 0 else (target, b"")
        )
        conn = _header_value(lower, b"connection:")
        close = conn is not None and b"close" in (
            p.strip() for p in conn.split(b",")
        )
        table = (
            self.routes.post if method == b"POST"
            else self.routes.get if method == b"GET"
            else None
        )
        if table is None:
            if path == b"/api/v0.1/events":
                # reference-exact: the stub answers 200 on ANY method
                # (engine RestClientController.java:177-180)
                handler = self.routes.get[b"/api/v0.1/events"]
                task = asyncio.get_running_loop().create_task(
                    handler(body, "", query.decode("latin-1"))
                )
                self.queue.put_nowait((task, close))
                return
            self._reject(405, b"method not allowed")
            return
        handler = table.get(path)
        if handler is None:
            self._reject(404, b"not found")
            return
        ctv = _header_value(lower, b"content-type:")
        ctype = ctv.decode() if ctv is not None else ""
        coro = handler(body, ctype, query.decode("latin-1"))
        # deadline propagation (resilience layer): same header contract as
        # the aiohttp lane — the budget is set in the handler task's context
        dlv = _header_value(lower, b"seldon-deadline-ms:")
        budget_s = (
            deadline_ms_header(dlv.decode("latin-1")) if dlv is not None else None
        )
        if budget_s is not None:
            coro = _with_deadline(coro, budget_s)
        # W3C trace context: same contract as the aiohttp lane
        tpv = _header_value(lower, b"traceparent:")
        trace_ctx = (
            parse_traceparent(tpv.decode("latin-1")) if tpv is not None else None
        )
        if trace_ctx is not None:
            coro = _with_trace(coro, trace_ctx)
        # tenant/tier identity: forwarded by the gateway's remote lane
        tenv = _header_value(lower, b"seldon-tenant:")
        tiv = _header_value(lower, b"seldon-tier:")
        if tenv is not None or tiv is not None:
            coro = _with_qos(
                coro,
                tenv.decode("latin-1").strip() if tenv is not None else None,
                tiv.decode("latin-1").strip() if tiv is not None else None,
            )
        task = asyncio.get_running_loop().create_task(coro)
        self.queue.put_nowait((task, close))


class FastHttpServer:
    """Owns the listening socket; ``await start()`` / ``await stop()``.
    ``start_uds`` additionally serves the SAME route table over a unix
    domain socket — the HTTP face of the co-located lane (the gateway's
    framed relay is runtime/udsrelay.py; this one serves node-mesh peers
    dialing ``unix:`` bindings through runtime/client.py)."""

    def __init__(self, engine):
        self.routes = _EngineRoutes(engine)
        self._server: Optional[asyncio.AbstractServer] = None
        self._uds_server: Optional[asyncio.AbstractServer] = None
        self._uds_path: Optional[str] = None
        self._protocols: set = set()

    async def start(self, host: str, port: int) -> None:
        loop = asyncio.get_running_loop()
        self._server = await loop.create_server(
            lambda: _FastHttpProtocol(self.routes, self._protocols),
            host, port, backlog=4096,
        )
        self.port = self._server.sockets[0].getsockname()[1]

    async def start_uds(self, path: str) -> None:
        import os

        try:
            os.unlink(path)  # stale socket from a crashed predecessor
        except FileNotFoundError:
            pass
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        loop = asyncio.get_running_loop()
        self._uds_server = await loop.create_unix_server(
            lambda: _FastHttpProtocol(self.routes, self._protocols),
            path=path,
        )
        self._uds_path = path

    async def stop(self) -> None:
        servers = [s for s in (self._server, self._uds_server) if s is not None]
        if not servers:
            return
        for s in servers:
            s.close()
        # Server.wait_closed (3.12.1+) waits for every connection handler;
        # idle keepalive connections never finish on their own, so close
        # their transports first or shutdown hangs forever
        for proto in list(self._protocols):
            if proto.transport is not None:
                proto.transport.close()
        for s in servers:
            try:
                await asyncio.wait_for(s.wait_closed(), timeout=5.0)
            except asyncio.TimeoutError:
                pass  # listener is closed either way; don't wedge shutdown
        self._server = None
        self._uds_server = None
        if self._uds_path is not None:
            import os

            try:
                os.unlink(self._uds_path)
            except FileNotFoundError:
                pass
            self._uds_path = None


async def serve_fast(engine, host: str, port: int,
                     uds_path: Optional[str] = None) -> FastHttpServer:
    server = FastHttpServer(engine)
    await server.start(host, port)
    if uds_path:
        await server.start_uds(uds_path)
    return server
