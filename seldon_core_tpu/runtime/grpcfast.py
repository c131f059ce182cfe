"""Wire-level gRPC data plane — HTTP/2 + HPACK terminated in-framework.

The stock Python gRPC runtime (grpc.aio) costs ~370us of CPU per unary RPC
across client+server on this class of host — an echo benchmark tops out
near 2.6k calls/s/core before any model work.  The reference's engine
serves 28k gRPC predictions/s (its docs/benchmarking.md:58) on a 16-core JVM;
matching that per-core on a single shared core needs the per-RPC path to
be tens of microseconds, so — exactly as with HTTP/1.1 (runtime/
httpfast.py) — the framework terminates the protocol itself:

  * server: ``FastGrpcServer`` speaks HTTP/2 (RFC 7540) + HPACK (RFC 7541,
    native/hpackcodec.py) on an asyncio.Protocol and dispatches unary gRPC
    calls by :path.  Predict rides the engine's wire-bytes hot path
    (``predict_proto_wire`` — no protobuf object materialises).
  * client: ``FastGrpcChannel`` is the load-rig/client counterpart
    (multiplexed streams over one connection, pipelined).

Interop is pinned both ways in tests/test_grpcfast.py: a stock grpc.aio
client against ``FastGrpcServer``, and ``FastGrpcChannel`` against a stock
grpc.aio server.  Scope (documented contract): unary calls, identity
encoding, trailers-only error responses; streaming RPCs and TLS stay on
the stock grpc.aio server (runtime/grpc_server.py), which remains the
full-surface lane.

Reference parity: engine grpc/SeldonGrpcServer.java:34-62 (service
surface), its docs/benchmarking.md:48-64 (the gRPC numbers this lane chases).
"""

from __future__ import annotations

import asyncio
import struct
from typing import Awaitable, Callable, Dict, List, Optional, Tuple

from seldon_core_tpu.native.hpackcodec import (
    HpackDecoder,
    HpackError,
    encode_headers,
)

__all__ = ["FastGrpcServer", "FastGrpcChannel", "serve_grpc_fast"]

_PREFACE = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"

# frame types
_DATA = 0x0
_HEADERS = 0x1
_PRIORITY = 0x2
_RST_STREAM = 0x3
_SETTINGS = 0x4
_PUSH_PROMISE = 0x5
_PING = 0x6
_GOAWAY = 0x7
_WINDOW_UPDATE = 0x8
_CONTINUATION = 0x9

# flags
_F_END_STREAM = 0x1
_F_ACK = 0x1
_F_END_HEADERS = 0x4
_F_PADDED = 0x8
_F_PRIORITY = 0x20

_DEFAULT_WINDOW = 65535
_BIG_WINDOW = (1 << 31) - 1
_WINDOW_REPLENISH = 1 << 20  # send a connection WINDOW_UPDATE per MiB read
_MAX_MESSAGE = 256 * 1024 * 1024  # matches grpc_server.GRPC_MAX_MESSAGE

_SETTINGS_HEADER_TABLE_SIZE = 0x1
_SETTINGS_MAX_CONCURRENT_STREAMS = 0x3
_SETTINGS_INITIAL_WINDOW_SIZE = 0x4
_SETTINGS_MAX_FRAME_SIZE = 0x5

# gRPC status codes used here
GRPC_OK = 0
GRPC_INTERNAL = 13
GRPC_UNIMPLEMENTED = 12
GRPC_RESOURCE_EXHAUSTED = 8

Handler = Callable[[bytes], Awaitable[bytes]]


def _frame(ftype: int, flags: int, stream_id: int, payload: bytes) -> bytes:
    return struct.pack(
        ">I", len(payload)
    )[1:] + bytes((ftype, flags)) + struct.pack(">I", stream_id) + payload


def _settings_payload(pairs: List[Tuple[int, int]]) -> bytes:
    return b"".join(struct.pack(">HI", k, v) for k, v in pairs)


def _grpc_frame(message: bytes) -> bytes:
    """5-byte gRPC length-prefixed framing (uncompressed)."""
    return b"\x00" + struct.pack(">I", len(message)) + message


class _H2Endpoint(asyncio.Protocol):
    """Shared HTTP/2 connection machinery (frame parse, HPACK state, flow
    control).  Subclasses handle HEADERS/DATA events."""

    is_server = True

    def __init__(self):
        self.buf = bytearray()
        self.transport: Optional[asyncio.Transport] = None
        self.decoder = HpackDecoder()
        self.preface_seen = not self.is_server
        self.recv_since_update = 0
        self.conn_send_window = _DEFAULT_WINDOW
        self.peer_initial_window = _DEFAULT_WINDOW
        self.peer_max_frame = 16384
        self.stream_send_windows: Dict[int, int] = {}
        # in-flight outbound stream payloads (flow-control partial sends):
        # sid -> {buf, off, trailer, end}
        self._tx: Dict[int, dict] = {}
        self._header_accum: Optional[Tuple[int, int, bytearray]] = None
        self.closed = asyncio.get_event_loop().create_future()

    # -- lifecycle -----------------------------------------------------------

    def connection_made(self, transport):
        self.transport = transport
        transport.set_write_buffer_limits(high=1 << 22)
        hello = b"" if self.is_server else _PREFACE
        hello += _frame(
            _SETTINGS, 0, 0,
            _settings_payload([
                (_SETTINGS_INITIAL_WINDOW_SIZE, _BIG_WINDOW),
                (_SETTINGS_MAX_CONCURRENT_STREAMS, 1 << 20),
            ]),
        )
        # open the connection-level receive window wide: unlike stream
        # windows it starts at 65535 regardless of SETTINGS
        hello += _frame(
            _WINDOW_UPDATE, 0, 0,
            struct.pack(">I", _BIG_WINDOW - _DEFAULT_WINDOW),
        )
        transport.write(hello)

    def connection_lost(self, exc):
        if not self.closed.done():
            self.closed.set_result(None)
        self._on_close(exc)

    def _on_close(self, exc):
        pass

    def _fatal(self, msg: str):
        if self.transport is not None and not self.transport.is_closing():
            self.transport.write(
                _frame(_GOAWAY, 0, 0, struct.pack(">II", 0, 2) + msg.encode())
            )
            self.transport.close()

    # -- frame parsing -------------------------------------------------------

    def data_received(self, data):
        self.buf += data
        consumed = 0
        if not self.preface_seen:
            if len(self.buf) < len(_PREFACE):
                return
            if bytes(self.buf[: len(_PREFACE)]) != _PREFACE:
                self._fatal("bad connection preface")
                return
            consumed = len(_PREFACE)
            self.preface_seen = True
        try:
            while len(self.buf) - consumed >= 9:
                ln = int.from_bytes(self.buf[consumed: consumed + 3], "big")
                if len(self.buf) - consumed < 9 + ln:
                    break
                ftype = self.buf[consumed + 3]
                flags = self.buf[consumed + 4]
                sid = (
                    int.from_bytes(
                        self.buf[consumed + 5: consumed + 9], "big"
                    ) & 0x7FFFFFFF
                )
                payload = bytes(self.buf[consumed + 9: consumed + 9 + ln])
                consumed += 9 + ln
                self._on_frame(ftype, flags, sid, payload)
        except HpackError as e:
            self._fatal(f"hpack: {e}")
        finally:
            if consumed:
                del self.buf[:consumed]

    def _on_frame(self, ftype, flags, sid, payload):
        if ftype == _SETTINGS:
            if not flags & _F_ACK:
                for off in range(0, len(payload) - 5, 6):
                    k, v = struct.unpack_from(">HI", payload, off)
                    if k == _SETTINGS_INITIAL_WINDOW_SIZE:
                        delta = v - self.peer_initial_window
                        self.peer_initial_window = v
                        for s in self.stream_send_windows:
                            self.stream_send_windows[s] += delta
                    elif k == _SETTINGS_MAX_FRAME_SIZE:
                        self.peer_max_frame = v
                    # HEADER_TABLE_SIZE announces the PEER's decode-table
                    # limit (RFC 7540 §6.5.2) — it constrains encoders, and
                    # ours never references dynamic entries, so ignore it;
                    # our decoder's table is sized by OUR advertised default
                self.transport.write(_frame(_SETTINGS, _F_ACK, 0, b""))
                # RFC 7540 §6.9.2: a SETTINGS raising INITIAL_WINDOW_SIZE
                # can make stalled streams sendable — resume them
                self._drain_pending()
        elif ftype == _WINDOW_UPDATE:
            (inc,) = struct.unpack(">I", payload)
            inc &= 0x7FFFFFFF
            if sid == 0:
                self.conn_send_window += inc
            elif sid in self.stream_send_windows or sid in self._tx:
                # only track windows for live streams (a per-finished-stream
                # entry would leak one dict slot per call)
                self.stream_send_windows[sid] = (
                    self.stream_send_windows.get(
                        sid, self.peer_initial_window
                    ) + inc
                )
            self._drain_pending()
        elif ftype == _PING:
            if not flags & _F_ACK:
                self.transport.write(_frame(_PING, _F_ACK, 0, payload))
        elif ftype == _HEADERS:
            block = payload
            pad = 0
            if flags & _F_PADDED:
                pad = block[0]
                block = block[1:]
            if flags & _F_PRIORITY:
                block = block[5:]
            if pad:
                block = block[:-pad]
            if flags & _F_END_HEADERS:
                self._on_headers(
                    sid, self.decoder.decode(block),
                    bool(flags & _F_END_STREAM),
                )
            else:
                self._header_accum = (
                    sid, flags & _F_END_STREAM, bytearray(block)
                )
        elif ftype == _CONTINUATION:
            if self._header_accum is None or self._header_accum[0] != sid:
                self._fatal("unexpected CONTINUATION")
                return
            self._header_accum[2].extend(payload)
            if flags & _F_END_HEADERS:
                sid0, es, blk = self._header_accum
                self._header_accum = None
                self._on_headers(
                    sid0, self.decoder.decode(bytes(blk)), bool(es)
                )
        elif ftype == _DATA:
            body = payload
            if flags & _F_PADDED:
                pad = body[0]
                body = body[1: len(body) - pad]
            self._on_data(sid, body, bool(flags & _F_END_STREAM))
            self.recv_since_update += len(payload)
            if self.recv_since_update >= _WINDOW_REPLENISH:
                self.transport.write(
                    _frame(
                        _WINDOW_UPDATE, 0, 0,
                        struct.pack(">I", self.recv_since_update),
                    )
                )
                self.recv_since_update = 0
        elif ftype == _RST_STREAM:
            self._on_rst(sid)
        elif ftype == _GOAWAY:
            self.transport.close()
        # PRIORITY / PUSH_PROMISE / unknown: ignored

    # -- flow-controlled sending --------------------------------------------

    def _send_stream(self, sid: int, framed: bytes, trailer: bytes = b"",
                     end_on_data: bool = False):
        """Queue a stream's outbound payload and send as much as the flow
        windows allow; the rest resumes on WINDOW_UPDATE.  ``trailer`` is a
        pre-built frame (server trailers HEADERS) written after the last
        DATA byte; ``end_on_data`` puts END_STREAM on the final DATA frame
        (client requests)."""
        self._tx[sid] = {
            "buf": framed, "off": 0, "trailer": trailer, "end": end_on_data,
        }
        self._pump(sid)

    def _pump(self, sid: int):
        tx = self._tx.get(sid)
        if tx is None or self.transport is None or self.transport.is_closing():
            return
        buf = tx["buf"]
        out = bytearray()
        while tx["off"] < len(buf):
            window = min(
                self.conn_send_window,
                self.stream_send_windows.get(sid, self.peer_initial_window),
            )
            n = min(len(buf) - tx["off"], window, self.peer_max_frame)
            if n <= 0:
                if out:
                    self.transport.write(bytes(out))
                return  # stalled on flow control; WINDOW_UPDATE resumes
            chunk = buf[tx["off"]: tx["off"] + n]
            tx["off"] += n
            last = tx["off"] >= len(buf)
            flags = _F_END_STREAM if (last and tx["end"]) else 0
            out += _frame(_DATA, flags, sid, chunk)
            self.conn_send_window -= n
            self.stream_send_windows[sid] = (
                self.stream_send_windows.get(sid, self.peer_initial_window)
                - n
            )
        if tx["end"] and not buf:  # empty payload still needs END_STREAM
            out += _frame(_DATA, _F_END_STREAM, sid, b"")
        out += tx["trailer"]
        if out:
            self.transport.write(bytes(out))
        del self._tx[sid]
        self.stream_send_windows.pop(sid, None)  # stream done: no leak

    def _drain_pending(self):
        for sid in list(self._tx):
            self._pump(sid)

    def _abort_stream_tx(self, sid: int):
        self._tx.pop(sid, None)
        self.stream_send_windows.pop(sid, None)

    # -- subclass events -----------------------------------------------------

    def _on_headers(self, sid, headers, end_stream):
        raise NotImplementedError

    def _on_data(self, sid, body, end_stream):
        raise NotImplementedError

    def _on_rst(self, sid):
        pass


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------


class _ServerConnection(_H2Endpoint):
    is_server = True

    def __init__(self, handlers: Dict[bytes, Handler], protocols: set):
        super().__init__()
        self.handlers = handlers
        self.protocols = protocols
        self.streams: Dict[int, Tuple[bytes, bytearray]] = {}  # sid -> (path, body)
        self._tasks: set = set()  # strong refs: create_task alone can be GC'd
        # response HEADERS + OK trailers are constant: build once per conn
        self._resp_headers = encode_headers(
            [(b":status", b"200"), (b"content-type", b"application/grpc")]
        )
        self._ok_trailers = encode_headers(
            [(b"grpc-status", b"0"), (b"grpc-message", b"")]
        )

    def connection_made(self, transport):
        super().connection_made(transport)
        self.protocols.add(self)

    def _on_close(self, exc):
        self.protocols.discard(self)

    def _on_headers(self, sid, headers, end_stream):
        path = b""
        for name, value in headers:
            if name == b":path":
                path = value
                break
        self.streams[sid] = (path, bytearray())
        if end_stream:  # unary call with no body: invalid -> trailers-only
            self._trailers_only(sid, GRPC_INTERNAL, b"missing request body")
            self.streams.pop(sid, None)

    def _on_data(self, sid, body, end_stream):
        entry = self.streams.get(sid)
        if entry is None:
            return
        entry[1].extend(body)
        if len(entry[1]) > _MAX_MESSAGE + 5:
            self._trailers_only(
                sid, GRPC_RESOURCE_EXHAUSTED, b"message too large"
            )
            self.streams.pop(sid, None)
            return
        if end_stream:
            path, buf = self.streams.pop(sid)
            handler = self.handlers.get(path)
            if handler is None:
                self._trailers_only(
                    sid, GRPC_UNIMPLEMENTED,
                    b"unknown method " + path,
                )
                return
            if len(buf) < 5 or buf[0] != 0:
                self._trailers_only(
                    sid, GRPC_INTERNAL, b"compressed or malformed grpc frame"
                )
                return
            (mlen,) = struct.unpack_from(">I", buf, 1)
            if mlen != len(buf) - 5:
                self._trailers_only(
                    sid, GRPC_INTERNAL, b"grpc frame length mismatch"
                )
                return
            task = asyncio.get_running_loop().create_task(
                self._run(sid, handler, bytes(buf[5:]))
            )
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)

    def _on_rst(self, sid):
        self.streams.pop(sid, None)
        self._abort_stream_tx(sid)

    async def _run(self, sid: int, handler: Handler, message: bytes):
        try:
            response = await handler(message)
        except NotImplementedError as e:
            self._trailers_only(sid, GRPC_UNIMPLEMENTED, str(e).encode())
            return
        except Exception as e:  # handler bug: surface as INTERNAL
            self._trailers_only(sid, GRPC_INTERNAL, str(e).encode())
            return
        if self.transport is None or self.transport.is_closing():
            return
        head = _frame(_HEADERS, _F_END_HEADERS, sid, self._resp_headers)
        trailer = _frame(
            _HEADERS, _F_END_HEADERS | _F_END_STREAM, sid, self._ok_trailers
        )
        self.transport.write(head)
        self._send_stream(sid, _grpc_frame(response), trailer=trailer)

    def _trailers_only(self, sid: int, status: int, message: bytes):
        if self.transport is None or self.transport.is_closing():
            return
        block = encode_headers([
            (b":status", b"200"),
            (b"content-type", b"application/grpc"),
            (b"grpc-status", str(status).encode()),
            (b"grpc-message", message[:1024]),
        ])
        self.transport.write(
            _frame(_HEADERS, _F_END_HEADERS | _F_END_STREAM, sid, block)
        )


class FastGrpcServer:
    """Engine-facing server: routes the Seldon service's unary methods.

    ``handlers`` maps gRPC paths to ``async (request bytes) -> response
    bytes``; ``for_engine`` wires the standard Seldon surface."""

    def __init__(self, handlers: Dict[bytes, Handler]):
        self.handlers = handlers
        self._server: Optional[asyncio.AbstractServer] = None
        self._protocols: set = set()

    @classmethod
    def for_engine(cls, engine) -> "FastGrpcServer":
        from seldon_core_tpu import protoconv
        from seldon_core_tpu.graph.spec import GraphSpecError
        from seldon_core_tpu.messages import SeldonMessage, SeldonMessageError
        from seldon_core_tpu.proto_gen import prediction_pb2 as pb

        async def predict(wire: bytes) -> bytes:
            # identical semantics to grpc_server.make_engine_grpc_server's
            # predict_wire: typed errors -> FAILURE SeldonMessage
            try:
                return await engine.predict_proto_wire(wire)
            except (SeldonMessageError, GraphSpecError) as e:
                return protoconv.msg_to_proto(
                    SeldonMessage.failure(str(e))
                ).SerializeToString()

        async def send_feedback(wire: bytes) -> bytes:
            # typed errors -> FAILURE SeldonMessage, like the stock lane's
            # _wrap (grpc_server.py)
            try:
                fb = protoconv.feedback_from_proto(
                    pb.Feedback.FromString(wire)
                )
                ack = await engine.send_feedback(fb)
            except (SeldonMessageError, GraphSpecError) as e:
                return protoconv.msg_to_proto(
                    SeldonMessage.failure(str(e))
                ).SerializeToString()
            return protoconv.msg_to_proto(ack).SerializeToString()

        return cls({
            b"/seldon.protos.Seldon/Predict": predict,
            b"/seldon.protos.Seldon/SendFeedback": send_feedback,
            # node-service aliases: engines compose as MODEL leaves of
            # larger cross-process graphs; feedback arrives on the Router/
            # Generic services (grpc_server.make_engine_grpc_server,
            # runtime/client.py GrpcNodeRuntime:198-209)
            b"/seldon.protos.Model/Predict": predict,
            b"/seldon.protos.Router/SendFeedback": send_feedback,
            b"/seldon.protos.Generic/SendFeedback": send_feedback,
        })

    async def start(self, host: str, port: int) -> None:
        loop = asyncio.get_running_loop()
        self._server = await loop.create_server(
            lambda: _ServerConnection(self.handlers, self._protocols),
            host, port, backlog=4096,
        )

    async def stop(self) -> None:
        if self._server is None:
            return
        self._server.close()
        for proto in list(self._protocols):
            if proto.transport is not None:
                proto.transport.close()
        try:
            await asyncio.wait_for(self._server.wait_closed(), timeout=5.0)
        except asyncio.TimeoutError:
            pass
        self._server = None


async def serve_grpc_fast(engine, host: str, port: int) -> FastGrpcServer:
    server = FastGrpcServer.for_engine(engine)
    await server.start(host, port)
    return server


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------


class GrpcCallError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(f"grpc-status {status}: {message}")
        self.status = status
        self.grpc_message = message


class _ClientConnection(_H2Endpoint):
    is_server = False

    def __init__(self, authority: bytes):
        super().__init__()
        self.authority = authority
        self.next_stream = 1
        self.calls: Dict[int, dict] = {}

    def _on_close(self, exc):
        err = GrpcCallError(14, "connection lost")  # UNAVAILABLE
        for call in self.calls.values():
            if not call["future"].done():
                call["future"].set_exception(err)
        self.calls.clear()

    def start_call(self, path: bytes, message: bytes) -> asyncio.Future:
        if self.transport is None or self.transport.is_closing():
            # fail fast: a write on a closed transport is a silent no-op and
            # the future would never resolve
            raise GrpcCallError(14, "connection closed")
        sid = self.next_stream
        self.next_stream += 2
        fut = asyncio.get_running_loop().create_future()
        self.calls[sid] = {"future": fut, "body": bytearray(), "status": None}
        block = encode_headers([
            (b":method", b"POST"),
            (b":scheme", b"http"),
            (b":path", path),
            (b":authority", self.authority),
            (b"content-type", b"application/grpc"),
            (b"te", b"trailers"),
        ])
        framed = _grpc_frame(message)
        self.transport.write(_frame(_HEADERS, _F_END_HEADERS, sid, block))
        self._send_stream(sid, framed, end_on_data=True)
        return fut

    def _on_headers(self, sid, headers, end_stream):
        call = self.calls.get(sid)
        if call is None:
            return
        for name, value in headers:
            if name == b"grpc-status":
                call["status"] = int(value)
            elif name == b"grpc-message":
                call["message"] = value.decode("utf-8", "replace")
        if end_stream:
            self._finish(sid)

    def _on_data(self, sid, body, end_stream):
        call = self.calls.get(sid)
        if call is None:
            return
        call["body"].extend(body)
        if end_stream:  # servers normally end on trailers, but be lenient
            self._finish(sid)

    def _on_rst(self, sid):
        self._abort_stream_tx(sid)
        call = self.calls.pop(sid, None)
        if call is not None and not call["future"].done():
            call["future"].set_exception(GrpcCallError(13, "stream reset"))

    def _finish(self, sid):
        self._abort_stream_tx(sid)
        call = self.calls.pop(sid, None)
        if call is None or call["future"].done():
            return
        status = call["status"]
        if status not in (None, 0):
            call["future"].set_exception(
                GrpcCallError(status, call.get("message", ""))
            )
            return
        buf = call["body"]
        if len(buf) < 5:
            call["future"].set_exception(
                GrpcCallError(13, "short grpc frame")
            )
            return
        call["future"].set_result(bytes(buf[5:]))


class FastGrpcChannel:
    """Minimal multiplexing unary client: ``await channel.call(path,
    message_bytes) -> response_bytes``."""

    def __init__(self):
        self._conn: Optional[_ClientConnection] = None

    async def connect(self, host: str, port: int) -> "FastGrpcChannel":
        loop = asyncio.get_running_loop()
        _, self._conn = await loop.create_connection(
            lambda: _ClientConnection(f"{host}:{port}".encode()), host, port
        )
        return self

    async def call(self, path: bytes, message: bytes) -> bytes:
        return await self._conn.start_call(path, message)

    async def close(self) -> None:
        if self._conn is not None and self._conn.transport is not None:
            self._conn.transport.close()
            await self._conn.closed
