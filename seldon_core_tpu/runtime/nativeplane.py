"""Native REST data plane — ctypes driver for native/dataplane.cpp.

Role split (see the C++ header comment): the C++ IO thread terminates
HTTP/1.1, parses numeric predict payloads, and coalesces rows into stacked
batches; Python's entire per-request involvement is one blocking FFI call
per BATCH:

    dp_next_batch() -> numpy view -> pad to bucket -> ONE XLA dispatch
                    -> dp_complete_batch(y)

so the interpreter cost is amortised across up to ``max_batch`` requests.
Requests outside the fast lane's shape (feedback, admin routes, strData /
binData / jsonData, form bodies, >2-D tensors) arrive on the misc queue and
are served through the SAME route table as the Python fast server
(httpfast._EngineRoutes), keeping wire semantics identical — the native
plane is a hot path, not a second implementation of the API.

Eligibility is a rule over the GRAPH (``native_ineligible_reason``): it
mirrors the engine's pipelined-batcher conditions (runtime/engine.py) —
compiled mode, batchable graph, no state updates on predict — and
excludes generator graphs (streaming + the GenLane scheduler live on the
Python fast lane) and graphs that emit per-request routing/tags
(detected by a probe dispatch when a prewarmed width is available).
engine_main picks the lane from that rule; on an eligible graph a plane
that fails to build, load or bind is an error, not a lane change.

The reference's analogue is the Tomcat NIO + Jackson stack each engine pod
runs (engine RestClientController.java); this is its TPU-native
replacement: C++ for the wire, XLA for the math, Python only for control.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import time
from typing import Optional

import numpy as np

from seldon_core_tpu.runtime.qos import TIER_INTERACTIVE
from seldon_core_tpu.utils.costledger import costledger_enabled
from seldon_core_tpu.utils.hotrecord import SPINE
from seldon_core_tpu.utils.perf import OBSERVATORY

__all__ = ["NativeDataPlane", "native_ineligible_reason",
           "native_plane_available"]

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(_REPO_ROOT, "native", "dataplane.cpp")
_CODEC_SRC = os.path.join(_REPO_ROOT, "native", "fastcodec.cpp")
_LIB_PATH = os.path.join(_REPO_ROOT, "native", "libdataplane.so")

_lock = threading.Lock()
_lib = None
_load_attempted = False


class _DpBatchView(ctypes.Structure):
    _fields_ = [
        ("id", ctypes.c_longlong),
        ("rows", ctypes.c_longlong),
        ("width", ctypes.c_longlong),
        ("data", ctypes.POINTER(ctypes.c_double)),
    ]


class _DpMiscView(ctypes.Structure):
    _fields_ = [
        ("id", ctypes.c_longlong),
        ("method", ctypes.c_void_p),
        ("method_len", ctypes.c_longlong),
        ("path", ctypes.c_void_p),
        ("path_len", ctypes.c_longlong),
        ("query", ctypes.c_void_p),
        ("query_len", ctypes.c_longlong),
        ("ctype", ctypes.c_void_p),
        ("ctype_len", ctypes.c_longlong),
        ("body", ctypes.c_void_p),
        ("body_len", ctypes.c_longlong),
    ]


def _build() -> bool:
    if not (os.path.exists(_SRC) and os.path.exists(_CODEC_SRC)):
        return False
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
             "-o", _LIB_PATH, _SRC, _CODEC_SRC],
            check=True, capture_output=True,
        )
    except (OSError, subprocess.CalledProcessError) as e:
        logger.warning("native dataplane build failed: %s", e)
        return False
    return True


def _load():
    global _lib, _load_attempted
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        srcs = [p for p in (_SRC, _CODEC_SRC) if os.path.exists(p)]
        fresh = os.path.exists(_LIB_PATH) and (
            not srcs
            or os.path.getmtime(_LIB_PATH)
            >= max(os.path.getmtime(p) for p in srcs)
        )
        if not fresh and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            logger.warning("native dataplane load failed: %s", e)
            return None
        lib.dp_start.restype = ctypes.c_void_p
        lib.dp_start.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_longlong,
            ctypes.c_double, ctypes.c_int, ctypes.c_char_p, ctypes.c_longlong,
            ctypes.c_char_p, ctypes.c_longlong,
        ]
        lib.dp_port.restype = ctypes.c_int
        lib.dp_port.argtypes = [ctypes.c_void_p]
        lib.dp_grpc_port.restype = ctypes.c_int
        lib.dp_grpc_port.argtypes = [ctypes.c_void_p]
        lib.dp_respond_grpc.restype = ctypes.c_int
        lib.dp_respond_grpc.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_longlong,
            ctypes.c_char_p, ctypes.c_longlong,
        ]
        lib.dp_next_batch.restype = ctypes.c_int
        lib.dp_next_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(_DpBatchView)
        ]
        lib.dp_complete_batch.restype = ctypes.c_int
        lib.dp_complete_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_double), ctypes.c_longlong,
            ctypes.c_longlong,
        ]
        lib.dp_fail_batch.restype = ctypes.c_int
        lib.dp_fail_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_longlong,
        ]
        lib.dp_next_misc.restype = ctypes.c_int
        lib.dp_next_misc.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(_DpMiscView)
        ]
        lib.dp_respond_misc.restype = ctypes.c_int
        lib.dp_respond_misc.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_longlong,
        ]
        lib.dp_stats.restype = None
        lib.dp_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong)
        ]
        lib.dp_stop.restype = None
        lib.dp_stop.argtypes = [ctypes.c_void_p]
        lib.dp_shutdown.restype = None
        lib.dp_shutdown.argtypes = [ctypes.c_void_p]
        lib.dp_destroy.restype = None
        lib.dp_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_plane_available() -> bool:
    return _load() is not None


def _pad_rows(x: np.ndarray, max_batch: int) -> np.ndarray:
    """Pad to the power-of-two bucket set capped at max_batch — the same
    shapes the Python batcher compiles (batching.py:_dispatch_chunked), so
    both planes share one XLA executable cache."""
    n = len(x)
    if n <= 1:
        return x
    target = min(1 << (n - 1).bit_length(), max_batch)
    if target <= n:
        return x
    pad = np.repeat(x[-1:], target - n, axis=0)
    return np.concatenate([x, pad], axis=0)


# metrics bucket edges — must match utils/metrics.py _BUCKETS and the
# kBuckets table in native/dataplane.cpp
_BUCKET_EDGES = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    2.5, 5.0, 10.0,
)


def native_ineligible_reason(engine) -> Optional[str]:
    """Why this engine's GRAPH cannot be served by the native plane, or
    None when it can.  The lane is chosen from this stated rule
    (runtime/engine_main.py) — never from an exception out of the plane:
    on an eligible graph a build, load or bind failure is an error."""
    if engine.genserver is not None:
        return ("generator graph: token streaming and the GenLane "
                "continuous-batching scheduler are served by the Python "
                "fast lane")
    if engine.compiled is None or engine.batcher is None \
            or not engine._pipelined:
        return ("the native plane serves pipelined batchable compiled "
                "graphs (stateless predict); this graph is not one")
    if any(u.static_tags for u in engine.compiled.units.values()):
        return ("graph units declare static_tags; the native composer "
                "does not merge tags into meta")
    # graphs emitting per-request routing/tags need per-request meta the
    # C++ composer doesn't build — probed on any prewarmed width
    widths = [w for w in engine._known_good_widths if len(w) == 1]
    if widths:
        x = np.zeros((1,) + widths[0], dtype=np.float64)
        _, routing, tags = engine.compiled.predict_arrays(
            x, update_states=False
        )
        if routing or tags:
            return ("graph emits per-request routing/tags; the Python "
                    "lane serves it with full meta")
    return None


class NativeDataPlane:
    """Owns the C++ plane handle plus the Python dispatch/misc threads."""

    def __init__(self, engine, host: str, port: int,
                 grpc_port: Optional[int] = None,
                 workers: Optional[int] = None):
        self.engine = engine
        reason = native_ineligible_reason(engine)
        if reason is not None:
            raise RuntimeError(reason)
        self.lib = _load()
        if self.lib is None:
            raise RuntimeError(
                "native dataplane unavailable: native/libdataplane.so "
                "could not be built with g++ or loaded (see the warning "
                "logged above)")
        names_frag = getattr(engine, "_names_fragment", "") or ""
        proto_names = bytes(getattr(engine, "_proto_names_frag", b"") or b"")
        self.max_batch = engine.batcher.max_batch
        depth = workers or engine.batcher.max_inflight
        self.handle = self.lib.dp_start(
            host.encode(), int(port),
            -1 if grpc_port is None else int(grpc_port),
            int(self.max_batch),
            float(engine.batcher.max_wait_ms), int(depth),
            names_frag.encode(), len(names_frag.encode()),
            proto_names, len(proto_names),
        )
        if not self.handle:
            raise RuntimeError(f"native dataplane failed to bind {host}:{port}")
        self.port = self.lib.dp_port(self.handle)
        self.grpc_port = (
            self.lib.dp_grpc_port(self.handle) if grpc_port is not None
            else None
        )
        self._loop = None  # captured by start() for misc dispatch
        self._threads = []
        self._stopped = False
        self._last_stats = np.zeros(38, dtype=np.int64)
        self._workers = depth

    # -- threads -----------------------------------------------------------

    def start(self, loop) -> None:
        """Spawn the dispatch worker threads and the misc-lane bridge.
        ``loop`` is the running asyncio loop serving the engine's full
        route semantics."""
        self._loop = loop
        from seldon_core_tpu.runtime.httpfast import _EngineRoutes

        self._routes = _EngineRoutes(self.engine)
        self._grpc_handlers = {}
        if self.grpc_port is not None:
            from seldon_core_tpu.runtime.grpcfast import FastGrpcServer

            self._grpc_handlers = FastGrpcServer.for_engine(
                self.engine
            ).handlers
        for i in range(self._workers):
            t = threading.Thread(
                target=self._dispatch_loop, name=f"dp-dispatch-{i}",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._misc_loop, name="dp-misc",
                             daemon=True)
        t.start()
        self._threads.append(t)

    def _dispatch_loop(self) -> None:
        engine = self.engine
        lib = self.lib
        handle = self.handle
        view = _DpBatchView()
        fail_400 = (
            b'{"status":{"code":400,"status":"FAILURE",'
            b'"reason":"graph rejected input shape"}}'
        )
        fail_tags = (
            b'{"status":{"code":500,"status":"FAILURE","reason":"graph '
            b'emits per-request routing/tags; restart with '
            b'ENGINE_HTTP_IMPL=fast"}}'
        )
        while True:
            if not lib.dp_next_batch(handle, ctypes.byref(view)):
                return  # shutdown
            rows = int(view.rows)
            width = int(view.width)
            x = np.ctypeslib.as_array(view.data, shape=(rows, width))
            try:
                # spans (when tracing is enabled): "plane_batch" covers
                # the Python side of one native batch — pad, device
                # dispatch, output marshalling — and the fused dispatch
                # record isolates the device round-trip, so a served
                # request decomposes into C++ parse/queue (total minus
                # plane) + framework (plane minus dispatch) + device
                with engine.tracer.span(
                    "", "plane_batch", kind="plane", rows=rows
                ):
                    padded = _pad_rows(x, self.max_batch)
                    # pad rows burn device FLOPs without serving traffic —
                    # same accounting as the Python batcher's lane
                    OBSERVATORY.note_padding(rows, len(padded))
                    # ONE fused telemetry record per dispatch hop (engine
                    # lane parity, utils/hotrecord.py): the unified
                    # verdict rides the plane span's head decision, and
                    # the perf/quality/span folds all happen off-path
                    wants = SPINE.dispatch_wants()
                    t_dispatch = time.perf_counter()
                    start_s = time.time()
                    try:
                        y, routing, tags = engine.compiled.predict_arrays(
                            padded, update_states=False
                        )
                    except BaseException as e:
                        # failed dispatches keep their span too (engine
                        # lane parity): the incident trace must show the
                        # device hop that died — and the typed error on
                        # the open plane span is what the postmortem
                        # retention policy keys on for this lane
                        engine.tracer.annotate(
                            status=500, error=type(e).__name__
                        )
                        if wants.trace:
                            SPINE.record_failed_dispatch(
                                executable=engine.compiled.executable_key(
                                    padded
                                ),
                                seconds=time.perf_counter() - t_dispatch,
                                start_s=start_s, rows=rows,
                                method="native", error=type(e).__name__,
                            )
                        raise
                    # force the readback here (jax dispatch is async —
                    # device time is only paid at the readback);
                    # it is also the only array touch observability needs
                    y = np.asarray(y)
                    dispatch_s = time.perf_counter() - t_dispatch
                    # flush-record parity with MicroBatcher._flush: a
                    # native batch IS a stacked flush, so it books batch
                    # occupancy AND carries the cost-ledger attribution
                    # payload (utils/costledger.py) — without this the
                    # ledger is blind on the lane that serves most real
                    # traffic.  The C++ coalescer doesn't surface request
                    # boundaries or Seldon-Tenant to Python, so the wall
                    # and pad tax book to the anonymous tenant at the
                    # default tier; requests=0 marks the count unknown
                    cost = None
                    if costledger_enabled():
                        cost = {
                            "dep": engine.deployment.name,
                            "padded": len(padded),
                            "tenants": [("", TIER_INTERACTIVE,
                                         float(rows), 0, 0)],
                        }
                    SPINE.record_flush(
                        rows=rows, requests=0, start_s=start_s,
                        duration_s=dispatch_s, cost=cost,
                    )
                    if wants.any:
                        # `padded is x` means it is a VIEW into the C++
                        # plane's request buffer, which is recycled the
                        # moment the batch completes — a deferred quality
                        # fold must hold its own copy
                        xq = None
                        if wants.quality:
                            xq = np.array(x) if padded is x else padded
                        SPINE.record_dispatch(
                            wants,
                            executable=engine.compiled.executable_key(
                                padded
                            ),
                            seconds=dispatch_s,
                            start_s=start_s,
                            rows=rows, real_rows=rows, method="native",
                            quality_node=engine._quality_node,
                            X=xq, Y=y,
                            # fused graphs: the per-node phase
                            # decomposition rides the native lane's
                            # record too (engine lane parity)
                            phases=getattr(
                                engine.compiled, "phases", None
                            ),
                        )
                    if routing or tags:
                        # data-dependent tags slipped past the static
                        # checks: the C++ composer cannot merge them into
                        # meta, so refuse loudly rather than strip them
                        logger.error(
                            "native plane cannot serve tag/routing-"
                            "emitting graph; set ENGINE_HTTP_IMPL=fast"
                        )
                        lib.dp_fail_batch(
                            handle, view.id, 500, fail_tags, len(fail_tags)
                        )
                        continue
                    y = np.ascontiguousarray(
                        np.asarray(y)[:rows], dtype=np.float64
                    )
                    # the C++ composer emits 2-D fragments; higher-rank
                    # model outputs flatten per row (same wire width)
                    if y.ndim != 2:
                        y = y.reshape(rows, -1)
                    engine._known_good_widths.add((width,))
                    lib.dp_complete_batch(
                        handle, view.id,
                        y.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                        y.shape[0], y.shape[1],
                    )
            except (TypeError, ValueError) as e:
                # novel width failing at trace time = client shape error
                # (engine.py:_batched_predict_sync's 400/500 split)
                if (width,) in engine._known_good_widths:
                    logger.exception("native plane dispatch failed")
                    lib.dp_fail_batch(handle, view.id, 500, None, 0)
                else:
                    logger.debug("native plane rejected width %s: %s",
                                 width, e)
                    lib.dp_fail_batch(
                        handle, view.id, 400, fail_400, len(fail_400)
                    )
            except Exception:
                logger.exception("native plane dispatch failed")
                lib.dp_fail_batch(handle, view.id, 500, None, 0)

    def _misc_loop(self) -> None:
        import asyncio

        lib = self.lib
        handle = self.handle
        view = _DpMiscView()
        while True:
            if not lib.dp_next_misc(handle, ctypes.byref(view)):
                return  # shutdown
            mid = int(view.id)
            method = ctypes.string_at(view.method, view.method_len)
            path = ctypes.string_at(view.path, view.path_len)
            query = ctypes.string_at(view.query, view.query_len)
            ctype = ctypes.string_at(view.ctype, view.ctype_len)
            body = ctypes.string_at(view.body, view.body_len)
            if method == b"GRPC":
                fut = asyncio.run_coroutine_threadsafe(
                    self._handle_grpc(path, body), self._loop,
                )
                fut.add_done_callback(
                    lambda f, mid=mid: self._grpc_done(mid, f)
                )
                continue
            fut = asyncio.run_coroutine_threadsafe(
                self._handle_misc(method, path, query, ctype, body),
                self._loop,
            )
            # respond from the future's completion callback so one slow
            # handler never serializes the misc lane (health probes must
            # not queue behind a long feedback POST)
            fut.add_done_callback(
                lambda f, mid=mid: self._misc_done(mid, f)
            )

    def _misc_done(self, mid: int, fut) -> None:
        if self._stopped or self.handle is None:
            return
        try:
            status, resp, rctype = fut.result()
        except Exception as e:  # handler crashed
            logger.exception("misc handler failed")
            status, resp, rctype = 500, str(e).encode(), "text/plain"
        self.lib.dp_respond_misc(
            self.handle, mid, int(status), rctype.encode(), resp, len(resp)
        )

    async def _handle_grpc(self, path: bytes, message: bytes):
        """gRPC misc lane: same handler table and status mapping as the
        Python fast gRPC server (grpcfast._ServerConnection._run)."""
        handler = self._grpc_handlers.get(path)
        if handler is None:
            return 12, b"unknown method " + path, b""  # UNIMPLEMENTED
        try:
            response = await handler(message)
        except NotImplementedError as e:
            return 12, str(e).encode(), b""
        except Exception as e:  # handler bug: surface as INTERNAL
            logger.exception("grpc misc handler failed")
            return 13, str(e).encode(), b""
        return 0, b"", response

    def _grpc_done(self, mid: int, fut) -> None:
        if self._stopped or self.handle is None:
            return
        try:
            status, message, payload = fut.result()
        except Exception as e:
            logger.exception("grpc misc handler failed")
            status, message, payload = 13, str(e).encode(), b""
        self.lib.dp_respond_grpc(
            self.handle, mid, int(status), message, len(message),
            payload, len(payload),
        )

    async def _handle_misc(self, method, path, query, ctype, body):
        """Full-semantics lane: same table as the Python fast server."""
        table = (
            self._routes.post if method == b"POST"
            else self._routes.get if method == b"GET"
            else None
        )
        handler = table.get(path) if table is not None else None
        if handler is None:
            return (405, b"method not allowed", "text/plain") \
                if table is None else (404, b"not found", "text/plain")
        if path == b"/prometheus":
            self._merge_native_metrics()
        result = await handler(
            body, ctype.decode("latin-1"), query.decode("latin-1")
        )
        from seldon_core_tpu.runtime.httpfast import StreamResult

        if isinstance(result, StreamResult):
            # the C++ misc bridge sends single complete responses; SSE
            # streaming lives on the Python lanes (ENGINE_HTTP_IMPL=fast)
            await result.agen.aclose()
            return (
                501,
                b'{"status":{"code":501,"status":"FAILURE","reason":'
                b'"streaming is served by the Python data plane '
                b'(ENGINE_HTTP_IMPL=fast)"}}',
                "application/json",
            )
        status, resp, rctype = result
        if isinstance(resp, list):
            # the binary wire lane answers framed PARTS (the Python
            # writers send them as separate buffers); the C++ misc bridge
            # sends one complete buffer
            from seldon_core_tpu.runtime import wire

            resp = wire.join_parts(resp)
        return status, resp, rctype

    # -- metrics -----------------------------------------------------------

    def _merge_native_metrics(self) -> None:
        """Fold the C++ lanes' counters into the engine's prometheus
        histogram so /prometheus reports one truth.  dp_stats exposes two
        19-slot blocks — HTTP/1.1 then h2/gRPC — merged into distinct
        metric children (REST vs gRPC must not be conflated, same as the
        Python lanes).  Deltas since the last scrape are injected
        bucket-exactly (prometheus_client has no bucket-level API; the
        private counters are stable across releases and guarded here)."""
        stats = np.zeros(38, dtype=np.int64)
        arr = (ctypes.c_longlong * 38)()
        self.lib.dp_stats(self.handle, arr)
        stats[:] = arr[:]
        delta = stats - self._last_stats
        self._last_stats = stats
        metrics = self.engine.metrics
        if metrics.registry is None:
            return
        lanes = (
            (delta[:19], ("predictions", "POST", "200")),
            (delta[19:], ("predictions", "GRPC", "200")),
        )
        for d, labels in lanes:
            if d[0] <= 0:
                continue
            try:
                child = metrics._server_child(*labels)
                buckets = getattr(child, "_buckets", None)
                csum = getattr(child, "_sum", None)
                if buckets is None or csum is None:
                    continue
                # child._buckets are per-bucket (non-cumulative) counters
                # parallel to upper_bounds (finite edges + +Inf); the
                # renderer accumulates and derives _count
                for i in range(15):
                    n = int(d[4 + i])
                    if n:
                        buckets[i].inc(n)
                csum.inc(float(d[3]) / 1e6)
            except Exception:  # private-API drift: drop samples, don't 500
                logger.debug("native metric merge skipped", exc_info=True)

    # -- lifecycle ---------------------------------------------------------

    async def stop(self) -> None:
        """Two-phase: dp_shutdown wakes every blocked worker and stops IO
        (the Plane stays allocated so threads mid-dispatch stay safe);
        dp_destroy frees it only after the workers joined.  A thread wedged
        past the join timeout leaks the Plane deliberately — a small leak
        at process exit beats a use-after-free."""
        if self._stopped or self.handle is None:
            return
        self._stopped = True
        import asyncio

        loop = asyncio.get_running_loop()
        handle = self.handle
        await loop.run_in_executor(None, self.lib.dp_shutdown, handle)

        def _join_all() -> bool:
            deadline = 35.0  # dispatch timeout + slack
            for t in self._threads:
                import time as _time

                t0 = _time.monotonic()
                t.join(timeout=deadline)
                deadline = max(1.0, deadline - (_time.monotonic() - t0))
                if t.is_alive():
                    return False
            return True

        joined = await loop.run_in_executor(None, _join_all)
        self.handle = None
        if joined:
            self.lib.dp_destroy(handle)
        else:
            logger.warning(
                "native plane worker wedged; leaking plane at shutdown"
            )


async def serve_native(engine, host: str, port: int,
                       grpc_port: Optional[int] = None) -> NativeDataPlane:
    import asyncio

    plane = NativeDataPlane(engine, host, port, grpc_port=grpc_port)
    plane.start(asyncio.get_running_loop())
    return plane
