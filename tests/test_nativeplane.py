"""Native data plane (native/dataplane.cpp + runtime/nativeplane.py):
wire parity with the Python lanes, misc-lane fallback, concurrency, and
lifecycle.  Runs on the CPU platform like every other serving test; the
plane itself is platform-agnostic (it only sees numpy batches)."""

import asyncio
import json

import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.nativeplane import (
    native_plane_available,
    serve_native,
)

pytestmark = pytest.mark.skipif(
    not native_plane_available(), reason="no native toolchain"
)

STUB = SeldonDeploymentSpec.from_json_dict(
    {
        "spec": {
            "name": "np-test",
            "predictors": [
                {
                    "name": "p",
                    "graph": {
                        "name": "stub",
                        "implementation": "SIMPLE_MODEL",
                        "type": "MODEL",
                    },
                }
            ],
        }
    }
)


async def _post(host, port, path, body, ctype="application/json"):
    reader, writer = await asyncio.open_connection(host, port)
    payload = body.encode() if isinstance(body, str) else body
    writer.write(
        (
            f"POST {path} HTTP/1.1\r\nHost: t\r\nContent-Type: {ctype}\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n"
        ).encode() + payload
    )
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    lower = head.lower()
    j = lower.find(b"content-length:")
    clen = int(lower[j + 15: lower.find(b"\r", j)])
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp


async def _get(host, port, path):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    lower = head.lower()
    j = lower.find(b"content-length:")
    clen = int(lower[j + 15: lower.find(b"\r", j)])
    resp = await reader.readexactly(clen)
    writer.close()
    return status, resp


@pytest.fixture()
def plane_engine():
    engine = EngineService(STUB, max_batch=64, max_wait_ms=1.0,
                           pipeline_depth=4)
    engine.prewarm([1])
    return engine


def _serve(engine):
    return serve_native(engine, "127.0.0.1", 0)


def test_fast_lane_parity_with_python_path(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            req = '{"data":{"ndarray":[[0.25]]}}'
            status, native = await _post(
                "127.0.0.1", plane.port, "/api/v0.1/predictions", req
            )
            assert status == 200
            py_text, py_status = await plane_engine.predict_json(req)
            assert py_status == 200
            nd = json.loads(native)
            pd = json.loads(py_text)
            assert nd["data"]["names"] == pd["data"]["names"]
            np.testing.assert_allclose(
                nd["data"]["ndarray"], pd["data"]["ndarray"]
            )
            assert nd["status"] == pd["status"]
            assert nd["meta"]["puid"]  # generated, base32
        finally:
            await plane.stop()

    asyncio.run(run())


def test_binary_wire_frame_answers_over_the_native_plane(plane_engine):
    """An application/x-seldon-tensor request on the DEFAULT lane rides
    the misc bridge: its framed response parts must be joined into the
    one buffer the C++ side sends (it used to raise in the completion
    callback and the client hung)."""
    from seldon_core_tpu.runtime import wire

    async def run():
        plane = await _serve(plane_engine)
        try:
            x = np.asarray([[0.25], [0.5]], np.float32)
            status, raw = await asyncio.wait_for(_post(
                "127.0.0.1", plane.port, "/api/v0.1/predictions",
                wire.join_parts(wire.encode_frame(x)),
                ctype=wire.WIRE_CONTENT_TYPE,
            ), 30)
            assert status == 200
            y_bin = np.asarray(wire.decode_frame(raw).array)
            _, y_json = await _post(
                "127.0.0.1", plane.port, "/api/v0.1/predictions",
                json.dumps({"data": {"ndarray": x.tolist()}}))
            np.testing.assert_array_equal(
                y_bin.astype(np.float32),
                np.asarray(json.loads(y_json)["data"]["ndarray"],
                           np.float32))
        finally:
            await plane.stop()

    asyncio.run(run())


def test_tensor_kind_meta_echo_and_multirow(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            req = json.dumps({
                "meta": {"puid": "keep-me", "tags": {"a": 1}},
                "data": {"tensor": {"shape": [3, 1],
                                    "values": [0.1, 0.2, 0.3]}},
            })
            status, resp = await _post(
                "127.0.0.1", plane.port, "/api/v0.1/predictions", req
            )
            assert status == 200
            doc = json.loads(resp)
            assert doc["meta"]["puid"] == "keep-me"
            assert doc["meta"]["tags"] == {"a": 1}
            assert doc["data"]["tensor"]["shape"] == [3, 3]
            assert len(doc["data"]["tensor"]["values"]) == 9
        finally:
            await plane.stop()

    asyncio.run(run())


def test_misc_lane_routes(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            assert (await _get("127.0.0.1", plane.port, "/ping"))[1] == b"pong"
            assert (await _get("127.0.0.1", plane.port, "/ready"))[0] == 200
            status, resp = await _get("127.0.0.1", plane.port, "/nope")
            assert status == 404
            # form-encoded predictions ride the misc lane into the engine
            from urllib.parse import quote

            body = "json=" + quote('{"data":{"ndarray":[[0.5]]}}')
            status, resp = await _post(
                "127.0.0.1", plane.port, "/api/v0.1/predictions", body,
                ctype="application/x-www-form-urlencoded",
            )
            assert status == 200
            assert json.loads(resp)["status"]["status"] == "SUCCESS"
            # bad JSON -> engine's typed 400
            status, resp = await _post(
                "127.0.0.1", plane.port, "/api/v0.1/predictions", "nope"
            )
            assert status == 400
            assert json.loads(resp)["status"]["status"] == "FAILURE"
        finally:
            await plane.stop()

    asyncio.run(run())


def test_feedback_via_misc_lane(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            fb = json.dumps({
                "request": {"data": {"ndarray": [[0.5]]}},
                "response": {"data": {"ndarray": [[0.1, 0.9, 0.5]]}},
                "reward": 1.0,
            })
            status, resp = await _post(
                "127.0.0.1", plane.port, "/api/v0.1/feedback", fb
            )
            assert status == 200
        finally:
            await plane.stop()

    asyncio.run(run())


def test_concurrent_burst_batches(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            async def one(i):
                req = json.dumps({"data": {"ndarray": [[i / 100.0]]}})
                status, resp = await _post(
                    "127.0.0.1", plane.port, "/api/v0.1/predictions", req
                )
                assert status == 200
                doc = json.loads(resp)
                assert doc["data"]["ndarray"] == [[
                    pytest.approx(0.1, abs=1e-6),
                    pytest.approx(0.9, abs=1e-6),
                    pytest.approx(0.5, abs=1e-6),
                ]]

            await asyncio.gather(*[one(i) for i in range(96)])
        finally:
            await plane.stop()

    asyncio.run(run())


def test_prometheus_reports_native_lane(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            for _ in range(4):
                await _post(
                    "127.0.0.1", plane.port, "/api/v0.1/predictions",
                    '{"data":{"ndarray":[[0.5]]}}',
                )
            status, resp = await _get("127.0.0.1", plane.port, "/prometheus")
            assert status == 200
            text = resp.decode()
            for line in text.splitlines():
                if (line.startswith(
                        "seldon_api_engine_server_requests_duration_seconds_count")
                        and 'service="predictions"' in line):
                    assert float(line.rsplit(" ", 1)[1]) >= 4
                    break
            else:
                pytest.fail("no predictions histogram in exposition")
        finally:
            await plane.stop()

    asyncio.run(run())


def test_keepalive_and_connection_close(plane_engine):
    async def run():
        plane = await _serve(plane_engine)
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", plane.port
            )
            body = b'{"data":{"ndarray":[[0.5]]}}'
            req = (
                b"POST /api/v0.1/predictions HTTP/1.1\r\nHost: t\r\n"
                b"Content-Length: %d\r\n\r\n" % len(body) + body
            )
            for _ in range(3):  # keepalive reuse
                writer.write(req)
                head = await reader.readuntil(b"\r\n\r\n")
                assert b" 200 " in head.split(b"\r\n")[0]
                lower = head.lower()
                j = lower.find(b"content-length:")
                clen = int(lower[j + 15: lower.find(b"\r", j)])
                await reader.readexactly(clen)
            # explicit close is honoured
            writer.write(
                b"POST /api/v0.1/predictions HTTP/1.1\r\nHost: t\r\n"
                b"Connection: close\r\nContent-Length: %d\r\n\r\n" % len(body)
                + body
            )
            head = await reader.readuntil(b"\r\n\r\n")
            assert b"connection: close" in head.lower()
            lower = head.lower()
            j = lower.find(b"content-length:")
            clen = int(lower[j + 15: lower.find(b"\r", j)])
            await reader.readexactly(clen)
            assert await reader.read(1) == b""  # server closed
            writer.close()
        finally:
            await plane.stop()

    asyncio.run(run())


def test_grpc_lane_stock_client(plane_engine):
    """Native h2 lane vs an unmodified grpc.aio client (Huffman + dynamic
    table HPACK, real flow control): tensor fast lane, puid echo, ndarray
    through the misc lane, unknown method -> UNIMPLEMENTED."""
    import grpc

    from seldon_core_tpu.proto_gen import prediction_pb2 as pb

    async def run():
        plane = await serve_native(plane_engine, "127.0.0.1", 0, grpc_port=0)
        try:
            ch = grpc.aio.insecure_channel(f"127.0.0.1:{plane.grpc_port}")
            stub = ch.unary_unary(
                "/seldon.protos.Seldon/Predict",
                request_serializer=pb.SeldonMessage.SerializeToString,
                response_deserializer=pb.SeldonMessage.FromString,
            )
            r = await stub(
                pb.SeldonMessage(
                    data=pb.DefaultData(
                        tensor=pb.Tensor(shape=[2, 1], values=[0.5, 0.6])
                    )
                ),
                timeout=30,
            )
            assert list(r.data.tensor.shape) == [2, 3]
            assert len(r.data.tensor.values) == 6
            assert r.status.code == 200
            assert len(r.meta.puid) == 26
            assert list(r.data.names) == plane_engine.compiled._output_names(
                plane_engine.predictor.graph, {}
            )
            r2 = await stub(
                pb.SeldonMessage(
                    meta=pb.Meta(puid="echo-me"),
                    data=pb.DefaultData(
                        tensor=pb.Tensor(shape=[1, 1], values=[0.1])
                    ),
                ),
                timeout=30,
            )
            assert r2.meta.puid == "echo-me"
            # ndarray payloads decline to the misc lane (full proto path)
            from google.protobuf import struct_pb2

            lv = struct_pb2.ListValue()
            row = struct_pb2.ListValue()
            row.values.add().number_value = 0.7
            lv.values.add().list_value.CopyFrom(row)
            r3 = await stub(
                pb.SeldonMessage(data=pb.DefaultData(ndarray=lv)), timeout=30
            )
            assert r3.status.code == 200
            assert r3.data.WhichOneof("data_oneof") == "ndarray"
            # unknown method -> UNIMPLEMENTED via trailers-only
            bad = ch.unary_unary(
                "/seldon.protos.Seldon/Nope",
                request_serializer=pb.SeldonMessage.SerializeToString,
                response_deserializer=pb.SeldonMessage.FromString,
            )
            with pytest.raises(grpc.aio.AioRpcError) as ei:
                await bad(pb.SeldonMessage(), timeout=30)
            assert ei.value.code() == grpc.StatusCode.UNIMPLEMENTED
            await ch.close()
        finally:
            await plane.stop()

    asyncio.run(run())


def test_grpc_lane_concurrent_burst(plane_engine):
    import grpc

    from seldon_core_tpu.proto_gen import prediction_pb2 as pb

    async def run():
        plane = await serve_native(plane_engine, "127.0.0.1", 0, grpc_port=0)
        try:
            ch = grpc.aio.insecure_channel(f"127.0.0.1:{plane.grpc_port}")
            stub = ch.unary_unary(
                "/seldon.protos.Seldon/Predict",
                request_serializer=pb.SeldonMessage.SerializeToString,
                response_deserializer=pb.SeldonMessage.FromString,
            )

            async def one(i):
                r = await stub(
                    pb.SeldonMessage(
                        data=pb.DefaultData(
                            tensor=pb.Tensor(shape=[1, 1], values=[i / 64])
                        )
                    ),
                    timeout=30,
                )
                assert list(r.data.tensor.values) == [
                    pytest.approx(0.1, abs=1e-6),
                    pytest.approx(0.9, abs=1e-6),
                    pytest.approx(0.5, abs=1e-6),
                ]

            await asyncio.gather(*[one(i) for i in range(80)])
            await ch.close()
        finally:
            await plane.stop()

    asyncio.run(run())


def test_ineligible_graph_rejected():
    # router graph (per-request routing, stateful PRNG) must refuse the
    # native plane — it serves through the Python lanes with full meta
    spec = SeldonDeploymentSpec.from_json_dict({
        "spec": {
            "name": "abtest",
            "predictors": [{
                "name": "p",
                "graph": {
                    "name": "r",
                    "type": "ROUTER",
                    "implementation": "RANDOM_ABTEST",
                    "children": [
                        {"name": "a", "type": "MODEL",
                         "implementation": "SIMPLE_MODEL"},
                        {"name": "b", "type": "MODEL",
                         "implementation": "SIMPLE_MODEL"},
                    ],
                },
            }],
        }
    })
    engine = EngineService(spec)

    async def run():
        with pytest.raises(RuntimeError):
            await serve_native(engine, "127.0.0.1", 0)

    asyncio.run(run())
