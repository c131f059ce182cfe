"""Binary tensor wire contract (runtime/wire.py): codec round trips,
torn-frame robustness, JSON-vs-binary parity on EVERY lane (engine
object path, fast HTTP, aiohttp REST, framed relay, gateway ingress,
coalesced multi-frame, node-mesh client), sidecar metadata propagation,
and the ``SELDON_TPU_WIRE=0`` kill switch restoring the JSON path.

The parity contract is *per identical dispatch*: requests stacked into
different pad buckets may differ in f32 reduction order on either lane
(a pre-existing batching property), so parity pins run sequentially —
same rows, same bucket, same executable."""

import asyncio
import json
import os

import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import SeldonMessage
from seldon_core_tpu.runtime import wire
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.utils.telemetry import RECORDER


def sigmoid_spec(name="wire-dep", n_features=4):
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {
            "name": name,
            "oauth_key": "k", "oauth_secret": "s",
            "predictors": [{
                "name": "p",
                "graph": {"name": "m", "type": "MODEL"},
                "components": [{
                    "name": "m", "runtime": "inprocess",
                    "class_path": "SigmoidPredictor",
                    "parameters": [
                        {"name": "n_features", "value": str(n_features),
                         "type": "INT"},
                    ],
                }],
            }],
        }
    })


def frame_bytes(arr, **kw):
    return wire.join_parts(wire.encode_frame(arr, **kw))


def rows4(seed=0, n=1):
    return np.random.default_rng(seed).normal(size=(n, 4))


def json_payload(x):
    return json.dumps({"data": {"ndarray": np.asarray(x).tolist()}})


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [
    np.float32, np.float64, np.int8, np.int16, np.int32, np.int64,
    np.uint8, np.bool_, np.float16,
])
def test_codec_roundtrip_dtypes(dtype):
    a = (np.arange(24).reshape(3, 8) % 2).astype(dtype)
    f = wire.decode_frame(frame_bytes(a))
    assert f.array.dtype == np.dtype(dtype)
    assert np.array_equal(f.array, a)
    assert not f.is_response and f.status == 0


def test_codec_roundtrip_header_and_sidecar():
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    meta = wire.pack_wire_meta(
        puid="abc", deadline_ms=123.5, traceparent="00-" + "ab" * 16
        + "-" + "cd" * 8 + "-01", tenant="t1", tier="batch",
        extra={"names": ["x", "y"], "kind": "ndarray"},
    )
    f = wire.decode_frame(frame_bytes(a, status=200, response=True,
                                      meta_bytes=meta))
    assert f.is_response and f.status == 200
    assert f.meta["puid"] == "abc"
    assert f.meta["deadline_ms"] == 123.5
    assert f.meta["tenant"] == "t1" and f.meta["tier"] == "batch"
    assert f.extra() == {"names": ["x", "y"], "kind": "ndarray"}
    assert np.array_equal(f.array, a)


def test_codec_scale_plane_roundtrip():
    rows = np.random.default_rng(1).normal(size=(4, 16))
    q, scales = wire.quantize_rows(rows)
    f = wire.decode_frame(frame_bytes(q, scales=scales))
    assert f.scales is not None and f.array.dtype == np.int8
    # int8 quantization is lossy by construction — bounded by one step
    step = np.abs(rows).max(axis=1, keepdims=True) / 127.0
    assert np.all(np.abs(f.rows() - rows) <= step + 1e-7)


def test_codec_multi_roundtrip():
    subs = [frame_bytes(rows4(i)) for i in range(3)]
    f = wire.decode_frame(wire.join_parts(wire.encode_multi(subs)))
    assert f.is_multi and len(f.subframes) == 3
    for i, sub in enumerate(f.subframes):
        assert np.array_equal(wire.decode_frame(sub).array, rows4(i))


def test_codec_typed_errors():
    good = frame_bytes(rows4())
    with pytest.raises(wire.WireError, match="magic"):
        wire.decode_frame(b"XXXX" + good[4:])
    with pytest.raises(wire.WireError, match="version"):
        wire.decode_frame(good[:4] + b"\x09" + good[5:])
    with pytest.raises(wire.WireError, match="truncated"):
        wire.decode_frame(good[:7])            # torn header
    # torn mid-frame: the strict length check names the disagreement
    with pytest.raises(wire.WireError, match="implies|truncated"):
        wire.decode_frame(good[:len(good) // 2])
    # dtype x shape disagreeing with the byte count answers typed (both
    # a short and a long payload)
    with pytest.raises(wire.WireError, match="implies"):
        wire.decode_frame(good[:-4])
    with pytest.raises(wire.WireError, match="implies"):
        wire.decode_frame(good + b"zz")
    # unknown dtype code
    bad_dtype = bytearray(good)
    bad_dtype[6] = 99
    with pytest.raises(wire.WireError, match="dtype"):
        wire.decode_frame(bytes(bad_dtype))
    # over-length: a declared tensor beyond the cap fails 413 BEFORE
    # any allocation — the header claims 2**30 x 1024 f64s
    huge = bytearray(frame_bytes(np.zeros((2, 2))))
    import struct

    struct.pack_into("!II", huge, 14, 2 ** 30, 1024)
    with pytest.raises(wire.WireFrameTooLarge):
        wire.decode_frame(bytes(huge[:14 + 8]) + b"", max_bytes=1 << 20)
    assert wire.WireFrameTooLarge.http_code == 413
    assert wire.WireError.http_code == 400


def test_sidecar_version_rules():
    # FUTURE sidecar version degrades to "no metadata" (forward compat)
    meta = bytearray(wire.pack_wire_meta(puid="abc", tenant="t"))
    meta[0] = 9
    f = wire.decode_frame(frame_bytes(rows4(), meta_bytes=bytes(meta)))
    assert f.meta["puid"] is None and f.meta["tenant"] is None
    # structurally torn sidecar is a typed 400 (corrupt frame)
    torn = wire.pack_wire_meta(puid="abcdef")[:-3]
    with pytest.raises(wire.WireError, match="sidecar"):
        wire.decode_frame(frame_bytes(rows4(), meta_bytes=torn))


def test_message_bridges():
    msg = SeldonMessage.from_json(json_payload(rows4()))
    msg.meta.puid = "pp"
    parts = wire.frame_from_message(msg, sidecar=False)
    back = wire.message_from_frame(wire.decode_frame(wire.join_parts(parts)))
    assert back.meta.puid == "pp"
    assert back.data.kind == "ndarray"
    assert np.array_equal(np.asarray(back.array()), np.asarray(msg.array()))
    # error response frame -> FAILURE message
    err = wire.decode_frame(frame_bytes(
        None, status=503, response=True,
        meta_bytes=wire.pack_wire_meta(extra={"error": "shed"})))
    m = wire.message_from_frame(err)
    assert m.status.status == "FAILURE" and m.status.code == 503
    assert m.status.info == "shed"


def test_copy_accounting_counts_joins():
    before = RECORDER.snapshot()["wire"]["bytes_copied"]
    parts = wire.encode_frame(np.zeros((8, 8)))
    wire.join_parts(parts)
    after = RECORDER.snapshot()["wire"]["bytes_copied"]
    assert after - before >= 8 * 8 * 8  # the join materialized the payload


# ---------------------------------------------------------------------------
# engine object path
# ---------------------------------------------------------------------------


def test_engine_wire_parity_bit_identical():
    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        try:
            for i in range(3):
                x = rows4(i)
                jtext, jstatus = await eng.predict_json(json_payload(x))
                jarr = np.asarray(
                    json.loads(jtext)["data"]["ndarray"], dtype=np.float64)
                status, parts = await eng.predict_wire(frame_bytes(x))
                assert status == 200 and jstatus == 200
                resp = wire.decode_frame(wire.join_parts(parts))
                assert resp.is_response and resp.status == 200
                barr = np.asarray(resp.array, dtype=np.float64)
                assert np.array_equal(jarr, barr)
                # the response sidecar carries the static output names
                assert resp.extra().get("names") == list(eng._static_names)
        finally:
            await eng.close()

    asyncio.run(run())


def test_engine_wire_multi_isolates_torn_sub():
    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        try:
            ok = frame_bytes(rows4(), meta_bytes=wire.pack_wire_meta(
                puid="good"))
            status, parts = await eng.predict_wire(wire.join_parts(
                wire.encode_multi([ok, b"torn-bytes"])))
            assert status == 200
            multi = wire.decode_frame(wire.join_parts(parts))
            subs = [wire.decode_frame(s) for s in multi.subframes]
            assert subs[0].status == 200
            assert subs[0].meta["puid"] == "good"
            assert subs[1].status == 400
            assert "magic" in subs[1].extra()["error"] \
                or "truncated" in subs[1].extra()["error"]
        finally:
            await eng.close()

    asyncio.run(run())


def test_engine_wire_sidecar_binds_deadline_trace_qos():
    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        seen = {}
        orig = eng._submit

        async def spy(rows):
            from seldon_core_tpu.runtime.qos import (
                current_tenant,
                current_tier,
            )
            from seldon_core_tpu.runtime.resilience import remaining_s
            from seldon_core_tpu.utils.tracing import current_trace_context

            seen["tenant"] = current_tenant()
            seen["tier"] = current_tier()
            seen["remaining_s"] = remaining_s()
            ctx = current_trace_context()
            seen["trace_id"] = ctx.trace_id if ctx is not None else None
            return await orig(rows)

        eng._submit = spy
        try:
            tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
            meta = wire.pack_wire_meta(deadline_ms=5000.0, traceparent=tp,
                                       tenant="t-wire", tier="batch")
            status, _parts = await eng.predict_wire(
                frame_bytes(rows4(), meta_bytes=meta))
            assert status == 200
            # the sidecar bound exactly like HTTP headers would:
            # PR-12's relay metadata semantics, wire-native
            assert seen["tenant"] == "t-wire"
            assert seen["tier"] == "batch"
            assert seen["remaining_s"] is not None
            assert 0 < seen["remaining_s"] <= 5.0
            assert seen["trace_id"] == "ab" * 16
        finally:
            await eng.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# fast HTTP lane
# ---------------------------------------------------------------------------


async def _http_round(port, body, ctype, reader=None, writer=None,
                      path="/api/v0.1/predictions"):
    """One request over a (kept-alive) raw connection; returns
    (status, content_type, body, reader, writer)."""
    if writer is None or writer.is_closing():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write((
        "POST %s HTTP/1.1\r\nHost: t\r\nContent-Type: %s\r\n"
        "Content-Length: %d\r\n\r\n" % (path, ctype, len(body))
    ).encode())
    writer.write(body)
    await writer.drain()
    hdr = await reader.readuntil(b"\r\n\r\n")
    status = int(hdr.split(b" ", 2)[1])
    clen = ct = None
    for line in hdr.split(b"\r\n"):
        low = line.lower()
        if low.startswith(b"content-length:"):
            clen = int(line.split(b":", 1)[1])
        elif low.startswith(b"content-type:"):
            ct = line.split(b":", 1)[1].strip().decode()
    resp = await reader.readexactly(clen)
    return status, ct, resp, reader, writer


def test_httpfast_binary_copies_a_quarter_of_json_at_most():
    """The binary lane's reason to exist, as a count and not a timing: over
    the fast HTTP lane a [16, 784] float32 predict copies, by the codec's own
    accounting, at most a quarter of the bytes the JSON lane must (socket
    bytes -> bytes -> str, values materialized as f64, and back — a LOWER
    bound from the measured body sizes)."""
    from seldon_core_tpu.runtime.httpfast import serve_fast

    rows, feats, n = 16, 784, 10

    async def run():
        eng = EngineService(sigmoid_spec(n_features=feats), max_batch=64,
                            max_wait_ms=0.5)
        srv = await serve_fast(eng, "127.0.0.1", 0)
        r = w = None
        try:
            x = np.random.default_rng(7).normal(
                size=(rows, feats)).astype(np.float32)
            jreq = json_payload(x.astype(np.float64)).encode()
            st, _ct, jresp, r, w = await _http_round(
                srv.port, jreq, "application/json")
            assert st == 200
            good = frame_bytes(x)
            st, _ct, _b, r, w = await _http_round(  # warm the lane
                srv.port, good, wire.WIRE_CONTENT_TYPE, r, w)
            assert st == 200
            before = RECORDER.snapshot()["wire"]["bytes_copied"]
            for _ in range(n):
                st, _ct, _b, r, w = await _http_round(
                    srv.port, good, wire.WIRE_CONTENT_TYPE, r, w)
                assert st == 200
            copied = (RECORDER.snapshot()["wire"]["bytes_copied"]
                      - before) / n
            json_copied = 2 * len(jreq) + 8 * rows * feats + 2 * len(jresp)
            assert 0 < copied <= json_copied / 4, (copied, json_copied)
        finally:
            if w is not None:
                w.close()
            await srv.stop()
            await eng.close()

    asyncio.run(run())


def test_httpfast_binary_parity_then_typed_errors_keep_serving():
    from seldon_core_tpu.runtime.httpfast import serve_fast

    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        srv = await serve_fast(eng, "127.0.0.1", 0)
        r = w = None
        try:
            x = rows4(5)
            st, _ct, jbody, r, w = await _http_round(
                srv.port, json_payload(x).encode(), "application/json")
            jarr = np.asarray(json.loads(jbody)["data"]["ndarray"])
            good = frame_bytes(x)
            st, ct, bbody, r, w = await _http_round(
                srv.port, good, wire.WIRE_CONTENT_TYPE, r, w)
            assert st == 200 and ct == wire.WIRE_CONTENT_TYPE
            barr = np.asarray(
                wire.decode_frame(bbody).array, dtype=np.float64)
            assert np.array_equal(jarr, barr)
            # torn frames answer typed 400s on the SAME connection...
            for bad in (b"XXXX" + good[4:], good[:9], good[:-3]):
                st, ct, body, r, w = await _http_round(
                    srv.port, bad, wire.WIRE_CONTENT_TYPE, r, w)
                assert st == 400, body
                assert json.loads(body)["status"]["status"] == "FAILURE"
            # ...and the connection still serves afterwards
            st, _ct, body, r, w = await _http_round(
                srv.port, good, wire.WIRE_CONTENT_TYPE, r, w)
            assert st == 200
            # an over-length DECLARED tensor answers a typed 413
            import struct

            huge = bytearray(good)
            struct.pack_into("!II", huge, 14, 2 ** 30, 1024)
            st, _ct, body, r, w = await _http_round(
                srv.port, bytes(huge), wire.WIRE_CONTENT_TYPE, r, w)
            assert st == 413, body
            assert json.loads(body)["status"]["code"] == 413
        finally:
            if w is not None:
                w.close()
            await srv.stop()
            await eng.close()

    asyncio.run(run())


def test_httpfast_mid_frame_disconnect_keeps_server_alive():
    from seldon_core_tpu.runtime.httpfast import serve_fast

    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        srv = await serve_fast(eng, "127.0.0.1", 0)
        try:
            good = frame_bytes(rows4())
            # announce a full frame, send half, hang up mid-frame
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", srv.port)
            writer.write((
                "POST /api/v0.1/predictions HTTP/1.1\r\nHost: t\r\n"
                "Content-Type: %s\r\nContent-Length: %d\r\n\r\n"
                % (wire.WIRE_CONTENT_TYPE, len(good))
            ).encode())
            writer.write(good[:len(good) // 2])
            await writer.drain()
            writer.close()
            await asyncio.sleep(0.05)
            # the server neither crashed nor hung: a fresh connection
            # serves normally
            st, _ct, _body, r2, w2 = await _http_round(
                srv.port, good, wire.WIRE_CONTENT_TYPE)
            assert st == 200
            w2.close()
        finally:
            await srv.stop()
            await eng.close()

    asyncio.run(run())


def test_httpfast_kill_switch_answers_415(monkeypatch):
    from seldon_core_tpu.runtime.httpfast import serve_fast

    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        srv = await serve_fast(eng, "127.0.0.1", 0)
        try:
            monkeypatch.setenv("SELDON_TPU_WIRE", "0")
            st, ct, body, r, w = await _http_round(
                srv.port, frame_bytes(rows4()), wire.WIRE_CONTENT_TYPE)
            assert st == 415
            assert json.loads(body)["status"]["code"] == 415
            # JSON unaffected — the kill switch restores the JSON path
            st, _ct, _body, r, w = await _http_round(
                srv.port, json_payload(rows4()).encode(),
                "application/json", r, w)
            assert st == 200
            w.close()
        finally:
            await srv.stop()
            await eng.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# aiohttp REST lane
# ---------------------------------------------------------------------------


def test_rest_aiohttp_binary_parity():
    import aiohttp

    from seldon_core_tpu.runtime.rest import make_engine_app, serve_app

    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        runner = await serve_app(make_engine_app(eng), "127.0.0.1", 0)
        port = runner.addresses[0][1]
        try:
            x = rows4(2)
            async with aiohttp.ClientSession() as sess:
                async with sess.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    data=json_payload(x),
                    headers={"Content-Type": "application/json"},
                ) as r:
                    jarr = np.asarray(
                        (await r.json())["data"]["ndarray"])
                async with sess.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    data=frame_bytes(x),
                    headers={"Content-Type": wire.WIRE_CONTENT_TYPE},
                ) as r:
                    assert r.status == 200
                    assert r.content_type == wire.WIRE_CONTENT_TYPE
                    resp = wire.decode_frame(await r.read())
                assert np.array_equal(
                    jarr, np.asarray(resp.array, dtype=np.float64))
                # torn frame: typed 400 as JSON the peer can always read
                async with sess.post(
                    f"http://127.0.0.1:{port}/api/v0.1/predictions",
                    data=b"garbage",
                    headers={"Content-Type": wire.WIRE_CONTENT_TYPE},
                ) as r:
                    assert r.status == 400
                    assert (await r.json())["status"]["status"] == "FAILURE"
        finally:
            await runner.cleanup()
            await eng.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# framed relay lane
# ---------------------------------------------------------------------------


def test_relay_op_wire_parity(tmp_path):
    from seldon_core_tpu.runtime.udsrelay import (
        OP_WIRE,
        UdsRelayClient,
        serve_uds,
    )

    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        server = await serve_uds(eng, str(tmp_path / "w.sock"))
        client = UdsRelayClient(str(tmp_path / "w.sock"))
        try:
            x = rows4(3)
            jtext, _ = await eng.predict_json(json_payload(x))
            jarr = np.asarray(json.loads(jtext)["data"]["ndarray"])
            body, status = await client.call(OP_WIRE, frame_bytes(x))
            assert status == 200
            barr = np.asarray(
                wire.decode_frame(body).array, dtype=np.float64)
            assert np.array_equal(jarr, barr)
            # torn frame: typed 400 rides the relay status head
            body, status = await client.call(OP_WIRE, b"nonsense")
            assert status == 400
        finally:
            await client.close()
            await server.stop()
            await eng.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# gateway: ingress, dispatch, coalescer, kill switch
# ---------------------------------------------------------------------------


def _gateway_over_uds(tmp_path):
    from seldon_core_tpu.gateway.apife import ApiGateway, DeploymentStore
    from seldon_core_tpu.runtime.udsrelay import serve_uds

    async def boot():
        spec = sigmoid_spec()
        eng = EngineService(spec, max_batch=32, max_wait_ms=0.5)
        relay = await serve_uds(eng, str(tmp_path / "gw.sock"))
        store = DeploymentStore()
        store.register(spec, {"p": "uds:" + str(tmp_path / "gw.sock")})
        gw = ApiGateway(store=store, require_auth=False)
        return eng, relay, gw

    return boot


def test_gateway_ingress_binary_end_to_end(tmp_path, monkeypatch):
    import aiohttp
    from aiohttp import web

    from seldon_core_tpu.gateway.apife import make_gateway_app

    async def run():
        eng, relay, gw = await _gateway_over_uds(tmp_path)()
        runner = web.AppRunner(make_gateway_app(gw), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        monkeypatch.setenv("SELDON_TPU_WIRE_COALESCE_US", "0")
        try:
            x = rows4(9)
            async with aiohttp.ClientSession() as sess:
                url = f"http://127.0.0.1:{port}/api/v0.1/predictions"
                async with sess.post(
                    url, data=json_payload(x),
                    headers={"Content-Type": "application/json"},
                ) as r:
                    jarr = np.asarray((await r.json())["data"]["ndarray"])
                meta = wire.pack_wire_meta(tenant="ing-t", tier="batch")
                async with sess.post(
                    url, data=frame_bytes(x, meta_bytes=meta),
                    headers={"Content-Type": wire.WIRE_CONTENT_TYPE},
                ) as r:
                    assert r.status == 200
                    assert r.content_type == wire.WIRE_CONTENT_TYPE
                    resp = wire.decode_frame(await r.read())
                assert np.array_equal(
                    jarr, np.asarray(resp.array, dtype=np.float64))
                # the sidecar tenant reached the gateway's accounting
                assert "ing-t" in gw.tenants.snapshot()["tenants"]
                # torn ingress frame: typed 400
                async with sess.post(
                    url, data=b"junk",
                    headers={"Content-Type": wire.WIRE_CONTENT_TYPE},
                ) as r:
                    assert r.status == 400
                # kill switch: typed 415 at ingress
                monkeypatch.setenv("SELDON_TPU_WIRE", "0")
                async with sess.post(
                    url, data=frame_bytes(x),
                    headers={"Content-Type": wire.WIRE_CONTENT_TYPE},
                ) as r:
                    assert r.status == 415
        finally:
            await runner.cleanup()
            await gw.close()
            await relay.stop()
            await eng.close()

    asyncio.run(run())


def test_gateway_uds_dispatch_parity_and_kill_switch(tmp_path, monkeypatch):
    async def run():
        eng, relay, gw = await _gateway_over_uds(tmp_path)()
        monkeypatch.setenv("SELDON_TPU_WIRE_COALESCE_US", "0")
        try:
            for i in range(3):
                x = rows4(20 + i)
                monkeypatch.setenv("SELDON_TPU_WIRE", "0")
                before = RECORDER.snapshot()["wire"]["requests"]
                jr = await gw.predict(
                    SeldonMessage.from_json(json_payload(x)))
                after = RECORDER.snapshot()["wire"]["requests"]
                # kill switch: no binary dispatch happened
                assert after.get("dispatch-uds/binary", 0) == \
                    before.get("dispatch-uds/binary", 0)
                monkeypatch.setenv("SELDON_TPU_WIRE", "1")
                br = await gw.predict(
                    SeldonMessage.from_json(json_payload(x)))
                assert np.array_equal(
                    np.asarray(jr.array()), np.asarray(br.array()))
            after = RECORDER.snapshot()["wire"]["requests"]
            assert after.get("dispatch-uds/binary", 0) >= 3
        finally:
            await gw.close()
            await relay.stop()
            await eng.close()

    asyncio.run(run())


def test_gateway_coalescer_rides_fewer_frames(tmp_path, monkeypatch):
    async def run():
        eng, relay, gw = await _gateway_over_uds(tmp_path)()
        monkeypatch.setenv("SELDON_TPU_WIRE_COALESCE_US", "5000")
        try:
            X = rows4(31, n=8)
            before = RECORDER.snapshot()["wire"]
            resps = await asyncio.gather(*(
                gw.predict(SeldonMessage.from_array(X[i:i + 1]))
                for i in range(8)
            ))
            after = RECORDER.snapshot()["wire"]
            for r in resps:
                assert r.status is None or r.status.status == "SUCCESS"
            # every response matches ITS request (de-coalescing cannot
            # cross wires): recompute sequentially and compare
            for i, r in enumerate(resps):
                solo = await gw.predict(SeldonMessage.from_array(X[i:i + 1]))
                assert np.allclose(
                    np.asarray(r.array()), np.asarray(solo.array()),
                    atol=1e-5,
                )
            coalesced = after["coalesced"] - before["coalesced"]
            frames = (after["requests"].get("relay/binary", 0)
                      - before["requests"].get("relay/binary", 0))
            assert coalesced >= 2
            assert frames < 8  # fewer relay hops than requests
        finally:
            await gw.close()
            await relay.stop()
            await eng.close()

    asyncio.run(run())


def test_gateway_coalesced_error_isolated_per_slot(tmp_path, monkeypatch):
    """One sub-request with a payload the graph rejects answers ITS
    caller typed; co-travellers in the same coalesced frame stay green."""
    async def run():
        eng, relay, gw = await _gateway_over_uds(tmp_path)()
        monkeypatch.setenv("SELDON_TPU_WIRE_COALESCE_US", "5000")
        try:
            good = SeldonMessage.from_array(rows4(40))
            bad = SeldonMessage.from_array(
                np.zeros((1, 9)))  # wrong feature width
            rg, rb = await asyncio.gather(gw.predict(good),
                                          gw.predict(bad))
            assert rg.status is None or rg.status.status == "SUCCESS"
            assert rb.status is not None and rb.status.status == "FAILURE"
        finally:
            await gw.close()
            await relay.stop()
            await eng.close()

    asyncio.run(run())


def test_gateway_uds_negotiates_down_from_pre_wire_relay(tmp_path,
                                                         monkeypatch):
    """A PRE-WIRE engine build answers OP_WIRE with the unknown-op 400 —
    the gateway must negotiate the socket down to JSON and serve, not
    fail every numeric predict for its lifetime (rolling upgrades)."""
    from seldon_core_tpu.runtime import udsrelay

    orig_handle = udsrelay._UdsServerProtocol._handle

    async def pre_wire_handle(self, op, data, meta=None):
        if op == udsrelay.OP_WIRE:
            return 400, SeldonMessage.failure(
                f"unknown relay op {op}", code=400
            ).to_json().encode()
        return await orig_handle(self, op, data, meta)

    monkeypatch.setattr(
        udsrelay._UdsServerProtocol, "_handle", pre_wire_handle)
    # a COALESCED burst must negotiate down too — the multi response is
    # the same non-frame 400 body, fanned to every slot
    monkeypatch.setenv("SELDON_TPU_WIRE_COALESCE_US", "5000")

    async def run():
        eng, relay, gw = await _gateway_over_uds(tmp_path)()
        try:
            resps = await asyncio.gather(*(
                gw.predict(SeldonMessage.from_array(rows4(60 + i)))
                for i in range(4)
            ))
            for r in resps:
                assert r.status is None or r.status.status == "SUCCESS"
            assert str(tmp_path / "gw.sock") in gw._wire_json_only
            # and it STAYS on JSON (no per-call re-probe)
            resp2 = await gw.predict(SeldonMessage.from_array(rows4(69)))
            assert resp2.status is None or resp2.status.status == "SUCCESS"
        finally:
            await gw.close()
            await relay.stop()
            await eng.close()

    asyncio.run(run())


def test_engine_wire_multi_isolates_unexpected_exception():
    """A slot whose model raises an UNEXPECTED exception (not a typed
    SeldonMessageError) answers ITS slot 500; co-travellers stay 200."""
    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        orig = eng._submit

        async def submit(rows):
            if float(np.asarray(rows)[0, 0]) == 999.0:
                raise RuntimeError("model exploded")
            return await orig(rows)

        eng._submit = submit
        try:
            good = frame_bytes(rows4(70), meta_bytes=wire.pack_wire_meta(
                puid="ok"))
            bad_rows = rows4(71).copy()
            bad_rows[0, 0] = 999.0
            bad = frame_bytes(bad_rows, meta_bytes=wire.pack_wire_meta(
                puid="boom"))
            status, parts = await eng.predict_wire(wire.join_parts(
                wire.encode_multi([good, bad])))
            assert status == 200
            subs = [wire.decode_frame(s) for s in wire.decode_frame(
                wire.join_parts(parts)).subframes]
            assert subs[0].status == 200
            assert subs[1].status == 500
            assert "model exploded" in subs[1].extra()["error"]
            assert subs[1].meta["puid"] == "boom"
        finally:
            await eng.close()

    asyncio.run(run())


def test_gateway_tcp_dispatch_binary_and_negotiation(monkeypatch):
    """The TCP lane speaks frames to a wire-capable engine and
    negotiates PERMANENTLY down to JSON against a peer that declines."""
    from aiohttp import web

    from seldon_core_tpu.gateway.apife import ApiGateway, DeploymentStore
    from seldon_core_tpu.runtime.httpfast import serve_fast

    async def run():
        spec = sigmoid_spec()
        eng = EngineService(spec, max_batch=8, max_wait_ms=0.5)
        srv = await serve_fast(eng, "127.0.0.1", 0)

        async def json_only(request):
            from seldon_core_tpu.runtime.rest import _payload_text

            try:
                msg = SeldonMessage.from_json(await _payload_text(request))
            except Exception:  # noqa: BLE001
                return web.Response(status=400, text="no",
                                    content_type="text/plain")
            return web.Response(text=msg.to_json(),
                                content_type="application/json")

        app = web.Application()
        app.router.add_post("/api/v0.1/predictions", json_only)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        stub_port = runner.addresses[0][1]

        store = DeploymentStore()
        store.register(spec, {"p": f"http://127.0.0.1:{srv.port}"})
        gw = ApiGateway(store=store, require_auth=False)
        stub_spec = sigmoid_spec(name="stub-dep")
        store2 = DeploymentStore()
        store2.register(stub_spec, {"p": f"http://127.0.0.1:{stub_port}"})
        gw2 = ApiGateway(store=store2, require_auth=False)
        try:
            x = rows4(50)
            before = RECORDER.snapshot()["wire"]["requests"]
            br = await gw.predict(SeldonMessage.from_array(x))
            after = RECORDER.snapshot()["wire"]["requests"]
            assert br.status is None or br.status.status == "SUCCESS"
            assert after.get("dispatch-tcp/binary", 0) > \
                before.get("dispatch-tcp/binary", 0)
            # parity vs the direct JSON object path
            jtext, _ = await eng.predict_json(json_payload(x))
            assert np.array_equal(
                np.asarray(json.loads(jtext)["data"]["ndarray"]),
                np.asarray(br.array(), dtype=np.float64))
            # JSON-only peer: the call still lands, the url is
            # remembered as json-only
            echoed = await gw2.predict(SeldonMessage.from_array(x))
            assert echoed.status is None \
                or echoed.status.status == "SUCCESS"
            assert f"http://127.0.0.1:{stub_port}" in gw2._wire_json_only
        finally:
            await gw.close()
            await gw2.close()
            await runner.cleanup()
            await srv.stop()
            await eng.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# node-mesh client
# ---------------------------------------------------------------------------


def test_node_client_binary_parity_and_fallback():
    from aiohttp import web

    from seldon_core_tpu.graph.spec import ComponentBinding, PredictiveUnit, UnitType
    from seldon_core_tpu.runtime.client import RestNodeRuntime
    from seldon_core_tpu.runtime.httpfast import serve_fast

    async def run():
        eng = EngineService(sigmoid_spec(), max_batch=8, max_wait_ms=0.5)
        srv = await serve_fast(eng, "127.0.0.1", 0)
        node = PredictiveUnit(name="m", type=UnitType.MODEL)
        rt = RestNodeRuntime(node, ComponentBinding(
            name="m", runtime="rest", host="127.0.0.1", port=srv.port))
        rt_json = RestNodeRuntime(node, ComponentBinding(
            name="m", runtime="rest", host="127.0.0.1", port=srv.port))
        rt_json._wire_ok = False

        # a JSON-only peer (the unit-microservice shape): /predict
        # parses JSON (raw or the form-encoded ``json=`` convention)
        # and 400s binary bodies
        from seldon_core_tpu.runtime.rest import _payload_text

        async def json_only(request):
            try:
                msg = SeldonMessage.from_json(await _payload_text(request))
            except Exception:  # noqa: BLE001
                return web.Response(
                    status=400, text="not json",
                    content_type="text/plain")
            return web.Response(
                text=msg.to_json(), content_type="application/json")

        app = web.Application()
        app.router.add_post("/predict", json_only)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        stub_port = runner.addresses[0][1]
        rt_stub = RestNodeRuntime(node, ComponentBinding(
            name="m", runtime="rest", host="127.0.0.1", port=stub_port))
        try:
            x = rows4(11)
            msg = SeldonMessage.from_array(x)
            out_bin = await rt.predict(msg)
            out_json = await rt_json.predict(SeldonMessage.from_array(x))
            assert np.array_equal(np.asarray(out_bin.array()),
                                  np.asarray(out_json.array()))
            # against the JSON-only peer the binary attempt negotiates
            # down, the call still succeeds, and the lane is remembered
            echoed = await rt_stub.predict(SeldonMessage.from_array(x))
            assert np.allclose(np.asarray(echoed.array()), x)
            assert rt_stub._wire_ok is False
        finally:
            await rt.close()
            await rt_json.close()
            await rt_stub.close()
            await runner.cleanup()
            await srv.stop()
            await eng.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# metric families
# ---------------------------------------------------------------------------


def test_wire_metric_families_exported():
    RECORDER.record_wire_request("ingress", "binary")
    RECORDER.record_wire_copy(64)
    RECORDER.record_wire_coalesced(2)
    exp = RECORDER.exposition().decode()
    assert 'seldon_tpu_wire_requests_total{format="binary",lane="ingress"}' \
        in exp or "seldon_tpu_wire_requests_total" in exp
    assert "seldon_tpu_wire_bytes_copied_total" in exp
    assert "seldon_tpu_wire_coalesced_total" in exp
    snap = RECORDER.snapshot()["wire"]
    assert snap["requests"].get("ingress/binary", 0) >= 1
    assert snap["bytes_copied"] >= 64
    assert snap["coalesced"] >= 2
