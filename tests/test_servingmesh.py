"""Disaggregated prefill/decode serving mesh (runtime/servingmesh.py +
runtime/kvstream.py + the genserver role/import machinery).

The load-bearing contracts:

* disaggregated generation (prefill replica -> KV-block stream over the
  relay -> decode replica) is TOKEN-IDENTICAL to the unified scheduler
  for the same seeds — greedy f32 and the int8-KV arm;
* a torn handoff reclaims every reserved block (pool occupancy returns
  to baseline; the TTL reaper covers a sender that just vanishes);
* role misconfigs answer typed 503s (generation at a decode-only
  replica, a handoff at a non-decode replica, prefill with no peers);
* ``SELDON_TPU_DISAGG=0`` restores the unified path bit-for-bit;
* reserved import blocks can never be picked as eviction victims and
  pinned shared-prefix blocks can never be freed;
* tensor-parallel dispatch: the scheduler's compiled executables over a
  ≥2-device mesh produce the same tokens as the single-device path.
"""

import asyncio
import json
import os
import tempfile
import threading
import time
import uuid

import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import LoadShedError
from seldon_core_tpu.models.generate import TransformerGenerator
from seldon_core_tpu.runtime import kvstream
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.genserver import BlockAllocator, GenServer
from seldon_core_tpu.runtime.servingmesh import (
    DisaggCoordinator,
    HandoffError,
    RoleMismatchError,
    resolve_gen_role,
)
from seldon_core_tpu.runtime.udsrelay import serve_uds


def _unit(**overrides):
    kw = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
              max_new_tokens=16, dtype="float32", eos_token=-1)
    kw.update(overrides)
    return TransformerGenerator(**kw)


def _genserver(unit=None, role="unified", coordinator=None, **kw):
    unit = unit or _unit()
    state = unit.init_state(None)
    cs = unit.continuous_spec(state)
    defaults = dict(num_blocks=64, block_size=4, span=4, prefill_chunk=8)
    defaults.update(kw)
    return GenServer(**cs, role=role, coordinator=coordinator, **defaults)


class LoopbackCoordinator:
    """In-process handoff driver that still exercises the REAL wire
    format (serialize -> parse on every frame) against a decode
    GenServer — the relay minus the socket."""

    def __init__(self, decode_gs, chunk=2):
        self.decode = decode_gs
        self.chunk = chunk

    def submit(self, export, done_cb):
        threading.Thread(
            target=self._run, args=(export, done_cb), daemon=True
        ).start()

    def _run(self, export, done_cb):
        hid = uuid.uuid4().bytes
        try:
            _, h, body = kvstream.parse_frame(
                kvstream.begin_frame(export, hid))
            self.decode.kv_reserve(h, kvstream.parse_begin(body))
            for fr in kvstream.block_frames(export, hid, self.chunk):
                _, h2, b2 = kvstream.parse_frame(fr)
                imp = self.decode._imports[h2]
                first, layers = kvstream.parse_blocks(b2, imp.meta)
                self.decode.kv_receive(h2, first, layers)
            req = self.decode.kv_commit(h)
            done_cb(np.asarray(req.future.result(timeout=120))[0])
        except BaseException as e:  # noqa: BLE001 - surfaced per request
            done_cb(e)

    def close(self):
        pass

    def snapshot(self):
        return {"loopback": True}

    def chain_estimate_s(self):
        return None


_PROMPT = (np.arange(22) % 13 + 1).reshape(1, -1)


def _wait_blocks_freed(gs, timeout_s=10.0):
    """Assert every KV block recycles, tolerating the retire step that
    may run a scheduler tick after the request future resolves."""
    deadline = time.monotonic() + timeout_s
    while gs._allocator.used != 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert gs._allocator.used == 0


# -- export/import round trip -------------------------------------------

@pytest.mark.parametrize("unit_kw,row", [
    ({}, (2, 16)),
    # two KV heads of 64 ride one 128-lane row of either pool; the wire
    # states the model's heads (models/generate.py init_block_pool)
    ({"head_dim": 64}, (1, 128)),
], ids=["hd16", "hd64-paired"])
def test_disagg_token_identical_greedy_f32(unit_kw, row):
    unified = _genserver(_unit(**unit_kw))
    decode = _genserver(_unit(**unit_kw), role="decode")
    prefill = _genserver(_unit(**unit_kw), role="prefill",
                         coordinator=LoopbackCoordinator(decode))
    try:
        decode._ensure_device()
        assert decode._pool["l0"]["k"].shape[2:] == row
        y0 = unified.submit(_PROMPT).future.result(timeout=120)
        y1 = prefill.submit(_PROMPT).future.result(timeout=120)
        np.testing.assert_array_equal(y0, y1)
        assert prefill.retired_total.get("handoff") == 1
        assert decode.imports_committed_total == 1
        # block recycling: the client future can resolve from the
        # scheduler's per-step delivery a tick BEFORE _retire_finished
        # releases the sequence's blocks — poll briefly instead of
        # racing the scheduler thread (flaked under full-suite load)
        _wait_blocks_freed(prefill)
        _wait_blocks_freed(decode)  # retired decode freed them
    finally:
        unified.stop()
        prefill.stop()
        decode.stop()


def test_disagg_token_identical_int8_kv():
    unit_kw = dict(kv_quant="int8")
    unified = _genserver(_unit(**unit_kw))
    decode = _genserver(_unit(**unit_kw), role="decode")
    prefill = _genserver(
        _unit(**unit_kw), role="prefill",
        coordinator=LoopbackCoordinator(decode))
    try:
        y0 = unified.submit(_PROMPT).future.result(timeout=120)
        y1 = prefill.submit(_PROMPT).future.result(timeout=120)
        np.testing.assert_array_equal(y0, y1)
        assert decode.imports_committed_total == 1
    finally:
        unified.stop()
        prefill.stop()
        decode.stop()


def test_disagg_multi_request_streams_match_unified():
    """Several co-scheduled requests hand off independently and every
    stream concatenates to the unified answer."""
    unified = _genserver()
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill",
                         coordinator=LoopbackCoordinator(decode))
    try:
        prompts = [(np.arange(10 + 3 * i) % 17 + 1).reshape(1, -1)
                   for i in range(3)]
        want = [unified.submit(p).future.result(timeout=120)
                for p in prompts]
        reqs = [prefill.submit(p) for p in prompts]
        got = [r.future.result(timeout=120) for r in reqs]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert prefill.retired_total.get("handoff") == 3
    finally:
        unified.stop()
        prefill.stop()
        decode.stop()


# -- torn handoffs -------------------------------------------------------

def _export_for(gs, prompt):
    """Run a prefill-role GenServer up to the export (capturing it
    instead of handing off) — gives tests a real KvExport plus the
    pending request and the completion callback."""
    captured = {}

    class Capture:
        def submit(self, export, done_cb):
            captured["export"] = export
            captured["done"] = done_cb

        def close(self):
            pass

        def snapshot(self):
            return {}

        def chain_estimate_s(self):
            return None

    gs.coordinator = Capture()
    captured["req"] = gs.submit(prompt)
    deadline = time.monotonic() + 60
    while "export" not in captured and time.monotonic() < deadline:
        time.sleep(0.01)
    assert "export" in captured, "prefill never exported"
    return captured


def test_a_handed_off_prompt_holds_no_slot_of_the_prefill_replica():
    """A prefill replica never decodes, so a finished prompt needs no entry
    of the decode carry once its first token is picked: the slot goes back
    with the blocks at the hand-off, and outstanding hand-offs -- however
    many, however slow the decode side -- never stall admission."""
    held = []

    class Hold:
        def submit(self, export, done_cb):
            held.append((export, done_cb))

        def close(self):
            pass

        def snapshot(self):
            return {}

        def chain_estimate_s(self):
            return None

    prefill = _genserver(role="prefill", coordinator=Hold(), slots=2)
    try:
        reqs = [prefill.submit(_PROMPT + i) for i in range(5)]
        deadline = time.monotonic() + 60
        while len(held) < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(held) == 5       # all five exported, none handed back
        _wait_blocks_freed(prefill)
        assert len(prefill._slot_free) == 2
        for export, done in held:
            done(np.arange(16))
        for req in reqs:
            assert np.asarray(req.future.result(timeout=60)).shape == (1, 16)
    finally:
        prefill.stop()


def test_torn_handoff_reclaims_all_blocks():
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill")
    try:
        captured = _export_for(prefill, _PROMPT)
        export = captured["export"]
        hid = uuid.uuid4().bytes
        decode.kv_reserve(hid, export.meta)
        snap = decode._allocator.snapshot()
        assert snap["reserved"] == export.meta.n_blocks
        baseline_used = snap["used"] - snap["reserved"]
        # stream ONE chunk, then tear the handoff
        frame = next(iter(kvstream.block_frames(export, hid, 2)))
        _, h2, b2 = kvstream.parse_frame(frame)
        first, layers = kvstream.parse_blocks(b2, export.meta)
        decode.kv_receive(hid, first, layers)
        assert decode.kv_abort(hid) is True
        snap = decode._allocator.snapshot()
        assert snap["reserved"] == 0
        assert snap["used"] == baseline_used  # zero leaked blocks
        assert decode.imports_reclaimed_total == 1
        # the abandoned prefill request fails typed + retryable once the
        # coordinator reports the tear back
        captured["done"](HandoffError("torn mid-stream"))
        with pytest.raises(HandoffError):
            captured["req"].future.result(timeout=60)
    finally:
        prefill.stop()
        decode.stop()


def test_commit_before_all_blocks_is_torn_and_reclaims():
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill")
    try:
        export = _export_for(prefill, _PROMPT)["export"]
        hid = uuid.uuid4().bytes
        decode.kv_reserve(hid, export.meta)
        with pytest.raises(kvstream.KvWireError, match="torn"):
            decode.kv_commit(hid)
        assert decode._allocator.snapshot()["reserved"] == 0
        assert decode.imports_reclaimed_total == 1
    finally:
        prefill.stop()
        decode.stop()


def test_ttl_reaper_reclaims_stale_import():
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill")
    try:
        export = _export_for(prefill, _PROMPT)["export"]
        hid = uuid.uuid4().bytes
        decode.kv_reserve(hid, export.meta)
        decode._import_ttl_s = 0.05  # shrink the TTL for the test
        baseline = decode._allocator.snapshot()
        assert baseline["reserved"] > 0
        time.sleep(0.1)
        # any traffic tick runs the reaper; poke the scheduler directly
        with decode._wake:
            decode._wake.notify_all()
        deadline = time.monotonic() + 10
        while decode.imports_reclaimed_total == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        snap = decode._allocator.snapshot()
        assert decode.imports_reclaimed_total == 1
        assert snap["reserved"] == 0
        assert snap["used"] == 0  # high-water only; occupancy back
    finally:
        prefill.stop()
        decode.stop()


def test_commit_racing_ttl_reap_answers_typed_not_corrupt():
    """A COMMIT landing after the reaper reclaimed the reservation must
    answer 'unknown or expired' — never admit a sequence onto blocks
    that went back to the free list (the claim is an atomic pop)."""
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill")
    try:
        export = _export_for(prefill, _PROMPT)["export"]
        hid = uuid.uuid4().bytes
        decode.kv_reserve(hid, export.meta)
        for fr in kvstream.block_frames(export, hid, 2):
            _, h2, b2 = kvstream.parse_frame(fr)
            first, layers = kvstream.parse_blocks(b2, export.meta)
            decode.kv_receive(h2, first, layers)
        # the reaper wins the race (simulated: same pop-first claim)
        imp = decode._imports.pop(hid)
        decode._allocator.release_reserved(imp.blocks)
        with pytest.raises(kvstream.KvWireError, match="unknown"):
            decode.kv_commit(hid)
        assert not decode._remote_arrivals
    finally:
        prefill.stop()
        decode.stop()


def test_stop_fails_requests_with_handoff_in_flight():
    """A request whose handoff sits at the coordinator when the
    scheduler stops must fail typed — not hang its awaiting client
    forever (it lives in no scheduler list)."""
    prefill = _genserver(role="prefill")
    captured = _export_for(prefill, _PROMPT)  # handoff parked, never done
    prefill.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        captured["req"].future.result(timeout=30)


def test_fail_all_releases_committed_import_reservations():
    """A committed-but-not-yet-admitted import still holds RESERVED
    blocks; a scheduler failure between commit and admission must
    release them (a leak here shrinks the pool permanently)."""
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill")
    try:
        export = _export_for(prefill, _PROMPT)["export"]
        hid = uuid.uuid4().bytes
        decode.kv_reserve(hid, export.meta)
        for fr in kvstream.block_frames(export, hid, 2):
            _, h2, b2 = kvstream.parse_frame(fr)
            first, layers = kvstream.parse_blocks(b2, export.meta)
            decode.kv_receive(h2, first, layers)
        req = decode.kv_commit(hid)
        # simulate a tick failure before _import_admit ran: grab the
        # committed import back out of the arrivals queue first so the
        # scheduler can't admit it under us
        deadline = time.monotonic() + 30
        while decode._remote_arrivals and time.monotonic() < deadline:
            decode._fail_all(RuntimeError("boom"))
            break
        # whether _fail_all or admission won, no reservation may remain
        deadline = time.monotonic() + 30
        while (decode._allocator.snapshot()["reserved"] > 0
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert decode._allocator.snapshot()["reserved"] == 0
        # and the request surface resolved one way or the other
        try:
            req.future.result(timeout=60)
        except RuntimeError:
            pass
    finally:
        prefill.stop()
        decode.stop()


# -- role misconfig / kill switch ---------------------------------------

def test_generation_at_decode_replica_is_typed_503():
    decode = _genserver(role="decode")
    try:
        with pytest.raises(RoleMismatchError) as ei:
            decode.submit(_PROMPT)
        assert ei.value.http_code == 503
    finally:
        decode.stop()


def test_prefill_without_peers_fails_typed():
    prefill = _genserver(role="prefill")  # no coordinator
    try:
        req = prefill.submit(_PROMPT)
        with pytest.raises(HandoffError):
            req.future.result(timeout=60)
    finally:
        prefill.stop()


def test_kill_switch_forces_unified_role(monkeypatch):
    monkeypatch.setenv("SELDON_TPU_DISAGG", "0")
    assert resolve_gen_role("prefill") == "unified"
    assert resolve_gen_role("decode") == "unified"
    monkeypatch.delenv("SELDON_TPU_DISAGG")
    assert resolve_gen_role("prefill") == "prefill"


# -- allocator audit (satellite: pin vs eviction vs import) -------------

def test_reserved_blocks_refused_by_free_and_invisible_to_eviction():
    alloc = BlockAllocator(16)
    owned = alloc.alloc(5)
    reserved = alloc.reserve(4)
    # free() must refuse reserved ids — a confused caller cannot return
    # an in-flight import's blocks to the pool
    alloc.free(reserved)
    assert alloc.snapshot()["reserved"] == 4
    assert alloc.used == 9
    # a full drain of owned blocks leaves the reservation intact
    alloc.free(owned)
    assert alloc.used == 4
    got = alloc.alloc(11)
    assert got is not None and not set(got) & set(reserved)
    alloc.free(got)
    alloc.release_reserved(reserved)
    assert alloc.used == 0
    # double release is harmless
    alloc.release_reserved(reserved)
    assert alloc.used == 0


def test_pinned_blocks_never_freed():
    alloc = BlockAllocator(8)
    blocks = alloc.alloc(3)
    alloc.pin(blocks[:2])
    alloc.free(blocks)
    # the two pinned blocks stay resident forever
    assert alloc.used == 2
    assert alloc.snapshot()["pinned"] == 2


def test_eviction_pressure_never_touches_reserved_import():
    """A decode replica under pool pressure (local sequences evicting
    each other) must never reclaim an in-flight import's reservation —
    the committed sequence decodes token-identically afterwards."""
    unified = _genserver(num_blocks=20)
    decode = _genserver(role="decode", num_blocks=20, slots=2)
    prefill = _genserver(role="prefill", num_blocks=20)
    try:
        want = unified.submit(_PROMPT).future.result(timeout=120)
        export = _export_for(prefill, _PROMPT)["export"]
        hid = uuid.uuid4().bytes
        decode.kv_reserve(hid, export.meta)
        reserved = set(decode._imports[hid].blocks)
        # churn the decode replica's own pool around the reservation:
        # these long generations force eviction pressure in a 19-block
        # pool missing 6 reserved blocks
        churn = [(np.arange(12) % 7 + 1).reshape(1, -1) for _ in range(3)]
        churn_reqs = [decode_submit_local(decode, p) for p in churn]
        for r in churn_reqs:
            r.future.result(timeout=120)
        assert set(decode._imports[hid].blocks) == reserved
        assert decode._allocator.snapshot()["reserved"] == len(reserved)
        # now finish the import: content untouched => tokens identical
        for fr in kvstream.block_frames(export, hid, 2):
            _, h2, b2 = kvstream.parse_frame(fr)
            first, layers = kvstream.parse_blocks(b2, export.meta)
            decode.kv_receive(h2, first, layers)
        req = decode.kv_commit(hid)
        got = np.asarray(req.future.result(timeout=120))
        np.testing.assert_array_equal(want, got)
    finally:
        unified.stop()
        prefill.stop()
        decode.stop()


def decode_submit_local(decode_gs, prompt):
    """Bypass the decode-role guard for test churn traffic: the guard is
    a routing contract, not a scheduler limitation."""
    real_role = decode_gs.role
    decode_gs.role = "unified"
    try:
        return decode_gs.submit(prompt)
    finally:
        decode_gs.role = real_role


# -- the wire format -----------------------------------------------------

def test_wire_roundtrip_preserves_meta_and_tensors():
    meta = kvstream.KvBeginMeta(
        n_layers=2, block_size=4, kv_heads=2, head_dim=16,
        dtype="float32", n_blocks=3, n_valid=9, pending=42, max_new=16,
        prefix_len=0, prompt=np.arange(9, dtype=np.int32),
        emitted=[42], key_data=np.asarray([1, 2, 3, 4], np.uint32),
        tier="batch",
    )
    rng = np.random.default_rng(0)
    layers = [
        {"k": rng.normal(size=(3, 4, 2, 16)).astype(np.float32),
         "v": rng.normal(size=(3, 4, 2, 16)).astype(np.float32)}
        for _ in range(2)
    ]
    export = kvstream.KvExport(meta=meta, layers=layers)
    hid = uuid.uuid4().bytes
    sub, h, body = kvstream.parse_frame(kvstream.begin_frame(export, hid))
    assert (sub, h) == (kvstream.KV_BEGIN, hid)
    got = kvstream.parse_begin(body)
    assert (got.n_layers, got.block_size, got.kv_heads, got.head_dim,
            got.dtype, got.n_blocks, got.n_valid, got.pending,
            got.max_new, got.tier) == (
        2, 4, 2, 16, "float32", 3, 9, 42, 16, "batch")
    np.testing.assert_array_equal(got.prompt, meta.prompt)
    assert got.emitted == [42]
    np.testing.assert_array_equal(got.key_data, meta.key_data)
    frames = list(kvstream.block_frames(export, hid, 2))
    assert len(frames) == 2  # 3 blocks at chunk 2
    staged = [
        {"k": np.zeros((3, 4, 2, 16), np.float32),
         "v": np.zeros((3, 4, 2, 16), np.float32)}
        for _ in range(2)
    ]
    for fr in frames:
        _, _, b = kvstream.parse_frame(fr)
        first, chunk = kvstream.parse_blocks(b, got)
        for stage, lay in zip(staged, chunk):
            for name, arr in lay.items():
                stage[name][first:first + arr.shape[0]] = arr
    for stage, lay in zip(staged, layers):
        np.testing.assert_array_equal(stage["k"], lay["k"])
        np.testing.assert_array_equal(stage["v"], lay["v"])
    # tokens + stats helpers
    toks = np.arange(16, dtype=np.int32)
    np.testing.assert_array_equal(
        kvstream.unpack_tokens(kvstream.pack_tokens(toks)), toks)
    s = kvstream.unpack_stats(kvstream.pack_stats(10, 63, 1, 2))
    assert s == {"free": 10, "total": 63, "waiting": 1, "inflight": 2}


def test_geometry_mismatch_refused_typed():
    decode = _genserver(role="decode")
    prefill = _genserver(role="prefill")
    try:
        export = _export_for(prefill, _PROMPT)["export"]
        bad = kvstream.KvBeginMeta(
            **{**export.meta.__dict__, "kv_heads": 7})
        with pytest.raises(kvstream.KvWireError, match="geometry"):
            decode.kv_reserve(uuid.uuid4().bytes, bad)
        assert decode._allocator is not None
        assert decode._allocator.snapshot()["reserved"] == 0
    finally:
        prefill.stop()
        decode.stop()


def test_pool_full_reserve_sheds_typed_retryable():
    decode = _genserver(role="decode", num_blocks=4)  # 3 usable blocks
    prefill = _genserver(role="prefill")
    try:
        export = _export_for(prefill, _PROMPT)["export"]
        assert export.meta.n_blocks > 3
        with pytest.raises(LoadShedError):
            decode.kv_reserve(uuid.uuid4().bytes, export.meta)
    finally:
        prefill.stop()
        decode.stop()


# -- the full relay stack (engines + coordinator + UDS) ------------------

def _gen_spec():
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "d", "predictors": [{
            "name": "p",
            "graph": {"name": "gen", "type": "MODEL"},
            "components": [{
                "name": "gen", "runtime": "inprocess",
                "class_path": "TransformerGenerator",
                "parameters": [
                    {"name": "vocab", "value": "64", "type": "INT"},
                    {"name": "d_model", "value": "32", "type": "INT"},
                    {"name": "n_heads", "value": "2", "type": "INT"},
                    {"name": "n_layers", "value": "2", "type": "INT"},
                    {"name": "d_ff", "value": "64", "type": "INT"},
                    {"name": "max_new_tokens", "value": "16",
                     "type": "INT"},
                    {"name": "dtype", "value": "float32",
                     "type": "STRING"},
                ],
            }],
        }]}
    })


def test_disagg_over_uds_relay_token_identical_and_kill_switch():
    """The acceptance path: 1 prefill + 1 decode EngineService over a
    real UDS relay produce byte-identical predictions to a unified
    engine, the /stats surfaces show the handoff, role misconfig
    answers 503, and SELDON_TPU_DISAGG=0 restores unified bit-for-bit."""
    sock = os.path.join(tempfile.mkdtemp(prefix="seldon-kv-"),
                        "decode.sock")
    decode_engine = EngineService(_gen_spec(), gen_role="decode")
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    server = asyncio.run_coroutine_threadsafe(
        serve_uds(decode_engine, sock), loop).result(10)
    prefill_engine = EngineService(
        _gen_spec(), gen_role="prefill", decode_peers=[f"uds:{sock}"])
    unified_engine = EngineService(_gen_spec())
    payload = json.dumps({"data": {"ndarray": [list(range(1, 23))]}})
    try:
        t0, s0 = asyncio.run(unified_engine.predict_json(payload))
        t1, s1 = asyncio.run(prefill_engine.predict_json(payload))
        assert s0 == 200 and s1 == 200
        a0 = np.asarray(json.loads(t0)["data"]["ndarray"])
        a1 = np.asarray(json.loads(t1)["data"]["ndarray"])
        np.testing.assert_array_equal(a0, a1)
        # the handoff is visible on both /stats surfaces
        disagg = prefill_engine.genserver.snapshot()["disagg"]
        assert disagg["handoffs"].get("ok") == 1
        assert disagg["bytes_per_tok"] > 0
        assert disagg["handoff_ms_p50"] > 0
        imports = decode_engine.genserver.snapshot()["imports"]
        assert imports["committed_total"] == 1
        # role misconfig: a client generation at the decode replica
        t2, s2 = asyncio.run(decode_engine.predict_json(payload))
        assert s2 == 503 and "decode-only" in t2
        # a handoff BEGIN at a non-decode replica answers 503 typed
        export_frame = kvstream.begin_frame(
            kvstream.KvExport(meta=kvstream.KvBeginMeta(
                n_layers=2, block_size=4, kv_heads=2, head_dim=16,
                dtype="float32", n_blocks=1, n_valid=4, pending=1,
                max_new=4, prefix_len=0,
                prompt=np.arange(4, dtype=np.int32), emitted=[1],
                key_data=None), layers=[]),
            uuid.uuid4().bytes)
        status, body = asyncio.run(unified_engine.kv_frame(export_frame))
        assert status == 503 and b"role misconfig" in body
        # kill switch: bit-for-bit unified
        os.environ["SELDON_TPU_DISAGG"] = "0"
        try:
            killed = EngineService(
                _gen_spec(), gen_role="prefill",
                decode_peers=[f"uds:{sock}"])
            assert killed.gen_role == "unified"
            t3, s3 = asyncio.run(killed.predict_json(payload))
            assert s3 == 200
            np.testing.assert_array_equal(
                np.asarray(json.loads(t3)["data"]["ndarray"]), a0)
            asyncio.run(killed.close())
        finally:
            os.environ.pop("SELDON_TPU_DISAGG", None)
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        for e in (decode_engine, prefill_engine, unified_engine):
            asyncio.run(e.close())


def test_coordinator_p2c_prefers_freer_peer_and_walks_on_refusal():
    """Two decode peers over real UDS relays: one with a pool too small
    to ever accept the handoff.  The coordinator's free-block p2c
    prefers the big pool, and when the order lands on the tiny one its
    typed refusal walks to the next candidate — the handoff still
    lands."""
    tmp = tempfile.mkdtemp(prefix="seldon-kv-")
    small_sock = os.path.join(tmp, "small.sock")
    big_sock = os.path.join(tmp, "big.sock")
    small = _genserver(role="decode", num_blocks=4)
    big = _genserver(role="decode")

    class _Shim:
        """Engine-shaped wrapper the relay server dispatches into."""

        def __init__(self, gs):
            self.genserver = gs
            self.gen_role = gs.role

        async def kv_frame(self, payload):
            eng = EngineService.__new__(EngineService)
            eng.genserver = self.genserver
            return await EngineService.kv_frame(eng, payload)

    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    s1 = asyncio.run_coroutine_threadsafe(
        serve_uds(_Shim(small), small_sock), loop).result(10)
    s2 = asyncio.run_coroutine_threadsafe(
        serve_uds(_Shim(big), big_sock), loop).result(10)
    prefill = _genserver(role="prefill")
    coord = DisaggCoordinator(
        [f"uds:{small_sock}", f"uds:{big_sock}"])
    prefill.coordinator = coord
    try:
        unified = _genserver()
        want = unified.submit(_PROMPT).future.result(timeout=120)
        unified.stop()
        got = prefill.submit(_PROMPT).future.result(timeout=120)
        np.testing.assert_array_equal(want, got)
        assert big.imports_committed_total == 1
        assert small.imports_committed_total == 0
        snap = coord.snapshot()
        assert snap["handoffs"].get("ok") == 1
        # the free-block scrape saw both peers
        assert f"uds:{big_sock}" in snap["peer_free_blocks"]
    finally:
        asyncio.run_coroutine_threadsafe(s1.stop(), loop).result(10)
        asyncio.run_coroutine_threadsafe(s2.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        prefill.stop()
        small.stop()
        big.stop()


# -- phase-aware routing at the gateway ---------------------------------

def test_endpoint_spec_role_suffix_parses():
    from seldon_core_tpu.gateway.balancer import ReplicaEndpoint

    ep = ReplicaEndpoint("http://h:1+role:prefill")
    assert ep.role == "prefill" and ep.base_url == "http://h:1"
    ep = ReplicaEndpoint("http://h:1+uds:/x.sock+role:decode")
    assert ep.role == "decode" and ep.uds_path == "/x.sock"
    # order-insensitive: +role: before +uds: must keep BOTH
    ep = ReplicaEndpoint("http://h:1+role:decode+uds:/x.sock")
    assert ep.role == "decode" and ep.uds_path == "/x.sock"
    assert ep.base_url == "http://h:1"
    ep = ReplicaEndpoint("http://h:1")
    assert ep.role == "unified"
    assert ep.snapshot()["role"] == "unified"


def test_gateway_pick_excludes_decode_replicas():
    from seldon_core_tpu.gateway.apife import _not_decode
    from seldon_core_tpu.gateway.balancer import ReplicaSet

    rs = ReplicaSet([
        "http://prefill-0:1+role:prefill",
        "http://decode-0:1+role:decode",
        "http://decode-1:1+role:decode",
    ])
    for _ in range(32):
        ep, _decision = rs.pick(eligible=_not_decode)
        assert ep.role != "decode"


def test_inprocess_endpoint_reads_engine_role():
    from seldon_core_tpu.gateway.balancer import ReplicaEndpoint

    class FakeEngine:
        gen_role = "decode"

        async def predict(self, msg):
            return msg

    assert ReplicaEndpoint(FakeEngine()).role == "decode"


# -- tensor-parallel dispatch -------------------------------------------

def test_mesh_sharded_scheduler_token_identical(devices8):
    """The scheduler's compiled prefill/decode executables over a tp=2
    mesh (params sharded by the unit, pool sharded by shard_gen_pool)
    produce the same tokens as the single-device path."""
    from seldon_core_tpu.parallel.mesh import MeshSpec, build_mesh

    single = _genserver()
    mesh = build_mesh(MeshSpec({"tp": 2}), devices=devices8[:2])
    meshed_unit = _unit(mesh=mesh)
    meshed = _genserver(meshed_unit)
    try:
        assert meshed.mesh is mesh
        y0 = single.submit(_PROMPT).future.result(timeout=180)
        y1 = meshed.submit(_PROMPT).future.result(timeout=180)
        np.testing.assert_array_equal(y0, y1)
        # the pool actually landed sharded over both devices
        k0 = meshed._pool["l0"]["k"]
        assert len(k0.sharding.device_set) == 2
        assert meshed.snapshot()["mesh"] == {"tp": 2}
    finally:
        single.stop()
        meshed.stop()


def test_mesh_disagg_composes(devices8):
    """Disaggregation + tensor-parallel dispatch: a mesh-sharded decode
    replica imports a single-device prefill's handoff token-identically
    (the wire format is host arrays — device layout is a local
    concern)."""
    from seldon_core_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec({"tp": 2}), devices=devices8[:2])
    unified = _genserver()
    decode = _genserver(_unit(mesh=mesh), role="decode")
    prefill = _genserver(role="prefill",
                         coordinator=LoopbackCoordinator(decode))
    try:
        want = unified.submit(_PROMPT).future.result(timeout=180)
        got = prefill.submit(_PROMPT).future.result(timeout=180)
        np.testing.assert_array_equal(want, got)
    finally:
        unified.stop()
        prefill.stop()
        decode.stop()
