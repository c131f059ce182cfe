"""Mesh-wide observability plane (gateway/fleet.py + the trace/profile
plumbing it federates over).

The acceptance scenario (ISSUE 13): a disaggregated generation request
traced END TO END — ``GET /trace?trace_id=`` on the gateway returns ONE
assembled tree whose critical path includes the prefill dispatch, the
KV-handoff wire segment, and decode steps from the decode engine's
scheduler, verified over the real UDS relay lane; plus the /fleet
replica-outlier rollup (a +30 ms FaultyEngine replica must surface as
the outlier), partial-trace markers instead of empty results, the
coordinated profile window contract, and the SELDON_TPU_FLEET=0 kill
switch.
"""

import asyncio
import json
import os
import tempfile
import threading
import uuid

import numpy as np
import pytest

from seldon_core_tpu.gateway.apife import ApiGateway, DeploymentStore
from seldon_core_tpu.gateway.fleet import (
    compute_outliers,
    extract_replica_row,
    federated_export_document,
    federated_trace_document,
    fleet_document,
    gather_sources,
    profile_start,
    profile_stop,
    profile_status,
)
from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import SeldonMessage
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.udsrelay import OP_TRACE, serve_uds
from seldon_core_tpu.testing.faults import FaultSpec, FaultyEngine
from seldon_core_tpu.utils.quality import QUALITY
from seldon_core_tpu.utils.tracing import TRACER, Span, trace_document


@pytest.fixture(autouse=True)
def _clean_tracer():
    TRACER.clear()
    TRACER.disable()
    TRACER.sample = 1.0
    yield
    TRACER.clear()
    TRACER.disable()
    TRACER.sample = 1.0


def _gen_spec(name="d"):
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": name, "predictors": [{
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


def _iris_spec(name="d"):
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": name, "predictors": [{
            "name": "p",
            "graph": {"name": "m", "type": "MODEL"},
            "components": [{
                "name": "m", "runtime": "inprocess",
                "class_path": "IrisClassifier",
            }],
        }]}
    })


def _relay_loop():
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    return loop


# ---------------------------------------------------------------------------
# The acceptance path: federated trace of a disaggregated generation
# ---------------------------------------------------------------------------


def test_federated_trace_of_disagg_generation_over_real_relay():
    """1 prefill + 1 decode engine over the real UDS relay: the gateway
    assembles ONE tree containing the gateway ingress span, the prefill
    dispatch, the kv_handoff wire segment, and the decode engine's
    import/decode spans — with critical-path segments summing exactly
    to the root duration."""
    TRACER.enable()
    sock = os.path.join(tempfile.mkdtemp(prefix="fleet-kv-"),
                        "decode.sock")
    decode_engine = EngineService(_gen_spec(), gen_role="decode")
    loop = _relay_loop()
    server = asyncio.run_coroutine_threadsafe(
        serve_uds(decode_engine, sock), loop).result(10)
    prefill_engine = EngineService(
        _gen_spec(), gen_role="prefill", decode_peers=[f"uds:{sock}"])
    store = DeploymentStore()
    store.register(_gen_spec(), {"p": prefill_engine})
    gw = ApiGateway(store, require_auth=False)
    msg = SeldonMessage.from_json(
        json.dumps({"data": {"ndarray": [list(range(1, 23))]}}))
    async def run():
        resp = await gw.predict(msg)
        assert resp.status is None or resp.status.status == "SUCCESS"
        puid = resp.meta.puid
        # the handoff span lands from the coordinator thread; decode
        # spans from the decode scheduler — drain via the query path
        trace_id = ""
        for _ in range(50):
            spans = TRACER.trace(puid)
            trace_id = next(
                (s.trace_id for s in spans if s.trace_id), "")
            by_name = {s.name for s in TRACER.by_trace(trace_id)} \
                if trace_id else set()
            if {"kv_handoff", "decode", "kv_import"} <= by_name:
                break
            await asyncio.sleep(0.1)
        doc = await federated_trace_document(gw, trace_id=trace_id)
        export = await federated_export_document(gw, trace_id=trace_id)
        await gw.close()
        return doc, export

    try:
        doc, export = asyncio.run(run())
        assert doc["federated"] is True
        names = {(s["name"], s["kind"]) for s in doc["spans"]}
        assert ("gateway", "request") in names
        assert ("prefill", "dispatch") in names
        assert ("kv_handoff", "kv_handoff") in names
        assert ("kv_import", "kv_import") in names
        assert ("decode", "dispatch") in names
        assert doc["partial"] is False, doc["missing"]
        # ONE tree: every span reachable from the single root
        assert len(doc["tree"]) == 1
        # the critical path crosses all three legs...
        cp_names = {c["name"] for c in doc["critical_path"]}
        assert {"kv_handoff", "decode"} <= cp_names
        # ...and its segments sum exactly to the root duration (each
        # figure in the document is rounded to 3 decimals, so "exactly"
        # is within half a unit of the last place per term)
        total = sum(c["self_ms"] for c in doc["critical_path"])
        assert total == pytest.approx(
            doc["root_duration_ms"],
            abs=0.0005 * (len(doc["critical_path"]) + 1))
        assert doc["phases"]["total_ms"] == pytest.approx(
            doc["root_duration_ms"], abs=0.01)
        assert doc["phases"]["decode_ms"] > 0
        # the relay OP_TRACE lane answered (the decode peer is a source)
        lanes = {r["lane"] for r in doc["sources"]}
        assert "relay" in lanes and "local" in lanes
        assert not any(r["error"] for r in doc["sources"])
        # Perfetto export renders per-process tracks
        tracks = {e["args"]["name"] for e in export["traceEvents"]
                  if e.get("name") == "process_name"}
        assert "decode replica" in tracks
        assert "prefill replica" in tracks
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        for e in (decode_engine, prefill_engine):
            asyncio.run(e.close())


def test_kv_handoff_firehose_line_carries_trace_identity():
    """Satellite: the per-handoff ``method="kv_handoff"`` audit line
    carries trace_id + tenant + tier so firehose consumers join
    handoffs to traces."""
    TRACER.enable()
    sock = os.path.join(tempfile.mkdtemp(prefix="fleet-kv-"),
                        "decode.sock")
    decode_engine = EngineService(_gen_spec(), gen_role="decode")
    loop = _relay_loop()
    server = asyncio.run_coroutine_threadsafe(
        serve_uds(decode_engine, sock), loop).result(10)
    events = []
    prefill_engine = EngineService(
        _gen_spec(), gen_role="prefill", decode_peers=[f"uds:{sock}"])
    prefill_engine.audit.enabled = True
    prefill_engine.audit.sink = events.append
    payload = json.dumps({"data": {"ndarray": [list(range(1, 23))]}})

    async def run():
        with TRACER.span("puid-ho", "client", kind="request",
                         method="predict"):
            _text, status = await prefill_engine.predict_json(payload)
        assert status == 200
        lines = []
        for _ in range(50):
            lines = [e for e in events
                     if e.get("method") == "kv_handoff"]
            if lines:
                break
            await asyncio.sleep(0.1)
        for e in (decode_engine, prefill_engine):
            await e.close()
        return lines

    try:
        lines = asyncio.run(run())
        assert lines, "no kv_handoff firehose line recorded"
        line = lines[0]
        assert line.get("trace_id"), line
        # the puid is the engine request's correlation id (the engine
        # mints one when the payload carries none)
        assert line.get("puid"), line
        assert line.get("tier") == "interactive"
        # the trace_id joins to a real recorded handoff span
        spans = {s.name for s in TRACER.by_trace(line["trace_id"])}
        assert "kv_handoff" in spans
    finally:
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)


def test_gen_step_dispatch_exemplar_joins_decode_to_trace():
    """Satellite: the decode-side scheduler step lands a
    ``seldon_tpu_dispatch_seconds{executable="gen_step:*"}``
    observation whose OpenMetrics exemplar carries the handoff's
    trace_id."""
    from seldon_core_tpu.utils.telemetry import RECORDER

    TRACER.enable()
    engine = EngineService(_gen_spec())
    payload = json.dumps({"data": {"ndarray": [list(range(1, 23))]}})
    try:
        with TRACER.span("puid-ex", "client", kind="request",
                         method="predict"):
            _text, status = asyncio.run(engine.predict_json(payload))
        assert status == 200
        ctxs = TRACER.trace("puid-ex")
        trace_id = next(s.trace_id for s in ctxs if s.trace_id)
        exposition = RECORDER.exposition(openmetrics=True).decode()
        assert 'executable="gen_step:' in exposition
        # at least one gen_step bucket carries a trace exemplar
        assert "trace_id=" in exposition
        assert trace_id in exposition
    finally:
        asyncio.run(engine.close())


# ---------------------------------------------------------------------------
# Federation mechanics: remote merge, partial markers, kill switch
# ---------------------------------------------------------------------------


class _TraceShim:
    """A relay-served 'remote process': answers OP_TRACE with canned
    spans — the federation merge path without a second interpreter."""

    def __init__(self, spans):
        self.spans = spans

    def trace_json(self, query: str) -> str:
        q = json.loads(query or "{}")
        tid = q.get("trace_id", "")
        return json.dumps({
            "spans": [s.to_json_dict() for s in self.spans
                      if s.trace_id == tid],
        })


def test_federated_merge_pulls_remote_subtree_over_relay():
    """Spans only a REMOTE process holds merge into the gateway's tree:
    without federation the decode subtree is invisible; with it the
    tree is whole and partial=False."""
    TRACER.enable()
    trace_id = "ab" * 16
    root = Span(puid="pX", name="gateway", kind="request",
                method="predict", start_s=1000.0, duration_ms=50.0,
                trace_id=trace_id, span_id="11" * 8)
    TRACER.add(root)
    remote = [
        Span(puid="pX", name="decode", kind="dispatch", method="decode",
             start_s=1000.01, duration_ms=30.0, trace_id=trace_id,
             span_id="22" * 8, parent_span_id="11" * 8),
    ]
    sock = os.path.join(tempfile.mkdtemp(prefix="fleet-shim-"),
                        "shim.sock")
    loop = _relay_loop()
    server = asyncio.run_coroutine_threadsafe(
        serve_uds(_TraceShim(remote), sock), loop).result(10)
    gw = ApiGateway(DeploymentStore(), require_auth=False)
    os.environ["SELDON_TPU_FLEET_PEERS"] = f"uds:{sock}"

    async def run():
        merged = await federated_trace_document(gw, trace_id=trace_id)
        os.environ["SELDON_TPU_FLEET"] = "0"
        try:
            killed = await federated_trace_document(
                gw, trace_id=trace_id)
        finally:
            os.environ.pop("SELDON_TPU_FLEET", None)
        await gw.close()
        return merged, killed

    try:
        doc, killed = asyncio.run(run())
        names = {s["name"] for s in doc["spans"]}
        assert names == {"gateway", "decode"}
        assert doc["partial"] is False
        assert len(doc["tree"]) == 1
        assert doc["tree"][0]["children"][0]["name"] == "decode"
        peer_report = next(r for r in doc["sources"]
                           if r["lane"] == "relay")
        assert peer_report["spans"] == 1
        # kill switch: local data only, bit-for-bit the pre-fleet shape
        assert killed["federated"] is False
        assert {s["name"] for s in killed["spans"]} == {"gateway"}
    finally:
        os.environ.pop("SELDON_TPU_FLEET_PEERS", None)
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)


def test_partial_tree_marker_on_local_and_federated_paths():
    """Satellite fix: a trace whose subtree was evicted (or whose
    source errored) answers the PARTIAL tree with an explicit marker
    and a missing list — never a silent empty result."""
    TRACER.enable()
    trace_id = "cd" * 16
    # a child whose parent the ring no longer holds
    TRACER.add(Span(
        puid="pY", name="dispatch", kind="dispatch", method="predict",
        start_s=1000.0, duration_ms=5.0, trace_id=trace_id,
        span_id="33" * 8, parent_span_id="44" * 8))
    local = trace_document(TRACER, trace_id=trace_id)
    assert local["partial"] is True
    assert any("parent_span_id" in m for m in local["missing"])
    assert local["tree"], "the partial tree must still render"
    # a named trace with NOTHING left is partial too — not empty-silent
    gone = trace_document(TRACER, trace_id="ef" * 16)
    assert gone["partial"] is True and gone["missing"]
    # federated: a dead source makes the result partial with a
    # per-source reason
    gw = ApiGateway(DeploymentStore(), require_auth=False)
    os.environ["SELDON_TPU_FLEET_PEERS"] = "uds:/nonexistent/peer.sock"

    async def run():
        doc = await federated_trace_document(gw, trace_id=trace_id)
        await gw.close()
        return doc

    try:
        doc = asyncio.run(run())
        assert doc["partial"] is True
        reasons = [m for m in doc["missing"] if m.get("source")]
        assert reasons and "peer.sock" in reasons[0]["source"]
    finally:
        os.environ.pop("SELDON_TPU_FLEET_PEERS", None)


# ---------------------------------------------------------------------------
# Fleet aggregation (GET /fleet)
# ---------------------------------------------------------------------------


def test_fleet_surfaces_slow_replica_as_outlier():
    """The ISSUE's outlier test: a +30 ms FaultyEngine replica must
    surface as THE outlier of its set on /fleet."""
    # earlier test files train the process-global quality observatory's
    # drift reference for the shared iris node name; against that
    # inherited reference the starved replica's tiny live window can
    # score a PSI big enough to outrank the injected +30ms on the
    # outlier ladder — this test is about the LATENCY outlier, so it
    # starts from fresh drift state
    QUALITY.reset()
    spec = _iris_spec()
    fast = EngineService(spec)
    slow = FaultyEngine(EngineService(spec), FaultSpec(delay_s=0.03))
    store = DeploymentStore()
    store.register(spec, {"p": [fast, slow]})
    gw = ApiGateway(store, require_auth=False)
    msg = SeldonMessage.from_json(
        json.dumps({"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}}))

    async def run():
        # warm both replicas DIRECTLY first: the first dispatch pays XLA
        # compilation, and a compile-priced EWMA would brand the healthy
        # replica the slow one (p2c then starves it and the EWMA never
        # recovers)
        await fast.predict(msg)
        await slow.inner.predict(msg)
        for _ in range(60):
            await gw.predict(msg)
        doc = await fleet_document(gw)
        await gw.close()
        return doc

    try:
        doc = asyncio.run(run())
        dep = doc["deployments"]["d/p"]
        assert set(dep["replicas"]) == {"inprocess-0", "inprocess-1"}
        # the slow replica's gateway-side EWMA reads ~30 ms against a
        # fast sibling: it must be flagged, and be the WORST outlier
        assert dep["outliers"], dep
        worst = dep["outliers"][0]
        assert worst["replica"] == "inprocess-1"
        assert worst["metric"] == "ewma_ms"
        assert worst["ratio"] >= 1.5
        assert dep["replicas"]["inprocess-1"]["ewma_ms"] > \
            dep["replicas"]["inprocess-0"]["ewma_ms"]
        # the outlier gauge published the rollup
        from seldon_core_tpu.utils.telemetry import RECORDER

        assert RECORDER.fleet_outliers["d/p"]["inprocess-1"] >= 1.5
        assert RECORDER.fleet_replicas["d/p"] == 2
    finally:
        asyncio.run(fast.close())
        asyncio.run(slow.inner.close())


def test_outlier_math_hand_computed():
    rows = {
        "r0": {"dispatch_p99_ms": 10.0, "mfu": 0.4,
               "free_kv_blocks": 100},
        "r1": {"dispatch_p99_ms": 10.0, "mfu": 0.4,
               "free_kv_blocks": 100},
        "r2": {"dispatch_p99_ms": 30.0, "mfu": 0.1,
               "free_kv_blocks": 10},
    }
    out = compute_outliers(rows, threshold=1.5)
    assert out["median"]["dispatch_p99_ms"] == 10.0
    assert out["ratios"]["r2"]["dispatch_p99_ms"] == 3.0
    assert out["ratios"]["r2"]["mfu"] == 4.0       # lower-is-worse folds
    assert out["ratios"]["r2"]["free_kv_blocks"] == 10.0
    assert out["ratios"]["r0"]["dispatch_p99_ms"] == 1.0
    flagged = {(o["replica"], o["metric"]) for o in out["outliers"]}
    assert ("r2", "dispatch_p99_ms") in flagged
    assert ("r0", "mfu") not in flagged
    # two-replica sets use the true (middle-two-average) median so the
    # sick replica can flag against its healthy sibling
    two = compute_outliers(
        {"a": {"ewma_ms": 2.0}, "b": {"ewma_ms": 30.0}}, threshold=1.5)
    assert two["ratios"]["b"]["ewma_ms"] >= 1.5


def test_extract_replica_row_defensive_and_complete():
    stats = {
        "telemetry": {
            "batch": {"inflight_dispatches": 3},
            "request_latency_s": {
                "engine": {"count": 100, "p99": 0.2},
            },
        },
        "genserver": {
            "role": "decode",
            "kv_blocks": {"total": 1000, "used": 400},
            "imports": {"pending": 1, "committed_total": 7,
                        "reclaimed_total": 0},
        },
        "quality": {"nodes": {
            "m": {"status": "live", "psi_max": 0.31},
        }},
    }
    perf = {"executables": [
        {"executable": "e1", "calls": 10,
         "latency_ms": {"p50": 5.0, "p99": 9.0}, "mfu": 0.25},
        {"executable": "e2", "calls": 30,
         "latency_ms": {"p50": 1.0, "p99": 2.0}, "mfu": 0.5},
    ]}
    row = extract_replica_row(stats, perf, None)
    assert row["inflight"] == 3
    assert row["requests"] == 100
    assert row["request_p99_ms"] == 200.0
    assert row["dispatch_p99_ms"] == 9.0
    assert row["dispatch_p50_ms"] == 2.0     # call-weighted
    assert row["mfu"] == 0.5
    assert row["free_kv_blocks"] == 600
    assert row["role"] == "decode"
    assert row["imports"]["committed_total"] == 7
    assert row["drift_max"] == 0.31
    # garbage in -> absent fields, never zeros or raises
    assert extract_replica_row(None, None, None) == {}
    assert "mfu" not in extract_replica_row(
        {}, {"executables": [{"latency_ms": "bogus"}]}, {})


def test_fleet_kill_switch_local_only(monkeypatch):
    spec = _iris_spec()
    e1 = EngineService(spec)
    store = DeploymentStore()
    store.register(spec, {"p": [e1, "http://127.0.0.1:1/dead"]})
    gw = ApiGateway(store, require_auth=False)
    monkeypatch.setenv("SELDON_TPU_FLEET", "0")
    try:
        doc = asyncio.run(fleet_document(gw))
        assert doc["enabled"] is False
        # only the in-process replica reports — no fan-out to the URL
        dep = doc["deployments"]["d/p"]
        assert list(dep["replicas"]) == ["inprocess-0"]
    finally:
        asyncio.run(gw.close())
        asyncio.run(e1.close())


def test_dead_lease_row_reads_dead_not_stale_docs(monkeypatch):
    """PR-17 liveness coherence: an engine whose store lease lapsed must
    read ``lease: dead`` on /fleet instead of silently serving its
    scrape-stashed fleet_docs, with staleness pinned to at least the
    lease TTL and the dead row kept out of the outlier median."""
    import time as _t

    from seldon_core_tpu.gateway.federation import lease_ttl_s
    from seldon_core_tpu.utils.telemetry import RECORDER

    spec = _iris_spec()
    live = EngineService(spec)
    store = DeploymentStore()
    store.register(spec, {"p": [live, "http://127.0.0.1:1/gone"]})
    gw = ApiGateway(store, require_auth=False)
    published = {}
    monkeypatch.setattr(
        RECORDER, "set_fleet_staleness",
        lambda set_name, replica, s: published.__setitem__(replica, s))
    try:
        (src,) = [s for s in gather_sources(gw) if s.lane == "http"]
        ep = src.endpoint
        # a scrape pass once stashed healthy-looking docs ...
        ep.fleet_docs = {
            "ts": _t.monotonic(),
            "stats": {"telemetry": {"request_latency_s": {
                "engine": {"count": 500, "p99": 0.002}}}},
            "perf": None, "quality": None,
        }
        # ... then the lease lapsed (federation.apply_leases verdict)
        ep.lease_state = "dead"
        doc = asyncio.run(fleet_document(gw))
        dep = doc["deployments"]["d/p"]
        row = dep["replicas"][ep.name]
        assert row["lease"] == "dead"
        assert row["error"] == "engine lease lapsed"
        # the stale figures are NOT served as a live row
        assert "requests" not in row
        assert row["staleness_s"] >= lease_ttl_s()
        # dead row stays out of the outlier median
        assert ep.name not in dep["ratios"]
        assert all(o["replica"] != ep.name for o in dep["outliers"])
        # the staleness gauge reflects the lease state, not doc age
        assert published[ep.name] >= lease_ttl_s()
    finally:
        asyncio.run(gw.close())
        asyncio.run(live.close())


def test_scrape_tick_gauges_publish_dead_lease_staleness(monkeypatch):
    """refresh_outlier_gauges (the scrape-tick lane, no /fleet query):
    a dead-lease replica must still publish a staleness gauge — pinned
    to the lease TTL — even when too few live rows remain for outlier
    math."""
    import time as _t

    from seldon_core_tpu.gateway.fleet import refresh_outlier_gauges
    from seldon_core_tpu.gateway.federation import lease_ttl_s
    from seldon_core_tpu.utils.telemetry import RECORDER

    spec = _iris_spec()
    store = DeploymentStore()
    store.register(spec, {"p": ["http://127.0.0.1:1/a",
                                "http://127.0.0.1:2/b"]})
    gw = ApiGateway(store, require_auth=False)
    published = {}
    monkeypatch.setattr(
        RECORDER, "set_fleet_staleness",
        lambda set_name, replica, s: published.__setitem__(replica, s))
    try:
        srcs = [s for s in gather_sources(gw) if s.lane == "http"]
        assert len(srcs) == 2
        dead, alive = srcs[0].endpoint, srcs[1].endpoint
        now = _t.monotonic()
        dead.fleet_docs = {"ts": now, "stats": {}, "perf": None,
                           "quality": None}
        dead.lease_state = "dead"
        alive.fleet_docs = {"ts": now, "stats": {}, "perf": None,
                            "quality": None}
        alive.lease_state = "live"
        refresh_outlier_gauges(gw)
        # one live row is below the outlier quorum, but the dead
        # replica's staleness still lands (that's the alertable signal)
        assert published[dead.name] >= lease_ttl_s()
        assert published[alive.name] < lease_ttl_s()
    finally:
        asyncio.run(gw.close())


# ---------------------------------------------------------------------------
# Coordinated profiling windows
# ---------------------------------------------------------------------------


@pytest.fixture
def no_window_left():
    """For the tests that open a REAL profiler window (the CPU one): none
    is left behind, however the test ends.  ``profile_window_stop`` waits
    for a stop in flight (the auto-stop timer's), so after it the trace
    is written and the process lock is free for the next test."""
    from seldon_core_tpu.utils import tracing

    yield
    tracing.profile_window_stop()
    assert tracing.profile_window_status()["active"] is False
    assert not tracing._PROFILE_LOCK.locked()


def test_profile_window_coordinated_and_overlap_refused(tmp_path,
                                                        monkeypatch,
                                                        no_window_left):
    monkeypatch.setenv("SELDON_TPU_PROFILE_DIR", str(tmp_path))
    spec = _iris_spec()
    e1 = EngineService(spec)
    store = DeploymentStore()
    store.register(spec, {"p": e1})
    gw = ApiGateway(store, require_auth=False)
    try:
        status, manifest = asyncio.run(
            profile_start(gw, duration_s=30.0))
        assert status == 200
        assert manifest["state"] == "open"
        entry = manifest["sources"][0]
        assert entry["lane"] == "inprocess"
        assert entry["artifact"].startswith(str(tmp_path))
        # overlap refused, never queued — gateway side
        status2, doc2 = asyncio.run(profile_start(gw, duration_s=1.0))
        assert status2 == 409 and "already open" in doc2["error"]
        # ...and engine side (the process-local lock)
        from seldon_core_tpu.utils.tracing import (
            ProfileBusyError,
            profile_window_start,
        )

        with pytest.raises(ProfileBusyError):
            profile_window_start(str(tmp_path / "second"), 1.0)
        status3, closed = asyncio.run(profile_stop(gw))
        assert status3 == 200 and closed["state"] == "closed"
        st = profile_status(gw)
        assert st["local"]["active"] is False
        assert st["manifest"]["window"] == manifest["window"]
        # the artifact directory exists — one manifest entry per source
        assert os.path.isdir(entry["artifact"])
        # a fresh window opens cleanly after the stop
        status4, m4 = asyncio.run(profile_start(gw, duration_s=30.0))
        assert status4 == 200 and m4["window"] != manifest["window"]
        asyncio.run(profile_stop(gw))
    finally:
        from seldon_core_tpu.utils.tracing import profile_window_stop

        profile_window_stop()  # idempotent cleanup
        asyncio.run(gw.close())
        asyncio.run(e1.close())


def test_profile_window_auto_stops_at_duration(tmp_path, no_window_left):
    import time

    from seldon_core_tpu.utils.tracing import (
        profile_window_start,
        profile_window_status,
        profile_window_stop,
    )

    try:
        res = profile_window_start(str(tmp_path / "w"), 0.3)
        assert res["active"] is True
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if not profile_window_status()["active"]:
                break
            time.sleep(0.05)
        st = profile_window_status()
        assert st["active"] is False
        assert st["last"]["artifact"].endswith("w")
    finally:
        profile_window_stop()


# ---------------------------------------------------------------------------
# Gateway HTTP surface
# ---------------------------------------------------------------------------


def test_gateway_http_routes_serve_fleet_surfaces(no_window_left):
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.gateway.apife import make_gateway_app

    TRACER.enable()
    spec = _iris_spec()
    e1 = EngineService(spec)
    store = DeploymentStore()
    store.register(spec, {"p": e1})
    gw = ApiGateway(store, require_auth=False)

    async def run():
        async with TestClient(TestServer(make_gateway_app(gw))) as client:
            r = await client.post(
                "/api/v0.1/predictions",
                json={"data": {"ndarray": [[5.1, 3.5, 1.4, 0.2]]}})
            assert r.status == 200
            body = await r.json()
            puid = body["meta"]["puid"]
            # the gateway /trace route federates by puid too
            r = await client.get("/trace", params={"puid": puid})
            assert r.status == 200
            doc = await r.json()
            assert doc["federated"] is True
            assert {s["name"] for s in doc["spans"]} >= {"gateway"}
            r = await client.get("/fleet")
            assert r.status == 200
            fdoc = await r.json()
            assert "d/p" in fdoc["deployments"]
            r = await client.post("/profile/start",
                                  json={"duration_s": 30.0})
            assert r.status == 200
            r = await client.post("/profile/start",
                                  json={"duration_s": 1.0})
            assert r.status == 409
            r = await client.post("/profile/stop")
            assert r.status == 200
            r = await client.get("/profile")
            assert r.status == 200
            assert (await r.json())["local"]["active"] is False

    try:
        asyncio.run(run())
    finally:
        asyncio.run(e1.close())


def test_engine_profile_routes_contract(no_window_left):
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.runtime.rest import make_engine_app

    engine = EngineService(_iris_spec())

    async def run():
        async with TestClient(TestServer(make_engine_app(engine))) as c:
            r = await c.post("/profile/start", json={"duration_s": 30.0})
            assert r.status == 200
            doc = await r.json()
            assert doc["active"] is True and doc["artifact"]
            r = await c.post("/profile/start", json={})
            assert r.status == 409
            r = await c.post("/profile/stop")
            assert r.status == 200
            r = await c.get("/profile")
            assert (await r.json())["active"] is False

    try:
        asyncio.run(run())
    finally:
        asyncio.run(engine.close())


def test_gather_sources_includes_decode_peers_and_dedups():
    spec = _gen_spec()
    sock = "/tmp/fleet-fake-decode.sock"
    prefill = EngineService(
        _gen_spec(), gen_role="prefill", decode_peers=[f"uds:{sock}"])
    store = DeploymentStore()
    store.register(spec, {"p": [prefill, prefill]})
    gw = ApiGateway(store, require_auth=False)
    try:
        sources = gather_sources(gw)
        lanes = [(s.lane, s.role) for s in sources]
        # the duplicate in-process registration dedups to one source,
        # and the coordinator's decode peer is discovered as a relay
        # source even though it is registered nowhere
        assert lanes.count(("inprocess", "prefill")) == 1
        assert ("relay", "decode") in lanes
    finally:
        asyncio.run(gw.close())
        asyncio.run(prefill.close())
