"""Generation-lane flight recorder (utils/genperf.py): bubble-ledger
arithmetic on a hand-timed fake clock, the host+device+bubble ≈ wall
accounting identity, phase-split residuals, the served-decode null
guards, ``GET /genperf`` on both REST lanes, the per-sequence lifecycle
timeline joining the causal trace, tick-error visibility, and the
kill-switch contract (all observatories off => ZERO ring writes from a
full scheduler run)."""

import asyncio
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.models.transformer import LMConfig, lm_init
from seldon_core_tpu.runtime.engine import EngineService
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.utils.genperf import BUBBLE_CAUSES, GENPERF
from seldon_core_tpu.utils.hotrecord import SPINE
from seldon_core_tpu.utils.perf import OBSERVATORY
from seldon_core_tpu.utils.quality import QUALITY
from seldon_core_tpu.utils.telemetry import RECORDER, TPU_METRIC_FAMILIES
from seldon_core_tpu.utils.tracing import (
    TRACER,
    TraceContext,
    new_span_id,
    new_trace_id,
    trace_scope,
)

CFG = LMConfig(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return lm_init(jax.random.key(3), CFG)


@pytest.fixture(autouse=True)
def _clean():
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()
    TRACER.clear()
    yield
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()
    TRACER.clear()


def _server(params, **kw):
    kw.setdefault("max_new_tokens", 10)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("slots", 8)
    kw.setdefault("span", 3)
    kw.setdefault("prefill_chunk", 4)
    return GenServer(params, kw.pop("cfg", CFG), **kw)


def _settle(srv, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = srv.snapshot()
        if not s["inflight_sequences"] and not s["waiting_sequences"]:
            return s
        time.sleep(0.01)
    raise AssertionError("scheduler did not settle")


def deployment():
    return SeldonDeploymentSpec.from_json_dict(
        {"spec": {"name": "genperf-dep", "predictors": [{
            "name": "p",
            "graph": {"name": "m", "implementation": "SIMPLE_MODEL",
                      "type": "MODEL"},
        }]}}
    )


def gen_deployment():
    return SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "genperf-gen", "predictors": [{
            "name": "p",
            "graph": {"name": "g", "type": "MODEL"},
            "components": [{
                "name": "g", "runtime": "inprocess",
                "class_path": "TransformerGenerator",
                "parameters": [
                    {"name": k, "value": v, "type": t} for k, v, t in (
                        ("vocab", "48", "INT"), ("d_model", "32", "INT"),
                        ("n_heads", "4", "INT"), ("n_layers", "2", "INT"),
                        ("d_ff", "64", "INT"),
                        ("max_new_tokens", "9", "INT"),
                        ("temperature", "0.0", "FLOAT"),
                        ("dtype", "float32", "STRING"))],
            }],
        }]}
    })


# -- bubble-ledger arithmetic (hand-timed fake clock) ------------------------


def test_bubble_ledger_arithmetic_fake_clock():
    """Four hand-timed ticks, one bubble per cause: the ledger must
    reproduce the exact per-cause sums and the exact bubble fraction —
    no measurement noise, pure arithmetic."""
    GENPERF.observe_tick("decode", {
        "wall_s": 0.010, "device_s": 0.006,
        "bubble_s": 0.002, "bubble_cause": "host"})
    GENPERF.observe_tick("decode", {
        "wall_s": 0.010, "device_s": 0.004,
        "bubble_s": 0.003, "bubble_cause": "admission_stall"})
    GENPERF.observe_tick("prefill", {
        "wall_s": 0.020, "device_s": 0.015,
        "bubble_s": 0.001, "bubble_cause": "pool_exhaustion"})
    GENPERF.observe_tick("idle", {
        "wall_s": 0.005, "bubble_s": 0.004, "bubble_cause": "idle"})
    doc = GENPERF.document()
    by_cause = doc["bubbles"]["by_cause_s"]
    assert by_cause == {"host": 0.002, "admission_stall": 0.003,
                        "pool_exhaustion": 0.001, "idle": 0.004}
    assert doc["bubbles"]["by_cause_ticks"] == {
        "host": 1, "admission_stall": 1, "pool_exhaustion": 1, "idle": 1}
    assert set(by_cause) <= set(BUBBLE_CAUSES)
    # wall 0.045, bubble 0.010 -> fraction 0.010 / 0.055
    assert doc["bubbles"]["fraction"] == round(0.010 / 0.055, 4)
    assert doc["ticks"] == {"decode": 2, "prefill": 1, "idle": 1}
    # idle duty cycle: 0.005 of the 0.055 total scheduler wall
    assert doc["idle"]["ticks"] == 1
    assert doc["idle"]["duty_cycle"] == round(0.005 / 0.055, 4)


def test_accounting_identity_host_device_bubble_covers_wall():
    """host := wall - device and bubble := inter-tick gap, so the
    ledger accounts for scheduler wall BY CONSTRUCTION — the demo
    artifact's >= 95 % criterion checks the wiring, not luck."""
    GENPERF.observe_tick("decode", {
        "wall_s": 0.012, "device_s": 0.009,
        "bubble_s": 0.001, "bubble_cause": "host"})
    GENPERF.observe_tick("mixed", {"wall_s": 0.030, "device_s": 0.022})
    acct = GENPERF.document()["accounting"]
    assert acct["scheduler_wall_s"] == round(0.012 + 0.030 + 0.001, 4)
    assert acct["host_s"] == round(0.003 + 0.008, 4)
    assert acct["device_s"] == round(0.009 + 0.022, 4)
    assert acct["bubble_s"] == 0.001
    assert acct["accounted_fraction"] == 1.0


def test_phase_split_residual_lands_in_host_other():
    """Named phases get their fenced device time subtracted; tick wall
    not covered by any named phase shows up as host_other, never
    disappears."""
    GENPERF.observe_tick("decode", {
        "wall_s": 0.010, "device_s": 0.005,
        "phases": {"admit": 0.002, "decode": 0.006},
        "device_phases": {"decode": 0.005}})
    ph = GENPERF.document()["phases"]
    assert ph["host_s"]["decode/admit"] == 0.002
    assert ph["host_s"]["decode/decode"] == round(0.006 - 0.005, 4)
    assert ph["device_s"]["decode/decode"] == 0.005
    assert ph["host_s"]["decode/host_other"] == round(0.010 - 0.008, 4)


def test_served_decode_null_guard_without_cost_features(monkeypatch):
    """No registered decode-step cost features (perf observatory off or
    scheduler never initialized a device): the MFU/BW figures are None,
    never a KeyError — but the raw token/throughput accounting stays."""
    monkeypatch.setattr(OBSERVATORY, "enabled", False)
    GENPERF.observe_tick("decode", {
        "wall_s": 0.010, "device_s": 0.004, "tokens": 8, "steps": 3,
        "real_rows": 2, "rows": 4,
        "device_phases": {"decode": 0.004}, "phases": {"decode": 0.004}})
    served = GENPERF.document()["served_decode"]
    assert served["served_decode_mfu_pct"] is None
    assert served["served_decode_hbm_bw_util_pct"] is None
    assert served["real_tokens"] == 8
    assert served["served_decode_tok_s_device"] == round(8 / 0.004, 1)


def test_requests_block_folds_both_sides_and_publishes_kv_positions():
    """The scheduler's stages ride the tick record, the lane's are folded
    once per stream; the served-decode block carries the program's own
    count of live cache positions."""
    GENPERF.observe_tick("mixed", {
        "wall_s": 0.02, "device_s": 0.015,
        "device_phases": {"decode": 0.01}, "steps": 8, "tokens": 16,
        "kv_positions": 3200,
        "req_queue_s": (0.001, 0.003), "req_prefill_s": (0.150,)})
    GENPERF.observe_stream_first(0.0005, 0.0015, 0.1560)
    GENPERF.observe_stream_first(0.0005, 0.0015, 70.0)
    doc = GENPERF.document()
    req = doc["requests"]
    assert (req["streams"], req["admitted"], req["first_tokens"]) == (2, 2, 1)
    assert req["stage_s"] == {
        "lane_in": pytest.approx(0.001), "queue": pytest.approx(0.004),
        "prefill": pytest.approx(0.150), "lane_out": pytest.approx(0.003)}
    assert req["ttft_s"] == pytest.approx(70.156)
    edges, counts = (req["ttft_ms_hist"]["edges_ms"],
                     req["ttft_ms_hist"]["counts"])
    assert edges[0] == 1.0 and edges[-1] == 60000.0
    assert max(b / a for a, b in zip(edges, edges[1:])) <= 1.15
    assert len(counts) == len(edges) + 1 and sum(counts) == 2
    assert counts[-1] == 1          # 70 s: the overflow bucket
    i = counts.index(1)
    assert edges[i - 1] <= 156.0 < edges[i]
    assert doc["served_decode"]["kv_positions"] == 3200


def test_served_decode_counts_the_steps_that_attended_in_place():
    """``inplace_steps`` rides the tick record beside ``steps``: 0 while
    decode takes the gather path, equal to ``device_steps`` when every
    round's tick record says in-place (bench/layer_metrics/
    decode_inplace_share.json divides the two)."""
    tick = {"wall_s": 0.02, "device_s": 0.01, "tokens": 16, "steps": 8,
            "device_phases": {"decode": 0.01}, "phases": {"decode": 0.01}}
    GENPERF.observe_tick("decode", tick)
    served = GENPERF.document()["served_decode"]
    assert (served["device_steps"], served["inplace_steps"]) == (8, 0)
    GENPERF.reset()
    for _ in range(3):
        GENPERF.observe_tick("mixed", {**tick, "inplace_steps": 8})
    served = GENPERF.document()["served_decode"]
    assert served["inplace_steps"] == served["device_steps"] == 24


def test_served_decode_counts_the_steps_whose_states_the_kernel_updated():
    """``retention_fused_steps`` rides the tick record beside
    ``inplace_steps``: 0 for a tick record that does not say, equal to
    ``device_steps`` when every round's says so (bench/layer_metrics/
    decode_retention_fused_share.json divides the two), and it is not the
    attention kernel's count."""
    tick = {"wall_s": 0.02, "device_s": 0.01, "tokens": 16, "steps": 8,
            "device_phases": {"decode": 0.01}, "phases": {"decode": 0.01}}
    GENPERF.observe_tick("decode", tick)
    served = GENPERF.document()["served_decode"]
    assert (served["device_steps"], served["retention_fused_steps"]) == (8, 0)
    GENPERF.reset()
    for _ in range(3):
        GENPERF.observe_tick("mixed", {**tick, "retention_fused_steps": 8})
    served = GENPERF.document()["served_decode"]
    assert served["retention_fused_steps"] == served["device_steps"] == 24
    assert served["inplace_steps"] == 0


def test_served_decode_counts_the_steps_whose_ssm_states_the_kernel_updated():
    """``ssm_fused_steps`` rides the tick record beside
    ``retention_fused_steps``: 0 for a tick record that does not say (a
    generator without state-space layers, or the CPU's ``ssm_step`` over
    gathered rows), equal to ``device_steps`` when every round's says so
    (bench/layer_metrics/decode_ssm_fused_share.json divides the two), and
    it is neither the attention kernel's count nor retention's -- such
    layers stand beside attention layers in one generator."""
    tick = {"wall_s": 0.02, "device_s": 0.01, "tokens": 16, "steps": 8,
            "device_phases": {"decode": 0.01}, "phases": {"decode": 0.01}}
    GENPERF.observe_tick("decode", {**tick, "inplace_steps": 8})
    served = GENPERF.document()["served_decode"]
    assert (served["device_steps"], served["ssm_fused_steps"]) == (8, 0)
    GENPERF.reset()
    for _ in range(3):
        GENPERF.observe_tick("mixed", {**tick, "inplace_steps": 8,
                                       "ssm_fused_steps": 8})
    served = GENPERF.document()["served_decode"]
    assert served["ssm_fused_steps"] == served["device_steps"] == 24
    assert served["inplace_steps"] == 24
    assert served["retention_fused_steps"] == 0


def test_served_counts_the_passes_and_calls_whose_experts_ran_one_kernel():
    """``experts_fused_passes`` rides the tick record beside ``passes``
    and ``prefill_experts_fused_calls`` beside ``prefill_calls``: 0 for a
    record that does not say (a generator without expert layers, or the
    two grouped matmuls), equal to ``passes`` / ``calls`` when every
    round's and every call's expert layers took an expert's feed-forward
    as one kernel (bench/layer_metrics/decode_experts_fused_share.json
    divides the first two)."""
    tick = {"wall_s": 0.02, "device_s": 0.01, "tokens": 16, "steps": 8,
            "passes": 10, "prefill_calls": 1, "prefill_rows": 3,
            "device_phases": {"decode": 0.01}, "phases": {"decode": 0.01}}
    GENPERF.observe_tick("decode", tick)
    doc = GENPERF.document()
    assert (doc["served_decode"]["passes"],
            doc["served_decode"]["experts_fused_passes"]) == (10, 0)
    assert (doc["served_prefill"]["calls"],
            doc["served_prefill"]["experts_fused_calls"]) == (1, 0)
    GENPERF.reset()
    for kind in ("decode", "mixed"):
        GENPERF.observe_tick(kind, {**tick, "experts_fused_passes": 10,
                                    "prefill_experts_fused_calls": 1})
    doc = GENPERF.document()
    assert (doc["served_decode"]["experts_fused_passes"]
            == doc["served_decode"]["passes"] == 20)
    assert (doc["served_prefill"]["experts_fused_calls"]
            == doc["served_prefill"]["calls"] == 2)


def test_served_prefill_counts_the_rows_whose_chunk_ran_the_kernel():
    """``prefill_retention_fused_rows`` rides the tick record beside
    ``prefill_rows`` whatever the tick's kind: 0 for a record that does
    not say (the CPU's path, ``jax.numpy`` row by row), equal to ``rows``
    when every call's chunk ran the kernel of ops/retention.py
    (bench/layer_metrics/prefill_retention_fused_share.json divides the
    two); tests/test_brumby_block.py has a server count both."""
    tick = {"wall_s": 0.02, "device_s": 0.01, "prefill_calls": 1,
            "prefill_rows": 3, "prefill_carried_rows": 2,
            "prefill_tokens": 600}
    GENPERF.observe_tick("prefill", tick)
    served = GENPERF.document()["served_prefill"]
    assert (served["rows"], served["retention_fused_rows"]) == (3, 0)
    GENPERF.reset()
    for kind in ("prefill", "mixed", "decode"):
        GENPERF.observe_tick(kind, {**tick, "prefill_retention_fused_rows": 3})
    served = GENPERF.document()["served_prefill"]
    assert served["retention_fused_rows"] == served["rows"] == 9
    assert served["carried_rows"] == 6


@pytest.mark.parametrize("depth", [0, 1])
def test_ahead_steps_fold_into_served_decode(depth):
    """``ahead_steps`` rides the tick record beside ``inplace_steps``: 0 at
    depth 0, ``span`` a round where the round was dispatched behind a
    program still unread; it is in the document from boot."""
    assert GENPERF.document()["served_decode"]["ahead_steps"] == 0
    span, rounds = 8, 5
    for _ in range(rounds):
        GENPERF.observe_tick("decode", {
            "wall_s": 0.07, "device_s": 0.065,
            "device_phases": {"decode": 0.065}, "steps": span,
            "inplace_steps": span, "ahead_steps": span * depth,
            "tokens": 5 * span})
    # prefill and idle ticks dispatch no round: nothing of theirs counts
    GENPERF.observe_tick("prefill", {"wall_s": 0.02, "device_s": 0.014,
                                     "ahead_steps": span})
    served = GENPERF.document()["served_decode"]
    assert served["device_steps"] == span * rounds
    assert served["inplace_steps"] == span * rounds
    assert served["ahead_steps"] == span * rounds * depth


def test_device_seconds_of_queued_programs_are_booked_once():
    """Round k dispatched at 0.000 and seen done at 0.065; the chunk and
    round k+1 dispatched at 0.060 and 0.061, while k ran, and seen done at
    0.079 and 0.144.  Each is booked from the later of its own dispatch and
    the completion seen before it, so the three sum to the stretch the
    device was seen busy -- not to 0.065 + 0.019 + 0.083 -- and a fenced
    program reads its whole dispatch -> ready interval, as before."""
    from seldon_core_tpu.utils.genperf import booked_device_s

    k = booked_device_s(0.000, 0.065, prev_done=0.0)
    chunk = booked_device_s(0.060, 0.079, prev_done=0.065)
    k1 = booked_device_s(0.061, 0.144, prev_done=0.079)
    assert (k, chunk, k1) == pytest.approx((0.065, 0.014, 0.065))
    assert k + chunk + k1 == pytest.approx(0.144)
    # fenced: dispatched after everything before it was seen to end
    assert booked_device_s(0.200, 0.268, prev_done=0.144) == \
        pytest.approx(0.068)
    # the records of the two ticks that observed them fold to the same sum
    GENPERF.observe_tick("decode", {
        "wall_s": 0.066, "device_s": k, "device_phases": {"decode": k},
        "steps": 8, "ahead_steps": 0})
    GENPERF.observe_tick("mixed", {
        "wall_s": 0.079, "device_s": chunk + k1,
        "device_phases": {"prefill": chunk, "decode": k1},
        "phases": {"prefill": 0.016, "decode": 0.062},
        "steps": 8, "ahead_steps": 8})
    doc = GENPERF.document()
    assert doc["served_decode"]["decode_device_s"] == pytest.approx(
        0.130, abs=1e-4)
    assert doc["accounting"]["device_s"] == pytest.approx(0.144, abs=1e-4)
    assert doc["phases"]["device_s"]["mixed/prefill"] == pytest.approx(
        0.014, abs=1e-4)


def test_tick_error_counter_and_family():
    assert "seldon_tpu_gen_tick_errors_total" in TPU_METRIC_FAMILIES
    before = RECORDER.gen_tick_errors
    GENPERF.observe_tick_error()
    RECORDER.record_gen_tick_error()
    assert GENPERF.document()["tick_errors_total"] == 1
    assert RECORDER.gen_tick_errors == before + 1


# -- the real scheduler feeding the recorder ---------------------------------


def test_scheduler_run_accounts_for_wall(params):
    """A real (CPU) scheduler run: every tick lands in the recorder via
    the spine's off-path drainer, the accounting identity holds, and
    KV block ages appear at retirement."""
    srv = _server(params)
    try:
        reqs = [srv.submit(np.full((1, 5), i + 1.0)) for i in range(4)]
        for r in reqs:
            r.future.result(timeout=30)
        _settle(srv)
    finally:
        srv.stop()
    SPINE.drain()
    doc = GENPERF.document()
    assert sum(doc["ticks"].values()) > 0
    assert doc["accounting"]["accounted_fraction"] >= 0.95
    assert doc["rows"]["real_total"] > 0
    assert doc["rows"]["real_fraction"] <= 1.0
    assert doc["kv"]["blocks_released_total"] > 0
    assert doc["kv"]["block_age_s"]["count"] > 0
    # the scheduler registered analytic decode-step costs at device init
    assert OBSERVATORY.cost_features("gen_decode_step") is not None
    assert doc["served_decode"]["real_tokens"] > 0
    # on the CPU decode attends through the gather path
    assert doc["served_decode"]["device_steps"] > 0
    assert doc["served_decode"]["inplace_steps"] == 0


def test_idle_ticks_accounted(params):
    """Satellite: idle spins are explicit — a tick that wakes but runs
    no prefill/decode work (here: the cancel-drop path) lands in
    steps_total['idle'] and /genperf carries an idle duty-cycle
    figure instead of silence."""
    srv = _server(params)
    try:
        req = srv.submit(np.full((1, 5), 3.0))
        req.cancel()        # dropped at the next tick's _drop_cancelled
        try:
            req.future.result(timeout=30)
        except Exception:
            pass            # cancellation may resolve or fail the future
        deadline = time.monotonic() + 10
        while srv.snapshot()["steps_total"].get("idle", 0) == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        snap = srv.snapshot()
    finally:
        srv.stop()
    SPINE.drain()
    assert snap["steps_total"].get("idle", 0) > 0
    doc = GENPERF.document()
    assert doc["idle"]["ticks"] > 0
    assert doc["idle"]["duty_cycle"] is not None


def test_tick_error_path_visible(params, monkeypatch):
    """Satellite: a raising tick is COUNTED (snapshot + recorder +
    /genperf), not silently retried forever."""
    srv = _server(params)
    before = RECORDER.gen_tick_errors

    def boom():
        raise RuntimeError("injected tick failure")

    try:
        monkeypatch.setattr(srv, "_admit", boom)
        req = srv.submit(np.full((1, 5), 2.0))
        with pytest.raises(Exception):
            req.future.result(timeout=30)
        deadline = time.monotonic() + 10
        while srv.snapshot()["tick_errors_total"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        snap = srv.snapshot()
    finally:
        srv.stop()
    assert snap["tick_errors_total"] >= 1
    assert RECORDER.gen_tick_errors > before
    assert GENPERF.document()["tick_errors_total"] >= 1


def test_sequence_timeline_joins_causal_trace(params, monkeypatch):
    """Per-sequence lifecycle (enqueue -> admit -> prefill chunks ->
    decode rounds -> retire) is emitted as ONE gen_sequence span into
    the SAME trace tree as the submitting request."""
    monkeypatch.setattr(TRACER, "enabled", True)
    ctx = TraceContext(trace_id=new_trace_id(), span_id=new_span_id(),
                       sampled=True, puid="p-genperf")
    srv = _server(params)
    try:
        with trace_scope(ctx):
            req = srv.submit(np.full((1, 6), 4.0))
        req.future.result(timeout=30)
        _settle(srv)
    finally:
        srv.stop()
    SPINE.drain()
    spans = TRACER.by_trace(ctx.trace_id)
    seq_spans = [s for s in spans if s.name == "gen_sequence"]
    assert len(seq_spans) == 1, [s.name for s in spans]
    span = seq_spans[0]
    assert span.kind == "gen_seq"
    assert span.parent_span_id == ctx.span_id
    assert span.puid == "p-genperf"
    names = [e["name"] for e in span.events]
    assert names[0] == "enqueue"
    assert "admit" in names
    assert "prefill_chunk" in names
    assert "decode_round" in names
    assert names[-1] == "retire"
    # events are monotonically timestamped — a timeline, not a bag
    stamps = [e["ts"] for e in span.events]
    assert stamps == sorted(stamps)


def test_kill_switches_leave_zero_ring_writes(params, monkeypatch):
    """SELDON_TPU_TELEMETRY=0 + trace/perf/quality off: a FULL scheduler
    run performs ZERO ring writes and the recorder sees ZERO ticks —
    the flight recorder costs nothing when turned off."""
    monkeypatch.setattr(SPINE, "telemetry_enabled", False)
    monkeypatch.setattr(TRACER, "enabled", False)
    monkeypatch.setattr(OBSERVATORY, "enabled", False)
    monkeypatch.setattr(QUALITY, "enabled", False)
    # the cost ledger (on by default) rides the same tick records —
    # cut it too or its WANT_COST payloads keep the ring warm
    monkeypatch.setenv("SELDON_TPU_COSTLEDGER", "0")
    writes = {"n": 0}
    real_append = SPINE._append

    def counting_append(rec):
        writes["n"] += 1
        return real_append(rec)

    monkeypatch.setattr(SPINE, "_append", counting_append)
    srv = _server(params)
    try:
        srv.submit(np.full((1, 5), 5.0)).future.result(timeout=30)
        _settle(srv)
    finally:
        srv.stop()
    SPINE.drain()
    assert writes["n"] == 0
    assert GENPERF.document()["ticks"] == {}


def test_gen_continuous_kill_switch_keeps_genperf_empty(monkeypatch):
    """SELDON_TPU_GEN_CONTINUOUS=0: no scheduler exists, so /genperf
    reports scheduler: null and an empty recorder — and serving still
    works (the static-path contract lives in test_genserver)."""
    monkeypatch.setenv("SELDON_TPU_GEN_CONTINUOUS", "0")
    engine = EngineService(deployment())
    doc = engine.genperf_document()
    assert doc["scheduler"] is None
    assert doc["adaptive_chunk"] is None
    assert doc["ticks"] == {}
    assert doc["served_decode"]["served_decode_mfu_pct"] is None


# -- the REST surfaces -------------------------------------------------------


def test_genperf_endpoint_on_both_lanes():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.runtime.rest import make_engine_app

    engine = EngineService(deployment())

    async def run():
        async with TestClient(TestServer(make_engine_app(engine))) as client:
            r = await client.get("/genperf")
            assert r.status == 200
            doc = await r.json()
            assert doc["engine"]["deployment"] == "genperf-dep"
            assert "accounting" in doc and "bubbles" in doc
            assert "served_decode" in doc

    asyncio.run(run())

    from seldon_core_tpu.runtime.httpfast import serve_fast

    async def run_fast():
        import aiohttp

        server = await serve_fast(engine, "127.0.0.1", 0)
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.get(
                    f"http://127.0.0.1:{server.port}/genperf"
                ) as r:
                    assert r.status == 200
                    doc = await r.json()
                    assert "accounting" in doc and "bubbles" in doc
        finally:
            await server.stop()

    asyncio.run(run_fast())


async def _stream(engine, prompt, t_recv=None):
    raw = json.dumps({"data": {"ndarray": [prompt]}})
    frames = [json.loads(f) async for f in engine.generate_stream(
        raw, chunk=3, t_recv=t_recv)]
    assert frames[-1]["done"] is True
    return frames


def _requests_settled(engine, streams):
    """The ``requests`` block once the tick that queued the last first
    chunk has published its record (a stream's lane side is folded
    mid-tick, the scheduler's side at the tick's end)."""
    deadline = time.monotonic() + 10
    while True:
        req = engine.genperf_document()["requests"]
        if (req["first_tokens"] >= streams and req["admitted"] >= streams) \
                or time.monotonic() > deadline:
            return req
        time.sleep(0.01)


@pytest.mark.parametrize("lane_gives_recv", [True, False])
def test_request_stages_sum_to_ttft_for_every_stream(lane_gives_recv):
    """Per streamed request the four stages (recv -> submit -> admit ->
    first chunk queued -> handed to the writer) sum to its recv -> writer
    time, because they are differences of five stamps on one clock: shown
    stream by stream as deltas of the cumulative block."""
    engine = EngineService(gen_deployment())
    try:
        before = _requests_settled(engine, 0)
        assert before["streams"] == 0 and before["ttft_s"] == 0.0
        for n in range(1, 4):
            t_recv = time.perf_counter() - 0.005 if lane_gives_recv else None
            asyncio.run(_stream(engine, [1.0 + n] * (3 + n), t_recv))
            after = _requests_settled(engine, n)
            assert (after["streams"], after["admitted"],
                    after["first_tokens"]) == (n, n, n)
            stages = {k: after["stage_s"][k] - before["stage_s"][k]
                      for k in after["stage_s"]}
            assert set(stages) == {"lane_in", "queue", "prefill", "lane_out"}
            assert all(v >= 0.0 for v in stages.values()), stages
            assert sum(stages.values()) == pytest.approx(
                after["ttft_s"] - before["ttft_s"], abs=1e-9)
            if lane_gives_recv:     # the lane's 5 ms are in lane_in
                assert stages["lane_in"] >= 0.005
            assert sum(after["ttft_ms_hist"]["counts"]) == n
            before = after
    finally:
        asyncio.run(engine.close())


def test_requests_block_is_monotone_across_two_gets_on_the_fast_lane():
    """GET /genperf twice around two SSE streams on the lane the benchmark
    drives: every number of ``requests`` is cumulative, so the second
    document is nowhere below the first and a window delta is meaningful;
    a unary request is no stream and leaves the block alone."""
    from seldon_core_tpu.runtime.httpfast import serve_fast

    engine = EngineService(gen_deployment())

    def flat(req):
        return ([req["streams"], req["admitted"], req["first_tokens"],
                 req["ttft_s"]] + [req["stage_s"][k] for k in sorted(
                     req["stage_s"])] + req["ttft_ms_hist"]["counts"])

    async def run():
        import aiohttp

        server = await serve_fast(engine, "127.0.0.1", 0)
        base = f"http://127.0.0.1:{server.port}"
        docs = []
        try:
            async with aiohttp.ClientSession() as sess:
                for round_ in range(2):
                    body = {"data": {"ndarray": [[2.0, 3.0, 4.0 + round_]]},
                            "chunk": 3}
                    async with sess.post(
                            base + "/api/v0.1/generate/stream",
                            json=body) as r:
                        assert r.status == 200
                        assert b'"done": true' in await r.read()
                    async with sess.post(
                            base + "/api/v0.1/predictions",
                            json={"data": {"ndarray": [[5.0, 6.0]]}}) as r:
                        assert r.status == 200
                    await asyncio.get_running_loop().run_in_executor(
                        None, _requests_settled, engine, round_ + 1)
                    async with sess.get(base + "/genperf") as r:
                        docs.append((await r.json())["requests"])
        finally:
            await server.stop()
        return docs

    try:
        first, second = asyncio.run(run())
    finally:
        asyncio.run(engine.close())
    assert first["streams"] == 1 and second["streams"] == 2
    assert second["admitted"] == 2 and second["first_tokens"] == 2
    assert all(b >= a for a, b in zip(flat(first), flat(second)))
    assert first["ttft_ms_hist"]["edges_ms"] == \
        second["ttft_ms_hist"]["edges_ms"]
    assert second["stage_s"]["lane_in"] > 0.0   # the lane stamped t_recv


# -- scheduler phases on the profiler's clock ---------------------------------

PHASE_NAMES = {
    "GenServer._tick", "GenServer._admit", "GenServer._retire",
    "GenServer._publish", "GenServer._run/wait",
    "GenServer._prefill_tick", "GenServer._prefill_tick/build",
    "GenServer._prefill_tick/device", "GenServer._prefill_tick/readback",
    "GenServer._prefill_tick/emit",
    "GenServer._decode_round", "GenServer._decode_round/capacity",
    "GenServer._decode_round/build", "GenServer._decode_round/device",
    "GenServer._decode_round/readback", "GenServer._decode_round/emit",
}


#: the waits for a program that ran queued behind another: never a
#: ``/device``, which a trace reader pairs with the module starting inside
WAIT_NAMES = {"GenServer._decode_round/wait", "GenServer._prefill_tick/wait"}


#: the boot's phases (``_BootPhase``: the boot timeline's spans on the
#: profiler's clock), each with the phases it may open under: the device's
#: bring-up before the first tick, and a shape's first dispatch inside the
#: span that dispatches it
BOOT_NAMES = {
    "GenServer._init_device": {None},
    **{"GenServer._init_device/" + name: {"GenServer._init_device"}
       for name in ("pool", "kernels", "carry")},
    **{fn + "/first_dispatch": {fn + "/build", fn + "/device"}
       for fn in ("GenServer._prefill_tick", "GenServer._decode_round")},
}


@pytest.mark.parametrize("depth", [0, 1])
def test_scheduler_opens_every_phase_annotation_properly_nested(
        params, monkeypatch, depth, recorded_spans):
    """With jax.profiler.TraceAnnotation replaced by a recorder (no
    profiler session), one tiny scheduler run opens every phase name the
    trace reductions look for, as a well-formed stack on the scheduler
    thread, each sub-phase inside its function inside ``_tick``, and the
    span that wraps a program's dispatch -- ``/device`` where fenced, the
    second ``/build`` otherwise -- says what work the program was given
    under the server's dispatch number; the first ``/build`` says nothing.
    Held at depth 0 the names are the synchronous order's; a round ahead,
    the waits for queued programs are ``/wait`` and ``/device`` stays the
    fenced rounds' (the first, then one in ``_FENCE_EVERY``)."""
    from seldon_core_tpu.runtime import genserver as gs

    if depth == 0:
        monkeypatch.setattr(gs.GenServer, "_depth", lambda self: 0)
    else:
        monkeypatch.setattr(gs, "_FENCE_EVERY", 10 ** 9)

    log = recorded_spans.log      # (thread id, "B"/"E", name, args)
    srv = _server(params)
    try:
        reqs = [srv.submit(np.full((1, 6), i + 1.0)) for i in range(3)]
        chunks = list(srv.stream(np.full((1, 5), 7.0), chunk=3))
        for r in reqs:
            r.future.result(timeout=30)
        _settle(srv)
        time.sleep(0.05)        # the scheduler parks in _run/wait
    finally:
        srv.stop()
    assert sum(c.shape[1] for c in chunks) == 10
    assert len({t for t, *_ in log}) == 1, "phases off the scheduler thread"
    stack, parents, device_args = [], {}, {}
    for _, kind, name, args in log:
        if kind == "B":
            parents.setdefault(name, set()).add(stack[-1] if stack else None)
            stack.append(name)
            if name.endswith("/device"):
                device_args.setdefault(name, []).append(args)
        else:
            assert stack and stack.pop() == name, f"{name} closed out of turn"
    # stop() can catch the scheduler parked: at most the wait is left open
    assert stack in ([], ["GenServer._run/wait"])
    # every existing span keeps its name and its place; the boot's are
    # beside and inside them
    assert set(parents) >= set(BOOT_NAMES)
    for name, under in BOOT_NAMES.items():
        assert parents.pop(name) <= under, name
    if depth == 0:
        assert set(parents) == PHASE_NAMES
    else:
        assert PHASE_NAMES <= set(parents) <= PHASE_NAMES | WAIT_NAMES
        assert "GenServer._decode_round/wait" in parents
    assert parents["GenServer._tick"] == {None}
    assert parents["GenServer._run/wait"] == {None}
    for name, ups in parents.items():
        if "/" in name and name != "GenServer._run/wait":
            assert ups == {name.split("/")[0]}, (name, ups)
        elif name not in ("GenServer._tick", "GenServer._run/wait"):
            assert ups == {"GenServer._tick"}, (name, ups)
    for name, calls in device_args.items():
        for args in calls:
            assert {"rows", "real_rows", "nblk", "kv_positions"} <= set(args)
            assert 1 <= args["real_rows"] <= args["rows"]
            assert args["nblk"] >= 1 and args["kv_positions"] > 0
    if depth == 0:
        assert any(a["real_rows"] > 1
                   for a in device_args["GenServer._decode_round/device"])
    # fenced or not, every dispatch says its work (``dispatches`` asserts
    # the full set and the argument-less ``/build`` before it) under one
    # number a server, rising by one a program across both kinds
    work = {kind: recorded_spans.dispatches(kind)
            for kind in ("decode", "prefill")}
    seqs = sorted(a["seq"] for calls in work.values() for a in calls)
    assert seqs == list(range(1, len(seqs) + 1))
    for kind, calls in work.items():
        assert calls and [a["seq"] for a in calls] == sorted(
            a["seq"] for a in calls)
        fenced = len(device_args.get(
            recorded_spans.FUNCTIONS[kind] + "/device", []))
        if depth == 0:
            assert fenced == len(calls)
        elif kind == "decode":
            assert fenced < len(calls)      # some rode a second /build
        # what was dispatched is read back under its own number, once
        assert sorted(a["seq"] for a in recorded_spans.carrying(
            "/emit", kind)) == [a["seq"] for a in calls]
        assert all(set(a) == {"seq"} and a["seq"] in seqs
                   for a in recorded_spans.carrying("/wait", kind))
    assert all(a["expert_slots"] == 0 and a["passes"] == srv.span
               and a["blocks"] == srv.span
               for a in work["decode"])


def test_dispatching_spans_sum_to_the_genperf_counters(params,
                                                       recorded_spans):
    """Over a server's life the arguments of its dispatching spans add up
    to what ``/genperf`` counted: they are the tick record's own numbers,
    so a trace reader that sums the calls it joined and a counter reader
    that subtracts two documents speak of the same work."""
    srv = _server(params)
    try:
        reqs = [srv.submit(np.full((1, 5 + 3 * i), i + 1.0))
                for i in range(4)]
        for r in reqs:
            r.future.result(timeout=30)
        _settle(srv)
        time.sleep(0.05)
        SPINE.drain()
        doc = GENPERF.document()
    finally:
        srv.stop()
    decode = recorded_spans.dispatches("decode")
    prefill = recorded_spans.dispatches("prefill")
    served = doc["served_decode"]
    assert sum(a["kv_positions"] for a in decode) == served["kv_positions"]
    assert sum(a["passes"] for a in decode) == served["passes"]
    assert sum(a["passes"] * a["real_rows"] for a in decode) == \
        served["row_passes"]
    assert len(decode) * srv.span == served["device_steps"]
    assert len(prefill) == doc["served_prefill"]["calls"]
    assert sum(a["tokens"] for a in prefill) == \
        doc["served_prefill"]["tokens"] == sum(5 + 3 * i for i in range(4))
    # a prompt's chunks attend causally: over its calls, n(n+1)/2 positions
    assert sum(a["attended"] for a in prefill) == sum(
        n * (n + 1) // 2 for n in (5 + 3 * i for i in range(4)))
    assert sum(a["kv_positions"] for a in prefill) >= sum(
        a["tokens"] for a in prefill)
    assert doc["rows"]["real_total"] == sum(
        a["real_rows"] for a in decode + prefill)


def test_new_metric_families_registered():
    for fam in (
        "seldon_tpu_gen_step_seconds",
        "seldon_tpu_gen_bubble_seconds_total",
        "seldon_tpu_gen_served_mfu",
        "seldon_tpu_gen_kv_block_age_seconds",
        "seldon_tpu_gen_tick_errors_total",
    ):
        assert fam in TPU_METRIC_FAMILIES
    RECORDER.record_gen_step_seconds("decode", "decode", 0.004)
    RECORDER.record_gen_bubble("host", 0.002)
    RECORDER.record_gen_kv_block_age(1.5)
    RECORDER.set_gen_served_mfu(0.12)
    snap = RECORDER.snapshot()["generation"]["continuous"]
    assert snap["bubble_seconds"].get("host", 0) >= 0.002
    assert snap["served_mfu"] == 0.12
    if RECORDER.registry is not None:
        text = RECORDER.exposition().decode()
        assert "seldon_tpu_gen_bubble_seconds_total" in text
        assert "seldon_tpu_gen_served_mfu" in text


# -- /genperf and /stats say what they said before models/served.py ------------

import served_kinds  # noqa: E402, I001 - tests/served_kinds.py, beside this file


@pytest.mark.parametrize("kind", list(served_kinds.KINDS))
def test_a_scripted_run_reads_what_it_read_at_the_parent_commit(
        kind, monkeypatch):
    """One scripted run of a generator of ``kind`` (tests/served_kinds.py:
    a fixed seed, three requests one after the other) against the fixture
    the commit BEFORE the description was written from (PR 44: the scheduler
    then reckoned every count itself): /genperf ``served_decode`` and
    ``served_prefill`` and /stats ``genserver``, key for key and value for
    value, the wall-clock fields left out by name
    (``served_kinds.WALL_CLOCK``).  Counters later PRs added stand in it
    at what such a run counts: ``ssm_fused_steps`` 0 (PR 47);
    ``shared_passes`` (PR 48) 0 but for the diffusion run's six rounds of
    two blocks, 6, whose ``experts_read`` fell from 448 to 436 with them
    -- a shared pass reads the union of two passes' picks once; since a
    round leaves its last block's K/V pass to the next (PR 51) that run
    shares 9 (one in each of three first rounds, two in each of three
    second ones), its four rows RUN 76 passes of the 80 their rounds'
    blocks count (no row's last block is written), and it reads 419;
    ``programs.stored_at_boot`` (PR 53) 0: such a run keeps no store;
    ``experts_fused_passes`` / ``experts_fused_calls`` (PR 56) 0: on the
    CPU the two grouped matmuls serve."""
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "resources", "genperf_parent.json")
    with open(path) as f:
        want = json.load(f)[kind]
    got = json.loads(json.dumps(served_kinds.scripted_run(kind, monkeypatch)))
    assert set(got) == set(want)
    for section in want:
        assert got[section] == want[section], section
