"""The resource-attribution ledger (utils/costledger.py).

What these tests pin, per the PR-19 acceptance list:

  * the attribution rule itself, with HAND-COMPUTED expected values on
    both producer lanes — a shared micro-batcher flush splitting one
    fenced wall across tenants (plus the pad-tax remainder), and a
    generate-scheduler tick splitting per-phase walls with bubbles
    booked to idle and KV releases integrated to block-seconds;
  * the accounting identity ``attributed + pad_tax + idle +
    unattributed == device_wall`` under RANDOM fold traces (property
    test), not just the happy path;
  * the kill switch ``SELDON_TPU_COSTLEDGER=0``: zero fold work, and
    bit-identical serving outputs;
  * the usage-weighted WFQ hook (``usage_advance`` ratios + clamps and
    the virtual-clock reordering behind ``SELDON_TPU_QOS_USAGE_-
    WEIGHTED=1``);
  * the federation contract: ``merge_cost_documents`` is pure
    summation, and a single-engine fleet's gateway ``/costs`` equals
    the engine's own document.

The conftest autouse fixture resets LEDGER between tests; tests that
fold real traffic still reset explicitly at their start so pre-test
imports can't leak spend into hand-computed expectations.
"""

import asyncio
import random

import numpy as np
import pytest

from seldon_core_tpu.utils.costledger import (
    LEDGER,
    CostLedger,
    costledger_enabled,
    merge_cost_documents,
    usage_weighted_enabled,
)
from seldon_core_tpu.utils.hotrecord import SPINE


def _identity_gap(acct) -> float:
    wall = acct["device_wall_s"]
    if wall <= 0:
        return 0.0
    lhs = (acct["attributed_s"] + acct["pad_tax_s"] + acct["idle_s"]
           + acct["unattributed_s"])
    return abs(lhs - wall) / wall


# ---- the attribution rule, hand-computed ----------------------------


def test_fold_flush_hand_computed_split():
    """5 real units padded to 8, 100 ms wall: every share is exact.

    a has 3 units, b has 2.  Attributed: wall * units / 8 ->
    a = 0.0375, b = 0.025.  Pad remainder wall * 3/8 = 0.0375 splits
    by real share (3:2) -> a = 0.0225, b = 0.015.  Everything sums
    back to the wall.
    """
    led = CostLedger()
    led.fold_flush(
        {"dep": "d", "padded": 8,
         "tenants": [("a", "interactive", 3, 3, 30),
                     ("b", "offline", 2, 1, 20)]},
        0.1)
    assert led.device_s[("a", "d", "batch")] == pytest.approx(0.0375)
    assert led.device_s[("b", "d", "batch")] == pytest.approx(0.025)
    assert led.pad_tax_s[("a", "d")] == pytest.approx(0.0225)
    assert led.pad_tax_s[("b", "d")] == pytest.approx(0.015)
    assert led.served_tokens[("a", "d", "batch")] == 30
    assert led.tier_device_s[("interactive", "batch")] == pytest.approx(
        0.0375 + 0.0225)
    acct = led._accounting_locked()
    assert acct["folds"] == 1
    assert acct["unattributed_s"] == 0.0
    assert acct["accounted_fraction"] == pytest.approx(1.0)
    assert _identity_gap(acct) < 1e-9


def test_fold_flush_zero_unit_rows_book_counts_not_device():
    """A zero-unit row (tokens emitted by an earlier dispatch) books
    its request/served-token counts but takes no device or pad share —
    the co-batched real row keeps the whole wall."""
    led = CostLedger()
    led.fold_flush(
        {"dep": "d", "padded": 4,
         "tenants": [("real", "", 4, 1, 4), ("ghost", "", 0, 1, 7)]},
        0.2)
    assert led.device_s[("real", "d", "batch")] == pytest.approx(0.2)
    assert led.device_s.get(("ghost", "d", "batch"), 0.0) == 0.0
    assert ("ghost", "d") not in led.pad_tax_s
    assert led.served_tokens[("ghost", "d", "batch")] == 7
    assert led._usage["ghost"][1] == 1.0  # request counted for WFQ mean
    assert _identity_gap(led._accounting_locked()) < 1e-9


def test_fold_flush_without_rows_is_unattributed():
    led = CostLedger()
    led.fold_flush({"dep": "d", "padded": 0, "tenants": []}, 0.05)
    acct = led._accounting_locked()
    assert acct["unattributed_s"] == pytest.approx(0.05)
    assert acct["attributed_s"] == 0.0
    # the 0.97 alert keys off this: unattributed time is NOT accounted
    assert acct["accounted_fraction"] == 0.0
    assert _identity_gap(acct) < 1e-9


def test_fold_gen_tick_hand_computed_two_phases():
    """One scheduler tick, both phases + a bubble + KV releases.

    prefill: 60 ms over cap 12 (real 9: a=6, b=3) ->
      a = 0.03, b = 0.015; pad 60ms*3/12 = 0.015 splits 2:1.
    decode: 40 ms over cap 4 (real 2: a=1, b=1) ->
      each 0.01; pad 0.02 splits 1:1.
    bubble 50 ms -> idle.  Sum = 150 ms wall, fraction 1.0.
    """
    led = CostLedger()
    led.fold_gen_tick({
        "device_phases": {"prefill": 0.06, "decode": 0.04},
        "bubble_s": 0.05,
        "attr": {
            "dep": "lm",
            "phases": {
                "prefill": {"padded": 12, "tenants": [
                    ("a", "interactive", 6, 1, 0),
                    ("b", "offline", 3, 1, 0)]},
                "decode": {"padded": 4, "tenants": [
                    ("a", "interactive", 1, 0, 1),
                    ("b", "offline", 1, 0, 1)]},
            },
            "kv": (("a", 0.75), ("b", 1.25)),
        },
    })
    assert led.device_s[("a", "lm", "prefill")] == pytest.approx(0.03)
    assert led.device_s[("b", "lm", "prefill")] == pytest.approx(0.015)
    assert led.device_s[("a", "lm", "decode")] == pytest.approx(0.01)
    assert led.device_s[("b", "lm", "decode")] == pytest.approx(0.01)
    assert led.pad_tax_s[("a", "lm")] == pytest.approx(0.01 + 0.01)
    assert led.pad_tax_s[("b", "lm")] == pytest.approx(0.005 + 0.01)
    assert led.kv_block_s[("a", "lm")] == pytest.approx(0.75)
    assert led.kv_block_s[("b", "lm")] == pytest.approx(1.25)
    acct = led._accounting_locked()
    assert acct["device_wall_s"] == pytest.approx(0.15)
    assert acct["idle_s"] == pytest.approx(0.05)
    assert acct["unattributed_s"] == 0.0
    assert acct["accounted_fraction"] == pytest.approx(1.0)
    assert _identity_gap(acct) < 1e-9


def test_fold_gen_tick_phase_without_attr_is_unattributed():
    """A fenced phase wall with no attribution payload must still be
    conserved — it lands in unattributed_s and DRAGS the accounted
    fraction down (that is what the <0.97 alert watches)."""
    led = CostLedger()
    led.fold_gen_tick({
        "device_phases": {"prefill": 0.02, "decode": 0.03},
        "bubble_s": 0.0,
        "attr": {"dep": "lm", "phases": {
            "prefill": {"padded": 2, "tenants": [("a", "", 2, 1, 0)]},
        }},
    })
    acct = led._accounting_locked()
    assert acct["attributed_s"] == pytest.approx(0.02)
    assert acct["unattributed_s"] == pytest.approx(0.03)
    assert acct["accounted_fraction"] == pytest.approx(0.4)
    assert _identity_gap(acct) < 1e-9


# ---- producer lanes, end to end -------------------------------------


def test_batcher_lane_shared_flush_splits_by_real_rows():
    """The real spine path: five concurrent submits from two tenants
    coalesce into ONE padded flush; after draining the spine the ledger
    holds the hand-computed 3:2 split on device time and pad tax."""
    from seldon_core_tpu.runtime.batching import MicroBatcher
    from seldon_core_tpu.runtime.qos import qos_scope

    # what an earlier test of this process left on the spine is not this
    # test's to fold, and a loaded machine may stall the loop for longer
    # than 50 ms between two of the submits below (ROADMAP C11: seen once
    # under six workers, PR 44): the window is wide, the split is the same
    SPINE.drain()
    LEDGER.reset()

    async def run():
        async def batch_fn(x):
            await asyncio.sleep(0.02)
            return np.zeros((len(x), 1)), {}

        mb = MicroBatcher(batch_fn, max_batch=8, max_wait_ms=1000.0,
                          pad_to_buckets=True, coalesce_ms=400.0)
        mb.cost_deployment = "dep"

        async def one(tenant, rows):
            with qos_scope(tenant):
                await mb.submit(np.ones((rows, 4)))

        await asyncio.gather(
            one("team-a", 1), one("team-a", 1), one("team-a", 1),
            one("team-b", 2),
        )

    asyncio.run(run())
    SPINE.drain()
    acct = LEDGER._accounting_locked()
    assert acct["folds"] == 1, "expected one shared coalesced flush"
    dev_a = LEDGER.device_s[("team-a", "dep", "batch")]
    dev_b = LEDGER.device_s[("team-b", "dep", "batch")]
    pad_a = LEDGER.pad_tax_s[("team-a", "dep")]
    pad_b = LEDGER.pad_tax_s[("team-b", "dep")]
    assert dev_a / dev_b == pytest.approx(1.5)
    assert pad_a / pad_b == pytest.approx(1.5)
    # 5 real of 8 dispatched: pad tax is 3/5 of the attributed time
    assert (pad_a + pad_b) / (dev_a + dev_b) == pytest.approx(0.6)
    assert acct["accounted_fraction"] == pytest.approx(1.0)
    # the accounting block rounds to 1e-6 and this wall is O(20ms):
    # the rounded identity closes to ~1e-4 relative, not machine eps
    assert _identity_gap(acct) < 1e-3


def test_genserver_lane_attributes_both_tenants():
    """Continuous-batching lane: two tenants share real scheduler
    ticks; the ledger must attribute prefill+decode walls to both,
    integrate KV-block-seconds, and close the identity."""
    import time

    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.transformer import LMConfig, lm_init
    from seldon_core_tpu.runtime.genserver import GenServer
    from seldon_core_tpu.runtime.qos import qos_scope

    LEDGER.reset()
    cfg = LMConfig(vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                   dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    srv = GenServer(params, cfg, max_new_tokens=4, block_size=4,
                    num_blocks=32, slots=4, span=2, prefill_chunk=4)
    srv.cost_deployment = "lm"
    rng = np.random.default_rng(0)
    try:
        reqs = []
        with qos_scope("anna", "interactive"):
            reqs.append(srv.submit(
                rng.integers(0, 32, size=(1, 3)).astype(float),
                tier="interactive"))
        with qos_scope("bob", "offline"):
            reqs.append(srv.submit(
                rng.integers(0, 32, size=(2, 6)).astype(float),
                tier="offline"))
        for r in reqs:
            r.future.result(timeout=180)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            s = srv.snapshot()
            if not s["inflight_sequences"] and not s["waiting_sequences"]:
                break
            time.sleep(0.01)
    finally:
        srv.stop()
    SPINE.drain()
    doc = LEDGER.document()
    acct = doc["accounting"]
    rows = {r["tenant"]: r for r in doc["tenants"]}
    assert acct["unattributed_s"] == 0.0
    assert acct["accounted_fraction"] >= 0.999
    assert _identity_gap(acct) < 1e-3
    for tenant in ("anna", "bob"):
        assert sum(rows[tenant]["device_s"].values()) > 0
        assert rows[tenant]["kv_block_s"] > 0
    # 2 long offline rows vs 1 short interactive row: skew must land
    assert (sum(rows["bob"]["device_s"].values())
            > sum(rows["anna"]["device_s"].values()))


# ---- the identity, adversarially ------------------------------------


def test_identity_holds_under_random_fold_traces():
    """Property test: whatever mix of flushes, gen ticks, bubbles,
    attr-less phases, zero-unit rows and under-padded dispatches the
    producers throw at it, every cent of device wall lands in exactly
    one bucket."""
    rng = random.Random(19)
    led = CostLedger()
    tenants = ["a", "b", "c", ""]
    tiers = ["interactive", "offline", ""]
    for _ in range(300):
        if rng.random() < 0.5:
            rows = [(rng.choice(tenants), rng.choice(tiers),
                     rng.choice([0, 1, 2, 5]), rng.randint(0, 3),
                     rng.randint(0, 50))
                    for _ in range(rng.randint(0, 4))]
            led.fold_flush(
                {"dep": rng.choice(["d1", "d2"]),
                 # sometimes UNDER the real sum: cap clamps to real
                 "padded": rng.choice([0, 1, 4, 8]),
                 "tenants": rows},
                rng.random() * 0.01)
        else:
            phases = {}
            for ph in ("prefill", "decode"):
                if rng.random() < 0.8:
                    phases[ph] = {
                        "padded": rng.choice([0, 2, 8]),
                        "tenants": [
                            (rng.choice(tenants), rng.choice(tiers),
                             rng.choice([0, 1, 3]), rng.randint(0, 2),
                             rng.randint(0, 9))
                            for _ in range(rng.randint(0, 3))],
                    }
            led.fold_gen_tick({
                "device_phases": {
                    ph: rng.random() * 0.01
                    for ph in ("prefill", "decode")
                    if rng.random() < 0.9},
                "bubble_s": rng.choice([0.0, rng.random() * 0.005]),
                "attr": {"dep": "lm", "phases": phases,
                         "kv": tuple(
                             (rng.choice(tenants), rng.random())
                             for _ in range(rng.randint(0, 2)))},
            })
    acct = led._accounting_locked()
    assert acct["device_wall_s"] > 0
    assert _identity_gap(acct) < 1e-6


# ---- kill switch ----------------------------------------------------


def test_kill_switch_zero_fold_work_and_identical_outputs(monkeypatch):
    """SELDON_TPU_COSTLEDGER=0: the producers attach nothing, the
    drainer folds nothing, and the served bytes are bit-identical."""
    from seldon_core_tpu.runtime.batching import MicroBatcher
    from seldon_core_tpu.runtime.qos import qos_scope

    def serve():
        async def run():
            async def batch_fn(x):
                return x * 2.0, {}

            mb = MicroBatcher(batch_fn, max_batch=8, max_wait_ms=50.0,
                              pad_to_buckets=True, coalesce_ms=20.0)
            mb.cost_deployment = "dep"

            async def one(tenant, seed):
                with qos_scope(tenant):
                    return await mb.submit(
                        np.arange(4, dtype=np.float64).reshape(1, 4)
                        + seed)

            return await asyncio.gather(
                one("a", 0.0), one("a", 1.0), one("b", 2.0))

        return asyncio.run(run())

    assert costledger_enabled()
    LEDGER.reset()
    on = serve()
    SPINE.drain()
    assert LEDGER.folds > 0

    monkeypatch.setenv("SELDON_TPU_COSTLEDGER", "0")
    assert not costledger_enabled()
    LEDGER.reset()
    off = serve()
    SPINE.drain()
    assert LEDGER.folds == 0
    assert LEDGER.wall_s == 0.0
    assert not LEDGER.device_s and not LEDGER.bytes_by
    assert LEDGER.document()["enabled"] is False
    for (y_on, _aux_on), (y_off, _aux_off) in zip(on, off):
        np.testing.assert_array_equal(np.asarray(y_on), np.asarray(y_off))


# ---- usage-weighted WFQ ---------------------------------------------


def test_usage_advance_ratio_and_clamps():
    LEDGER.reset()
    # hog: 9 s over 10 requests; light: 1 s over 10 requests
    LEDGER.fold_flush({"dep": "d", "padded": 1,
                       "tenants": [("hog", "", 1, 10, 0)]}, 9.0)
    LEDGER.fold_flush({"dep": "d", "padded": 1,
                       "tenants": [("light", "", 1, 10, 0)]}, 1.0)
    # global mean 0.5 s/req: hog 0.9/0.5 = 1.8; light 0.1/0.5 = 0.2,
    # clamped up to the 0.25 floor
    assert LEDGER.usage_advance("hog") == pytest.approx(1.8)
    assert LEDGER.usage_advance("light") == pytest.approx(0.25)
    assert LEDGER.usage_advance("stranger") == 1.0
    assert LEDGER.usage_advance("") == 1.0


def test_usage_weighted_wfq_reorders_grants(monkeypatch):
    """With SELDON_TPU_QOS_USAGE_WEIGHTED=1 the hog's virtual clock
    advances 9x faster, so an interleaved backlog drains the light
    tenant first; unweighted, grants strictly alternate."""
    from seldon_core_tpu.runtime.qos import TenantGovernor

    def grant_order():
        async def run():
            gov = TenantGovernor(rate=0.0, burst=0.0, fair_inflight=1)
            assert gov._acquire_nowait("warm")
            order, futs = [], []
            for _ in range(4):
                for tenant in ("hog", "light"):
                    fut = gov._enqueue(tenant)
                    fut.add_done_callback(
                        lambda _f, t=tenant: order.append(t))
                    futs.append(fut)
            for _ in range(8):
                gov._release()
            await asyncio.gather(*futs)
            await asyncio.sleep(0)
            return order

        return asyncio.run(run())

    def seed():
        LEDGER.reset()
        LEDGER.fold_flush({"dep": "d", "padded": 1,
                           "tenants": [("hog", "", 1, 10, 0)]}, 9.0)
        LEDGER.fold_flush({"dep": "d", "padded": 1,
                           "tenants": [("light", "", 1, 10, 0)]}, 1.0)

    seed()
    assert not usage_weighted_enabled()
    baseline = grant_order()
    assert baseline[:4].count("light") == 2  # strict alternation

    monkeypatch.setenv("SELDON_TPU_QOS_USAGE_WEIGHTED", "1")
    assert usage_weighted_enabled()
    seed()
    weighted = grant_order()
    assert weighted[2:6].count("light") >= 3  # light drains first


# ---- federation -----------------------------------------------------


def _seeded_document():
    LEDGER.reset()
    LEDGER.fold_flush(
        {"dep": "d", "padded": 8,
         "tenants": [("a", "interactive", 3, 3, 30),
                     ("b", "offline", 2, 1, 20)]}, 0.1)
    LEDGER.fold_gen_tick({
        "device_phases": {"decode": 0.04},
        "bubble_s": 0.01,
        "attr": {"dep": "lm", "phases": {
            "decode": {"padded": 4,
                       "tenants": [("a", "interactive", 1, 0, 1)]}},
            "kv": (("a", 0.5),)},
    })
    LEDGER.note_bytes("a", "d", "wire", 1000)
    return LEDGER.document()


def test_merge_cost_documents_sums_two_replicas():
    doc = _seeded_document()
    merged = merge_cost_documents([doc, doc, None])
    rows = {(r["tenant"], r["deployment"]): r for r in merged["tenants"]}
    one = {(r["tenant"], r["deployment"]): r for r in doc["tenants"]}
    assert set(rows) == set(one)
    for key, r in one.items():
        for ph, v in r["device_s"].items():
            assert rows[key]["device_s"][ph] == pytest.approx(2 * v)
        assert rows[key]["pad_tax_s"] == pytest.approx(
            2 * r["pad_tax_s"])
    assert rows[("a", "lm")]["kv_block_s"] == pytest.approx(1.0)
    assert rows[("a", "d")]["bytes"]["wire"] == 2000
    acct = merged["accounting"]
    assert acct["device_wall_s"] == pytest.approx(
        2 * doc["accounting"]["device_wall_s"])
    assert acct["folds"] == 2 * doc["accounting"]["folds"]
    # summing preserves the fraction (both replicas fully accounted)
    assert acct["accounted_fraction"] == pytest.approx(
        doc["accounting"]["accounted_fraction"], abs=1e-5)
    assert merged["capacity"]["chips"] == 2 * doc["capacity"]["chips"]
    assert _identity_gap(acct) < 1e-4


def test_single_engine_gateway_rollup_equals_engine_document(monkeypatch):
    """Acceptance: engine /costs and gateway /costs agree for a
    single-engine fleet — in-process engines share the gateway's
    process-global ledger, and merging one document is the identity."""
    from seldon_core_tpu.gateway import fleet

    monkeypatch.setenv("SELDON_TPU_FLEET", "0")
    engine_doc = _seeded_document()
    gw_doc = asyncio.run(fleet.costs_document(object()))
    assert gw_doc["federated"] is False
    assert gw_doc["tenants"] == engine_doc["tenants"]
    assert gw_doc["tiers"] == engine_doc["tiers"]
    for k, v in engine_doc["accounting"].items():
        assert gw_doc["accounting"][k] == pytest.approx(v, abs=1e-5)
    assert gw_doc["capacity"]["chips"] == engine_doc["capacity"]["chips"]
    assert gw_doc["enabled"] is True
