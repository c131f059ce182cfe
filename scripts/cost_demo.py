"""Cost-attribution demo: two tenants with skewed load, and the
resource-attribution ledger (utils/costledger.py) proving who consumed
the chip — end to end, all in-process, CPU only (no TPU required).

Three arms:

  * **batcher arm** — five concurrent requests from two tenants
    ("team-a": 3x1 row, "team-b": 1x2 rows) coalesce into ONE padded
    micro-batch flush (5 real rows -> pow-2 bucket of 8).  The flush
    record's fenced wall must split 3:2 across the tenants, the 3-row
    pad remainder must split 3:2 as pad tax, and the accounting
    identity ``attributed + pad_tax + idle + unattributed == wall``
    must hold exactly;
  * **genserver arm** — a tiny LM under the continuous-batching
    scheduler serves an interactive tenant ("anna", light) against an
    offline tenant ("bob", heavy: 3x the rows, longer prompts).  The
    per-tick attribution payloads must land the skew (bob's
    device-seconds > anna's), integrate KV-block-seconds for both, and
    keep ``accounted_fraction == 1.0``;
  * **WFQ arm** — the usage-weighted fair queue
    (``SELDON_TPU_QOS_USAGE_WEIGHTED=1``): after the ledger has seen a
    hog tenant burn 9x the device-seconds per request of a light
    tenant, an interleaved backlog must drain the light tenant FIRST
    (vs the unweighted baseline's strict alternation) — the virtual
    clock advancing by attributed cost, not request count.

Each arm ASSERTS (exit 1 on failure — the CI lane is non-blocking but
the artifact says pass/fail loudly).

Artifacts:

    <out>/costs.json    the genserver arm's full /costs document plus
                        per-arm numbers and pass/fail per assertion

Run via ``make cost-demo``; CI uploads the artifact from a non-blocking
lane, mirroring ``overload-demo`` / ``scale-demo``."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

# script lives in scripts/ — put the repo root on the path; the demo is
# CPU-sized, so never fight for (or fault on) an accelerator
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

REL_EPS = 1e-3  # accounting rounds to 1e-6; arms run O(10ms) walls


def _identity_gap(acct) -> float:
    """|attributed + pad + idle + unattributed - wall| / wall."""
    wall = acct["device_wall_s"]
    if wall <= 0:
        return 0.0
    lhs = (acct["attributed_s"] + acct["pad_tax_s"] + acct["idle_s"]
           + acct["unattributed_s"])
    return abs(lhs - wall) / wall


async def _batcher_arm(doc):
    from seldon_core_tpu.runtime.batching import MicroBatcher
    from seldon_core_tpu.runtime.qos import qos_scope
    from seldon_core_tpu.utils.costledger import LEDGER
    from seldon_core_tpu.utils.hotrecord import SPINE

    LEDGER.reset()

    async def batch_fn(x):
        await asyncio.sleep(0.02)  # a deterministic "device" wall
        return np.zeros((len(x), 1)), {}

    mb = MicroBatcher(batch_fn, max_batch=8, max_wait_ms=100.0,
                      pad_to_buckets=True, coalesce_ms=50.0)
    mb.cost_deployment = "demo"

    async def one(tenant, rows):
        with qos_scope(tenant):
            await mb.submit(np.ones((rows, 4)))

    # all five land in the same event-loop tick, inside the coalesce
    # window: ONE shared flush of 5 real rows padded to 8
    await asyncio.gather(
        one("team-a", 1), one("team-a", 1), one("team-a", 1),
        one("team-b", 2),
    )
    SPINE.drain()
    full = LEDGER.document()
    acct = full["accounting"]
    rows = {r["tenant"]: r for r in full["tenants"]}
    dev_a = rows["team-a"]["device_s"].get("batch", 0.0)
    dev_b = rows["team-b"]["device_s"].get("batch", 0.0)
    pad_a = rows["team-a"]["pad_tax_s"]
    pad_b = rows["team-b"]["pad_tax_s"]
    checks = {
        "batcher_single_shared_flush": acct["folds"] == 1,
        "batcher_identity_holds": _identity_gap(acct) < REL_EPS,
        "batcher_accounted_fraction_1": acct["accounted_fraction"] >= 0.999,
        # 3 real rows vs 2 real rows sharing one fenced wall
        "batcher_device_split_3_to_2":
            dev_b > 0 and abs(dev_a / dev_b - 1.5) < REL_EPS,
        # the 3 pad rows are taxed by the same real shares
        "batcher_pad_tax_split_3_to_2":
            pad_b > 0 and abs(pad_a / pad_b - 1.5) < REL_EPS,
    }
    doc["batcher_arm"] = {
        "accounting": acct,
        "team_a": {"device_s": dev_a, "pad_tax_s": pad_a},
        "team_b": {"device_s": dev_b, "pad_tax_s": pad_b},
        "checks": checks,
    }
    return checks


def _genserver_arm(doc):
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.transformer import LMConfig, lm_init
    from seldon_core_tpu.runtime.genserver import GenServer
    from seldon_core_tpu.runtime.qos import qos_scope
    from seldon_core_tpu.utils.costledger import LEDGER
    from seldon_core_tpu.utils.hotrecord import SPINE

    LEDGER.reset()
    cfg = LMConfig(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                   dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    srv = GenServer(params, cfg, max_new_tokens=8, block_size=4,
                    num_blocks=64, slots=8, span=3, prefill_chunk=4)
    srv.cost_deployment = "demo"
    rng = np.random.default_rng(0)
    try:
        reqs = []
        # anna: interactive, light — 2 requests, 1 short row each
        for _ in range(2):
            with qos_scope("anna", "interactive"):
                reqs.append(srv.submit(
                    rng.integers(0, 48, size=(1, 4)).astype(float),
                    tier="interactive"))
        # bob: offline, heavy — 2 requests, 3 long rows each
        for _ in range(2):
            with qos_scope("bob", "offline"):
                reqs.append(srv.submit(
                    rng.integers(0, 48, size=(3, 10)).astype(float),
                    tier="offline"))
        for r in reqs:
            r.future.result(timeout=180)
        # retirement (and its KV release) runs a beat after the last token
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            s = srv.snapshot()
            if not s["inflight_sequences"] and not s["waiting_sequences"]:
                break
            time.sleep(0.01)
    finally:
        srv.stop()
    SPINE.drain()
    full = LEDGER.document()
    acct = full["accounting"]
    rows = {r["tenant"]: r for r in full["tenants"]}

    def _dev(t):
        return sum(rows.get(t, {}).get("device_s", {}).values())

    def _tier_cost_per_tok(tier):
        dev = toks = 0.0
        for name, t in full["tiers"].items():
            if name.startswith(tier + "/"):
                dev += t["device_s"]
                toks += t["served_tokens"]
        return dev / toks if toks else None

    inter = _tier_cost_per_tok("interactive")
    off = _tier_cost_per_tok("offline")
    checks = {
        "genserver_identity_holds": _identity_gap(acct) < REL_EPS,
        "genserver_accounted_fraction_1":
            acct["accounted_fraction"] >= 0.999,
        "genserver_nothing_unattributed": acct["unattributed_s"] == 0.0,
        # 6 long offline rows vs 2 short interactive rows: the skew must
        # land in the attributed table
        "genserver_skew_attributed": _dev("bob") > _dev("anna"),
        "genserver_kv_block_seconds_both": (
            rows.get("anna", {}).get("kv_block_s", 0.0) > 0
            and rows.get("bob", {}).get("kv_block_s", 0.0) > 0),
        "genserver_both_tiers_priced":
            inter is not None and off is not None,
    }
    doc["genserver_arm"] = {
        "accounting": acct,
        "anna_device_s": round(_dev("anna"), 6),
        "bob_device_s": round(_dev("bob"), 6),
        "anna_kv_block_s": rows.get("anna", {}).get("kv_block_s", 0.0),
        "bob_kv_block_s": rows.get("bob", {}).get("kv_block_s", 0.0),
        "cost_per_tok_interactive_s": inter,
        "cost_per_tok_offline_s": off,
        "checks": checks,
    }
    doc["costs"] = full
    doc["cost_attributed_fraction"] = acct["accounted_fraction"]
    if inter and off:
        doc["cost_per_1k_tok_interactive_vs_offline_x"] = round(
            inter / off, 3)
    return checks


async def _wfq_order(weighted: bool):
    """Grant order for an interleaved 4+4 backlog behind one busy slot."""
    from seldon_core_tpu.runtime.qos import TenantGovernor
    from seldon_core_tpu.utils.costledger import LEDGER

    LEDGER.reset()
    # the ledger has watched: hog burns 9x the device-seconds per
    # request of light (seeded through the public fold path)
    LEDGER.fold_flush(
        {"dep": "demo", "padded": 1,
         "tenants": [("hog", "interactive", 1, 10, 0)]}, 9.0)
    LEDGER.fold_flush(
        {"dep": "demo", "padded": 1,
         "tenants": [("light", "interactive", 1, 10, 0)]}, 1.0)
    if weighted:
        os.environ["SELDON_TPU_QOS_USAGE_WEIGHTED"] = "1"
    try:
        gov = TenantGovernor(rate=0.0, burst=0.0, fair_inflight=1)
        assert gov._acquire_nowait("warm")  # occupy the single slot
        order = []
        futs = []
        for _ in range(4):
            for tenant in ("hog", "light"):
                fut = gov._enqueue(tenant)
                fut.add_done_callback(
                    lambda _f, t=tenant: order.append(t))
                futs.append(fut)
        for _ in range(8):
            gov._release()  # grant the smallest virtual start tag
        await asyncio.gather(*futs)
        await asyncio.sleep(0)  # drain the done-callbacks
        return order
    finally:
        os.environ.pop("SELDON_TPU_QOS_USAGE_WEIGHTED", None)


async def _wfq_arm(doc):
    baseline = await _wfq_order(weighted=False)
    weighted = await _wfq_order(weighted=True)
    checks = {
        # unweighted SFQ treats the requests as equal: strict alternation
        "wfq_baseline_alternates":
            baseline[:4].count("light") == 2,
        # cost-weighted: the hog's virtual clock runs ~9x faster, so the
        # light tenant's backlog drains ahead of the hog's
        "wfq_weighted_reorders_light_first":
            weighted[2:6].count("light") >= 3,
    }
    doc["wfq_arm"] = {
        "baseline_grant_order": baseline,
        "weighted_grant_order": weighted,
        "checks": checks,
    }
    return checks


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="cost_demo")
    args = parser.parse_args()

    from seldon_core_tpu.utils.costledger import LEDGER

    doc = {}
    checks = asyncio.run(_batcher_arm(doc))
    checks.update(_genserver_arm(doc))
    checks.update(asyncio.run(_wfq_arm(doc)))
    LEDGER.reset()
    doc["checks"] = checks
    doc["ok"] = all(checks.values())

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "costs.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    b = doc["batcher_arm"]
    g = doc["genserver_arm"]
    print(f"batcher arm    team-a/team-b device split "
          f"{b['team_a']['device_s']:.4f}/{b['team_b']['device_s']:.4f} s "
          f"(3:2), pad tax {b['team_a']['pad_tax_s']:.4f}/"
          f"{b['team_b']['pad_tax_s']:.4f} s")
    print(f"genserver arm  anna {g['anna_device_s']:.4f} s vs bob "
          f"{g['bob_device_s']:.4f} s attributed; accounted_fraction "
          f"{g['accounting']['accounted_fraction']}")
    print(f"wfq arm        baseline {doc['wfq_arm']['baseline_grant_order']}"
          f" -> weighted {doc['wfq_arm']['weighted_grant_order']}")
    for name, ok in checks.items():
        print(f"  [{'ok' if ok else 'FAIL'}] {name}")
    print(f"artifact: {path}")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
