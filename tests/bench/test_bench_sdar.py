"""``sdar-30b-a3b`` (bench/configs/, bench/archs/sdar_moe/) at a tiny size
on the CPU: the configuration and its cell pass the manifest's checks as
they stand; the numerics child — the program's own ``paged_forward`` and
``paged_decode_round``, driven by archs/sdar_moe/drive.py — comes to ``ok``
against the plain reference and to not ok, each by the number that should
catch it, with one thing of the program broken underneath; and after the
cell's ladder a live engine compiles nothing under the mix's traffic."""

import asyncio
import importlib
import time

import bench_paths
import pytest
from bench_paths import REPO
from lib import buckets, client, sample, traffic
from lib.engine import (
    Engine,
    compile_counters,
    deployment_doc,
    engine_env,
    unit_spec,
)
from lib.manifest import Manifest, arch_module, reserved_ids

MAN = Manifest(REPO)
CONFIG, CELL = "sdar-30b-a3b", "sdar-30b-a3b.codegen.r80"
# every width a toy's, every key and the unit's keywords the file's own
TINY = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=512,
            mask_token_id=500)
TINY_DEPLOYMENT = dict(pool_blocks=64, slots=4, prefill_chunk=32,
                       block_size=16)
TINY_MIX = dict(max_positions=88,
                prompt_tokens={"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 64},
                output_tokens={"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 4, "max": 24})
PROMPTS = [9, 31, 50, 64, 70]


def tiny(steps=4, **unit_literals):
    """The file at a toy's widths, computing in float32: what the program
    rounds is then far under what a fault moves."""
    cfg = {**MAN.config(CONFIG), **TINY, "denoising_steps": steps,
           "name": "tiny-sdar"}
    cfg["reserved_ids"] = [{"id": 500, "why": "mask_token_id"}]
    # float32 at a toy's size has no router flips to allow for: the dense
    # cell's limit, every row held to it
    cfg["numerics"] = {"tolerance_rms": 0.1}
    cfg["deployment"] = {**cfg["deployment"], **TINY_DEPLOYMENT,
                         "dtype": "float32"}
    cfg["unit"] = {**cfg["unit"], "parameters": {
        **cfg["unit"]["parameters"], **unit_literals}}
    return cfg


def test_the_file_holds_every_published_width_and_cuts_depth_alone():
    import json

    doc = MAN.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (doc[key], doc["published"][key], value) == (7, 48, 48)
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers"]
    assert (doc["hidden_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["head_dim"]) == (2048, 32, 4, 128)
    assert (doc["moe_intermediate_size"], doc["num_experts"],
            doc["num_experts_per_tok"], doc["vocab_size"]) == (
                768, 128, 8, 151936)
    assert doc["tie_word_embeddings"] is False and doc["departures"] == []
    assumed = " ".join(doc["assumed"])
    for size in ("block_length", "denoising_steps", "remasking",
                 "mask_token_id"):
        assert size in assumed and size in doc
    assert reserved_ids(doc) == (doc["mask_token_id"],)
    s = arch_module(MAN.bench, doc, "needs").sizes(doc)
    params = (doc["num_hidden_layers"] * (
        s["attn_params"] + s["E"] * s["expert_params"]) + 2 * s["head_params"])
    assert 4.98e9 < params < 4.99e9          # 9.97 GB in bf16


def test_the_unit_the_file_names_is_the_published_block():
    from lib.children import build_unit

    doc = MAN.config(CONFIG)
    c = build_unit(unit_spec(doc, doc["deployment"], 3, 8)).cfg
    assert (c.d_model, c.n_heads, c.kv_heads, c.hd, c.n_layers) == (
        2048, 32, 4, 128, 7)
    assert (c.d_expert, c.n_experts, c.moe_k, c.moe_norm_topk) == (
        768, 128, 8, True)
    assert (c.vocab, c.tie_embeddings, c.qk_norm, c.norm_eps) == (
        151936, False, True, 1e-6)
    assert (c.rope_base, c.block_length, c.denoising_steps, c.mask_id) == (
        1e6, 4, 4, 151669)


def test_needs_count_a_round_by_its_passes_and_the_experts_it_read():
    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    s, shape = needs.sizes(doc), needs.round_shape(doc)
    assert shape == {"block": 4, "blocks": 2, "denoise": 8, "commit": 2,
                     "expert_layer_passes": 2 * (4 * 7 + 6)}
    # experts_read_share writes served_decode.expert_slots as passes x a
    # constant (a dense engine has passes and no slots): the file's own
    read_share = MAN.layer_metric("experts_read_share")["formula"]
    assert read_share["den"] == [{"path": "served_decode.passes"}]
    assert read_share["scale"] == pytest.approx(
        100.0 * (shape["denoise"] + shape["commit"])
        / (s["E"] * shape["expert_layer_passes"]))
    # 8 rows x 4 positions x 8 picks over 128 experts: the issue's 111
    assert round(needs.expected_read(doc, 32)) == 111
    assert round(needs.expected_read(doc, 4)) == 28
    few = {"served_decode": {"experts_read": 68 * 20, "expert_slots": 68 * 128}}
    all_ = {"served_decode": {"experts_read": 68 * 128,
                              "expert_slots": 68 * 128}}
    a = needs.decode_step(doc, 8, 3000, few)
    b = needs.decode_step(doc, 8, 3000, all_)
    # a program that reads 20 experts a layer is not credited with 128
    assert b["bytes"] - a["bytes"] == pytest.approx(
        2.0 * 68 * 108 * s["expert_params"] / 8)
    assert a["flops"] == b["flops"]
    none = needs.decode_step(doc, 8, 3000, {})
    assert a["bytes"] < none["bytes"] < b["bytes"]
    assert needs.experts(doc, 8, few)["bytes"] == 2.0 * 68 * 20 * s[
        "expert_params"]
    # a prefill call likewise: the experts its calls read by the program's
    # own count, no head (a prompt chooses no token)
    some = {"served_prefill": {"experts_read": 3 * 7 * 100,
                               "expert_slots": 3 * 7 * 128}}
    p = needs.prefill(doc, 3, 600, 90000, some)
    assert p["bytes"] == pytest.approx(
        2.0 * 3 * 7 * (s["attn_params"] + 100 * s["expert_params"])
        + 2 * s["kv_bytes_per_position"] * 600)
    assert p["bytes"] < needs.prefill(doc, 3, 600, 90000, {})["bytes"]


# -- (d) the numerics child, sound and broken -------------------------------


def numerics(cfg):
    from lib import children

    dep = cfg["deployment"]
    spec = {
        "repo": REPO, "platforms": ["cpu"], "bench_dir": MAN.bench,
        "config": cfg, "deployment": dep,
        "unit": unit_spec(cfg, dep, 2 ** 31 + 9, 24),
        "sample": sample.plan(PROMPTS, dep, 88), "sample_seed": 17}
    return children.numerics(
        spec, {"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.mark.parametrize("steps", [4, 2])
def test_numerics_child_is_ok_on_the_programs_own_round(steps):
    """Rows of 9, 31, 50 and 64 prompt tokens (remainders 1, 3, 2, 0)
    through chunked prefill under the block-causal mask and one round of
    two blocks; every denoising pass that fixed something is an event."""
    num = numerics(tiny(steps))
    v = num["verdict"]
    assert num["ok"] is True, v
    assert num["lens"] == [9, 31, 64, 70] and num["chunks"] == [1, 3]
    assert 0.0 < max(num["by_row"]["prefill_err"]) < 0.01 * num["tolerance"]
    assert num["decode_max_margin"] <= 0.01 * num["tolerance"]
    assert num["reserved_emitted"] == 0


def break_kv_commit(monkeypatch):
    """The pass that writes a finished block's K/V goes over a block with a
    hole in it — its last position's embedding blanked, as where a mask
    still stood: the cache keeps K/V of a block that was never the
    finished one."""
    from seldon_core_tpu.models import generate

    real, seen = generate._paged_block, {"n": 0}

    def block(lp, x, pool_layer, tables, start, valid, cfg, **kw):
        if kw.get("view") is not None and kw.get("write"):
            # the K/V pass's layers, in order, every time the round is traced
            first = seen["n"] % cfg.n_layers == 0
            seen["n"] += 1
            if first:
                x = x.at[:, -1, :].set(0.0)
        return real(lp, x, pool_layer, tables, start, valid, cfg, **kw)

    monkeypatch.setattr(generate, "_paged_block", block)


def break_head_norms(monkeypatch):
    from seldon_core_tpu.models import generate

    real = generate._rmsnorm
    monkeypatch.setattr(
        generate, "_rmsnorm",
        lambda x, w, eps=1e-6: x if x.ndim == 4 else real(x, w, eps))


def break_expert_swap(monkeypatch):
    from seldon_core_tpu.parallel import moe

    real = moe.moe_dropless

    def swapped(lp, h, valid, cfg):
        down = lp["e_down"]
        return real({**lp, "e_down": down.at[0].set(down[1]).at[1].set(
            down[0])}, h, valid, cfg)

    monkeypatch.setattr(moe, "moe_dropless", swapped)


@pytest.mark.parametrize("fault, by", [
    ("kv-from-a-block-with-a-hole", "decode_margin"),
    ("head-norms-taken-out", "prefill_err"),
    ("topk-weights-unnormalised", "prefill_err"),
    ("one-experts-matrices-swapped", "prefill_err"),
])
def test_a_fault_of_the_program_comes_out_not_ok_by_its_own_number(
        monkeypatch, fault, by):
    import jax

    jax.clear_caches()
    cfg = tiny(2 if fault.startswith("kv") else 4)
    if fault.startswith("kv"):
        break_kv_commit(monkeypatch)
    elif fault.startswith("head"):
        break_head_norms(monkeypatch)
    elif fault.startswith("topk"):
        cfg = tiny(4, moe_norm_topk=False)
    else:
        break_expert_swap(monkeypatch)
    try:
        num = numerics(cfg)
    finally:
        jax.clear_caches()      # the broken traces must not outlive the test
    v, rows = num["verdict"], num["by_row"]
    assert num["ok"] is False, v
    if by == "decode_margin":
        # the prefill is sound; block 2's passes attend to block 1's K/V
        assert v["prefill"]["over"] == 0 and v["decode"]["over"] >= 1
        assert max(rows["prefill_err"]) < 0.01 * num["tolerance"]
    else:
        assert v["prefill"]["over"] >= 3
        assert max(rows["prefill_err"]) > 2 * num["tolerance"]


# -- (e) the ladder's arithmetic against a live engine ---------------------


def test_the_cells_ladder_reaches_what_a_block_generator_reaches():
    doc = MAN.config(CONFIG)
    cell = MAN.cell(CELL)
    dep = MAN.deployment(cell, doc)
    assert (dep["prefill_emits"], dep["round_quantum"]) == (
        0, doc["block_length"])
    progs = buckets.programs(dep, buckets.caps(MAN.mix(cell["mix"])))
    # 6 row counts x prefill widths 1/2/4 and decode widths 1/2/4/8 (the
    # gather path: a power of two of blocks a row count)
    assert len(progs["prefill"]) == 18 and len(progs["decode"]) == 24
    bench_paths.check_ladder(MAN, CELL)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sdar_live")
    config = tiny()
    dep = config["deployment"]
    path = str(tmp / "deployment.json")
    bench_paths.dump(path, deployment_doc(config, dep, 2 ** 31 + 3, 24))
    env = {**engine_env(dep, str(tmp / "profile")),
           "JAX_COMPILATION_CACHE_DIR": str(tmp / "xla_cache"),
           "JAX_PLATFORMS": "cpu"}
    eng = Engine(REPO, path, env, str(tmp / "engine.log"),
                 boot_timeout_s=300)
    try:
        yield asyncio.run(_drive(eng, config, dep))
    finally:
        eng.stop()


async def _drive(eng, config, dep):
    async def get(path):
        status, doc = await client.http_json(eng.port, "GET", path)
        assert status == 200, (path, status, doc)
        return doc

    vocab, reserved = config["vocab_size"], reserved_ids(config)
    mix = {**MAN.mix("codegen"), **TINY_MIX}
    n = 0
    for b in buckets.row_buckets(dep["slots"]):
        for length, max_new in buckets.ladder_rows(dep, buckets.caps(mix)):
            rec = await client.stream_once(eng.port, client.rows_body(
                [traffic.prompt_tokens(7, n * 64 + r, length, vocab, reserved)
                 for r in range(b)], max_new, dep["span"]),
                time.monotonic, 300.0)
            assert rec["done"], rec
            n += 1
    before = {"stats": await get("/stats"), "genperf": await get("/genperf")}
    reqs = traffic.open_loop(mix, 12.0, 3.0, 1.0)
    bodies = [client.stream_body(
        traffic.prompt_tokens(5, r.index, r.prompt_len, vocab, reserved),
        r.out_len, dep["span"]) for r in reqs]
    records = await client.run_open_loop(
        eng.port, reqs, bodies, vocab, time.monotonic(), 60.0,
        reserved=reserved)
    after = {"stats": await get("/stats"), "genperf": await get("/genperf")}
    return {"records": records, "before": before, "after": after, "dep": dep}


def test_a_deployment_file_naming_the_unit_serves_it_through_genserver(
        session):
    g = session["after"]["stats"]["genserver"]
    assert g["round"] == {"block_length": 4, "denoising_steps": 4}
    assert g["tick_errors_total"] == 0 and g["admitted_total"] > 36
    recs = session["records"]
    assert len(recs) == 36 and all(r["ok"] for r in recs), [
        r for r in recs if not r["ok"]][:2]
    for r in recs:
        assert r["n_out"] == r["out_len"]     # "ok": none of them reserved


def test_after_the_ladder_the_mixes_traffic_compiles_nothing(session):
    before = compile_counters(session["before"]["stats"])
    after = compile_counters(session["after"]["stats"])
    assert after["compiles"] == before["compiles"] > 0
    progs = session["after"]["stats"]["genserver"]["programs"]
    assert progs["prefill"] and progs["decode"]
    assert progs == session["before"]["stats"]["genserver"]["programs"]


@pytest.mark.parametrize("name, low, high", [
    ("experts_read_share", 0.0, None), ("decode_tokens_per_pass", 0.3, 0.8),
    ("decode_inplace_share", 0.0, 0.0), ("decode_step_ms", 0.0, None),
])
def test_the_new_counters_read_from_the_live_engine(session, name, low, high):
    metric = MAN.layer_metric(name)
    reader = importlib.import_module("readers." + metric["reader"])
    value = reader.read(metric, {
        "genperf_before": session["before"]["genperf"],
        "genperf_after": session["after"]["genperf"],
        "stats_before": session["before"]["stats"],
        "stats_after": session["after"]["stats"], "harness": {}})
    assert value is not None and value >= low
    if high is not None:
        assert value <= high
    served = session["after"]["genperf"]["served_decode"]
    for key in ("passes", "row_passes", "experts_read", "expert_slots"):
        assert served[key] > 0
    assert served["experts_read"] <= served["expert_slots"]
    assert served["device_steps"] * 5 == served["passes"] * 4   # span 8, 10


def stage_planes():
    """One device plane: a decode round of 1,000 us whose ops are the
    grouped matmul's kernel (600 us), the router (100), attention (200) and
    a copy without a scope path (100)."""
    from lib.trace_reduce import MODULE_LINE, OP_LINE

    path = "jit(paged_decode_round)/jit(main)/while/body/"
    return [{"name": "/device:TPU:0", "lines": [
        {"name": MODULE_LINE, "events": [
            ["jit_paged_decode_round(1)", 1000.0, 1000000.0]]},
        {"name": OP_LINE, "events": [
            ["%gmm.12", 1000.0, 600000.0,
             path + "denoise/ffn/experts/pallas_call"],
            ["%fusion.1", 601000.0, 100000.0,
             path + "denoise/ffn/router/dot_general"],
            ["%fusion.2", 701000.0, 200000.0, path + "denoise/attn/exp"],
            ["%copy.3", 901000.0, 100000.0]]}]}]


def test_the_stage_reader_sorts_ops_by_the_blocks_own_scopes(monkeypatch):
    from lib import trace_scopes
    from readers import trace_stages

    known = trace_scopes.SCOPES
    red = trace_stages.stages(stage_planes())
    assert trace_scopes.SCOPES == known          # the list is lent, not kept
    dec = red["programs"]["decode"]
    assert dec["calls"] == 1 and dec["module_s"] == pytest.approx(1e-3)
    assert dec["by_scope_s"] == {
        "experts": pytest.approx(6e-4), "attn": pytest.approx(2e-4),
        "router": pytest.approx(1e-4), "unscoped": pytest.approx(1e-4)}
    # lib/trace_scopes.py's own list puts both under ffn
    plain = trace_scopes.reduce_scopes(stage_planes())["programs"]["decode"]
    assert plain["by_scope_s"]["ffn"] == pytest.approx(7e-4)
    assert plain["by_scope_s"]["unscoped"] == pytest.approx(1e-4)
    # the two metrics over that reduction
    monkeypatch.setattr(trace_stages, "reduction", lambda path: red)
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: "a-trace")
    doc = MAN.config(CONFIG)
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": CELL},
           "bench_dir": MAN.bench, "config": doc,
           "device": {"kind": "TPU v5 lite"},
           "traced": {"decode_rows_mean": 8.0},
           "genperf_before": {}, "genperf_after": {"served_decode": {
               "experts_read": 68 * 100, "expert_slots": 68 * 128}}}
    share = trace_stages.read(MAN.layer_metric("decode_experts_share"), ctx)
    assert share == pytest.approx(70.0)
    needs = arch_module(MAN.bench, doc, "needs")
    need = needs.experts(doc, 8.0, ctx["genperf_after"])
    roof = trace_stages.read(MAN.layer_metric("experts_roofline"), ctx)
    assert roof == pytest.approx(100.0 * need["bytes"] / 819e9 / 6e-4)
    assert ctx["bounds"] == {"experts_roofline": "memory"}
    # no rows in the traced span, no scopes of these: nothing, never 0
    assert trace_stages.read(MAN.layer_metric("experts_roofline"),
                             {**ctx, "traced": None}) is None
    monkeypatch.setattr(trace_stages, "reduction", lambda path: {
        "programs": {"decode": plain}})
    assert trace_stages.read(
        MAN.layer_metric("decode_experts_share"), ctx) is None


def test_a_stage_reader_with_nothing_to_read_returns_nothing(tmp_path):
    """The parent of the PR that brought the scopes has none: the new
    device-trace metrics are left out, nothing raises."""
    from readers import trace_stages

    for name in ("decode_experts_share", "experts_roofline"):
        metric = MAN.layer_metric(name)
        assert trace_stages.read(metric, {"trace": None}) is None
        assert trace_stages.read(metric, {
            "trace": {"busy_s": 1.0},
            "cell": {"name": "no-such-cell.codegen.r80"}}) is None


# -- the K/V fault of (d) at a cell's own size, on the chip --------------------
#
#     python3 tests/bench/test_bench_sdar.py --workload <cell> --seeds 1,2 \
#         --out chiprun_out/fault.<cell>.json
#
# bench/tools/limits.py's readings of the PROGRAM with ``break_kv_commit``
# planted under it: what the cell's decode limit reads of a round whose
# cache keeps a block that was never the finished one (PERF.md section 2).
# As there, the parent stays off JAX and one child holds the chip.


def _fault_child(spec_path):
    import json

    from lib import children

    with open(spec_path) as f:
        spec = json.load(f)
    device = children._setup(spec)
    break_kv_commit(pytest.MonkeyPatch())
    print(json.dumps(children.limits(spec, device)), flush=True)


def _fault_main(argv):
    import argparse
    import json
    import os

    import run as bench_run
    from lib.engine import cache_env, run_child
    from tools import limits

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=REPO)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    args.seed, args.trace = seeds[0], 0
    run = bench_run.Run(args)
    args.seconds = float(run.man.doc["run_seconds"])
    spec = run.numerics_spec()
    spec.update(seeds=seeds, control_seeds=[], units=[
        unit_spec(run.config, run.dep, s, run.caps["max_out"])
        for s in seeds])
    doc = run_child(run.repo, [os.path.abspath(__file__), "--child",
                               run.write("fault_spec.json", spec)],
                    cache_env(run.repo), 3300)
    doc["summary"] = limits.summary(doc, run.config["numerics"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f)
    print(json.dumps({"device": doc["device"], **doc["summary"]}, indent=1))


if __name__ == "__main__":
    import sys

    if sys.argv[1:2] == ["--child"]:
        _fault_child(sys.argv[2])
    else:
        _fault_main(sys.argv[1:])
