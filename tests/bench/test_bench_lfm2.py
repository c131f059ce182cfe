"""``lfm2-8b-a1b`` (bench/configs/, bench/archs/lfm2_moe/) at a tiny size on
the CPU: the configuration and its cell pass the manifest's checks as they
stand; the numerics child — the program's own ``paged_forward`` chunk by
chunk and one ``paged_decode_round`` — comes to ``ok`` against the plain
reference and to not ok with one thing of the program broken underneath;
after the cell's ladder a live engine, booted from a deployment file that
names the unit, compiles nothing under the mix's traffic; and the reader
that takes its scopes from a metric's own formula."""

import asyncio
import dataclasses
import importlib
import json
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
from lib.manifest import Manifest, arch_module

MAN = Manifest(REPO)
CONFIG, CELL = "lfm2-8b-a1b", "lfm2-8b-a1b.codegen.r80"
# every width a toy's, every key and the unit's keywords the file's own
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=6, intermediate_size=96, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=512)
TINY_DEPLOYMENT = dict(pool_blocks=64, slots=4, prefill_chunk=32,
                       block_size=16)
TINY_MIX = dict(max_positions=88,
                prompt_tokens={"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 64},
                output_tokens={"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 4, "max": 24})
PROMPTS = [9, 31, 50, 64, 70]


def tiny(**unit_literals):
    """The file at a toy's widths and its first six layers, computing in
    float32: what the program rounds is then far under what a fault
    moves."""
    cfg = {**MAN.config(CONFIG), **TINY, "name": "tiny-lfm2"}
    cfg["layer_types"] = cfg["layer_types"][:6]
    # float32 at a toy's size has no router flips to allow for: the dense
    # cell's limit, every row held to it
    cfg["numerics"] = {"tolerance_rms": 0.1}
    cfg["deployment"] = {**cfg["deployment"], **TINY_DEPLOYMENT,
                         "dtype": "float32"}
    cfg["unit"] = {**cfg["unit"], "parameters": {
        **cfg["unit"]["parameters"], "layer_kinds": "ccaccc",
        **unit_literals}}
    return cfg


def test_the_file_holds_every_published_width_and_cuts_depth_alone():
    doc = MAN.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (doc[key], doc["published"][key], value) == (14, 24, 24)
        elif key == "layer_types":
            assert doc[key] == value[:14]       # the first 14, verbatim
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers"]
    assert doc["layer_pattern"]["leading_dense"] == doc["num_dense_layers"]
    assert doc["layer_pattern"]["period"] == 4
    assert (doc["hidden_size"], doc["num_attention_heads"],
            doc["num_key_value_heads"], doc["intermediate_size"]) == (
                2048, 32, 8, 7168)
    assert (doc["moe_intermediate_size"], doc["num_experts"],
            doc["num_experts_per_tok"], doc["vocab_size"]) == (
                1792, 32, 4, 65536)
    assumed = " ".join(doc["assumed"])
    for size in ("tie_word_embeddings", "head_dim", "expert_bias",
                 "routed_scaling_factor"):
        assert size in assumed
    assert len(doc["departures"]) == 1 and "float32" in doc["departures"][0]
    s = arch_module(MAN.bench, doc, "needs").sizes(doc)
    assert (s["attn_layers"], s["conv_layers"], s["dense_layers"],
            s["routed_layers"], s["hd"]) == (3, 11, 2, 12, 64)
    params = (s["fixed_params"] + s["head_params"]
              + s["routed_layers"] * s["E"] * s["expert_params"])
    assert 4.66e9 < params < 4.68e9          # 9.33 GB in bf16
    assert s["kv_bytes_per_position"] == 6144
    assert s["state_bytes_per_row"] == 11 * 2 * 2048 * 2


def test_the_unit_the_file_names_is_the_published_block():
    from lib.children import build_unit

    doc = MAN.config(CONFIG)
    c = build_unit(unit_spec(doc, doc["deployment"], 3, 8)).cfg
    assert (c.d_model, c.n_heads, c.kv_heads, c.hd, c.n_layers) == (
        2048, 32, 8, 64, 14)
    assert (c.d_ff, c.d_expert, c.n_experts, c.moe_k, c.moe_norm_topk) == (
        7168, 1792, 32, 4, True)
    assert (c.vocab, c.tie_embeddings, c.qk_norm, c.norm_eps,
            c.rope_base) == (65536, True, True, 1e-5, 1e6)
    assert (c.conv_kernel, c.dense_layers, c.router, c.block_length) == (
        3, 2, "sigmoid_bias", 1)
    letters = {"conv": "conv", "full_attention": "attn"}
    assert [m for m, _ in c.kinds] == [letters[t] for t in doc["layer_types"]]
    assert [f for _, f in c.kinds] == ["gated"] * 2 + ["experts"] * 12


def test_needs_count_kv_over_attention_layers_and_experts_by_the_counter():
    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    s = needs.sizes(doc)
    few = {"served_decode": {"experts_read": 96 * 10, "expert_slots": 96 * 32}}
    all_ = {"served_decode": {"experts_read": 96 * 32,
                              "expert_slots": 96 * 32}}
    a = needs.decode_step(doc, 16, 6400, few)
    b = needs.decode_step(doc, 16, 6400, all_)
    # a program that reads 10 experts a layer is not credited with 32
    assert b["bytes"] - a["bytes"] == pytest.approx(
        2.0 * 12 * 22 * s["expert_params"])
    assert a["flops"] == b["flops"]
    none = needs.decode_step(doc, 16, 6400, {})
    assert a["bytes"] < none["bytes"] < b["bytes"]
    # K/V a position over the 3 attention layers only; a state a row
    more = needs.decode_step(doc, 16, 6400 + 1000, few)
    assert more["bytes"] - a["bytes"] == pytest.approx(1000 * 3 * 2 * 8 * 64 * 2)
    wider = needs.decode_step(doc, 17, 6400, few)
    assert wider["bytes"] - a["bytes"] == pytest.approx(
        6144 + 2 * 11 * 2 * 2048 * 2)
    # the round's experts: span x routed layers (12, not 14)
    assert needs.experts(doc, 16, few)["bytes"] == pytest.approx(
        2.0 * 8 * 12 * 10 * s["expert_params"])
    assert needs.experts(doc, 16, few)["flops"] == pytest.approx(
        2.0 * 8 * 12 * 16 * 4 * s["expert_params"])
    # a prefill that picks a token brings no count: the expectation, every
    # expert from a few dozen tokens up
    assert round(needs.expected_read(doc, 200)) == 32
    p = needs.prefill(doc, 3, 600, 90000, {})
    assert p["bytes"] == pytest.approx(
        2.0 * 3 * (s["fixed_params"] + s["head_params"]
                   + 12 * needs.expected_read(doc, 200) * s["expert_params"])
        + 2 * 6144 * 600)


# -- the numerics child, sound and broken -----------------------------------


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


def dirty_pool(monkeypatch):
    """A pool as earlier sequences left it: every state entry holds
    something.  The numerics child hands each row fresh blocks, so only
    this shows whether a row at position 0 reads what its block held."""
    from seldon_core_tpu.models import generate

    real = generate.init_block_pool

    def left_behind(cfg, num_blocks, block_size):
        import jax.numpy as jnp

        return {li: {name: jnp.ones_like(buf) if name == "conv" else buf
                     for name, buf in layer.items()}
                for li, layer in real(cfg, num_blocks, block_size).items()}

    monkeypatch.setattr(generate, "init_block_pool", left_behind)


def test_numerics_child_is_ok_on_the_programs_own_path(monkeypatch):
    """Rows of 9 to 70 prompt tokens at chunk 32: one to three chunks, the
    later ones starting from the state the earlier left; then one round of
    8 steps through the cache.  On a pool that earlier sequences left
    dirty all the same: a row at position 0 reads zeros."""
    num = numerics(tiny())
    assert num["ok"] is True, num["verdict"]
    assert num["lens"] == [9, 31, 64, 70] and num["chunks"] == [1, 3]
    assert 0.0 < max(num["by_row"]["prefill_err"]) < 0.01 * num["tolerance"]
    assert num["decode_max_margin"] <= 0.01 * num["tolerance"]
    dirty_pool(monkeypatch)
    again = numerics(tiny())
    assert again["ok"] is True, again["verdict"]
    assert again["by_row"]["prefill_err"] == num["by_row"]["prefill_err"]


def break_state_not_carried(monkeypatch):
    """Every call starts its convolution from zeros: a chunk forgets what
    the chunk before it left, a step what the step before it did."""
    from seldon_core_tpu.models import generate

    real = generate._short_conv
    monkeypatch.setattr(
        generate, "_short_conv",
        lambda lp, x, layer, tables, start, valid, cfg: real(
            lp, x, layer, tables, start * 0, valid, cfg))


def break_state_not_zero_at_0(monkeypatch):
    """A row reads its block's entry whatever its position: at 0 it starts
    from what the block's last owner left."""
    from seldon_core_tpu.models import generate

    real = generate._short_conv
    monkeypatch.setattr(
        generate, "_short_conv",
        lambda lp, x, layer, tables, start, valid, cfg: real(
            lp, x, layer, tables, start * 0 + 1, valid, cfg))
    dirty_pool(monkeypatch)


def break_bias_in_the_weights(monkeypatch):
    """The selection bias weighs the chosen experts too (a dense
    combination over all experts, toy-size only)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.parallel import moe

    def biased(lp, h, valid, cfg, impl=None):
        B, W, D = h.shape
        x = h.reshape(B * W, D)
        score = jax.nn.sigmoid(
            x.astype(jnp.float32) @ lp["router"].astype(jnp.float32))
        score = score + lp["expert_bias"]            # the fault
        top_w, top_e = jax.lax.top_k(score, cfg.moe_k)
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
        weight = jnp.einsum("tk,tke->te", top_w, jax.nn.one_hot(
            top_e, cfg.n_experts, dtype=jnp.float32))
        gu = jnp.einsum("td,edf->tef", x, lp["e_gate_up"])
        F = cfg.d_expert
        act = jax.nn.silu(gu[..., :F]) * gu[..., F:]
        y = jnp.einsum("tef,efd,te->td", act, lp["e_down"], weight)
        return y.reshape(B, W, D).astype(h.dtype), jnp.int32(0)

    monkeypatch.setattr(moe, "moe_dropless", biased)


def break_a_dense_layer_routed(monkeypatch):
    """The second leading layer runs a router and experts where the
    published block holds a dense FFN (the layer keeps the dense weights,
    which the reference reads, beside the experts the program now runs)."""
    from seldon_core_tpu.models import generate

    real = generate.lm_init

    def init(rng, cfg):
        params = real(rng, cfg)
        dense = real(rng, dataclasses.replace(cfg, dense_layers=2))
        params["l1"].update(
            {k: dense["l1"][k] for k in ("w1", "w2", "w3")})
        return params

    monkeypatch.setattr(generate, "lm_init", init)


@pytest.mark.parametrize("fault", [
    "state-not-carried-across-a-chunk", "state-not-zero-at-position-0",
    "bias-added-to-the-weights", "a-leading-dense-layer-routed"])
def test_a_fault_of_the_program_comes_out_not_ok(monkeypatch, fault):
    import jax

    jax.clear_caches()
    cfg = tiny()
    if fault.startswith("state-not-carried"):
        break_state_not_carried(monkeypatch)
    elif fault.startswith("state-not-zero"):
        break_state_not_zero_at_0(monkeypatch)
    elif fault.startswith("bias"):
        break_bias_in_the_weights(monkeypatch)
    else:
        cfg = tiny(dense_layers=1)
        break_a_dense_layer_routed(monkeypatch)
    try:
        num = numerics(cfg)
    finally:
        jax.clear_caches()      # the broken traces must not outlive the test
    v, rows = num["verdict"], num["by_row"]
    assert num["ok"] is False, v
    if fault.startswith("state-not-carried"):
        # rows of one chunk prefill soundly (9 and 31 tokens); the rows of
        # three chunks do not, and every row's round forgets its state
        assert rows["prefill_err"][0] < 0.01 * num["tolerance"]
        assert rows["prefill_err"][1] < 0.01 * num["tolerance"]
        assert min(rows["prefill_err"][2:]) > num["tolerance"]
        assert v["decode"]["over"] >= 1
    elif fault.startswith("state-not-zero"):
        # what a row's first two positions read fades with its length: the
        # short rows are far over, the longest may be under
        assert v["prefill"]["over"] >= 2
        assert max(rows["prefill_err"][:2]) > 5 * num["tolerance"]
    else:
        assert v["prefill"]["over"] >= 3
        assert max(rows["prefill_err"]) > 2 * num["tolerance"]


# -- the ladder's arithmetic against a live engine ---------------------------


def test_the_cells_ladder_reaches_what_a_token_a_step_generator_reaches():
    doc = MAN.config(CONFIG)
    cell = MAN.cell(CELL)
    dep = MAN.deployment(cell, doc)
    assert "prefill_emits" not in dep and "round_quantum" not in dep
    progs = buckets.programs(dep, buckets.caps(MAN.mix(cell["mix"])))
    # 6 row counts x prefill widths 1/2/4 and decode widths 1/2/4/8 (the
    # gather path: a power of two of blocks a row count)
    assert len(progs["prefill"]) == 18 and len(progs["decode"]) == 24
    bench_paths.check_ladder(MAN, CELL)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lfm2_live")
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

    vocab = config["vocab_size"]
    mix = {**MAN.mix("codegen"), **TINY_MIX}
    n = 0
    for b in buckets.row_buckets(dep["slots"]):
        for length, max_new in buckets.ladder_rows(dep, buckets.caps(mix)):
            rec = await client.stream_once(eng.port, client.rows_body(
                [traffic.prompt_tokens(7, n * 64 + r, length, vocab)
                 for r in range(b)], max_new, dep["span"]),
                time.monotonic, 300.0)
            assert rec["done"], rec
            n += 1
    before = {"stats": await get("/stats"), "genperf": await get("/genperf")}
    reqs = traffic.open_loop(mix, 12.0, 3.0, 1.0)
    bodies = [client.stream_body(
        traffic.prompt_tokens(5, r.index, r.prompt_len, vocab),
        r.out_len, dep["span"]) for r in reqs]
    records = await client.run_open_loop(
        eng.port, reqs, bodies, vocab, time.monotonic(), 60.0)
    after = {"stats": await get("/stats"), "genperf": await get("/genperf")}
    return {"records": records, "before": before, "after": after, "dep": dep}


def test_a_deployment_file_naming_the_unit_serves_it_through_genserver(
        session):
    g = session["after"]["stats"]["genserver"]
    assert g["round"] == {"block_length": 1, "denoising_steps": 1}
    assert g["tick_errors_total"] == 0 and g["admitted_total"] > 36
    recs = session["records"]
    assert len(recs) == 36 and all(r["ok"] for r in recs), [
        r for r in recs if not r["ok"]][:2]
    for r in recs:
        assert r["n_out"] == r["out_len"]


def test_after_the_ladder_the_mixes_traffic_compiles_nothing(session):
    before = compile_counters(session["before"]["stats"])
    after = compile_counters(session["after"]["stats"])
    assert after["compiles"] == before["compiles"] > 0
    progs = session["after"]["stats"]["genserver"]["programs"]
    assert progs["prefill"] and progs["decode"]
    assert progs == session["before"]["stats"]["genserver"]["programs"]


@pytest.mark.parametrize("name, low, high", [
    ("prefill_carried_share", 1.0, 99.0), ("decode_inplace_share", 0.0, 0.0),
    ("decode_step_ms", 0.0, None),
])
def test_the_counters_read_from_the_live_engine(session, name, low, high):
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
    assert served["passes"] == served["device_steps"] > 0
    # experts held x ROUTED layers (4 of the toy's 6) x steps
    assert served["expert_slots"] == served["passes"] * 4 * 8
    assert 0 < served["experts_read"] <= served["expert_slots"]
    prefill = session["after"]["genperf"]["served_prefill"]
    assert 0 < prefill["carried_rows"] < prefill["rows"]
    assert prefill["expert_slots"] == 0     # its prefill returns logits


# -- the reader that takes its scopes from the metric's own formula ----------


def stage_planes():
    """One device plane: a decode round of 1,000 us whose ops are the
    in_proj (200 us), the convolution and its state (50), the out_proj
    (100), the experts (500), attention (100) and a copy without a scope
    path (50)."""
    from lib.trace_reduce import MODULE_LINE, OP_LINE

    path = "jit(paged_decode_round)/jit(main)/while/body/"
    return [{"name": "/device:TPU:0", "lines": [
        {"name": MODULE_LINE, "events": [
            ["jit_paged_decode_round(1)", 1000.0, 1000000.0]]},
        {"name": OP_LINE, "events": [
            ["%fusion.1", 1000.0, 200000.0, path + "conv_in/dot_general"],
            ["%fusion.2", 201000.0, 50000.0, path + "conv/scatter"],
            ["%fusion.3", 251000.0, 100000.0, path + "conv_out/dot_general"],
            ["%gmm.12", 351000.0, 500000.0,
             path + "ffn/experts/pallas_call"],
            ["%fusion.4", 851000.0, 100000.0, path + "attn/exp"],
            ["%copy.5", 951000.0, 50000.0]]}]}]


def test_the_named_reader_sorts_ops_by_the_scopes_a_formula_names(
        monkeypatch):
    from lib import trace_scopes
    from readers import trace_named

    known = trace_scopes.SCOPES
    scopes = ("conv", "conv_in", "conv_out")
    red = trace_named.stages(stage_planes(), scopes)
    assert trace_scopes.SCOPES == known          # the list is lent, not kept
    dec = red["programs"]["decode"]
    assert dec["by_scope_s"] == {
        "ffn": pytest.approx(5e-4), "conv_in": pytest.approx(2e-4),
        "conv_out": pytest.approx(1e-4), "attn": pytest.approx(1e-4),
        "conv": pytest.approx(5e-5), "unscoped": pytest.approx(5e-5)}
    asked = []
    monkeypatch.setattr(trace_named, "reduction",
                        lambda path, scopes: asked.append(scopes) or red)
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: "a-trace")
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": CELL}}
    share = trace_named.read(MAN.layer_metric("decode_conv_share"), ctx)
    assert share == pytest.approx(35.0) and asked == [scopes]
    # the same reader under another formula needs no new file: the share of
    # a roofline of any stage a block names
    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    counters = {"served_decode": {"experts_read": 96 * 20,
                                  "expert_slots": 96 * 32}}
    roof = trace_named.read(
        {"name": "x_roofline", "formula": {
            "program": "decode", "scopes": ["ffn"], "needs": "experts"}},
        {**ctx, "bench_dir": MAN.bench, "config": doc,
         "device": {"kind": "TPU v5 lite"},
         "traced": {"decode_rows_mean": 16.0},
         "genperf_before": {}, "genperf_after": counters})
    assert roof == pytest.approx(
        100.0 * needs.experts(doc, 16.0, counters)["bytes"] / 819e9 / 5e-4)
    # a program without the scopes (the parent of the PR that brought
    # them): nothing, never 0
    plain = trace_scopes.reduce_scopes(stage_planes())
    monkeypatch.setattr(trace_named, "reduction", lambda path, scopes: plain)
    assert trace_named.read(
        MAN.layer_metric("prefill_conv_share"), ctx) is None
    assert trace_named.read(
        MAN.layer_metric("decode_conv_share"), ctx) is None


def test_the_named_reader_with_nothing_to_read_returns_nothing():
    from readers import trace_named

    for name in ("decode_conv_share", "prefill_conv_share"):
        metric = MAN.layer_metric(name)
        assert metric["reader"] == "trace_named"
        assert trace_named.read(metric, {"trace": None}) is None
        assert trace_named.read(metric, {
            "trace": {"busy_s": 1.0},
            "cell": {"name": "no-such-cell.codegen.r80"}}) is None
