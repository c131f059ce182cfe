"""``nemotron3-nano-30b-a3b`` (bench/configs/, bench/archs/nemotron_h/) at a
tiny size on the CPU: the configuration and its cell pass the manifest's
checks as they stand; the numerics child -- the program's own
``paged_forward`` chunk by chunk and one ``paged_decode_round`` -- comes to
``ok`` against the plain reference and to not ok with one thing of the
program broken underneath, each fault by its own number; after the cell's
ladder a live engine, booted from a deployment file that names the unit,
has loaded the programs the ladder's arithmetic names and compiles nothing
under the mix's traffic; and the layer metrics the cell adds read what the
readers that were there give them."""

import asyncio
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
CONFIG, CELL = "nemotron3-nano-30b-a3b", "nemotron3-nano-30b-a3b.codegen.r80"
# every width a toy's, every key and the unit's keywords the file's own;
# half of the router's eight experts held, as the file holds half of 128
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=7, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16,
            moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=48, n_routed_experts=4,
            num_experts_per_tok=3, vocab_size=512)
TINY_DEPLOYMENT = dict(pool_blocks=64, slots=4, prefill_chunk=32,
                       block_size=16)
TINY_MIX = dict(max_positions=88,
                prompt_tokens={"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 64},
                output_tokens={"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 4, "max": 24})
PROMPTS = [9, 31, 50, 64, 70]
# the limit a state kept in bfloat16 is read against (x the logits' rms,
# which is 1.0 here): the float32 program reads 4e-6 at the most, a state
# rounded at every write 5e-5 and 5e-4 on the rows of three chunks -- a
# Mamba-2 state forgets within tens of positions (a decay of 0.2-0.999 a
# position), so its rounding does not pile up as a retention state's does
BF16_LIMIT = 1.5e-5


def tiny(tolerance=0.1, **unit_literals):
    """The file at a toy's widths and its first seven blocks (one whole
    period), computing in float32: what the program rounds is then far
    under what a fault moves."""
    cfg = {**MAN.config(CONFIG), **TINY, "name": "tiny-nemotron"}
    cfg["published"] = {**cfg["published"], "n_routed_experts": 8}
    # float32 at a toy's size has no router flips to allow for: the dense
    # cell's limit, every row held to it
    cfg["numerics"] = {"tolerance_rms": tolerance}
    cfg["deployment"] = {**cfg["deployment"], **TINY_DEPLOYMENT,
                         "dtype": "float32"}
    cfg["unit"] = {**cfg["unit"], "parameters": {
        **cfg["unit"]["parameters"], "layer_kinds": "mememte",
        "n_experts": 8, **unit_literals}}
    return cfg


def test_the_file_holds_every_published_key_and_cuts_depth_and_experts():
    doc = MAN.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (doc[key], doc["published"][key], value) == (14, 52, 52)
        elif key == "n_routed_experts":
            assert (doc[key], doc["published"][key], value) == (64, 128, 128)
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert doc["shared_by_chips"] == 2 and doc["departures"] == []
    assert doc["layer_pattern"]["leading_dense"] == 0
    assert doc["layer_pattern"]["period"] == 7
    assert doc["hybrid_override_pattern"][:14] == "MEMEM*E" * 2
    assumed = " ".join(doc["assumed"])
    for size in ("rotary", "float32", "A_log", "dt_bias",
                 "e_score_correction_bias"):
        assert size in assumed
    p = doc["unit"]["parameters"]
    assert p["n_experts"] == doc["published"]["n_routed_experts"] == 128
    assert p["experts_held"] == {"from": "n_routed_experts"}
    assert doc["deployment"] == {
        "block_size": 256, "span": 8, "slots": 16, "prefill_chunk": 256,
        "dtype": "bfloat16", "temperature": 0.0, "eos_token": -1,
        "pool_blocks": 128}
    s = arch_module(MAN.bench, doc, "needs").sizes(doc)
    assert (s["ssm_layers"], s["routed_layers"], s["attn_layers"], s["hd"],
            s["E"], s["E_router"]) == (6, 6, 2, 128, 64, 128)
    assert (s["inner"], s["conv_dim"]) == (4096, 6144)
    assert s["expert_params"] == 9_977_856
    assert s["attn_params"] == 23_396_352
    assert 38.7e6 < s["ssm_params"] < 38.8e6
    params = (s["fixed_params"] + 2 * s["head_params"]
              + s["routed_layers"] * s["E"] * s["expert_params"])
    assert 4.93e9 < params < 4.94e9          # 9.87 GB in bf16
    assert s["kv_bytes_per_position"] == 2 * 2 * 2 * 128 * 2
    assert s["state_bytes_per_row_layer"] == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert s["state_bytes_per_row"] == 6 * 2_134_016


def test_the_unit_the_file_names_is_the_published_block():
    from lib.children import build_unit

    doc = MAN.config(CONFIG)
    c = build_unit(unit_spec(doc, doc["deployment"], 3, 8)).cfg
    assert (c.d_model, c.n_heads, c.kv_heads, c.hd, c.n_layers) == (
        2688, 32, 2, 128, 14)
    assert (c.d_expert, c.n_experts, c.held, c.experts_first, c.moe_k,
            c.moe_norm_topk, c.d_shared) == (1856, 128, 64, 0, 6, True, 3712)
    assert (c.router, c.router_scale, c.router_eps, c.expert_act) == (
        "sigmoid_bias", 2.5, 1e-20, "relu2")
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state,
            c.conv_kernel) == (64, 64, 8, 128, 4)
    assert (c.vocab, c.tie_embeddings, c.rope, c.qk_norm, c.norm_eps) == (
        131072, False, False, False, 1e-5)
    kinds = {"M": ("ssm", None), "E": (None, "experts"), "*": ("attn", None)}
    assert list(c.kinds) == [kinds[x]
                             for x in doc["hybrid_override_pattern"][:14]]


def test_needs_count_states_held_experts_and_kv_over_their_own_layers():
    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    s = needs.sizes(doc)
    few = {"served_decode": {"experts_read": 48 * 20, "expert_slots": 48 * 64,
                             "expert_slots_held": 8 * 16 * 6 * 3,
                             "row_passes": 8 * 16}}
    all_ = {"served_decode": {**few["served_decode"],
                              "experts_read": 48 * 64}}
    a = needs.decode_step(doc, 16, 6400, few)
    b = needs.decode_step(doc, 16, 6400, all_)
    # a program that reads 20 held experts a layer is not credited with 64
    assert b["bytes"] - a["bytes"] == pytest.approx(
        2.0 * 6 * 44 * s["expert_params"])
    assert a["flops"] == b["flops"]
    none = needs.decode_step(doc, 16, 6400, {})
    assert a["bytes"] < none["bytes"] < b["bytes"]
    # without counters a row's picks here are 6 x 64 / 128 = 3 a layer
    assert needs.picks_here(doc, {}) == needs.picks_here(doc, few) == 3.0
    # K/V a position over the 2 attention layers only; a state a row
    more = needs.decode_step(doc, 16, 6400 + 1000, few)
    assert more["bytes"] - a["bytes"] == pytest.approx(1000 * 2048)
    wider = needs.decode_step(doc, 17, 6400, few)
    assert wider["bytes"] - a["bytes"] == pytest.approx(
        2048 + 2 * 6 * 2_134_016)
    # the round's scopes: span x layers of the kind
    assert needs.experts(doc, 16, few)["bytes"] == pytest.approx(
        2.0 * 8 * 6 * 20 * s["expert_params"])
    assert needs.experts(doc, 16, few)["flops"] == pytest.approx(
        2.0 * 8 * 6 * 16 * 3 * s["expert_params"])
    assert needs.ssm(doc, 16, {}) == {
        "bytes": pytest.approx(8 * 2.0 * 16 * 6 * 2_134_016),
        "flops": pytest.approx(8 * 16 * 6 * 5 * 64 * 64 * 128)}
    # the mean prefill call of the window, by its own counters
    window = {"served_prefill": {"calls": 10, "rows": 25, "tokens": 3000}}
    assert needs.ssm_prefill(doc, 11.0, window) == {
        "bytes": pytest.approx(2.0 * 2.5 * 6 * 2_134_016),
        "flops": pytest.approx(300 * 6 * 5 * 64 * 64 * 128)}
    assert needs.ssm_prefill(doc, 11.0, {}) == {"bytes": 0.0, "flops": 0.0}
    # a prefill that picks a token brings no count: the expectation among
    # the 64 held, every one of them from a few dozen tokens up
    assert round(needs.expected_read(doc, 200)) == 64
    assert 0.3 < needs.expected_read(doc, 1) / 6 < 0.5
    # ... which seeded weights do not bear out, so a prefill call is
    # credited with the 3 held experts one token's picks reach, no more
    p = needs.prefill(doc, 3, 768, 90000, {})
    assert p["bytes"] == pytest.approx(
        2.0 * 3 * (s["fixed_params"] + s["head_params"]
                   + 6 * 3 * s["expert_params"])
        + 2 * 2048 * 768 + 2.0 * 3 * 6 * 2_134_016)


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

        return {li: {name: jnp.ones_like(buf) if name in ("conv", "h")
                     else buf for name, buf in layer.items()}
                for li, layer in real(cfg, num_blocks, block_size).items()}

    monkeypatch.setattr(generate, "init_block_pool", left_behind)


def test_numerics_child_is_ok_on_the_programs_own_path(monkeypatch):
    """Rows of 9 to 70 prompt tokens at chunk 32: one to three chunks, the
    later ones starting from the states the earlier left; then one round of
    8 steps through the pool.  On a pool that earlier sequences left dirty
    all the same: a row at position 0 reads zeros."""
    num = numerics(tiny())
    assert num["ok"] is True, num["verdict"]
    assert num["lens"] == [9, 31, 64, 70] and num["chunks"] == [1, 3]
    assert 0.0 < max(num["by_row"]["prefill_err"]) < 0.01 * num["tolerance"]
    assert num["decode_max_margin"] <= 0.01 * num["tolerance"]
    # ... so a limit far under the toy's holds it too: what the state kept
    # in bfloat16 is read against, below
    assert max(num["by_row"]["prefill_err"]) < BF16_LIMIT / 3
    assert numerics(tiny(tolerance=BF16_LIMIT))["ok"] is True
    dirty_pool(monkeypatch)
    again = numerics(tiny())
    assert again["ok"] is True, again["verdict"]
    assert again["by_row"]["prefill_err"] == num["by_row"]["prefill_err"]


def break_state_in_bfloat16(monkeypatch):
    """The matrix state is stored in bfloat16 (the precision below the one
    the configuration states for it): every write rounds it."""
    import jax.numpy as jnp

    from seldon_core_tpu.models import generate

    real = generate.init_block_pool

    def rounded(cfg, num_blocks, block_size):
        pool = real(cfg, num_blocks, block_size)
        return {li: {name: buf.astype(jnp.bfloat16) if name == "h" else buf
                     for name, buf in layer.items()}
                for li, layer in pool.items()}

    monkeypatch.setattr(generate, "init_block_pool", rounded)


def break_taps_dropped_at_a_chunk_edge(monkeypatch):
    """The convolution starts every call from zeros: a chunk forgets the
    three positions before it, a step the three before its token -- the
    matrix state is carried as it should be."""
    from seldon_core_tpu.models import generate

    real = generate._carried_taps
    monkeypatch.setattr(
        generate, "_carried_taps",
        lambda state, zz, tables, start, width: real(
            state, zz, tables, start * 0, width))


def break_a_held_range_shifted_by_one(monkeypatch):
    """The layer computes experts 1-4 of the router's eight with the
    weights of 0-3: a pick of expert e runs expert e - 1's matrices."""
    import dataclasses

    from seldon_core_tpu.parallel import moe

    real = moe.moe_dropless
    monkeypatch.setattr(
        moe, "moe_dropless",
        lambda lp, h, valid, cfg, impl=None: real(
            lp, h, valid, dataclasses.replace(cfg, experts_first=1), impl))


FAULTS = {
    "state-kept-in-bfloat16": break_state_in_bfloat16,
    "taps-dropped-at-a-chunk-edge": break_taps_dropped_at_a_chunk_edge,
    "router-scale-left-out": dict(router_scale=1.0),
    "shared-expert-left-out": dict(d_shared=0),
    "held-range-shifted-by-one": break_a_held_range_shifted_by_one,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_of_the_program_comes_out_not_ok(monkeypatch, fault):
    import jax

    jax.clear_caches()
    how = FAULTS[fault]
    # a rounding of the state to bfloat16 is no gross fault: it is read
    # against its own limit, which the float32 program passes (above)
    cfg = tiny(BF16_LIMIT if fault == "state-kept-in-bfloat16" else 0.1)
    if fault == "shared-expert-left-out":
        # the program runs without the shared expert whose weights the
        # reference reads: they are drawn beside the program's own
        cfg = tiny(d_shared=0)
        from seldon_core_tpu.models import generate

        real = generate.lm_init

        def init(rng, c):
            import dataclasses

            params = real(rng, c)
            whole = real(rng, dataclasses.replace(c, d_shared=48))
            for li, lp in whole.items():
                if "s_up" in lp:
                    params[li].update(s_up=lp["s_up"], s_down=lp["s_down"])
            return params

        monkeypatch.setattr(generate, "lm_init", init)
    elif isinstance(how, dict):
        cfg = tiny(**how)
    else:
        how(monkeypatch)
    try:
        num = numerics(cfg)
    finally:
        jax.clear_caches()      # the broken traces must not outlive the test
    v, rows = num["verdict"], num["by_row"]
    assert num["ok"] is False, v
    if fault == "state-kept-in-bfloat16":
        # 2^-9 a write: a row of one chunk prefills from no state and reads
        # as before; the rows of three chunks are over, the round's steps
        # read a rounded state too
        assert max(rows["prefill_err"][:2]) < num["tolerance"] / 3
        assert v["prefill"]["over"] == 2
        assert 3 * num["tolerance"] < min(rows["prefill_err"][2:])
        assert max(rows["prefill_err"]) < 100 * num["tolerance"]
    elif fault == "taps-dropped-at-a-chunk-edge":
        # rows of one chunk prefill soundly (9 and 31 tokens); the rows of
        # three chunks do not, and every row's round drops its taps
        assert rows["prefill_err"][0] < 0.01 * num["tolerance"]
        assert rows["prefill_err"][1] < 0.01 * num["tolerance"]
        # (three taps of a Mamba-2 layer's x | B | C at each of two chunk
        # edges: the longest row is far over, the other a third of the
        # limit -- ten thousand times the sound reading)
        assert min(rows["prefill_err"][2:]) > 0.3 * num["tolerance"]
        assert max(rows["prefill_err"][2:]) > 3 * num["tolerance"]
        assert v["decode"]["over"] >= 1
    else:
        assert v["prefill"]["over"] >= 3
        assert max(rows["prefill_err"]) > 2 * num["tolerance"]


# -- the ladder's arithmetic against a live engine ---------------------------


def test_the_cells_ladder_is_five_row_counts_and_the_mixes_widths():
    """16 slots: row counts 1, 2, 4, 8, 16 -- every decode round runs in
    the 16-row program or below, never a 32-row one -- x prefill widths
    1/2/4 and, on the gather path, decode widths 1/2/4/8 (where the
    in-place kernel serves, the chip, one decode program a row count:
    runtime/genserver.py ``_decode_table_width``)."""
    doc = MAN.config(CONFIG)
    cell = MAN.cell(CELL)
    dep = MAN.deployment(cell, doc)
    assert "prefill_emits" not in dep and "round_quantum" not in dep
    assert "deployment" not in cell          # the configuration's, as it is
    progs = buckets.programs(dep, buckets.caps(MAN.mix(cell["mix"])))
    rows = [1, 2, 4, 8, 16]
    assert progs["prefill"] == [(b, 256, u) for b in rows for u in (1, 2, 4)]
    assert progs["decode"] == [(b, v) for b in rows for v in (1, 2, 4, 8)]
    bench_paths.check_ladder(MAN, CELL)
    # the judged batch: every slot's row, the pool holds them with room
    plan = sample.plan([16, 160, 300, 600, 1024] * 4, dep,
                       MAN.mix(cell["mix"])["max_positions"])
    assert plan["offered"] == 16 and sum(plan["blocks"]) < dep["pool_blocks"]


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("nemotron_live")
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
    return {"records": records, "before": before, "after": after, "dep": dep,
            "programs": buckets.programs(dep, buckets.caps(mix))}


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


def test_the_engine_loads_what_the_ladders_arithmetic_names(session):
    """After the ladder the engine holds exactly ``buckets.programs`` of
    the deployment -- every row count x every reachable width, nothing
    else -- and the mix's traffic adds none and compiles nothing."""
    before = compile_counters(session["before"]["stats"])
    after = compile_counters(session["after"]["stats"])
    assert after["compiles"] == before["compiles"] > 0
    progs = session["after"]["stats"]["genserver"]["programs"]
    assert progs == session["before"]["stats"]["genserver"]["programs"]
    want = session["programs"]
    assert {p[0] for p in want["decode"]} == {1, 2, 4}
    # (/stats counts the distinct shapes dispatched: 3 row counts x the
    # prefill widths 1, 2, 4 and x the gather path's decode widths)
    assert progs["prefill"] == len(want["prefill"]) == 9
    assert progs["decode"] == len(want["decode"])


@pytest.mark.parametrize("name, low, high", [
    ("prefill_carried_share", 1.0, 99.0),
    ("experts_held_load_share", 35.0, 65.0),
    ("decode_inplace_share", 0.0, 0.0), ("decode_step_ms", 0.0, None),
])
def test_the_counters_read_from_the_live_engine(session, name, low, high):
    metric = MAN.layer_metric(name)
    if name == "experts_held_load_share":
        # the file's scale is for 6 picks x 6 expert layers a row-pass; the
        # toy picks 3 in each of 3
        metric = {**metric, "formula": {**metric["formula"],
                                        "scale": 100.0 / 9}}
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
    # experts HELD (4 of the router's 8) x expert layers (3 of 7) x steps
    assert served["expert_slots"] == served["passes"] * 3 * 4
    assert 0 < served["experts_read"] <= served["expert_slots"]
    assert 0 < served["expert_slots_held"] < served["row_passes"] * 9
    # three Mamba-2 layers' taps [3, 128] and state [8, 8, 16], float32 here
    assert served["ssm_state_bytes"] == (
        2 * 3 * 4 * (3 * 128 + 8 * 8 * 16) * served["row_passes"])
    prefill = session["after"]["genperf"]["served_prefill"]
    assert 0 < prefill["carried_rows"] < prefill["rows"]
    assert prefill["expert_slots"] == 0     # its prefill returns logits


# -- the layer metrics the cell adds: data files over readers that exist -----


def stage_planes(program="paged_decode_round"):
    """One device plane: a call of 1,000 us whose ops are the in_proj (200
    us), the convolution (50), the recurrence and its state (300), the
    gated norm and out_proj (100), the experts (250), the shared expert
    (50) and a copy without a scope path (50)."""
    from lib.trace_reduce import MODULE_LINE, OP_LINE

    path = f"jit({program})/jit(main)/while/body/jit(_paged_block)/"
    return [{"name": "/device:TPU:0", "lines": [
        {"name": MODULE_LINE, "events": [
            [f"jit_{program}(1)", 1000.0, 1000000.0]]},
        {"name": OP_LINE, "events": [
            ["%fusion.1", 1000.0, 200000.0, path + "ssm_in/dot_general"],
            ["%fusion.2", 201000.0, 50000.0, path + "ssm_conv/add"],
            ["%fusion.3", 251000.0, 300000.0, path + "ssm/scatter"],
            ["%fusion.4", 551000.0, 100000.0, path + "ssm_out/dot_general"],
            ["%gmm.12", 651000.0, 250000.0,
             path + "ffn/experts/pallas_call"],
            ["%fusion.5", 901000.0, 50000.0,
             path + "ffn/shared_expert/dot_general"],
            ["%copy.6", 951000.0, 50000.0]]}]}]


@pytest.mark.parametrize("name, program, want", [
    ("decode_ssm_share", "paged_decode_round", 65.0),
    ("prefill_ssm_share", "paged_forward", 65.0),
])
def test_the_named_reader_takes_the_ssm_scopes_as_data(monkeypatch, name,
                                                       program, want):
    from readers import trace_named

    metric = MAN.layer_metric(name)
    assert metric["reader"] == "trace_named"
    scopes = tuple(sorted(metric["formula"]["scopes"]))
    assert scopes == ("ssm", "ssm_conv", "ssm_in", "ssm_out")
    red = trace_named.stages(stage_planes(program), scopes)
    monkeypatch.setattr(trace_named, "reduction", lambda path, s: red)
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: "a-trace")
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": CELL}}
    assert trace_named.read(metric, ctx) == pytest.approx(want)
    # a program without the scopes (the parent commit): nothing, never 0
    from lib import trace_scopes

    plain = trace_scopes.reduce_scopes(stage_planes(program))
    monkeypatch.setattr(trace_named, "reduction", lambda path, s: plain)
    assert trace_named.read(metric, ctx) is None
    assert trace_named.read(metric, {"trace": None}) is None


def test_the_ssm_rooflines_hold_the_recurrences_scope_to_its_state_bytes(
        monkeypatch):
    """``ssm`` alone is the roofline's scope (the projections beside it are
    matmuls of the weights): 16 rows' states read and written once a step
    over the 300 us the plane books there."""
    from readers import trace_named

    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: "a-trace")
    window = {"served_prefill": {"calls": 10, "rows": 25, "tokens": 3000}}
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": CELL},
           "bench_dir": MAN.bench, "config": doc,
           "device": {"kind": "TPU v5 lite"},
           "traced": {"decode_rows_mean": 16.0},
           "genperf_before": {}, "genperf_after": window}
    for name, program, need in (
            ("ssm_roofline", "paged_decode_round",
             needs.ssm(doc, 16.0, {})),
            ("prefill_ssm_roofline", "paged_forward",
             needs.ssm_prefill(doc, 16.0, window))):
        metric = MAN.layer_metric(name)
        assert metric["formula"]["scopes"] == ["ssm"]
        red = trace_named.stages(stage_planes(program), ("ssm",))
        monkeypatch.setattr(trace_named, "reduction", lambda path, s: red)
        value = trace_named.read(metric, ctx)
        assert value == pytest.approx(100.0 * need["bytes"] / 819e9 / 3e-4)
        assert ctx["bounds"][name] == "memory"
