"""``brumby-14b`` (bench/configs/, bench/archs/brumby/) at a tiny size on the
CPU: the configuration, its mix and its cell pass the manifest's checks as
they stand; the numerics child — the program's own ``paged_forward`` chunk
by chunk (the chunk form) and one ``paged_decode_round`` (the recurrent
form) — comes to ``ok`` against the plain reference's attention form and to
not ok with one thing of the program broken underneath; the cell's ladder
is the programs a block-a-row engine loads; and the four layer metrics read
a trace that has their scopes and keep silent on one that has not."""

import json

import bench_paths
import pytest
from bench_paths import REPO
from lib import buckets, sample
from lib.engine import unit_spec
from lib.manifest import Manifest, arch_module

MAN = Manifest(REPO)
CONFIG, CELL = "brumby-14b", "brumby-14b.longdoc.r80"
# every width a toy's, every key and the unit's keywords the file's own
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_hidden_layers=4, intermediate_size=96,
            vocab_size=512)
# a block a row, as the cell deploys it: 4 rows and the scratch block
TINY_DEPLOYMENT = dict(pool_blocks=5, slots=4, prefill_chunk=32,
                       block_size=128)
PROMPTS = [9, 31, 50, 64, 70]


def tiny(tolerance=0.1):
    cfg = {**MAN.config(CONFIG), **TINY, "name": "tiny-brumby"}
    # float32 at a toy's size: what the program rounds is far under what a
    # fault moves, and every row is held to the limit (no discrete choice)
    cfg["numerics"] = {"tolerance_rms": tolerance}
    cfg["deployment"] = {**cfg["deployment"], **TINY_DEPLOYMENT,
                         "dtype": "float32"}
    cfg["unit"] = {**cfg["unit"], "parameters": {
        **cfg["unit"]["parameters"], "layer_kinds": "rrrr"}}
    return cfg


def test_the_file_holds_every_published_key_and_cuts_depth_alone():
    doc = MAN.config(CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Brumby-14B-Base")
    assert doc["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (doc[key], doc["published"][key], value) == (
                doc[key], 40, 40) and doc[key] in (6, 8)
        else:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers"]
    assert doc["layer_pattern"]["leading_dense"] == 0
    assert doc["layer_pattern"]["period"] == 1 and doc["departures"] == []
    assumed = " ".join(doc["assumed"])
    for said in ("retention_degree", "logsigmoid", "retention_eps",
                 "1/sqrt(head_dim)", "RMSNorm", "float32", "[4.6, 7.6]"):
        assert said in assumed, said
    assert all("the catalog row's config does not state" in a
               for a in doc["assumed"])
    s = arch_module(MAN.bench, doc, "needs").sizes(doc)
    assert (s["hd"], s["P"], s["KV"], s["H"]) == (128, 8256, 8, 40)
    assert s["state_bytes_per_row_layer"] == 8 * 8256 * 129 * 4 == 34080768
    assert s["layer_params"] == 330342408           # 0.661 GB in bf16
    params = s["matmul_params"] + s["head_params"]  # + the embedding
    assert params == s["L"] * s["layer_params"] + 2 * 151936 * 5120
    # what the deployment's block and pool follow from (the file's prose)
    dep, mix = doc["deployment"], MAN.mix("longdoc")
    assert dep["block_size"] % dep["prefill_chunk"] == 0
    assert dep["block_size"] >= mix["max_positions"] + dep["span"]
    assert dep["block_size"] - dep["prefill_chunk"] < (
        mix["max_positions"] + dep["span"])
    assert dep["pool_blocks"] == dep["slots"] + 1
    assert "discrete_share" not in doc["numerics"]


def test_the_unit_the_file_names_is_the_published_block():
    from lib.children import build_unit

    doc = MAN.config(CONFIG)
    c = build_unit(unit_spec(doc, doc["deployment"], 3, 8)).cfg
    assert (c.d_model, c.n_heads, c.kv_heads, c.hd, c.d_ff) == (
        5120, 40, 8, 128, 17408)
    assert (c.vocab, c.tie_embeddings, c.qk_norm, c.norm_eps,
            c.rope_base) == (151936, False, True, 1e-6, 1e6)
    assert c.n_layers == doc["num_hidden_layers"] == c.dense_layers
    assert set(c.kinds) == {("ret", "gated")} and c.d_expert == 0


def test_needs_count_the_state_once_read_and_once_written_a_token():
    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    s = needs.sizes(doc)
    L, state = s["L"], s["state_bytes_per_row"]
    assert state == L * 34080768
    a, b = needs.decode_step(doc, 16, 0, {}), needs.decode_step(doc, 17, 0, {})
    assert b["bytes"] - a["bytes"] == 2 * state
    # nothing is kept by position
    assert needs.decode_step(doc, 16, 99999, {}) == a
    assert a["bytes"] == 2.0 * s["matmul_params"] + 16 * 2 * state
    r = needs.retention(doc, 16, {})
    assert r["bytes"] == doc["deployment"]["span"] * 16 * 2 * state
    assert r["flops"] == 8 * 16 * L * 2 * (8 + 40) * 8256 * 129
    # a prefill call is reckoned from the window's own counters: 3 calls of
    # 5 (row, chunk) pairs and 1,100 tokens each
    served = {"served_prefill": {"calls": 3, "tokens": 3300,
                                 "rows": 15}}
    p = needs.retention_prefill(doc, 7.0, served)
    assert p["bytes"] == 5 * 2 * state
    per_token = L * (2 * (8 + 40) * 8256 * 129 + 4 * 40 * 128 * 257 / 2)
    assert p["flops"] == pytest.approx(1100 * per_token)
    # ... and is nothing where the program counted no chunk (the parent)
    assert needs.retention_prefill(doc, 7.0, {}) == {"bytes": 0.0,
                                                     "flops": 0.0}
    assert needs.retention_prefill(
        doc, 7.0, {"served_prefill": {"calls": 3, "tokens": 9}}
    )["flops"] == 0.0
    whole = needs.prefill(doc, 2, 1024, 10 ** 9, {})
    assert whole["bytes"] == 2 * 2.0 * s["matmul_params"] + 4 * 2 * state
    assert whole["flops"] == pytest.approx(
        1024 * (2.0 * (s["matmul_params"] - s["head_params"]) + per_token))


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


def test_numerics_child_is_ok_on_the_programs_own_path():
    """Rows of 9 to 70 prompt tokens at chunk 32: one to three chunks, the
    later ones starting from the state the earlier left; then one round of
    8 steps through the state; every reference row padded to the block."""
    cfg = tiny()
    plan = sample.plan(PROMPTS, cfg["deployment"], 88)
    assert plan["blocks"] == [1, 1, 1, 1]               # a block a row
    num = numerics(cfg)
    assert num["ok"] is True, num["verdict"]
    assert num["lens"] == [9, 31, 64, 70] and num["chunks"] == [1, 3]
    assert 0.0 < max(num["by_row"]["prefill_err"]) < 1e-4 * num["tolerance"]
    assert num["decode_max_margin"] <= 1e-4 * num["tolerance"]
    # ... so a limit a hundredth of the toy's holds it too: what the state
    # kept in bfloat16 is read against, below
    assert numerics(tiny(tolerance=0.001))["ok"] is True


def break_state_not_carried(monkeypatch):
    """Every call starts from a zero state: a chunk forgets what the chunk
    before it left, a step what the step before it did."""
    from seldon_core_tpu.models import generate

    real = generate._retention
    monkeypatch.setattr(
        generate, "_retention",
        lambda lp, x, layer, tables, start, valid, cfg: real(
            lp, x, layer, tables, start * 0, valid, cfg))


def break_state_in_bfloat16(monkeypatch):
    """The state is rounded to bfloat16 every time it is written (what a
    pool kept in the activations' dtype would hold)."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops import retention

    real = retention.retention

    def rounded(*args):
        y, state = real(*args)
        return y, jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), state)

    monkeypatch.setattr(retention, "retention", rounded)


def break_gate_dropped(monkeypatch):
    import jax.numpy as jnp

    from seldon_core_tpu.ops import retention

    real = retention.retention
    monkeypatch.setattr(
        retention, "retention",
        lambda q, k, v, log_g, *rest: real(q, k, v, jnp.zeros_like(log_g),
                                           *rest))


@pytest.mark.parametrize("fault", [
    "state-not-carried-across-a-chunk", "state-kept-in-bfloat16",
    "gate-dropped"])
def test_a_fault_of_the_program_comes_out_not_ok(monkeypatch, fault):
    import jax

    jax.clear_caches()
    {"state-not-carried-across-a-chunk": break_state_not_carried,
     "state-kept-in-bfloat16": break_state_in_bfloat16,
     "gate-dropped": break_gate_dropped}[fault](monkeypatch)
    # a rounding of the state to bfloat16 is no gross fault: it is read
    # against a limit a hundredth of the toy's, which the float32 program
    # passes with three hundred times of room (above)
    tolerance = 0.001 if fault.startswith("state-kept") else 0.1
    try:
        num = numerics(tiny(tolerance))
    finally:
        jax.clear_caches()      # the broken traces must not outlive the test
    v, rows = num["verdict"], num["by_row"]
    assert num["ok"] is False, v
    if fault.startswith("state-not-carried"):
        # rows of one chunk prefill soundly (9 and 31 tokens); the rows of
        # three chunks do not, and every row's round forgets its state
        assert max(rows["prefill_err"][:2]) < 0.01 * num["tolerance"]
        assert min(rows["prefill_err"][2:]) > num["tolerance"]
        assert v["decode"]["over"] >= 1
    elif fault.startswith("state-kept"):
        # 2^-9 a write: a row of one chunk prefills from no state and
        # reads as before; the rows of three chunks read 4 to 10 times the
        # limit, a thousand times what the float32 state reads there
        assert max(rows["prefill_err"][:2]) < 0.01 * num["tolerance"]
        assert v["prefill"]["over"] == 2
        assert 3 * num["tolerance"] < min(rows["prefill_err"][2:])
        assert max(rows["prefill_err"]) < 30 * num["tolerance"]
    else:
        # a decay of 0.99-0.9995 a position matters little over 9 tokens
        # and more with every chunk: the longer rows are over
        assert v["prefill"]["over"] >= 3
        assert max(rows["prefill_err"]) > 2 * num["tolerance"]


# -- the ladder's arithmetic: a block a row ----------------------------------


def test_the_cells_ladder_is_one_program_a_row_count_and_kind():
    """A block holds a whole row, so every table is one column wide: five
    prefill and five decode programs (rows 1 .. 16), where the cells of the
    other configurations load 18 + 6 -- exactly what a block-a-row engine
    dispatches (tests/test_brumby_block.py reads its widths: all 1)."""
    doc = MAN.config(CONFIG)
    cell = MAN.cell(CELL)
    dep = MAN.deployment(cell, doc)
    mix = MAN.mix(cell["mix"])
    assert "prefill_emits" not in dep and "round_quantum" not in dep
    progs = buckets.programs(dep, buckets.caps(mix))
    assert progs["prefill"] == [(b, 256, 1) for b in (1, 2, 4, 8, 16)]
    assert progs["decode"] == [(b, 1) for b in (1, 2, 4, 8, 16)]
    assert len(buckets.ladder_rows(dep, buckets.caps(mix))) == 1
    bench_paths.check_ladder(MAN, CELL)
    # the judged batch: every slot's row in its own block, the pool's 16
    reqs = [2048, 4000, 6144, 9000, 12288] * 4
    plan = sample.plan(reqs, dep, mix["max_positions"])
    assert plan["blocks"] == [1] * 16 and plan["offered"] == 16
    assert max(plan["lens"]) == 12288 and plan["chunks"] == [8, 48]
    # the reference takes a 12,800-position row alone (lib/sample.py)
    reference = arch_module(MAN.bench, doc, "reference")
    row = reference.row_bytes(doc, dep["block_size"], 1 + dep["span"])
    assert sample.GROUP_BYTES < row < 2 * sample.GROUP_BYTES
    groups = sample.reference_groups(
        [n + dep["span"] for n in plan["lens"]],
        lambda S: reference.row_bytes(doc, S, 1 + dep["span"]),
        dep["block_size"])
    assert [(S, len(rows)) for S, rows in groups] == [(12800, 1)] * 16


# -- the layer metrics: data files over the reader that is there -------------


def stage_planes(program="paged_decode_round"):
    """One device plane: a call of 1,000 us whose ops are the projections
    (150 us), the retention proper (500), W_o (50), the FFN (250) and a
    copy without a scope path (50)."""
    from lib.trace_reduce import MODULE_LINE, OP_LINE

    path = f"jit({program})/jit(main)/while/body/jit(_paged_block)/"
    return [{"name": "/device:TPU:0", "lines": [
        {"name": MODULE_LINE, "events": [
            [f"jit_{program}(1)", 1000.0, 1000000.0]]},
        {"name": OP_LINE, "events": [
            ["%fusion.1", 1000.0, 150000.0, path + "ret_in/dot_general"],
            ["%fusion.2", 151000.0, 500000.0,
             path + "retention/while/body/dot_general"],
            ["%fusion.3", 651000.0, 50000.0, path + "ret_out/dot_general"],
            ["%fusion.4", 701000.0, 250000.0, path + "ffn/dot_general"],
            ["%copy.5", 951000.0, 50000.0]]}]}]


@pytest.mark.parametrize("name, program, want", [
    ("decode_retention_share", "paged_decode_round", 70.0),
    ("prefill_retention_share", "paged_forward", 70.0),
    ("retention_roofline", "paged_decode_round", None),
    ("prefill_retention_roofline", "paged_forward", None)])
def test_the_four_metrics_read_their_scopes_and_nothing_without_them(
        monkeypatch, name, program, want):
    from lib import trace_scopes
    from readers import trace_named

    entry = next(m for m in MAN.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert entry["source"] == "device_trace"
    metric = MAN.layer_metric(name)
    assert metric["reader"] == "trace_named"
    scopes = tuple(sorted(metric["formula"]["scopes"]))
    red = trace_named.stages(stage_planes(program), scopes)
    monkeypatch.setattr(trace_named, "reduction", lambda path, s: red)
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: "a-trace")
    doc = MAN.config(CONFIG)
    needs = arch_module(MAN.bench, doc, "needs")
    counters = {"served_prefill": {"calls": 4, "tokens": 4 * 2048,
                                   "rows": 4 * 8}}
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": CELL},
           "bench_dir": MAN.bench, "config": doc,
           "device": {"kind": "TPU v5 lite"},
           "traced": {"decode_rows_mean": 12.0},
           "genperf_before": {}, "genperf_after": counters}
    value = trace_named.read(metric, ctx)
    if want is not None:
        assert value == pytest.approx(want)
    elif name == "retention_roofline":
        # memory-bound: a read and a write of 12 rows' state, 8 steps
        need = needs.retention(doc, 12.0, counters)
        assert value == pytest.approx(100.0 * need["bytes"] / 819e9 / 5e-4)
        assert ctx["bounds"][name] == "memory"
    else:
        need = needs.retention_prefill(doc, 12.0, counters)
        assert value == pytest.approx(100.0 * need["flops"] / 197e12 / 5e-4)
        assert ctx["bounds"][name] == "compute"
    # a program without the scopes (the parent of this PR runs no such
    # layer; any other cell's program): nothing, never 0
    plain = trace_scopes.reduce_scopes(stage_planes(program))
    monkeypatch.setattr(trace_named, "reduction", lambda path, s: plain)
    assert trace_named.read(metric, ctx) is None
    assert trace_named.read(metric, {"trace": None}) is None
    # ... and the metrics every cell owes read 0, not nothing, as long as
    # the program names its FFN
    from readers import trace_scopes as scope_reader

    assert "ffn" in trace_scopes.SCOPES
    assert scope_reader is not None
