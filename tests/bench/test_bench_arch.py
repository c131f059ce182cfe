"""The architecture seam: a configuration's ``unit`` section becomes the
SeldonDeployment (byte for byte what the hard-wired map gave for
``starcoder2-3b``), the numerics child builds the unit the way the engine
does and finds its reference by the configuration's ``arch`` — on the CPU
at a tiny size, where a reference of another block comes out not ok."""

import json
import os

import bench_paths
import pytest
from bench_paths import BENCH, REPO
from lib import buckets, sample
from lib.engine import EngineFailure, deployment_doc, run_child, unit_spec
from lib.formula import deltas
from lib.manifest import Manifest, ManifestError

MAN = Manifest(REPO)
CHILD = os.path.join(BENCH, "lib", "children.py")


def test_starcoder2_3b_deployment_is_the_recorded_one_byte_for_byte():
    """bench/testdata/starcoder2-3b.deployment.json was written by the
    parent of PR 26 (the hard-wired map of seven published keys) for seed
    2147483901 and the codegen mix's 512-token cap."""
    cfg = MAN.config("starcoder2-3b")
    cell = MAN.cell("starcoder2-3b.codegen.r80")
    doc = deployment_doc(cfg, MAN.deployment(cell, cfg), 2147483901,
                         buckets.caps(MAN.mix(cell["mix"]))["max_out"])
    with open(os.path.join(BENCH, "testdata",
                           "starcoder2-3b.deployment.json")) as f:
        assert json.dumps(doc, indent=1) == f.read()


def tiny(arch="dense_gelu"):
    cfg = {**MAN.config("starcoder2-3b"), **bench_paths.TINY_CONFIG,
           "name": "tiny", "arch": arch}
    return cfg, {**cfg["deployment"], **bench_paths.TINY_DEPLOYMENT}


def test_unit_parameters_are_published_keys_or_literals_and_nothing_else():
    cfg, dep = tiny()
    cfg["unit"] = {"class_path": "x:Y", "parameters": {
        "vocab": {"from": "vocab_size"}, "gated": True, "act": "silu",
        "eps": 1e-5, "top_k": 8}}
    spec = unit_spec(cfg, dep, 2 ** 31 + 5, 7)
    assert spec["class_path"] == "x:Y"
    assert [(p["name"], p["value"], p["type"]) for p in spec["parameters"]
            ][:5] == [("vocab", "512", "INT"), ("gated", "True", "BOOL"),
                      ("act", "silu", "STRING"), ("eps", "1e-05", "FLOAT"),
                      ("top_k", "8", "INT")]
    assert {p["name"]: p["value"] for p in spec["parameters"]}[
        "seed"] == str((2 ** 31 + 5) % (2 ** 31 - 1))
    for bad in ({"from": "no_such_key"}, {"key": "vocab_size"}):
        cfg["unit"]["parameters"]["vocab"] = bad
        with pytest.raises(KeyError, match="not a key of the file"):
            unit_spec(cfg, dep, 1, 7)
    del cfg["unit"]
    with pytest.raises(KeyError):       # no default unit
        unit_spec(cfg, dep, 1, 7)


@pytest.mark.parametrize("value, type_name", [
    (["sliding_attention", "full_attention"], "list"),
    ({"from": "layer_types"}, "list"),
    ({"from": "positions_limit"}, "dict"),
    (None, "NoneType"),
], ids=["a-literal-list", "a-published-list", "a-published-group", "null"])
def test_a_unit_parameter_a_deployment_cannot_carry_is_refused_by_name(
        value, type_name):
    """A SeldonDeployment parameter is INT, FLOAT, STRING or BOOL.  A list a
    unit needs (a layer pattern) is the unit's to derive from the published
    scalars; handing it over is refused with the configuration, the keyword
    and the type — it was a bare ``KeyError: <class 'list'>``."""
    cfg, dep = tiny()
    cfg["layer_types"] = ["sliding_attention", "full_attention"]
    cfg["unit"] = {"class_path": "x:Y", "parameters": {
        "vocab": {"from": "vocab_size"}, "pattern": value}}
    with pytest.raises(ManifestError) as e:
        unit_spec(cfg, dep, 1, 7)
    for part in ("'tiny'", "'pattern'", type_name, "INT, FLOAT, STRING"):
        assert part in str(e.value)


def test_the_child_builds_the_unit_as_the_engine_does():
    """``resolve_unit_class`` + ``params_to_kwargs`` over the typed list:
    every keyword the configuration passes reaches the constructor, and
    one the unit does not take is an error, not dropped."""
    from lib.children import build_unit
    from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
    from seldon_core_tpu.graph.units import resolve_unit_class

    cfg, dep = tiny()
    spec = unit_spec(cfg, dep, 3, 24)
    unit = build_unit(spec)
    assert type(unit) is resolve_unit_class(cfg["unit"]["class_path"])
    c = unit.cfg
    assert (c.vocab, c.d_model, c.n_heads, c.kv_heads, c.n_layers) == (
        512, 128, 4, 2, 2)
    assert c.rope_base == cfg["rope_theta"] and unit.seed == 3
    assert unit.max_new_tokens == 24 and unit.eos_token == -1
    # the same list, read back from the deployment document the engine boots
    binding = SeldonDeploymentSpec.from_json_dict(
        deployment_doc(cfg, dep, 3, 24)).predictor().components[0]
    assert binding.class_path == cfg["unit"]["class_path"]
    assert [p.to_json_dict() for p in binding.parameters] == spec[
        "parameters"]
    cfg["unit"] = {**cfg["unit"], "parameters": {
        **cfg["unit"]["parameters"], "gated_ffn": True}}
    with pytest.raises(TypeError, match="gated_ffn"):
        build_unit(unit_spec(cfg, dep, 3, 24))


def test_deltas_are_the_windows_numbers_nested_as_the_document():
    before = {"a": 1, "b": {"c": 2.5, "d": "x"}, "gone": 4}
    after = {"a": 4, "b": {"c": 3.0, "d": "y", "e": 7}, "f": {"g": 1},
             "flag": True}
    assert deltas(before, after) == {
        "a": 3, "b": {"c": 0.5, "e": 7}, "f": {"g": 1}}
    assert deltas({}, {}) == {}


RELU_FOR_GELU = ("jax.nn.gelu(h @ lp[\"w1\"], approximate=True)",
                 "jax.nn.relu(h @ lp[\"w1\"])")


@pytest.fixture(scope="module")
def arch_root(tmp_path_factory):
    """A copy of the benchmark with two more architectures, added as files:
    ``tinyarch`` (bench_paths: gated FFN, untied head) and ``dense_relu``,
    the dense block's reference with another activation — a reference that
    reads the same weights and is of another block."""
    root = bench_paths.copy_root(tmp_path_factory.mktemp("arch"))
    bench_paths.add_tiny_cell(root)
    bench_paths.add_tiny_arch(root)
    src = os.path.join(root, "bench", "archs", "dense_gelu", "reference.py")
    dst = os.path.join(root, "bench", "archs", "dense_relu")
    os.makedirs(dst)
    with open(src) as f:
        text = f.read()
    assert text.count(RELU_FOR_GELU[0]) == 1
    with open(os.path.join(dst, "reference.py"), "w") as f:
        f.write(text.replace(*RELU_FOR_GELU))
    return root


# four slots, chunks of 32: rows of one, two and three chunks
TINY_PROMPTS = [9, 31, 50, 64, 90]


def numerics(root, tmp_path, arch, cfg=None):
    cfg, dep = tiny(arch) if cfg is None else (cfg, cfg["deployment"])
    spec = {
        "repo": REPO, "platforms": ["cpu"],
        "bench_dir": os.path.join(root, "bench"), "config": cfg,
        "unit": unit_spec(cfg, dep, 2 ** 31 + 9, 24), "deployment": dep,
        "sample": sample.plan(TINY_PROMPTS, dep, 120), "sample_seed": 17}
    path = str(tmp_path / f"numerics_{arch}.json")
    bench_paths.dump(path, spec)
    return run_child(
        REPO, [CHILD, "numerics", path],
        {"JAX_PLATFORMS": "cpu",
         "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache")}, 600.0)


def test_numerics_child_on_the_cpu_is_ok_against_its_own_block(
        arch_root, tmp_path):
    num = numerics(arch_root, tmp_path, "dense_gelu")
    assert num["ok"] is True, num
    assert num["device"]["platform"] == "cpu"
    assert 0.0 < num["prefill_max_abs_err"] < 0.5 * num["tolerance"]
    assert num["decode_max_margin"] <= 2 * num["tolerance"]
    v = num["verdict"]
    assert (v["rows"], num["rows_offered"], num["chunks"]) == (4, 4, [1, 3])
    assert num["lens"] == [9, 31, 64, 90]
    assert v["prefill"]["over"] == v["decode"]["over"] == 0
    assert v["prefill"]["allowed"] == v["decode"]["allowed"] == 0.0
    assert len(num["by_row"]["prefill_err"]) == 4


def test_numerics_child_is_not_ok_against_a_reference_of_another_block(
        arch_root, tmp_path):
    num = numerics(arch_root, tmp_path, "dense_relu")
    assert num["ok"] is False, num
    assert num["prefill_max_abs_err"] > 2 * num["tolerance"]
    assert num["verdict"]["prefill"]["share"] == 1.0      # every row


def test_numerics_child_fails_loudly_where_the_reference_wants_other_weights(
        arch_root, tmp_path):
    """``tinyarch`` declares a gate matrix and an untied head the dense
    unit's weights do not have: no number, and the child says which."""
    gated = {**Manifest(arch_root).config("tinygated"),
             "unit": tiny()[0]["unit"]}
    with pytest.raises(EngineFailure, match="KeyError: 'wq'"):
        numerics(arch_root, tmp_path, "tinyarch", gated)
    with pytest.raises(EngineFailure, match="no such file"):
        numerics(arch_root, tmp_path, "nowhere")


# -- a block that is not the repo's: the numerics child on the toy MoE -------


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """The benchmark with ``toymoe`` added as files (bench_paths), and two
    references to put in its place: ``tinyarch``, the DENSE toy (it reads
    the same attention and shared-expert weights: no router, no window, no
    rotary-free layers), and ``toymoe_nowindow``, its own with the window
    taken out."""
    root = bench_paths.toy_root(tmp_path_factory.mktemp("moe"))
    window = ' & (ahead < config["sliding_window"])'
    assert bench_paths.TOYMOE_REFERENCE.count(window) == 1
    dst = os.path.join(root, "bench", "archs", "toymoe_nowindow")
    os.makedirs(dst)
    with open(os.path.join(dst, "reference.py"), "w") as f:
        f.write(bench_paths.TOYMOE_REFERENCE.replace(window, ""))
    return root


def toy_numerics(root, monkeypatch, arch):
    """lib/children.py ``numerics`` in this process, the test-local unit's
    paged programs (tests/bench/toy_moe.py) in the place of the program's:
    the unit is built from the deployment document as the engine builds
    one, and the child drives chunked prefill and a decode round."""
    import toy_moe
    from lib import children
    from seldon_core_tpu.models import generate

    for name in ("init_block_pool", "paged_forward_jit",
                 "paged_decode_round_jit"):
        monkeypatch.setattr(generate, name, getattr(toy_moe, name))
    man = Manifest(root)
    cell = man.cell("toymoe.tinymix.r80")
    cfg = man.config(cell["config"])
    dep = man.deployment(cell, cfg)
    caps = buckets.caps(man.mix(cell["mix"]))
    spec = {
        "repo": REPO, "platforms": ["cpu"], "bench_dir": man.bench,
        "config": {**cfg, "arch": arch}, "deployment": dep,
        "unit": unit_spec(cfg, dep, 2 ** 31 + 9, caps["max_out"]),
        "sample": sample.plan(TINY_PROMPTS, dep, caps["max_positions"]),
        "sample_seed": 17}
    num = children.numerics(
        spec, {"platform": "cpu", "kind": "cpu", "count": 1})
    return num, cfg


@pytest.mark.parametrize("arch, ok, rows_over", [
    ("toymoe", True, 0), ("tinyarch", False, 4), ("toymoe_nowindow", False, 2),
], ids=["its-own-block", "the-dense-toy-in-its-place", "no-window"])
def test_numerics_child_judges_a_block_that_is_not_the_repos(
        moe_root, monkeypatch, arch, ok, rows_over):
    num, cfg = toy_numerics(moe_root, monkeypatch, arch)
    v = num["verdict"]
    assert num["ok"] is ok, v
    # rows of 9, 31, 64 and 80 positions + a round: two within the window
    # of 48, two past it, none past the configuration's own limit
    assert num["lens"] == [9, 31, 64, 80] and num["chunks"] == [1, 3]
    assert cfg["sliding_window"] < num["lens"][-2]
    assert num["lens"][-1] + 8 <= cfg["positions_limit"]["value"]
    assert v["prefill"]["over"] == rows_over
    assert v["prefill"]["allowed"] == v["decode"]["allowed"] == 0.25
    errs = num["by_row"]["prefill_err"]
    if ok:
        assert 0.0 < max(errs) < 0.01 * num["tolerance"]
        assert num["decode_max_margin"] <= 0.01 * num["tolerance"]
    elif rows_over == 2:
        # only the rows that reach past the window see that it is gone
        assert max(errs[:2]) < 0.01 * num["tolerance"] < min(errs[2:])
    # the unit derived the pattern the file lists for the reference
    from lib.children import build_unit

    unit = build_unit(unit_spec(cfg, cfg["deployment"], 1, 8))
    assert [unit.cfg.sliding(i) for i in range(3)] == [
        t == "sliding_attention" for t in cfg["layer_types"]]


# -- a round that is not one token a step: the numerics child on block diffusion


LENGTHS_MASK = " & (here[None, None, :] < lengths[:, None, None])"


@pytest.fixture(scope="module")
def blockdiff_root(tmp_path_factory):
    """The benchmark with ``toyblockdiff`` added as files (bench_paths), and
    two architectures to put in its place: ``toyblockdiff_undriven``, its
    reference WITHOUT the driver file (the child falls back to a row's one
    teacher-forced pass), and ``toyblockdiff_untold``, driver and all, whose
    reference does not use the lengths it is told."""
    root = bench_paths.toy_root(tmp_path_factory.mktemp("blockdiff"))
    assert bench_paths.TOYBLOCKDIFF_REFERENCE.count(LENGTHS_MASK) == 1
    for arch, files in (
            ("toyblockdiff_undriven",
             {"reference": bench_paths.TOYBLOCKDIFF_REFERENCE}),
            ("toyblockdiff_untold",
             {"reference": bench_paths.TOYBLOCKDIFF_REFERENCE.replace(
                 LENGTHS_MASK, ""),
              "drive": bench_paths.TOYBLOCKDIFF_DRIVE})):
        os.makedirs(os.path.join(root, "bench", "archs", arch))
        for name, text in files.items():
            with open(os.path.join(root, "bench", "archs", arch,
                                   name + ".py"), "w") as f:
                f.write(text)
    return root


def blockdiff_numerics(root, monkeypatch, steps, arch="toyblockdiff",
                       fault=None):
    """lib/children.py ``numerics`` in this process on ``toyblockdiff``, the
    test-local generator's functions (tests/bench/toy_blockdiff.py) in the
    place of the program's three, one of them broken where ``fault`` says."""
    import numpy as np
    import toy_blockdiff as toy
    from lib import children
    from seldon_core_tpu.models import generate

    man = Manifest(root)
    cell = man.cell("toyblockdiff.tinymix.r80")
    cfg = {**man.config(cell["config"]), "denoising_steps": steps,
           "arch": arch}
    if fault == "kv-from-the-last-pass":
        real, last = toy._forward, {}

        def forward(params, tokens, pool, tables, start, width, cfg):
            # the round's own calls are one block wide; the one over a
            # block with no mask left is the pass that writes its K/V
            if tokens.shape[1] == cfg.block_length:
                if (np.asarray(tokens) != cfg.mask_id).all() and last:
                    tokens = last["saw"]
                else:
                    last["saw"] = tokens
            return real(params, tokens, pool, tables, start, width, cfg)

        monkeypatch.setattr(toy, "_forward", forward)
    elif fault == "causal-inside-the-block":
        monkeypatch.setattr(toy, "_visible",
                            lambda pos, kpos, block_length: kpos <= pos)
    round_fn = toy.paged_decode_round_jit
    if fault == "the-mask-id-streamed":
        def round_fn(*a, **kw):
            blocks, *rest = toy.paged_decode_round_jit(*a, **kw)
            return (blocks.at[1, 6].set(cfg["mask_token_id"]), *rest)
    monkeypatch.setattr(generate, "init_block_pool", toy.init_block_pool)
    monkeypatch.setattr(generate, "paged_forward_jit", toy.paged_forward_jit)
    monkeypatch.setattr(generate, "paged_decode_round_jit", round_fn)
    dep = man.deployment(cell, cfg)
    caps = buckets.caps(man.mix(cell["mix"]))
    spec = {
        "repo": REPO, "platforms": ["cpu"], "bench_dir": man.bench,
        "config": cfg, "deployment": dep,
        "unit": unit_spec(cfg, dep, 2 ** 31 + 9, caps["max_out"]),
        "sample": sample.plan(TINY_PROMPTS, dep, caps["max_positions"]),
        "sample_seed": 17}
    num = children.numerics(
        spec, {"platform": "cpu", "kind": "cpu", "count": 1})
    return num, cfg


@pytest.mark.parametrize("steps", [4, 2])
def test_numerics_child_judges_a_round_that_is_passes_over_a_block(
        blockdiff_root, monkeypatch, steps):
    """Rows of 9, 31, 64 and 80 prompt tokens — a remainder of 1, 3, 0 and
    0 seeds the first block — through chunked prefill under the
    block-causal mask and one round of two blocks; every denoising pass
    that unmasked something is an event of its own, and the reference
    agrees with each to rounding."""
    num, cfg = blockdiff_numerics(blockdiff_root, monkeypatch, steps)
    v = num["verdict"]
    assert num["ok"] is True, v
    assert num["lens"] == [9, 31, 64, 80] and num["chunks"] == [1, 3]
    assert [n % cfg["block_length"] for n in num["lens"]] == [1, 3, 0, 0]
    assert v["prefill"]["allowed"] == v["decode"]["allowed"] == 0.0
    assert 0.0 < max(num["by_row"]["prefill_err"]) < 0.01 * num["tolerance"]
    assert num["decode_max_margin"] <= 0.01 * num["tolerance"]
    assert num["reserved_emitted"] == 0


@pytest.mark.parametrize("fault, steps, arch, by", [
    ("kv-from-the-last-pass", 2, "toyblockdiff", "decode_margin"),
    ("causal-inside-the-block", 4, "toyblockdiff", "prefill_err"),
    ("causal-inside-the-block", 2, "toyblockdiff", "prefill_err"),
    (None, 4, "toyblockdiff_untold", "prefill_err"),
    (None, 4, "toyblockdiff_undriven", "decode_margin"),
    (None, 2, "toyblockdiff_undriven", "decode_margin"),
    ("the-mask-id-streamed", 4, "toyblockdiff", "reserved_emitted"),
    ("the-mask-id-streamed", 2, "toyblockdiff", "reserved_emitted"),
], ids=["kv-written-from-the-last-denoising-pass", "causal-inside-the-block-4",
        "causal-inside-the-block-2", "the-reference-ignores-lengths",
        "the-driver-file-taken-away-4", "the-driver-file-taken-away-2",
        "the-mask-id-streamed-4", "the-mask-id-streamed-2"])
def test_a_fault_of_a_round_over_blocks_comes_out_not_ok_by_its_own_number(
        blockdiff_root, monkeypatch, fault, steps, arch, by):
    num, cfg = blockdiff_numerics(blockdiff_root, monkeypatch, steps, arch,
                                  fault)
    v, rows = num["verdict"], num["by_row"]
    assert num["ok"] is False
    tol = num["tolerance"]
    if fault == "kv-from-the-last-pass":
        # block 1's K/V came from an input that still held masks: seen only
        # where block 2 attends to it, in what block 2's passes chose.  The
        # prefill, and a row whose first block had one position to fill
        # (its last pass saw no mask: n = 31), read sound
        assert by == "decode_margin" and v["prefill"]["over"] == 0
        assert v["decode"]["over"] >= 1 and rows["decode_margin"][1] == 0.0
        assert max(rows["decode_margin"]) > 2 * v["decode"]["limit"]
    elif fault == "causal-inside-the-block":
        # every prompt's K/V differs from its second layer on
        assert by == "prefill_err" and v["prefill"]["over"] >= 3
        assert max(rows["prefill_err"]) > 4 * tol
    elif arch == "toyblockdiff_untold":
        # a prompt that is no whole number of blocks ends in a short block,
        # which under this mask looks AHEAD — into the pad, unless the
        # reference keeps it out by the lengths it is told.  Whole blocks
        # (64, 80) and every event of the round (contexts of whole blocks)
        # never see it
        assert by == "prefill_err" and v["decode"]["over"] == 0
        assert rows["prefill_err"][0] > 4 * tol
        assert max(rows["prefill_err"][2:]) < 0.01 * tol
    elif arch == "toyblockdiff_undriven":
        # the program is SOUND; what judges it is a row's one
        # teacher-forced pass, which no pass of this generator ever ran:
        # several rms in every row
        assert by == "decode_margin" and v["decode"]["share"] == 1.0
        assert min(rows["decode_margin"]) > 10 * v["decode"]["limit"]
    else:
        # one id altered where the round hands its blocks over: no logit
        # moved, the exact check alone sees it (limit 0)
        assert by == "reserved_emitted" and num["reserved_emitted"] == 1
        assert v["ok"] is True and max(rows["decode_margin"]) <= 0.01 * tol
