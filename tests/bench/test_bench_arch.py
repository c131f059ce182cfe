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
from lib.manifest import Manifest

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
