"""BENCHMARK.json and the data files say the same thing: every name
resolves to its file, every file to an entry, names and units stay within
the allowed characters — and a cell, a configuration, a mix and a layer
metric are each added by ADDING files (shown in a temporary copy)."""

import importlib
import os

import bench_paths
import pytest
from bench_paths import BENCH, REPO, load
from lib import buckets
from lib.manifest import (
    NAME_RE,
    UNIT_RE,
    Manifest,
    ManifestError,
    arch_module,
)

MAN = Manifest(REPO)
CELLS = [w["name"] for w in MAN.doc["workloads"]]
CONFIGS = [c["name"] for c in MAN.doc["configs"]]
PER_LAYER = [m["name"] for m in MAN.doc["per_layer"]]
E2E = [m["name"] for m in MAN.doc["end_to_end"]]
WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "vocab_size")


def listed(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, kind))
                  if f.endswith(".json"))


def test_manifest_has_exactly_the_contract_keys():
    assert sorted(MAN.doc) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert MAN.doc["paths"] == ["bench", "tests/bench"]
    assert MAN.doc["command"] == ["python3", "bench/run.py"]
    assert 1 <= MAN.doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert "setup_s" in E2E


@pytest.mark.parametrize("kind,names", [
    ("cells", CELLS), ("configs", CONFIGS), ("layer_metrics", PER_LAYER),
])
def test_every_entry_has_its_file_and_every_file_its_entry(kind, names):
    assert listed(kind) == sorted(names)


def test_every_cells_mix_has_its_file_and_every_mix_file_its_cell():
    """A mix is named by cells, not by the manifest.  A file no cell names
    fails, unless it says itself which cell it waits for
    (``awaiting_cell``): ``complete`` was measured in PR 26 and its cell
    left out (PERF.md section 7)."""
    used = {w["traffic"] for w in MAN.doc["workloads"]}
    waiting = {name for name in listed("traffic")
               if MAN.mix(name).get("awaiting_cell")}
    assert waiting == {"complete"}
    assert sorted(used | waiting) == listed("traffic")
    for name in listed("traffic"):
        mix = MAN.mix(name)
        assert mix["name"] == name and NAME_RE.match(name)
        cp = buckets.caps(mix)
        assert 0 < cp["min_prompt"] <= cp["max_prompt"]
        assert cp["max_positions"] + 8 <= 4096


def check_cell(man, cell):
    doc = man.cell(cell)              # raises where config/mix/chips differ
    entry = man.workload(cell)
    assert doc["name"] == cell and doc["why"] == entry["why"]
    assert len(entry["why"]) <= 200
    assert entry["chips"] in (1, 4)
    man.config(doc["config"])
    mix = man.mix(doc["mix"])
    assert doc["arrivals"]["kind"] == "open"
    assert doc["arrivals"]["rate"] > 0 and doc["drain_s"] > 0
    # no cell reaches past the window the block does not implement
    assert buckets.caps(mix)["max_positions"] + 8 <= 4096
    # every cell reports setup_s, another end-to-end and a per-layer metric
    e2e = [m["name"] for m in man.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert man.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_agrees_with_its_manifest_entry(cell):
    check_cell(MAN, cell)


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_declares_source_cuts_and_departures(config):
    entry = next(c for c in MAN.doc["configs"] if c["name"] == config)
    doc = MAN.config(config)
    assert doc["name"] == config and doc["source"] == entry["source"]
    assert entry["file"] == f"bench/configs/{config}.json"
    assert doc["reduced"] == entry["reduced"]
    assert not set(doc["reduced"]) & set(WIDTH_KEYS)   # no width is cut
    assert "sizes through the repo's block" in doc["described_as"]
    # the block is the configuration's to name: its reference and needs are
    # files of bench/archs/<arch>/, its unit and the unit's keywords data
    for module in ("reference", "needs"):
        assert os.path.isfile(os.path.join(
            BENCH, "archs", doc["arch"], module + ".py"))
    assert doc["unit"]["class_path"]
    for keyword, value in doc["unit"]["parameters"].items():
        assert NAME_RE.match(keyword)
        if isinstance(value, dict):
            assert list(value) == ["from"] and value["from"] in doc
    assert doc["departures"] and doc["assumed"] and doc["hbm"]
    assert doc["hidden_size"] // doc["num_attention_heads"] == 128
    for key in ("block_size", "span", "slots", "pool_blocks",
                "prefill_chunk"):
        assert doc["deployment"][key] > 0


def check_layer_metric(man, name):
    entry = next(m for m in man.doc["per_layer"] if m["name"] == name)
    doc = man.layer_metric(name)
    assert (doc["name"], doc["layer"], doc["unit"], doc["moves"]) == (
        name, entry["layer"], entry["unit"], entry["moves"])
    # which cells report a metric is said ONCE, by the optional
    # "workloads" of its manifest entry: a file that repeated it would
    # have to be edited whenever a cell is added
    assert "cells" not in doc and "workloads" not in doc
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    reader = importlib.import_module("readers." + doc["reader"])
    assert callable(reader.read)
    # the end-to-end metric it moves is reported wherever it is
    cells = [w["name"] for w in man.doc["workloads"]]
    for cell in entry.get("workloads", cells):
        assert cell in cells
        assert doc["moves"] in [
            m["name"] for m in man.metrics_for(cell, "end_to_end")]
    if name.endswith("_roofline"):
        assert entry["unit"] == "%" and entry["source"] == "device_trace"


@pytest.mark.parametrize("name", PER_LAYER)
def test_layer_metric_file_agrees_with_its_entry_and_has_a_reader(name):
    check_layer_metric(MAN, name)


def test_names_and_units_stay_within_the_allowed_characters():
    names = CELLS + CONFIGS + PER_LAYER + E2E
    names += [w["traffic"] for w in MAN.doc["workloads"]]
    names += [k for c in MAN.doc["configs"] for k in c["reduced"]]
    assert len(set(CELLS)) == len(CELLS)
    assert len(set(PER_LAYER + E2E)) == len(PER_LAYER + E2E)
    for n in names:
        assert NAME_RE.match(n), n
    for m in MAN.doc["end_to_end"] + MAN.doc["per_layer"]:
        assert UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MAN.doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for root, _, files in os.walk(BENCH):
        if os.sep + "out" in root or "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), REPO)
            assert all(NAME_RE.match(p) for p in rel.split(os.sep)), rel


def test_a_cell_config_mix_and_layer_metric_are_added_by_adding_files(
        tmp_path):
    root = bench_paths.copy_root(tmp_path)
    before = bench_paths.snapshot(root)
    cell = bench_paths.add_tiny_cell(root)
    bench_paths.assert_untouched(before)
    man = Manifest(root)
    # the copy, with its added entries, still agrees file by file
    for w in man.doc["workloads"]:
        check_cell(man, w["name"])
    for m in man.doc["per_layer"]:
        check_layer_metric(man, m["name"])
    for kind, names in (
            ("cells", [w["name"] for w in man.doc["workloads"]]),
            ("configs", [c["name"] for c in man.doc["configs"]]),
            ("layer_metrics", [m["name"] for m in man.doc["per_layer"]])):
        assert sorted(names) == sorted(
            f[:-5] for f in os.listdir(os.path.join(root, "bench", kind)))
    doc = man.cell(cell)
    cfg = man.config(doc["config"])
    dep = man.deployment(doc, cfg)
    assert dep["slots"] == 4 and cfg["hidden_size"] == 128
    assert man.mix(doc["mix"])["name"] == "tinymix"
    names = [m["name"] for m in man.metrics_for(cell, "per_layer")]
    assert "prefill_ticks" in names and "decode_step_ms" in names
    assert "prefill_ticks" not in [
        m["name"] for m in man.metrics_for(CELLS[0], "per_layer")]
    # the generator, the bucket arithmetic and a reader take the new
    # files as they are
    from lib import traffic
    from readers import genperf

    reqs = traffic.open_loop(man.mix("tinymix"), 8.0, 8.0, 2.0)
    assert len(reqs) == 64 and max(r.prompt_len for r in reqs) <= 64
    progs = buckets.programs(dep, buckets.caps(man.mix("tinymix")))
    assert (4, 32, 4) in [tuple(p) for p in progs["prefill"]]
    value = genperf.read(man.layer_metric("prefill_ticks"), {
        "genperf_before": {"ticks": {"prefill": 2}},
        "genperf_after": {"ticks": {"prefill": 5, "mixed": 4}},
        "harness": {}})
    assert value == 7.0


def test_an_architecture_is_added_by_adding_files(tmp_path):
    """A configuration of ANOTHER block — its reference, its needs, its
    unit and that unit's keywords — comes as files and manifest entries:
    no file that was there is edited, the deployment document carries the
    new parameters verbatim, and the roofline reader takes the new needs."""
    from lib.engine import deployment_doc
    from lib.peaks import peaks_for
    from readers import trace

    root = bench_paths.copy_root(tmp_path)
    before = bench_paths.snapshot(root)
    bench_paths.add_tiny_cell(root)
    cell = bench_paths.add_tiny_arch(root)
    bench_paths.assert_untouched(before)
    man = Manifest(root)
    for w in man.doc["workloads"]:
        check_cell(man, w["name"])
    assert sorted(os.listdir(os.path.join(root, "bench", "archs"))) == [
        "dense_gelu", "tinyarch"]
    doc = man.cell(cell)
    cfg = man.config(doc["config"])
    dep = man.deployment(doc, cfg)
    # the unit section reaches the SeldonDeployment as it stands: class,
    # keywords in the file's order, typed; then what a run owns
    comp = deployment_doc(cfg, dep, 5, 24)["spec"]["predictors"][0][
        "components"][0]
    assert comp["class_path"] == "a_test.units:GatedGenerator"
    got = [(p["name"], p["value"], p["type"]) for p in comp["parameters"]]
    assert got == [
        ("vocab", "512", "INT"), ("d_model", "128", "INT"),
        ("n_heads", "4", "INT"), ("n_kv_heads", "2", "INT"),
        ("n_layers", "2", "INT"), ("d_ff", "512", "INT"),
        ("rope_base", "10000.0", "FLOAT"), ("norm_eps", "1e-05", "FLOAT"),
        ("tie_head", "False", "BOOL"), ("ffn", "gated_silu", "STRING"),
        ("n_experts", "0", "INT"),
        ("max_new_tokens", "24", "INT"), ("seed", "5", "INT"),
        ("temperature", "0.0", "FLOAT"), ("eos_token", "-1", "INT"),
        ("dtype", "bfloat16", "STRING")]
    # needs.py of the new directory: three FFN matrices, worked by hand
    needs = arch_module(man.bench, cfg, "needs")
    layer = 128 * (128 + 2 * 2 * 32) + 128 * 128 + 3 * 128 * 512
    assert needs.sizes(cfg)["layer_params"] == layer == 245_760
    assert needs is not arch_module(man.bench, man.config("starcoder2-3b"), "needs")
    ctx = {
        "bench_dir": man.bench, "config": cfg, "deployment": dep,
        "device": {"kind": "TPU v5 lite"},
        "trace": {"programs": {
            "decode": {"seconds": 0.004, "calls": 5},
            "prefill": {"seconds": 0.002, "calls": 3}}},
        "traced": {"decode_rows_mean": 3.0,
                   "decode_live_positions_mean": 120.0,
                   "prefill_tokens": 90.0, "prefill_attended": 2000.0},
        "genperf_before": {"served_prefill": {"tokens": 40}},
        "genperf_after": {"served_prefill": {"tokens": 100}},
    }
    weight_bytes = 2 * (2 * layer + 512 * 128)
    kv_pos = 2 * 2 * 2 * 32 * 2
    bw = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    decode = trace.read(man.layer_metric("decode_roofline"), ctx)
    steps = 5 * dep["span"]
    assert decode == pytest.approx(
        100.0 * (weight_bytes + kv_pos * 123.0) / bw * steps / 0.004)
    # ... and the counters it is handed are the window's deltas (60 prompt
    # tokens counted by the program, not the harness's 90)
    share = trace.read(man.layer_metric("prefill_roofline"), ctx)
    assert share == pytest.approx(
        100.0 * (3 * weight_bytes + 2 * kv_pos * 60.0) / bw / 0.002)
    assert ctx["bounds"] == {"decode_roofline": "memory",
                             "prefill_roofline": "memory"}
    # the same trace under the dense block's needs reads something else
    dense = {**ctx, "config": {**cfg, "arch": "dense_gelu"}}
    assert trace.read(man.layer_metric("decode_roofline"), dense) < decode


@pytest.mark.parametrize("config,module,says", [
    ({"name": "c"}, "needs", "names no 'arch'"),
    ({"name": "c", "arch": "../lib"}, "needs", "names no 'arch'"),
    ({"name": "c", "arch": "nowhere"}, "needs", "no such file"),
    ({"name": "c", "arch": "dense_gelu"}, "kernels", "no such file"),
])
def test_a_configuration_whose_arch_has_no_files_is_refused(
        config, module, says):
    with pytest.raises(ManifestError, match=says):
        arch_module(MAN.bench, config, module)


def test_a_cell_file_that_disagrees_with_the_manifest_is_refused(tmp_path):
    root = bench_paths.copy_root(tmp_path)
    path = os.path.join(root, "bench", "cells", CELLS[0] + ".json")
    doc = load(path)
    doc["mix"] = "something-else"
    bench_paths.dump(path, doc)
    with pytest.raises(ManifestError):
        Manifest(root).cell(CELLS[0])
    with pytest.raises(ManifestError):
        Manifest(root).cell("no-such-cell")
