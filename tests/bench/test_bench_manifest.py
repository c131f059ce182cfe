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
from lib.manifest import NAME_RE, UNIT_RE, Manifest, ManifestError

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
    ("traffic", sorted({w["traffic"] for w in MAN.doc["workloads"]})),
])
def test_every_entry_has_its_file_and_every_file_its_entry(kind, names):
    assert listed(kind) == sorted(names)


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
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            if f != "BENCHMARK.json":
                p = os.path.join(d, f)
                before[p] = open(p, "rb").read()
    cell = bench_paths.add_tiny_cell(root)
    for p, raw in before.items():
        assert open(p, "rb").read() == raw, f"{p} was edited"
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
