"""BENCHMARK.json and the data files say the same thing: every name
resolves to its file, every file to an entry, names and units stay within
the allowed characters — and a cell, a configuration, a mix and a layer
metric are each added by ADDING files (shown in a temporary copy)."""

import importlib
import inspect
import os

import bench_paths
import pytest
from bench_paths import BENCH, REPO, load
from lib import buckets
from lib.engine import unit_spec
from lib.manifest import (
    NAME_RE,
    UNIT_RE,
    Manifest,
    ManifestError,
    arch_module,
    reserved_ids,
)

MAN = Manifest(REPO)
CELLS = [w["name"] for w in MAN.doc["workloads"]]
CONFIGS = [c["name"] for c in MAN.doc["configs"]]
PER_LAYER = [m["name"] for m in MAN.doc["per_layer"]]
E2E = [m["name"] for m in MAN.doc["end_to_end"]]
# What section 4 of the model-configs guide never cuts: a width.  A key of
# these, or one that ends as a width's name ends, may not be in ``reduced``.
WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "sliding_window", "num_experts_per_tok")
WIDTH_ENDINGS = ("_dim", "_rank", "intermediate_size", "state_size")
# ... and the counts that may be the chip's share of a stated deployment:
# how many layers, heads, routed experts, rows of the vocabulary are held
# here.  Each in ``reduced`` needs its published count beside it.
LAYERS = "num_hidden_layers"
VOCAB = "vocab_size"
HEADS = ("num_attention_heads", "num_key_value_heads")
MIN_EXPERTS, MIN_VOCAB_SHARE, MIN_LAYERS = 8, 8, 4


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
    # a mix belongs to no configuration, so it has no position cap of its
    # own: a cell's check holds it to its configuration's, and a mix that
    # waits for a cell to the configuration that cell names
    for name in waiting:
        awaited = MAN.mix(name)["awaiting_cell"].split(":")[0]
        config, = [c for c in CONFIGS if awaited.startswith(c + ".")]
        check_positions(MAN.config(config), MAN.config(config)["deployment"],
                        MAN.mix(name))


def check_positions(config, dep, mix):
    """No row reaches past what the configuration says it can hold: the
    longest the mix sends plus one decode round."""
    assert (buckets.caps(mix)["max_positions"] + dep["span"]
            <= config["positions_limit"]["value"])


def check_cell(man, cell):
    doc = man.cell(cell)              # raises where config/mix/chips differ
    entry = man.workload(cell)
    assert doc["name"] == cell and doc["why"] == entry["why"]
    assert len(entry["why"]) <= 200
    assert entry["chips"] in (1, 4)
    config = man.config(doc["config"])
    mix = man.mix(doc["mix"])
    assert doc["arrivals"]["kind"] == "open"
    assert doc["arrivals"]["rate"] > 0 and doc["drain_s"] > 0
    check_positions(config, man.deployment(doc, config), mix)
    # every cell reports setup_s, another end-to-end and a per-layer metric
    e2e = [m["name"] for m in man.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert man.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_agrees_with_its_manifest_entry(cell):
    check_cell(MAN, cell)


def is_width(key):
    return key in WIDTH_KEYS or key.endswith(WIDTH_ENDINGS)


def is_expert_count(key):
    return "experts" in key and not is_width(key)


def check_reduced(doc):
    """``reduced`` names no width, and a count (layers, heads, routed
    experts, rows of the vocabulary) only as the chip's share of a
    deployment the file states, within the guide's floors."""
    reduced = doc["reduced"]
    assert not [k for k in reduced if is_width(k)], "a width is never cut"
    counts = [k for k in reduced if type(doc[k]) is int]
    if not counts:
        return
    published = doc["published"]
    for key in counts:
        assert 0 < doc[key] < published[key], key
    shared = [k for k in counts
              if k in HEADS or k == VOCAB or is_expert_count(k)]
    if shared:
        # one number for every count of a layer: the chips that share it
        chips = doc["shared_by_chips"]
        assert chips > 1 and doc["layer_divided"]
        for key in shared:
            assert doc[key] * chips == published[key], key
    for key in filter(is_expert_count, counts):
        assert doc[key] >= MIN_EXPERTS, key
    if VOCAB in counts:
        assert doc[VOCAB] * MIN_VOCAB_SHARE >= published[VOCAB]
    if LAYERS in counts:
        pattern = doc["layer_pattern"]
        after = doc[LAYERS] - pattern["leading_dense"]
        assert after >= MIN_LAYERS and after % pattern["period"] == 0


def check_reserved(doc):
    """Ids a configuration keeps out of the traffic and of every answer:
    each in range, none twice, each with its reason."""
    entries = doc.get("reserved_ids", [])
    assert all(type(r) is dict and set(r) == {"id", "why"} and r["why"]
               for r in entries)
    ids = list(reserved_ids(doc))
    assert ids == [r["id"] for r in entries]
    assert all(type(i) is int and 0 <= i < doc["vocab_size"] for i in ids)
    assert len(set(ids)) == len(ids) < doc["vocab_size"]


def check_round(dep):
    """The two counts a deployment may state of a round that is not
    single-token steps (lib/buckets.py): a round is whole quanta long and
    a block of the pool holds whole quanta."""
    assert dep.get("prefill_emits", 1) in (0, 1)
    quantum = dep.get("round_quantum", 1)
    assert type(quantum) is int and quantum >= 1
    assert dep["span"] % quantum == 0 and dep["block_size"] % quantum == 0


def check_config(man, config, doc=None):
    """A configuration is held to what it DECLARES — its own head width,
    its own position cap, its own cuts — not to one model's shape.  (``doc``
    stands in for the file where a test breaks one rule at a time.)"""
    entry = next(c for c in man.doc["configs"] if c["name"] == config)
    doc = doc or man.config(config)
    assert doc["name"] == config and doc["source"] == entry["source"]
    assert entry["file"] == f"bench/configs/{config}.json"
    assert doc["reduced"] == entry["reduced"]
    check_reduced(doc)
    # a block that is implemented as published has nothing to depart from
    # and, with every size given, nothing to assume: the lists may be empty
    assert isinstance(doc["departures"], list)
    assert isinstance(doc["assumed"], list)
    assert ("sizes through the repo's block" in doc["described_as"]) == bool(
        doc["departures"])
    # the block is the configuration's to name: its reference and needs are
    # files of bench/archs/<arch>/, its unit and the unit's keywords data
    reference = arch_module(man.bench, doc, "reference")
    assert list(inspect.signature(reference.forward).parameters) == [
        "params", "tokens", "config", "at", "lengths"]
    assert reference.row_bytes(doc, 2 * doc["deployment"]["block_size"],
                               1 + doc["deployment"]["span"]) > 0
    # how a round is driven is the architecture's to say, or nobody's: then
    # it is single-token steps (lib/children.py one_token_a_step)
    driver = arch_module(man.bench, doc, "drive", optional=True)
    if driver is not None:
        assert list(inspect.signature(driver.drive).parameters) == [
            "unit", "params", "pool", "tables", "prompts", "logits",
            "deployment"]
    check_reserved(doc)
    check_round(doc["deployment"])
    assert doc["unit"]["class_path"]
    assert all(NAME_RE.match(k) for k in doc["unit"]["parameters"])
    # every parameter a key of the file or a literal, and a scalar
    unit_spec(doc, doc["deployment"], 1, 8)
    assert doc["hbm"]
    # the head width is the file's own key where it has one, and the
    # architecture's arithmetic reads the same
    hd = doc.get("head_dim", doc["hidden_size"] // doc["num_attention_heads"])
    assert arch_module(man.bench, doc, "needs").sizes(doc)["hd"] == hd
    limit = doc["positions_limit"]
    assert limit["value"] > 0 and limit["why"]
    for key in ("block_size", "span", "slots", "pool_blocks",
                "prefill_chunk"):
        assert doc["deployment"][key] > 0


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_declares_source_cuts_and_departures(config):
    check_config(MAN, config)


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


def check_copy(man):
    """Every parametrised check of this directory, on a copy's entries."""
    for w in man.doc["workloads"]:
        check_cell(man, w["name"])
        bench_paths.check_ladder(man, w["name"])
    for c in man.doc["configs"]:
        check_config(man, c["name"])
    for m in man.doc["per_layer"]:
        check_layer_metric(man, m["name"])


def test_a_cell_config_mix_and_layer_metric_are_added_by_adding_files(
        tmp_path):
    root = bench_paths.copy_root(tmp_path)
    before = bench_paths.snapshot(root)
    cell = bench_paths.add_tiny_cell(root)
    bench_paths.assert_untouched(before)
    man = Manifest(root)
    # the copy, with its added entries, still agrees file by file
    check_copy(man)
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
    check_copy(man)
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


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """The benchmark with four configurations added as files: the dense
    toys, ``toymoe``, a block that is NOT a smaller StarCoder2, and
    ``toyblockdiff``, a generator whose round is not one token a step —
    and two architectures whose files break a rule: a reference that takes
    no ``lengths``, a driver that takes another list of arguments."""
    root = bench_paths.toy_root(tmp_path_factory.mktemp("moe"))
    for arch, name, text in (
            ("untold", "reference", bench_paths.TOYBLOCKDIFF_REFERENCE.replace(
                "config, at, lengths):", "config, at):")),
            ("undriven", "reference", bench_paths.TOYBLOCKDIFF_REFERENCE),
            ("undriven", "drive", bench_paths.TOYBLOCKDIFF_DRIVE.replace(
                "prompts, logits, deployment):", "prompts, deployment):"))):
        os.makedirs(os.path.join(root, "bench", "archs", arch), exist_ok=True)
        with open(os.path.join(root, "bench", "archs", arch, name + ".py"),
                  "w") as f:
            f.write(text)
    for arch in ("untold", "undriven"):
        with open(os.path.join(root, "bench", "archs", arch, "needs.py"),
                  "w") as f:
            f.write(bench_paths.TOYBLOCKDIFF_NEEDS)
    return root


def test_a_generator_whose_round_is_not_one_token_a_step_is_added_by_adding_files(
        tmp_path):
    """Diffusion over blocks: the reference, the needs AND the driver of a
    round under ``bench/archs/toyblockdiff/``, a configuration with a
    reserved mask id and the two counts of its round in ``deployment``, a
    cell: new files and manifest entries only, every check of this
    directory holds on the copy, and the ladder takes every width the
    generator's OWN arithmetic reaches (with the program's counts it does
    not: that is what the two keys are for)."""
    root = bench_paths.copy_root(tmp_path)
    before = bench_paths.snapshot(root)
    bench_paths.add_tiny_cell(root)
    cell = bench_paths.add_toy_blockdiff(root)
    bench_paths.assert_untouched(before)
    man = Manifest(root)
    check_copy(man)
    assert sorted(os.listdir(os.path.join(man.bench, "archs",
                                          "toyblockdiff"))) == [
        "drive.py", "needs.py", "reference.py"]
    added = set(bench_paths.snapshot(root)) - set(before)
    assert sorted(os.path.relpath(p, man.bench) for p in added
                  if "toyblockdiff" in p) == [
        "archs/toyblockdiff/drive.py", "archs/toyblockdiff/needs.py",
        "archs/toyblockdiff/reference.py",
        "cells/toyblockdiff.tinymix.r80.json", "configs/toyblockdiff.json"]
    cfg = man.config("toyblockdiff")
    assert reserved_ids(cfg) == (cfg["mask_token_id"],) == (500,)
    assert reserved_ids(man.config("starcoder2-3b")) == ()
    assert arch_module(man.bench, man.config("starcoder2-3b"), "drive",
                       optional=True) is None
    doc = man.cell(cell)
    dep = man.deployment(doc, cfg)
    cp = buckets.caps(man.mix(doc["mix"]))
    L = cfg["block_length"]

    def widths(prompt_len, max_new):
        """The generator's own account (tests/bench/toy_blockdiff.py): a
        row's first round starts where its last whole block ends and
        emits the prompt's remainder again; the prefill emits nothing."""
        dec, rem = set(), prompt_len % L
        at, out = prompt_len - rem, -rem
        while out < max_new:
            at, out = at + dep["span"], out + dep["span"]
            dec.add(buckets.pow2(buckets.blocks(at, dep["block_size"])))
        return dec

    reach = set()
    for p in range(cp["min_prompt"], cp["max_prompt"] + 1):
        for o in range(1, min(cp["max_out"], cp["max_positions"] - p) + 1):
            assert buckets.touched(p, o, dep)[1] == widths(p, o), (p, o)
            reach |= widths(p, o)
    assert reach == buckets.reachable(dep, cp)[1] == {1, 2, 4, 8}
    ladder = buckets.ladder_rows(dep, cp)
    assert set().union(*(widths(*row) for row in ladder)) == reach
    # under the program's counts the ladder misses two of them
    old = {k: v for k, v in dep.items()
           if k not in bench_paths.TOYBLOCKDIFF_ROUND}
    assert set().union(*(widths(*row) for row in buckets.ladder_rows(
        old, cp))) == {1, 4}


def test_a_round_that_is_not_span_steps_states_its_needs_by_the_span_th(
        moe_root):
    """readers/trace.py multiplies ``decode_step`` by calls x span.  An
    architecture whose round is passes over a block returns a ``span``-th
    of a ROUND's bytes and FLOPs — here 2 blocks x (4 + 1) passes, each
    all weights and the live K/V once — and the reader is as it was."""
    from lib.peaks import peaks_for
    from readers import trace

    man = Manifest(moe_root)
    cfg = man.config("toyblockdiff")
    dep = man.deployment(man.cell("toyblockdiff.tinymix.r80"), cfg)
    needs = arch_module(man.bench, cfg, "needs")
    layer = 64 * 16 * (4 + 2 * 2) + 4 * 16 * 64 + 3 * 64 * 128
    assert needs.sizes(cfg)["layer_params"] == layer == 36_864
    weights = 2 * (2 * layer + 2 * 512 * 64)          # an untied head
    kv_pos = 2 * 2 * 2 * 16 * 2
    rows, live = 3.0, 120.0
    a_round = 2 * (5 * (weights + kv_pos * live) + kv_pos * rows * 4)
    need = needs.decode_step(cfg, rows, live, {})
    assert need["bytes"] == pytest.approx(a_round / dep["span"])
    assert need["flops"] == pytest.approx(2 * 5 * (
        2.0 * (2 * layer + 512 * 64) * rows * 4
        + 4 * 2 * 4 * 16 * live * 4) / dep["span"])
    ctx = {"bench_dir": man.bench, "config": cfg, "deployment": dep,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"decode": {"seconds": 0.004, "calls": 5}}},
           "traced": {"decode_rows_mean": rows,
                      "decode_live_positions_mean": live}}
    share = trace.read(man.layer_metric("decode_roofline"), ctx)
    # five rounds' bytes over the traced seconds, whatever the span
    bw = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert share == pytest.approx(100.0 * 5 * a_round / bw / 0.004)
    steps2 = {**cfg, "denoising_steps": 2}
    assert needs.decode_step(steps2, rows, live, {})["bytes"] < need["bytes"]


def test_a_block_that_is_not_the_repos_is_added_by_adding_files(moe_root):
    """Head width apart from hidden // heads, a router over more experts
    than are held, a window of its own shorter than a judged row, a chip's
    share in ``reduced``, nothing departed from and nothing assumed: new
    files and manifest entries only, and every check of this directory
    holds on the copy.  (On the parent of PR 31 its case of the
    configuration test failed ``hidden_size // num_attention_heads ==
    128``, and so did the dense toy's, at 32.)"""
    man = Manifest(moe_root)
    check_copy(man)
    assert sorted(os.listdir(os.path.join(man.bench, "archs"))) == [
        "dense_gelu", "tinyarch", "toyblockdiff", "toymoe",
        "undriven", "untold"]       # the last two: the fixture's broken ones
    cfg = man.config("toymoe")
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 16
    assert cfg["head_dim"] == 32 != 128
    assert cfg["reduced"] == ["num_experts"] and cfg["assumed"] == []
    assert cfg["departures"] == [] and not cfg["tie_word_embeddings"]
    cell = man.cell("toymoe.tinymix.r80")
    longest = buckets.caps(man.mix(cell["mix"]))["max_positions"]
    assert cfg["sliding_window"] < longest < cfg["positions_limit"]["value"]
    # StarCoder2's 4,096 is nobody else's cap
    assert cfg["positions_limit"]["value"] != man.config(
        "starcoder2-3b")["positions_limit"]["value"]
    # the deployment document carries scalars only, the list stays behind
    from lib.engine import deployment_doc

    dep = man.deployment(cell, cfg)
    comp = deployment_doc(cfg, dep, 5, 24)["spec"]["predictors"][0][
        "components"][0]
    names = [p["name"] for p in comp["parameters"]]
    assert "head_dim" in names and "full_every" in names
    assert "layer_types" not in names
    assert arch_module(man.bench, cfg, "needs").sizes(cfg)["hd"] == 32


def broken(**changes):
    def apply(doc):
        for key, value in changes.items():
            if value is None:
                doc.pop(key)
            else:
                doc[key] = value
    return apply


def in_deployment(**changes):
    def apply(doc):
        doc["deployment"] = {**doc["deployment"], **changes}
    return apply


def sliced_vocab(doc):
    doc.update(vocab_size=32, reduced=["num_experts", "vocab_size"],
               shared_by_chips=32, num_experts=1,
               published={"num_experts": 32, "vocab_size": 1024})


@pytest.mark.parametrize("config, change", [
    ("toymoe", broken(published=None)),
    ("toymoe", broken(published={})),
    ("toymoe", broken(shared_by_chips=None)),
    ("toymoe", broken(shared_by_chips=2)),
    ("toymoe", broken(layer_divided="")),
    ("toymoe", broken(num_experts=4, shared_by_chips=8)),
    ("toymoe", sliced_vocab),
    ("toymoe", broken(reduced=["num_experts", "head_dim"])),
    ("toymoe", broken(reduced=["num_experts", "moe_intermediate_size"])),
    ("toymoe", broken(reduced=["num_experts", "num_experts_per_tok"])),
    ("toymoe", broken(reduced=["num_experts", "sliding_window"])),
    ("toymoe", broken(reduced=["num_experts", "num_hidden_layers"],
                      published={"num_experts": 32,
                                 "num_hidden_layers": 32})),
    ("toymoe", broken(reduced=["num_experts", "num_hidden_layers"],
                      published={"num_experts": 32, "num_hidden_layers": 32},
                      layer_pattern={"leading_dense": 1, "period": 4})),
    ("toymoe", broken(departures=["no norm after a sub-layer"])),
    ("toymoe", broken(described_as="a test's sizes through the repo's "
                                   "block")),
    ("toymoe", broken(assumed=None)),
    ("toymoe", broken(departures=None)),
    ("tiny", broken(head_dim=64)),
    ("toymoe", broken(head_dim=None)),
    ("toymoe", broken(positions_limit=None)),
    ("toymoe", broken(positions_limit={"value": 128})),
    ("toymoe", broken(layer_types=None, unit={
        "class_path": "x:Y", "parameters": {"p": {"from": "published"}}})),
    ("starcoder2-3b", broken(departures=[])),
    ("starcoder2-3b", broken(positions_limit=None)),
    ("starcoder2-3b", broken(reduced=["vocab_size"])),
    ("toyblockdiff", broken(reserved_ids=[{"id": 512, "why": "mask"}])),
    ("toyblockdiff", broken(reserved_ids=[{"id": -1, "why": "mask"}])),
    ("toyblockdiff", broken(reserved_ids=[{"id": 500, "why": "mask"},
                                          {"id": 500, "why": "pad"}])),
    ("toyblockdiff", broken(reserved_ids=[{"id": 500, "why": ""}])),
    ("toyblockdiff", broken(reserved_ids=[{"id": 500}])),
    ("toyblockdiff", broken(reserved_ids=[500])),
    ("toyblockdiff", broken(reserved_ids=[{"id": 500.0, "why": "mask"}])),
    ("starcoder2-3b", broken(reserved_ids=[{"id": 49152, "why": "mask"}])),
    ("toyblockdiff", broken(arch="untold")),
    ("toyblockdiff", broken(arch="undriven")),
    ("toyblockdiff", in_deployment(round_quantum=3)),
    ("toyblockdiff", in_deployment(round_quantum=0)),
    ("toyblockdiff", in_deployment(round_quantum=32)),
    ("toyblockdiff", in_deployment(prefill_emits=2)),
], ids=["no-published", "count-not-published", "no-deployment",
        "chips-times-held-is-not-published", "not-said-how-divided",
        "under-8-experts", "under-an-eighth-of-the-vocabulary",
        "head_dim-cut", "expert-width-cut", "experts-per-token-cut",
        "window-cut", "layers-cut-without-a-pattern",
        "layers-cut-under-a-period", "departs-without-the-phrase",
        "the-phrase-without-departing", "assumed-absent",
        "departures-absent", "head_dim-is-not-the-archs",
        "head_dim-left-to-hidden-over-heads", "no-positions_limit",
        "positions_limit-without-its-reason", "a-group-for-a-unit-parameter",
        "starcoder2-departs-and-says-so", "starcoder2-no-positions_limit",
        "starcoder2-vocabulary-cut-unstated", "reserved-id-past-the-vocabulary",
        "reserved-id-negative", "reserved-id-twice", "reserved-without-a-reason",
        "reserved-without-the-key", "reserved-a-bare-number",
        "reserved-id-not-an-integer", "starcoder2-reserved-id-out-of-range",
        "reference-not-told-lengths", "driver-of-another-signature",
        "round-quantum-splits-the-span", "round-quantum-zero",
        "round-quantum-over-a-block", "prefill-emits-two"])
def test_a_configuration_that_breaks_one_rule_is_refused(moe_root, config,
                                                         change):
    man = Manifest(moe_root)
    doc = man.config(config)
    check_config(man, config, doc)          # sound as it stands
    change(doc)
    entry = next(c for c in man.doc["configs"] if c["name"] == config)
    entry["reduced"] = doc["reduced"]       # the entry agrees: the RULE fails
    with pytest.raises((AssertionError, KeyError, ManifestError)):
        check_config(man, config, doc)


def test_the_chips_share_may_be_layers_heads_experts_and_vocabulary(
        moe_root):
    """What section 4 calls the usual cut, stated in full, passes: a whole
    period and four layers after the leading dense one, 8 of 32 experts,
    a quarter of the vocabulary and of the heads over 4 chips."""
    man = Manifest(moe_root)
    doc = man.config("toymoe")
    doc.update(
        reduced=["num_hidden_layers", "num_experts", "vocab_size",
                 "num_attention_heads", "num_key_value_heads",
                 "layer_types"],
        num_hidden_layers=7, layer_types=doc["layer_types"] * 2 + ["x"],
        layer_pattern={"leading_dense": 1, "period": 3},
        published={"num_hidden_layers": 31, "num_experts": 32,
                   "vocab_size": 2048, "num_attention_heads": 16,
                   "num_key_value_heads": 8})
    check_reduced(doc)
    doc["published"]["vocab_size"] = 8 * 512 + 4
    with pytest.raises(AssertionError):
        check_reduced(doc)


@pytest.mark.parametrize("limit, span, ok", [
    (96, 8, True), (95, 8, False), (96, 9, False), (4096, 8, True)])
def test_a_cell_past_its_configurations_positions_limit_is_refused(
        moe_root, limit, span, ok):
    """``tinymix`` holds rows of 88 positions: with a round of ``span`` it
    has to stay within the limit of the CELL'S configuration, whatever
    another configuration's is."""
    man = Manifest(moe_root)
    cell = man.cell("toymoe.tinymix.r80")
    cfg = man.config("toymoe")
    cfg["positions_limit"]["value"] = limit
    dep = {**man.deployment(cell, cfg), "span": span}
    mix = man.mix(cell["mix"])
    assert buckets.caps(mix)["max_positions"] == 88
    if ok:
        check_positions(cfg, dep, mix)
    else:
        with pytest.raises(AssertionError):
            check_positions(cfg, dep, mix)


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


def test_the_harness_names_no_unit_no_block_and_no_way_to_decode_but_the_default():
    """``lib/``, ``readers/``, ``tools/`` and ``run.py`` learn a unit's
    class and keywords from the configuration file, a block's formulas
    from ``archs/<arch>/`` and how a round is driven from its ``drive.py``
    — or drive the one default, a round of single-token steps."""
    import re

    unit = MAN.config(CONFIGS[0])["unit"]
    words = [unit["class_path"], *unit["parameters"],
             *(v["from"] for v in unit["parameters"].values()
               if isinstance(v, dict)),
             "diffusion", "denois", "block_length", "unmask", "speculative",
             "draft", "bidirectional", "gelu", "silu", "rmsnorm"]
    # the one size every answer is held to, under either name
    words = [w for w in words if w not in ("vocab", "vocab_size")]
    files = [os.path.join(BENCH, "run.py")]
    for sub in ("lib", "readers", "tools"):
        files += [os.path.join(BENCH, sub, f)
                  for f in sorted(os.listdir(os.path.join(BENCH, sub)))
                  if f.endswith(".py")]
    assert len(files) > 25
    hits = {}
    for path in files:
        with open(path) as f:
            text = f.read()
        found = [w for w in words
                 if re.search(rf"(?<![A-Za-z_]){re.escape(w)}(?![a-z_])",
                              text, re.IGNORECASE)]
        hits[os.path.relpath(path, BENCH)] = found
    assert {k: v for k, v in hits.items() if v} == {}
