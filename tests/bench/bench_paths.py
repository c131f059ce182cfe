"""Shared by the benchmark's CPU tests: puts ``bench/`` on the path (its
packages are ``lib`` and ``readers``; the repo root's legacy ``bench.py``
keeps the name ``bench``) and builds temporary benchmark roots."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY_CONFIG = dict(
    hidden_size=128, intermediate_size=512, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
TINY_DEPLOYMENT = dict(pool_blocks=256, slots=4, prefill_chunk=32,
                       block_size=16)


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def copy_root(tmp_path) -> str:
    """BENCHMARK.json and bench/ (without run outputs) in a temporary
    root, the program linked beside them."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(REPO, "seldon_core_tpu"),
               os.path.join(root, "seldon_core_tpu"))
    return root


def add_tiny_cell(root: str) -> str:
    """Add a configuration, a mix, a cell and a layer metric to ``root`` by
    ADDING files and manifest entries only; returns the cell's name."""
    bench = os.path.join(root, "bench")
    cfg = load(os.path.join(bench, "configs", "starcoder2-3b.json"))
    cfg.update(TINY_CONFIG, name="tiny")
    cfg["deployment"] = {**cfg["deployment"], **TINY_DEPLOYMENT}
    dump(os.path.join(bench, "configs", "tiny.json"), cfg)
    mix = load(os.path.join(bench, "traffic", "codegen.json"))
    mix.update(
        name="tinymix", max_positions=88,
        prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 64},
        output_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5,
                       "min": 4, "max": 24})
    dump(os.path.join(bench, "traffic", "tinymix.json"), mix)
    cell = "tiny.tinymix.r80"
    dump(os.path.join(bench, "cells", cell + ".json"), {
        "name": cell, "config": "tiny", "mix": "tinymix", "chips": 1,
        "arrivals": {"kind": "open", "rate": 8.0},
        "drain_s": 2, "soak_s": 2, "trace_s": 1, "why": "a test's cell"})
    dump(os.path.join(bench, "layer_metrics", "prefill_ticks.json"), {
        "name": "prefill_ticks", "layer": "scheduler", "unit": "count",
        "reader": "genperf", "moves": "ttft_p50_ms",
        "formula": {"num": [{"path": "ticks.prefill"},
                            {"path": "ticks.mixed"}]},
        "what": "ticks that ran a prefill chunk"})
    man = load(os.path.join(root, "BENCHMARK.json"))
    man["configs"].append({
        "name": "tiny", "source": "a test", "reduced": [],
        "file": "bench/configs/tiny.json", "why": "a test's configuration"})
    man["workloads"].append({
        "name": cell, "config": "tiny", "traffic": "tinymix", "chips": 1,
        "why": "a test's cell"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    man["per_layer"].append({
        "name": "prefill_ticks", "unit": "count", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "ttft_p50_ms", "workloads": [cell]})
    dump(os.path.join(root, "BENCHMARK.json"), man)
    return cell
