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


def snapshot(root: str) -> dict:
    """Every file under ``root`` but the manifest, as bytes: what adding
    something may not touch."""
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if f != "BENCHMARK.json":
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = fh.read()
    return out


def assert_untouched(before: dict) -> None:
    for p, raw in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == raw, f"{p} was edited"


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


TINYARCH_REFERENCE = '''"""A test's block, not the repo's: pre-RMSNorm (eps from the configuration),
bias-free separate Q/K/V, half-split RoPE, GQA, a GATED SiLU FFN of three
matrices and an UNTIED head (``lm_head``)."""

import math

import jax
import jax.numpy as jnp


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    half = x.shape[-1] // 2
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def forward(params, tokens, config):
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"][tokens]
        B, S, D = x.shape
        hd = D // H
        for i in range(config["num_hidden_layers"]):
            lp = p[f"l{i}"]
            h = _norm(x, lp["ln1"], eps)
            q = _rope((h @ lp["wq"]).reshape(B, S, H, hd), theta)
            k = _rope((h @ lp["wk"]).reshape(B, S, KV, hd), theta)
            v = (h @ lp["wv"]).reshape(B, S, KV, hd)
            k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(B, S, D) @ lp["wo"]
            h = _norm(x, lp["ln2"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
                     ) @ lp["w_down"]
        return _norm(x, p["ln_f"], eps) @ p["lm_head"]
'''

TINYARCH_NEEDS = '''"""Bytes and FLOPs of the test's block: THREE FFN matrices a layer, an
untied head (the unembedding reads V*D a step; the embedding is only
gathered), and the prompt tokens the program counted."""


def sizes(config):
    D, F = config["hidden_size"], config["intermediate_size"]
    hd = D // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    L, V = config["num_hidden_layers"], config["vocab_size"]
    layer = D * (D + 2 * kv) + D * D + 3 * D * F
    return {"layer_params": layer, "matmul_params": L * layer + V * D,
            "weight_bytes": 2 * (L * layer + V * D),
            "kv_bytes_per_position": L * 2 * kv * 2,
            "attn_flops_per_position": 4 * L * D}


def decode_step(config, rows, live_positions, counters):
    s = sizes(config)
    return {"bytes": s["weight_bytes"]
            + s["kv_bytes_per_position"] * (live_positions + rows),
            "flops": 2.0 * s["matmul_params"] * rows
            + s["attn_flops_per_position"] * live_positions}


def prefill(config, calls, tokens, attended_positions, counters):
    s = sizes(config)
    # a block may take what the program counted instead of the harness's
    # estimate: here the prompt tokens of the window, where there are any
    tokens = counters.get("served_prefill", {}).get("tokens") or tokens
    return {"bytes": calls * s["weight_bytes"]
            + 2 * s["kv_bytes_per_position"] * tokens,
            "flops": 2.0 * s["matmul_params"] * tokens
            + s["attn_flops_per_position"] * attended_positions}
'''

TINYARCH_UNIT = {
    "class_path": "a_test.units:GatedGenerator",
    "parameters": {
        "vocab": {"from": "vocab_size"}, "d_model": {"from": "hidden_size"},
        "n_heads": {"from": "num_attention_heads"},
        "n_kv_heads": {"from": "num_key_value_heads"},
        "n_layers": {"from": "num_hidden_layers"},
        "d_ff": {"from": "intermediate_size"},
        "rope_base": {"from": "rope_theta"},
        "norm_eps": {"from": "rms_norm_eps"},
        "tie_head": {"from": "tie_word_embeddings"},
        "ffn": "gated_silu", "n_experts": 0,
    },
}


def add_tiny_arch(root: str) -> str:
    """Add an ARCHITECTURE to ``root`` — ``bench/archs/tinyarch/`` with the
    reference and the needs of a block the repo does not have, and a
    configuration that names it and its own unit — by ADDING files and
    manifest entries only; then a cell of that configuration on
    ``add_tiny_cell``'s mix.  Returns the cell's name."""
    bench = os.path.join(root, "bench")
    arch = os.path.join(bench, "archs", "tinyarch")
    os.makedirs(arch)
    with open(os.path.join(arch, "reference.py"), "w") as f:
        f.write(TINYARCH_REFERENCE)
    with open(os.path.join(arch, "needs.py"), "w") as f:
        f.write(TINYARCH_NEEDS)
    base = load(os.path.join(bench, "configs", "starcoder2-3b.json"))
    cfg = {
        "name": "tinygated", "source": "a test", "arch": "tinyarch",
        "described_as": "a test's sizes through a test's block",
        "unit": TINYARCH_UNIT, **TINY_CONFIG,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": False, "reduced": [],
        "deployment": {**base["deployment"], **TINY_DEPLOYMENT},
        "numerics": base["numerics"],
    }
    dump(os.path.join(bench, "configs", "tinygated.json"), cfg)
    cell = "tinygated.tinymix.r80"
    dump(os.path.join(bench, "cells", cell + ".json"), {
        "name": cell, "config": "tinygated", "mix": "tinymix", "chips": 1,
        "arrivals": {"kind": "open", "rate": 8.0},
        "drain_s": 2, "soak_s": 2, "trace_s": 1,
        "why": "a test's cell of a test's block"})
    man = load(os.path.join(root, "BENCHMARK.json"))
    man["configs"].append({
        "name": "tinygated", "source": "a test", "reduced": [],
        "file": "bench/configs/tinygated.json",
        "why": "a test's configuration of a test's block"})
    man["workloads"].append({
        "name": cell, "config": "tinygated", "traffic": "tinymix",
        "chips": 1, "why": "a test's cell of a test's block"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    dump(os.path.join(root, "BENCHMARK.json"), man)
    return cell
