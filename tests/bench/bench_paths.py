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
    cfg.update(TINY_CONFIG, name="tiny", source="a test")
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
matrices and an UNTIED head (``lm_head``).  A head is ``head_dim`` wide
where the configuration says so, else hidden // heads."""

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


def _hd(config):
    return config.get("head_dim", config["hidden_size"]
                      // config["num_attention_heads"])


def forward(params, tokens, config, at, lengths):
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    eps, theta, hd = config["rms_norm_eps"], config["rope_theta"], _hd(config)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"][tokens]
        B, S, D = x.shape
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
            x = x + a.reshape(B, S, H * hd) @ lp["wo"]
            h = _norm(x, lp["ln2"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
                     ) @ lp["w_down"]
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return _norm(x, p["ln_f"], eps) @ p["lm_head"]


def row_bytes(config, S, judged):
    D, F = config["hidden_size"], config["intermediate_size"]
    H = config["num_attention_heads"]
    return 4 * (2 * H * S * S + S * (4 * D + 4 * H * _hd(config) + 3 * F)
                + judged * config["vocab_size"])
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
    return {"hd": hd, "layer_params": layer,
            "matmul_params": L * layer + V * D,
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
        # the block as its "source" has it, every size given
        "departures": [], "assumed": [],
        "positions_limit": {"value": 512, "why": "a test's rotary table"},
        "hbm": {"total": "a test's: kilobytes"},
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


def check_ladder(man, cell):
    """The warm-up ladder of a cell takes every program its lengths can
    reach, and no row reaches past its configuration's own limit."""
    from lib import buckets

    doc = man.cell(cell)
    config = man.config(doc["config"])
    dep = man.deployment(doc, config)
    cp = buckets.caps(man.mix(doc["mix"]))
    want_pre, want_dec = buckets.reachable(dep, cp)
    got_pre, got_dec = set(), set()
    for length, max_new in buckets.ladder_rows(dep, cp):
        assert cp["min_prompt"] <= length <= cp["max_prompt"]
        pre, dec = buckets.touched(length, max_new, dep)
        got_pre |= pre
        got_dec |= dec
    assert want_pre <= got_pre and want_dec <= got_dec
    # brute force over every request the mix can draw
    seen_pre, seen_dec = set(), set()
    for p in range(cp["min_prompt"], cp["max_prompt"] + 1, 7):
        o = min(cp["max_out"], cp["max_positions"] - p)
        pre, dec = buckets.touched(p, o, dep)
        seen_pre |= pre
        seen_dec |= dec
    assert seen_pre <= want_pre and seen_dec <= want_dec
    progs = buckets.programs(dep, cp)
    rows = buckets.row_buckets(dep["slots"])
    assert rows[-1] == dep["slots"] and rows[0] == 1
    assert len(progs["prefill"]) == len(rows) * len(want_pre)
    assert len(progs["decode"]) == len(rows) * len(want_dec)
    # no row reaches past what the cell's OWN configuration can hold
    assert cp["max_positions"] + dep["span"] <= config["positions_limit"][
        "value"]


# -- the second toy architecture: a block that is NOT the repo's --------------

TOYMOE_REFERENCE = '''"""A test's block, shaped like what a draw of public models brings: RMSNorm
before each sub-layer; bias-free q, k, v of ``head_dim`` a head (NOT hidden
// heads); GQA; ``layer_types`` from the file: rotary embedding and a
window of ``sliding_window`` keys on the sliding layers, neither on the
full ones; ``num_dense_layers`` leading gated-SiLU layers, then expert
layers: sigmoid scores in float32 over the PUBLISHED number of experts,
the top ``num_experts_per_tok`` of them, weights normalised over the chosen
and times ``route_scale``, a shared expert beside them.  The file's
``num_experts`` is this chip's share (``reduced``): the first that many
are held, and a chosen expert that lives on another chip adds nothing, in
the program and here alike.  Untied head."""

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


def _gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _experts(h, lp, config):
    held = config["num_experts"]
    score = jax.nn.sigmoid(h @ lp["router"])            # every published one
    top, idx = jax.lax.top_k(score, config["num_experts_per_tok"])
    weight = top / top.sum(-1, keepdims=True) * config["route_scale"]
    # every held expert on every token, then the chosen ones among them
    every = jnp.einsum(
        "bsef,efd->bsed",
        jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, lp["e_gate"]))
        * jnp.einsum("bsd,edf->bsef", h, lp["e_up"]), lp["e_down"])
    onehot = jax.nn.one_hot(idx, held)                  # 0 for one not held
    return jnp.einsum("bsk,bske,bsed->bsd", weight, onehot, every)


def forward(params, tokens, config, at, lengths):
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"][tokens]
        B, S, D = x.shape
        ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        for i, kind in enumerate(config["layer_types"]):
            lp = p[f"l{i}"]
            h = _norm(x, lp["ln1"], eps)
            q = (h @ lp["wq"]).reshape(B, S, H, hd)
            k = (h @ lp["wk"]).reshape(B, S, KV, hd)
            v = (h @ lp["wv"]).reshape(B, S, KV, hd)
            seen = ahead >= 0
            if kind == "sliding_attention":
                q, k = (_rope(t, config["rope_theta"]) for t in (q, k))
                seen = seen & (ahead < config["sliding_window"])
            k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            s = jnp.where(seen, s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(B, S, H * hd) @ lp["wo"]
            h = _norm(x, lp["ln2"], eps)
            y = _gated(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            if i >= config["num_dense_layers"]:
                y = y + _experts(h, lp, config)
            x = x + y
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return _norm(x, p["ln_f"], eps) @ p["lm_head"]


def row_bytes(config, S, judged):
    """Scores and softmax, the stream's copies, and what dominates a routed
    block: every held expert's hidden on every token."""
    D, H, hd = (config["hidden_size"], config["num_attention_heads"],
                config["head_dim"])
    E, F = config["num_experts"], config["moe_intermediate_size"]
    return 4 * (2 * H * S * S + S * (4 * D + 4 * H * hd + 3 * E * F + E * D)
                + judged * config["vocab_size"])
'''

TOYMOE_NEEDS = '''"""Bytes and FLOPs of the test's routed block.  A step reads the experts
it chose, not all of them: the program's count where it has one
(``counters``), else the most ``rows`` tokens can choose."""


def sizes(config):
    D, hd = config["hidden_size"], config["head_dim"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    L, dense = config["num_hidden_layers"], config["num_dense_layers"]
    attn = D * hd * (H + 2 * KV) + H * hd * D
    expert = 3 * D * config["moe_intermediate_size"]
    return {"hd": hd, "attn_params": attn, "expert_params": expert,
            "dense_params": dense * (
                attn + 3 * D * config["intermediate_size"]),
            "expert_layers": L - dense,
            "head_params": config["vocab_size"] * D,
            "kv_bytes_per_position": L * 2 * KV * hd * 2}


def _step(config, rows, counters):
    s = sizes(config)
    chosen = rows * config["num_experts_per_tok"]
    read = counters.get("served_decode", {}).get(
        "experts_read_per_step", min(config["num_experts"], chosen))
    per_layer = s["attn_params"] + s["expert_params"] * (1 + read)
    return s, s["dense_params"] + s["expert_layers"] * per_layer + s[
        "head_params"]


def decode_step(config, rows, live_positions, counters):
    s, params = _step(config, rows, counters)
    return {"bytes": 2 * params
            + s["kv_bytes_per_position"] * (live_positions + rows),
            "flops": 2.0 * params * rows}


def prefill(config, calls, tokens, attended_positions, counters):
    s, params = _step(config, tokens / max(calls, 1), counters)
    return {"bytes": calls * 2 * params
            + 2 * s["kv_bytes_per_position"] * tokens,
            "flops": 2.0 * params * tokens}
'''

TOYMOE_CONFIG = {
    "name": "toymoe", "source": "a test", "arch": "toymoe",
    "described_as": "a test's block as its source has it, one chip's share "
                    "of its experts",
    "unit": {
        "class_path": "toy_moe:ToyMoEGenerator",
        "parameters": {
            "vocab": {"from": "vocab_size"},
            "d_model": {"from": "hidden_size"},
            "head_dim": {"from": "head_dim"},
            "n_heads": {"from": "num_attention_heads"},
            "n_kv_heads": {"from": "num_key_value_heads"},
            "n_layers": {"from": "num_hidden_layers"},
            "n_dense_layers": {"from": "num_dense_layers"},
            "d_ff": {"from": "intermediate_size"},
            "d_expert": {"from": "moe_intermediate_size"},
            "n_experts": {"from": "num_experts"},
            "router_width": 32,
            "experts_per_tok": {"from": "num_experts_per_tok"},
            "window": {"from": "sliding_window"},
            "full_every": {"from": "global_attn_every_n_layers"},
            "rope_base": {"from": "rope_theta"},
            "norm_eps": {"from": "rms_norm_eps"},
            "route_scale": {"from": "route_scale"},
        },
    },
    # hidden // heads is 16: the head is 32 wide because the file says so
    "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3, "num_dense_layers": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "route_scale": 1.5, "vocab_size": 512, "tie_word_embeddings": False,
    "sliding_window": 48, "global_attn_every_n_layers": 3,
    # the list stays in the file for the reference; the unit derives it
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 128,
    "positions_limit": {
        "value": 128, "why": "max_position_embeddings; the window of 48 "
                             "is implemented and every judged row of more "
                             "than 48 positions crosses it"},
    "reduced": ["num_experts"],
    "published": {"num_experts": 32},
    "shared_by_chips": 4,
    "layer_divided": "each expert layer's 32 routed experts over 4 chips, "
                     "8 here; attention, the shared expert and the router "
                     "(all 32 scores, top 2) on every chip",
    "assumed": [], "departures": [],
    "hbm": {"total": "a test's: kilobytes"},
    "numerics": {
        "tolerance_rms": 0.1,
        "discrete_share": {
            "prefill": 0.25, "decode": 0.25,
            "why": "a top-2 router: a score that falls the other way spoils "
                   "a row wholly (tests/bench/test_bench_verdict.py)"}},
}


def add_toy_moe(root: str) -> str:
    """Add ``bench/archs/toymoe/`` — a routed block with a head width, a
    window, an untied head and a chip's share of its experts — its
    configuration and a cell on ``add_tiny_cell``'s mix, by ADDING files
    and manifest entries only.  Its program side is tests/bench/toy_moe.py.
    Returns the cell's name."""
    bench = os.path.join(root, "bench")
    arch = os.path.join(bench, "archs", "toymoe")
    os.makedirs(arch)
    with open(os.path.join(arch, "reference.py"), "w") as f:
        f.write(TOYMOE_REFERENCE)
    with open(os.path.join(arch, "needs.py"), "w") as f:
        f.write(TOYMOE_NEEDS)
    base = load(os.path.join(bench, "configs", "starcoder2-3b.json"))
    dump(os.path.join(bench, "configs", "toymoe.json"), {
        **TOYMOE_CONFIG,
        "deployment": {**base["deployment"], **TINY_DEPLOYMENT}})
    cell = "toymoe.tinymix.r80"
    why = ("tinymix on a routed block: rows of 9-80 positions, the longest "
           "two past the window of 48")
    dump(os.path.join(bench, "cells", cell + ".json"), {
        "name": cell, "config": "toymoe", "mix": "tinymix", "chips": 1,
        "arrivals": {"kind": "open", "rate": 8.0},
        "drain_s": 2, "soak_s": 2, "trace_s": 1, "why": why})
    man = load(os.path.join(root, "BENCHMARK.json"))
    man["configs"].append({
        "name": "toymoe", "source": "a test", "reduced": ["num_experts"],
        "file": "bench/configs/toymoe.json",
        "why": "a test's routed block, one chip's share of its experts"})
    man["workloads"].append({
        "name": cell, "config": "toymoe", "traffic": "tinymix", "chips": 1,
        "why": why})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    dump(os.path.join(root, "BENCHMARK.json"), man)
    return cell


# -- the third toy architecture: a round that is NOT one token a step ---------

TOYBLOCKDIFF_REFERENCE = '''"""A test's generator by diffusion over blocks, as its "source" has it: the
Qwen3 block at a toy's sizes — RMSNorm before each sub-layer, bias-free q,
k, v of ``head_dim`` a head with an RMSNorm a head on q and k, rotary
embedding, GQA, a gated-SiLU FFN, an untied head — under a BLOCK-CAUSAL
mask: a position sees every key up to the end of its own block of
``block_length``, the later ones of that block too.  No cache: one pass
over a context as a pass of the program saw it (mask ids where they
stood).  A row's last block may be short (a prompt that is no whole number
of blocks), and then what follows it in a padded group is no key of the
row: ``lengths`` keeps the pad out."""

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


def forward(params, tokens, config, at, lengths):
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps, theta = (config["head_dim"], config["rms_norm_eps"],
                      config["rope_theta"])
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"][tokens]
        B, S, D = x.shape
        here = jnp.arange(S)
        block_end = (here // config["block_length"] + 1) * config[
            "block_length"]
        seen = here[None, :] < block_end[:, None]                 # [S, S]
        seen = seen[None] & (here[None, None, :] < lengths[:, None, None])
        for i in range(config["num_hidden_layers"]):
            lp = p[f"l{i}"]
            h = _norm(x, lp["ln1"], eps)
            q = _norm((h @ lp["wq"]).reshape(B, S, H, hd), lp["q_norm"], eps)
            k = _norm((h @ lp["wk"]).reshape(B, S, KV, hd), lp["k_norm"], eps)
            v = (h @ lp["wv"]).reshape(B, S, KV, hd)
            q, k = _rope(q, theta), _rope(k, theta)
            k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            s = jnp.where(seen[:, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(B, S, H * hd) @ lp["wo"]
            h = _norm(x, lp["ln2"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
                     ) @ lp["w_down"]
        x = jnp.take_along_axis(x, at[..., None], axis=1)
        return _norm(x, p["ln_f"], eps) @ p["lm_head"]


def row_bytes(config, S, judged):
    D, F = config["hidden_size"], config["intermediate_size"]
    H, hd = config["num_attention_heads"], config["head_dim"]
    return 4 * (2 * H * S * S + S * (4 * D + 4 * H * hd + 3 * F)
                + judged * config["vocab_size"])
'''

TOYBLOCKDIFF_NEEDS = '''"""Bytes and FLOPs of a generator whose round is not ``span`` single-token
steps.  The roofline reader multiplies ``decode_step`` by calls x ``span``
(readers/trace.py), so ``decode_step`` is a ``span``-th of what ONE ROUND
needs, and the round is written down here from the file's own sizes and
``deployment``: ``span / block_length`` blocks, each ``denoising_steps``
passes and one more that writes its K/V; a pass reads every weight and the
rows' live K/V once and computes ``rows x block_length`` positions."""


def sizes(config):
    D, F, hd = (config["hidden_size"], config["intermediate_size"],
                config["head_dim"])
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    L, V = config["num_hidden_layers"], config["vocab_size"]
    layer = D * hd * (H + 2 * KV) + H * hd * D + 3 * D * F
    return {"hd": hd, "layer_params": layer,
            "matmul_params": L * layer + V * D,
            "weight_bytes": 2 * (L * layer + 2 * V * D),
            "kv_bytes_per_position": L * 2 * KV * hd * 2,
            "attn_flops_per_position": 4 * L * H * hd}


def round_needs(config, rows, live_positions):
    s, block = sizes(config), config["block_length"]
    blocks = config["deployment"]["span"] // block
    passes = config["denoising_steps"] + 1
    a_pass = {
        "bytes": s["weight_bytes"]
        + s["kv_bytes_per_position"] * live_positions,
        "flops": 2.0 * s["matmul_params"] * rows * block
        + s["attn_flops_per_position"] * live_positions * block}
    return {"bytes": blocks * (passes * a_pass["bytes"]
                               + s["kv_bytes_per_position"] * rows * block),
            "flops": blocks * passes * a_pass["flops"]}


def decode_step(config, rows, live_positions, counters):
    span = config["deployment"]["span"]
    return {k: v / span
            for k, v in round_needs(config, rows, live_positions).items()}


def prefill(config, calls, tokens, attended_positions, counters):
    s = sizes(config)
    return {"bytes": calls * s["weight_bytes"]
            + 2 * s["kv_bytes_per_position"] * tokens,
            "flops": 2.0 * s["matmul_params"] * tokens
            + s["attn_flops_per_position"] * attended_positions}
'''

TOYBLOCKDIFF_DRIVE = '''"""How a round of the test's block-diffusion generator is driven, and what
of it the reference is asked (lib/children.py says what an event is).

The prefill has run over each whole prompt, its last block short where the
prompt is no whole number of blocks: that is the row's ``prefill`` event.
The round is the program's own (``paged_decode_round``: in the tests the
toy's in its place): ``span / block_length`` blocks a row, the first one
begun by the prompt's remainder.  Every denoising pass that unmasked
something in a row is an event: the context is the prompt's whole blocks,
the blocks this round has finished and the block AS THAT PASS SAW IT (mask
ids where they stood); the positions judged are the ones it unmasked, with
the ids it put there.  The pass that writes a finished block's K/V chooses
nothing and is no event: the next block's events see what it wrote."""

import jax.numpy as jnp
import numpy as np

NOT_JUDGED = -1


def drive(unit, params, pool, tables, prompts, logits, deployment):
    from seldon_core_tpu.models.generate import paged_decode_round_jit

    block = unit.cfg.block_length
    R, B = len(prompts), tables.shape[0]
    n_valid = np.zeros((B,), np.int32)
    n_valid[:R] = [len(p) for p in prompts]
    blocks, pool, passes = paged_decode_round_jit(
        params, pool, tables, jnp.zeros((B,), jnp.int32),
        jnp.asarray(n_valid), jnp.asarray(n_valid > 0),
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.uint32), unit.cfg,
        span=deployment["span"], temperature=unit.temperature,
        top_k=unit.top_k, top_p=unit.top_p, eos_token=unit.eos_token)
    blocks = np.asarray(blocks)
    whole = [len(p) - len(p) % block for p in prompts]
    events = [{"row": r, "ids": p, "at": np.asarray([len(p) - 1]),
               "chose": np.asarray([NOT_JUDGED]), "prefill": 0}
              for r, p in enumerate(prompts)]
    for a_pass in passes:
        done = a_pass["block"] * block
        saw, picked, chose = (np.asarray(a_pass[k])
                              for k in ("saw", "picked", "chose"))
        for r, p in enumerate(prompts):
            at = np.flatnonzero(picked[r])
            if len(at):
                events.append({
                    "row": r, "chose": chose[r, at],
                    "ids": np.concatenate([p[:whole[r]], blocks[r, :done],
                                           saw[r]]),
                    "at": whole[r] + done + at})
    return {"tokens": [blocks[r, len(p) - whole[r]:]
                       for r, p in enumerate(prompts)],
            "events": events}
'''

TOYBLOCKDIFF_CONFIG = {
    "name": "toyblockdiff", "source": "a test", "arch": "toyblockdiff",
    "described_as": "a test's generator by diffusion over blocks, as its "
                    "source has it",
    "unit": {
        "class_path": "toy_blockdiff:ToyBlockDiffGenerator",
        "parameters": {
            "vocab": {"from": "vocab_size"},
            "d_model": {"from": "hidden_size"},
            "head_dim": {"from": "head_dim"},
            "n_heads": {"from": "num_attention_heads"},
            "n_kv_heads": {"from": "num_key_value_heads"},
            "n_layers": {"from": "num_hidden_layers"},
            "d_ff": {"from": "intermediate_size"},
            "rope_base": {"from": "rope_theta"},
            "norm_eps": {"from": "rms_norm_eps"},
            "block_length": {"from": "block_length"},
            "denoising_steps": {"from": "denoising_steps"},
            "mask_id": {"from": "mask_token_id"},
        },
    },
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2,
    "intermediate_size": 128, "vocab_size": 512,
    "tie_word_embeddings": False, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "max_position_embeddings": 128,
    "block_length": 4, "denoising_steps": 4, "mask_token_id": 500,
    # not the last id of the vocabulary: a range check alone lets it through
    "reserved_ids": [{
        "id": 500, "why": "mask_token_id: in a prompt it is a hole the "
                          "program fills, in an answer a position it never "
                          "filled"}],
    "positions_limit": {"value": 128, "why": "max_position_embeddings"},
    "reduced": [], "assumed": [], "departures": [],
    "hbm": {"total": "a test's: kilobytes"},
    "numerics": {"tolerance_rms": 0.1},
}
# what a round of this generator does to the ladder's arithmetic
# (lib/buckets.py): a prefill emits nothing, and a row's first round starts
# where its last whole block ends
TOYBLOCKDIFF_ROUND = {"prefill_emits": 0, "round_quantum": 4}


def add_toy_blockdiff(root: str) -> str:
    """Add ``bench/archs/toyblockdiff/`` — reference, needs AND the driver
    of a round that is passes over a block — its configuration with a
    reserved mask id and a cell on ``add_tiny_cell``'s mix, by ADDING files
    and manifest entries only.  Its program side is
    tests/bench/toy_blockdiff.py.  Returns the cell's name."""
    bench = os.path.join(root, "bench")
    arch = os.path.join(bench, "archs", "toyblockdiff")
    os.makedirs(arch)
    for name, text in (("reference", TOYBLOCKDIFF_REFERENCE),
                       ("needs", TOYBLOCKDIFF_NEEDS),
                       ("drive", TOYBLOCKDIFF_DRIVE)):
        with open(os.path.join(arch, name + ".py"), "w") as f:
            f.write(text)
    base = load(os.path.join(bench, "configs", "starcoder2-3b.json"))
    dump(os.path.join(bench, "configs", "toyblockdiff.json"), {
        **TOYBLOCKDIFF_CONFIG,
        "deployment": {**base["deployment"], **TINY_DEPLOYMENT,
                       **TOYBLOCKDIFF_ROUND}})
    cell = "toyblockdiff.tinymix.r80"
    why = ("tinymix on a generator by diffusion over blocks of 4: prompts of "
           "8-64 tokens, most no whole number of blocks, two blocks a round")
    dump(os.path.join(bench, "cells", cell + ".json"), {
        "name": cell, "config": "toyblockdiff", "mix": "tinymix", "chips": 1,
        "arrivals": {"kind": "open", "rate": 8.0},
        "drain_s": 2, "soak_s": 2, "trace_s": 1, "why": why})
    man = load(os.path.join(root, "BENCHMARK.json"))
    man["configs"].append({
        "name": "toyblockdiff", "source": "a test", "reduced": [],
        "file": "bench/configs/toyblockdiff.json",
        "why": "a test's generator whose round is passes over a block"})
    man["workloads"].append({
        "name": cell, "config": "toyblockdiff", "traffic": "tinymix",
        "chips": 1, "why": why})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    dump(os.path.join(root, "BENCHMARK.json"), man)
    return cell


def toy_root(tmp_path) -> str:
    """A copy of the benchmark with the dense toys, ``toymoe`` and
    ``toyblockdiff`` added, no file that was there edited."""
    root = copy_root(tmp_path)
    before = snapshot(root)
    add_tiny_cell(root)
    add_tiny_arch(root)
    add_toy_moe(root)
    add_toy_blockdiff(root)
    assert_untouched(before)
    return root
