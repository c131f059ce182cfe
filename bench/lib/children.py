"""The child that owns the chip once the engine has gone.  It prints ONE
JSON object as its last stdout line.

  numerics    the program's ``paged_forward`` prefill followed by
              ``paged_decode_round`` at the configuration's full widths
              against the plain reference of the block the configuration
              names (archs/<arch>/reference.py), on a seeded sample.

    python bench/lib/children.py numerics <spec.json>
"""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(spec: dict):
    sys.path.insert(0, spec["repo"])
    sys.path.insert(0, BENCH)
    import jax

    from seldon_core_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] not in spec["platforms"]:
        raise SystemExit(
            f"device is {device}, the run needs one of {spec['platforms']}")
    return jax, device


def build_unit(unit: dict):
    """The program's own generator unit, built the way the engine builds
    it from a deployment file (``graph/units.py``): the class the
    ``class_path`` resolves to, with the typed parameter list as its
    keywords — so ``cfg`` is the object the engine jits with, and nothing
    the configuration passes can be dropped on the way."""
    from seldon_core_tpu.graph.spec import Parameter, params_to_kwargs
    from seldon_core_tpu.graph.units import resolve_unit_class

    cls = resolve_unit_class(unit["class_path"])
    return cls(**params_to_kwargs(
        [Parameter.from_json_dict(d) for d in unit["parameters"]]))


def numerics(spec: dict) -> dict:
    jax, device = _setup(spec)
    import jax.numpy as jnp
    import numpy as np

    from lib.manifest import arch_module
    from seldon_core_tpu.models.generate import (
        init_block_pool,
        paged_decode_round_jit,
        paged_forward_jit,
    )

    reference = arch_module(spec["bench_dir"], spec["config"], "reference")
    unit = build_unit(spec["unit"])
    cfg, dep = unit.cfg, spec["deployment"]
    params = unit.init_state(None)["params"]
    pool = init_block_pool(cfg, dep["pool_blocks"], dep["block_size"])
    span, C = dep["span"], dep["prefill_chunk"]
    lens = spec["sample_lens"]           # e.g. [40, 33]: one program (B, C, 4)
    B = len(lens)
    rng = np.random.default_rng(spec["sample_seed"])
    toks = np.zeros((B, C), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, spec["config"]["vocab_size"], n)
    nblk = spec["sample_blocks"]
    tables = np.zeros((B, nblk), np.int32)
    for i in range(B):
        tables[i] = 1 + i * nblk + np.arange(nblk)
    width = np.asarray(lens, np.int32)
    logits, pool = paged_forward_jit(
        params, jnp.asarray(toks), pool, jnp.asarray(tables),
        jnp.zeros((B,), jnp.int32), jnp.asarray(width), cfg=cfg,
        last_only=True)
    sys_logits = np.asarray(logits)
    first = sys_logits.argmax(-1).astype(np.int32)
    out, pool, *_ = paged_decode_round_jit(
        params, pool, jnp.asarray(tables), jnp.asarray(first),
        jnp.asarray(width), jnp.ones((B,), bool), jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.uint32), cfg, span=span,
        temperature=unit.temperature, top_k=unit.top_k, top_p=unit.top_p,
        eos_token=unit.eos_token)
    sys_toks = np.asarray(out)            # [B, span]
    del pool

    worst_prefill = 0.0
    worst_margin = 0.0
    rms = []
    for i, n in enumerate(lens):
        # the row's prompt, its first token and the round's tokens, through
        # the reference in ONE full causal pass (teacher-forced on the
        # system's own tokens): position n-1 gives the prefill logits,
        # positions n .. n+span-1 the logits each decode step chose from
        seq = np.concatenate([toks[i, :n], first[i:i + 1], sys_toks[i]])
        ref = np.asarray(reference.forward(
            params, jnp.asarray(seq[None, :-1]), spec["config"]))[0]
        rms.append(float(np.sqrt(np.mean(ref[n - 1] ** 2))))
        worst_prefill = max(worst_prefill,
                            float(np.abs(ref[n - 1] - sys_logits[i]).max()))
        for j in range(span):
            row = ref[n + j]
            worst_margin = max(worst_margin,
                               float(row.max() - row[sys_toks[i, j]]))
    ref_rms = float(np.mean(rms))
    tol = spec["tolerance_rms"] * ref_rms
    return {
        "device": device, "ref_logit_rms": ref_rms,
        "prefill_max_abs_err": worst_prefill,
        "decode_max_margin": worst_margin, "tolerance": tol,
        "ok": bool(worst_prefill <= tol and worst_margin <= 2 * tol),
        "memory_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()),
    }


if __name__ == "__main__":
    with open(sys.argv[2]) as f:
        _spec = json.load(f)
    _result = {"numerics": numerics}[sys.argv[1]](_spec)
    print(json.dumps(_result), flush=True)
