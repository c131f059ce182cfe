#!/usr/bin/env python3
"""Compile the two paged programs at a configuration's real widths for a
DESCRIBED v5e chip (no chip attached) and print ``memory_analysis()`` and
the compile seconds.  This is how ``pool_blocks`` in bench/configs/*.json
was sized; nothing here runs on a device and nothing it prints is a device
metric.  The unit is built as the numerics child builds it
(lib/children.py ``build_unit``, from the configuration's ``unit``
section), and the decode round is lowered with ``inplace=True``: the
program the chip runs, where ``JAX_PLATFORMS=cpu`` alone would pick the
gather path.

    JAX_PLATFORMS=cpu python bench/tools/rehearse_aot.py \
        --config starcoder2-3b --pool-blocks 12000 \
        --prefill 8,256,64 --decode 64,128
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--pool-blocks", type=int, required=True)
    ap.add_argument("--prefill", action="append", default=[],
                    help="rows,chunk,blocks")
    ap.add_argument("--decode", action="append", default=[],
                    help="rows,blocks")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lib.children import build_unit
    from lib.engine import unit_spec
    from seldon_core_tpu.models.generate import (
        init_block_pool,
        paged_decode_round_jit,
        paged_forward_jit,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        doc = json.load(f)
    dep = doc["deployment"]
    unit = build_unit(unit_spec(doc, dep, 0, 1))
    cfg = unit.cfg
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(jax.eval_shape(
        lambda: unit.init_state(None)["params"]))
    n_param = sum(x.size for x in jax.tree.leaves(params))
    # on the CPU backend init_block_pool stores a bf16 pool as float32
    # (XLA:CPU has no bf16 scatter); the chip keeps the unit's dtype
    pool = on_chip(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, cfg.dtype if s.ndim == 4 and s.dtype == jnp.float32
            else s.dtype),
        jax.eval_shape(lambda: init_block_pool(
            cfg, args.pool_blocks, int(dep["block_size"])))))
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool))
    print(json.dumps({"config": args.config, "params": n_param,
                      "param_bytes": 2 * n_param,
                      "pool_blocks": args.pool_blocks,
                      "pool_bytes": pool_bytes}), flush=True)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def report(name, lowered):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        print(json.dumps({
            "program": name,
            "compile_s": round(time.perf_counter() - t0, 1),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes,
        }), flush=True)

    for spec in args.prefill:
        B, C, nblk = (int(v) for v in spec.split(","))
        report(f"prefill({B},{C},{nblk})", paged_forward_jit.lower(
            params, arr((B, C), jnp.int32), pool, arr((B, nblk), jnp.int32),
            arr((B,), jnp.int32), arr((B,), jnp.int32), cfg=cfg,
            last_only=True))
    for spec in args.decode:
        B, nblk = (int(v) for v in spec.split(","))
        report(f"decode({B},{nblk})", paged_decode_round_jit.lower(
            params, pool, arr((B, nblk), jnp.int32), arr((B,), jnp.int32),
            arr((B,), jnp.int32), arr((B,), jnp.bool_), arr((B,), jnp.bool_),
            arr((B,), jnp.uint32), cfg, span=int(dep["span"]),
            temperature=0.0, top_k=0, top_p=0.0, eos_token=-1,
            inplace=True))


if __name__ == "__main__":
    main()
