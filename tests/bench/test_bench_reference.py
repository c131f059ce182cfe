"""The plain reference (bench/archs/dense_gelu/reference.py) against the program at a
tiny size on the CPU: the dense forward, and prefill followed by a decode
round through the paged pool."""

import bench_paths
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lib.manifest import arch_module

reference = arch_module(bench_paths.BENCH, {"arch": "dense_gelu"},
                        "reference")

# configuration documents: the published keys the reference reads
SIZES = [
    dict(vocab_size=97, hidden_size=64, num_attention_heads=4,
         num_key_value_heads=2, num_hidden_layers=2, intermediate_size=160,
         rope_theta=999999.44),
    dict(vocab_size=61, hidden_size=96, num_attention_heads=6,
         num_key_value_heads=2, num_hidden_layers=3, intermediate_size=128,
         rope_theta=999999.44),
]


def build(sz, dtype=jnp.float32):
    """The program's own config and weights at a configuration's sizes;
    the reference gets the configuration document, never ``cfg``."""
    from seldon_core_tpu.models.transformer import LMConfig, lm_init

    cfg = LMConfig(
        dtype=dtype, rope=True, rope_base=sz["rope_theta"],
        vocab=sz["vocab_size"], d_model=sz["hidden_size"],
        n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"],
        n_layers=sz["num_hidden_layers"], d_ff=sz["intermediate_size"])
    return cfg, lm_init(jax.random.key(3), cfg)


@pytest.mark.parametrize("sz", SIZES, ids=["gqa2", "gqa3"])
def test_reference_equals_the_programs_dense_forward(sz):
    from seldon_core_tpu.models.transformer import lm_apply

    cfg, params = build(sz)
    toks = jax.random.randint(jax.random.key(1), (2, 19), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lm_apply(params, toks, cfg))
    got = np.asarray(reference.forward(params, toks, sz))
    # float32 against float32: only the order of additions differs
    assert np.abs(got - want).max() < 2e-4 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("sz", SIZES, ids=["gqa2", "gqa3"])
def test_paged_prefill_then_decode_round_agree_with_the_reference(sz):
    from seldon_core_tpu.models.generate import (
        init_block_pool,
        paged_decode_round_jit,
        paged_forward_jit,
    )

    cfg, params = build(sz)
    lens, C, nblk, span = [13, 9], 16, 2, 4
    rng = np.random.default_rng(0)
    toks = np.zeros((2, C), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    tables = np.asarray([[1, 2], [3, 4]], np.int32)
    pool = init_block_pool(cfg, 8, 16)
    logits, pool = paged_forward_jit(
        params, jnp.asarray(toks), pool, jnp.asarray(tables),
        jnp.zeros((2,), jnp.int32), jnp.asarray(lens, jnp.int32), cfg=cfg,
        last_only=True)
    first = np.asarray(logits).argmax(-1).astype(np.int32)
    out, *_ = paged_decode_round_jit(
        params, pool, jnp.asarray(tables), jnp.asarray(first),
        jnp.asarray(lens, jnp.int32), jnp.ones((2,), bool),
        jnp.zeros((2,), bool), jnp.zeros((2,), jnp.uint32), cfg, span=span,
        temperature=0.0, top_k=0, top_p=0.0, eos_token=-1)
    out = np.asarray(out)
    for i, n in enumerate(lens):
        seq = np.concatenate([toks[i, :n], first[i:i + 1], out[i]])
        ref = np.asarray(reference.forward(
            params, jnp.asarray(seq[None, :-1]), sz))[0]
        assert np.abs(ref[n - 1] - np.asarray(logits)[i]).max() < 1e-3
        for j in range(span):     # each step chose (nearly) the best logit
            assert ref[n + j].max() - ref[n + j][out[i, j]] < 1e-3


def test_a_lower_precision_than_bf16_would_fail_the_chip_tolerance():
    """The chip tolerance is 0.1 x the logits' rms (configuration files,
    ``numerics.tolerance_rms``).  At a tiny size: bf16 weights stay well
    inside it, weights rounded to 4 mantissa bits do not."""
    cfg, params = build(SIZES[0])
    toks = jax.random.randint(jax.random.key(2), (2, 24), 0, cfg.vocab)
    want = np.asarray(reference.forward(params, toks, SIZES[0]))
    rms = float(np.sqrt(np.mean(want ** 2)))

    def rounded(bits):
        def f(a):
            if a.ndim < 2:
                return a
            m, e = np.frexp(np.asarray(a, np.float32))
            return jnp.asarray(np.ldexp(np.round(m * 2 ** bits) / 2 ** bits,
                                        e), jnp.float32)
        return jax.tree.map(f, params)

    def err(p):
        got = np.asarray(reference.forward(p, toks, SIZES[0]))
        return float(np.abs(got - want).max())

    assert err(rounded(8)) < 0.1 * rms     # bf16 keeps 8 bits
    assert err(rounded(3)) > 0.1 * rms     # fp8-e4m3 keeps 3
