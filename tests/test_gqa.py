"""Grouped-query attention: cache shapes shrink by the group factor, the
GQA formulation matches head-repeated MHA numerics exactly, generation and
training run end-to-end, and invalid head configs fail at config time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.generate import (
    TransformerGenerator,
    generate,
    init_block_pool,
)
from seldon_core_tpu.models.transformer import (
    LMConfig,
    gqa_attention,
    lm_apply,
    lm_init,
    lm_train_step,
)


def test_gqa_matches_repeated_mha_numerics():
    """gqa_attention == plain attention with K/V heads explicitly
    repeated — the formulation only changes the dataflow, not the math."""
    rng = np.random.default_rng(0)
    B, H, KV, S, hd = 2, 8, 2, 16, 32
    q = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, hd)), jnp.float32)
    got = np.asarray(gqa_attention(q, k, v, causal=True))

    krep = jnp.repeat(k, H // KV, axis=1)
    vrep = jnp.repeat(v, H // KV, axis=1)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, krep) * scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    s = jnp.where(qpos >= kpos, s, -1e30)
    want = np.asarray(jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vrep
    ))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_gqa_cache_shrinks_by_group_factor():
    cfg_mha = LMConfig(vocab=64, d_model=64, n_heads=8, n_layers=2, d_ff=128)
    cfg_gqa = LMConfig(vocab=64, d_model=64, n_heads=8, n_layers=2, d_ff=128,
                       n_kv_heads=2)
    # the pool's KV axis: [num_blocks, block_size, KV, hd]
    c_mha = init_block_pool(cfg_mha, num_blocks=9, block_size=16)
    c_gqa = init_block_pool(cfg_gqa, num_blocks=9, block_size=16)
    assert c_mha["l0"]["k"].shape == (9, 16, 8, 8)
    assert c_gqa["l0"]["k"].shape == (9, 16, 2, 8)
    # wqkv output shrinks too: q (64) + k/v (2 heads x 8 dim each)
    p = lm_init(jax.random.key(0), cfg_gqa)
    assert p["l0"]["wqkv"].shape == (64, 64 + 2 * 2 * 8)


def test_gqa_generate_prefill_decode_consistency():
    """generate() (prefill + cached decode scan) must agree with teacher
    forcing through lm_apply: greedy tokens re-fed through the full forward
    reproduce the same argmax chain."""
    cfg = LMConfig(vocab=64, d_model=64, n_heads=8, n_layers=2, d_ff=128,
                   n_kv_heads=2, dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(2, 8)), jnp.int32
    )
    toks = np.asarray(generate(params, prompt, cfg, max_new_tokens=6))
    full = np.asarray(prompt)
    for i in range(6):
        logits = np.asarray(lm_apply(params, jnp.asarray(full), cfg))
        nxt = logits[:, -1, :].argmax(-1)
        np.testing.assert_array_equal(nxt, toks[:, i])
        full = np.concatenate([full, nxt[:, None].astype(np.int32)], axis=1)


def test_gqa_unit_serves_and_int8_composes():
    gen = TransformerGenerator(vocab=64, d_model=64, n_heads=8, n_kv_heads=2,
                               n_layers=2, d_ff=128, max_new_tokens=8,
                               dtype="float32", quant="int8")
    state = gen.init_state(jax.random.key(0))
    y = np.asarray(gen.predict(state, jnp.zeros((2, 4), jnp.float32)))
    assert y.shape == (2, 8)
    assert ((y >= 0) & (y < 64)).all()


def test_gqa_trains():
    import optax

    cfg = LMConfig(vocab=64, d_model=64, n_heads=8, n_layers=2, d_ff=128,
                   n_kv_heads=4, dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    opt = optax.adam(1e-3)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(4, 17)), jnp.int32
    )}
    params, _, loss = lm_train_step(
        params, opt.init(params), batch, opt, cfg, use_flash=False
    )
    assert np.isfinite(float(loss))


def test_gqa_invalid_heads_rejected():
    with pytest.raises(ValueError, match="not divisible"):
        LMConfig(n_heads=4, n_kv_heads=3)


def test_rope_properties():
    """RoPE: norm-preserving rotation; relative-position invariance of
    attention scores (the property that makes position-relative behavior
    learnable); disabled via cfg.rope=False."""
    from seldon_core_tpu.models.transformer import LMConfig, apply_rope

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 2, 8, 16)), jnp.float32)
    pos = jnp.arange(8)
    r = apply_rope(x, pos)
    # rotation preserves per-vector norm
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(r), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5,
    )
    # score depends only on RELATIVE offset: <R(p)q, R(p+d)k> equal for
    # all p at fixed d
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    scores = []
    for p in (0, 3, 11):
        qr = apply_rope(q, jnp.asarray([p]))
        kr = apply_rope(k, jnp.asarray([p + 5]))
        scores.append(float(np.asarray(qr * kr).sum()))
    np.testing.assert_allclose(scores, scores[0], rtol=1e-4)
    # odd head dim rejected at config time when rope is on
    with pytest.raises(ValueError, match="even head dim"):
        LMConfig(d_model=12, n_heads=4)  # hd=3
    LMConfig(d_model=12, n_heads=4, rope=False)  # fine without rope


def test_rope_matmul_form_equals_concat_form():
    """apply_rope computes the rotate-half as x @ [[0,I],[-I,0]] (the
    concat form lowered to unfusable lane-pad fusions on TPU — round-5
    profile); the signed-permutation matmul must reproduce the textbook
    [x1*cos - x2*sin, x2*cos + x1*sin] EXACTLY in f32, and accept
    per-row [B, S] position arrays."""
    from seldon_core_tpu.models.transformer import apply_rope

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 3, 5, 16)), jnp.float32)
    base = 10000.0

    def concat_form(x, positions):
        hd = x.shape[-1]
        half = hd // 2
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[..., None] * freqs
        cos = (jnp.cos(ang)[None, None] if ang.ndim == 2
               else jnp.cos(ang)[:, None])
        sin = (jnp.sin(ang)[None, None] if ang.ndim == 2
               else jnp.sin(ang)[:, None])
        x1 = x[..., :half]
        x2 = x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    pos = jnp.arange(5) + 7
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, pos)),
        np.asarray(concat_form(x, pos)), rtol=0, atol=1e-6)
    # per-row positions (batched speculative decoding)
    pos2 = jnp.asarray(rng.integers(0, 100, size=(2, 5)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, pos2)),
        np.asarray(concat_form(x, pos2)), rtol=0, atol=1e-6)


def test_weights_path_roundtrip_and_validation(tmp_path):
    """save_lm_weights -> weights_path serves the EXACT checkpoint;
    wrong-architecture or state-format checkpoints fail at load time."""
    from seldon_core_tpu.models.generate import TransformerGenerator
    from seldon_core_tpu.models.transformer import (
        LMConfig, lm_init, load_lm_weights, save_lm_weights,
    )

    cfg = LMConfig(vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                   dtype=jnp.float32)
    params = lm_init(jax.random.key(7), cfg)
    path = str(tmp_path / "w.npz")
    save_lm_weights(params, path)

    gen = TransformerGenerator(vocab=64, d_model=64, n_heads=4, n_layers=2,
                               d_ff=128, max_new_tokens=4, dtype="float32",
                               weights_path=path, seed=123)
    state = gen.init_state(jax.random.key(99))
    np.testing.assert_array_equal(
        np.asarray(state["params"]["l0"]["wqkv"]),
        np.asarray(params["l0"]["wqkv"]),
    )

    # missing file
    with pytest.raises(FileNotFoundError):
        load_lm_weights(params, str(tmp_path / "nope.npz"))
    # layer-count mismatch -> missing leaves
    big = lm_init(jax.random.key(0), LMConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=4, d_ff=128,
        dtype=jnp.float32))
    with pytest.raises(ValueError, match="missing leaves"):
        load_lm_weights(big, path)
    # shape mismatch (different d_ff)
    wide = lm_init(jax.random.key(0), LMConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=256,
        dtype=jnp.float32))
    with pytest.raises(ValueError, match="shape mismatch"):
        load_lm_weights(wide, path)
