"""MoE layers inside the transformer LM: ep-sharded experts in the
flagship model, load-balance loss in training, and MoE generation."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from seldon_core_tpu.models.generate import generate
from seldon_core_tpu.models.transformer import (
    LMConfig,
    lm_apply,
    lm_init,
    lm_loss,
    lm_pipeline_params,
    lm_train_step,
    param_shardings,
)
from seldon_core_tpu.parallel.mesh import build_mesh

CFG = LMConfig(vocab=48, d_model=16, n_heads=2, n_layers=2, d_ff=32,
               dtype=jnp.float32, moe_every=2, n_experts=4, moe_k=2)


def _tokens(seed, b, s):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 48, size=(b, s)), jnp.int32
    )


def test_moe_lm_forward_and_lb():
    params = lm_init(jax.random.key(0), CFG)
    assert "moe" in params["l1"] and "w1" in params["l0"]  # every 2nd layer
    logits, lb = lm_apply(params, _tokens(0, 2, 8), CFG, return_lb=True)
    assert logits.shape == (2, 8, 48)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(lb) >= 0.99  # one MoE layer's switch lb-loss lower bound


def test_moe_lm_train_step_updates_experts_and_router(devices8):
    mesh = build_mesh({"dp": 2, "ep": 4})
    params = lm_init(jax.random.key(1), CFG)
    sharded = jax.device_put(params, param_shardings(mesh, params))
    # expert stacks sharded over ep; router replicated
    assert not sharded["l1"]["moe"]["w1"].sharding.is_fully_replicated
    assert sharded["l1"]["moe"]["wg"].sharding.is_fully_replicated

    opt = optax.adam(1e-2)
    opt_state = opt.init(sharded)
    batch = {"tokens": _tokens(1, 4, 9)}
    step = jax.jit(lambda p, o, b: lm_train_step(p, o, b, opt, CFG, mesh))
    p1, opt_state, loss1 = step(sharded, opt_state, batch)
    p2, _, loss2 = step(p1, opt_state, batch)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss1)
    # both experts and router moved
    assert float(jnp.abs(p2["l1"]["moe"]["w1"] - sharded["l1"]["moe"]["w1"]).sum()) > 0
    assert float(jnp.abs(p2["l1"]["moe"]["wg"] - sharded["l1"]["moe"]["wg"]).sum()) > 0


def test_moe_lm_sharded_matches_unsharded(devices8):
    mesh = build_mesh({"ep": 4}, devices=devices8[:4])
    params = lm_init(jax.random.key(2), CFG)
    tokens = _tokens(2, 2, 8)
    ref = np.asarray(lm_apply(params, tokens, CFG))
    sharded = jax.device_put(params, param_shardings(mesh, params))
    got = np.asarray(jax.jit(
        lambda p, t: lm_apply(p, t, CFG, mesh)
    )(sharded, tokens))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_moe_rejected_in_pipeline(devices8):
    mesh = build_mesh({"pp": 2}, devices=devices8[:2])
    params = lm_init(jax.random.key(3), CFG)
    with pytest.raises(ValueError, match="MoE"):
        lm_pipeline_params(params, CFG, 2, mesh)


def test_moe_generation():
    params = lm_init(jax.random.key(4), CFG)
    prompt = _tokens(4, 2, 5)
    y = np.asarray(generate(params, prompt, CFG, max_new_tokens=6))
    assert y.shape == (2, 6)
    assert ((0 <= y) & (y < 48)).all()


def test_moe_generation_matches_naive_reforward():
    """The input only the static lane serves (continuous_spec is None for
    MoE): generate() over its private pool equals recomputing the whole
    forward every step.  Two experts, both taken by every token, so expert
    capacity (sized from the tokens in a pass) never drops one: a cached
    step of B tokens and a re-forward of B x S are then the same function
    of the sequence — with drops they are not, whatever the cache."""
    import dataclasses

    from seldon_core_tpu.models.generate import TransformerGenerator

    assert TransformerGenerator(moe_every=2).continuous_spec({}) is None
    cfg = dataclasses.replace(CFG, n_experts=2)
    params = lm_init(jax.random.key(4), cfg)
    prompt = _tokens(4, 2, 5)
    got = np.asarray(jax.jit(
        lambda p, t: generate(p, t, cfg, max_new_tokens=6))(params, prompt))
    tokens, want = prompt, []
    for _ in range(6):
        nxt = jnp.argmax(lm_apply(params, tokens, cfg)[:, -1, :],
                         axis=-1).astype(jnp.int32)
        want.append(nxt)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, np.asarray(jnp.stack(want, axis=1)))


def test_moe_generator_unit_serves():
    """MoE generation reachable from a deployment config, incl. NaN-proof
    prompt handling."""
    from seldon_core_tpu.models.generate import TransformerGenerator

    u = TransformerGenerator(vocab=48, d_model=16, n_heads=2, n_layers=2,
                             d_ff=32, max_new_tokens=4, dtype="float32",
                             moe_every=2, n_experts=4, moe_k=2)
    st = u.init_state(jax.random.key(0))
    X = jnp.asarray([[float("nan"), 1e12, -3.0, 7.0]], jnp.float32)
    y = np.asarray(u.predict(st, X))
    assert y.shape == (1, 4)
    assert ((0 <= y) & (y < 48)).all()


def test_moe_units_declare_batch_coupling():
    """MoE capacity routing couples co-batched rows, so MoE-configured
    serving units must opt out of request coalescing."""
    from seldon_core_tpu.models.generate import TransformerGenerator
    from seldon_core_tpu.models.transformer import TransformerLM

    assert TransformerLM(moe_every=2).batch_coupled is True
    assert TransformerLM().batch_coupled is False
    assert TransformerGenerator(moe_every=2).batch_coupled is True
    assert TransformerGenerator().batch_coupled is False
