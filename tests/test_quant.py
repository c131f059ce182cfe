"""Int8 quantized serving: weight/activation quantization numerics, argmax
stability vs the f32 MLP, and the quantized unit through the engine."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np

from seldon_core_tpu.models.mnist import MnistClassifier, QuantizedMnistClassifier
from seldon_core_tpu.ops.quant import (
    QuantizedMLP,
    quant_matmul,
    quantize_mlp_params,
    quantize_weight,
)


def test_quantize_weight_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w_q, s = quantize_weight(w)
    assert w_q.dtype == jnp.int8 and s.shape == (32,)
    deq = np.asarray(w_q, np.float32) * np.asarray(s)[None, :]
    err = np.abs(deq - np.asarray(w)).max()
    # per-channel symmetric: error bounded by half a quantization step
    assert err <= float(np.asarray(s).max()) * 0.5 + 1e-6


def test_dequant_matmul_preserves_input_rank():
    from seldon_core_tpu.ops.quant import dequant_matmul

    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w_q, s = quantize_weight(w)
    # rank-1 input -> rank-1 [out] output, same as a plain matmul
    x1 = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    y1 = dequant_matmul(x1, w_q, s)
    assert y1.shape == (32,)
    # rank-3 leading dims pass through
    x3 = jnp.asarray(rng.normal(size=(2, 3, 64)), jnp.float32)
    assert dequant_matmul(x3, w_q, s).shape == (2, 3, 32)
    ref = np.asarray(x1 @ w)
    assert np.abs(np.asarray(y1) - ref).max() / np.abs(ref).max() < 0.02


def test_quant_matmul_close_to_f32():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w_q, s = quantize_weight(w)
    got = np.asarray(quant_matmul(x, w_q, s))
    ref = np.asarray(x @ w)
    # relative error ~1% for int8 dynamic quantization
    assert np.abs(got - ref).max() / np.abs(ref).max() < 0.02


def test_quantized_mlp_argmax_agrees_with_f32():
    f32_unit = MnistClassifier(hidden=64, depth=2, dtype="float32",
                               use_pallas="never")
    q_unit = QuantizedMnistClassifier(hidden=64, depth=2, dtype="float32",
                                      use_pallas="never")
    f32_state = f32_unit.init_state(jax.random.key(0))
    q_state = q_unit.init_state(jax.random.key(0))
    X = jnp.asarray(np.random.default_rng(2).normal(size=(256, 784)),
                    jnp.float32)
    p_f32 = np.asarray(f32_unit.predict(f32_state, X))
    p_q = np.asarray(q_unit.predict(q_state, X))
    np.testing.assert_allclose(p_q.sum(axis=1), 1.0, atol=1e-5)
    # an untrained random MLP is the WORST case for argmax stability (its
    # logits are near-uniform, so borderline rows flip on tiny noise);
    # probabilities must still be close and agreement high
    assert np.abs(p_f32 - p_q).max() < 0.05
    agree = (p_f32.argmax(1) == p_q.argmax(1)).mean()
    assert agree >= 0.95, f"argmax agreement {agree}"


def test_quantized_unit_serves_through_engine():
    from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
    from seldon_core_tpu.runtime.engine import EngineService

    spec = SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "q", "predictors": [{
            "name": "p",
            "graph": {"name": "m", "type": "MODEL"},
            "components": [{
                "name": "m", "runtime": "inprocess",
                "class_path": "QuantizedMnistClassifier",
                "parameters": [{"name": "hidden", "value": "32",
                                "type": "INT"}],
            }],
        }]}
    })
    engine = EngineService(spec)
    assert engine.mode == "compiled" and engine.batcher is not None

    async def run():
        text, status = await engine.predict_json(
            json.dumps({"data": {"ndarray": np.zeros((2, 784)).tolist()}})
        )
        assert status == 200
        probs = np.asarray(json.loads(text)["data"]["ndarray"])
        assert probs.shape == (2, 10)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-4)

    asyncio.run(run())


def test_quantized_lm_generate_matches_shapes_and_quality():
    """Int8 transformer serving (quantize_lm_params + lm_matmul): the
    quantized generator produces valid token ids and the quantized
    TransformerLM's logits track the bf16 model's argmax closely."""
    from seldon_core_tpu.models.generate import TransformerGenerator
    from seldon_core_tpu.models.transformer import (
        LMConfig, TransformerLM, lm_apply, lm_init,
    )
    from seldon_core_tpu.ops.quant import quantize_lm_params

    cfg = LMConfig(vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                   dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    qparams = quantize_lm_params(params)
    # every layer weight replaced by _q/_s; embed and norms untouched
    assert "wqkv_q" in qparams["l0"] and "wqkv" not in qparams["l0"]
    assert qparams["l0"]["wqkv_q"].dtype == jnp.int8
    assert qparams["embed"] is params["embed"]

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(4, 16)), jnp.int32
    )
    logits = np.asarray(lm_apply(params, tokens, cfg))
    cfg_q = LMConfig(vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                     dtype=jnp.float32, quant="int8")
    qlogits = np.asarray(lm_apply(qparams, tokens, cfg_q))
    assert qlogits.shape == logits.shape
    agree = (logits.argmax(-1) == qlogits.argmax(-1)).mean()
    assert agree >= 0.9, f"argmax agreement {agree}"

    # the full serving unit: quantized weights, cached decode loop
    gen = TransformerGenerator(vocab=64, d_model=64, n_heads=4, n_layers=2,
                               d_ff=128, max_new_tokens=8, dtype="float32",
                               quant="int8")
    state = gen.init_state(jax.random.key(1))
    y = np.asarray(gen.predict(state, jnp.zeros((2, 4), jnp.float32)))
    assert y.shape == (2, 8)
    assert ((y >= 0) & (y < 64)).all()

    # the quantized LM unit serves logits too
    lm = TransformerLM(vocab=64, d_model=64, n_heads=4, n_layers=2,
                       d_ff=128, dtype="float32", quant="int8")
    lstate = lm.init_state(jax.random.key(2))
    out = np.asarray(lm.predict(lstate, jnp.zeros((2, 4), jnp.float32)))
    assert out.shape == (2, 4, 64)


def test_attention_parameter_modes():
    """attention=xla|flash|auto resolve to a static flash decision;
    invalid values fail at construction (graph-load time)."""
    import pytest

    from seldon_core_tpu.models.transformer import TransformerLM, resolve_flash

    assert resolve_flash("xla", None) is False
    # the kernels are Mosaic-TPU kernels: auto means XLA on this CPU
    # backend, and an explicit 'flash' RAISES rather than quietly
    # serving XLA under the name the operator asked for
    assert resolve_flash("auto", None) is False
    with pytest.raises(ValueError, match="single-chip TPU"):
        resolve_flash("flash", None)
    with pytest.raises(ValueError):
        TransformerLM(attention="nope")
    with pytest.raises(ValueError):
        TransformerLM(quant="fp4")


def test_quant_lm_training_guarded():
    import optax
    import pytest

    from seldon_core_tpu.models.transformer import LMConfig, lm_train_step

    cfg = LMConfig(quant="int8")
    with pytest.raises(ValueError):
        lm_train_step({}, {}, {}, optax.sgd(1e-2), cfg)
