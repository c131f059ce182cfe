"""Tabular model families (mean classifier, sigmoid predictor, min-max
transformer, boosted oblivious trees) + a smoke test that every example
deployment JSON in examples/ parses and serves a prediction."""

import asyncio
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.graph.compiled import CompiledGraph
from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.messages import SeldonMessage
from seldon_core_tpu.models.tabular import (
    MeanClassifier,
    MeanTransformer,
    ObliviousTreeEnsemble,
    SigmoidPredictor,
)
from seldon_core_tpu.runtime.engine import EngineService

EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"


def test_mean_classifier_semantics():
    u = MeanClassifier(threshold=1.0)
    st = u.init_state(None)
    X = jnp.asarray([[1.0, 1.0], [3.0, 5.0]])
    y = np.asarray(u.predict(st, X))
    assert y.shape == (2, 1)
    assert y[0, 0] == pytest.approx(0.5)          # mean 1.0 == threshold
    assert y[1, 0] == pytest.approx(1 / (1 + np.exp(-3.0)))  # mean 4.0


def test_sigmoid_predictor_learns_task():
    u = SigmoidPredictor(train_steps=300, seed=0)
    st = u.init_state(jax.random.key(0))
    rng = np.random.default_rng(42)
    X = rng.normal(size=(512, 10)).astype(np.float32)
    y_true = (1 / (1 + np.exp(-X[:, 0] * X[:, 1])) >= 0.5).astype(int)
    probs = np.asarray(u.predict(st, jnp.asarray(X)))
    assert probs.shape == (512, 2)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    acc = ((probs[:, 1] > 0.5).astype(int) == y_true).mean()
    assert acc > 0.8, f"sigmoid predictor failed to learn: acc={acc}"


def test_mean_transformer_minmax_and_constant_batch():
    u = MeanTransformer()
    X = jnp.asarray([[0.0, 5.0], [10.0, 2.5]])
    out = np.asarray(u.transform_input(None, X))
    np.testing.assert_allclose(out, [[0.0, 0.5], [1.0, 0.25]], atol=1e-6)
    const = np.asarray(u.transform_input(None, jnp.full((3, 4), 7.0)))
    np.testing.assert_array_equal(const, np.zeros((3, 4)))


def test_oblivious_trees_beat_base_predictor():
    u = ObliviousTreeEnsemble(n_trees=16, depth=3, seed=0)
    st = u.init_state(None)
    # held-out sample of the same synthetic task
    rng = np.random.default_rng(99)
    X = rng.normal(size=(512, 8))
    y = np.sin(X[:, 0]) + 0.5 * X[:, 1] * (X[:, 2] > 0)
    pred = np.asarray(jax.jit(u.predict)(st, jnp.asarray(X)))[:, 0]
    mse_model = float(np.mean((pred - y) ** 2))
    mse_base = float(np.mean((float(st["base"]) - y) ** 2))
    assert pred.shape == (512,)
    assert mse_model < 0.5 * mse_base, (mse_model, mse_base)


def test_batch_coupled_unit_disables_request_coalescing():
    """A graph containing MeanTransformer must not micro-batch: one
    caller's rows would shift another caller's min/max."""
    spec = SeldonDeploymentSpec.from_json(
        (EXAMPLES / "mean_transformer_deployment.json").read_text()
    )
    engine = EngineService(spec)
    assert engine.batcher is None
    # and a plain model graph still batches
    mnist = SeldonDeploymentSpec.from_json(
        (EXAMPLES / "mnist_deployment.json").read_text()
    )
    assert EngineService(mnist).batcher is not None


def test_oblivious_trees_compile_into_graph():
    g = {"name": "gbm", "type": "MODEL"}
    comps = [{
        "name": "gbm", "runtime": "inprocess",
        "class_path": "ObliviousTreeEnsemble",
        "parameters": [{"name": "n_trees", "value": "8", "type": "INT"}],
    }]
    spec = SeldonDeploymentSpec.from_json_dict(
        {"spec": {"name": "g", "predictors": [
            {"name": "p", "graph": g, "components": comps}]}}
    )
    cg = CompiledGraph(spec.predictor())
    y, _, _ = cg.predict_arrays(np.zeros((4, 8), np.float32))
    assert np.asarray(y).shape == (4, 1)


_EXAMPLE_FEATURES = {
    "iris_deployment.json": 4,
    "mnist_deployment.json": 784,
    "epsilon_greedy_deployment.json": 784,
    "ensemble4_deployment.json": 784,
    "outlier_pipeline_deployment.json": 784,
    "canary_deployment.json": 784,
    "mean_transformer_deployment.json": 6,
    "gbm_deployment.json": 8,
    "generator_deployment.json": 5,  # 5-token prompts -> generated tokens
    "stub_deployment.json": 1,  # the reference's max-throughput stub graph
    "generator_tp_deployment.json": 5,  # tp=4 mesh-sharded LM generator
    "generator_ep_deployment.json": 5,  # ep=4 MoE expert-parallel generator
    "generator_int8_deployment.json": 4,  # int8 weights/KV + GQA opt-ins
    "speculative_deployment.json": 5,  # draft/verify generation opt-in
    # shared-prefix KV cache + eos stop handling opt-ins
    "generator_prefix_deployment.json": 4,
    # the full-width 165.7M-parameter LM chip_smoke.py serves on the chip;
    # too heavy for the CPU tier-1 budget, so it runs under -m slow here
    "lm_d1024_deployment.json": 5,
}


@pytest.mark.parametrize("fname", [
    pytest.param(f, marks=pytest.mark.slow)
    if f == "lm_d1024_deployment.json" else f
    for f in sorted(_EXAMPLE_FEATURES)
])
def test_every_example_deployment_serves(fname):
    path = EXAMPLES / fname
    assert path.exists(), f"example listed but missing: {fname}"
    spec = SeldonDeploymentSpec.from_json(path.read_text())
    n = _EXAMPLE_FEATURES[fname]
    x = np.random.default_rng(0).normal(size=(2, n)).tolist()
    msg = SeldonMessage.from_json(json.dumps({"data": {"ndarray": x}}))
    for p in spec.predictors:
        engine = EngineService(spec, p.name)
        resp = asyncio.run(engine.predict(msg))
        assert resp.status is None or resp.status.status != "FAILURE", (
            fname, p.name, resp.status)
        arr = np.asarray(resp.data.array)
        assert arr.shape[0] == 2 and np.isfinite(arr).all(), (fname, p.name)


def test_example_dir_has_no_untested_deployments():
    on_disk = {p.name for p in EXAMPLES.glob("*_deployment.json")}
    assert on_disk == set(_EXAMPLE_FEATURES), (
        "keep _EXAMPLE_FEATURES in sync with examples/"
    )
