"""MNIST classifier family — the flagship serving workload.

The reference serves a TF softmax-regression MNIST graph
(examples/models/deep_mnist/DeepMnist.py:1-17: restore session, sess.run on a
784-feature batch).  Here the models are pure-JAX functions designed for the
MXU: bfloat16 weights, batched matmuls, no Python control flow under jit.
Two variants:

  * ``MnistClassifier`` — MLP (784 -> hidden^depth -> 10).  The serving
    flagship: big fused matmuls, bf16 on the MXU, f32 softmax out.
  * ``MnistCNN``        — small convnet for parity with "deep" MNIST demos.

Both expose a functional training API (``init_params`` / ``apply`` /
``train_step``) used by the multi-chip dry-run and the feedback/online-
learning path; ``train_step`` is pure and pjit-shardable over (data, model)
mesh axes.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from seldon_core_tpu.graph.units import Unit, register_unit

__all__ = ["MnistClassifier", "QuantizedMnistClassifier", "MnistCNN",
           "mlp_init", "mlp_apply", "train_step"]

NUM_CLASSES = 10
INPUT_DIM = 784


# ---------------------------------------------------------------------------
# Functional MLP core
# ---------------------------------------------------------------------------


def mlp_init(
    rng,
    hidden: int = 512,
    depth: int = 2,
    in_dim: int = INPUT_DIM,
    out_dim: int = NUM_CLASSES,
    dtype=jnp.bfloat16,
) -> Dict[str, Any]:
    """He-initialised MLP parameters as a flat dict pytree."""
    dims = [in_dim] + [hidden] * depth + [out_dim]
    params: Dict[str, Any] = {}
    keys = jax.random.split(rng, len(dims) - 1)
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        scale = jnp.sqrt(2.0 / d_in)
        params[f"w{i}"] = (
            jax.random.normal(keys[i], (d_in, d_out), jnp.float32) * scale
        ).astype(dtype)
        params[f"b{i}"] = jnp.zeros((d_out,), dtype)
    return params


def mlp_apply(params: Dict[str, Any], x) -> jax.Array:
    """Logits.  Compute in the params' dtype (bf16 on the MXU), accumulate
    the final logits in f32."""
    n_layers = len(params) // 2
    dtype = params["w0"].dtype
    h = x.astype(dtype)
    for i in range(n_layers - 1):
        h = jnp.maximum(h @ params[f"w{i}"] + params[f"b{i}"], 0.0)
    logits = (h @ params[f"w{n_layers-1}"]).astype(jnp.float32) + params[
        f"b{n_layers-1}"
    ].astype(jnp.float32)
    return logits


def loss_fn(params, batch) -> jax.Array:
    x, y = batch["image"], batch["label"]
    logits = mlp_apply(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def train_step(params, opt_state, batch, optimizer) -> Tuple[Any, Any, jax.Array]:
    """One SGD/optax step; pure, shardable with pjit over a (data, model)
    mesh — gradients reduce over the data axis via XLA collectives."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)), params, updates
    )
    return params, opt_state, loss


# ---------------------------------------------------------------------------
# Serving units
# ---------------------------------------------------------------------------


@register_unit("MnistClassifier")
class MnistClassifier(Unit):
    """MLP MNIST unit.  Params live in the unit *state* so the compiled graph
    threads them (ready for sharding / hot-swap); predict returns class
    probabilities like the reference wrapper's predict_proba convention."""

    class_names = [f"class:{i}" for i in range(NUM_CLASSES)]

    def __init__(
        self,
        hidden: int = 512,
        depth: int = 2,
        seed: int = 0,
        dtype: str = "bfloat16",
        use_pallas: str = "auto",
    ):
        self.hidden = int(hidden)
        self.depth = int(depth)
        self.seed = int(seed)
        self.dtype = jnp.dtype(dtype)
        # kernel-path decision is made HERE (static under jit): "auto"
        # means the kernel on a TPU backend and XLA elsewhere; "never"
        # forces the XLA path; "interpret" runs the kernel in interpreter
        # mode (CPU tests of the kernel itself)
        self.use_pallas = str(use_pallas)
        if self.use_pallas not in ("auto", "never", "interpret"):
            raise ValueError(
                f"use_pallas={use_pallas!r} not supported "
                "(auto | never | interpret)")
        from seldon_core_tpu.ops.fused_mlp import pallas_supported

        self._pallas = self.use_pallas == "interpret" or (
            self.use_pallas == "auto" and pallas_supported())

    @property
    def kernels(self):
        return ("fused_mlp",) if self._pallas else ()

    def init_state(self, rng):
        if rng is None:
            rng = jax.random.key(self.seed)
        # fold in the construction seed so two ensemble members with different
        # seeds differ even under one graph rng
        rng = jax.random.fold_in(rng, self.seed)
        return mlp_init(rng, hidden=self.hidden, depth=self.depth, dtype=self.dtype)

    def predict(self, state, X):
        X = X.reshape(X.shape[0], -1)
        if self._pallas:
            from seldon_core_tpu.ops.fused_mlp import (
                fused_mlp_fits,
                fused_mlp_softmax,
            )

            # the kernel's one size constraint, tested up front: an MLP
            # too large to sit in VMEM takes XLA; anything the kernel
            # raises past this point is an error, not a lane change
            if fused_mlp_fits(state):
                return fused_mlp_softmax(
                    state, X, interpret=self.use_pallas == "interpret"
                )
        return jax.nn.softmax(mlp_apply(state, X), axis=-1)


@register_unit("QuantizedMnistClassifier")
class QuantizedMnistClassifier(MnistClassifier):
    """Int8 serving variant: weights quantize once at init (symmetric
    per-channel) and serve weight-only (dequant_matmul: XLA fuses the
    convert+scale into the dot's weight read, so weights stream at int8
    size — ops/quant.py records the measured trade-offs).  Activations
    are never quantized; argmax-stable for classifier heads."""

    kernels = ()  # serves through dequant_matmul (XLA), never the kernel

    def init_state(self, rng):
        from seldon_core_tpu.ops.quant import quantize_mlp_params

        return quantize_mlp_params(super().init_state(rng))

    def predict(self, state, X):
        from seldon_core_tpu.ops.quant import QuantizedMLP

        return QuantizedMLP.apply(state, X.reshape(X.shape[0], -1))


@register_unit("MnistCNN")
class MnistCNN(Unit):
    """Small convnet (2x conv+pool, 1 dense).  Accepts [B, 784] or
    [B, 28, 28, 1] input; NHWC layout for TPU convolutions."""

    class_names = [f"class:{i}" for i in range(NUM_CLASSES)]

    def __init__(self, channels: int = 32, seed: int = 0, dtype: str = "bfloat16"):
        self.channels = int(channels)
        self.seed = int(seed)
        self.dtype = jnp.dtype(dtype)

    def init_state(self, rng):
        if rng is None:
            rng = jax.random.key(self.seed)
        rng = jax.random.fold_in(rng, self.seed)
        k1, k2, k3 = jax.random.split(rng, 3)
        c = self.channels
        dt = self.dtype

        def conv_w(key, shape):
            fan_in = shape[0] * shape[1] * shape[2]
            return (
                jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)
            ).astype(dt)

        return {
            "c1": conv_w(k1, (3, 3, 1, c)),
            "c2": conv_w(k2, (3, 3, c, 2 * c)),
            "w": (
                jax.random.normal(k3, (7 * 7 * 2 * c, NUM_CLASSES), jnp.float32)
                * jnp.sqrt(2.0 / (7 * 7 * 2 * c))
            ).astype(dt),
            "b": jnp.zeros((NUM_CLASSES,), dt),
        }

    def predict(self, state, X):
        if X.ndim == 2:
            X = X.reshape(-1, 28, 28, 1)
        h = X.astype(self.dtype)
        for w in (state["c1"], state["c2"]):
            h = jax.lax.conv_general_dilated(
                h, w, window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            h = jnp.maximum(h, 0.0)
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
        h = h.reshape(h.shape[0], -1)
        logits = (h @ state["w"]).astype(jnp.float32) + state["b"].astype(jnp.float32)
        return jax.nn.softmax(logits, axis=-1)
