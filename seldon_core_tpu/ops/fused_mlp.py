"""Fused MLP-forward Pallas kernel — the serving flagship's hot op.

The reference's "model math" is a TF session per microservice
(examples/models/deep_mnist/DeepMnist.py:1-17); the TPU-native equivalent
keeps the whole forward in one kernel:

    probs = softmax(relu(x @ w0 + b0) ... @ wL + bL)

Under plain XLA each layer's activation round-trips HBM between fused
regions; this kernel tiles the batch, keeps every weight and intermediate in
VMEM, and runs matmul -> bias -> relu -> ... -> softmax per batch tile with
zero HBM traffic for intermediates.  Weights are bf16 (MXU-native), the
final logits and softmax accumulate in f32.

VMEM budget: every layer's weights must be resident (~16 MB/core), and
Pallas double-buffers every blocked operand — the weights too, even
though their block index never changes — so the budget counts each
input and the output tile TWICE plus the f32 intermediates once.
Serving MLPs (784x512x512x10 bf16 ~= 1.3 MB, 2.6 MB buffered) are far
under it.  ``fused_mlp_fits`` is that test; callers ask it before calling
(models/mnist.py) and take the XLA path for an MLP too large for VMEM.
``fused_mlp_softmax`` itself raises ``ValueError`` on any violated
constraint.

``pallas_supported()`` is a rule, not a probe: these are Mosaic-TPU
kernels, so they are taken on a TPU backend and nowhere else.  A kernel
that does not lower on the TPU raises where it is compiled — it is never
swapped for the XLA path behind the caller's back.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = ["fused_mlp_fits", "fused_mlp_softmax", "pallas_supported"]

_VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # leave headroom under ~16 MB/core
_BLOCK_B = 128  # batch-tile rows


def _layer_params(params: Dict[str, Any]):
    n_layers = len(params) // 2
    return [(params[f"w{i}"], params[f"b{i}"]) for i in range(n_layers)]


def _mlp_kernel(*refs, n_layers: int):
    """refs = (x_ref, w0, b0, w1, b1, ..., out_ref).  One batch tile: all
    layers + softmax computed entirely in VMEM."""
    x_ref = refs[0]
    out_ref = refs[-1]
    h = x_ref[:]
    for i in range(n_layers):
        w_ref, b_ref = refs[1 + 2 * i], refs[2 + 2 * i]
        w = w_ref[:]
        h = jnp.dot(
            h.astype(w.dtype), w, preferred_element_type=jnp.float32
        ) + b_ref[:].astype(jnp.float32)
        if i < n_layers - 1:
            h = jnp.maximum(h, 0.0)
    # softmax in f32 (numerically-stable shift)
    h = h - jnp.max(h, axis=-1, keepdims=True)
    e = jnp.exp(h)
    out_ref[:] = e / jnp.sum(e, axis=-1, keepdims=True)


def _vmem_bytes(layers, block_b: int) -> int:
    """VMEM the kernel needs for one batch tile: blocked operands (x tile,
    every weight and bias, the output tile) are double-buffered by the
    Pallas pipeline; the widest f32 intermediate is counted once."""
    in_dim = layers[0][0].shape[0]
    out_dim = layers[-1][0].shape[1]
    weight_bytes = sum(w.size * w.dtype.itemsize + b.size * b.dtype.itemsize
                       for w, b in layers)
    x_tile = 4 * block_b * in_dim
    out_tile = 4 * block_b * out_dim
    act = 4 * block_b * max(w.shape[1] for w, _ in layers)
    return 2 * (weight_bytes + x_tile + out_tile) + act


def fused_mlp_fits(params: Dict[str, Any], block_b: int = _BLOCK_B) -> bool:
    """The kernel's one size constraint, for callers to test BEFORE
    calling: all layers resident in VMEM under the budget."""
    layers = _layer_params(params)
    return bool(layers) and _vmem_bytes(layers, block_b) <= _VMEM_BUDGET_BYTES


def fused_mlp_softmax(
    params: Dict[str, Any],
    x: jax.Array,
    *,
    block_b: int = _BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    """softmax(mlp(x)) fused in one Pallas kernel.

    params: flat dict {w0,b0,...,wL,bL} (models/mnist.py mlp_init layout);
    x: [B, in_dim] float array.  Returns [B, out_dim] float32 probabilities.
    Raises ValueError when the kernel's constraints don't hold."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layers = _layer_params(params)
    if not layers:
        raise ValueError("empty params")
    if x.ndim != 2:
        raise ValueError(f"x must be [B, D], got {x.shape}")
    in_dim = layers[0][0].shape[0]
    out_dim = layers[-1][0].shape[1]
    if x.shape[1] != in_dim:
        raise ValueError(f"x dim {x.shape[1]} != w0 in_dim {in_dim}")
    need = _vmem_bytes(layers, block_b)
    if need > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"fused MLP needs ~{need >> 20} MiB VMEM "
            f"(budget {_VMEM_BUDGET_BYTES >> 20} MiB)"
        )

    B = x.shape[0]
    block_b = min(block_b, max(B, 1))
    grid = (pl.cdiv(B, block_b),)

    # x is tiled over the batch grid; weights/biases are whole-array blocks
    # (the same block index every step, so they are fetched once)
    in_specs = [
        pl.BlockSpec((block_b, in_dim), lambda i: (i, 0),
                     memory_space=pltpu.VMEM)
    ]
    flat_inputs = [x]
    for w, b in layers:
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        # biases as [1, D] — TPU VMEM wants >=2D tiles
        in_specs.append(pl.BlockSpec((1, b.shape[0]), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        flat_inputs += [w, b.reshape(1, -1)]

    fn = pl.pallas_call(
        functools.partial(_mlp_kernel, n_layers=len(layers)),
        out_shape=jax.ShapeDtypeStruct((B, out_dim), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, out_dim), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )
    return fn(*flat_inputs)


def pallas_supported() -> bool:
    """True on a TPU backend: the kernels in ops/ are Mosaic-TPU kernels.
    Static under jit (the backend cannot change inside a trace)."""
    return jax.default_backend() == "tpu"
