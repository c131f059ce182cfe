"""Mixture-of-experts layer with expert parallelism over an ``ep`` mesh axis.

The reference's only "expert" notion is the COMBINER ensemble (every member
sees every request — engine PredictiveUnitBean.java:96-118); MoE is its
sparse TPU-native sibling: a learned router sends each token to its top-k
experts, experts live one shard per chip along ``ep``, and the token
shuffle to/from expert shards is an all-to-all that XLA inserts from the
sharding annotations (GSPMD — no hand-written collectives).

Two layers live here.

``moe_apply`` (training, ``lm_apply`` / ``lm_loss``; ``LMConfig.moe_every``)
is static-shaped for the MXU: routing uses the classic dispatch/combine
one-hot tensors (Switch-Transformer style) with a fixed per-expert capacity
``C = ceil(k * T * capacity_factor / E)``; tokens past capacity overflow and
pass through on the residual path.  The heavy math is two batched einsums
over ``[E, C, D]`` blocks, sharded ``P('ep', ...)`` so each chip multiplies
only its experts' blocks.  Its capacity couples the rows of a batch.

``moe_dropless`` (serving, the paged programs; ``LMConfig.d_expert``) drops
nothing: a float32 softmax router, the top ``k`` a token, the picks sorted
by expert and two grouped matmuls (``_grouped_matmul``) over the experts
held.  A token's result depends on that token alone, so a row's answer is
the same whoever shares its batch -- and the grouped matmul visits only the
experts a pass chose, so the weights read grow with the experts CHOSEN, not
the experts held.
"""

from __future__ import annotations

import functools

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MoEConfig", "moe_init", "moe_apply", "moe_param_shardings",
           "moe_leaf_spec", "dropless_init", "moe_dropless"]


@dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 128
    n_experts: int = 8
    k: int = 2                    # top-k routing (1 = Switch)
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16


def moe_init(rng, cfg: MoEConfig) -> Dict[str, Any]:
    kg, k1, k2 = jax.random.split(rng, 3)
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
        ).astype(dt)

    return {
        # router in f32: small, and routing decisions are precision-sensitive
        "wg": jax.random.normal(kg, (cfg.d_model, cfg.n_experts), jnp.float32)
        * (cfg.d_model ** -0.5),
        "w1": dense(k1, (cfg.n_experts, cfg.d_model, cfg.d_ff), cfg.d_model),
        "w2": dense(k2, (cfg.n_experts, cfg.d_ff, cfg.d_model), cfg.d_ff),
    }


def moe_leaf_spec(name: str, leaf, mesh: Mesh, axis: str = "ep") -> P:
    """PartitionSpec for one MoE param leaf: expert stacks shard over the
    ep axis, the router replicates.  THE single source of the MoE layout —
    used here and by the LM's param_shardings so the rules cannot drift."""
    if name in ("w1", "w2") and axis in mesh.axis_names:
        return P(axis, *([None] * (leaf.ndim - 1)))
    return P()


def moe_param_shardings(mesh: Mesh, params, axis: str = "ep") -> Any:
    """Experts shard over ``ep``; router weights replicate."""
    def spec(path, leaf):
        name = getattr(path[-1], "key", str(path[-1]))
        return NamedSharding(mesh, moe_leaf_spec(name, leaf, mesh, axis))

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat]
    )


def _capacity(cfg: MoEConfig, n_tokens: int) -> int:
    return max(1, math.ceil(cfg.k * n_tokens * cfg.capacity_factor
                            / cfg.n_experts))


def _route(gates, cfg: MoEConfig, capacity: int):
    """Top-k dispatch/combine tensors from gate probabilities.

    gates [T, E] -> dispatch [T, E, C] in {0,1}, combine [T, E, C] f32.
    Earlier tokens win capacity slots (deterministic, like the reference's
    deterministic seeded router RandomABTestUnit.java:27-58 is replayable).
    """
    T, E = gates.shape
    if cfg.k > E:
        # argmax over an all -inf row would silently re-pick expert 0 and
        # double-consume its capacity slots
        raise ValueError(f"k={cfg.k} > n_experts={E}")
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    taken = jnp.zeros((T, E), jnp.float32)   # choices already made
    used = jnp.zeros((E,), jnp.float32)      # slots consumed per expert

    for _ in range(cfg.k):
        masked = jnp.where(taken > 0, -jnp.inf, gates)
        idx = jnp.argmax(masked, axis=1)                      # [T]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)    # [T,E]
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot     # queue pos
        pos = pos + used[None, :] * onehot                    # offset by prior k
        keep = onehot * (pos < capacity)
        slot = jax.nn.one_hot(pos.sum(1).astype(jnp.int32), capacity,
                              dtype=jnp.float32)              # [T,C]
        disp = keep[:, :, None] * slot[:, None, :]            # [T,E,C]
        gate_val = (gates * onehot).sum(1, keepdims=True)     # chosen prob
        dispatch = dispatch + disp
        combine = combine + disp * gate_val[:, :, None]
        taken = taken + onehot
        used = used + keep.sum(0)

    if cfg.k > 1:
        # renormalise combine weights over the k chosen experts per token;
        # for k=1 keep the raw gate scale on the output — dividing by the
        # gate's own value would cancel it and zero the router gradient
        # (Switch-style routing learns through that scale)
        denom = combine.sum(axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine


def moe_apply(
    params,
    x,
    cfg: MoEConfig,
    mesh: Optional[Mesh] = None,
    axis: str = "ep",
) -> Tuple[Any, Any]:
    """x [..., D] -> (y [..., D], aux) with residual pass-through overflow.

    aux = {"lb_loss": switch-style load-balance loss, "overflow": fraction
    of token-choices dropped for capacity}.  Under a mesh the [E, C, D]
    expert blocks are sharding-constrained to ``P('ep', ...)``; XLA lowers
    the dispatch/combine einsums to all-to-alls over ICI.
    """
    orig_shape = x.shape
    D = orig_shape[-1]
    xt = x.reshape(-1, D)                                     # [T,D]
    T = xt.shape[0]
    capacity = _capacity(cfg, T)

    logits = xt.astype(jnp.float32) @ params["wg"]            # [T,E]
    gates = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = _route(gates, cfg, capacity)

    xin = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)  # [E,C,D]
    if mesh is not None and axis in mesh.axis_names:
        constraint = NamedSharding(mesh, P(axis, None, None))
        xin = jax.lax.with_sharding_constraint(xin, constraint)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, params["w1"]))
    out = jnp.einsum("ecf,efd->ecd", h, params["w2"])         # [E,C,D]
    if mesh is not None and axis in mesh.axis_names:
        out = jax.lax.with_sharding_constraint(out, constraint)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out)

    # residual pass-through for overflowed tokens (their combine mass is 0)
    got = dispatch.sum(axis=(1, 2))                           # choices served
    y = jnp.where((got > 0)[:, None], y, xt)

    # switch-style load-balance loss: E * sum_e f_e * p_e
    density = jax.nn.one_hot(
        jnp.argmax(gates, axis=1), cfg.n_experts, dtype=jnp.float32
    ).mean(0)
    lb_loss = cfg.n_experts * jnp.sum(density * gates.mean(0))
    overflow = 1.0 - got.sum() / (cfg.k * T)
    return y.reshape(orig_shape), {"lb_loss": lb_loss, "overflow": overflow}


# ---------------------------------------------------------------------------
# The dropless layer of the served path
# ---------------------------------------------------------------------------


def dropless_init(rng, cfg) -> Dict[str, Any]:
    """One layer's router and experts for ``cfg`` (an ``LMConfig`` with
    ``d_expert`` > 0): ``router`` [D, E] over ALL ``n_experts``; the experts
    HELD here (``cfg.held``: all of them, or the chip's share
    ``experts_held``), ``e_gate_up`` [held, D, 2F], each expert's gate and
    up matrices side by side (one grouped matmul reads both) -- under
    ``cfg.expert_act`` "relu2", whose experts have no gate, ``e_up``
    [held, F, D], each expert's up matrix stored OUTPUT-major as its down
    matrix is input-major: a TPU keeps a dimension of whole 128-lane
    registers minor-most, and a width like 1856 = 29 x 64 is none, so a
    [held, D, 1856] stack lies on the chip with D minor and the grouped
    kernel, which takes its operand in the order declared, had every
    layer's stack copied into that order before the round (3.8 GB of
    temporaries over six layers: PERF.md section 6, PR 46) --
    and ``e_down`` [held, F, D]; under ``cfg.d_shared`` the
    shared expert's ``s_gate_up`` / ``s_up`` [D, (2)Fs] and ``s_down``
    [Fs, D].  All in the model's dtype, drawn in it: an
    expert stack is gigabytes at real widths and a float32 draw would be
    twice that beside it.  Under ``cfg.router`` "sigmoid_bias" also
    ``expert_bias`` [E] float32, drawn from the seed and NOT zero (a real
    one is what load balancing left behind): a tenth of a sigmoid's range,
    which moves most tokens' chosen set."""
    kr, kg, kd = jax.random.split(rng, 3)
    ks = jax.random.fold_in(kg, 1)
    E, D, F, dt = cfg.n_experts, cfg.d_model, cfg.d_expert, cfg.dtype
    held, gated = cfg.held, cfg.expert_act == "silu"

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dt)
                * jnp.asarray(fan_in ** -0.5, dt)).astype(dt)

    out = {"router": dense(kr, (D, E), D),
           "e_down": dense(kd, (held, F, D), F)}
    if gated:
        out["e_gate_up"] = dense(kg, (held, D, 2 * F), D)
    else:
        out["e_up"] = dense(kg, (held, F, D), D)
    if cfg.d_shared:
        Fs = cfg.d_shared
        out["s_gate_up" if gated else "s_up"] = dense(
            ks, (D, (2 if gated else 1) * Fs), D)
        out["s_down"] = dense(jax.random.fold_in(ks, 1), (Fs, D), Fs)
    if cfg.router == "sigmoid_bias":
        out["expert_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(kr, 1), (E,), jnp.float32)
    return out


# The grouped matmul's tiles on the TPU (megablox ``gmm``): a group's rows
# against its expert's matrix, the WHOLE contraction and as much of the
# output width as keeps one weight tile within ``_WEIGHT_TILE_BYTES`` (it is
# double-buffered in a v5e's 16 MiB of scoped vector memory: 2048 x 1536
# bf16 = 6.3 MB fits, a 256-row tile beside it does not; ``_weight_tile``),
# ``_ROW_TILE`` rows
# at decode sizes and ``_ROW_TILE_WIDE`` from ``_WIDE_FROM`` rows up.
# Measured on one TPU v5e (PERF.md section 6, PR 34, call P34b: 128 experts
# of 2048 x 1536 and 768 x 2048, bf16, uniform top-8 picks; ms a matmul,
# gate|up / down, beside the least time for the bytes of the experts hit):
#   tokens (experts hit)   least        jax.lax.ragged_dot   gmm, these tiles
#   16    (84)          0.65 / 0.32      0.99 / 0.67         0.76 / 0.40
#   64    (125)         0.96 / 0.48      2.68 / 1.59         1.13 / 0.61
#   1024  (128)         0.98 / 0.49      3.14 / 1.92         1.51 / 0.88
# XLA's own lowering of ragged_dot streams the chosen experts at 35-60% of
# the chip's bandwidth, these tiles at 80-85%, to the same bits; row tiles
# of 16 / 32 / 64 read within 3% of each other, narrower output tiles
# (512, 768, 1024) within 3% of the whole width.  At the seam itself
# (bench/tools/gmm_tiles.py, PERF.md section 6, PR 48: M = 2048 sorted picks
# of which 1152 real, what a decode round's shared pass brings at 32 rows)
# 64-row tiles read 1.16 / 0.61 and 128-row tiles 1.19 / 0.64, and at 1024
# picks 1.15 / 0.62 and 1.13 / 0.60: within 4% either way, so the seam stays.
_ROW_TILE, _ROW_TILE_WIDE, _WIDE_FROM = 64, 128, 2048
_WEIGHT_TILE_BYTES = 6_500_000


def _ragged_dot(x, w, sizes, transposed=False):
    return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transposed else w,
                              sizes, preferred_element_type=jnp.float32)


def _whole_tile(n: int, most: int) -> int:
    """The widest tile of whole 128-lane registers that divides ``n`` and
    is at most ``most`` wide; 0 where none does."""
    return next((t for t in range(most // 128 * 128, 0, -128)
                 if n % t == 0), 0)


def _weight_tile(K: int, N: int, itemsize: int):
    """``(tk, tn)``: the tile of a [K, N] expert matrix the grouped kernel
    streams -- the WHOLE contraction and as much of the output width as
    keeps the tile within ``_WEIGHT_TILE_BYTES``, halved while it halves
    into whole 256s (the tiles measured above).  A width that halves no
    further is cut into the widest whole-128 tiles that DIVIDE it; a width
    that is no multiple of 128 at all (1856 = 29 x 64) stays whole and the
    contraction is cut that way instead (the kernel sums its tiles in
    float32): every tile a whole one, none reaching past the matrix."""
    tn = N
    while K * tn * itemsize > _WEIGHT_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    if K * tn * itemsize <= _WEIGHT_TILE_BYTES:
        return K, tn
    fits = _WEIGHT_TILE_BYTES // itemsize
    if _whole_tile(N, fits // K):
        return K, _whole_tile(N, fits // K)
    return _whole_tile(K, fits // N) or K, N


def _gmm(x, w, sizes, interpret=False, transposed=False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    M, K = x.shape
    N = w.shape[1 if transposed else 2]
    tm = (_ROW_TILE_WIDE if M >= _WIDE_FROM
          else min(_ROW_TILE, -(-M // 16) * 16))
    tk, tn = _weight_tile(K, N, w.dtype.itemsize)
    rows = -(-M // tm) * tm             # whole row tiles: the pad lies past
    if rows != M:                       # every group and is never computed
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    out = gmm(x, w, sizes, preferred_element_type=jnp.float32,
              tiling=(tm, tk, tn), transpose_rhs=transposed,
              interpret=interpret)
    return out[:M]


def _grouped_matmul(x, w, sizes, impl=None, transposed=False):
    """x [M, K], its rows sorted by group, times w [G, K, N] -- or, under
    ``transposed``, each group's matrix stored [N, K] -- by
    ``sizes`` [G] rows a group -> [M, N] float32; rows past the last group
    hold nothing defined.  A group without rows is not visited: its matrix
    is not read.

    ``impl`` None leaves the choice to the platform the program is LOWERED
    for (``jax.lax.platform_dependent``: a program compiled ahead of time
    for a described chip takes the chip's branch): megablox's ``gmm`` (a
    Pallas TPU kernel) with the tiles above on a TPU,
    ``jax.lax.ragged_dot`` anywhere else (the same contract; XLA's own
    lowering).  ``"gmm_interpret"`` runs the kernel in interpret mode
    (tests on the CPU)."""
    how = {"transposed": True} if transposed else {}
    if impl is None:
        return jax.lax.platform_dependent(
            x, w, sizes, tpu=functools.partial(_gmm, **how),
            default=functools.partial(_ragged_dot, **how))
    if impl == "ragged_dot":
        return _ragged_dot(x, w, sizes, **how)
    return _gmm(x, w, sizes, interpret=impl == "gmm_interpret", **how)


def _expert_act(u, cfg):
    """An expert's hidden activation from its first matmul's output ``u``:
    ``silu(gate) * up`` of the two halves side by side, or ``relu(u)^2``."""
    if cfg.expert_act == "relu2":
        return jnp.square(jax.nn.relu(u))
    F = u.shape[-1] // 2
    return jax.nn.silu(u[..., :F]) * u[..., F:]


def moe_dropless(lp, h, valid, cfg, impl=None):
    """Dropless top-k routed experts on h [B, W, D] -> (y [B, W, D],
    experts read: int32 scalar -- or, where the layer holds a share of the
    experts it routes over, int32 [2]: experts read, and the real tokens'
    picks that fell on held experts; what they picked in all is ``moe_k`` a
    token, which the host knows).

    ``g = softmax(h Wr)`` over all experts in float32; the ``moe_k``
    largest; ``w_e = g_e / sum of the chosen`` (``moe_norm_topk``);
    ``y = sum_e w_e (silu(h W_gate,e) * (h W_up,e)) W_down,e``.
    Under ``cfg.router`` "sigmoid_bias": ``g = sigmoid(h Wr)``; chosen are
    the ``moe_k`` largest of ``g + expert_bias``; the weights are the
    UNBIASED ``g`` at the chosen, ``/ (their sum + cfg.router_eps)`` -- the
    bias steers who is chosen and never what a choice weighs.  The weights
    are then multiplied by ``cfg.router_scale``; under ``cfg.expert_act``
    "relu2" an expert is ``relu(h W_up,e)^2 W_down,e``; under
    ``cfg.d_shared`` a shared expert of the same form takes every real
    token and is added unweighted.

    The T*k picks are sorted by expert and go through the experts as two
    grouped matmuls (``_grouped_matmul``; ``impl`` is its), which visit a
    group's weight tiles only where the group has rows: an expert nobody
    chose is not read.  ``valid`` [B, W] marks the real tokens: a pad
    position or an empty slot picks nothing (its picks sort behind every
    group and lie outside all of them), so padding reads no expert and the
    count returned -- groups with at least one row -- is of real work.

    Under ``cfg.experts_held`` the layer holds experts ``[experts_first,
    experts_first + experts_held)`` of the ``n_experts`` the router scores
    (the chip's share of a layer divided by expert parallelism): a pick of
    an expert that is not here sorts behind every group as padding does and
    adds nothing -- what the other chips' experts would add is left out, by
    design -- and the experts read are counted among the held."""
    B, W, D = h.shape
    k, held, first = cfg.moe_k, cfg.held, cfg.experts_first
    x = h.reshape(B * W, D)
    live = valid.reshape(B * W)
    with jax.named_scope("router"):
        logits = jax.lax.dot_general(
            x, lp["router"], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if cfg.router == "sigmoid_bias":
            gates = jax.nn.sigmoid(logits)
            _, top_e = jax.lax.top_k(gates + lp["expert_bias"], k)
            top_w = jnp.take_along_axis(gates, top_e, axis=-1)
            if cfg.moe_norm_topk:
                top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True)
                                 + cfg.router_eps)
        else:
            gates = jax.nn.softmax(logits, axis=-1)
            top_w, top_e = jax.lax.top_k(gates, k)            # [T, k]
            if cfg.moe_norm_topk:
                top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        if cfg.router_scale != 1.0:
            top_w = top_w * cfg.router_scale
        here = live[:, None] & (top_e >= first) & (top_e < first + held)
        pick = jnp.where(here, top_e - first, held).reshape(-1)  # [T*k]
        order = jnp.argsort(pick, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[pick].add(1)[:held]
        read = jnp.count_nonzero(sizes).astype(jnp.int32)
        if cfg.experts_held:
            read = jnp.stack([read, jnp.sum(here, dtype=jnp.int32)])
    with jax.named_scope("experts"):
        xs = x[order // k]                                    # [T*k, D]
        if cfg.expert_act == "relu2":
            up = _grouped_matmul(xs, lp["e_up"], sizes, impl,
                                 transposed=True)
        else:
            up = _grouped_matmul(xs, lp["e_gate_up"], sizes, impl)
        ys = _grouped_matmul(_expert_act(up, cfg).astype(x.dtype),
                             lp["e_down"], sizes, impl)
        # back to token order; rows past the last group (a pad's picks, a
        # pick of an expert held elsewhere) hold nothing defined
        ys = ys[jnp.argsort(order)].reshape(B * W, k, D)
        y = jnp.sum(jnp.where(here[..., None], ys * top_w[..., None], 0.0),
                    axis=1)
    if cfg.d_shared:
        with jax.named_scope("shared_expert"):
            from seldon_core_tpu.ops.quant import lm_matmul

            gated = cfg.expert_act == "silu"
            up = lm_matmul(lp, "s_gate_up" if gated else "s_up", x,
                           out_dtype=jnp.float32)
            y = y + jnp.where(live[:, None], lm_matmul(
                lp, "s_down", _expert_act(up, cfg).astype(x.dtype),
                out_dtype=jnp.float32), 0.0)
    return y.astype(h.dtype).reshape(B, W, D), read
