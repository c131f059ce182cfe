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
by expert and, over the experts held, two grouped matmuls
(``_grouped_matmul``) -- on one TPU ONE kernel for an expert's whole
feed-forward (``_experts_fused``).  A token's result depends on that token
alone, so a row's answer is the same whoever shares its batch -- and either
form visits only the experts a pass chose, so the weights read grow with
the experts CHOSEN, not the experts held.
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
           "moe_leaf_spec", "dropless_init", "moe_dropless",
           "fused_supported"]


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


# The grouped matmul's tiles on the TPU (megablox ``gmm``), where the pair
# of them serves -- a TPU under a mesh and the widths ``fused_supported``
# refuses; one chip takes ``_experts_fused`` below, whose table holds the
# pair's times at these tiles beside its own: a group's rows against its
# expert's matrix, the WHOLE contraction and as much of the output width as
# keeps one weight tile within ``_WEIGHT_TILE_BYTES`` (it is double-buffered
# in the 16 MiB of vector memory the compiler gives a kernel by default:
# 2048 x 1536 bf16 = 6.3 MB fits, a 256-row tile beside it does not;
# ``_weight_tile``), ``_ROW_TILE`` rows at decode sizes and
# ``_ROW_TILE_WIDE`` from ``_WIDE_FROM`` rows up.  Measured on one TPU v5e
# (PERF.md section 6, PR 34, call P34b: 128 experts of 2048 x 1536 and 768 x
# 2048, bf16, uniform top-8 picks; ms a matmul, gate|up / down, beside the
# least time for the bytes of the experts hit):
#   tokens (experts hit)   least        jax.lax.ragged_dot   gmm, these tiles
#   16    (84)          0.65 / 0.32      0.99 / 0.67         0.76 / 0.40
#   64    (125)         0.96 / 0.48      2.68 / 1.59         1.13 / 0.61
#   1024  (128)         0.98 / 0.49      3.14 / 1.92         1.51 / 0.88
# XLA's own lowering of ragged_dot streams the chosen experts at 35-60% of
# the chip's bandwidth, these tiles at 80-89%, to the same bits; row tiles
# of 16 / 32 / 64 read within 3% of each other, narrower output tiles
# (512, 768, 1024) within 3% of the whole width.  At the seam itself
# (bench/tools/gmm_tiles.py; PERF.md section 6, PR 48 and again PR 56, call
# P56a: M = 2048 sorted picks of which 1152 real, what a decode round's
# shared pass brings at 32 rows) 64-row tiles read 1.17 / 0.62 and 128-row
# tiles 1.19 / 0.64, and at 1024 picks 1.16 / 0.62 and 1.14 / 0.60: within
# 4% either way, so the seam stays.
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
    return _act(u, cfg.expert_act == "silu")


def _act(u, gated: bool):
    if not gated:
        return jnp.square(jax.nn.relu(u))
    F = u.shape[-1] // 2
    return jax.nn.silu(u[..., :F]) * u[..., F:]


# ---------------------------------------------------------------------------
# An expert's whole feed-forward as ONE kernel
# ---------------------------------------------------------------------------

# The pair of grouped matmuls with the activation between them leaves the
# first matmul's output in HBM in float32, visits every expert twice, and
# -- megablox's pipeline holds ONE weight tile on its way in, sent for a
# step ahead -- lets the copy queue run dry wherever a row tile straddles two
# experts.  ``_experts_fused`` keeps that output and the hidden in vector
# memory, brings an expert's matrices WHOLE by its own copies (the next
# expert's sent for before this one's are waited for) and reads an expert
# once however many tiles it straddles.  One TPU v5e, bfloat16, each token's
# picks distinct experts drawn uniformly, ms a layer (PERF.md section 6,
# PR 56, call P56a: scripts/experts_fused_time.py; the pair is ``_gmm`` ->
# ``_act`` -> ``_gmm`` at the tiles above, "least" the bytes of the experts
# hit at the chip's 819 GB/s):
#   experts of          picks (real)  hit  least  pair   fused at 64 / 128
#   128 x 2048 x 768,   2048 (1152)   128  1.475  1.734  1.662 / 1.694
#   silu gate|up        2048 (2048)   128  1.475  1.798  1.678 / 1.710
#   (sdar-30b-a3b)      1024  (576)   127  1.463  1.684  1.670 / 1.656
#                       1024 (1024)   128  1.475  1.725  1.694 / 1.674
#                        512  (512)   123  1.417  1.612  1.601 / 1.592
#                        128  (128)    86  0.991  1.109  1.116 / 1.108
#   32 x 2048 x 1792,    128  (128)    32  0.860  0.969  0.971 / 0.967
#   silu (lfm2-8b-a1b)    64   (64)    30  0.807  0.905  0.904 / 0.909
#                       1024 (1024)    32  0.860  1.111  0.987 / 0.990
#                       2048 (2048)    32  0.860  1.299  1.023 / 1.011
#   64 of 128 x 2688 x    96   (49)    34  0.828  0.931  0.937 / 0.934
#   1856, relu2 (nemo-    48   (22)    19  0.463  0.532  0.534 / 0.539
#   tron3-nano-30b-a3b) 1536  (798)    64  1.559  1.925  1.746 / 1.757
# (the pair at the better of its row tiles).  The fused call streams the
# experts hit at 87-89% of the published bandwidth whatever the picks -- all
# its bytes counted, 742 GB/s, where the dense cell's matmuls read 753: what
# this chip gives -- so it is 2-7% under the pair at a diffusion round's
# sizes, 9-22% under it at a prefill call's, and level with it at 128 picks
# and fewer, where the pair moves next to no activations and streams at 89%
# already (inside the programs the pair costs more than alone, and the
# rounds of all three cells gain: PERF.md section 6, PR 56).  It is never
# slower: no seam by size.  Its row tile: 128 up to
# 1024 picks (fewer visits), 64 above (two visits of 128 rows on one expert
# outlast its successor's copy).
_FUSED_ROW_TILE, _FUSED_NARROW_FROM, _FUSED_ROW_TILE_NARROW = 128, 1025, 64
# What the call may ask of a TPU's vector memory (a v5e core has 128 MiB;
# the compiler's default for a kernel is 16 MiB, and two experts' matrices
# -- one computed on, one on its way in -- are 18.9 MB at sdar-30b-a3b's
# widths, 44 MB at lfm2-8b-a1b's, 40 MB at nemotron3-nano-30b-a3b's): the
# call states its own need (``_fused_vmem``) as its limit, and
# ``fused_supported`` refuses widths whose need is above this.
_FUSED_VMEM_BYTES = 96 * 2 ** 20
_FUSED_SLOTS = 2


def _fused_rows(M: int) -> int:
    """The row tile of the fused call for ``M`` sorted picks (the table
    above), no taller than ``M`` in whole sublane tiles."""
    tm = (_FUSED_ROW_TILE if M < _FUSED_NARROW_FROM
          else _FUSED_ROW_TILE_NARROW)
    return min(tm, -(-M // 16) * 16)


def _fused_vmem(tm: int, D: int, F: int, gated: bool, itemsize: int) -> int:
    """Bytes of vector memory the fused call holds at a row tile of ``tm``:
    the slots of whole experts, the row tile in and out (double-buffered
    by the pipeline) and the tile's intermediates (the first matmul's
    float32 output, the hidden in float32 and in the activations' dtype,
    the second's float32 output beside the tile it is merged into)."""
    ups = 2 if gated else 1
    experts = _FUSED_SLOTS * (ups + 1) * D * F * itemsize
    tiles = 2 * tm * D * (itemsize + 4)
    between = tm * (ups * F * 4 + F * (4 + itemsize) + 2 * D * 4)
    return experts + tiles + between


def fused_supported(*, backend: str, dtype: Any, mesh: Optional[Any],
                    d_model: int, d_expert: int, gated: bool) -> bool:
    """True where an expert layer takes an expert's whole feed-forward as
    one kernel (``_experts_fused``), False where the two grouped matmuls
    with the activation between them serve.  Decided from what the caller
    can observe, as ops.ssm.step_supported decides: a TPU backend (Mosaic),
    no mesh (a Mosaic call does not partition under GSPMD), bfloat16 or
    float32 (the row tiles are whole sublane tiles of either), a model
    width of whole 128-lane registers and an expert width the two halves of
    a gated first matmul split at one (768, 1792) or, without a gate, of
    whole sublane tiles (1856 = 116 x 16: the matrix is stored [F, D]), and
    two whole experts beside the tallest row tile within
    ``_FUSED_VMEM_BYTES``.  The picks a call brings do not enter: the table
    above has no size at which the pair is faster."""
    if backend != "tpu" or mesh is not None:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return False
    if d_model % 128 or d_expert % (128 if gated else 16):
        return False
    return (_fused_vmem(_FUSED_ROW_TILE, d_model, d_expert, gated,
                        jnp.dtype(dtype).itemsize) <= _FUSED_VMEM_BYTES)


def _fused_kernel(offsets, groups, tiles, hits, rank, count, x_ref, up_hbm,
                  down_hbm, out_ref, up_buf, down_buf, sems, *, gated: bool):
    """Visit ``v`` of the walk megablox's metadata lays out -- a row tile
    ``tiles[v]`` against the expert ``groups[v]`` that has rows in it, the
    experts hit in order, a tile that straddles experts visited once an
    expert -- computes ``act(x W_up) W_down`` of the tile's rows and keeps
    the rows that are the expert's.  The experts' matrices stay in HBM and
    come whole, the ``j``-th expert hit into slot ``j % 2``: at an expert's
    FIRST visit the next expert's two matrices are sent for (their slot is
    the one the expert before has finished with) before this one's are
    waited for, the down matrix only after the first matmul -- so the next
    copy is queued while this one still runs, an expert's matrices are read
    once however many tiles it straddles, and an expert nobody chose is
    never sent for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    v = pl.program_id(0)
    g = groups[v]
    j = rank[g]
    slot = jax.lax.rem(j, _FUSED_SLOTS)
    first = jnp.logical_or(v == 0, groups[jnp.maximum(v - 1, 0)] != g)

    def copy(j, down: bool):
        e, s = hits[j], jax.lax.rem(j, _FUSED_SLOTS)
        if down:
            return pltpu.make_async_copy(down_hbm.at[e], down_buf.at[s],
                                         sems.at[1, s])
        return pltpu.make_async_copy(up_hbm.at[e], up_buf.at[s],
                                     sems.at[0, s])

    @pl.when(v == 0)
    def _():
        copy(0, False).start()
        copy(0, True).start()

    @pl.when(first)
    def _():
        @pl.when(j + 1 < count[0])
        def _():
            copy(j + 1, False).start()
            copy(j + 1, True).start()

        copy(j, False).wait()

    x = x_ref[...]
    u = jax.lax.dot_general(
        x, up_buf[slot], (((1,), (0 if gated else 1,)), ((), ())),
        preferred_element_type=jnp.float32)
    h = _act(u, gated).astype(x.dtype)

    @pl.when(first)
    def _():
        copy(j, True).wait()

    y = jnp.dot(h, down_buf[slot], preferred_element_type=jnp.float32)
    tm = x.shape[0]
    row = tiles[v] * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = jnp.logical_and(row >= offsets[g], row < offsets[g + 1])
    out_ref[...] = jnp.where(mine, y, out_ref[...])


def _experts_fused(x, w_up, w_down, sizes, *, gated: bool,
                   interpret: bool = False):
    """x [M, D], its rows sorted by group, through each group's expert --
    ``act(x W_up[g]) W_down[g]``, ``W_up`` [G, D, 2F] gate and up side by
    side (``gated``) or [G, F, D] -- by ``sizes`` [G] rows a group -> [M,
    D] float32, as ONE Pallas call: ``_grouped_matmul``'s contract (a group
    without rows is not visited and its matrices are not read; rows past
    the last group hold nothing defined; the row tile may straddle groups)
    and its rounding points (float32 accumulation in both matmuls, the
    activation in float32, one cast of the hidden to ``x``'s dtype), with
    the first matmul's output and the hidden in vector memory only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    M, D = x.shape
    G, F = w_down.shape[:2]
    tm = _fused_rows(M)
    rows = -(-M // tm) * tm             # whole row tiles: the pad lies past
    if rows != M:                       # every group and is never computed
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    (offsets, groups, tiles), visits = make_group_metadata(
        group_sizes=sizes, m=rows, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False)
    hit = sizes > 0
    rank = jnp.cumsum(hit, dtype=jnp.int32) - 1
    hits = jnp.nonzero(hit, size=G, fill_value=0)[0].astype(jnp.int32)
    count = jnp.sum(hit, dtype=jnp.int32).reshape(1)

    def tile(v, offsets, groups, tiles, *_):
        return tiles[v], 0

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    need = _fused_vmem(tm, D, F, gated, x.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(visits,),
            in_specs=[pl.BlockSpec((tm, D), tile), hbm, hbm],
            out_specs=pl.BlockSpec((tm, D), tile),
            scratch_shapes=[
                pltpu.VMEM((_FUSED_SLOTS,) + w_up.shape[1:], w_up.dtype),
                pltpu.VMEM((_FUSED_SLOTS, F, D), w_down.dtype),
                pltpu.SemaphoreType.DMA((2, _FUSED_SLOTS)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(need + need // 4, 16 * 2 ** 20)),
        interpret=interpret,
        name="experts_fused",
    )(offsets, groups, tiles, hits, rank, count, x, w_up, w_down)
    return out[:M]


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
    grouped matmuls (``_grouped_matmul``; ``impl`` is its) or, under
    ``impl`` "fused" (a caller on one TPU whom ``fused_supported`` told so;
    "fused_interpret": the same kernel in Pallas interpret mode, tests on
    the CPU), as ONE kernel (``_experts_fused``); both visit a
    group's weights only where the group has rows: an expert nobody
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
        gated = cfg.expert_act == "silu"
        w_up = lp["e_gate_up" if gated else "e_up"]
        if impl in ("fused", "fused_interpret"):
            ys = _experts_fused(xs, w_up, lp["e_down"], sizes, gated=gated,
                                interpret=impl == "fused_interpret")
        else:
            up = _grouped_matmul(xs, w_up, sizes, impl, transposed=not gated)
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
