"""Decoder-only transformer LM — the long-context / distributed flagship.

The reference serves no sequence models (SURVEY.md §5: long-context absent,
pre-LLM era); this family exists so the graph IR's nodes can span a TPU mesh
slice, which the task's north star requires.  Parallelism is GSPMD-first
(the scaling-book recipe): parameters carry ``NamedSharding``s —

    wqkv [D, 3D]   P(None, 'tp')     heads sharded over tp
    wo   [D, D]    P('tp', None)     row-sharded; XLA inserts the psum
    w1   [D, F]    P(None, 'tp')
    w2   [F, D]    P('tp', None)
    embed [V, D]   replicated (small vocabs); norms replicated

activations shard as tokens ``[B, S] : P('dp', 'sp')``, and attention runs
as a ``shard_map`` ring over the ``sp`` axis (parallel/ring_attention.py),
rotating K/V blocks over ICI with online-softmax accumulation.  Everything
else — gradient all-reduce over dp, activation collectives for tp — is
inserted by XLA from the shardings.

``train_step`` is a pure (params, opt_state, batch) -> (params, opt_state,
loss) function; jit it over the mesh for the full dp/tp/sp-parallel training
step (used by ``__graft_entry__.dryrun_multichip``)."""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seldon_core_tpu.graph.units import Unit, register_unit
from seldon_core_tpu.parallel.moe import moe_leaf_spec
from seldon_core_tpu.parallel.pipeline import (
    merge_microbatches,
    pipeline_apply,
    split_microbatches,
    stack_stage_params,
    stage_param_shardings,
)
from seldon_core_tpu.parallel.mesh import shard_map as compat_shard_map
from seldon_core_tpu.parallel.ring_attention import ring_attention

__all__ = ["LMConfig", "lm_init", "lm_apply", "lm_loss", "lm_train_step",
           "param_shardings", "TransformerLM", "resolve_flash",
           "save_lm_weights", "load_lm_weights",
           "lm_pipeline_params", "lm_pipeline_apply", "lm_pipeline_loss",
           "lm_pipeline_train_step"]


#: ``LMConfig.layer_kinds`` letters of a block with one sub-layer
_ONE_SUBLAYER = {"m": ("ssm", None), "t": ("attn", None),
                 "e": (None, "experts")}


@dataclass(frozen=True)
class LMConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    # grouped-query attention: n_kv_heads < n_heads shares each K/V head
    # across n_heads/n_kv_heads query heads, in every forward here (the
    # cache-free one, the paged programs, the in-place decode kernel).  On
    # TPU this is a SERVING lever first: the KV cache shrinks by the group
    # factor, and cached decode is HBM-bound on exactly that stream.
    # 0 = multi-head attention (n_kv_heads == n_heads).
    n_kv_heads: int = 0
    # width of one head where the source publishes it apart from
    # d_model // n_heads (q is then n_heads * head_dim wide, not d_model);
    # 0 = d_model // n_heads.  ``hd`` is the one place that resolves it.
    head_dim: int = 0
    # an RMSNorm over each q and each k head (one weight vector of ``hd``
    # each a layer) before the rotary embedding
    qk_norm: bool = False
    # the eps of every RMSNorm
    norm_eps: float = 1e-6
    # False: the unembedding is its own matrix ``lm_head`` [D, V]
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # MoE: every ``moe_every``-th block (1-indexed) swaps its dense FFN for
    # a mixture of ``n_experts`` experts, top-``moe_k`` routed, sharded over
    # the mesh's ``ep`` axis (parallel/moe.py).  0 = dense everywhere.
    moe_every: int = 0
    n_experts: int = 8
    moe_k: int = 2
    # "int8": serve layer matmuls from symmetric per-channel int8 weights,
    # weight-only W8A16 (ops/quant.py dequant_matmul) — weights stream at
    # half the bytes, activations never quantize.  Serving-only.
    quant: str = "none"
    # "int8": store the KV cache as int8 with per-token-per-head f32
    # scales (absmax over the head dim).  Cached decode is HBM-bound on
    # the K/V stream — at large batch it is ~6x the weight stream — so
    # halving cache bytes is the decode-throughput lever int8 WEIGHTS
    # cannot be (models/generate.py reads the scales back into the score
    # and PV dots; prefill/training numerics untouched).  Serving-only.
    kv_quant: str = "none"
    # rotary position embeddings (RoPE, the modern standard).  Without ANY
    # positional signal a causal transformer cannot express
    # position-relative behavior (it must fall back to content-based
    # induction); rotation is applied to q/k after the head split, so the
    # KV cache stores rotated keys and cached decode needs no extra state.
    rope: bool = True
    rope_base: float = 10000.0
    # DROPLESS routed experts (parallel/moe.py ``moe_dropless``): > 0 makes
    # every layer's FFN ``n_experts`` gated-SiLU experts of this width, the
    # top ``moe_k`` of a float32 softmax router a token, their weights
    # renormalised over the chosen (``moe_norm_topk``).  No capacity, no
    # token dropped: a row's answer does not depend on who shares its batch,
    # which is what lets the scheduler co-batch such a generator.  Serving
    # only (the paged programs); ``moe_every`` is the capacity-routed layer
    # of ``lm_apply`` / ``lm_loss``.
    d_expert: int = 0
    moe_norm_topk: bool = True
    # generation by diffusion over blocks (models/generate.py): > 1 makes a
    # decode round ``denoising_steps`` passes over each block of
    # ``block_length`` positions that start as ``mask_id``, under a
    # block-causal mask; 1 = one token a step, causal.
    block_length: int = 1
    denoising_steps: int = 1
    mask_id: int = -1
    # THE LAYER KINDS, one description (``kind``): what mixes a layer's
    # positions and what its FFN is.  ``layer_kinds`` is one letter a layer,
    # "a" causal attention, "c" a gated short convolution (``[B | C | X] =
    # in_proj(u)``; a depthwise causal convolution of ``conv_kernel`` taps
    # over ``B * X``, gated by ``C``; ``out_proj``: a layer that keeps the
    # last ``conv_kernel - 1`` positions of ``B * X`` a sequence in the
    # K/V's place, models/generate.py), "r" power retention of degree 2
    # (ops/retention.py: q, k, v and o as attention has them and a gate of
    # one logit a KV head; a layer that keeps no position but a float32
    # matrix state a sequence and a KV head, read and rewritten by every
    # token); "" = attention in every layer.
    # ``dense_layers``: the first so many layers' FFN is a dense gated-SiLU
    # FFN of width ``d_ff`` (``w2(silu(w1 u) * w3 u)``) where the others
    # hold the experts of ``d_expert`` -- or, without experts, EVERY layer's
    # (``dense_layers == n_layers``).  ``router``: how an expert layer
    # scores, "softmax" or "sigmoid_bias" (parallel/moe.py).  Serving only,
    # as ``d_expert`` is.
    # BLOCKS OF ONE SUB-LAYER (``x + sublayer(norm(x))``, one norm): "m" a
    # Mamba-2 state-space mixer with no FFN (ops/ssm.py: ``ssm_heads`` heads
    # of ``ssm_head_dim``, ``ssm_groups`` groups of B and C of
    # ``ssm_state``, a depthwise causal convolution of ``conv_kernel`` taps
    # with a bias over x | B | C; a layer that keeps those taps and a
    # float32 matrix state ``[heads, head_dim, state]`` a sequence), "t"
    # causal attention with no FFN, "e" an FFN with no mixer (the dropless
    # experts of ``d_expert``).
    layer_kinds: str = ""
    conv_kernel: int = 3
    dense_layers: int = 0
    router: str = "softmax"
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    # THE EXPERT LAYER'S FORM (parallel/moe.py ``moe_dropless``).
    # ``expert_act``: "silu" gated experts ``w_down(silu(w_gate h) * w_up
    # h)``, "relu2" experts of two matrices ``w_down(relu(w_up h)^2)``;
    # ``d_shared`` > 0: a shared expert of that width and the same form on
    # every token, added to the routed sum unweighted; ``router_scale``
    # multiplies the routed weights; ``router_eps`` is what the
    # "sigmoid_bias" renormalisation adds to the chosen scores' sum.
    # ``experts_held`` > 0: THE CHIP'S SHARE of a layer divided over chips
    # by expert parallelism -- the router keeps its ``n_experts`` outputs
    # and its ``moe_k`` a token, the layer holds experts ``[experts_first,
    # experts_first + experts_held)`` and computes their part of the result
    # alone; what the absent experts would add is left out (nothing stands
    # in for the other chips).  0 = every expert is held.
    expert_act: str = "silu"
    d_shared: int = 0
    router_scale: float = 1.0
    router_eps: float = 1e-6
    experts_held: int = 0
    experts_first: int = 0

    @property
    def hd(self) -> int:
        """Width of one attention head."""
        return self.head_dim or self.d_model // self.n_heads

    def is_moe_layer(self, i: int) -> bool:
        return self.moe_every > 0 and (i + 1) % self.moe_every == 0

    def kind(self, i: int) -> Tuple[Optional[str], Optional[str]]:
        """Layer ``i`` as ``(mixer, ffn)``: "attn" | "conv" | "ret" | "ssm",
        and "gelu" (the two-matrix FFN), "moe" (capacity-routed,
        ``moe_every``), "gated" (a dense gated-SiLU layer) or "experts"
        (dropless); None for the half a block of one sub-layer lacks.  The
        one place that reads the fields above; a program traces the
        decoder block once a distinct kind."""
        letter = self.layer_kinds[i:i + 1]
        if letter in _ONE_SUBLAYER:
            return _ONE_SUBLAYER[letter]
        mixer = {"c": "conv", "r": "ret"}.get(letter, "attn")
        if self.d_expert or self.dense_layers:
            return mixer, "gated" if i < self.dense_layers else "experts"
        return mixer, "moe" if self.is_moe_layer(i) else "gelu"

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        return tuple(self.kind(i) for i in range(self.n_layers))

    @property
    def expert_layers(self) -> int:
        """Layers whose FFN is dropless routed experts."""
        return sum(ffn == "experts" for _, ffn in self.kinds)

    @property
    def held(self) -> int:
        """Experts an expert layer holds here (``experts_held``)."""
        return self.experts_held or self.n_experts

    @property
    def ssm_inner(self) -> int:
        """Width of a state-space mixer's x: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels its convolution runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def __post_init__(self):
        if self.d_model % self.n_heads != 0:
            # caught at config construction (graph load), not as an opaque
            # reshape error at first-request trace time
            raise ValueError(
                f"d_model={self.d_model} not divisible by "
                f"n_heads={self.n_heads}"
            )
        if self.quant not in ("none", "int8"):
            raise ValueError(
                f"quant={self.quant!r} not supported (none | int8)"
            )
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant={self.kv_quant!r} not supported (none | int8)"
            )
        kv = self.kv_heads
        if self.n_heads % kv != 0:
            raise ValueError(
                f"n_heads={self.n_heads} not divisible by "
                f"n_kv_heads={kv}"
            )
        if self.rope and self.hd % 2 != 0:
            raise ValueError(
                f"RoPE needs an even head dim, got {self.hd}"
            )
        if self.d_expert and (self.moe_every
                              or not 0 < self.moe_k <= self.n_experts):
            raise ValueError(
                "d_expert (dropless experts in every layer) needs "
                "moe_every=0 and 0 < moe_k <= n_experts"
            )
        if self.layer_kinds and (
                len(self.layer_kinds) != self.n_layers
                or set(self.layer_kinds) - set("acrmte")
                or self.conv_kernel < 2):
            raise ValueError(
                f"layer_kinds={self.layer_kinds!r} is one letter a layer, "
                f"'a', 'c' or 'r' (or, of one sub-layer, 'm', 't' or 'e'), "
                f"for n_layers={self.n_layers}, and a "
                f"convolution has at least 2 taps (got {self.conv_kernel})")
        if (not 0 <= self.dense_layers <= self.n_layers
                or (self.dense_layers not in (0, self.n_layers)
                    and not self.d_expert)
                or (self.dense_layers and self.moe_every)):
            raise ValueError(
                f"dense_layers={self.dense_layers} are the leading layers of "
                "a configuration with experts (d_expert), or all n_layers "
                "of one without (and without moe_every)")
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(
                f"router={self.router!r} not supported "
                "(softmax | sigmoid_bias)")
        if self.expert_act not in ("silu", "relu2"):
            raise ValueError(
                f"expert_act={self.expert_act!r} not supported "
                "(silu | relu2)")
        if "e" in self.layer_kinds and not self.d_expert:
            raise ValueError(
                "an 'e' layer is dropless routed experts: it needs d_expert")
        if not (0 <= self.experts_first
                and self.experts_first + self.held <= self.n_experts):
            raise ValueError(
                f"experts_held={self.experts_held} from experts_first="
                f"{self.experts_first} on reach past n_experts="
                f"{self.n_experts}: the share is a range of the router's "
                "own outputs")
        if "m" in self.layer_kinds and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0
                and self.ssm_state > 0 and self.ssm_groups > 0
                and self.ssm_heads % self.ssm_groups == 0):
            raise ValueError(
                "an 'm' layer (Mamba-2) needs ssm_heads, ssm_head_dim and "
                "ssm_state, and ssm_groups that divide the heads")
        if set(self.layer_kinds) & set("crm") and (self.block_length > 1
                                                   or self.moe_every):
            raise ValueError(
                "a gated short-convolution, retention or state-space layer "
                "rides neither "
                "a round of denoising passes (block_length > 1: its state "
                "would have to be rolled back a pass) nor moe_every")
        if "r" in self.layer_kinds and (
                set(self.layer_kinds) != {"r"} or self.hd % 2
                or self.kv_quant != "none"):
            raise ValueError(
                "retention layers are served where EVERY layer is one "
                "(layer_kinds all 'r': a row's state lives at its first "
                "block's id, one entry a block, so such a generator is "
                "deployed a block a row and holds no K/V), with an even "
                "head width and kv_quant none")
        if self.block_length > 1 and (
                self.block_length % self.denoising_steps
                or not 0 <= self.mask_id < self.vocab):
            raise ValueError(
                f"block_length={self.block_length} needs denoising_steps "
                f"that divide it and a mask_id inside the vocabulary, got "
                f"{self.denoising_steps} and {self.mask_id}"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads


def _rmsnorm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * w


def apply_rope(x, positions, base: float = 10000.0):
    """Rotate [B, H, S, hd] by per-position angles; positions [S] shared
    across the batch (may be traced — cached decode passes start+arange)
    or [B, S] PER-ROW (batched speculative decoding, where rows sit at
    different sequence lengths).  Half-split convention; f32 trig,
    output in the input dtype.

    The rotate-half is computed as ``x @ R`` with R the constant signed
    permutation [[0, I], [-I, 0]] — EXACT arithmetic (each output is
    ±one input) and MXU-fusable.  The obvious
    ``concat([-x2, x1])`` lowers to lane-dim pad+maximum fusions that
    cannot fuse into the flash kernel's custom-call boundary: profiled
    at ~290 us/layer on the B=32 S=512 prefill (~3.5 ms/pass, ~7% of
    the whole forward)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = base ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )  # [half]
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [...,S,half]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if angles.ndim == 2:  # shared positions [S, half]
        c = jnp.concatenate([cos, cos], axis=-1)[None, None]  # [1,1,S,hd]
        s = jnp.concatenate([sin, sin], axis=-1)[None, None]
    else:  # per-row positions [B, S, half] -> broadcast over heads
        c = jnp.concatenate([cos, cos], axis=-1)[:, None]  # [B,1,S,hd]
        s = jnp.concatenate([sin, sin], axis=-1)[:, None]
    eye = jnp.eye(half, dtype=x.dtype)
    zero = jnp.zeros((half, half), x.dtype)
    rot = jnp.concatenate([
        jnp.concatenate([zero, eye], axis=1),    # rows i<half: +x1 -> out2
        jnp.concatenate([-eye, zero], axis=1),   # rows i>=half: -x2 -> out1
    ], axis=0)  # [hd, hd]
    rx = jax.lax.dot_general(
        x, rot, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out = x.astype(jnp.float32) * c + rx * s
    return out.astype(x.dtype)


def _ssm_init(rng, cfg: LMConfig) -> Dict[str, Any]:
    """One Mamba-2 mixer's weights (ops/ssm.py; models/generate.py
    ``_ssm``): ``ssm_in`` [D, inner + conv_dim + heads] = z | x B C | dt
    side by side, the taps ``conv_w`` [K, conv_dim] (the last one on the
    position itself) and their bias ``conv_b``, ``ssm_norm`` [inner] the
    gated norm's weight, ``ssm_out`` [inner, D]; and, float32, one a head:
    ``A_log`` = log of a uniform draw from 1 .. 16 (a decay ``exp(-dt A)``
    of 0.2 .. 0.999 a position), ``dt_bias`` the inverse softplus of a
    log-uniform draw from 0.001 .. 0.1 (``time_step_min`` / ``_max``,
    floored at ``time_step_floor`` 1e-4: Mamba-2's own initialisation, and
    the published keys' values) and the skip ``ssm_D`` uniform from 0.5 ..
    1.5.  The bias and the norm's weight are drawn too, NOT zero and one: a
    program that drops either fails the comparison."""
    dt = cfg.dtype
    D, inner, heads = cfg.d_model, cfg.ssm_inner, cfg.ssm_heads
    k = jax.random.split(rng, 8)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    step = jnp.maximum(jnp.exp(jax.random.uniform(
        k[4], (heads,), jnp.float32, math.log(0.001), math.log(0.1))), 1e-4)
    return {
        "ssm_in": dense(k[0], (D, inner + cfg.ssm_conv_dim + heads), D),
        "conv_w": dense(k[1], (cfg.conv_kernel, cfg.ssm_conv_dim),
                        cfg.conv_kernel),
        "conv_b": (0.1 * jax.random.normal(
            k[2], (cfg.ssm_conv_dim,), jnp.float32)).astype(dt),
        "A_log": jnp.log(jax.random.uniform(
            k[3], (heads,), jnp.float32, 1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_D": jax.random.uniform(k[5], (heads,), jnp.float32, 0.5, 1.5),
        "ssm_norm": (1.0 + 0.1 * jax.random.normal(
            k[6], (inner,), jnp.float32)).astype(dt),
        "ssm_out": dense(k[7], (inner, D), inner),
    }


def lm_init(rng, cfg: LMConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, cfg.n_layers * 4 + 1)
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
        ).astype(dt)

    params: Dict[str, Any] = {
        "embed": dense(keys[0], (cfg.vocab, cfg.d_model), cfg.d_model),
    }
    hd = cfg.hd
    q_out = cfg.n_heads * hd
    qkv_out = q_out + 2 * cfg.kv_heads * hd  # q | k | v segments
    D = cfg.d_model
    for i in range(cfg.n_layers):
        k = keys[1 + 4 * i : 1 + 4 * (i + 1)]
        mixer, ffn = cfg.kind(i)
        # a block of one sub-layer has the one norm of the half it holds
        lp = {name: jnp.ones((D,), dt) for name, half in (
            ("ln1", mixer), ("ln2", ffn)) if half}
        if mixer == "ssm":
            lp.update(_ssm_init(k[0], cfg))
        elif mixer is None:
            pass
        elif mixer == "conv":
            # in_proj -> B | C | X side by side; the taps [K, D], the last
            # one on the position itself; out_proj
            lp["conv_in"] = dense(k[0], (D, 3 * D), D)
            lp["conv_w"] = dense(jax.random.fold_in(k[0], 1),
                                 (cfg.conv_kernel, D), cfg.conv_kernel)
            lp["conv_out"] = dense(k[1], (D, D), D)
        else:
            lp["wqkv"] = dense(k[0], (D, qkv_out), D)
            lp["wo"] = dense(k[1], (q_out, D), q_out)
            if cfg.qk_norm:
                lp["q_norm"] = jnp.ones((hd,), dt)
                lp["k_norm"] = jnp.ones((hd,), dt)
            if mixer == "ret":
                # the gate: one logit a KV head; its bias float32 and NOT
                # zero -- sigmoid of 4.6 .. 7.6 is a decay of 0.99 ..
                # 0.9995 a position, a memory of 100 to 2,000 positions,
                # so what an earlier chunk left always weighs
                lp["ret_gate"] = dense(jax.random.fold_in(k[1], 1),
                                       (D, cfg.kv_heads), D)
                lp["ret_gate_b"] = jax.random.uniform(
                    jax.random.fold_in(k[1], 2), (cfg.kv_heads,),
                    jnp.float32, 4.6, 7.6)
        if ffn == "experts":
            from seldon_core_tpu.parallel.moe import dropless_init

            lp.update(dropless_init(k[2], cfg))
        elif ffn is None:
            pass
        elif ffn == "gated":
            lp["w1"] = dense(k[2], (D, cfg.d_ff), D)
            lp["w3"] = dense(jax.random.fold_in(k[2], 1), (D, cfg.d_ff), D)
            lp["w2"] = dense(k[3], (cfg.d_ff, D), cfg.d_ff)
        elif ffn == "moe":
            from seldon_core_tpu.parallel.moe import MoEConfig, moe_init

            lp["moe"] = moe_init(
                k[2],
                MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                          n_experts=cfg.n_experts, k=cfg.moe_k, dtype=dt),
            )
        else:
            lp["w1"] = dense(k[2], (cfg.d_model, cfg.d_ff), cfg.d_model)
            lp["w2"] = dense(k[3], (cfg.d_ff, cfg.d_model), cfg.d_ff)
        params[f"l{i}"] = lp
    params["ln_f"] = jnp.ones((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(
            jax.random.fold_in(keys[0], 1), (cfg.d_model, cfg.vocab),
            cfg.d_model)
    return params


def param_shardings(mesh: Mesh, params) -> Any:
    """NamedShardings for the tp layout above (replicated where not listed)."""

    def spec_for(path, leaf) -> P:
        # path is a tuple of DictKey objects; the leaf name is the last key
        names = [getattr(p, "key", str(p)) for p in path]
        name = names[-1]
        if "moe" in names:
            return moe_leaf_spec(name, leaf, mesh)
        has_tp = "tp" in mesh.axis_names
        # int8 layout (quantize_lm_params): w_q shards like w; the
        # per-output-channel scales follow the output axis' sharding
        if name.endswith("_q") or name.endswith("_s"):
            base, kind = name[:-2], name[-1]
            if base in ("wqkv", "w1"):
                if kind == "q":
                    return P(None, "tp") if has_tp else P()
                return P("tp") if has_tp else P()
            if base in ("wo", "w2"):
                # output axis replicated (the psum happens over tp)
                return P("tp", None) if (has_tp and kind == "q") else P()
            return P()
        if name in ("wqkv", "w1", "w3"):
            return P(None, "tp") if has_tp else P()
        if name in ("wo", "w2"):
            return P("tp", None) if has_tp else P()
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = [NamedSharding(mesh, spec_for(path, leaf))
                 for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def gqa_attention(q, k, v, causal: bool):
    """Grouped-query attention without materialising repeated K/V.

    q [B, H, S, hd]; k/v [B, KV, S_k, hd] with H = KV * g.  The group axis
    rides the dot_general batch dims, so K/V stream from HBM ONCE at their
    stored (grouped) size — an explicit head-repeat would rebuild the full
    MHA-sized tensors and erase GQA's bandwidth win."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    g = H // KV
    qg = q.reshape(B, KV, g * S, hd)  # group heads fold into the row axis
    scale = jnp.float32(1.0 / (hd ** 0.5))
    s = jax.lax.dot_general(
        qg, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) * scale  # [B, KV, g*S, S_k]
    s = s.reshape(B, KV, g, S, k.shape[2])
    if causal:
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(k.shape[2])[None, :]
        s = jnp.where((qpos >= kpos)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jax.lax.dot_general(
        p.reshape(B, KV, g * S, k.shape[2]), v,
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)  # [B, KV, g*S, hd]
    return out.reshape(B, H, S, hd)


def _attention(q, k, v, mesh: Optional[Mesh], causal: bool,
               use_flash: bool = False):
    """q [B, H, S, hd], k/v [B, KV, S, hd] -> [B, H, S, hd]; ring over sp
    when the mesh shards S.

    ``use_flash`` opts the single-chip path into the Pallas flash kernel
    (differentiable — custom flash VJP) for the shapes the kernel
    documents (``flash_applies``: S % 128, hd <= 256); other shapes take
    the XLA formulation.  The choice is made from the shapes BEFORE the
    call, so an error out of the kernel surfaces instead of changing
    path.  Grouped K/V (KV < H) takes the GQA formulation; the ring path
    requires full MHA heads."""
    from seldon_core_tpu.ops.flash_attention import (
        flash_applies,
        flash_attention,
    )

    # auto mode only takes the kernel where it measures faster than XLA's
    # fused attention (thresholds above; grouped K/V wins from much
    # shorter S); "force" overrides (explicit opt-in / the benchmarking
    # arm).  Single-chip only: pallas_call is not auto-partitionable
    # under GSPMD, so any multi-device mesh (tp/dp/sp) keeps XLA
    grouped = k.shape[1] != q.shape[1]
    auto_min = FLASH_AUTO_MIN_S_GQA if grouped else FLASH_AUTO_MIN_S
    take_flash = (
        (use_flash == "force" or (use_flash and q.shape[2] >= auto_min))
        and (mesh is None or mesh.size == 1)
        and flash_applies(q.shape, k.shape)
    )
    if grouped and mesh is not None and "sp" in mesh.axis_names \
            and mesh.shape["sp"] > 1:
        raise ValueError(
            "sequence-parallel ring attention requires "
            "n_kv_heads == n_heads"
        )
    if take_flash:
        # the flash kernel is GQA-native (grouped K/V block indexing)
        return flash_attention(q, k, v, causal=causal)
    if grouped:
        return gqa_attention(q, k, v, causal)
    if mesh is not None and "sp" in mesh.axis_names and mesh.shape["sp"] > 1:
        specs = P(
            "dp" if "dp" in mesh.axis_names else None,
            "tp" if "tp" in mesh.axis_names else None,
            "sp",
            None,
        )

        ring = partial(
            compat_shard_map,
            mesh=mesh,
            in_specs=(specs, specs, specs),
            out_specs=specs,
        )(lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp", causal=causal))
        return ring(q, k, v)
    # single-block fallback: plain causal attention
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qpos = jnp.arange(q.shape[2])[:, None]
        kpos = jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qpos >= kpos, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _block(lp, x, cfg: LMConfig, mesh: Optional[Mesh], causal: bool,
           use_flash: bool = False):
    """One decoder block: attn + FFN (dense or MoE) with residuals.
    x [B,S,D] -> (x', lb_loss) where lb_loss is 0 for dense layers."""
    from seldon_core_tpu.ops.quant import lm_matmul

    B, S, D = x.shape
    hd = cfg.hd
    kv = cfg.kv_heads
    h = _rmsnorm(x, lp["ln1"])
    qkv = lm_matmul(lp, "wqkv", h, out_dtype=x.dtype)  # [B,S,D+2*kv*hd]
    q, k, v = jnp.split(qkv, [D, D + kv * hd], axis=-1)

    def heads(t, n):
        return t.reshape(B, S, n, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q, cfg.n_heads), heads(k, kv), heads(v, kv)
    if cfg.rope:
        # rotation BEFORE any sharded attention: positions are global, so
        # the sp ring path needs no per-shard offsets
        positions = jnp.arange(S)
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
    a = _attention(q, k, v, mesh, causal, use_flash)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, D)
    x = x + lm_matmul(lp, "wo", a, out_dtype=x.dtype)
    h = _rmsnorm(x, lp["ln2"])
    y, lb = _ffn(lp, h, cfg, mesh)
    return x + y, lb


def _ffn(lp, h, cfg: LMConfig, mesh: Optional[Mesh], valid=None,
         kind: Optional[str] = None, experts: Optional[str] = None):
    """Feed-forward on h [B,S,D] -> (y, aux), by the layer's ``kind``
    (``LMConfig.kind``'s second; None reads it off the layer's weights):
    "gelu" the dense two-matrix tanh-GELU FFN (aux 0), "moe" the
    capacity-routed experts of ``moe_every`` (aux the load-balance loss),
    "experts" the dropless routed gated-SiLU experts of ``cfg.d_expert``
    (aux the number of experts read; ``valid`` [B,S] keeps pad positions
    from picking any; ``experts`` is ``moe_dropless``'s ``impl``), "gated"
    a leading dense layer of such a configuration, ``w2(silu(w1 h) * w3
    h)`` (aux 0)."""
    from seldon_core_tpu.ops.quant import lm_matmul

    if kind is None:
        kind = ("experts" if "router" in lp else "gated" if "w3" in lp
                else "moe" if "moe" in lp else "gelu")
    if kind == "experts":
        from seldon_core_tpu.parallel.moe import moe_dropless

        if valid is None:
            valid = jnp.ones(h.shape[:2], bool)
        # (named only where one is forced: the platform decides otherwise)
        return moe_dropless(lp, h, valid, cfg,
                            **({"impl": experts} if experts else {}))
    if kind == "gated":
        u = (jax.nn.silu(lm_matmul(lp, "w1", h, out_dtype=h.dtype))
             * lm_matmul(lp, "w3", h, out_dtype=h.dtype))
        return lm_matmul(lp, "w2", u, out_dtype=h.dtype), jnp.int32(0)
    if kind == "moe":
        from seldon_core_tpu.parallel.moe import MoEConfig, moe_apply

        mcfg = MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                         n_experts=cfg.n_experts, k=cfg.moe_k,
                         dtype=cfg.dtype)
        y, aux = moe_apply(lp["moe"], h, mcfg, mesh=mesh)
        return y, aux["lb_loss"]
    u = jax.nn.gelu(lm_matmul(lp, "w1", h, out_dtype=h.dtype))
    return lm_matmul(lp, "w2", u, out_dtype=h.dtype), jnp.float32(0.0)


def lm_apply(
    params, tokens, cfg: LMConfig, mesh: Optional[Mesh] = None,
    causal: bool = True, use_flash: bool = False, return_lb: bool = False
):
    """tokens [B, S] int32 -> logits [B, S, V] (f32).  ``use_flash`` uses
    the Pallas flash kernel on single-chip meshes (differentiable).
    ``return_lb`` additionally returns the summed MoE load-balance loss."""
    if (cfg.d_expert or cfg.qk_norm or cfg.head_dim or cfg.block_length > 1
            or not cfg.tie_embeddings or cfg.dense_layers
            or set(cfg.layer_kinds) & set("crmte")):
        raise ValueError(
            "the cache-free forward implements the repo's own block only; "
            "a configuration with head_dim, qk_norm, an untied head, "
            "dropless experts, dense gated layers, short-convolution, "
            "retention or state-space layers, blocks of one sub-layer or "
            "block diffusion is served by the paged "
            "programs (models/generate.py)")
    x = params["embed"][tokens]  # [B,S,D]
    lb_total = jnp.float32(0.0)
    for i in range(cfg.n_layers):
        x, lb = _block(params[f"l{i}"], x, cfg, mesh, causal, use_flash)
        lb_total = lb_total + lb
    x = _rmsnorm(x, params["ln_f"])
    logits = (x @ params["embed"].T).astype(jnp.float32)
    return (logits, lb_total) if return_lb else logits


LB_LOSS_COEF = 0.01  # Switch-style aux-loss weight


def lm_loss(params, batch, cfg: LMConfig, mesh: Optional[Mesh] = None,
            apply_fn=None, use_flash: Optional[bool] = None):
    """Next-token cross-entropy (+ weighted MoE load-balance loss when the
    config has MoE layers); batch = {tokens: [B, S+1]}.

    ``apply_fn(params, tokens) -> logits`` overrides the forward (used by the
    pipelined variant); defaults to ``lm_apply``.  ``use_flash=None`` picks
    the Pallas flash kernel automatically on single-chip TPU (the kernel
    carries a custom VJP, so training uses it too); shapes outside its
    constraints fall back to XLA attention inside ``_attention``."""
    tokens = batch["tokens"]
    lb_total = jnp.float32(0.0)
    if use_flash is None:
        from seldon_core_tpu.ops.fused_mlp import pallas_supported

        use_flash = pallas_supported()
    if apply_fn is None:
        logits, lb_total = lm_apply(params, tokens[:, :-1], cfg, mesh,
                                    return_lb=True, use_flash=use_flash)
    else:
        if cfg.moe_every:
            # a custom forward cannot report the lb loss through this
            # interface; training without it collapses the router
            raise ValueError(
                "lm_loss(apply_fn=...) does not support MoE configs"
            )
        logits = apply_fn(params, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + LB_LOSS_COEF * lb_total


def _grad_update(loss_fn, params, opt_state, batch, optimizer):
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(lambda p, u: p + u.astype(p.dtype), params, updates)
    return params, opt_state, loss


def lm_train_step(params, opt_state, batch, optimizer, cfg: LMConfig,
                  mesh: Optional[Mesh] = None,
                  use_flash: Optional[bool] = None):
    if cfg.quant != "none":
        # int8 weights are not differentiable — quantization is a serving
        # transform (quantize_lm_params), applied after training
        raise ValueError("lm_train_step requires quant='none'")
    return _grad_update(
        lambda p, b: lm_loss(p, b, cfg, mesh, use_flash=use_flash), params,
        opt_state, batch, optimizer,
    )


# ---------------------------------------------------------------------------
# Pipeline-parallel variant: the layer stack splits into pp stages, one stage
# per chip; microbatched GPipe schedule over ICI (parallel/pipeline.py).
# Embed/unembed stay outside the pipeline (replicated, batch over dp).
# ---------------------------------------------------------------------------


def lm_pipeline_params(params, cfg: LMConfig, n_stages: int, mesh: Mesh):
    """Re-layout lm_init params for a pp-stage pipeline.

    Returns {embed, ln_f, stages} where ``stages`` leaves are stacked
    [n_stages, layers_per_stage, ...] and sharded P('pp', ...).
    """
    if cfg.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={n_stages}"
        )
    if cfg.moe_every:
        # MoE layers have a different param tree than dense ones, so they
        # cannot stack into a homogeneous per-stage scan; also their
        # lb_loss would be silently dropped by the pipeline schedule
        raise ValueError("pipeline parallelism does not support MoE layers")
    lps = cfg.n_layers // n_stages
    per_stage = []
    for s in range(n_stages):
        layers = [params[f"l{s * lps + j}"] for j in range(lps)]
        per_stage.append(
            jax.tree_util.tree_map(lambda *ls: jnp.stack(ls, 0), *layers)
        )
    stages = stack_stage_params(per_stage)
    stages = jax.device_put(stages, stage_param_shardings(mesh, stages))
    return {"embed": params["embed"], "ln_f": params["ln_f"], "stages": stages}


def lm_pipeline_apply(pp_params, tokens, cfg: LMConfig, mesh: Mesh,
                      n_micro: int = 4, causal: bool = True):
    """Pipelined forward: tokens [B, S] -> logits [B, S, V]."""

    def stage_fn(stage_params, x):
        # stage_params leaves: [layers_per_stage, ...]; scan the sub-stack
        def body(h, lp):
            h2, _lb = _block(lp, h, cfg, mesh=None, causal=causal)
            return h2, None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    x = pp_params["embed"][tokens]  # [B,S,D]
    xm = split_microbatches(x, n_micro)
    ym = pipeline_apply(stage_fn, pp_params["stages"], xm, mesh=mesh)
    x = merge_microbatches(ym)
    x = _rmsnorm(x, pp_params["ln_f"])
    return (x @ pp_params["embed"].T).astype(jnp.float32)


def lm_pipeline_loss(pp_params, batch, cfg: LMConfig, mesh: Mesh,
                     n_micro: int = 4):
    return lm_loss(
        pp_params, batch, cfg, mesh,
        apply_fn=lambda p, t: lm_pipeline_apply(p, t, cfg, mesh, n_micro),
    )


def lm_pipeline_train_step(pp_params, opt_state, batch, optimizer,
                           cfg: LMConfig, mesh: Mesh, n_micro: int = 4):
    """Full pipeline-parallel train step — backward replays the GPipe
    schedule in reverse through the scan+ppermute graph."""
    return _grad_update(
        lambda p, b: lm_pipeline_loss(p, b, cfg, mesh, n_micro),
        pp_params, opt_state, batch, optimizer,
    )


#: ``auto`` mode thresholds, from interleaved A/B through the LM forward
#: on v5e (round 4, wide-block kernel: bq<=512/bk<=1024).  MHA hd=128:
#: 0.93x XLA at S=2048, 1.36x at S=8192 — kernel from 4096 up.  GROUPED
#: K/V (GQA) wins much earlier: 1.20x at S=512/B=32 and 3.13x at
#: S=2048/B=4 (hd=64, kv=4) — XLA's fallback materialises the grouped
#: score tensor while the kernel streams K/V once at stored size.
FLASH_AUTO_MIN_S = 4096
FLASH_AUTO_MIN_S_GQA = 512


def resolve_flash(attention: str, mesh: Optional[Mesh]):
    """Deployment-parameter attention mode -> static flash decision.

    ``auto``  — Pallas flash kernel on a single-chip TPU backend
                (pallas_call is not auto-partitionable under GSPMD, and
                the kernels are Mosaic-TPU kernels) AND where the
                sequence is long enough to win — checked per call in
                ``_attention``: grouped K/V (GQA) from
                ``FLASH_AUTO_MIN_S_GQA`` (512) up, MHA from
                ``FLASH_AUTO_MIN_S`` (4096) up;  returns True/False;
    ``flash`` — force the kernel at ANY length the kernel accepts
                (returns ``"force"``, the benchmarking arm / explicit
                opt-in); on a backend or mesh that cannot run it this
                RAISES — an explicit request is never quietly swapped
                for XLA;
    ``xla``   — force the plain XLA attention (the control arm)."""
    if attention == "xla":
        return False
    if attention not in ("auto", "flash"):
        raise ValueError(
            f"attention={attention!r} not supported (auto | flash | xla)"
        )
    multi = mesh is not None and mesh.size > 1
    from seldon_core_tpu.ops.fused_mlp import pallas_supported

    supported = pallas_supported() and not multi
    if attention == "flash":
        if not supported:
            raise ValueError(
                "attention='flash' needs a single-chip TPU backend "
                f"(backend={jax.default_backend()!r}, mesh size="
                f"{mesh.size if mesh is not None else 1}); use "
                "attention='auto' or 'xla'"
            )
        return "force"
    return supported


def save_lm_weights(params, path: str) -> str:
    """Checkpoint an lm_init-shaped params tree to one .npz — the
    train->serve hand-off (runtime/persistence.py flat-pytree format, so
    the same file also restores through the persistence machinery)."""
    from seldon_core_tpu.runtime.persistence import save_state_to_path

    return save_state_to_path(path, params)


def load_lm_weights(params, path: str):
    """Load trained weights onto a freshly-initialised params tree (the
    ``weights_path`` unit parameter).  Structure/dtype follow the serving
    config — an f32 training checkpoint serves as bf16, and quantization
    applies AFTER loading.

    STRICT: a missing file, a checkpoint whose keys don't cover the
    serving config's tree (layer-count mismatch, a state checkpoint
    rather than a params checkpoint), or a shape mismatch (wrong
    d_model/vocab/...) all raise a one-line config error at LOAD time —
    a generator pod silently serving random or misshapen weights is the
    worst failure mode."""
    if not path:
        return params
    import os as _os

    if not _os.path.exists(path):
        raise FileNotFoundError(f"weights_path {path!r} does not exist")
    import numpy as _np

    import jax as _jax

    from seldon_core_tpu.runtime.persistence import state_from_host

    with _np.load(path) as data:
        flat = dict(data)
    want = {
        _jax.tree_util.keystr(p): _np.asarray(leaf).shape
        for p, leaf in _jax.tree_util.tree_flatten_with_path(params)[0]
    }
    missing = sorted(set(want) - set(flat))
    if missing:
        raise ValueError(
            f"weights_path {path!r} does not cover the serving config: "
            f"{len(missing)} missing leaves (first: {missing[0]}); is the "
            f"checkpoint from a different architecture, or a unit-STATE "
            f"snapshot rather than save_lm_weights params?"
        )
    bad = [
        (k, flat[k].shape, want[k])
        for k in want if tuple(flat[k].shape) != tuple(want[k])
    ]
    if bad:
        k, got, exp = bad[0]
        raise ValueError(
            f"weights_path {path!r} shape mismatch at {k}: checkpoint "
            f"{got} vs serving config {exp} (+{len(bad) - 1} more)"
        )
    return state_from_host(flat, params)


@register_unit("TransformerLM")
class TransformerLM(Unit):
    """Serving unit: next-token logits for a token batch.  For multi-chip
    serving construct with a mesh; params shard per ``param_shardings``."""

    def __init__(
        self,
        vocab: int = 256,
        d_model: int = 128,
        n_heads: int = 4,
        n_layers: int = 2,
        d_ff: int = 512,
        seed: int = 0,
        mesh: Optional[Mesh] = None,
        dtype: str = "bfloat16",
        moe_every: int = 0,
        n_experts: int = 8,
        moe_k: int = 2,
        quant: str = "none",
        attention: str = "auto",
        n_kv_heads: int = 0,
        weights_path: str = "",
        rope: bool = True,
        rope_base: float = 10000.0,
    ):
        self.weights_path = str(weights_path)
        self.cfg = LMConfig(
            vocab=int(vocab), d_model=int(d_model), n_heads=int(n_heads),
            n_layers=int(n_layers), d_ff=int(d_ff),
            dtype=jnp.dtype(dtype).type,
            moe_every=int(moe_every), n_experts=int(n_experts),
            moe_k=int(moe_k), quant=str(quant),
            n_kv_heads=int(n_kv_heads),
            rope=bool(rope), rope_base=float(rope_base),
        )
        self.seed = int(seed)
        self.mesh = mesh
        self.use_flash = resolve_flash(str(attention), mesh)
        # MoE capacity routing flattens the stacked batch into one token
        # stream (shared capacity, cumsum slot order), so co-batched rows
        # change each other's overflow — no cross-request coalescing
        self.batch_coupled = self.cfg.moe_every > 0

    def init_state(self, rng):
        if rng is None:
            rng = jax.random.key(self.seed)
        rng = jax.random.fold_in(rng, self.seed)
        params = lm_init(rng, self.cfg)
        params = load_lm_weights(params, self.weights_path)
        if self.cfg.quant == "int8":
            from seldon_core_tpu.ops.quant import quantize_lm_params

            params = quantize_lm_params(params)
        if self.mesh is not None:
            params = jax.device_put(params, param_shardings(self.mesh, params))
        return params

    def predict(self, state, X):
        tokens = X.astype(jnp.int32)
        return lm_apply(
            state, tokens, self.cfg, self.mesh,
            use_flash=self.use_flash,
        )
