"""Speculative decoding — draft/verify generation, exact under greedy.

A small draft LM proposes ``k`` tokens with its own KV pool; the target LM
scores all ``k+1`` positions in ONE forward (one MXU pass instead of k+1
sequential decode steps); the longest prefix where the draft matched the
target's argmax is accepted plus one corrected token.  Greedy acceptance is
exact in exact arithmetic: the output equals vanilla greedy decoding of the
target token-for-token (pinned bit-exact by the f32 tests).  In low
precision an argmax near-tie can flip between the width-1 and width-(k+1)
forwards (different reduction orders), so bf16 outputs may diverge at tie
positions — same-quality tokens, not errors.  The target runs
~(accepted+1)x fewer sequential passes; acceptance rate tracks how well
the draft approximates the target (an unrelated random draft accepts ~0).

The round itself is ``models/generate.py paged_spec_round`` — the program
the scheduler's speculative mode dispatches (runtime/genserver.py
``_spec_round``).  This module is the static lane's driver for it: two
private pools (target and draft, identity tables), both prompts prefilled
with ``paged_forward``, then one SHARED batched ``lax.while_loop`` over
rounds under jit — every round ALL rows draft, ALL rows verify, and
acceptance is a masked per-row reduction; rows at different sequence
lengths share every MXU pass.  Rejected candidates' K/V are stale slots
past a row's ``n_valid`` that the next round overwrites before anything
attends them, so a row needs ``S + max_new + k + 1`` positions, not a slot
per candidate.  Rows that finish early keep riding the loop inactive
(their writes go to the scratch block, ``gained`` = 0).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from seldon_core_tpu.graph.units import Unit, register_unit
from seldon_core_tpu.models.generate import (
    paged_forward_jit,
    paged_spec_round,
    private_pool,
    sanitize_prompt,
)
from seldon_core_tpu.models.transformer import LMConfig, lm_init

__all__ = ["speculative_generate", "SpeculativeGenerator"]


def speculative_generate(
    target_params,
    draft_params,
    prompt,
    target_cfg: LMConfig,
    draft_cfg: LMConfig,
    max_new_tokens: int = 32,
    k: int = 4,
    max_rounds: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """prompt [B, S] int32 -> (tokens [B, max_new_tokens] int32,
    rounds int32 [B] — verify passes used per row; ~max_new/rounds tokens
    per target pass, vs exactly 1 for vanilla decoding).

    Greedy only; per-row output equals vanilla greedy decoding of the
    target over its confirmed prefix.  One SHARED batched round loop over
    ``paged_spec_round`` — see the module docstring.

    ``max_rounds > 0`` bounds the loop (it sizes nothing): a draft that
    tracks the target at mean acceptance ``a`` finishes in about
    ``max_new / (a*k + 1)`` rounds.  Rows still decoding when the rounds
    run out get zero-padded tails (``rounds`` returned == the bound for
    such rows — observable), so pick the bound from measured acceptance,
    not hope.  0 (default) allows the worst case, one token a round.

    Telemetry: eager calls record the per-request mean acceptance ratio
    into the flight recorder (seldon_tpu_speculative_accept_ratio);
    traced calls skip (trace-time constants are not serving data)."""
    if target_cfg.kv_quant == "int8" or draft_cfg.kv_quant == "int8":
        raise NotImplementedError(
            "speculative decoding runs float KV caches; quantize weights "
            "(quant='int8'), not the cache")
    B, S = prompt.shape
    W = k + 1
    R = max(max_new_tokens - 1, 1)  # worst case: 1 token gained per round
    if max_rounds > 0:
        R = min(R, int(max_rounds))
    # a live row starts a round at n_valid <= S + max_new - 2 and writes W
    # positions from there
    t_pool, tables = private_pool(target_cfg, B, S + max_new_tokens + W)
    d_pool, _ = private_pool(draft_cfg, B, S + max_new_tokens + W)

    # prefill both models on the prompt; last-position argmax = first token
    start = jnp.zeros((B,), jnp.int32)
    width = jnp.full((B,), S, jnp.int32)
    t_logits, t_pool = paged_forward_jit(
        target_params, prompt, t_pool, tables, start, width,
        cfg=target_cfg, last_only=True)
    _, d_pool = paged_forward_jit(
        draft_params, prompt, d_pool, tables, start, width,
        cfg=draft_cfg, last_only=True)
    first = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B]
    if max_new_tokens == 1:
        return first[:, None], jnp.zeros((B,), jnp.int32)

    def cond(c):
        r, n = c[0], c[1]
        return (r < R) & jnp.any(n < max_new_tokens)

    def body(c):
        r, n, pending, out, rounds_used, t_pool, d_pool = c
        active = n < max_new_tokens
        # ``pending`` is each row's last emitted token, not yet in a pool:
        # the pools hold the prompt and the n - 1 tokens before it
        new_toks, gained, corrected, t_pool, d_pool = paged_spec_round(
            target_params, draft_params, t_pool, d_pool, tables, tables,
            pending, S + n - 1, active, target_cfg, draft_cfg, k=k)
        # row b emits new_toks[b, :gained[b]] at out[b, n[b]:]; zeros past
        # them, which the next round overwrites (or the final cut drops)
        emit = jnp.where(jnp.arange(W)[None, :] < gained[:, None],
                         new_toks, 0)
        out = jax.vmap(
            lambda o, t, i: jax.lax.dynamic_update_slice(o, t, (i,))
        )(out, emit, n)
        pending = jnp.where(active, corrected, pending)
        return (r + 1, n + gained, pending, out,
                rounds_used + active.astype(jnp.int32), t_pool, d_pool)

    # a row's last live round starts at n <= max_new - 1 and writes W slots
    out = jnp.zeros((B, max_new_tokens + W), jnp.int32).at[:, 0].set(first)
    (_, n, _, out, rounds_used, _, _) = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.ones((B,), jnp.int32), first, out,
         jnp.zeros((B,), jnp.int32), t_pool, d_pool),
    )
    toks_out = out[:, :max_new_tokens]
    if not isinstance(rounds_used, jax.core.Tracer):
        # eager execution: per-request acceptance telemetry.  gained
        # tokens per round = accepted drafts + 1 corrected, so accepted
        # fraction = (emitted_after_first - rounds) / (rounds * k)
        import numpy as _np

        from seldon_core_tpu.utils.telemetry import RECORDER

        rounds = _np.asarray(rounds_used, dtype=_np.float64)
        emitted = _np.minimum(
            _np.asarray(n, dtype=_np.float64), float(max_new_tokens)) - 1.0
        with _np.errstate(divide="ignore", invalid="ignore"):
            ratio = _np.where(
                rounds > 0, (emitted - rounds) / (rounds * max(k, 1)), 0.0)
        RECORDER.observe_accept_ratio(
            float(_np.clip(ratio, 0.0, 1.0).mean()))
    return toks_out, rounds_used


@register_unit("SpeculativeGenerator")
class SpeculativeGenerator(Unit):
    """Serving unit: speculative draft/verify generation over the standard
    data plane.  Target and draft dimensions are graph parameters (draft_*
    defaults to a quarter-size model).  Concurrent callers coalesce into
    ONE shared batched round loop (speculative_generate); per-row outputs
    equal the single-row outputs, so coalescing never changes an answer.

    ``max_rounds`` bounds that loop: e.g. a draft measured at ~50%
    acceptance finishes ``max_new_tokens=256, k=4`` in ~256/(0.5*4+1) = 86
    rounds, so ``max_rounds=110`` leaves ~25% slack.  Rows that exhaust the
    bound get zero-padded tails — watch seldon_tpu_speculative_accept_ratio
    and raise it when the measured acceptance drifts below the estimate."""

    pure = True
    # per-row outputs are independent of co-batched rows (pinned by
    # tests), so concurrent callers coalesce like any other unit

    def __init__(self, vocab: int = 256, d_model: int = 128, n_heads: int = 4,
                 n_layers: int = 2, d_ff: int = 512,
                 draft_d_model: int = 0, draft_n_heads: int = 0,
                 draft_n_layers: int = 0, draft_d_ff: int = 0,
                 seed: int = 0, max_new_tokens: int = 32, k: int = 4,
                 max_rounds: int = 0,
                 dtype: str = "float32", rope: bool = True,
                 rope_base: float = 10000.0):
        dt = jnp.dtype(dtype).type
        rope = bool(rope)
        self.target_cfg = LMConfig(
            vocab=int(vocab), d_model=int(d_model), n_heads=int(n_heads),
            n_layers=int(n_layers), d_ff=int(d_ff), dtype=dt,
            rope=rope, rope_base=float(rope_base),
        )
        dd = int(draft_d_model) or max(16, int(d_model) // 4)
        dh = int(draft_n_heads) or max(2, int(n_heads) // 2)
        # derived defaults must keep hd integral — and EVEN when RoPE is
        # on (rotation pairs dimensions)
        while dd % dh != 0 or (rope and (dd // dh) % 2 != 0):
            if dh <= 1:
                raise ValueError(
                    f"cannot derive a draft head count for d_model={dd} "
                    f"with rope={rope}; set draft_n_heads explicitly"
                )
            dh -= 1
        self.draft_cfg = LMConfig(
            vocab=int(vocab), d_model=dd, n_heads=dh,
            n_layers=int(draft_n_layers) or max(1, int(n_layers) // 2),
            d_ff=int(draft_d_ff) or max(32, int(d_ff) // 4),
            dtype=dt, rope=rope, rope_base=float(rope_base),
        )
        self.seed = int(seed)
        self.max_new_tokens = int(max_new_tokens)
        self.k = int(k)
        self.max_rounds = int(max_rounds)

    def init_state(self, rng):
        if rng is None:
            rng = jax.random.key(self.seed)
        rng = jax.random.fold_in(rng, self.seed)
        kt, kd = jax.random.split(rng)
        return {"target": lm_init(kt, self.target_cfg),
                "draft": lm_init(kd, self.draft_cfg)}

    def continuous_spec(self, state):
        """Scheduler contract for the continuous-batching lane
        (runtime/genserver.py): the draft params/config put the scheduler
        in SPECULATIVE mode — the same paged_spec_round, over the
        scheduler's pools and composed with continuous admission.
        Greedy/float-KV only, matching speculative_generate's guards."""
        return {
            "params": state["target"],
            "cfg": self.target_cfg,
            "temperature": 0.0,
            "top_k": 0,
            "top_p": 0.0,
            "eos_token": -1,
            "max_new_tokens": self.max_new_tokens,
            "draft_params": state["draft"],
            "draft_cfg": self.draft_cfg,
            "spec_k": self.k,
            "seed": self.seed,
        }

    def predict(self, state, X):
        prompt = sanitize_prompt(X, self.target_cfg.vocab)
        toks, _rounds = speculative_generate(
            state["target"], state["draft"], prompt,
            self.target_cfg, self.draft_cfg,
            max_new_tokens=self.max_new_tokens, k=self.k,
            max_rounds=self.max_rounds,
        )
        return toks.astype(jnp.float32)
