"""Speculative decoding: greedy-exactness vs vanilla target decoding (the
defining invariant) and target-pass savings."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.generate import generate
from seldon_core_tpu.models.speculative import speculative_generate
from seldon_core_tpu.models.transformer import LMConfig, lm_init

TARGET = LMConfig(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                  dtype=jnp.float32)
DRAFT = LMConfig(vocab=48, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                 dtype=jnp.float32)


def test_speculative_equals_vanilla_greedy():
    tp = lm_init(jax.random.key(0), TARGET)
    dp = lm_init(jax.random.key(1), DRAFT)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, size=(1, 6)), jnp.int32
    )
    ref = np.asarray(generate(tp, prompt, TARGET, max_new_tokens=24))
    got, rounds = jax.jit(
        lambda t, d, p: speculative_generate(t, d, p, TARGET, DRAFT,
                                             max_new_tokens=24, k=4)
    )(tp, dp, prompt)
    np.testing.assert_array_equal(np.asarray(got), ref)
    assert 1 <= int(rounds[0]) <= 24


def test_speculative_static_lane_runs_the_paged_round(monkeypatch):
    """speculative_generate drives paged_spec_round — the program the
    scheduler's speculative mode dispatches — over two private pools, and
    still equals vanilla greedy for every row of a batch at different
    acceptance; a bound on the rounds leaves zero-padded tails."""
    import seldon_core_tpu.models.speculative as spec_mod

    traced = []
    inner = spec_mod.paged_spec_round

    def spy(*a, **kw):
        traced.append(kw["k"])
        return inner(*a, **kw)

    monkeypatch.setattr(spec_mod, "paged_spec_round", spy)
    tp = lm_init(jax.random.key(0), TARGET)
    dp = lm_init(jax.random.key(1), DRAFT)
    prompts = jnp.asarray(
        np.random.default_rng(3).integers(0, 48, size=(3, 6)), jnp.int32
    )
    ref = np.asarray(generate(tp, prompts, TARGET, max_new_tokens=12))
    got, rounds = speculative_generate(tp, dp, prompts, TARGET, DRAFT,
                                       max_new_tokens=12, k=3)
    assert traced == [3]  # traced once, into the while_loop's body
    np.testing.assert_array_equal(np.asarray(got), ref)
    assert ((1 <= np.asarray(rounds)) & (np.asarray(rounds) <= 11)).all()
    capped, r2 = speculative_generate(tp, dp, prompts, TARGET, DRAFT,
                                      max_new_tokens=12, k=3, max_rounds=2)
    capped = np.asarray(capped)
    assert (np.asarray(r2) == 2).all()
    for b in range(3):
        n = int(np.flatnonzero(capped[b] != ref[b])[0]) \
            if (capped[b] != ref[b]).any() else 12
        assert 3 <= n  # first token + two rounds of at least one each
        assert (capped[b, n:] == 0).all() or n == 12


def test_speculative_self_draft_max_acceptance():
    """Draft == target: every proposal matches, so rounds ~ max_new/(k+1)."""
    tp = lm_init(jax.random.key(2), TARGET)
    prompt = jnp.zeros((1, 4), jnp.int32)
    got, rounds = speculative_generate(tp, tp, prompt, TARGET, TARGET,
                                       max_new_tokens=20, k=4)
    ref = np.asarray(generate(tp, prompt, TARGET, max_new_tokens=20))
    np.testing.assert_array_equal(np.asarray(got), ref)
    # ideal is ceil((20-1)/(4+1)) = 4 rounds; S=1 draft steps vs S=k+1
    # verify segments reduce in different orders, so a near-tie argmax may
    # occasionally flip — allow minimal slack, far below the 19 passes
    # vanilla decoding would need
    assert int(rounds[0]) <= 5, int(rounds[0])


@pytest.mark.slow  # heavyweight equivalence check: full-suite/CI-shard coverage; excluded from the tier-1 time budget
def test_speculative_batched_matches_single_rows():
    """The defining batched invariant: every row of a vmapped batch equals
    its own B=1 decode exactly (f32), with per-row round counts."""
    tp = lm_init(jax.random.key(0), TARGET)
    dp = lm_init(jax.random.key(1), DRAFT)
    prompts = jnp.asarray(
        np.random.default_rng(3).integers(0, 48, size=(3, 6)), jnp.int32
    )
    batched, rounds = jax.jit(
        lambda t, d, p: speculative_generate(t, d, p, TARGET, DRAFT,
                                             max_new_tokens=16, k=4)
    )(tp, dp, prompts)
    assert batched.shape == (3, 16)
    assert rounds.shape == (3,)
    for b in range(3):
        single, r1 = speculative_generate(
            tp, dp, prompts[b: b + 1], TARGET, DRAFT,
            max_new_tokens=16, k=4,
        )
        np.testing.assert_array_equal(
            np.asarray(batched[b]), np.asarray(single[0])
        )
        assert int(rounds[b]) == int(r1[0])


def test_speculative_unit_serves_through_engine():
    import asyncio
    import json

    from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
    from seldon_core_tpu.runtime.engine import EngineService

    spec = SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "s", "predictors": [{
            "name": "p",
            "graph": {"name": "g", "type": "MODEL"},
            "components": [{
                "name": "g", "runtime": "inprocess",
                "class_path": "SpeculativeGenerator",
                "parameters": [
                    {"name": "vocab", "value": "48", "type": "INT"},
                    {"name": "d_model", "value": "32", "type": "INT"},
                    {"name": "n_layers", "value": "2", "type": "INT"},
                    {"name": "d_ff", "value": "64", "type": "INT"},
                    {"name": "max_new_tokens", "value": "8", "type": "INT"},
                ],
            }],
        }]}
    })
    engine = EngineService(spec)
    # rows independent since the vmapped batch path: callers coalesce
    assert engine.batcher is not None

    from seldon_core_tpu.messages import SeldonMessage

    msg = SeldonMessage.from_json(json.dumps(
        {"data": {"ndarray": [[1, 2, 3, 4], [5, 6, 7, 8]]}}
    ))
    resp = asyncio.run(engine.predict(msg))
    y = np.asarray(resp.data.array)
    assert y.shape == (2, 8)
    assert ((0 <= y) & (y < 48)).all()


def test_config_divisibility_validated_at_load():
    from seldon_core_tpu.models.speculative import SpeculativeGenerator

    with pytest.raises(ValueError, match="divisible"):
        LMConfig(d_model=40, n_heads=12)
    # derived draft defaults stay valid even for awkward target shapes
    u = SpeculativeGenerator(vocab=48, d_model=48, n_heads=12, n_layers=2,
                             d_ff=64)
    assert u.draft_cfg.d_model % u.draft_cfg.n_heads == 0
