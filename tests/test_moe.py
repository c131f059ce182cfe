"""MoE expert parallelism (ep axis): routing invariants, dense equivalence,
sharded-vs-unsharded numerics, and gradient flow through the router."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.parallel.mesh import build_mesh
from seldon_core_tpu.parallel.moe import (
    MoEConfig,
    moe_apply,
    moe_init,
    moe_param_shardings,
)


def _cfg(**kw):
    base = dict(d_model=16, d_ff=32, n_experts=4, k=2, capacity_factor=2.0,
                dtype=jnp.float32)
    base.update(kw)
    return MoEConfig(**base)


def test_single_expert_equals_dense_ffn():
    cfg = _cfg(n_experts=1, k=1, capacity_factor=8.0)
    params = moe_init(jax.random.key(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 16)), jnp.float32)
    y, aux = moe_apply(params, x, cfg)
    expect = jax.nn.gelu(x @ params["w1"][0]) @ params["w2"][0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect), atol=1e-5)
    assert float(aux["overflow"]) == pytest.approx(0.0, abs=1e-6)


def test_topk_combine_normalised_and_capacity_respected():
    cfg = _cfg()
    params = moe_init(jax.random.key(1), cfg)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 10, 16)),
                    jnp.float32)
    y, aux = moe_apply(params, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert 0.0 <= float(aux["overflow"]) <= 1.0
    # balanced-router lower bound: lb_loss >= 1 (equality iff uniform)
    assert float(aux["lb_loss"]) >= 0.99


def test_zero_capacity_overflow_passes_through():
    cfg = _cfg(capacity_factor=1e-9)  # capacity clamps to 1 slot per expert
    params = moe_init(jax.random.key(2), cfg)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(64, 16)),
                    jnp.float32)
    y, aux = moe_apply(params, x, cfg)
    assert float(aux["overflow"]) > 0.0
    # with T=64 tokens and 4 experts x 1 slot, most tokens pass through
    same = np.isclose(np.asarray(y), np.asarray(x), atol=1e-6).all(axis=-1)
    assert same.sum() >= 48


def test_sharded_matches_unsharded(devices8):
    cfg = _cfg(n_experts=8, k=2, capacity_factor=2.0)
    mesh = build_mesh({"ep": 8}, devices=devices8)
    params = moe_init(jax.random.key(3), cfg)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(32, 16)),
                    jnp.float32)
    y_ref, aux_ref = moe_apply(params, x, cfg)

    sharded = jax.device_put(params, moe_param_shardings(mesh, params))
    y_sh, aux_sh = jax.jit(
        lambda p, v: moe_apply(p, v, cfg, mesh=mesh)
    )(sharded, x)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    assert float(aux_sh["lb_loss"]) == pytest.approx(float(aux_ref["lb_loss"]),
                                                     abs=1e-5)


def test_gradients_reach_experts_and_router():
    cfg = _cfg()
    params = moe_init(jax.random.key(4), cfg)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 16)),
                    jnp.float32)

    def loss(p):
        y, aux = moe_apply(p, x, cfg)
        return jnp.sum(y * y) + 0.01 * aux["lb_loss"]

    g = jax.grad(loss)(params)
    assert float(jnp.abs(g["w1"]).sum()) > 0
    assert float(jnp.abs(g["w2"]).sum()) > 0
    assert float(jnp.abs(g["wg"]).sum()) > 0  # via combine weights + lb loss


def test_switch_k1_router_gradient_flows_through_task_loss():
    """k=1 must keep the gate scale on the output (no renorm) so the router
    learns from the task loss, not just the aux loss."""
    cfg = _cfg(k=1)
    params = moe_init(jax.random.key(5), cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(16, 16)),
                    jnp.float32)

    def task_loss(p):
        y, _ = moe_apply(p, x, cfg)
        return jnp.sum(y * y)  # no lb term: gradient must come via combine

    g = jax.grad(task_loss)(params)
    assert float(jnp.abs(g["wg"]).sum()) > 1e-3


def test_k_greater_than_experts_rejected():
    cfg = _cfg(n_experts=2, k=3)
    params = moe_init(jax.random.key(6), cfg)
    x = jnp.zeros((4, 16), jnp.float32)
    with pytest.raises(ValueError, match="n_experts"):
        moe_apply(params, x, cfg)


# -- the dropless layer's fused call (parallel/moe.py _experts_fused) ---------

from seldon_core_tpu.models.transformer import LMConfig  # noqa: E402
from seldon_core_tpu.parallel import moe  # noqa: E402
from seldon_core_tpu.parallel.moe import (  # noqa: E402
    dropless_init,
    moe_dropless,
)

_SILU = dict(d_model=128, n_heads=4, d_expert=128, n_experts=8, moe_k=2)
_RELU2 = dict(_SILU, expert_act="relu2", router="sigmoid_bias",
              router_scale=2.5, d_shared=64)


def _all_on(lp, cfg, expert):
    """The router with every token's first pick on ``expert``."""
    router = lp["router"].at[:, expert].set(0.0).at[0, expert].set(50.0)
    return {**lp, "router": router}, lambda h: h.at[..., 0].set(1.0)


def _nobody_on(lp, cfg, expert):
    """... and with ``expert`` never chosen."""
    router = lp["router"].at[:, expert].set(0.0).at[0, expert].set(-50.0)
    return {**lp, "router": router}, lambda h: h.at[..., 0].set(1.0)


#: name -> (configuration, [B, W], pad positions, what is done to the router,
#: what the case must show of the picks: sizes [held] -> bool)
FUSED_CASES = {
    # the three configurations' forms at toy widths
    "silu-gate-up": (_SILU, (2, 7), [(1, 4), (1, 5), (1, 6)], None,
                     lambda sizes, M: True),
    "relu2-over-a-transposed-up": (_RELU2, (2, 7), [], None,
                                   lambda sizes, M: True),
    "a-width-of-29x64": (dict(_RELU2, d_expert=1856, n_experts=4), (1, 6),
                         [], None, lambda sizes, M: True),
    "a-group-without-rows": (_SILU, (2, 7), [], (_nobody_on, 3),
                             lambda sizes, M: sizes[3] == 0 < sizes[4]),
    "every-pick-on-one-expert": (dict(_SILU, moe_k=1), (3, 5), [],
                                 (_all_on, 5),
                                 lambda sizes, M: sizes[5] == M),
    # a pad's picks sort behind every group: nothing defined there
    "pads-behind-every-group": (_SILU, (2, 8), [(0, 6), (0, 7), (1, 2),
                                                (1, 3), (1, 4)], None,
                                lambda sizes, M: sizes.sum() == M - 10),
    # ... and the picks of experts held elsewhere
    "picks-of-absent-experts": (dict(_RELU2, experts_held=3,
                                     experts_first=2), (2, 7), [(1, 6)],
                                None, lambda sizes, M: 0 < sizes.sum() < M),
    # 5 tokens x 3 picks: 15 rows in a tile of 16
    "m-no-multiple-of-the-row-tile": (dict(_SILU, moe_k=3), (1, 5), [],
                                      None, lambda sizes, M: M % 16 == 15),
    # 160 picks over 8 experts in tiles of 128: the first tile ends inside
    # a group, with at least two groups whole before it
    "a-row-tile-straddling-three-groups": (
        dict(_SILU, moe_k=4), (4, 10), [], None,
        lambda sizes, M: (np.searchsorted(np.cumsum(sizes), 128) >= 2
                          and 128 not in np.cumsum(sizes))),
}


@pytest.mark.parametrize("case", list(FUSED_CASES))
def test_the_fused_expert_call_equals_the_two_grouped_matmuls(case,
                                                              monkeypatch):
    """``moe_dropless`` with an expert's whole feed-forward as ONE Pallas
    call (``impl="fused_interpret"``: the kernel the chip runs, in
    interpret mode) against the layer over ``jax.lax.ragged_dot``, in
    float32: the real positions within 1e-5 of the oracle's rms, a pad
    position zero, the experts read (and the picks that fell on held
    experts) equal."""
    kw, (B, W), pads, tweak, shows = FUSED_CASES[case]
    cfg = LMConfig(**{"vocab": 96, "dtype": jnp.float32, **kw})
    lp = dropless_init(jax.random.key(4), cfg)
    h = jax.random.normal(jax.random.key(5), (B, W, cfg.d_model))
    if tweak:
        lp, fix = tweak[0](lp, cfg, tweak[1])
        h = fix(h)
    valid = np.ones((B, W), bool)
    for at in pads:
        valid[at] = False
    seen = []
    real = moe._experts_fused

    def spy(xs, w_up, w_down, sizes, **how):
        seen.append((np.asarray(sizes), xs.shape[0]))
        return real(xs, w_up, w_down, sizes, **how)

    monkeypatch.setattr(moe, "_experts_fused", spy)
    got, read = moe_dropless(lp, h, jnp.asarray(valid), cfg,
                             impl="fused_interpret")
    want, counted = moe_dropless(lp, h, jnp.asarray(valid), cfg,
                                 impl="ragged_dot")
    (sizes, M), = seen
    assert shows(sizes, M), (sizes, M)
    np.testing.assert_array_equal(np.asarray(read), np.asarray(counted))
    got, want = np.asarray(got), np.asarray(want)
    rms = np.sqrt(np.mean(want[valid] ** 2))
    assert np.abs(got - want)[valid].max() < 1e-5 * rms
    assert not got[~valid].any()


TPU = dict(backend="tpu", dtype=jnp.bfloat16, mesh=None, d_model=2048,
           d_expert=768, gated=True)


@pytest.mark.parametrize("seen, answer", [
    # the three configurations' widths on one chip
    (TPU, True),
    (dict(TPU, d_expert=1792), True),
    (dict(TPU, d_model=2688, d_expert=1856, gated=False), True),
    (dict(TPU, dtype=jnp.float32), True),
    # today's path: the CPU, a mesh, a dtype or a width it does not tile
    (dict(TPU, backend="cpu"), False),
    (dict(TPU, mesh="any"), False),
    (dict(TPU, dtype=jnp.float16), False),
    (dict(TPU, dtype=jnp.int8), False),
    (dict(TPU, d_expert=1856), False),      # the halves split in a register
    (dict(TPU, d_model=2000), False),
    (dict(TPU, d_model=8192, d_expert=4096), False),    # two experts: 403 MB
], ids=lambda v: "-".join(f"{k}={getattr(x, '__name__', x)}"
                          for k, x in v.items() if TPU[k] is not x)
   if isinstance(v, dict) else str(v))
def test_who_takes_the_fused_expert_call(seen, answer):
    """``fused_supported`` over what a caller observes, and what asks it:
    ``generate.experts_fused`` (here on the CPU: no) for a generator with
    and without expert layers."""
    from seldon_core_tpu.models import generate as G

    assert moe.fused_supported(**seen) is answer
    cfg = LMConfig(vocab=96, d_model=seen["d_model"], n_heads=4,
                   d_expert=seen["d_expert"], n_experts=8, moe_k=2,
                   expert_act="silu" if seen["gated"] else "relu2")
    assert G.experts_fused(cfg, seen["mesh"], seen["dtype"]) is False
    assert G.experts_fused(LMConfig(vocab=96, d_model=32, n_heads=4)) is False
