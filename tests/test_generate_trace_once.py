"""A paged program traces its decoder block once, not once a layer:
``generate._paged_block`` is a ``jit`` of its own inside ``paged_forward``
and ``paged_decode_round``, so a program's lowered text holds ONE private
function of the block (at most three for a round of denoising passes) and
does not grow with depth -- and the programs are what they were: the same
tokens and logits, bit for bit, as with the plain body called layer by
layer, and a pool that comes back donated.  CPU, small sizes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import generate
from seldon_core_tpu.models.generate import (
    init_block_pool,
    paged_decode_round_jit,
    paged_forward_jit,
)
from seldon_core_tpu.models.transformer import LMConfig, lm_init

ROWS, CHUNK, NBLK, BS, SPAN = 2, 8, 4, 8, 4
ROUND = dict(span=SPAN, temperature=0.0, top_k=0, top_p=1.0, eos_token=-1)


def config(kind: str, layers: int) -> LMConfig:
    base = dict(vocab=96, d_model=32, n_heads=4, n_kv_heads=2,
                n_layers=layers, dtype=jnp.float32)
    if kind == "dense":
        return LMConfig(d_ff=64, **base)
    return LMConfig(head_dim=16, qk_norm=True, tie_embeddings=False,
                    d_expert=16, n_experts=8, moe_k=2, block_length=4,
                    denoising_steps=4, mask_id=90, **base)


def operands(cfg: LMConfig):
    """Parameters, a pool and a batch of two rows (a whole chunk and a
    short one) with tables of their own blocks."""
    params = lm_init(jax.random.key(3), cfg)
    pool = init_block_pool(cfg, 1 + ROWS * NBLK, BS)
    tables = 1 + np.arange(ROWS * NBLK, dtype=np.int32).reshape(ROWS, NBLK)
    tokens = np.random.default_rng(7).integers(
        0, 80, size=(ROWS, CHUNK)).astype(np.int32)
    width = np.asarray([CHUNK, CHUNK - 3], np.int32)
    return params, pool, jnp.asarray(tables), jnp.asarray(tokens), width


def forward(cfg, params, pool, tables, tokens, width, lower=False):
    fn = paged_forward_jit.lower if lower else paged_forward_jit
    return fn(params, tokens, pool, tables, np.zeros((ROWS,), np.int32),
              width, cfg=cfg, head=cfg.block_length == 1)


def round_(cfg, params, pool, tables, token, n_valid, lower=False,
           inplace=None):
    fn = paged_decode_round_jit.lower if lower else paged_decode_round_jit
    return fn(params, pool, tables, token, n_valid,
              np.ones((ROWS,), bool), np.zeros((ROWS,), bool),
              np.zeros((ROWS,), np.uint32), cfg, inplace=inplace, **ROUND)


def lowered(kind: str, layers: int):
    """The text of the two programs at ``layers`` layers, traced anew."""
    cfg = config(kind, layers)
    params, pool, tables, tokens, width = operands(cfg)
    jax.clear_caches()
    return (
        forward(cfg, params, pool, tables, tokens, width, True).as_text(),
        round_(cfg, params, pool, tables, tokens[:, 0], width, True
               ).as_text())


def block_functions(text: str) -> int:
    return len(set(re.findall(r"func\.func private @(_paged_block\w*)\(",
                              text)))


# the most private functions of the block a program may hold.  A round of
# denoising passes: a pass that writes nothing, the commit, the commit's last
# layer (``kv_only``).  Its prefill runs without the head, so nobody reads
# its last layer's hidden states and JAX prunes that one call's outputs: two.
@pytest.mark.parametrize("kind, most", [
    ("dense", {"forward": 1, "round": 1}),
    ("blocks", {"forward": 2, "round": 3})])
def test_a_programs_text_holds_the_block_once_whatever_its_depth(kind, most):
    shallow, deep = lowered(kind, 2), lowered(kind, 8)
    for name, two, eight in zip(("forward", "round"), shallow, deep):
        assert len(eight) < 1.5 * len(two), (name, len(two), len(eight))
        n = block_functions(eight)
        assert 1 <= n <= most[name], (name, n)
        # and every layer calls it
        assert len(re.findall(r"call @_paged_block", eight)) >= 8, name


def run(cfg, inplace=None):
    """A chunk's logits (or the experts read, where the prompt chooses no
    token) and the round after it: what the programs hand back."""
    params, pool, tables, tokens, width = operands(cfg)
    out, pool = forward(cfg, params, pool, tables, tokens, width)
    token = tokens[:, :cfg.block_length] if cfg.block_length > 1 else (
        jnp.argmax(out, axis=-1).astype(jnp.int32))
    toks, pool, *rest = round_(cfg, params, pool, tables, token,
                               jnp.asarray(width), inplace=inplace)
    return jax.tree_util.tree_map(np.asarray, (out, toks, pool, rest))


# ``interpret``: the round attends over the pool in place, as on the chip
@pytest.mark.parametrize("inplace", [None, "interpret"],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("kind", ["dense", "blocks"])
def test_the_programs_are_those_of_the_plain_body_layer_by_layer(
        kind, inplace, monkeypatch):
    cfg = config(kind, 3)
    jax.clear_caches()
    got = run(cfg, inplace)
    assert hasattr(generate._paged_block, "lower")      # a jit of its own
    monkeypatch.setattr(generate, "_paged_block",
                        generate._paged_block.__wrapped__)
    jax.clear_caches()
    try:
        want = run(cfg, inplace)
    finally:
        jax.clear_caches()      # the unrolled traces must not outlive this
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kind", ["dense", "blocks"])
def test_the_pool_comes_back_donated(kind):
    cfg = config(kind, 2)
    params, pool, tables, tokens, width = operands(cfg)
    given = jax.tree_util.tree_leaves(pool)
    out, pool2 = forward(cfg, params, pool, tables, tokens, width)
    assert all(x.is_deleted() for x in given)
    given = jax.tree_util.tree_leaves(pool2)
    token = tokens[:, :cfg.block_length] if cfg.block_length > 1 else (
        tokens[:, 0])
    _, pool3, *_ = round_(cfg, params, pool2, tables, token,
                          jnp.asarray(width))
    assert all(x.is_deleted() for x in given)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(pool3))
