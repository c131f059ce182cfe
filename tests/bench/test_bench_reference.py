"""The plain reference (bench/archs/dense_gelu/reference.py) against the program at a
tiny size on the CPU: the dense forward, and prefill followed by a decode
round through the paged pool."""

from types import SimpleNamespace

import bench_paths
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from lib import children, sample
from lib.manifest import arch_module
from lib.verdict import judge

reference = arch_module(bench_paths.BENCH, {"arch": "dense_gelu"},
                        "reference")

# configuration documents: the published keys the reference reads
SIZES = [
    dict(vocab_size=97, hidden_size=64, num_attention_heads=4,
         num_key_value_heads=2, num_hidden_layers=2, intermediate_size=160,
         rope_theta=999999.44),
    dict(vocab_size=61, hidden_size=96, num_attention_heads=6,
         num_key_value_heads=2, num_hidden_layers=3, intermediate_size=128,
         rope_theta=999999.44),
]


def build(sz, dtype=jnp.float32):
    """The program's own config and weights at a configuration's sizes;
    the reference gets the configuration document, never ``cfg``."""
    from seldon_core_tpu.models.transformer import LMConfig, lm_init

    cfg = LMConfig(
        dtype=dtype, rope=True, rope_base=sz["rope_theta"],
        vocab=sz["vocab_size"], d_model=sz["hidden_size"],
        n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"],
        n_layers=sz["num_hidden_layers"], d_ff=sz["intermediate_size"])
    return cfg, lm_init(jax.random.key(3), cfg)


def everywhere(toks):
    """``at`` for every position of every row: the old contract's logits."""
    return jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)


@pytest.mark.parametrize("sz", SIZES, ids=["gqa2", "gqa3"])
def test_reference_equals_the_programs_dense_forward(sz):
    from seldon_core_tpu.models.transformer import lm_apply

    cfg, params = build(sz)
    toks = jax.random.randint(jax.random.key(1), (2, 19), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lm_apply(params, toks, cfg))
    got = np.asarray(reference.forward(params, toks, sz, everywhere(toks)))
    # float32 against float32: only the order of additions differs
    assert np.abs(got - want).max() < 2e-4 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("sz", SIZES, ids=["gqa2", "gqa3"])
def test_the_judged_positions_logits_are_the_full_passs_gathered(sz):
    """``forward(..., at)`` norms and unembeds the gathered positions only;
    it gives exactly what gathering [B, S, V] afterwards gave (the old
    contract), repeated and out-of-order positions included."""
    cfg, params = build(sz)
    toks = jax.random.randint(jax.random.key(2), (3, 23), 0, cfg.vocab)
    at = jnp.asarray([[4, 5, 6, 7, 22], [0, 0, 21, 3, 2], [18, 19, 20, 21, 22]])
    whole = np.asarray(reference.forward(params, toks, sz, everywhere(toks)))
    got = np.asarray(reference.forward(params, toks, sz, at))
    assert got.shape == (3, 5, cfg.vocab)
    assert np.array_equal(got, np.take_along_axis(
        whole, np.asarray(at)[..., None], axis=1))
    # what a row holds is asked of the architecture: the judged logits,
    # not [S, V], and the scores grow with the square of the length
    assert (reference.row_bytes(sz, 64, 9) - reference.row_bytes(sz, 64, 1)
            == 8 * 4 * cfg.vocab)
    assert reference.row_bytes(sz, 4096, 9) > 3 * reference.row_bytes(
        sz, 2048, 9)


# the judged batch of a tiny deployment: four slots, chunks of 16, blocks
# of 16: rows of one, two and three chunks (lib/sample.py)
DEP = dict(slots=4, span=4, block_size=16, prefill_chunk=16, pool_blocks=16)
PLAN = sample.plan([5, 13, 21, 30, 41], DEP, 64)


def unit_of(cfg):
    return SimpleNamespace(cfg=cfg, temperature=0.0, top_k=0, top_p=0.0,
                           eos_token=-1)


@pytest.mark.parametrize("sz", SIZES, ids=["gqa2", "gqa3"])
def test_paged_prefill_then_decode_round_agree_with_the_reference(sz):
    """The numerics child's own loop (chunk by chunk, then one decode
    round over all rows) against the reference in float32: only the order
    of additions differs, in every row, whatever its number of chunks."""
    cfg, params = build(sz)
    assert PLAN["lens"] == [5, 13, 30, 41] and PLAN["chunks"] == [1, 3]
    prompts = children.sample_tokens(PLAN["lens"], cfg.vocab, 0)
    prog = children.run_program(unit_of(cfg), params, DEP, prompts)
    ref = children.run_reference(reference, params, sz, prompts,
                                 prog["first"], prog["tokens"],
                                 DEP["block_size"])
    assert ref.shape == (4, 1 + DEP["span"], cfg.vocab)
    rows = children.by_row(ref, prog["logits"], prog["tokens"])
    assert max(rows["prefill_err"]) < 1e-3
    assert max(rows["decode_margin"]) < 1e-3   # each step chose the best
    # the grouped, right-padded passes give what one pass a row gives
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, prog["first"][i:i + 1],
                              prog["tokens"][i, :-1]])
        alone = np.asarray(reference.forward(
            params, jnp.asarray(seq[None]), sz,
            everywhere(seq[None])))[0]
        assert np.abs(alone[len(p) - 1:] - ref[i]).max() < 1e-4


@pytest.mark.parametrize("bits, ok, share", [(8, True, 0.0), (3, False, 1.0)],
                         ids=["bf16-keeps-8-bits", "fp8-e4m3-keeps-3"])
def test_a_lower_precision_than_bf16_would_fail_the_chip_tolerance(
        bits, ok, share):
    """The chip tolerance is 0.1 x the logits' rms (configuration files,
    ``numerics.tolerance_rms``), held by every judged row.  At a tiny
    size, the reference on rounded weights in the program's place (the
    control, as tools/limits.py reads it on the chip): bf16's 8 bits
    stay well inside it in every row, 4 significant bits are over it in
    every row."""
    cfg, params = build(SIZES[0])
    prompts = children.sample_tokens(PLAN["lens"], cfg.vocab, 2)
    first = np.zeros((4,), np.int32)
    tokens = np.ones((4, DEP["span"]), np.int32)

    def rounded(a):
        if a.ndim < 2:
            return a
        m, e = np.frexp(np.asarray(a, np.float32))
        return jnp.asarray(np.ldexp(np.round(m * 2 ** bits) / 2 ** bits, e),
                           jnp.float32)

    ref = children.run_reference(reference, params, SIZES[0], prompts,
                                 first, tokens, DEP["block_size"])
    got = children.run_reference(reference, jax.tree.map(rounded, params),
                                 SIZES[0], prompts, first, tokens,
                                 DEP["block_size"])
    rows = children.by_row(ref, got[:, 0], got[:, 1:].argmax(-1))
    v = judge(rows["prefill_err"], rows["decode_margin"],
              {"tolerance_rms": 0.1}, float(np.mean(rows["rms"])))
    assert v["ok"] is ok and v["prefill"]["share"] == share, v


def test_the_fp8_control_rounds_every_matrix_to_e4m3_with_one_scale():
    """``children.fp8_rounded``: 4 significant bits, nothing over 448
    scales, vectors untouched, the input tree emptied as it goes."""
    _, params = build(SIZES[0], jnp.bfloat16)
    want = {k: jax.tree.map(np.asarray, v) for k, v in params.items()}
    out = children.fp8_rounded(params)
    assert params == {} and sorted(out) == sorted(want)
    for key in ("wqkv", "w2"):
        a = np.asarray(out["l0"][key], np.float32)
        w = np.asarray(want["l0"][key], np.float32)
        assert a.dtype == w.dtype and a.shape == w.shape
        m, _ = np.frexp(a)
        assert np.array_equal(m * 16, np.round(m * 16))   # 4 bits
        big = np.abs(w) > np.abs(w).max() / 16
        assert np.abs(a - w)[big].max() <= np.abs(w[big]).max() / 16
        assert 0 < np.abs(a - w).max()
    assert np.array_equal(np.asarray(out["l0"]["ln1"]), want["l0"]["ln1"])
