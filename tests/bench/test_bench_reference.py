"""The plain reference (bench/archs/dense_gelu/reference.py) against the program at a
tiny size on the CPU: the dense forward, and prefill followed by a decode
round through the paged pool — and the numerics child's own functions
(lib/children.py): what they return where an architecture brings no driver
(the parent's arrays), and what they do with events that are not a row's
one teacher-forced pass."""

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
    ref = children.run_reference(reference, params, sz, prog["events"],
                                 DEP["block_size"])
    assert ref.shape == (4, 1 + DEP["span"], cfg.vocab)
    rows = children.by_row(prog["events"], ref, prog["logits"])
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


# what lib/children.py returned at the parent of PR 32 (29b9b78) for
# SIZES[0], weights from key 3, DEP / PLAN above and ``sample_tokens(...,
# 0)``: tokens and shapes held exactly, float32 logits to what another
# CPU's order of additions may move
PARENT = {
    "first": [43, 43, 56, 34],
    "tokens": [[43, 43, 43, 43], [43, 43, 43, 43], [9, 9, 9, 9],
               [48, 48, 48, 48]],
    "logits[:, :3]": [[-0.36242902278900146, -1.5170478820800781,
                       1.068111538887024],
                      [0.34990426898002625, 0.38032180070877075,
                       0.7300358414649963],
                      [-1.0639218091964722, 0.38758349418640137,
                       1.5035189390182495],
                      [1.404046893119812, -0.410702645778656,
                       0.31265899538993835]],
    "sum |logits|": 308.6826171875,
    "ref[:, ::4, 5]": [[0.9028128385543823, 0.5699101686477661],
                       [0.2137390673160553, -0.3859163224697113],
                       [-0.007575027644634247, 0.20626842975616455],
                       [0.47693008184432983, 0.3977731764316559]],
    "sum |ref|": 1550.856201171875,
    "rms": [1.0046844482421875, 1.1221235990524292, 0.94209223985672,
            0.959009051322937],
}


def test_with_no_driver_the_child_returns_what_the_parent_returned():
    """``dense_gelu`` brings no ``drive.py``: ``run_program`` takes the
    default round, a row is one event of 1 + span positions, and
    ``run_reference`` / ``by_row`` give the arrays of 29b9b78."""
    cfg, params = build(SIZES[0])
    prompts = children.sample_tokens(PLAN["lens"], cfg.vocab, 0)
    prog = children.run_program(unit_of(cfg), params, DEP, prompts)
    assert prog["first"].tolist() == PARENT["first"]
    assert prog["tokens"].tolist() == PARENT["tokens"]
    assert prog["first"].dtype == prog["tokens"].dtype == np.int32
    assert prog["logits"].shape == (4, cfg.vocab)
    near = dict(rel=0, abs=2e-5)
    assert prog["logits"][:, :3].tolist() == [
        pytest.approx(row, **near) for row in PARENT["logits[:, :3]"]]
    assert float(np.abs(prog["logits"]).sum()) == pytest.approx(
        PARENT["sum |logits|"], rel=1e-5)
    events = prog["events"]
    assert [e["row"] for e in events] == [0, 1, 2, 3]
    for e, p in zip(events, prompts):
        n = len(p)
        assert e["ids"].tolist() == (
            p.tolist() + [prog["first"][e["row"]]]
            + prog["tokens"][e["row"], :-1].tolist())
        assert e["at"].tolist() == list(range(n - 1, n + DEP["span"]))
        assert e["chose"].tolist() == [children.NOT_JUDGED] + prog["tokens"][
            e["row"]].tolist()
        assert e["prefill"] == 0
    ref = children.run_reference(reference, params, SIZES[0], events,
                                 DEP["block_size"])
    assert ref.shape == (4, 1 + DEP["span"], cfg.vocab)
    assert ref.dtype == np.float32
    assert ref[:, ::4, 5].tolist() == [
        pytest.approx(row, **near) for row in PARENT["ref[:, ::4, 5]"]]
    assert float(np.abs(ref).sum()) == pytest.approx(PARENT["sum |ref|"],
                                                     rel=1e-5)
    rows = children.by_row(events, ref, prog["logits"])
    assert rows["rms"] == pytest.approx(PARENT["rms"], rel=1e-5)
    assert rows["decode_margin"] == [0.0] * 4
    assert max(rows["prefill_err"]) < 2e-6
    # ... and by the old expression, on these very arrays, to the last bit
    step = ref[:, 1:]
    took = np.take_along_axis(step, prog["tokens"][..., None], -1)[..., 0]
    assert rows["decode_margin"] == (step.max(-1) - took).max(-1).tolist()
    assert rows["prefill_err"] == np.abs(
        ref[:, 0] - prog["logits"]).max(-1).tolist()


@pytest.mark.parametrize("sz", SIZES, ids=["gqa2", "gqa3"])
def test_the_dense_reference_does_not_read_the_lengths_it_is_told(sz):
    """A causal pass is unchanged before the pad, so ``dense_gelu`` takes
    ``lengths`` and ignores it: bit for bit what it gave without."""
    cfg, params = build(sz)
    toks = jax.random.randint(jax.random.key(4), (3, 32), 0, cfg.vocab)
    at = jnp.asarray([[4, 5, 6], [0, 20, 21], [29, 30, 31]])
    without = np.asarray(reference.forward(params, toks, sz, at))
    for lengths in ([32, 32, 32], [7, 22, 32], [1, 1, 1]):
        told = np.asarray(reference.forward(
            params, toks, sz, at, jnp.asarray(lengths, jnp.int32)))
        assert np.array_equal(told, without)


class Recording:
    """A reference that answers with the ids it was asked about: the logit
    of id v after position p of a row is ``tokens[p] + v / 1000``."""

    def __init__(self):
        self.calls = []

    def forward(self, params, tokens, config, at, lengths):
        self.calls.append((tokens.shape, np.asarray(at).tolist(),
                           np.asarray(lengths).tolist()))
        seen = np.take_along_axis(np.asarray(tokens), np.asarray(at), 1)
        return seen[..., None] + np.arange(config["vocab_size"]) / 1000.0

    def row_bytes(self, config, S, judged):
        return 4 * judged * config["vocab_size"]


def test_events_go_to_the_reference_as_rows_of_their_own_told_their_length():
    """Several events a row, of unequal lengths and unequal numbers of
    judged positions: grouped by padded length, ``at`` filled to the most
    an event asks for, every row's true length beside it."""
    events = [
        {"row": 0, "ids": np.arange(10, 19), "at": np.asarray([8]),
         "chose": np.asarray([-1]), "prefill": 0},
        {"row": 1, "ids": np.arange(30, 61), "at": np.asarray([30]),
         "chose": np.asarray([-1]), "prefill": 0},
        {"row": 0, "ids": np.arange(10, 22), "at": np.asarray([9, 11]),
         "chose": np.asarray([7, 3])},
        {"row": 1, "ids": np.arange(30, 66), "at": np.asarray([34, 33, 35]),
         "chose": np.asarray([1, 2, 5])},
    ]
    rec = Recording()
    ref = children.run_reference(rec, None, {"vocab_size": 8}, events, 16)
    assert ref.shape == (4, 3, 8)
    # the longest first: 36 -> 48, 31 -> 32, then 12 and 9 -> 16 together
    assert rec.calls == [
        ((1, 48), [[34, 33, 35]], [36]),
        ((1, 32), [[30, 0, 0]], [31]),
        ((2, 16), [[9, 11, 0], [8, 0, 0]], [12, 9])]
    assert ref[0, 0, 0] == 18 and ref[2, :2, 0].tolist() == [19, 21]
    assert ref[3, :, 0].tolist() == [64, 63, 65]
    pre = children.prefill_rows(events, ref)
    assert pre[:, 0].tolist() == [18, 60]
    # a row's margin is the worst over its judged (event, position) pairs:
    # the best id is 7 (+0.007), so id v lies (7 - v) / 1000 under it
    rows = children.by_row(events, ref, pre)
    assert rows["prefill_err"] == [0.0, 0.0]
    assert rows["decode_margin"] == pytest.approx([0.004, 0.006], abs=1e-5)
    # ids no answer may hold are out of the running for the best ...
    rows = children.by_row(events, ref, pre, reserved=(7, 6))
    assert rows["decode_margin"] == pytest.approx([0.002, 0.004], abs=1e-5)
    # ... another's choices at the same positions (the control) ...
    other = np.full((4, 3), 7)
    other[3] = [7, 0, 7]
    rows = children.by_row(events, ref, pre + 0.5, other)
    assert rows["decode_margin"] == pytest.approx([0.0, 0.007], abs=1e-5)
    assert rows["prefill_err"] == pytest.approx([0.5, 0.5])
    # ... and a NaN anywhere in a row's judged logits is that row's number
    ref[2, 1, 3] = np.nan
    rows = children.by_row(events, ref, pre)
    assert rows["decode_margin"][0] != rows["decode_margin"][0]
    assert rows["decode_margin"][1] == pytest.approx(0.006, abs=1e-5)
    assert children.reserved_emitted([[1, 7, 7], [6]], (7,)) == 2
    assert children.reserved_emitted(np.asarray([[1, 7], [6, 5]]), ()) == 0


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

    events = children.teacher_forced(prompts, first, tokens)
    ref = children.run_reference(reference, params, SIZES[0], events,
                                 DEP["block_size"])
    got = children.run_reference(reference, jax.tree.map(rounded, params),
                                 SIZES[0], events, DEP["block_size"])
    rows = children.by_row(events, ref, children.prefill_rows(events, got),
                           got.argmax(-1))
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
