"""The served path of a generator of power-retention layers (Brumby-14B-
Base's mechanism, bench/configs/brumby-14b.json: a float32 matrix state a
row, a KV head and a layer, kept at the row's first block's id, read and
rewritten by every token; a gated decay; gated-SiLU FFNs without experts;
an untied head) at a tiny size on the CPU, in float32: the recurrent form
(a decode step), the chunk form (a prefill chunk), the static lane and
``GenServer`` against the plain reference of bench/archs/brumby/, which is
the ATTENTION form and shares no code with them.

Tolerances: logits within 1e-4 of values of order 1 (both sides float32,
the reference at ``highest``; what differs is the order of the sums);
tokens exactly -- an argmax flips only on a tie of two float32 logits,
which these seeds do not have."""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import generate as G
from seldon_core_tpu.models.generate import (
    TransformerGenerator,
    generate,
    init_block_pool,
    paged_copy_block_jit,
    paged_decode_round_jit,
    paged_forward_jit,
    paged_spec_round,
    stream_chunks,
)
from seldon_core_tpu.models.transformer import LMConfig, lm_apply
from seldon_core_tpu.ops import retention as R
from seldon_core_tpu.runtime import genserver
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.utils.genperf import GENPERF
from seldon_core_tpu.utils.hotrecord import SPINE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(REPO, "bench", "archs", "brumby", "reference.py")
    spec = importlib.util.spec_from_file_location("brumby_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
LAYERS, KV, HD = 3, 2, 16
P = R.phi_width(HD)                 # 144 lanes, of which 136 hold a product


def config():
    """The configuration file's keys at a tiny size (what the reference
    reads) and the unit built from them as the deployment builds it."""
    doc = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=KV,
               head_dim=HD, num_hidden_layers=LAYERS, intermediate_size=48,
               rope_theta=1000000.0, rms_norm_eps=1e-6, vocab_size=96,
               retention_degree=2, retention_eps=1e-6)
    unit = TransformerGenerator(
        vocab=96, d_model=32, n_heads=4, n_kv_heads=KV, head_dim=HD,
        n_layers=LAYERS, layer_kinds="r" * LAYERS, dense_layers=LAYERS,
        d_ff=48, qk_norm=True, tie_embeddings=False, norm_eps=1e-6,
        rope_base=1000000.0, dtype="float32", seed=7)
    return doc, unit


@pytest.fixture(scope="module")
def model():
    doc, unit = config()
    return doc, unit, unit.init_state(None)["params"]


def prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lens]


def reference_logits(params, ids, doc):
    """The reference's logits after EVERY position of one row."""
    ids = np.asarray(ids, np.int32)[None]
    return np.asarray(REF.forward(
        params, jnp.asarray(ids), doc, jnp.arange(ids.shape[1])[None],
        jnp.asarray([ids.shape[1]]))[0])


def reference_answer(params, prompt, doc, max_new):
    """Greedy, every token from a whole forward pass of the reference over
    the row so far: no cache, no state."""
    seq = [int(t) for t in prompt]
    for _ in range(max_new):
        seq.append(int(reference_logits(params, seq, doc)[-1].argmax()))
    return np.asarray(seq[len(prompt):], np.int32)


# a block a row, as such a generator is deployed: block 0 is the scratch
# entry, rows 0 and 1 hold blocks 1 and 2 of 32 positions
TABLES = jnp.asarray([[1], [2]], jnp.int32)
BS, BLOCKS = 32, 4


def chunked(unit, params, rows, chunk, tables, pool=None, fused=None):
    """``rows`` prefilled ``chunk`` tokens a call as the scheduler does:
    rows of unequal length in one call, the shorter ones right-padded, a
    row that is through riding along with width 0.  Returns each row's
    logits from the call that consumed its last token, and the pool.
    ``fused`` is ``paged_forward``'s: None lets it decide (the CPU takes
    ``jax.numpy`` row by row), "interpret" runs the kernels of
    ops/retention.py in Pallas interpret mode."""
    if pool is None:
        pool = init_block_pool(unit.cfg, BLOCKS, BS)
    lens = [len(r) for r in rows]
    out = [None] * len(rows)
    for lo in range(0, max(lens), chunk):
        toks = np.zeros((len(rows), chunk), np.int32)
        width = np.zeros((len(rows),), np.int32)
        start = np.zeros((len(rows),), np.int32)
        for i, r in enumerate(rows):
            w = max(0, min(chunk, lens[i] - lo))
            toks[i, :w] = r[lo:lo + w]
            width[i], start[i] = w, min(lo, lens[i])
        logits, pool = paged_forward_jit(
            params, jnp.asarray(toks), pool, tables, jnp.asarray(start),
            jnp.asarray(width), cfg=unit.cfg, last_only=True, fused=fused)
        for i in range(len(rows)):
            if width[i] and lo + width[i] == lens[i]:
                out[i] = np.asarray(logits[i])
    return np.stack(out), pool


def decode(unit, params, pool, tables, token, n_valid, active, span,
           inplace=None):
    B = len(token)
    return paged_decode_round_jit(
        params, pool, tables, jnp.asarray(token, jnp.int32),
        jnp.asarray(n_valid, jnp.int32), jnp.asarray(active, bool),
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.uint32), unit.cfg,
        span=span, temperature=0.0, top_k=0, top_p=0.0, eos_token=-1,
        inplace=inplace)


# a decode round's step and a prefill call's chunk row by row in jax.numpy
# (what the CPU decides for itself) and through the kernels of
# ops/retention.py in Pallas interpret mode (what a TPU decides, as far as
# the CPU can run it): the one answer goes to both programs
BOTH_STEPS = pytest.mark.parametrize(
    "inplace", [None, "interpret"], ids=["step", "kernel"])


# -- the expansion and the parameters ----------------------------------------


def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k():
    rng = np.random.default_rng(0)
    for d in (2, 16, 128):
        q, k = rng.normal(size=(2, 3, d)).astype(np.float32)
        fq, fk = np.asarray(R.phi(q), np.float64), np.asarray(R.phi(k),
                                                              np.float64)
        assert fq.shape == (3, R.phi_width(d)) == (3, (d // 2 + 1) * d)
        np.testing.assert_allclose(
            (fq * fk).sum(-1), (q.astype(np.float64) * k).sum(-1) ** 2,
            rtol=1e-5)
        # d (d + 1) / 2 lanes hold a product, the last half block none
        assert (np.asarray(R._diagonal_weights(d)) > 0).sum() == (
            d * (d + 1) // 2)
    assert R.phi_width(128) == 8320


def test_the_pool_holds_one_state_a_block_and_no_kv(model):
    doc, unit, params = model
    pool = init_block_pool(unit.cfg, BLOCKS, BS)
    for i in range(LAYERS):
        assert {k: (v.shape, v.dtype) for k, v in pool[f"l{i}"].items()} == {
            "s": ((BLOCKS, KV * HD, P), jnp.float32),
            "z": ((BLOCKS, KV, P), jnp.float32)}
    assert G._pool_kv(pool) is None
    assert not G.decode_inplace(pool, heads=4, rows=2)
    lp = params["l0"]
    assert lp["ret_gate"].shape == (32, KV)
    # the gate's bias is float32 and far from zero: a decay of 0.99-0.9995
    bias = np.asarray(lp["ret_gate_b"])
    assert lp["ret_gate_b"].dtype == jnp.float32
    assert bias.min() >= 4.6 and bias.max() <= 7.6
    # every FFN is the dense gated one, no layer holds experts or K/V
    assert unit.cfg.kinds == (("ret", "gated"),) * LAYERS
    assert "w3" in lp and "router" not in lp and unit.cfg.expert_layers == 0
    assert params["lm_head"].shape == (32, 96)
    # the static lane's pool is a block a row too
    pool, tables = G.private_pool(unit.cfg, 3, 50)
    assert pool["l0"]["s"].shape[0] == 4 and tables.tolist() == [[1], [2],
                                                                 [3]]


# -- the programs against the reference ------------------------------------


@BOTH_STEPS
def test_whole_prefill_gives_the_references_logits_at_every_position(
        model, inplace):
    doc, unit, params = model
    row = prompts([13], seed=1)[0]
    pool = init_block_pool(unit.cfg, BLOCKS, BS)
    logits, _ = paged_forward_jit(
        params, jnp.asarray(row[None]), pool, TABLES[:1],
        jnp.zeros((1,), jnp.int32), jnp.asarray([13], jnp.int32),
        cfg=unit.cfg, last_only=False, fused=inplace)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               reference_logits(params, row, doc),
                               atol=1e-4, rtol=0)


@BOTH_STEPS
@pytest.mark.parametrize("chunk", [1, 4, 5, 16])
def test_chunked_prefill_then_decode_rounds_equal_the_reference(model, chunk,
                                                                inplace):
    """Two prompts of 13 and 8 tokens (unequal, so every call but a whole
    one has pad positions or a row of width 0) through the recurrent form
    alone (chunk 1), the chunk form at a size that divides neither (5), one
    that divides the shorter (4) and one call for everything (16); then two
    decode rounds through the state, teacher-checked: every token is the
    argmax of the reference's whole causal pass over the row so far."""
    doc, unit, params = model
    rows = prompts([13, 8], seed=2)
    logits, pool = chunked(unit, params, rows, chunk, TABLES, fused=inplace)
    for i, r in enumerate(rows):
        np.testing.assert_allclose(
            logits[i], reference_logits(params, r, doc)[-1], atol=1e-4,
            rtol=0)
    first = logits.argmax(-1).astype(np.int32)
    n_valid = np.asarray([13, 8], np.int32)
    got = [first[:, None]]
    token = first
    for _ in range(2):
        toks, pool, token, n_valid, *_ = decode(
            unit, params, pool, TABLES, token, n_valid, [True, True], 4,
            inplace)
        got.append(np.asarray(toks))
    got = np.concatenate(got, axis=1)
    for i, r in enumerate(rows):
        np.testing.assert_array_equal(
            got[i], reference_answer(params, r, doc, 9))
    # and the state the rounds left is the row's: one more token through a
    # chunk of two positions (one of them pad) lands on the reference
    seq = np.concatenate([rows[0], got[0]])
    nxt, _ = paged_forward_jit(
        params, jnp.asarray([[seq[-1], 0]], jnp.int32), pool, TABLES[:1],
        jnp.asarray([len(seq) - 1], jnp.int32), jnp.asarray([1], jnp.int32),
        cfg=unit.cfg, last_only=True, fused=inplace)
    np.testing.assert_allclose(np.asarray(nxt[0]),
                               reference_logits(params, seq, doc)[-1],
                               atol=1e-4, rtol=0)


@BOTH_STEPS
def test_padded_rows_touch_nothing_but_the_scratch_entry(model, inplace):
    """A decode round and a prefill chunk with an empty slot whose table is
    all zeros (what the scheduler pads with): the live row's tokens are
    what they are alone, the other row's state is as its prefill left it,
    and nothing but entry 0 could have been written for the pad."""
    doc, unit, params = model
    rows = prompts([9, 6], seed=3)
    logits, pool = chunked(unit, params, rows, 16, TABLES, fused=inplace)
    before = jax.tree.map(np.asarray, pool)
    tables = jnp.asarray([[1], [0]], jnp.int32)
    toks, pool, *_ = decode(
        unit, params, pool, tables, [int(logits[0].argmax()), 0], [9, 0],
        [True, False], 4, inplace)
    if inplace:
        # the kernel walks the live rows alone: the pad's scratch entry is
        # not written either
        for i in range(LAYERS):
            for name in ("s", "z"):
                np.testing.assert_array_equal(
                    np.asarray(pool[f"l{i}"][name])[0],
                    before[f"l{i}"][name][0])
    np.testing.assert_array_equal(
        np.asarray(toks)[0], reference_answer(params, rows[0], doc, 5)[1:])
    assert not np.asarray(toks)[1].any()
    _, pool = paged_forward_jit(
        params, jnp.zeros((2, 4), jnp.int32), pool, tables,
        jnp.asarray([13, 0], jnp.int32), jnp.asarray([4, 0], jnp.int32),
        cfg=unit.cfg, last_only=True, fused=inplace)
    for i in range(LAYERS):
        for name in ("s", "z"):
            after = np.asarray(pool[f"l{i}"][name])
            np.testing.assert_array_equal(after[2:], before[f"l{i}"][name][2:])
            if inplace:
                # the chunk kernel, like the step's, walks the live rows
                # alone
                np.testing.assert_array_equal(after[0],
                                              before[f"l{i}"][name][0])
            assert np.abs(after[1] - before[f"l{i}"][name][1]).max() > 0


@BOTH_STEPS
def test_a_block_reused_after_a_longer_row_gives_what_a_fresh_one_gives(
        model, inplace):
    """A sequence that starts at position 0 reads a zero state whatever its
    block held: after a longer sequence's prefill and a round over the same
    block, a new prompt there gives the reference's logits -- bit for bit
    what a fresh pool gives."""
    doc, unit, params = model
    old, new = prompts([21, 7], seed=4)
    logits, pool = chunked(unit, params, [old], 4, TABLES[:1], fused=inplace)
    _, pool, *_ = decode(unit, params, pool, TABLES[:1],
                         [int(logits[0].argmax())], [21], [True], 4, inplace)
    assert float(jnp.abs(pool["l0"]["s"][1]).max()) > 0
    reused, pool = chunked(unit, params, [new], 3, TABLES[:1], pool=pool,
                           fused=inplace)
    fresh, _ = chunked(unit, params, [new], 3, TABLES[:1], fused=inplace)
    np.testing.assert_array_equal(reused, fresh)
    np.testing.assert_allclose(
        reused[0], reference_logits(params, new, doc)[-1], atol=1e-4, rtol=0)
    # the step itself at position 0 (a round whose row has nothing cached
    # yet): it too reads zero whatever the entry holds
    alone = decode(unit, params, init_block_pool(unit.cfg, BLOCKS, BS),
                   TABLES[:1], [int(new[0])], [0], [True], 4, inplace)[0]
    dirty = decode(unit, params, pool, TABLES[:1], [int(new[0])], [0],
                   [True], 4, inplace)[0]
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(alone))


def test_copying_a_block_copies_the_state_kept_at_its_id(model):
    doc, unit, params = model
    row = prompts([6], seed=5)[0]
    _, pool = chunked(unit, params, [row], 16, TABLES[:1])
    want = {n: np.asarray(pool["l1"][n][1]) for n in ("s", "z")}
    pool = paged_copy_block_jit(pool, jnp.int32(1), jnp.int32(3))
    for n in ("s", "z"):
        np.testing.assert_array_equal(np.asarray(pool["l1"][n][3]), want[n])


def test_static_lane_gives_the_reference_answer(model):
    doc, unit, params = model
    rows = np.stack(prompts([10, 10], seed=6))
    want = np.stack([reference_answer(params, r, doc, 11) for r in rows])
    np.testing.assert_array_equal(np.asarray(generate(
        params, jnp.asarray(rows), unit.cfg, max_new_tokens=11)), want)
    chunks = list(stream_chunks(params, jnp.asarray(rows), unit.cfg,
                                max_new_tokens=11, chunk=4))
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), want)


# -- the step as a kernel ---------------------------------------------------


def built_state(rng, N, KV, d, keys=4):
    """Entries that keys built (``Z = sum phi(k_j)``, ``S = sum v_j
    phi(k_j)^T``): the normaliser of a query near a key is a sum of
    squares, far from zero."""
    ks = rng.normal(size=(N, KV, keys, d)).astype(np.float32)
    vs = rng.normal(size=(N, KV, keys, d)).astype(np.float32)
    fk = np.asarray(R.phi(ks))
    s = np.einsum("nkje,nkjp->nkep", vs, fk).reshape(N, KV * d, -1)
    return {"s": jnp.asarray(s, jnp.float32),
            "z": jnp.asarray(fk.sum(2), jnp.float32)}


# name: head width, KV heads, queries a KV head, diagonal blocks a tile
# (None: ``blocks_per_tile``), each row's (entry, start, width)
STEP_CASES = {
    # pads between live rows, their table all zeros (entry 0)
    "pads_between_live_rows": (16, 2, 2, None,
                               [(3, 5, 1), (0, 0, 0), (1, 9, 1), (0, 0, 0),
                                (4, 2, 1)]),
    "a_row_at_0_over_a_dirty_entry": (16, 2, 2, None, [(2, 0, 1), (1, 7, 1)]),
    "five_queries_a_head": (16, 1, 5, None, [(1, 3, 1), (2, 4, 1)]),
    "one_query_a_head": (16, 2, 1, None, [(1, 3, 1), (2, 4, 1)]),
    "three_tiles_of_three_blocks": (16, 2, 2, 3, [(1, 3, 1), (0, 0, 0),
                                                  (2, 0, 1)]),
    "nine_tiles_of_one_block": (16, 2, 2, 1, [(1, 3, 1), (2, 4, 1)]),
    "nobody_live": (16, 2, 2, None, [(0, 0, 0), (0, 0, 0)]),
    # the published head: 65 blocks of 128 lanes, five tiles of 13
    "a_head_of_128_lanes": (128, 1, 5, None, [(2, 6, 1), (0, 0, 0),
                                              (1, 0, 1)]),
}


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_the_step_kernel_agrees_with_the_step_row_by_row(case):
    """``_fused_step`` in Pallas interpret mode against ``_step`` under
    ``retention``'s loop over live rows, float32 both: ``y`` and the live
    rows' entries agree to the order of the sums, every other entry -- the
    scratch entry of the pads too -- is bit for bit what it was."""
    d, KV, G, tile, rows = STEP_CASES[case]
    rng = np.random.default_rng(len(case))
    B, N = len(rows), 5
    slot, start, width = (jnp.asarray(c, jnp.int32) for c in zip(*rows))
    k = rng.normal(size=(B, KV, 1, d)).astype(np.float32)
    # a query near its key: (q . k)^2 is no difference of large numbers
    q = (k[:, :, None] + 0.5 * rng.normal(size=(B, KV, G, 1, d))).astype(
        np.float32)
    v = rng.normal(size=(B, KV, 1, d)).astype(np.float32)
    log_g = np.log(rng.uniform(0.9, 0.999, (B, KV, 1))).astype(np.float32)
    state = built_state(rng, N, KV, d)
    want_y, want = R.retention(q, k, v, log_g, state, slot, start, width,
                               fused=False)
    live = width > 0
    y, s, z = R._fused_step(
        jnp.asarray(q)[:, :, :, 0], jnp.asarray(k)[:, :, 0],
        jnp.asarray(v)[:, :, 0], jnp.asarray(log_g)[:, :, 0], state["s"],
        state["z"], slot, start == 0, jnp.argsort(~live, stable=True),
        jnp.sum(live), interpret=True, tile=tile)
    assert y.dtype == jnp.float32 and y.shape == (B, KV, G, d)
    np.testing.assert_allclose(np.asarray(y)[:, :, :, None],
                               np.asarray(want_y), rtol=2e-5, atol=2e-6)
    for got, name in ((s, "s"), (z, "z")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[name]),
                                   rtol=1e-6, atol=1e-5)
        untouched = sorted(set(range(N)) - {
            e for e, _, w in rows if w})
        np.testing.assert_array_equal(np.asarray(got)[untouched],
                                      np.asarray(state[name])[untouched])
    assert not np.asarray(y)[~np.asarray(live)].any()
    if live.any():
        assert np.abs(np.asarray(s) - np.asarray(state["s"])).max() > 0


def test_the_traced_step_aliases_both_state_operands_to_its_outputs():
    """The ``pallas_call`` of a decode round's step writes the pool's
    entries where they lie: operands 6 and 7 (after slot, order, count,
    fresh, g and the small q|k|v operand) are outputs 1 and 2, in the
    block's trace as in the kernel's own."""
    doc, unit = config()
    cfg = unit.cfg
    pool = jax.eval_shape(lambda: init_block_pool(cfg, BLOCKS, BS))
    params = jax.eval_shape(lambda: unit.init_state(None)["params"])

    def block(lp, x, layer, tables, start, valid):
        return G._paged_block.__wrapped__(
            lp, x, layer, tables, start, valid, cfg, kind=cfg.kind(0),
            fused=True, interpret=True)

    jaxpr = jax.make_jaxpr(block)(
        params["l0"], jax.ShapeDtypeStruct((2, 1, 32), jnp.float32),
        pool["l0"], TABLES, jnp.zeros((2,), jnp.int32),
        jnp.ones((2, 1), bool))

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    found = list(calls(jaxpr.jaxpr))
    assert len(found) == 1
    eqn = found[0]
    assert tuple(eqn.params["input_output_aliases"]) == ((6, 1), (7, 2))
    for i, o in ((6, 1), (7, 2)):
        assert eqn.invars[i].aval.shape == eqn.outvars[o].aval.shape
    assert eqn.invars[6].aval.shape == (BLOCKS, KV * HD, P)
    assert eqn.invars[7].aval.shape == (BLOCKS, KV, P)
    # and the call of one position a row with ``fused`` False holds none
    plain = jax.make_jaxpr(lambda *a: G._paged_block.__wrapped__(
        *a, cfg, kind=cfg.kind(0)))(
            params["l0"], jax.ShapeDtypeStruct((2, 1, 32), jnp.float32),
            pool["l0"], TABLES, jnp.zeros((2,), jnp.int32),
            jnp.ones((2, 1), bool))
    assert not list(calls(plain.jaxpr))


SUPPORTED = dict(backend="tpu", state_dtype=jnp.float32, head_dim=128,
                 mesh=None, kv_heads=8, heads=40, rows=16)


@pytest.mark.parametrize("reason,change", [
    ("a_cpu_backend", {"backend": "cpu"}),
    ("a_bfloat16_state", {"state_dtype": jnp.bfloat16}),
    ("a_head_of_64", {"head_dim": 64}),
    ("a_mesh", {"mesh": object()}),
    ("a_batch_vector_memory_cannot_hold", {"rows": 512}),
])
def test_step_supported_refuses(reason, change):
    """The published shapes on a TPU take the kernel; each of these alone
    takes ``_step`` row by row."""
    assert R.step_supported(**SUPPORTED)
    assert not R.step_supported(**{**SUPPORTED, **change}), reason


def test_what_decides_for_the_step():
    """On this backend (the CPU) nobody takes the kernel unasked:
    ``retention_fused`` says no of a pool of states and of one without;
    five tiles of 13 blocks at the published head."""
    doc, unit = config()
    pool = init_block_pool(unit.cfg, BLOCKS, BS)
    assert not G.retention_fused(pool, heads=4, rows=2)
    dense = TransformerGenerator(vocab=48, d_model=32, n_heads=4, n_layers=1,
                                 d_ff=32, dtype="float32")
    assert not G.retention_fused(init_block_pool(dense.cfg, 4, 4))
    assert R.blocks_per_tile(128) == 13 and R.blocks_per_tile(16) == 9
    assert R.blocks_per_tile(2048) == 0     # one block is 16 MB
    with pytest.raises(ValueError, match="whole number of tiles"):
        R._fused_step(
            jnp.zeros((1, 1, 1, 16)), jnp.zeros((1, 1, 16)),
            jnp.zeros((1, 1, 16)), jnp.zeros((1, 1)), jnp.zeros((2, 16, P)),
            jnp.zeros((2, 1, P)), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), bool), jnp.zeros((1,), jnp.int32), jnp.int32(0),
            interpret=True, tile=2)


# -- the chunk form as a kernel ---------------------------------------------


# name: head width, KV heads, queries a KV head, chunk width, diagonal
# blocks a tile (None: ``blocks_per_tile``), each row's (entry, start, width)
CHUNK_CASES = {
    # part-filled chunks of 256: one position, a stretch, all but one, all
    "widths_1_100_255_256_of_256": (16, 1, 2, 256, None,
                                    [(1, 256, 1), (2, 512, 100),
                                     (3, 256, 255), (4, 768, 256)]),
    "a_row_at_0_over_a_dirty_entry": (16, 2, 2, 8, None,
                                      [(2, 0, 8), (1, 7, 5)]),
    "a_row_carried_from_a_state": (16, 2, 2, 8, None, [(3, 16, 8)]),
    "pads_between_live_rows": (16, 2, 2, 8, None,
                               [(3, 5, 8), (0, 0, 0), (1, 9, 3), (0, 0, 0),
                                (4, 0, 5)]),
    "nobody_live": (16, 2, 2, 8, None, [(0, 0, 0), (0, 0, 0)]),
    "five_queries_a_head": (16, 1, 5, 16, None, [(1, 16, 16), (2, 0, 1)]),
    "one_query_a_head": (16, 2, 1, 8, None, [(1, 3, 8), (2, 4, 2)]),
    "three_tiles_of_three_blocks": (16, 2, 2, 8, 3, [(1, 3, 8), (0, 0, 0),
                                                     (2, 0, 6)]),
    "nine_tiles_of_one_block": (16, 2, 2, 8, 1, [(1, 3, 8), (2, 4, 7)]),
    # the published head: 65 blocks of 128 lanes, five tiles of 13
    "a_head_of_128_lanes": (128, 1, 5, 16, None, [(2, 6, 16), (0, 0, 0),
                                                  (1, 0, 9)]),
}


def chunk_operands(rng, B, KV, G, W, d):
    """q near k (``(q . k)^2`` no difference of large numbers), both at
    the scale the per-head norms leave them, gates near 1."""
    k = (rng.normal(size=(B, KV, W, d)) / d ** 0.25).astype(np.float32)
    q = (k[:, :, None] + 0.5 * rng.normal(size=(B, KV, G, W, d))
         / d ** 0.25).astype(np.float32)
    v = rng.normal(size=(B, KV, W, d)).astype(np.float32)
    log_g = np.log(rng.uniform(0.9, 0.999, (B, KV, W))).astype(np.float32)
    return q, k, v, log_g


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_the_chunk_kernel_agrees_with_the_chunk_form_row_by_row(case):
    """``_fused_chunk`` in Pallas interpret mode against ``_chunk`` under
    ``retention``'s loop over live rows, float32 both: ``y`` at every valid
    position and the live rows' entries agree to the order of the sums,
    every other entry -- the scratch entry of the pads too -- is bit for
    bit what it was, and through ``retention`` a row that is not live
    reads zero."""
    d, KV, G, W, tile, rows = CHUNK_CASES[case]
    rng = np.random.default_rng(len(case))
    B, N = len(rows), 5
    slot, start, width = (jnp.asarray(c, jnp.int32) for c in zip(*rows))
    q, k, v, log_g = chunk_operands(rng, B, KV, G, W, d)
    state = built_state(rng, N, KV, d)
    want_y, want = R.retention(q, k, v, log_g, state, slot, start, width,
                               fused=False)
    live = width > 0
    y, s, z = R._fused_chunk(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(log_g),
        width, state["s"], state["z"], slot, start == 0,
        jnp.argsort(~live, stable=True), jnp.sum(live), interpret=True,
        tile=tile)
    assert y.dtype == jnp.float32 and y.shape == (B, KV, G, W, d)
    for b, (_, _, w) in enumerate(rows):
        np.testing.assert_allclose(
            np.asarray(y)[b, :, :, :w], np.asarray(want_y)[b, :, :, :w],
            rtol=2e-5, atol=2e-6)
    for got, name in ((s, "s"), (z, "z")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[name]),
                                   rtol=1e-6, atol=1e-5)
        untouched = sorted(set(range(N)) - {e for e, _, w in rows if w})
        np.testing.assert_array_equal(np.asarray(got)[untouched],
                                      np.asarray(state[name])[untouched])
    if live.any():
        assert np.abs(np.asarray(s) - np.asarray(state["s"])).max() > 0
    through, _ = R.retention(q, k, v, log_g, state, slot, start, width,
                             fused="interpret")
    assert not np.asarray(through)[~np.asarray(live)].any()


def test_two_chunks_through_the_kernel_equal_the_step_token_by_token():
    """A row of 16 positions from zero: two chunks of 8 through the chunk
    kernel, and sixteen calls of one position through ``_step``: the same
    ``y`` at every position and the same state at the end."""
    d, KV, G, W = 16, 2, 2, 8
    rng = np.random.default_rng(5)
    q, k, v, log_g = chunk_operands(rng, 1, KV, G, 2 * W, d)
    slot = jnp.asarray([2], jnp.int32)
    state = built_state(rng, 4, KV, d)       # dirty: the row starts at 0
    ys, by_chunk = [], state
    for lo in (0, W):
        y, by_chunk = R.retention(
            q[:, :, :, lo:lo + W], k[:, :, lo:lo + W], v[:, :, lo:lo + W],
            log_g[:, :, lo:lo + W], by_chunk, slot,
            jnp.asarray([lo], jnp.int32), jnp.asarray([W], jnp.int32),
            fused="interpret")
        ys.append(np.asarray(y))
    want, by_step = [], state
    for t in range(2 * W):
        y, by_step = R.retention(
            q[:, :, :, t:t + 1], k[:, :, t:t + 1], v[:, :, t:t + 1],
            log_g[:, :, t:t + 1], by_step, slot, jnp.asarray([t], jnp.int32),
            jnp.asarray([1], jnp.int32), fused=False)
        want.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(ys, axis=3),
                               np.concatenate(want, axis=3), rtol=2e-5,
                               atol=2e-6)
    for name in ("s", "z"):
        np.testing.assert_allclose(
            np.asarray(by_chunk[name]), np.asarray(by_step[name]), rtol=1e-5,
            atol=1e-5)


def test_the_traced_chunk_aliases_both_state_operands_to_its_outputs():
    """The ``pallas_call`` of a prefill call's chunk writes the pool's
    entries where they lie: operands 10 and 11 (after slot, order, count,
    fresh, the decay over the chunk and the head's q, k, weighted k, v and
    cumulative decay) are outputs 1 and 2, in the block's trace; and the
    block with ``fused`` False holds no kernel."""
    doc, unit = config()
    cfg = unit.cfg
    pool = jax.eval_shape(lambda: init_block_pool(cfg, BLOCKS, BS))
    params = jax.eval_shape(lambda: unit.init_state(None)["params"])
    operands = (params["l0"], jax.ShapeDtypeStruct((2, 8, 32), jnp.float32),
                pool["l0"], TABLES, jnp.zeros((2,), jnp.int32),
                jnp.ones((2, 8), bool))

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    found = list(calls(jax.make_jaxpr(
        lambda *a: G._paged_block.__wrapped__(
            *a, cfg, kind=cfg.kind(0), fused=True, interpret=True))(
                *operands).jaxpr))
    assert len(found) == 1
    eqn = found[0]
    assert tuple(eqn.params["input_output_aliases"]) == ((10, 1), (11, 2))
    assert eqn.invars[10].aval.shape == eqn.outvars[1].aval.shape == (
        BLOCKS, KV * HD, P)
    assert eqn.invars[11].aval.shape == eqn.outvars[2].aval.shape == (
        BLOCKS, KV, P)
    assert not list(calls(jax.make_jaxpr(
        lambda *a: G._paged_block.__wrapped__(*a, cfg, kind=cfg.kind(0)))(
            *operands).jaxpr))


CHUNK_SUPPORTED = dict({k: v for k, v in SUPPORTED.items() if k != "rows"},
                       width=256, act_dtype=jnp.bfloat16)


@pytest.mark.parametrize("reason,change", [
    ("a_cpu_backend", {"backend": "cpu"}),
    ("a_bfloat16_state", {"state_dtype": jnp.bfloat16}),
    ("a_head_of_64", {"head_dim": 64}),
    ("a_mesh", {"mesh": object()}),
    ("a_chunk_of_no_whole_sublane_tiles", {"width": 100}),
    ("a_chunk_too_wide_for_vector_memory", {"width": 1024}),
    ("float32_activations_vector_memory_cannot_hold",
     {"act_dtype": jnp.float32}),
])
def test_chunk_supported_refuses(reason, change):
    """The published shapes on a TPU take the chunk kernel (whatever the
    batch: it is not asked for the rows) and each of these alone takes
    ``_chunk`` row by row."""
    assert R.chunk_supported(**CHUNK_SUPPORTED)
    assert not R.chunk_supported(**{**CHUNK_SUPPORTED, **change}), reason


def test_what_decides_for_the_chunk(monkeypatch):
    """``retention_fused`` asks ``step_supported`` for a width of one and
    ``chunk_supported`` for any other; on this backend (the CPU) nobody
    takes either kernel unasked, and ``paged_forward`` called without an
    answer asks for itself."""
    doc, unit = config()
    pool = init_block_pool(unit.cfg, BLOCKS, BS)
    assert not G.retention_fused(pool, heads=4, rows=2, width=8)
    asked = []
    monkeypatch.setattr(R, "chunk_supported",
                        lambda **kw: asked.append(kw) or False)
    monkeypatch.setattr(R, "step_supported",
                        lambda **kw: asked.append("step") or False)
    G.retention_fused(pool, heads=4, rows=2, width=8, dtype=jnp.float32)
    G.retention_fused(pool, heads=4, rows=2)
    assert asked[1] == "step"
    assert asked[0] == dict(
        backend="cpu", state_dtype=jnp.float32, head_dim=HD, mesh=None,
        kv_heads=KV, heads=4, width=8, act_dtype=jnp.float32)
    with pytest.raises(ValueError, match="whole number of tiles"):
        R._fused_chunk(
            jnp.zeros((1, 1, 1, 8, 16)), jnp.zeros((1, 1, 8, 16)),
            jnp.zeros((1, 1, 8, 16)), jnp.zeros((1, 1, 8)),
            jnp.zeros((1,), jnp.int32), jnp.zeros((2, 16, P)),
            jnp.zeros((2, 1, P)), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), bool), jnp.zeros((1,), jnp.int32), jnp.int32(0),
            interpret=True, tile=2)


# -- what must fail -----------------------------------------------------------


INTACT = R.retention


def gate_dropped(q, k, v, log_g, state, slot, start, width, **kw):
    return INTACT(q, k, v, jnp.zeros_like(log_g), state, slot, start, width,
                  **kw)


def gate_applied_after_the_update(q, k, v, log_g, state, slot, start, width,
                                  **kw):
    # S_t = g_t (S_(t-1) + phi(k_t) v_t^T): the token's own term decays too
    # (phi is quadratic: sqrt(g) on k is g on phi(k))
    k = (k * jnp.exp(log_g / 2)[..., None]).astype(k.dtype)
    return INTACT(q, k, v, log_g, state, slot, start, width, **kw)


def normaliser_left_out(q, k, v, log_g, state, slot, start, width, **kw):
    # the normaliser's column of the state is not carried from call to call
    return INTACT(q, k, v, log_g,
                  {**state, "z": jnp.zeros_like(state["z"])}, slot, start,
                  width, **kw)


@pytest.mark.parametrize("fault", [
    None, gate_dropped, gate_applied_after_the_update, normaliser_left_out])
def test_a_program_that_leaves_out_part_of_the_layer_fails(model, fault,
                                                           monkeypatch):
    """Prefill in chunks of 4 and one round, against the reference: the
    program as it is lies within 1e-4 and emits the reference's tokens; the
    gate dropped, the gate applied to the wrong side of the update and the
    normaliser not carried each read a hundred times that or more."""
    doc, unit, params = model
    row = prompts([13], seed=8)[0]
    want = reference_logits(params, row, doc)[-1]
    jax.clear_caches()
    if fault is not None:
        monkeypatch.setattr(R, "retention", fault)
    try:
        logits, pool = chunked(unit, params, [row], 4, TABLES[:1])
        toks, *_ = decode(unit, params, pool, TABLES[:1],
                          [int(want.argmax())], [13], [True], 4)
    finally:
        jax.clear_caches()
    err = np.abs(logits[0] - want).max()
    answer = reference_answer(params, row, doc, 5)[1:]
    if fault is None:
        assert err < 1e-4
        np.testing.assert_array_equal(np.asarray(toks)[0], answer)
    else:
        assert err > 1e-2, (fault.__name__, err)


# -- the unit's description of its layers -----------------------------------


def test_the_unit_built_from_the_configuration_file_has_its_kinds():
    with open(os.path.join(REPO, "bench", "configs",
                           "brumby-14b.json")) as f:
        doc = json.load(f)
    p = doc["unit"]["parameters"]
    assert p["layer_kinds"] == "r" * doc["num_hidden_layers"]
    assert p["dense_layers"] == p["n_layers"] == {
        "from": "num_hidden_layers"}
    assert (doc["retention_degree"], doc["retention_eps"]) == (2, R.EPS)
    cfg = LMConfig(
        vocab=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        n_layers=doc["num_hidden_layers"], layer_kinds=p["layer_kinds"],
        dense_layers=doc["num_hidden_layers"], d_ff=doc["intermediate_size"],
        qk_norm=p["qk_norm"], tie_embeddings=doc["tie_word_embeddings"])
    assert set(cfg.kinds) == {("ret", "gated")} and cfg.hd == 128
    shapes = jax.eval_shape(lambda: init_block_pool(
        cfg, doc["deployment"]["pool_blocks"],
        doc["deployment"]["block_size"]))
    assert shapes["l0"]["s"].shape == (17, 8 * 128, 8320)
    assert shapes["l0"]["z"].shape == (17, 8, 8320)


def test_a_program_traces_the_block_once_and_names_its_stages(model):
    doc, unit, params = model
    lowered = paged_decode_round_jit.lower(
        params, init_block_pool(unit.cfg, BLOCKS, BS), TABLES,
        jnp.zeros((2,), jnp.int32), jnp.asarray([5, 8], jnp.int32),
        jnp.ones((2,), bool), jnp.zeros((2,), bool),
        jnp.zeros((2,), jnp.uint32), unit.cfg, span=4, temperature=0.0,
        top_k=0, top_p=0.0, eos_token=-1)
    assert len(set(re.findall(r"func\.func private @(_paged_block\w*)\(",
                              lowered.as_text()))) == 1
    text = "\n".join(re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text())).replace(
            "jit(_paged_block)/", "")
    for scope in ("ret_in/", "retention/", "ret_out/", "ffn/", "unembed/"):
        assert "/" + scope in text, scope
    for scope in ("kv_write/", "kv_gather/", "attn/"):
        assert "/" + scope not in text, scope


# -- lanes that cannot hold the state ----------------------------------------


def test_lanes_that_cannot_hold_the_state_refuse_by_name(model, monkeypatch):
    doc, unit, params = model
    spec = unit.continuous_spec({"params": params})
    kw = {"block_size": BS, "num_blocks": 4, "slots": 2, "span": 4,
          "prefill_chunk": 8}
    draft = TransformerGenerator(vocab=96, d_model=32, n_heads=4, n_layers=1,
                                 d_ff=32, dtype="float32")
    d_params = draft.init_state(None)["params"]
    with pytest.raises(ValueError, match="speculative decoding"):
        GenServer(**spec, draft_params=d_params, draft_cfg=draft.cfg, **kw)
    with pytest.raises(ValueError, match="shared prefix"):
        GenServer(**{**spec, "prefix_ids": np.asarray([1, 2, 3])}, **kw)
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="prefill / decode roles"):
            GenServer(**spec, role=role, **kw)
    small = dict(vocab=96, d_model=32, n_heads=4, n_layers=2, dense_layers=2)
    with pytest.raises(ValueError, match="shared prefix"):
        TransformerGenerator(**small, layer_kinds="rr", prefix_tokens="1,2")
    with pytest.raises(ValueError, match="one chip"):
        TransformerGenerator(**small, layer_kinds="rr", mesh=object())
    with pytest.raises(ValueError, match="roll the layer's state back"):
        paged_spec_round(params, d_params, init_block_pool(unit.cfg, 4, BS),
                         init_block_pool(draft.cfg, 16, 4), TABLES, TABLES,
                         jnp.zeros((2,), jnp.int32),
                         jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
                         unit.cfg, draft.cfg, k=2)
    # a state is found where a row's first block is: a layer that holds K/V
    # beside it needs a table of states smaller than the pool
    with pytest.raises(ValueError, match="EVERY layer"):
        LMConfig(n_layers=2, layer_kinds="ra", dense_layers=2)
    with pytest.raises(ValueError, match="denoising passes"):
        LMConfig(vocab=96, n_layers=2, layer_kinds="rr", block_length=4,
                 denoising_steps=4, mask_id=5)
    # a dense gated FFN in every layer or in the leading ones of an expert
    # model, nothing between
    with pytest.raises(ValueError, match="dense_layers"):
        LMConfig(n_layers=3, layer_kinds="rrr", dense_layers=2)
    with pytest.raises(ValueError, match="cache-free forward"):
        lm_apply(params, jnp.zeros((1, 4), jnp.int32), unit.cfg)
    # a pool of default size would hold a state a block of 16 positions:
    # refused at boot with the two settings to change, not left to the
    # allocator
    monkeypatch.setattr(genserver, "_device_memory_bytes", lambda: 1 << 20)
    with pytest.raises(ValueError, match="SELDON_TPU_GEN_BLOCK_SIZE.*"
                                         "SELDON_TPU_GEN_POOL_BLOCKS"):
        GenServer(**spec, **{**kw, "block_size": 16, "num_blocks": 1024})
    monkeypatch.setattr(genserver, "_device_memory_bytes", lambda: None)
    srv = GenServer(**spec, **kw)
    assert srv._served.retention_row_bytes == (
        LAYERS * 4 * (KV * HD + KV) * P)
    srv.stop()


# -- GenServer ----------------------------------------------------------------


@pytest.fixture()
def clean_genperf():
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()
    yield
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()


def server(unit, params, **kw):
    kw = {"block_size": BS, "num_blocks": 5, "slots": 4, "span": 4,
          "prefill_chunk": 8, **kw}
    return GenServer(**unit.continuous_spec({"params": params}), **kw)


def settled(tokens):
    """``/genperf`` once the tick that emitted the last of ``tokens`` has
    published its record."""
    import time

    deadline = time.monotonic() + 10
    while True:
        SPINE.drain()
        doc = GENPERF.document()
        if (doc["served_decode"]["real_tokens"] >= tokens
                or time.monotonic() > deadline):
            return doc
        time.sleep(0.02)


@pytest.mark.parametrize("fused", [False, "interpret"],
                         ids=["step", "kernel"])
def test_genserver_serves_the_reference_answer_a_block_a_row_and_counts(
        model, clean_genperf, monkeypatch, fused):
    """Rows of different lengths co-scheduled a block a row (every table is
    one column wide), prompts of one chunk and of three (the state carried
    over chunks), unary and streamed -- and what the server says of the
    state's traffic and of who updated it: on the CPU the step row by row
    (``retention_fused`` says no), and the kernel in Pallas interpret mode
    where it is made to say "interpret", as a TPU says yes."""
    import seldon_core_tpu.models.generate as gen_mod

    doc, unit, params = model
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "8")
    if fused:
        monkeypatch.setattr(gen_mod, "retention_fused",
                            lambda *a, **kw: fused)
    srv = server(unit, params)
    assert srv._kernels is None     # not decided yet: no pool
    try:
        cases = [(3, 6), (8, 9), (19, 7)]
        reqs = []
        for n, max_new in cases:
            rows = np.stack(prompts([n], seed=20 + n))
            reqs.append((rows, max_new, srv.submit(rows, max_new=max_new)))
        for rows, max_new, req in reqs:
            want = np.stack([reference_answer(params, r, doc, max_new)
                             for r in rows])
            np.testing.assert_array_equal(
                req.future.result(timeout=180), want)
        rows = np.stack(prompts([19], seed=39))
        chunks = list(srv.stream(rows, chunk=3, max_new=7))
        np.testing.assert_array_equal(
            np.concatenate(chunks, 1),
            np.stack([reference_answer(params, r, doc, 7) for r in rows]))
        perf = settled(6 + 9 + 7 + 7)
        snap = srv.snapshot()
        assert snap["tick_errors_total"] == 0
        assert {p[-1] for kind in ("prefill", "decode")
                for p in srv._programs[kind]} == {1}
        assert srv._kernels.states_inplace == fused
        assert not srv._kernels.attends_inplace
    finally:
        srv.stop()
    prefill, served = perf["served_prefill"], perf["served_decode"]
    state = LAYERS * 4 * (KV * HD + KV) * P     # a row's, over the layers
    # 19 tokens at chunk 8 are three chunks, the later two carried
    assert prefill["rows"] == 1 + 1 + 3 + 3
    assert prefill["carried_rows"] == 2 + 2
    # every call is a chunk of 8 positions a row: the chunk kernel's, where
    # the one answer the server asks for says so
    assert prefill["retention_fused_rows"] == (prefill["rows"] if fused
                                               else 0)
    assert prefill["retention_state_bytes"] == 2 * state * 8
    assert served["row_passes"] > 0
    assert served["retention_state_bytes"] == (
        2 * state * served["row_passes"])
    assert served["inplace_steps"] == 0
    assert served["retention_fused_steps"] == (
        served["device_steps"] if fused else 0)
    assert served["device_steps"] > 0


def test_genserver_preempts_and_readmits_mid_answer(model):
    """Blocks of 4 positions and a pool too small for two whole rows (a toy
    deployment: at the published widths a block is a row and no row ever
    outgrows it): the younger row is evicted, its blocks -- and the state
    kept at its first block's id -- go back, and on readmission it is
    recomputed from the prompt and the tokens it had emitted, from a zero
    state at position 0: the answer of an uninterrupted run."""
    doc, unit, params = model
    rows = prompts([6, 6], seed=31)
    want = [reference_answer(params, r, doc, 18) for r in rows]
    srv = server(unit, params, block_size=4, num_blocks=11)
    try:
        reqs = [srv.submit(r[None], max_new=18) for r in rows]
        for req, w in zip(reqs, want):
            np.testing.assert_array_equal(
                req.future.result(timeout=240)[0], w)
        assert srv.snapshot()["preempted_total"] >= 1
    finally:
        srv.stop()


def test_a_pool_that_cannot_hold_a_row_says_what_it_holds(model):
    doc, unit, params = model
    srv = server(unit, params, block_size=4, num_blocks=3)
    try:
        with pytest.raises(RuntimeError, match="state pool"):
            srv.submit(prompts([24], seed=1)[0][None],
                       max_new=4).future.result(timeout=120)
    finally:
        srv.stop()


def test_a_generator_without_retention_counts_none(clean_genperf):
    unit = TransformerGenerator(vocab=48, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, dtype="float32")
    srv = GenServer(**unit.continuous_spec(unit.init_state(None)),
                    block_size=4, num_blocks=32, slots=2, span=4,
                    prefill_chunk=4)
    try:
        srv.submit(np.arange(10)[None], max_new=5).future.result(timeout=180)
        perf = settled(5)
    finally:
        srv.stop()
    assert perf["served_prefill"]["rows"] == 3
    for block in ("served_prefill", "served_decode"):
        assert perf[block]["retention_state_bytes"] == 0
    assert perf["served_decode"]["retention_fused_steps"] == 0
    assert perf["served_prefill"]["retention_fused_rows"] == 0
