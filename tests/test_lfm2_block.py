"""The served path of a generator whose layers are of several kinds
(LFM2-8B-A1B's mechanisms, bench/configs/lfm2-8b-a1b.json: gated
short-convolution layers whose state lives beside the block pool, GQA
layers, two leading dense gated-SiLU layers, then experts chosen by a
sigmoid router with a selection bias) at a tiny size on the CPU, in
float32: the paged programs, the static lane and ``GenServer`` against the
plain reference of bench/archs/lfm2_moe/, which shares no code with them.

Tolerances: logits within 1e-4 of values of order 1 (both sides float32,
the reference at ``highest``; what differs is the order of a few sums);
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
from seldon_core_tpu.parallel.moe import dropless_init, moe_dropless
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.utils.genperf import GENPERF
from seldon_core_tpu.utils.hotrecord import SPINE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LETTER = {"conv": "c", "full_attention": "a"}


def _reference():
    path = os.path.join(REPO, "bench", "archs", "lfm2_moe", "reference.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv"]


def config():
    """The configuration file's keys at a tiny size (what the reference
    reads) and the unit built from them as the deployment builds it."""
    doc = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=6, layer_types=TYPES, num_dense_layers=2,
               conv_L_cache=3, conv_bias=False, intermediate_size=48,
               moe_intermediate_size=16, num_experts=8,
               num_experts_per_tok=2, norm_topk_prob=True,
               routed_scaling_factor=1, use_expert_bias=True,
               rope_theta=1000000.0, norm_eps=1e-5, vocab_size=96)
    unit = TransformerGenerator(
        vocab=96, d_model=32, n_heads=4, n_kv_heads=2, n_layers=6,
        layer_kinds="".join(LETTER[t] for t in TYPES), dense_layers=2,
        conv_kernel=3, d_ff=48, d_expert=16, n_experts=8, moe_k=2,
        moe_norm_topk=True, router="sigmoid_bias", qk_norm=True,
        norm_eps=1e-5, rope_base=1000000.0, dtype="float32", seed=7)
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


def chunked(unit, params, rows, chunk, tables, pool=None, bs=4, blocks=16,
            how=None):
    """``rows`` prefilled ``chunk`` tokens a call as the scheduler does:
    rows of unequal length in one call, the shorter ones right-padded, a
    row that is through riding along with width 0.  Returns each row's
    logits from the call that consumed its last token, and the pool."""
    if pool is None:
        pool = init_block_pool(unit.cfg, blocks, bs)
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
            jnp.asarray(width), cfg=unit.cfg, last_only=True, **(how or {}))
        for i in range(len(rows)):
            if width[i] and lo + width[i] == lens[i]:
                out[i] = np.asarray(logits[i])
    return np.stack(out), pool


def decode(unit, params, pool, tables, token, n_valid, active, span,
           how=None):
    B = len(token)
    return paged_decode_round_jit(
        params, pool, tables, jnp.asarray(token, jnp.int32),
        jnp.asarray(n_valid, jnp.int32), jnp.asarray(active, bool),
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.uint32), unit.cfg,
        span=span, temperature=0.0, top_k=0, top_p=0.0, eos_token=-1,
        **(how or {}))


TABLES = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]], jnp.int32)


# -- the programs against the reference ------------------------------------


def test_the_pool_holds_kv_for_attention_layers_and_a_state_for_the_rest(
        model):
    doc, unit, params = model
    pool = init_block_pool(unit.cfg, 16, 4)
    for i, kind in enumerate(TYPES):
        if kind == "conv":
            assert {k: v.shape for k, v in pool[f"l{i}"].items()} == {
                "conv": (16, 2, 32)}
        else:
            assert sorted(pool[f"l{i}"]) == ["k", "v"]
            assert pool[f"l{i}"]["k"].shape == (16, 4, 2, 8)
    # leading dense layers hold no router, routed layers a bias that is
    # not zero
    assert "w3" in params["l0"] and "router" not in params["l1"]
    assert float(jnp.abs(params["l2"]["expert_bias"]).min()) > 0
    assert unit.cfg.expert_layers == 4
    assert G._pool_kv(pool) is pool["l2"]
    assert not G.decode_inplace(pool, heads=4, rows=2)


def test_whole_prefill_gives_the_references_logits_at_every_position(model):
    doc, unit, params = model
    row = prompts([13], seed=1)[0]
    pool = init_block_pool(unit.cfg, 16, 4)
    logits, _ = paged_forward_jit(
        params, jnp.asarray(row[None]), pool, TABLES[:1],
        jnp.zeros((1,), jnp.int32), jnp.asarray([13], jnp.int32),
        cfg=unit.cfg, last_only=False)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               reference_logits(params, row, doc),
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("chunk, experts_fused", [
    (1, None), (2, None), (3, None), (5, None), (16, None),
    (5, "interpret")], ids=["1", "2", "3", "5", "16", "5-experts-fused"])
def test_chunked_prefill_then_decode_rounds_equal_the_reference(
        model, chunk, experts_fused):
    """The same two prompts (13 and 8 tokens: unequal, so every call but a
    whole one has pad positions or a row of width 0) in chunks shorter
    than, equal to and longer than the convolution's history; then two
    decode rounds through the cache, teacher-checked: every token is the
    argmax of the reference's whole forward pass over the row so far.
    Once with an expert's feed-forward as the ONE Pallas call the chip
    runs, in interpret mode, in both programs."""
    doc, unit, params = model
    how = {"experts_fused": experts_fused} if experts_fused else None
    rows = prompts([13, 8], seed=2)
    logits, pool = chunked(unit, params, rows, chunk, TABLES, how=how)
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
            how=how)
        got.append(np.asarray(toks))
    got = np.concatenate(got, axis=1)
    for i, r in enumerate(rows):
        np.testing.assert_array_equal(
            got[i], reference_answer(params, r, doc, 9))
    # and the state the rounds left is z at the row's last two positions:
    # one more token through a prefill of width 1 lands on the reference
    seq = np.concatenate([rows[0], got[0]])
    nxt, _ = paged_forward_jit(
        params, jnp.asarray(seq[None, -1:]), pool, TABLES[:1],
        jnp.asarray([len(seq) - 1], jnp.int32), jnp.asarray([1], jnp.int32),
        cfg=unit.cfg, last_only=True, **(how or {}))
    np.testing.assert_allclose(np.asarray(nxt[0]),
                               reference_logits(params, seq, doc)[-1],
                               atol=1e-4, rtol=0)


def test_an_inactive_row_writes_scratch_and_leaves_a_live_state_alone(
        model):
    """A decode round with an empty slot whose table is all zeros (what
    the scheduler pads with): the live row's tokens are what they are
    alone, and the empty slot touched nothing but block 0's entries."""
    doc, unit, params = model
    rows = prompts([9, 6], seed=3)
    logits, pool = chunked(unit, params, rows, 16, TABLES)
    before = jax.tree.map(np.asarray, pool)
    tables = np.asarray(TABLES).copy()
    tables[1] = 0
    toks, pool, *_ = decode(
        unit, params, pool, jnp.asarray(tables),
        [int(logits[0].argmax()), 0], [9, 0], [True, False], 4)
    np.testing.assert_array_equal(
        np.asarray(toks)[0], reference_answer(params, rows[0], doc, 5)[1:])
    assert not np.asarray(toks)[1].any()
    # row 1's state (at its first block, 7) is as its prefill left it
    for i, kind in enumerate(TYPES):
        if kind == "conv":
            np.testing.assert_array_equal(
                np.asarray(pool[f"l{i}"]["conv"])[7],
                before[f"l{i}"]["conv"][7])


def test_a_reused_block_needs_no_reset(model):
    """A sequence that starts at position 0 reads a zero state whatever its
    first block held: after another sequence's prefill and rounds over the
    same blocks, a new prompt there gives the reference's logits."""
    doc, unit, params = model
    old, new = prompts([11, 7], seed=4)
    logits, pool = chunked(unit, params, [old], 4, TABLES[:1])
    _, pool, *_ = decode(unit, params, pool, TABLES[:1],
                         [int(logits[0].argmax())], [11], [True], 4)
    assert float(jnp.abs(pool["l0"]["conv"][1]).max()) > 0
    logits, pool = chunked(unit, params, [new], 3, TABLES[:1], pool=pool)
    np.testing.assert_allclose(
        logits[0], reference_logits(params, new, doc)[-1], atol=1e-4, rtol=0)


def test_copying_a_block_copies_the_state_kept_at_its_id(model):
    doc, unit, params = model
    row = prompts([6], seed=5)[0]
    _, pool = chunked(unit, params, [row], 16, TABLES[:1])
    want = np.asarray(pool["l0"]["conv"][1])
    pool = paged_copy_block_jit(pool, jnp.int32(1), jnp.int32(9))
    np.testing.assert_array_equal(np.asarray(pool["l0"]["conv"][9]), want)
    np.testing.assert_array_equal(np.asarray(pool["l2"]["k"][9]),
                                  np.asarray(pool["l2"]["k"][1]))


def test_static_lane_gives_the_reference_answer(model):
    doc, unit, params = model
    rows = np.stack(prompts([10, 10], seed=6))
    want = np.stack([reference_answer(params, r, doc, 11) for r in rows])
    np.testing.assert_array_equal(np.asarray(generate(
        params, jnp.asarray(rows), unit.cfg, max_new_tokens=11)), want)
    chunks = list(stream_chunks(params, jnp.asarray(rows), unit.cfg,
                                max_new_tokens=11, chunk=4))
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), want)
    np.testing.assert_array_equal(np.asarray(unit.predict(
        {"params": params, "requests": jnp.zeros((), jnp.int32)},
        jnp.asarray(rows, jnp.float32)))[:, :11].astype(np.int32),
        np.stack([reference_answer(params, r, doc, unit.max_new_tokens)
                  for r in rows])[:, :11])


# -- the router ---------------------------------------------------------------


def router_case(bias):
    cfg = LMConfig(d_model=16, n_heads=2, d_expert=8, n_experts=6, moe_k=2,
                   router="sigmoid_bias", dtype=jnp.float32)
    lp = dropless_init(jax.random.key(3), cfg)
    lp["expert_bias"] = jnp.asarray(bias, jnp.float32)
    h = jax.random.normal(jax.random.key(4), (1, 5, 16), jnp.float32)
    return cfg, lp, h


def by_hand(lp, h, cfg, bias_in_weights=False, eps=1e-6):
    x = np.asarray(h, np.float64).reshape(-1, h.shape[-1])
    score = 1.0 / (1.0 + np.exp(-x @ np.asarray(lp["router"], np.float64)))
    biased = score + np.asarray(lp["expert_bias"], np.float64)
    out = np.zeros_like(x)
    chosen = []
    for t in range(x.shape[0]):
        top = np.argsort(-biased[t], kind="stable")[:cfg.moe_k]
        chosen.append(sorted(int(e) for e in top))
        w = (biased if bias_in_weights else score)[t, top]
        w = w / (w.sum() + eps)
        for e, we in zip(top, w):
            gu = x[t] @ np.asarray(lp["e_gate_up"][e], np.float64)
            g, u = gu[:cfg.d_expert], gu[cfg.d_expert:]
            out[t] += we * ((g / (1 + np.exp(-g)) * u)
                            @ np.asarray(lp["e_down"][e], np.float64))
    return out.reshape(h.shape), chosen


def test_the_bias_steers_the_choice_and_never_the_weights():
    """A bias that lifts two experts nobody would choose makes every token
    choose them; the weights stay the UNBIASED sigmoid scores over (their
    sum + 1e-6), which is not what adding the bias to the weights gives."""
    bias = [0.0, 0.0, 5.0, 0.0, 5.0, 0.0]
    cfg, lp, h = router_case(bias)
    valid = jnp.ones((1, 5), bool)
    y, read = moe_dropless(lp, h, valid, cfg, impl="ragged_dot")
    want, chosen = by_hand(lp, h, cfg)
    assert chosen == [[2, 4]] * 5 and int(read) == 2
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    wrong, _ = by_hand(lp, h, cfg, bias_in_weights=True)
    assert np.abs(wrong - want).max() > 1e-2
    # without the bias the tokens choose otherwise
    _, free = by_hand({**lp, "expert_bias": jnp.zeros((6,))}, h, cfg)
    assert free != chosen
    y0, _ = moe_dropless({**lp, "expert_bias": jnp.zeros((6,))}, h, valid,
                         cfg, impl="ragged_dot")
    assert np.abs(np.asarray(y0) - want).max() > 1e-2


def test_the_renormalisation_carries_the_published_1e_6():
    """Router scores near zero (a router matrix of -inf-like columns): the
    chosen scores sum to about 1e-6, so the ``+ 1e-6`` halves the weights;
    a program without it would return twice as much."""
    cfg, lp, h = router_case([0.0] * 6)
    lp["router"] = jnp.zeros_like(lp["router"])
    # a constant input column drives every logit to -14.5: sigmoid 5e-7
    h = jnp.concatenate([h[..., :-1], jnp.ones_like(h[..., :1])], axis=-1)
    lp["router"] = lp["router"].at[-1].set(-14.5)
    y, _ = moe_dropless(lp, h, jnp.ones((1, 5), bool), cfg,
                        impl="ragged_dot")
    want, _ = by_hand(lp, h, cfg)
    without, _ = by_hand(lp, h, cfg, eps=0.0)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-6)
    assert np.abs(without).max() > 1.5 * np.abs(want).max()


def test_softmax_routing_is_what_it_was():
    cfg = LMConfig(d_model=16, n_heads=2, d_expert=8, n_experts=6, moe_k=2,
                   dtype=jnp.float32)
    lp = dropless_init(jax.random.key(3), cfg)
    assert "expert_bias" not in lp and cfg.router == "softmax"


# -- the unit's description of its layers -----------------------------------


def test_the_units_pattern_string_is_the_files_layer_types():
    """The deployment document carries the pattern as ONE string literal;
    the file keeps the published list for the reference.  They say the
    same, and the unit built from the file has the file's kinds."""
    with open(os.path.join(REPO, "bench", "configs",
                           "lfm2-8b-a1b.json")) as f:
        doc = json.load(f)
    p = doc["unit"]["parameters"]
    assert p["layer_kinds"] == "".join(
        LETTER[t] for t in doc["layer_types"])
    assert len(doc["layer_types"]) == doc["num_hidden_layers"] == 14
    assert doc["routed_scaling_factor"] == 1    # the program has no factor
    cfg = LMConfig(
        vocab=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"],
        n_layers=doc["num_hidden_layers"], layer_kinds=p["layer_kinds"],
        dense_layers=doc["num_dense_layers"], d_ff=doc["intermediate_size"],
        d_expert=doc["moe_intermediate_size"], n_experts=doc["num_experts"],
        moe_k=doc["num_experts_per_tok"], router=p["router"])
    assert [m for m, _ in cfg.kinds] == [
        {"conv": "conv", "full_attention": "attn"}[t]
        for t in doc["layer_types"]]
    assert [f for _, f in cfg.kinds] == ["gated"] * 2 + ["experts"] * 12
    assert cfg.expert_layers == 12 and cfg.hd == 64


def block_functions(lowered) -> int:
    """Private functions the lowered module holds for the block: one a
    distinct trace of ``_paged_block`` (tests/test_generate_trace_once.py)."""
    return len(set(re.findall(r"func\.func private @(_paged_block\w*)\(",
                              lowered.as_text())))


def test_a_program_traces_the_block_once_a_kind(model):
    """Three kinds of layer here (conv + dense, conv + routed, attention +
    routed), six layers: the module holds three functions for the block and
    six calls, and a configuration with one kind holds one, as before."""
    doc, unit, params = model
    assert len(set(unit.cfg.kinds)) == 3

    def lowered(unit, params):
        return paged_forward_jit.lower(
            params, jnp.zeros((2, 4), jnp.int32),
            init_block_pool(unit.cfg, 16, 4), TABLES,
            jnp.zeros((2,), jnp.int32), jnp.full((2,), 4, jnp.int32),
            cfg=unit.cfg)

    hybrid = lowered(unit, params)
    assert block_functions(hybrid) == 3
    assert len(re.findall(r"call @_paged_block", hybrid.as_text())) == 6
    dense = TransformerGenerator(vocab=48, d_model=32, n_heads=4, n_layers=5,
                                 d_ff=64, dtype="float32")
    assert block_functions(lowered(dense, dense.init_state(None)["params"])
                           ) == 1


def test_the_block_names_its_stages_for_the_trace(model):
    doc, unit, params = model
    lowered = paged_decode_round_jit.lower(
        params, init_block_pool(unit.cfg, 16, 4), TABLES,
        jnp.zeros((2,), jnp.int32), jnp.asarray([5, 8], jnp.int32),
        jnp.ones((2,), bool), jnp.zeros((2,), bool),
        jnp.zeros((2,), jnp.uint32), unit.cfg, span=4, temperature=0.0,
        top_k=0, top_p=0.0, eos_token=-1)
    text = "\n".join(re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text())).replace(
            "jit(_paged_block)/", "")
    for scope in ("conv_in/", "conv/", "conv_out/", "qkv/", "qk_norm/",
                  "kv_write/", "kv_gather/", "attn/", "wo/", "ffn/router/",
                  "ffn/experts/", "ffn/"):
        assert "/" + scope in text, scope


# -- lanes that cannot hold the state ----------------------------------------


def test_lanes_that_cannot_hold_the_state_refuse_by_name(model):
    doc, unit, params = model
    spec = unit.continuous_spec({"params": params})
    kw = {"block_size": 4, "num_blocks": 16, "slots": 2, "span": 4,
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
    with pytest.raises(ValueError, match="shared prefix"):
        TransformerGenerator(vocab=96, d_model=32, n_heads=4, n_layers=2,
                             layer_kinds="ca", prefix_tokens="1,2")
    with pytest.raises(ValueError, match="roll the layer's state back"):
        pool = init_block_pool(unit.cfg, 16, 4)
        paged_spec_round(params, d_params, pool,
                         init_block_pool(draft.cfg, 16, 4), TABLES, TABLES,
                         jnp.zeros((2,), jnp.int32),
                         jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
                         unit.cfg, draft.cfg, k=2)
    with pytest.raises(ValueError, match="denoising passes"):
        LMConfig(vocab=96, n_layers=2, layer_kinds="ca", block_length=4,
                 denoising_steps=4, mask_id=5)
    with pytest.raises(ValueError, match="one letter a layer"):
        LMConfig(n_layers=3, layer_kinds="ca")
    with pytest.raises(ValueError, match="dense_layers"):
        LMConfig(n_layers=3, dense_layers=1)
    with pytest.raises(ValueError, match="router"):
        LMConfig(router="tanh")
    with pytest.raises(ValueError, match="cache-free forward"):
        lm_apply(params, jnp.zeros((1, 4), jnp.int32),
                 LMConfig(vocab=96, d_model=32, n_layers=2,
                          layer_kinds="ca"))


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
    kw = {"block_size": 4, "num_blocks": 64, "slots": 4, "span": 4,
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


def test_genserver_serves_the_reference_answer_and_counts_its_work(
        model, clean_genperf, recorded_spans, monkeypatch):
    """Rows of different lengths co-scheduled, prompts of one chunk and of
    three (state carried over chunks), unary and streamed -- and what the
    server says of it: prefill rows that began from a carried state beside
    all prefill rows, expert slots over the ROUTED layers only."""
    doc, unit, params = model
    # the chunk stays 8: the scheduler does not probe a wider one
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "8")
    srv = server(unit, params)
    try:
        cases = [(3, 6), (8, 9), (19, 7)]
        reqs = []
        for n, max_new in cases:
            rows = np.stack(prompts([n, n], seed=20 + n))
            reqs.append((rows, max_new, srv.submit(rows, max_new=max_new)))
        for rows, max_new, req in reqs:
            want = np.stack([reference_answer(params, r, doc, max_new)
                             for r in rows])
            np.testing.assert_array_equal(
                req.future.result(timeout=180), want)
        rows = np.stack(prompts([19, 19], seed=39))
        chunks = list(srv.stream(rows, chunk=3, max_new=7))
        np.testing.assert_array_equal(
            np.concatenate(chunks, 1),
            np.stack([reference_answer(params, r, doc, 7) for r in rows]))
        perf = settled(2 * (6 + 9 + 7 + 7))
        assert srv.snapshot()["tick_errors_total"] == 0
    finally:
        srv.stop()
    prefill, served = perf["served_prefill"], perf["served_decode"]
    # 19 tokens at chunk 8 are three chunks a row, the later two carried
    assert prefill["rows"] == 2 * (1 + 1 + 3 + 3)
    assert prefill["carried_rows"] == 2 * (2 + 2)
    assert prefill["tokens"] == 2 * (3 + 8 + 19 + 19)
    # a prefill that picks a token returns logits, not a count of experts
    assert prefill["expert_slots"] == prefill["experts_read"] == 0
    rounds = recorded_spans.dispatches("decode")
    assert all(a["expert_slots"] == 4 * 4 * 8 for a in rounds)   # span x
    #                                       routed layers (not 6) x experts
    assert sum(a["expert_slots"] for a in rounds) == served["expert_slots"]
    assert 0 < served["experts_read"] < served["expert_slots"]
    assert sum(a["experts_read"] for a in recorded_spans.carrying(
        "/emit", "decode")) == served["experts_read"]
    assert served["inplace_steps"] == 0
    assert sum(a["carried_rows"] for a in recorded_spans.dispatches(
        "prefill")) == prefill["carried_rows"]


def test_genserver_preempts_and_readmits_mid_answer(model):
    """A pool too small for two whole rows: the younger is evicted, its
    blocks -- and the state kept at its first block's id -- go back, and on
    readmission it is recomputed from the prompt and the tokens it had
    emitted, from a zero state at position 0: the answer of an
    uninterrupted run."""
    doc, unit, params = model
    rows = prompts([6, 6], seed=31)
    want = [reference_answer(params, r, doc, 18) for r in rows]
    # each row grows to 6 + 18 positions = 6 blocks of 4 (+ a round's
    # slack); a pool of 10 holds both admissions, not both answers
    srv = server(unit, params, num_blocks=11)
    try:
        reqs = [srv.submit(r[None], max_new=18) for r in rows]
        for req, w in zip(reqs, want):
            np.testing.assert_array_equal(
                req.future.result(timeout=240)[0], w)
        assert srv.snapshot()["preempted_total"] >= 1
    finally:
        srv.stop()


def test_a_dense_server_counts_prefill_rows_and_carries_none(clean_genperf):
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
    assert perf["served_prefill"]["rows"] == 3      # three chunks, one row
    assert perf["served_prefill"]["carried_rows"] == 0
