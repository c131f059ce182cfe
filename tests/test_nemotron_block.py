"""The served path of a generator whose blocks have ONE sub-layer
(NVIDIA-Nemotron-3-Nano-30B-A3B's mechanisms, bench/configs/nemotron3-nano-
30b-a3b.json: Mamba-2 state-space layers whose float32 matrix state lives
beside two attention layers' K/V in one pool, attention without a rotary
embedding, expert layers of relu^2 experts with a shared expert and a
router scaling factor, told which of the experts they route over they hold)
at a tiny size on the CPU, in float32: ops/ssm.py's forms against each
other, the paged programs, the static lane and ``GenServer`` against the
plain reference of bench/archs/nemotron_h/, which shares no code with them.

Tolerances: logits within 1e-4 of values of order 1 (both sides float32,
the reference at ``highest``; what differs is the order of a few sums);
tokens exactly -- an argmax flips only on a tie of two float32 logits,
which these seeds do not have."""

import dataclasses
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
from seldon_core_tpu.models.served import served
from seldon_core_tpu.models.transformer import LMConfig, lm_apply, lm_init
from seldon_core_tpu.ops import ssm
from seldon_core_tpu.parallel.moe import dropless_init, moe_dropless
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.utils.genperf import GENPERF
from seldon_core_tpu.utils.hotrecord import SPINE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LETTER = {"M": "m", "E": "e", "*": "t"}
PATTERN = "MEMEM*E"


def _reference():
    path = os.path.join(REPO, "bench", "archs", "nemotron_h", "reference.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
UNIT = dict(
    vocab=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=7,
    layer_kinds="".join(LETTER[c] for c in PATTERN), conv_kernel=4,
    ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16, d_expert=24,
    n_experts=8, moe_k=3, experts_held=4, router="sigmoid_bias",
    router_scale=2.5, router_eps=1e-20, expert_act="relu2", d_shared=48,
    rope=False, tie_embeddings=False, norm_eps=1e-5, dtype="float32", seed=7)


def config(**unit):
    """The configuration file's keys at a tiny size (what the reference
    reads: half of eight experts held, as the file holds half of 128) and
    the unit built from them as the deployment builds it."""
    unit = {**UNIT, **unit}
    doc = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_hidden_layers=7,
               hybrid_override_pattern=PATTERN + "MEMEM*", conv_kernel=4,
               mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
               ssm_state_size=16, moe_intermediate_size=24,
               moe_shared_expert_intermediate_size=48,
               n_routed_experts=unit["experts_held"] or unit["n_experts"],
               published={"n_routed_experts": 8}, num_experts_per_tok=3,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               layer_norm_epsilon=1e-5, vocab_size=96,
               experts_first=unit.get("experts_first", 0))
    return doc, TransformerGenerator(**unit)


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
           ssm_inplace=None, how=None):
    B = len(token)
    return paged_decode_round_jit(
        params, pool, tables, jnp.asarray(token, jnp.int32),
        jnp.asarray(n_valid, jnp.int32), jnp.asarray(active, bool),
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.uint32), unit.cfg,
        span=span, temperature=0.0, top_k=0, top_p=0.0, eos_token=-1,
        ssm_inplace=ssm_inplace, **(how or {}))


TABLES = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]], jnp.int32)

# a decode round's step over gathered rows in jax.numpy (what the CPU
# decides for itself) and through the kernel of ops/ssm.py in Pallas
# interpret mode (what a TPU decides, as far as the CPU can run it)
BOTH_STEPS = pytest.mark.parametrize(
    "ssm_inplace", [None, "interpret"], ids=["step", "kernel"])


# -- the recurrence's forms against each other -------------------------------


def recurrence_case(W, seed=0, rows=3):
    H, P, G, N = 4, 8, 2, 16
    k = jax.random.split(jax.random.key(seed), 7)
    f32 = jnp.float32
    return dict(
        x=jax.random.normal(k[0], (rows, W, H, P), f32),
        dt=jax.nn.softplus(jax.random.normal(k[1], (rows, W, H), f32) - 2.0),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), f32, 0.0, 2.5)),
        Bm=jax.random.normal(k[3], (rows, W, G, N), f32),
        Cm=jax.random.normal(k[4], (rows, W, G, N), f32),
        D=jax.random.uniform(k[5], (H,), f32, 0.5, 1.5),
        h=jax.random.normal(k[6], (rows, H, P, N), f32))


@pytest.mark.parametrize("W, chunk", [(1, 256), (7, 256), (12, 4), (13, 4)])
def test_step_form_chunk_form_and_scan_give_the_same_numbers(W, chunk):
    """From a carried state that is not zero: the chunked form in one chunk
    and over whole chunks of 4 with a ragged tail, the step position by
    position, and the scan of steps."""
    c = recurrence_case(W)
    want_y, want_h = ssm.ssm_scan(**c)
    got_y, got_h = ssm.ssm_chunk(**c, chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_h, want_h, atol=2e-5, rtol=1e-5)
    h, ys = c["h"], []
    for t in range(W):
        y, h = ssm.ssm_step(c["x"][:, t], c["dt"][:, t], c["A"],
                            c["Bm"][:, t], c["Cm"][:, t], c["D"], h)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want_y, atol=2e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5, rtol=1e-5)


def test_a_position_whose_dt_is_zero_is_no_position():
    """Row 0 has 5 real positions of 9, row 1 none: what the pad holds
    moves neither a real position's output nor the state, and a row of no
    positions leaves its state as it was."""
    c = recurrence_case(9, seed=1, rows=2)
    real = jnp.asarray([5, 0])
    c["dt"] = jnp.where(jnp.arange(9)[None, :, None] < real[:, None, None],
                        c["dt"], 0.0)
    y, h = ssm.ssm_chunk(**c)
    short = {k: (v[:1, :5] if v.ndim > 1 and k != "h" else v)
             for k, v in c.items()}
    short["h"] = c["h"][:1]
    want_y, want_h = ssm.ssm_scan(**short)
    np.testing.assert_allclose(y[:1, :5], want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(h[:1], want_h, atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(h[1], c["h"][1])


# -- the step over the pool where it lies: the kernel -------------------------

TOY, PUBLISHED = (8, 8, 16), (64, 64, 128)      # H, P, N
NAN = float("nan")


@pytest.mark.parametrize("shape, G, live, fresh, dirty", [
    (TOY, 2, [1, 0, 1, 0, 1], [], 0.0),             # pads between live rows
    (TOY, 2, [0, 1, 1], [1], NAN),      # a row at start 0 over a dirty entry
    (TOY, 2, [0, 0, 0, 0], [0, 2], 0.0),            # nobody live
    (TOY, 1, [1, 1, 0, 1], [3], 0.0),               # one group of 8 heads
    (TOY, 8, [1], [], 0.0),                 # a head a group, a batch of one
    (TOY, 2, [1] * 8, [0, 5], 0.0),                 # a batch of 8
    (TOY, 2, [1] * 5 + [0] * 4 + [1] * 7, [2], 0.0),    # ... of 16
    (PUBLISHED, 8, [1, 0, 1], [2], NAN),            # the published entry
    (PUBLISHED, 1, [1, 1], [], 0.0),
    ((4, 256, 128), 2, [0, 1], [], 0.0),    # a head of two chunks of rows
], ids=["pads-between", "fresh-over-dirty", "nobody-live", "one-group",
        "head-a-group-batch-1", "batch-8", "batch-16", "published",
        "published-one-group", "wide-head"])
def test_the_step_kernel_gives_ssm_steps_numbers_where_the_state_lies(
        shape, G, live, fresh, dirty):
    """``ssm_step_pool`` (the Pallas kernel in interpret mode) against
    ``ssm_step`` over gathered rows: ``y`` of the live rows and their
    entries within 1e-5 of values of order 1 -- float32 both, what differs
    is the order of the read-out's sum -- and every other entry of the
    pool, the scratch entry 0 and the entries of rows that are not live
    among them, bit for bit what it was.  A row at ``start`` 0 reads zeros
    whatever its entry holds (``dirty``: NaN there)."""
    H, P, N = shape
    B = len(live)
    k = jax.random.split(jax.random.key(B + G), 7)
    f32 = jnp.float32
    x = jax.random.normal(k[0], (B, H, P), f32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, H), f32) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), f32, 0.0, 2.5))
    Bm = jax.random.normal(k[3], (B, G, N), f32) / np.sqrt(N)
    Cm = jax.random.normal(k[4], (B, G, N), f32)
    D = jax.random.uniform(k[5], (H,), f32, 0.5, 1.5)
    pool = np.array(jax.random.normal(k[6], (B + 2, H, P, N), f32))
    slot = np.random.default_rng(B).permutation(np.arange(1, B + 2))[:B]
    start = np.where(np.isin(np.arange(B), fresh), 0, 5).astype(np.int32)
    if dirty != 0.0:
        pool[slot[start == 0]] = dirty
    live = np.asarray(live, bool)
    y, h = ssm.ssm_step_pool(
        x, dt, A, Bm, Cm, D, jnp.asarray(pool), jnp.asarray(slot),
        jnp.asarray(start), jnp.asarray(live), interpret=True)
    carried = jnp.where((start > 0)[:, None, None, None], pool[slot], 0.0)
    want_y, want_h = ssm.ssm_step(x, dt, A, Bm, Cm, D, carried)
    y, h = np.asarray(y), np.asarray(h)
    np.testing.assert_allclose(y[live], np.asarray(want_y)[live], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(h[slot[live]], np.asarray(want_h)[live],
                               atol=1e-5, rtol=1e-5)
    untouched = np.setdiff1d(np.arange(B + 2), slot[live])
    np.testing.assert_array_equal(h[untouched], pool[untouched])
    assert 0 in untouched                           # the scratch entry


#: what the kernel is offered at the published widths
OFFER = dict(backend="tpu", state_dtype=jnp.float32, heads=64, head_dim=64,
             groups=8, state=128, rows=16)


@pytest.mark.parametrize("change, serves", [
    ({}, True),
    ({"rows": 1}, True),
    ({"groups": 1}, True),
    ({"backend": "cpu"}, False),                    # Mosaic is the TPU's
    ({"state_dtype": jnp.bfloat16}, False),     # the tiles are float32's
    ({"mesh": object()}, False),        # a Mosaic call does not partition
    ({"state": 192}, False),            # N no whole 128-lane registers
    ({"head_dim": 60}, False),          # P no whole sublane tiles
    ({"heads": 60}, False),             # no whole groups of heads
    ({"head_dim": 96}, False),      # 128 rows are no whole heads of 96
    ({"state": 4096}, False),       # not an iteration's rows of it fit
    ({"heads": 1024, "groups": 1, "head_dim": 128}, False),    # nor the x, y
], ids=["published", "one-row", "one-group", "cpu", "bfloat16-state",
        "mesh", "state-192", "head-60", "heads-60", "head-96", "no-tile",
        "vmem"])
def test_step_supported_chooses_by_what_it_can_observe(change, serves):
    assert ssm.step_supported(**{**OFFER, **change}) is serves


def test_the_pools_owner_asks_for_the_state_space_layers_apart(
        model, monkeypatch):
    """``generate.ssm_fused`` reads the question off the pool: the CPU says
    no; a TPU backend yes for the published entry, no under a mesh and for
    a state kept in bfloat16; a pool without such layers no.  It is not the
    attention layers' answer, and ``Served.kernels`` keeps the two apart."""
    doc, unit, params = model
    pool = jax.eval_shape(lambda: init_block_pool(dataclasses.replace(
        unit.cfg, ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_state=128), 4, 4))
    assert not G.ssm_fused(pool, rows=16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert G.ssm_fused(pool, rows=16)
    assert not G.ssm_fused(pool, object(), rows=16)
    half = {**pool, "l0": {**pool["l0"], "h": jax.ShapeDtypeStruct(
        pool["l0"]["h"].shape, jnp.bfloat16)}}
    assert not G.ssm_fused(half, rows=16)
    assert not G.ssm_fused({"l0": pool["l5"]}, rows=16)
    # the toy's state of 16 is no whole register: its server takes the step
    toy = init_block_pool(unit.cfg, 4, 4)
    assert not G.ssm_fused(toy)
    k = served(unit.cfg).kernels(toy, None, 4, jnp.float32)
    assert not k.ssm_inplace and not k.states_inplace
    monkeypatch.setattr(G, "ssm_fused", lambda *a, **kw: "interpret")
    monkeypatch.setattr(G, "decode_inplace", lambda *a, **kw: False)
    k = served(unit.cfg).kernels(toy, None, 4, jnp.float32)
    # (the backend says "tpu" here: the expert layers' answer is asked of
    # the toy's widths, no whole registers, and of the published ones)
    assert k.round_how == {"inplace": False, "ssm_inplace": "interpret",
                           "experts_fused": False}
    assert k.round_counts(8, 8) == {"inplace_steps": 0,
                                    "retention_fused_steps": 0,
                                    "ssm_fused_steps": 8,
                                    "experts_fused_passes": 0}
    wide = dataclasses.replace(unit.cfg, d_model=2688, d_expert=1856)
    assert G.experts_fused(wide, None, jnp.bfloat16)
    assert not G.experts_fused(wide, object(), jnp.bfloat16)


# -- the programs against the reference ------------------------------------


def test_the_pool_holds_kv_states_and_nothing_by_the_layers_kind(model):
    doc, unit, params = model
    pool = init_block_pool(unit.cfg, 16, 4)
    for i, letter in enumerate(PATTERN):
        shapes = {k: (v.shape, v.dtype) for k, v in pool[f"l{i}"].items()}
        if letter == "M":
            assert shapes == {"conv": ((16, 3, 64 + 2 * 2 * 16), jnp.float32),
                              "h": ((16, 8, 8, 16), jnp.float32)}
            assert sorted(params[f"l{i}"]) == sorted([
                "ln1", "ssm_in", "conv_w", "conv_b", "A_log", "dt_bias",
                "ssm_D", "ssm_norm", "ssm_out"])
        elif letter == "*":
            assert sorted(shapes) == ["k", "v"]
            assert pool[f"l{i}"]["k"].shape == (16, 4, 2, 16)
            assert sorted(params[f"l{i}"]) == ["ln1", "wo", "wqkv"]
        else:
            assert shapes == {}
            assert sorted(params[f"l{i}"]) == sorted([
                "ln2", "router", "expert_bias", "e_up", "e_down", "s_up",
                "s_down"])
            assert params[f"l{i}"]["router"].shape == (32, 8)
            assert params[f"l{i}"]["e_up"].shape == (4, 24, 32)
    # the state is float32 whatever the model computes in
    half = init_block_pool(dataclasses.replace(unit.cfg, dtype=jnp.bfloat16),
                           4, 4)
    assert half["l0"]["h"].dtype == jnp.float32
    assert unit.cfg.expert_layers == 3 and unit.cfg.held == 4
    assert G._pool_kv(pool) is pool["l5"]
    assert not G.retention_fused(pool)
    l0 = params["l0"]
    assert l0["A_log"].dtype == l0["dt_bias"].dtype == jnp.float32
    step = jax.nn.softplus(l0["dt_bias"])
    assert 0.001 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6
    assert 1.0 <= float(jnp.exp(l0["A_log"]).min())
    assert float(jnp.exp(l0["A_log"]).max()) <= 16.0
    assert float(jnp.abs(l0["conv_b"]).min()) > 0


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


@BOTH_STEPS
@pytest.mark.parametrize("chunk", [16, 8, 4, 3, 1])
def test_chunked_prefill_then_decode_rounds_equal_the_reference(
        model, chunk, ssm_inplace):
    """The same two prompts (13 and 8 tokens: unequal, so every call but a
    whole one has pad positions or a row of width 0) in one, two and four
    chunks, and in chunks shorter than the convolution's history; then two
    decode rounds through the pool, teacher-checked: every token is the
    argmax of the reference's whole forward pass over the row so far."""
    chunks_then_rounds(model, chunk, ssm_inplace)


def test_the_fused_expert_call_serves_the_chunks_and_the_rounds(model):
    """The same, as far as the CPU can run what the chip decides: both
    programs with an expert's feed-forward as ONE Pallas call in interpret
    mode (``experts_fused="interpret"``: relu^2 over the transposed up
    matrix, the held range, the shared expert beside it), the rounds with
    the state-space kernel too."""
    chunks_then_rounds(model, 4, "interpret",
                       {"experts_fused": "interpret"})


def chunks_then_rounds(model, chunk, ssm_inplace, how=None):
    doc, unit, params = model
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
            ssm_inplace, how)
        got.append(np.asarray(toks))
    got = np.concatenate(got, axis=1)
    for i, r in enumerate(rows):
        np.testing.assert_array_equal(
            got[i], reference_answer(params, r, doc, 9))
    # and the state the rounds left is the row's: one more token through a
    # prefill of width 1 lands on the reference
    seq = np.concatenate([rows[0], got[0]])
    nxt, _ = paged_forward_jit(
        params, jnp.asarray(seq[None, -1:]), pool, TABLES[:1],
        jnp.asarray([len(seq) - 1], jnp.int32), jnp.asarray([1], jnp.int32),
        cfg=unit.cfg, last_only=True, **(how or {}))
    np.testing.assert_allclose(np.asarray(nxt[0]),
                               reference_logits(params, seq, doc)[-1],
                               atol=1e-4, rtol=0)


@BOTH_STEPS
def test_an_inactive_row_writes_scratch_and_leaves_a_live_state_alone(
        model, ssm_inplace):
    """A decode round with an empty slot whose table is all zeros (what
    the scheduler pads with): the live row's tokens are what they are
    alone, and the empty slot touched nothing but block 0's entries --
    under the kernel not even the scratch entry's ``h``."""
    doc, unit, params = model
    rows = prompts([9, 6], seed=3)
    logits, pool = chunked(unit, params, rows, 16, TABLES)
    before = jax.tree.map(np.asarray, pool)
    tables = np.asarray(TABLES).copy()
    tables[1] = 0
    toks, pool, *_ = decode(
        unit, params, pool, jnp.asarray(tables),
        [int(logits[0].argmax()), 0], [9, 0], [True, False], 4, ssm_inplace)
    np.testing.assert_array_equal(
        np.asarray(toks)[0], reference_answer(params, rows[0], doc, 5)[1:])
    assert not np.asarray(toks)[1].any()
    if ssm_inplace:
        np.testing.assert_array_equal(np.asarray(pool["l0"]["h"])[0],
                                      before["l0"]["h"][0])
    # row 1's states (at its first block, 7) are as its prefill left them
    for i, letter in enumerate(PATTERN):
        if letter == "M":
            for name in ("conv", "h"):
                np.testing.assert_array_equal(
                    np.asarray(pool[f"l{i}"][name])[7],
                    before[f"l{i}"][name][7])
                assert np.abs(before[f"l{i}"][name][7]).max() > 0


@BOTH_STEPS
def test_a_reused_block_needs_no_reset(model, ssm_inplace):
    """A sequence that starts at position 0 reads a zero state whatever its
    first block held: after another sequence's prefill and rounds over the
    same blocks, a new prompt there gives the reference's logits."""
    doc, unit, params = model
    old, new = prompts([11, 7], seed=4)
    logits, pool = chunked(unit, params, [old], 4, TABLES[:1])
    _, pool, *_ = decode(unit, params, pool, TABLES[:1],
                         [int(logits[0].argmax())], [11], [True], 4,
                         ssm_inplace)
    assert float(jnp.abs(pool["l0"]["conv"][1]).max()) > 0
    assert float(jnp.abs(pool["l0"]["h"][1]).max()) > 0
    logits, pool = chunked(unit, params, [new], 3, TABLES[:1], pool=pool)
    np.testing.assert_allclose(
        logits[0], reference_logits(params, new, doc)[-1], atol=1e-4, rtol=0)


def test_copying_a_block_copies_both_states_kept_at_its_id(model):
    doc, unit, params = model
    row = prompts([6], seed=5)[0]
    _, pool = chunked(unit, params, [row], 16, TABLES[:1])
    want = {name: np.asarray(pool["l0"][name][1]) for name in ("conv", "h")}
    pool = paged_copy_block_jit(pool, jnp.int32(1), jnp.int32(9))
    for name in ("conv", "h"):
        assert np.abs(want[name]).max() > 0
        np.testing.assert_array_equal(np.asarray(pool["l0"][name][9]),
                                      want[name])
    np.testing.assert_array_equal(np.asarray(pool["l5"]["k"][9]),
                                  np.asarray(pool["l5"]["k"][1]))
    assert pool["l1"] == {}


def test_static_lane_gives_the_reference_answer(model):
    doc, unit, params = model
    rows = np.stack(prompts([10, 10], seed=6))
    want = np.stack([reference_answer(params, r, doc, 11) for r in rows])
    np.testing.assert_array_equal(np.asarray(generate(
        params, jnp.asarray(rows), unit.cfg, max_new_tokens=11)), want)
    chunks = list(stream_chunks(params, jnp.asarray(rows), unit.cfg,
                                max_new_tokens=11, chunk=4))
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), want)


# -- the expert layer: its form and the chip's share -------------------------


def expert_case(**kw):
    cfg = LMConfig(**{**dict(
        d_model=16, n_heads=2, d_expert=8, n_experts=6, moe_k=2,
        router="sigmoid_bias", router_scale=2.5, router_eps=1e-20,
        expert_act="relu2", d_shared=12, dtype=jnp.float32), **kw})
    lp = dropless_init(jax.random.key(3), cfg)
    h = jax.random.normal(jax.random.key(4), (1, 5, cfg.d_model),
                          jnp.float32)
    return cfg, lp, h


def by_hand(lp, h, cfg, first=0, held=None, scale=2.5, eps=1e-20,
            shared=True):
    """The published layer in float64: every routed expert of ``[first,
    first + held)`` a chosen token weighs, and the shared expert."""
    x = np.asarray(h, np.float64).reshape(-1, h.shape[-1])
    score = 1.0 / (1.0 + np.exp(-x @ np.asarray(lp["router"], np.float64)))
    biased = score + np.asarray(lp["expert_bias"], np.float64)
    held = cfg.n_experts if held is None else held
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-biased[t], kind="stable")[:cfg.moe_k]
        w = score[t, top] / (score[t, top].sum() + eps) * scale
        for e, we in zip(top, w):
            if first <= e < first + held:
                up = np.maximum(
                    x[t] @ np.asarray(lp["e_up"][e - first], np.float64).T,
                    0)
                out[t] += we * (up ** 2
                                @ np.asarray(lp["e_down"][e - first],
                                             np.float64))
        if shared:
            up = np.maximum(x[t] @ np.asarray(lp["s_up"], np.float64), 0)
            out[t] += up ** 2 @ np.asarray(lp["s_down"], np.float64)
    return out.reshape(h.shape)


def test_relu2_experts_the_scale_the_eps_and_the_shared_expert_by_hand():
    cfg, lp, h = expert_case()
    valid = jnp.ones((1, 5), bool)
    y, read = moe_dropless(lp, h, valid, cfg, impl="ragged_dot")
    want = by_hand(lp, h, cfg)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert read.shape == () and 2 <= int(read) <= 6
    # each left out reads as something else
    for wrong in (dict(scale=1.0), dict(shared=False)):
        assert np.abs(by_hand(lp, h, cfg, **wrong) - want).max() > 1e-2
    # a pad position gets nothing, not even the shared expert
    y, _ = moe_dropless(lp, h, valid.at[0, 3].set(False), cfg,
                        impl="ragged_dot")
    assert not np.asarray(y)[0, 3].any()
    np.testing.assert_allclose(np.asarray(y)[0, :3], want[0, :3], atol=1e-5)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_whole():
    """The share test: the layer told it holds experts 0-2 and the layer
    told it holds 3-5, each WITHOUT the shared expert (every chip computes
    that alike), plus the shared expert counted once, add up to the uncut
    reference's whole layer; and each share alone is what the reference
    gives when it is given the same share."""
    cfg, lp, h = expert_case()
    valid = jnp.ones((1, 5), bool)
    t = h.reshape(5, 16)
    whole = np.asarray(REF._experts(lp, t, 2, True, 2.5, 0)).reshape(h.shape)
    np.testing.assert_allclose(whole, by_hand(lp, h, cfg), atol=1e-5)
    shared = np.asarray(REF._relu2(t, lp["s_up"], lp["s_down"])).reshape(
        h.shape)
    total, picks = 0.0, 0
    for first in (0, 3):
        half = dataclasses.replace(cfg, experts_held=3, experts_first=first)
        part = {**lp, "e_up": lp["e_up"][first:first + 3],
                "e_down": lp["e_down"][first:first + 3]}
        y, counted = moe_dropless(part, h, valid, half, impl="ragged_dot")
        assert counted.shape == (2,) and int(counted[0]) <= 3
        picks += int(counted[1])
        same = np.asarray(REF._experts(part, t, 2, True, 2.5, first))
        np.testing.assert_allclose(np.asarray(y), same.reshape(h.shape),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(y), by_hand(part, h, cfg, first=first, held=3),
            atol=1e-5)
        total = total + np.asarray(y) - shared
    np.testing.assert_allclose(total + shared, whole, atol=1e-5)
    assert picks == 5 * 2           # every real pick fell on one share
    # a held range shifted by one is another layer
    off = dataclasses.replace(cfg, experts_held=3, experts_first=1)
    y, _ = moe_dropless({**lp, "e_up": lp["e_up"][:3],
                         "e_down": lp["e_down"][:3]}, h, valid, off,
                        impl="ragged_dot")
    assert np.abs(np.asarray(y) - by_hand(lp, h, cfg, held=3)).max() > 1e-2


def test_the_grouped_kernel_serves_the_share_too():
    cfg, lp, h = expert_case(experts_held=3, experts_first=3, d_model=128,
                             d_expert=128, d_shared=128)
    valid = jnp.ones((1, 5), bool).at[0, 4].set(False)
    want, a = moe_dropless(lp, h, valid, cfg, impl="ragged_dot")
    got, b = moe_dropless(lp, h, valid, cfg, impl="gmm_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_width_that_is_no_multiple_of_128_stays_whole_and_k_is_cut(
        monkeypatch):
    """1856 = 29 x 64 has no tile of whole 128-lane registers that divides
    it: the grouped kernel then streams the width whole and cuts the
    CONTRACTION into whole tiles that divide it, so no tile reaches past
    its matrix -- here experts of 192 on a model of 256 under a budget of
    a bit over half a matrix: the up matmul (x [.., 256] by [192, 256],
    stored output-major) in two tiles of 128 x 192, the down matmul in two
    of 192 x 128."""
    from jax.experimental.pallas.ops.tpu import megablox

    from seldon_core_tpu.parallel import moe

    assert moe._weight_tile(2688, 1856, 2) == (896, 1856)
    assert moe._weight_tile(1856, 2688, 2) == (1856, 896)
    # the tiles the accepted cells' widths were measured at stand
    assert moe._weight_tile(2048, 1536, 2) == (2048, 1536)
    assert moe._weight_tile(2048, 3584, 2) == (2048, 896)
    assert moe._weight_tile(1792, 2048, 2) == (1792, 1024)
    cfg, lp, h = expert_case(d_model=256, d_expert=192, d_shared=0,
                             experts_held=3)
    assert lp["e_up"].shape == (3, 192, 256)        # output-major
    valid = jnp.ones((1, 5), bool)
    want, _ = moe_dropless(lp, h, valid, cfg, impl="ragged_dot")
    seen = []
    real = megablox.gmm

    def spy(x, w, sizes, **kw):
        seen.append(kw["tiling"])
        return real(x, w, sizes, **kw)

    monkeypatch.setattr(megablox, "gmm", spy)
    monkeypatch.setattr(moe, "_WEIGHT_TILE_BYTES", 110_000)
    got, _ = moe_dropless(lp, h, valid, cfg, impl="gmm_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert [t[1:] for t in seen] == [(128, 192), (192, 128)]


def test_a_share_is_a_range_of_the_routers_own_outputs():
    with pytest.raises(ValueError, match="reach past n_experts"):
        LMConfig(d_expert=8, n_experts=6, moe_k=2, experts_held=4,
                 experts_first=3)
    with pytest.raises(ValueError, match="needs d_expert"):
        LMConfig(n_layers=2, layer_kinds="me", ssm_heads=2, ssm_head_dim=4,
                 ssm_state=4)
    with pytest.raises(ValueError, match="needs ssm_heads"):
        LMConfig(n_layers=1, layer_kinds="m")
    with pytest.raises(ValueError, match="expert_act"):
        LMConfig(expert_act="gelu")


# -- the unit's description of its layers -----------------------------------


def test_the_units_pattern_string_is_the_files_published_pattern():
    """The deployment document carries the pattern as ONE string of this
    repo's letters; the file keeps the published string for the reference.
    They say the same, and the unit built from the file has its kinds."""
    with open(os.path.join(REPO, "bench", "configs",
                           "nemotron3-nano-30b-a3b.json")) as f:
        doc = json.load(f)
    p = doc["unit"]["parameters"]
    n = doc["num_hidden_layers"]
    assert n == 14 and len(doc["hybrid_override_pattern"]) == 52
    assert p["layer_kinds"] == "".join(
        LETTER[c] for c in doc["hybrid_override_pattern"][:n])
    cfg = LMConfig(
        vocab=doc["vocab_size"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        n_layers=n, layer_kinds=p["layer_kinds"],
        d_expert=doc["moe_intermediate_size"], n_experts=p["n_experts"],
        experts_held=doc["n_routed_experts"],
        moe_k=doc["num_experts_per_tok"], router=p["router"],
        ssm_heads=doc["mamba_num_heads"], ssm_head_dim=doc["mamba_head_dim"],
        ssm_groups=doc["n_groups"], ssm_state=doc["ssm_state_size"],
        conv_kernel=doc["conv_kernel"], rope=False)
    assert [k for k in cfg.kinds] == [
        {"M": ("ssm", None), "E": (None, "experts"), "*": ("attn", None)}[c]
        for c in doc["hybrid_override_pattern"][:n]]
    assert (cfg.expert_layers, cfg.held, cfg.n_experts) == (6, 64, 128)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.hd) == (4096, 6144, 128)


def block_functions(lowered) -> int:
    """Private functions the lowered module holds for the block: one a
    distinct trace of ``_paged_block`` (tests/test_generate_trace_once.py)."""
    return len(set(re.findall(r"func\.func private @(_paged_block\w*)\(",
                              lowered.as_text())))


def test_a_program_traces_the_block_once_a_kind(model):
    doc, unit, params = model
    assert len(set(unit.cfg.kinds)) == 3
    lowered = paged_forward_jit.lower(
        params, jnp.zeros((2, 4), jnp.int32),
        init_block_pool(unit.cfg, 16, 4), TABLES,
        jnp.zeros((2,), jnp.int32), jnp.full((2,), 4, jnp.int32),
        cfg=unit.cfg)
    assert block_functions(lowered) == 3
    assert len(re.findall(r"call @_paged_block", lowered.as_text())) == 7


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
    for scope in ("ssm_in/", "ssm_conv/", "ssm/", "ssm_out/", "qkv/",
                  "kv_write/", "kv_gather/", "attn/", "wo/", "ffn/router/",
                  "ffn/experts/", "ffn/shared_expert/"):
        assert "/" + scope in text, scope
    assert "/rope/" not in text         # the published attention has none


# -- what the scheduler is told ----------------------------------------------


def test_the_description_counts_held_experts_and_the_states_bytes(model):
    doc, unit, params = model
    d = served(unit.cfg)
    assert (d.holds, d.stateful, d.picks_first, d.quantum) == (
        "KV", True, True, 1)
    assert (d.routed, d.experts, d.counts_experts) == (3, 4, True)
    # three state-space layers: the taps [3, 128] and the state [8, 8, 16]
    assert d.ssm_row_bytes == 3 * 4 * (3 * 128 + 8 * 8 * 16)
    assert d.retention_row_bytes == 0
    assert d.round_counts([6, 13], 8)["expert_slots"] == 8 * 3 * 4
    assert d.prefill_counts([0, 8], [8, 3])["carried_rows"] == 1
    # a token's weights: of its 3 picks half fall on held experts
    costs = d.decode_costs()
    mixer = 32 * (2 * 64 + 128 + 8)
    attn = 32 * (64 + 2 * 32) + 64 * 32
    ffn = 32 * (8 + 2 * (3 * 24 * 0.5 + 48))
    assert costs["flops"] == 2 * (3 * mixer + attn + 3 * ffn + 32 * 96)
    assert costs["kv_bytes_per_position"] == 2 * 2 * 16 * 2
    # the boot refuses a pool whose state entries the device cannot hold
    shapes = jax.eval_shape(lambda: lm_init(jax.random.key(0), unit.cfg))
    d.refuse_pool(1024, shapes, lambda: None)
    with pytest.raises(ValueError, match="a BLOCK of the pool.*1024 blocks"
                                         ".*few, large blocks.*"
                                         "SELDON_TPU_GEN_POOL_BLOCKS"):
        d.refuse_pool(1024, shapes, lambda: 1 << 20)


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
    with pytest.raises(ValueError, match="served on one chip"):
        TransformerGenerator(**UNIT, mesh=object())
    with pytest.raises(ValueError, match="no ``ep`` mesh"):
        TransformerGenerator(vocab=96, d_model=32, n_heads=4, n_layers=2,
                             d_expert=8, n_experts=8, experts_held=4,
                             mesh=object())
    with pytest.raises(ValueError, match="roll the layer's state back"):
        pool = init_block_pool(unit.cfg, 16, 4)
        paged_spec_round(params, d_params, pool,
                         init_block_pool(draft.cfg, 16, 4), TABLES, TABLES,
                         jnp.zeros((2,), jnp.int32),
                         jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
                         unit.cfg, draft.cfg, k=2)
    with pytest.raises(ValueError, match="denoising passes"):
        LMConfig(vocab=96, n_layers=1, layer_kinds="m", ssm_heads=2,
                 ssm_head_dim=4, ssm_state=4, block_length=4,
                 denoising_steps=4, mask_id=5)
    with pytest.raises(ValueError, match="cache-free forward"):
        lm_apply(params, jnp.zeros((1, 4), jnp.int32), unit.cfg)


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


@pytest.mark.parametrize("fused", [False, "interpret"],
                         ids=["step", "kernel"])
def test_genserver_serves_the_reference_answer_and_counts_its_work(
        model, clean_genperf, recorded_spans, monkeypatch, fused):
    """Rows of different lengths co-scheduled, prompts of one chunk and of
    three (states carried over chunks), unary and streamed -- and what the
    server says of it: the state bytes its rows read and wrote, the picks
    that fell on held experts beside the slots of the held experts, and who
    updated the states: on the CPU ``ssm_step`` over gathered rows
    (``ssm_fused`` says no), and the kernel in Pallas interpret mode where
    it is made to say "interpret", as a TPU says yes."""
    doc, unit, params = model
    # the chunk stays 8: the scheduler does not probe a wider one
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "8")
    if fused:
        monkeypatch.setattr(G, "ssm_fused", lambda *a, **kw: fused)
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
        assert srv._kernels.ssm_inplace == fused
        assert not srv._kernels.attends_inplace
        assert not srv._kernels.states_inplace
    finally:
        srv.stop()
    prefill, dec = perf["served_prefill"], perf["served_decode"]
    assert dec["ssm_fused_steps"] == (dec["device_steps"] if fused else 0)
    assert dec["device_steps"] > 0
    assert dec["inplace_steps"] == dec["retention_fused_steps"] == 0
    # 19 tokens at chunk 8 are three chunks a row, the later two carried
    assert prefill["rows"] == 2 * (1 + 1 + 3 + 3)
    assert prefill["carried_rows"] == 2 * (2 + 2)
    row_bytes = served(unit.cfg).ssm_row_bytes
    assert prefill["ssm_state_bytes"] == 2 * row_bytes * prefill["rows"]
    assert dec["ssm_state_bytes"] == 2 * row_bytes * dec["row_passes"] > 0
    assert dec["retention_state_bytes"] == 0
    # a count only this kind makes is shown where it was made: a dense
    # generator's document reads as it did (tests/test_genperf.py)
    GENPERF.reset()
    empty = GENPERF.document()["served_decode"]
    assert "ssm_state_bytes" not in empty
    assert empty["expert_slots_held"] == 0      # a layer metric's path
    # a prefill that picks a token returns logits, not a count of experts
    assert prefill["expert_slots"] == prefill["experts_read"] == 0
    assert "expert_slots_held" not in prefill       # nothing counts it there
    rounds = recorded_spans.dispatches("decode")
    assert all(a["expert_slots"] == 4 * 3 * 4 for a in rounds)   # span x
    #                                  expert layers x the experts HELD
    assert sum(a["expert_slots"] for a in rounds) == dec["expert_slots"]
    assert 0 < dec["experts_read"] < dec["expert_slots"]
    emits = recorded_spans.carrying("/emit", "decode")
    assert sum(a["experts_read"] for a in emits) == dec["experts_read"]
    assert sum(a["expert_slots_held"] for a in emits) == (
        dec["expert_slots_held"])
    # a real row-pass picks moe_k = 3 experts in each of 3 layers; about
    # half of the picks fall on the half of the experts held here
    picks = dec["row_passes"] * 3 * 3
    assert 0.3 * picks < dec["expert_slots_held"] < 0.7 * picks
    assert sum(a["carried_rows"] for a in recorded_spans.dispatches(
        "prefill")) == prefill["carried_rows"]


def test_genserver_preempts_and_readmits_mid_answer(model):
    """A pool too small for two whole rows: the younger is evicted, its
    blocks -- and the states kept at its first block's id -- go back, and
    on readmission it is recomputed from the prompt and the tokens it had
    emitted, from a zero state at position 0: the answer of an
    uninterrupted run."""
    doc, unit, params = model
    rows = prompts([6, 6], seed=31)
    want = [reference_answer(params, r, doc, 18) for r in rows]
    srv = server(unit, params, num_blocks=11)
    try:
        reqs = [srv.submit(r[None], max_new=18) for r in rows]
        for req, w in zip(reqs, want):
            np.testing.assert_array_equal(
                req.future.result(timeout=240)[0], w)
        assert srv.snapshot()["preempted_total"] >= 1
    finally:
        srv.stop()
