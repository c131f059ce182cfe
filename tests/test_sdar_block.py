"""The served path of a generator by diffusion over blocks with dropless
routed experts (SDAR-30B-A3B-Chat's mechanisms, bench/configs/
sdar-30b-a3b.json) at a tiny size on the CPU, in float32: the paged
programs, the static lane and ``GenServer`` against the plain reference
of bench/archs/sdar_moe/, which shares no code with them.

Tolerances: tokens are compared exactly.  Both sides compute in float32
(the reference at ``highest``; XLA:CPU's float32 dots are exact to
rounding), the weights are seeded, and an argmax or a confidence ranking
flips only on a tie of two float32 logits, which these seeds do not have;
the expert layer against its loop is held to 1e-5 of values of order 1
(summation order over the chosen experts)."""

import functools
import importlib.util
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
    paged_decode_round_jit,
    paged_forward_jit,
    stream_chunks,
)
from seldon_core_tpu.models.transformer import LMConfig
from seldon_core_tpu.parallel.moe import dropless_init, moe_dropless
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.runtime.qos import qos_scope
from seldon_core_tpu.utils.costledger import LEDGER
from seldon_core_tpu.utils.genperf import GENPERF
from seldon_core_tpu.utils.hotrecord import SPINE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MASK = 90


def _reference():
    path = os.path.join(REPO, "bench", "archs", "sdar_moe", "reference.py")
    spec = importlib.util.spec_from_file_location("sdar_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def config(steps=4):
    """The configuration file's keys at a tiny size (what the reference
    reads) and the unit built from them as the deployment builds it."""
    doc = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_hidden_layers=2, rope_theta=10000.0,
               rms_norm_eps=1e-5, block_length=4, denoising_steps=steps,
               mask_token_id=MASK, num_experts=8, num_experts_per_tok=2,
               norm_topk_prob=True, moe_intermediate_size=16, vocab_size=96)
    unit = TransformerGenerator(
        vocab=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
        n_layers=2, qk_norm=True, norm_eps=1e-5, tie_embeddings=False,
        d_expert=16, n_experts=8, moe_k=2, moe_norm_topk=True,
        block_length=4, denoising_steps=steps, mask_id=MASK,
        dtype="float32", rope_base=10000.0, seed=5)
    return doc, unit


@pytest.fixture(scope="module", params=[4, 2], ids=["steps4", "steps2"])
def model(request):
    doc, unit = config(request.param)
    yield doc, unit, unit.init_state(None)["params"]
    # the programs of one schedule are of no use to the other, and a worker
    # that keeps every executable of both runs out of room to compile in
    _plain_jit.cache_clear()
    jax.clear_caches()


def _op_paths(lowered) -> str:
    """The scope path of every op of the compiled program, a line each --
    XLA's ``op_name``, which a trace's ``tf_op`` repeats -- with the
    block's own ``jit`` taken out (and the head's, which a round of
    several blocks binds once for its three places): a stage lies under the
    pass that runs it (``.../denoise/jit(_paged_block)/attn/...``)."""
    return re.sub(r"jit\((_paged_block|fix)\)/", "", "\n".join(re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text())))


def reference_answer(params, prompt, doc, max_new, eos=-1):
    """The greedy answer as the published generation has it, every pass a
    whole forward of the reference over the row so far: no cache."""
    L, steps = doc["block_length"], doc["denoising_steps"]
    seq = [int(t) for t in prompt]
    n0 = len(seq)
    while len(seq) - n0 < max_new:
        start = len(seq) - len(seq) % L
        block = seq[start:] + [MASK] * (L - (len(seq) - start))
        masked = np.arange(L) >= len(seq) - start
        for _ in range(steps):
            ids = np.asarray([seq[:start] + block], np.int32)
            logits = np.array(REF.forward(
                params, jnp.asarray(ids), doc,
                jnp.asarray([start + np.arange(L)]),
                jnp.asarray([ids.shape[1]]))[0])
            logits[:, MASK] = -np.inf
            chose = logits.argmax(-1)
            peak = logits.max(-1, keepdims=True)
            sure = 1.0 / np.exp(logits - peak).sum(-1)
            sure = np.where(masked, sure, -1.0)
            for i in np.argsort(-sure, kind="stable")[:L // steps]:
                if masked[i]:
                    block[i], masked[i] = int(chose[i]), False
        seq = seq[:start] + block
    out = seq[n0:n0 + max_new]
    if eos in out:
        out = out[:out.index(eos) + 1] + [eos] * (
            max_new - out.index(eos) - 1)
    return np.asarray(out, np.int32)


def prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, MASK, n).astype(np.int32) for n in lens]


# -- (a) the paged programs against the reference ---------------------------


@pytest.mark.parametrize("experts_fused", [None, "interpret"],
                         ids=["grouped-matmuls", "experts-fused"])
def test_chunked_prefill_then_a_round_equal_the_reference(model,
                                                          experts_fused):
    """``paged_forward`` chunk by chunk (chunks of 8 = two blocks) and one
    ``paged_decode_round`` of two blocks over rows whose prompts are and are
    not a whole number of blocks: the prefill's logits to rounding, the
    round's tokens to the id -- with the expert layers as the CPU runs them
    and with an expert's feed-forward as the ONE Pallas call the chip runs
    (``experts_fused="interpret"``, both programs)."""
    doc, unit, params = model
    how = {"experts_fused": experts_fused} if experts_fused else {}
    lens = [5, 8, 13, 16]
    rows = prompts(lens)
    bs, C, span = 8, 8, 8
    pool = init_block_pool(unit.cfg, 16, bs)
    tables = jnp.asarray(1 + np.arange(12).reshape(4, 3), jnp.int32)
    pos = [0] * 4
    last = [None] * 4
    while any(p < n for p, n in zip(pos, lens)):
        toks = np.zeros((4, C), np.int32)
        width = np.zeros((4,), np.int32)
        for r, row in enumerate(rows):
            w = min(C, lens[r] - pos[r])
            toks[r, :w], width[r] = row[pos[r]:pos[r] + w], w
        logits, pool = paged_forward_jit(
            params, jnp.asarray(toks), pool, tables,
            jnp.asarray(pos, jnp.int32), jnp.asarray(width), cfg=unit.cfg,
            **how)
        for r in range(4):
            if width[r] and pos[r] + width[r] == lens[r]:
                last[r] = np.asarray(logits[r])
            pos[r] += int(width[r])
    for r, row in enumerate(rows):
        want = np.asarray(REF.forward(
            params, jnp.asarray(row[None]), doc,
            jnp.asarray([[lens[r] - 1]]), jnp.asarray([lens[r]]))[0, 0])
        assert np.abs(last[r] - want).max() < 1e-4
    held = np.zeros((4, 4), np.int32)
    for r, row in enumerate(rows):
        held[r, :lens[r] % 4] = row[lens[r] - lens[r] % 4:]
    toks, pool, _, n_valid, *_ = paged_decode_round_jit(
        params, pool, tables, jnp.asarray(held), jnp.asarray(lens, jnp.int32),
        jnp.ones((4,), bool), jnp.zeros((4,), bool),
        jnp.zeros((4,), jnp.uint32), unit.cfg, span=span, temperature=0.0,
        top_k=0, top_p=0.0, eos_token=-1, **how)
    toks = np.asarray(toks)
    for r, row in enumerate(rows):
        rem = lens[r] % 4
        np.testing.assert_array_equal(toks[r, :rem], row[lens[r] - rem:])
        np.testing.assert_array_equal(
            toks[r, rem:], reference_answer(params, row, doc, span - rem))
        assert int(n_valid[r]) == lens[r] - rem + span


def test_a_round_in_place_equals_the_round_on_the_gather_path(model):
    """Two whole rounds of ``_denoising_round`` with the block's queries
    attending over the pool IN PLACE (``inplace="interpret"``: the Pallas
    kernel for a block of queries a row, its statistics joined with the
    block's fresh K/V outside it) against the gather path: rows whose first
    round begins with a prompt remainder, a row whose first block starts at
    position 0, an empty slot, a table padded past what any row owns, and an
    ``eos`` that a row generates inside the first round.  The SAME tokens
    to the id; the committed K/V of the live blocks to float32 rounding
    (the two paths sum a softmax in different orders: 1e-5 of values of
    order 1, where a wrong block or start reads at order 1)."""
    doc, unit, params = model
    lens = [3, 6, 9, 16, 0]                  # row 4 is an empty slot
    rows = prompts(lens[:4], seed=7)
    bs, own, width, span, B = 8, 5, 8, 8, 5
    tables = np.zeros((B, width), np.int32)
    tables[:4, :own] = 1 + np.random.default_rng(3).permutation(
        4 * own).reshape(4, own)
    active = jnp.asarray(np.asarray(lens) > 0)

    def two_rounds(inplace, eos):
        pool = init_block_pool(unit.cfg, 1 + 4 * own, bs)
        toks = np.zeros((B, 16), np.int32)
        for r, row in enumerate(rows):
            toks[r, :lens[r]] = row
        _, pool = paged_forward_jit(
            params, jnp.asarray(toks), pool, jnp.asarray(tables[:, :own]),
            jnp.zeros((B,), jnp.int32), jnp.asarray(lens, jnp.int32),
            cfg=unit.cfg)
        held = np.zeros((B, 4), np.int32)
        for r, row in enumerate(rows):
            held[r, :lens[r] % 4] = row[lens[r] - lens[r] % 4:]
        token, n_valid = jnp.asarray(held), jnp.asarray(lens, jnp.int32)
        seen, keys, out = jnp.zeros((B,), bool), jnp.zeros((B,), jnp.uint32), []
        for _ in range(2):
            t, pool, token, n_valid, seen, keys, *_ = paged_decode_round_jit(
                params, pool, jnp.asarray(tables), token, n_valid, active,
                seen, keys, unit.cfg, span=span, temperature=0.0, top_k=0,
                top_p=0.0, eos_token=eos, inplace=inplace)
            out.append(np.asarray(t))
        return np.concatenate(out, 1), pool, np.asarray(n_valid), seen

    free, _, _, _ = two_rounds(False, -1)
    eos = int(free[1, 5])                    # row 1's 4th new token
    want, pool_want, n_want, seen_want = two_rounds(False, eos)
    got, pool, n_got, seen = two_rounds("interpret", eos)
    assert np.asarray(seen_want)[1] and (want[1, 6:] == eos).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(n_got, n_want)
    np.testing.assert_array_equal(np.asarray(seen), np.asarray(seen_want))
    live = tables[:4, :own].reshape(-1)      # not the scratch block
    for li in pool_want:
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(pool[li][name])[live],
                np.asarray(pool_want[li][name])[live], atol=1e-5, rtol=0)
    # nothing is gathered in place: no op of the program sits under
    # ``kv_gather``, and the kernel is what ``attn`` holds
    args = (params, init_block_pool(unit.cfg, 1 + 4 * own, bs),
            jnp.asarray(tables), jnp.zeros((B, 4), jnp.int32),
            jnp.asarray(lens, jnp.int32), active, jnp.zeros((B,), bool),
            jnp.zeros((B,), jnp.uint32), unit.cfg)
    kw = dict(span=span, temperature=0.0, top_k=0, top_p=0.0, eos_token=-1)
    text = _op_paths(paged_decode_round_jit.lower(
        *args, **kw, inplace="interpret"))
    assert "kv_gather" not in text and "denoise/attn" in text
    assert "kv_gather" in paged_decode_round_jit.lower(
        *args, **kw, inplace=False).as_text(debug_info=True)


# -- (a') the pass two blocks share against each pass alone ------------------


def plain_round(params, pool, tables, token, n_valid, active, seen_eos, keys,
                cfg, *, span, eos_token, inplace, trace_passes=True):
    """A round of denoising passes in the plain formulation: every block's
    ``denoising_steps`` passes, then the pass that writes its K/V, each
    pass alone, the pool whole at the round's end -- the round as it was
    before the K/V pass of a block and the first denoising pass of the next
    became one pass of the layers, within a round and across two
    (``generate._denoising_round``), kept here to hold that one to it."""
    from seldon_core_tpu.ops.paged_attention import decode_plan

    L, steps = cfg.block_length, cfg.denoising_steps
    B = n_valid.shape[0]
    capacity = tables.shape[1] * G._pool_kv(pool)["k"].shape[1]
    base = n_valid - n_valid % L
    valid = jnp.broadcast_to(active[:, None], (B, L))
    head = G._head(params, cfg)

    def through(pool, plan, views, x, start, commit: bool):
        read = G._experts_counted(None, cfg)
        with jax.named_scope("embed"):
            h = params["embed"][x]
        for i in range(cfg.n_layers):
            h, layer, aux = G._paged_block(
                params[f"l{i}"], h, pool[f"l{i}"], tables, start, valid,
                cfg, plan=plan, interpret=inplace == "interpret",
                view=views and views[i], write=L if commit else 0,
                kv_only=commit and i == cfg.n_layers - 1, kind=cfg.kind(i))
            if commit:
                pool[f"l{i}"] = layer
            if cfg.d_expert:
                read = read + aux
        return h, pool, read

    def block(carry, b):
        pool, seen_eos, read = carry
        start = base + b * L
        pos = start[:, None] + jnp.arange(L)[None, :]
        masked = pos >= n_valid[:, None]
        x = jnp.where(masked, jnp.int32(cfg.mask_id), token)
        plan = views = None
        if inplace:
            plan = decode_plan(start, active, capacity, fresh=0)
        else:
            with jax.named_scope("kv_gather"):
                views = [G._paged_view(pool[f"l{i}"], tables, cfg.hd)
                         for i in range(cfg.n_layers)]

        def denoise(c, _):
            x, masked, read = c
            with jax.named_scope("denoise"):
                h, _, r = through(pool, plan, views, x, start, commit=False)
                with jax.named_scope("unembed"):
                    h = G._rmsnorm(h, params["ln_f"], cfg.norm_eps)
                    logits = (h @ head).astype(jnp.float32)
                with jax.named_scope("sample"):
                    logits = logits.at[..., cfg.mask_id].set(-jnp.inf)
                    chose = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    sure = jnp.exp(jnp.max(logits, axis=-1)
                                   - jax.nn.logsumexp(logits, axis=-1))
                    sure = jnp.where(masked, sure, -1.0)
                    rank = jnp.argsort(jnp.argsort(-sure, axis=-1), axis=-1)
                    picked = masked & (rank < L // steps)
            return ((jnp.where(picked, chose, x), masked & ~picked,
                     read + r), (x, picked, chose))

        (x, _, read), seen = jax.lax.scan(
            denoise, (x, masked, read), None, length=steps)
        with jax.named_scope("commit"):
            _, pool, r = through(pool, plan, views, x, start, commit=True)
        out = x
        if eos_token >= 0:
            hit = (x == eos_token) & (pos >= n_valid[:, None])
            hits = hit.astype(jnp.int32)
            after = (jnp.cumsum(hits, axis=1) - hits) > 0
            out = jnp.where(seen_eos[:, None] | after,
                            jnp.int32(eos_token), x)
            seen_eos = seen_eos | jnp.any(hit, axis=1)
        out = jnp.where(active[:, None], out, 0)
        return (pool, seen_eos, read + r), (out, seen)

    (pool, seen_eos, read), (toks, seen) = jax.lax.scan(
        block, (pool, seen_eos, G._experts_counted(None, cfg)),
        jnp.arange(span // L))
    toks = toks.transpose(1, 0, 2).reshape(B, span)
    n_valid = jnp.where(active, base + span, n_valid)
    out = (toks, pool, jnp.zeros((B,), jnp.int32), n_valid, seen_eos, keys)
    if cfg.d_expert:
        out += (G._experts_counted(read, cfg),)
    if trace_passes:
        out += (dict(zip(("saw", "picked", "chose"), seen)),)
    return out


def shared_round(*args, **kw):
    return G._denoising_round(*args, temperature=0.0, **kw)


ROUND_LENS = [3, 6, 9, 16, 0]        # remainders 3, 2, 1, 0; an empty slot


def round_case(unit, params, own=6):
    """Five rows prefilled over scattered blocks of 8 (``ROUND_LENS``: a
    prompt's remainder in three first blocks, a block that starts on a
    boundary, an inactive row) and the operands a round takes after it."""
    lens, B, bs = ROUND_LENS, len(ROUND_LENS), 8
    rows = prompts(lens[:4], seed=7)
    tables = np.zeros((B, own + 2), np.int32)
    tables[:4, :own] = 1 + np.random.default_rng(3).permutation(
        4 * own).reshape(4, own)
    toks = np.zeros((B, 16), np.int32)
    held = np.zeros((B, 4), np.int32)
    for r, row in enumerate(rows):
        toks[r, :lens[r]] = row
        held[r, :lens[r] % 4] = row[lens[r] - lens[r] % 4:]
    _, pool = paged_forward_jit(
        params, jnp.asarray(toks), init_block_pool(unit.cfg, 1 + 4 * own, bs),
        jnp.asarray(tables[:, :own]), jnp.zeros((B,), jnp.int32),
        jnp.asarray(lens, jnp.int32), cfg=unit.cfg)
    live = tables[:4, :own].reshape(-1)      # not the scratch block
    return pool, live, (
        jnp.asarray(tables), jnp.asarray(held), jnp.asarray(lens, jnp.int32),
        jnp.asarray(np.asarray(lens) > 0), jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.uint32))


def kv_of(pool, tables, row, upto):
    """The K/V ``pool`` holds of ``row``'s first ``upto`` positions, by the
    row's table: [layers, 2, upto, ...]."""
    pos = np.arange(upto)
    bs = pool["l0"]["k"].shape[1]
    blk = np.asarray(tables)[row, pos // bs]
    return np.stack([np.stack([np.asarray(pool[li][name])[blk, pos % bs]
                               for name in ("k", "v")])
                     for li in sorted(pool)])


@pytest.mark.parametrize("inplace", [False, "interpret"],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("blocks", [2, 3])
def test_a_round_that_shares_passes_equals_each_pass_alone(
        model, blocks, inplace):
    """A round of two and of three blocks -- the pass that writes block b's
    K/V and the first denoising pass of block b + 1 ONE pass over 8
    positions a row -- against ``plain_round``, on the gather path and
    through the kernel: the tokens, ``n_valid'``, the eos latch and what
    every pass saw, picked and chose to the id (a prompt's remainder in a
    first block, an inactive row, an eos that a row generates in the
    round's first block); the K/V the pool keeps -- up to the round's LAST
    block, which it hands on in ``token'`` and leaves unwritten -- to
    float32 rounding (a softmax summed in another order: 1e-5 of values of
    order 1); and never more experts read."""
    doc, unit, params = model
    span = 4 * blocks
    pool, live, operands = round_case(unit, params)

    def run(fn, eos):
        return jax.jit(functools.partial(
            fn, cfg=unit.cfg, span=span, eos_token=eos, inplace=inplace,
            trace_passes=True))(
                params, jax.tree.map(jnp.copy, pool), *operands)

    free = np.asarray(run(plain_round, -1)[0])
    eos = int(free[1, 3])           # row 1 (remainder 2): its 2nd new token
    want, got = run(plain_round, eos), run(shared_round, eos)
    assert np.asarray(want[4])[1] and (np.asarray(want[0])[1, 4:] == eos).all()
    for i in (0, 3, 4):             # tokens, n_valid', the eos latch
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want[i]))
    for name in ("saw", "picked", "chose"):
        assert got[7][name].shape == (blocks, doc["denoising_steps"], 5, 4)
        np.testing.assert_array_equal(
            np.asarray(got[7][name])[:, :, :4],
            np.asarray(want[7][name])[:, :, :4], err_msg=name)
    tables, ends = operands[0], np.asarray(want[3])
    for r in range(4):
        np.testing.assert_allclose(
            kv_of(got[1], tables, r, ends[r] - 4),
            kv_of(want[1], tables, r, ends[r] - 4), atol=1e-5, rtol=0)
        # the last block: handed on as the next round takes it (the ids the
        # passes fixed, not what the latch made of them), and not written
        saw, picked, chose = (np.asarray(want[7][name])[-1, -1, r]
                              for name in ("saw", "picked", "chose"))
        np.testing.assert_array_equal(
            ~np.asarray(got[2])[r], np.where(picked, chose, saw))
        assert not kv_of(got[1], tables, r, ends[r])[:, :, ends[r] - 4:].any()
    assert not np.asarray(got[2])[4].any()          # the empty slot's
    # a shared pass reads the union of two passes' picks, once
    assert 0 < int(got[6]["experts_read"]) < int(want[6]["experts_read"])


# -- (a'') rounds in sequence: the last K/V pass rides the next round --------


@functools.lru_cache(maxsize=None)
def _plain_jit(cfg, span, eos, inplace):
    return jax.jit(functools.partial(
        plain_round, cfg=cfg, span=span, eos_token=eos, inplace=inplace,
        trace_passes=True))


_ANSWERS = {}


def answer(params, prompt, doc, n, eos):
    """``reference_answer``'s first ``n`` tokens, computed once a prompt
    (an answer is a prefix of every longer one)."""
    key = (doc["denoising_steps"], prompt.tobytes(), eos)
    if key not in _ANSWERS or len(_ANSWERS[key]) < n:
        _ANSWERS[key] = reference_answer(params, prompt, doc, max(n, 24), eos)
    return _ANSWERS[key][:n]


def rounds_in_sequence(model, lens, schedule, inplace, span=8, eos=-1,
                       reference=True):
    """Rounds one after another as the scheduler drives them: ``schedule``
    names the rows of each round's batch (padded to a power of two with
    empty slots), a row rides from its first round to its last without a
    gap, and what a round hands the next lives in the carry BY SLOT
    (``genserver._carry_ops`` ``take`` / ``put``; ``Served.held`` says
    which rows bring a block).  Beside it the same rounds in the plain
    formulation, every pass alone over a pool that is whole after every
    round.  Round by round the tokens, ``n_valid'``, the eos latch and
    what every pass saw, picked and chose are the same to the id; at the
    end the pool's K/V of each row up to its lagging block are the plain
    pool's to float32 rounding, the lagging block itself was never
    written, and (``reference``) every row's new tokens are the float32
    reference's.  Returns each row's new tokens."""
    from seldon_core_tpu.models.served import served
    from seldon_core_tpu.runtime.genserver import _carry_ops

    doc, unit, params = model
    cfg, L, bs, own = unit.cfg, 4, 8, 6
    desc = served(cfg)
    take, put, _ = _carry_ops()
    R = len(lens)
    rows = prompts(lens, seed=7)
    tables = np.zeros((R, own + 2), np.int32)
    tables[:, :own] = 1 + np.random.default_rng(3).permutation(
        R * own).reshape(R, own)
    toks = np.zeros((R, 16), np.int32)
    for r, row in enumerate(rows):
        toks[r, :lens[r]] = row
    _, pool = paged_forward_jit(
        params, jnp.asarray(toks), init_block_pool(cfg, 1 + R * own, bs),
        jnp.asarray(tables[:, :own]), jnp.zeros((R,), jnp.int32),
        jnp.asarray(lens, jnp.int32), cfg=cfg)
    plain_pool = jax.tree.map(jnp.copy, pool)
    carry = {"tok": jnp.zeros((R + 1, L), jnp.int32),
             "seen": jnp.zeros((R + 1,), bool)}
    n_valid, rode = list(lens), [False] * R
    latch = np.zeros((R,), bool)        # the plain rounds', kept by row
    new = [[] for _ in range(R)]
    for batch in schedule:
        B = 1 << (len(batch) - 1).bit_length()
        idx = np.full((B,), R, np.int32)
        idx[:len(batch)] = batch
        tbl = np.zeros((B, own + 2), np.int32)
        tbl[:len(batch)] = tables[batch]
        nv = np.zeros((B,), np.int32)
        nv[:len(batch)] = [n_valid[r] for r in batch]
        active = np.arange(B) < len(batch)
        held = desc.held(B, [rode[r] for r in batch])
        first = np.zeros((B, L), np.int32)
        for i, r in enumerate(batch):
            assert rode[r] == (n_valid[r] > lens[r]), "a row rides on"
            off = n_valid[r] % L
            if off:
                first[i, :off] = held[i, :off] = rows[r][lens[r] - off:]
        token, seen, _ = take(carry, jnp.asarray(idx), jnp.asarray(held))
        got = paged_decode_round_jit(
            params, pool, jnp.asarray(tbl), token, jnp.asarray(nv),
            jnp.asarray(active), seen, jnp.zeros((B,), jnp.uint32), cfg,
            span=span, temperature=0.0, top_k=0, top_p=0.0, eos_token=eos,
            inplace=inplace, trace_passes=True)
        pool = got[1]
        carry, _ = put(carry, jnp.asarray(idx), got[2], got[4], None)
        was = np.zeros((B,), bool)
        was[:len(batch)] = latch[batch]
        want = _plain_jit(cfg, span, eos, inplace)(
            params, plain_pool, jnp.asarray(tbl), jnp.asarray(first),
            jnp.asarray(nv), jnp.asarray(active), jnp.asarray(was),
            jnp.zeros((B,), jnp.uint32))
        plain_pool = want[1]
        n = len(batch)
        for i in (0, 3, 4):         # tokens, n_valid', the eos latch
            np.testing.assert_array_equal(
                np.asarray(got[i])[:n], np.asarray(want[i])[:n])
        for name in ("saw", "picked", "chose"):
            np.testing.assert_array_equal(
                np.asarray(got[7][name])[:, :, :n],
                np.asarray(want[7][name])[:, :, :n], err_msg=name)
        latch[batch] = np.asarray(want[4])[:n]
        for i, r in enumerate(batch):
            new[r] += list(np.asarray(got[0])[i, n_valid[r] % L:])
            n_valid[r] += span - n_valid[r] % L
            rode[r] = True
    for r in range(R):
        lag = L if rode[r] else 0
        np.testing.assert_allclose(
            kv_of(pool, tables, r, n_valid[r] - lag),
            kv_of(plain_pool, tables, r, n_valid[r] - lag),
            atol=1e-5, rtol=0)
        # a row's last block gets no K/V pass: nobody will read it
        assert not kv_of(pool, tables, r, n_valid[r])[:, :, n_valid[r] - lag:
                                                      ].any()
        if reference:
            np.testing.assert_array_equal(
                new[r], answer(params, rows[r], doc, len(new[r]), eos))
    return [np.asarray(t) for t in new]


EVERY = [0, 1, 2, 3]
SEQUENCES = {
    # prompt remainders 3, 2, 1 and 0 in a row's first round
    "remainders": dict(lens=[3, 6, 9, 16], schedule=[EVERY] * 3),
    # rows in their first round beside rows that bring a block
    "a-fresh-row-joins": dict(
        lens=[3, 6, 9, 16], schedule=[[0, 1], [0, 1, 2], EVERY]),
    # rows 1 and 3 ride programs of 4, 2 and 4 padded rows, elsewhere in
    # the batch each time: a block moves by slot
    "between-row-counts": dict(
        lens=[3, 6, 9, 16, 5], schedule=[EVERY, [1, 3], [4, 1, 3]]),
    # rows 0 and 2 leave after their second and first round: ``max_new``
    # ended them inside it, and their last block is never written
    "max-new-ends-a-row-mid-round": dict(
        lens=[3, 6, 9, 16], schedule=[EVERY, [0, 1, 3], [1, 3]]),
}


@pytest.mark.parametrize("inplace", [False, "interpret"],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("case", [*SEQUENCES, "an-eos-in-a-pending-block",
                                  "rounds-of-one-block"])
def test_rounds_in_sequence_equal_each_pass_alone_and_the_reference(
        model, case, inplace):
    """The round's last block leaves its K/V pass to the next round's first
    pass (``rounds_in_sequence`` says what is held equal): over three
    rounds, on the gather path and through the kernel (the reference is
    asked on the gather path: the two paths are each held to the plain
    rounds to the id)."""
    reference = not inplace
    if case == "rounds-of-one-block":
        # ``span == block_length``: the one block's first pass rides the
        # K/V pass of the block the row brings, as in every round
        rounds_in_sequence(model, [3, 6, 9, 16], [EVERY] * 3, inplace,
                           span=4, reference=reference)
    elif case == "an-eos-in-a-pending-block":
        # row 1 (remainder 2) generates an eos in its first round's LAST
        # block, whose K/V the second round writes: the latch rides the
        # carry, and what follows is eos -- in that block and ever after
        doc, unit, params = model
        lens = [3, 6, 9, 16]
        free = list(answer(params, prompts(lens, seed=7)[1], doc, 24, -1))
        at = next(i for i in range(2, 6) if free[i] not in free[:i])
        new = rounds_in_sequence(model, lens, [EVERY] * 3, inplace,
                                 eos=int(free[at]), reference=reference)
        assert list(new[1][:at + 1]) == free[:at + 1]
        assert (new[1][at:] == free[at]).all()
    else:
        rounds_in_sequence(model, inplace=inplace, reference=reference,
                           **SEQUENCES[case])


@pytest.mark.parametrize("inplace", [False, "interpret"],
                         ids=["gather", "kernel"])
def test_a_round_of_one_block_is_the_round_every_count_runs(model, inplace):
    """``span == block_length`` was a program of its own (every pass alone,
    the plain formulation equation for equation) while a round handed
    nothing on; the static lane runs rounds of several blocks and of one
    after each other over one pool, so the one block too comes with its
    first pass made, riding the K/V pass of the block the row brings: the
    same scan body as a wider round's (its ``shared`` pass and ``steps - 1``
    passes under ``denoise``, no ``commit``), which is no longer the plain
    formulation's program."""
    doc, unit, params = model
    pool, _, operands = round_case(unit, params)
    text = {(fn, span): str(jax.make_jaxpr(functools.partial(
        fn, cfg=unit.cfg, span=span, eos_token=7, inplace=inplace,
        trace_passes=True))(params, pool, *operands))
        for fn, span in ((plain_round, 4), (shared_round, 4),
                         (shared_round, 8))}
    assert len(set(text.values())) == 3
    for span in (4, 8):
        paths = _op_paths(paged_decode_round_jit.lower(
            params, pool, *operands, unit.cfg, span=span, temperature=0.0,
            top_k=0, top_p=0.0, eos_token=-1, inplace=inplace))
        assert "shared/kv_write" in paths and "commit/" not in paths


def test_static_lane_one_round_or_many_gives_the_reference_answer(model):
    doc, unit, params = model
    for n, max_new in ((7, 10), (3, 5), (12, 8)):
        rows = np.stack(prompts([n, n], seed=n))
        want = np.stack([reference_answer(params, r, doc, max_new)
                         for r in rows])
        got = generate(params, jnp.asarray(rows), unit.cfg,
                       max_new_tokens=max_new)
        np.testing.assert_array_equal(np.asarray(got), want)
        chunks = list(stream_chunks(params, jnp.asarray(rows), unit.cfg,
                                    max_new_tokens=max_new, chunk=4))
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(c) for c in chunks], 1), want)
        assert all(c.shape[1] <= 4 for c in chunks)


# -- (b) nothing couples the rows of a batch ----------------------------------


def test_a_rows_tokens_do_not_depend_on_who_shares_its_batch(model):
    """What ``continuous_spec`` rests on: dropless routing takes a token by
    that token alone."""
    doc, unit, params = model
    rows = np.stack(prompts([9] * 8, seed=3))
    alone = generate(params, jnp.asarray(rows[:1]), unit.cfg,
                     max_new_tokens=11)
    together = generate(params, jnp.asarray(rows), unit.cfg,
                        max_new_tokens=11)
    np.testing.assert_array_equal(np.asarray(together)[:1],
                                  np.asarray(alone))
    state = unit.init_state(None)
    assert unit.continuous_spec(state) is not None
    assert unit.batch_coupled is False


def expert_loop(lp, h, cfg):
    """Every token through each expert it chose, one expert at a time."""
    x = np.asarray(h, np.float64).reshape(-1, h.shape[-1])
    logits = x @ np.asarray(lp["router"], np.float64)
    gates = np.exp(logits - logits.max(-1, keepdims=True))
    gates /= gates.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-gates[t], kind="stable")[:cfg.moe_k]
        w = gates[t, top] / (gates[t, top].sum() if cfg.moe_norm_topk
                             else 1.0)
        for e, we in zip(top, w):
            gu = x[t] @ np.asarray(lp["e_gate_up"][e], np.float64)
            g, u = gu[:cfg.d_expert], gu[cfg.d_expert:]
            out[t] += we * ((g / (1 + np.exp(-g)) * u)
                            @ np.asarray(lp["e_down"][e], np.float64))
    return out.reshape(h.shape)


@pytest.mark.parametrize("k, one_expert, norm", [
    (2, False, True), (2, False, False), (1, True, True), (3, True, True),
], ids=["top2", "top2-unnormalised", "every-pick-on-one-expert",
        "one-expert-first-of-three"])
def test_dropless_layer_equals_a_loop_over_experts(k, one_expert, norm):
    cfg = LMConfig(vocab=96, d_model=32, n_heads=4, d_expert=16,
                   n_experts=8, moe_k=k, moe_norm_topk=norm,
                   dtype=jnp.float32)
    lp = dropless_init(jax.random.key(1), cfg)
    if one_expert:
        # expert 5's router column dwarfs the rest: every token's first pick
        lp["router"] = lp["router"].at[:, 5].set(0.0)
        h = jax.random.normal(jax.random.key(2), (3, 5, 32))
        lp["router"] = lp["router"].at[0, 5].set(50.0)
        h = h.at[..., 0].set(1.0)
    else:
        h = jax.random.normal(jax.random.key(2), (3, 5, 32))
    valid = jnp.ones((3, 5), bool).at[1, 3:].set(False).at[2].set(False)
    y, read = moe_dropless(lp, h, valid, cfg)
    want = expert_loop(lp, h, cfg)
    live = np.asarray(valid)
    assert np.abs(np.asarray(y)[live] - want[live]).max() < 1e-5
    # a pad position picks nothing: it adds nothing and reads no expert
    assert not np.asarray(y)[~live].any()
    logits = np.asarray(h).reshape(-1, 32) @ np.asarray(lp["router"])
    chosen = np.argsort(-logits, axis=-1, kind="stable")[:, :k]
    assert int(read) == len(set(chosen[live.reshape(-1)].ravel()))
    if one_expert and k == 1:
        assert int(read) == 1


def test_the_tpu_kernel_and_xlas_grouped_matmul_agree():
    """What the chip runs (megablox ``gmm`` under the repo's tiles, here in
    Pallas interpret mode) against what the CPU runs (``ragged_dot``): the
    same layer to rounding, pad positions and empty experts included."""
    cfg = LMConfig(vocab=96, d_model=128, n_heads=4, d_expert=128,
                   n_experts=32, moe_k=2, dtype=jnp.float32)
    lp = dropless_init(jax.random.key(4), cfg)
    h = jax.random.normal(jax.random.key(5), (2, 7, 128))
    valid = jnp.ones((2, 7), bool).at[1, 4:].set(False)
    want, read = moe_dropless(lp, h, valid, cfg, impl="ragged_dot")
    got, read2 = moe_dropless(lp, h, valid, cfg, impl="gmm_interpret")
    assert int(read) == int(read2) <= 22         # 11 real tokens' picks
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    assert not np.asarray(got)[1, 4:].any()


# -- (c) GenServer: the same answers through the scheduler -------------------


@pytest.fixture()
def clean_genperf():
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()
    yield
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()


def served_decode(tokens):
    """``/genperf`` ``served_decode`` once the tick that emitted the last
    of ``tokens`` has published its record (a request's future resolves
    inside that tick, before the record is out)."""
    import time

    deadline = time.monotonic() + 10
    while True:
        SPINE.drain()
        served = GENPERF.document()["served_decode"]
        if served["real_tokens"] >= tokens or time.monotonic() > deadline:
            return served
        time.sleep(0.02)


def server(unit, params, **kw):
    state = {"params": params}
    kw = {"block_size": 8, "num_blocks": 64, "slots": 4, "span": 8,
          "prefill_chunk": 16, **kw}
    return GenServer(**unit.continuous_spec(state), **kw)


def test_genserver_serves_the_reference_answer(model, clean_genperf):
    """Unary and streamed; a prompt shorter than one block; ``max_new`` that
    is no multiple of the block length; rows of different lengths
    co-scheduled."""
    doc, unit, params = model
    srv = server(unit, params)
    try:
        cases = [(3, 5), (7, 10), (16, 8), (21, 13)]
        reqs = []
        for n, max_new in cases:
            rows = np.stack(prompts([n, n], seed=10 + n))
            reqs.append((rows, max_new, srv.submit(rows, max_new=max_new)))
        for rows, max_new, req in reqs:
            want = np.stack([reference_answer(params, r, doc, max_new)
                             for r in rows])
            np.testing.assert_array_equal(
                req.future.result(timeout=180), want)
            np.testing.assert_array_equal(np.asarray(generate(
                params, jnp.asarray(rows), unit.cfg,
                max_new_tokens=max_new)), want)
            chunks = list(srv.stream(rows, chunk=3, max_new=max_new))
            np.testing.assert_array_equal(np.concatenate(chunks, 1), want)
        snap = srv.snapshot()
        assert snap["round"] == {
            "block_length": 4, "denoising_steps": doc["denoising_steps"]}
        assert snap["tick_errors_total"] == 0
    finally:
        srv.stop()


def test_genserver_stops_at_an_eos_inside_a_block(model):
    doc, unit, params = model
    row = prompts([6], seed=21)[0]
    free = reference_answer(params, row, doc, 12)
    eos = int(free[5])                       # inside the second block
    want = reference_answer(params, row, doc, 12, eos=eos)
    assert (want[-1] == eos) and list(want).index(eos) <= 5
    spec = {**unit.continuous_spec({"params": params}), "eos_token": eos}
    srv = GenServer(**spec, block_size=8, num_blocks=64, slots=4, span=8,
                    prefill_chunk=16)
    try:
        got = srv.submit(row[None], max_new=12).future.result(timeout=180)
        np.testing.assert_array_equal(got[0], want)
        np.testing.assert_array_equal(np.asarray(generate(
            params, jnp.asarray(row[None]), unit.cfg, max_new_tokens=12,
            eos_token=eos))[0], want)
    finally:
        srv.stop()


def test_genserver_preempts_and_readmits_mid_answer(model):
    """A pool too small for two whole rows: the younger is evicted between
    rounds, its emitted tokens become prompt (no pending token: all of them
    are in the cache), and its answer is the reference's all the same."""
    doc, unit, params = model
    rows = prompts([6, 6], seed=31)
    want = [reference_answer(params, r, doc, 18) for r in rows]
    # each row grows to 6 + 18 (+ a round's slack) positions = 7 blocks of
    # 4; a pool of 10 holds both admissions, not both answers
    SPINE.drain()
    LEDGER.reset()
    srv = server(unit, params, block_size=4, num_blocks=11, span=8,
                 prefill_chunk=8)
    try:
        with qos_scope("anna", "interactive"):
            reqs = [srv.submit(r[None], max_new=18) for r in rows]
        for req, w in zip(reqs, want):
            np.testing.assert_array_equal(
                req.future.result(timeout=240)[0], w)
        snap = srv.snapshot()
        assert snap["preempted_total"] >= 1
    finally:
        srv.stop()
    # the cost ledger's per-request usage: a prefill that chooses no token
    # notes no request, a row's first tokens do -- once, though the evicted
    # row was prefilled twice
    SPINE.drain()
    assert LEDGER._usage["anna"][1] == 2.0


def test_a_diffusion_generator_refuses_what_it_cannot_serve(model):
    doc, unit, params = model
    spec = unit.continuous_spec({"params": params})
    for kw, match in (({"span": 6}, "span=6"), ({"block_size": 6}, "block_size"),
                      ({"prefill_chunk": 10}, "prefill_chunk"),
                      ({"temperature": 0.7}, "greedy")):
        with pytest.raises(ValueError, match=match):
            GenServer(**{**spec, "block_size": 8, "num_blocks": 16,
                         "slots": 2, "span": 8, "prefill_chunk": 16, **kw})
    with pytest.raises(ValueError, match="greedy"):
        TransformerGenerator(vocab=96, block_length=4, denoising_steps=4,
                             mask_id=MASK, temperature=0.5)
    with pytest.raises(ValueError, match="denoising_steps"):
        LMConfig(vocab=96, block_length=4, denoising_steps=3, mask_id=MASK)
    with pytest.raises(ValueError, match="cache-free forward"):
        from seldon_core_tpu.models.transformer import lm_apply

        lm_apply(params, jnp.zeros((1, 4), jnp.int32), unit.cfg)


# -- spans and counters --------------------------------------------------------


def test_genperf_counts_passes_and_experts_apart_from_tokens(model,
                                                             clean_genperf):
    """One row, a prompt of 6 (remainder 2), 14 tokens: two rounds of two
    blocks, ``steps`` + 1 passes a block; the first round emits 6.  The
    round's last block leaves its K/V pass to the next round: the row runs
    one pass fewer in its first round, and its last block's never."""
    doc, unit, params = model
    steps, layers, E = doc["denoising_steps"], 2, doc["num_experts"]
    srv = server(unit, params)
    try:
        row = prompts([6], seed=41)[0]
        srv.submit(row[None], max_new=14).future.result(timeout=180)
        served = served_decode(14)
        prefill = GENPERF.document()["served_prefill"]
    finally:
        srv.stop()
    # the prompt is one chunk: its program returns the experts its two
    # layers read in the logits' place (a token picks 2 of E)
    assert prefill["calls"] == 1 and prefill["expert_slots"] == layers * E
    assert 2 * 2 <= prefill["experts_read"] <= prefill["expert_slots"]
    passes = 2 * 2 * (steps + 1)
    assert served["device_steps"] == 16 and served["real_tokens"] == 14
    # a round counts its own blocks' passes; the row RAN one fewer: each
    # round left its last block's K/V pass to the next, and there was none
    # after the second (9 passes, then 10)
    assert served["passes"] == passes == served["row_passes"] + 1
    # every K/V pass that ran rode another pass of the device: one inside
    # the first round, two in the second (the block brought, and its own)
    assert served["shared_passes"] == 3
    assert served["inplace_steps"] == 0
    # the K/V-writing pass stops at its last layer's K/V: one expert layer
    # fewer a block
    assert served["expert_slots"] == E * 4 * (steps * layers + layers - 1)
    # one row's block of 4 tokens picks at most 8 distinct experts of 8
    assert 4 * 2 <= served["experts_read"] <= served["expert_slots"]
    assert served["experts_read"] < served["expert_slots"]


def test_dispatching_spans_say_a_rounds_passes_and_experts(
        model, clean_genperf, recorded_spans):
    """Every dispatch of a diffusion-block generator says its work on the
    span that wraps it -- the round's passes and blocks, the cache
    positions its passes read, the experts held x expert-layer passes --
    and what the program counted itself comes back on ``/emit`` under the
    same number: summed over a server's life they are ``/genperf``'s."""
    doc, unit, params = model
    srv = server(unit, params)
    try:
        reqs = [srv.submit(np.stack(prompts([n, n], seed=60 + n)),
                           max_new=max_new)
                for n, max_new in ((6, 14), (21, 9), (3, 8))]
        for r in reqs:
            r.future.result(timeout=180)
        served = served_decode(2 * (14 + 9 + 8))
        prefill = GENPERF.document()["served_prefill"]
    finally:
        srv.stop()
    rounds = recorded_spans.dispatches("decode")
    chunks = recorded_spans.dispatches("prefill")
    seqs = sorted(a["seq"] for a in rounds + chunks)
    assert seqs == list(range(1, len(seqs) + 1))
    steps = doc["denoising_steps"]
    assert all(a["blocks"] == 2 and a["passes"] == 2 * (steps + 1)
               and a["inplace"] == 0 for a in rounds)
    for key in ("kv_positions", "expert_slots", "passes"):
        assert sum(a[key] for a in rounds) == served[key] > 0, key
    # what the rows RAN: each of the six left its last block's K/V pass to
    # a next round that never came
    assert sum(a["passes"] * a["real_rows"] for a in rounds) == \
        served["row_passes"] + 6
    read = {kind: recorded_spans.carrying("/emit", kind)
            for kind in ("decode", "prefill")}
    assert sorted(a["seq"] for a in read["decode"]) == [
        a["seq"] for a in rounds]
    assert sorted(a["seq"] for a in read["prefill"]) == [
        a["seq"] for a in chunks]
    assert sum(a["experts_read"] for a in read["decode"]) == \
        served["experts_read"] > 0
    assert sum(a["experts_read"] for a in read["prefill"]) == \
        prefill["experts_read"] > 0
    # a call never reads more experts than it holds slots for
    slots = {a["seq"]: a["expert_slots"] for a in rounds + chunks}
    assert all(0 < a["experts_read"] <= slots[a["seq"]]
               for a in read["decode"] + read["prefill"])
    assert (len(chunks), sum(a["tokens"] for a in chunks),
            sum(a["expert_slots"] for a in chunks)) == (
        prefill["calls"], prefill["tokens"], prefill["expert_slots"])
    assert prefill["tokens"] == 2 * (6 + 21 + 3)
    assert sum(a["attended"] for a in chunks) == 2 * sum(
        n * (n + 1) // 2 for n in (6, 21, 3))


def test_a_dense_generator_reports_no_experts_and_one_pass_a_step(
        clean_genperf):
    unit = TransformerGenerator(vocab=48, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, dtype="float32")
    state = unit.init_state(None)
    srv = GenServer(**unit.continuous_spec(state), block_size=4,
                    num_blocks=32, slots=2, span=4, prefill_chunk=8)
    try:
        srv.submit(np.arange(5)[None], max_new=9).future.result(timeout=180)
        served = served_decode(9)
        assert srv.snapshot()["round"] == {
            "block_length": 1, "denoising_steps": 1}
    finally:
        srv.stop()
    assert served["passes"] == served["row_passes"] == served["device_steps"]
    assert served["experts_read"] == served["expert_slots"] == 0
    assert served["shared_passes"] == 0
    SPINE.drain()
    assert GENPERF.document()["served_prefill"] == {
        "calls": 1, "experts_fused_calls": 0, "experts_read": 0,
        "expert_slots": 0, "tokens": 5, "rows": 1, "carried_rows": 0,
        "retention_fused_rows": 0, "retention_state_bytes": 0}


def test_observe_tick_folds_the_new_counters():
    GENPERF.reset()
    for kind in ("decode", "mixed", "prefill"):
        GENPERF.observe_tick(kind, {
            "wall_s": 0.01, "device_s": 0.008, "steps": 8, "tokens": 6,
            "passes": 10, "row_passes": 30, "experts_read": 700,
            "expert_slots": 8704, "prefill_calls": 2,
            "prefill_experts_read": 1500, "prefill_expert_slots": 1792,
            "prefill_tokens": 300, "prefill_rows": 7,
            "prefill_carried_rows": 3})
    served = GENPERF.document()["served_decode"]
    prefill = GENPERF.document()["served_prefill"]
    GENPERF.reset()
    # a chunk is read back in whatever tick comes next: every kind folds it
    assert prefill == {"calls": 6, "experts_fused_calls": 0,
                       "experts_read": 4500,
                       "expert_slots": 5376, "tokens": 900, "rows": 21,
                       "carried_rows": 9, "retention_fused_rows": 0,
                       "retention_state_bytes": 0}
    # a prefill tick's are not a decode round's
    assert (served["passes"], served["row_passes"], served["experts_read"],
            served["expert_slots"]) == (20, 60, 1400, 17408)


@pytest.mark.parametrize("span", [4, 8])
def test_the_block_names_its_stages_for_the_trace(model, span):
    """``jax.named_scope`` ``qk_norm``, ``router``, ``experts`` inside the
    block and ``denoise`` / ``commit`` around a pass: op metadata the trace
    readers sort device time by (bench/readers/trace_stages.py).  The pass
    that writes a block's K/V is the one the next block's first denoising
    pass rides, ``shared`` -- in a round of one block too, whose block rides
    the K/V pass of the block the row brings -- and the head after it is
    that first pass's; no pass is a ``commit`` of its own."""
    doc, unit, params = model
    pool = init_block_pool(unit.cfg, 8, 8)
    text = _op_paths(paged_decode_round_jit.lower(
        params, pool, jnp.ones((2, 2), jnp.int32), jnp.zeros((2, 4), jnp.int32),
        jnp.asarray([5, 8], jnp.int32), jnp.ones((2,), bool),
        jnp.zeros((2,), bool), jnp.zeros((2,), jnp.uint32), unit.cfg,
        span=span, temperature=0.0, top_k=0, top_p=0.0, eos_token=-1))
    for scope in ("denoise/qk_norm", "denoise/ffn/router",
                  "denoise/ffn/experts", "shared/ffn/experts",
                  "denoise/unembed", "shared/kv_write", "denoise/attn"):
        assert scope in text, scope
    assert "commit/" not in text
    # a block's first head lies under the shared pass
    assert re.search(r"shared/(\S*/)?unembed", text) is not None


def test_a_prefill_without_its_head_returns_the_experts_read(model):
    """``head=False`` (what ``GenServer`` asks of a generator whose prompt
    chooses no token): the same pool as with the head, no ``unembed`` in
    the program, and the count of expert groups with a real token in the
    logits' place."""
    doc, unit, params = model
    rows = np.stack(prompts([6, 6], seed=5))
    rows[1, 3:] = 0
    args = (jnp.asarray(rows), jnp.asarray([[1, 2], [3, 4]], jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.asarray([6, 3], jnp.int32))

    def run(**kw):
        pool = init_block_pool(unit.cfg, 8, 8)
        return paged_forward_jit(params, args[0], pool, *args[1:],
                                 cfg=unit.cfg, **kw)

    logits, pool = run()
    read, pool2 = run(head=False)
    assert logits.shape == (2, unit.cfg.vocab)
    jax.tree.map(np.testing.assert_array_equal, pool, pool2)
    # two layers of 8 experts; 9 real tokens pick 2 each
    assert read.dtype == jnp.int32 and 2 * 2 <= int(read) <= 2 * 8
    text = paged_forward_jit.lower(
        params, args[0], init_block_pool(unit.cfg, 8, 8), *args[1:],
        cfg=unit.cfg, head=False).as_text(debug_info=True)
    assert "ffn/experts" in text and "unembed" not in text
