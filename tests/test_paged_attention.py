"""The in-place paged decode attention kernel (ops/paged_attention.py)
against the gather path it replaces — at width 1 (generate._paged_view +
_attend_paged) and for the queries of one diffusion block a row
(_attend_view_and_fresh): Pallas interpret mode on the CPU, the choosing
function, the decode round with the in-place path forced, and the kernel
compiled for a described v5e at the benchmark cells' shapes.  A head
narrower than a 128-lane row is a further case of each: the pool is then
made as ``init_block_pool`` makes it, several KV heads a row."""

import functools
import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.generate import (
    _attend_paged,
    _attend_pool_and_fresh,
    _attend_view_and_fresh,
    _paged_view,
    _paged_write,
    decode_inplace,
    init_block_pool,
    paged_decode_round_jit,
    paged_forward_jit,
)
from seldon_core_tpu.models.transformer import LMConfig, lm_init
from seldon_core_tpu.ops.paged_attention import (
    blocks_per_chunk,
    decode_plan,
    heads_per_row,
    inplace_supported,
    paged_decode_attention,
)

KV, HD = 2, 128


def _case(bs, g, dtype, n_valid, active, nblk, seed=0, width=1, kv=KV,
          hd=HD):
    """Random q and pools, and tables of scrambled, non-contiguous block
    ids; an inactive row's table is all zeros (the scheduler's padding).
    The pool has ``init_block_pool``'s shape: ``heads_per_row`` KV heads a
    row of the pool, its bytes those of ``[N, bs, kv, hd]``."""
    rng = np.random.default_rng(seed)
    B, H = len(n_valid), kv * g
    N = B * nblk + 3
    pair = heads_per_row(kv, hd)
    q = jnp.asarray(rng.normal(size=(B, H, width, hd)), dtype)
    pool = {name: jnp.asarray(rng.normal(size=(N, bs, kv, hd)), dtype
                              ).reshape(N, bs, kv // pair, hd * pair)
            for name in ("k", "v")}
    ids = rng.permutation(np.arange(1, N))[:B * nblk].reshape(B, nblk)
    tables = np.where(np.asarray(active)[:, None], ids, 0).astype(np.int32)
    return (q, pool, jnp.asarray(tables), jnp.asarray(n_valid, jnp.int32),
            jnp.asarray(active))


def _both(q, pool, tables, n_valid, active):
    want = _attend_paged(q, _paged_view(pool, tables, q.shape[-1]), n_valid)
    capacity = tables.shape[1] * pool["k"].shape[1]
    got = paged_decode_attention(
        q, pool["k"], pool["v"], tables,
        *decode_plan(n_valid, active, capacity), interpret=True)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _tol(dtype):
    # bf16: one rounding of p (2^-9) and one of the output, |v| <~ 4
    return 3e-2 if dtype == jnp.bfloat16 else 2e-5


def _head_cases(*axes, narrow):
    """Every combination of ``axes`` (lists of values, ``dtype`` the last)
    at the 128-wide head the tests began with, then the ``narrow`` heads
    (``(kv, hd)``; a subset of the axes each) that ride a row together.  A
    position's KV heads must fill whole 32-bit rows of 128 words: two heads
    of 64 in bfloat16 are half a row, and only float32 serves them."""
    names = {jnp.float32: "float32", jnp.bfloat16: "bfloat16"}
    out = []
    for (kv, hd), sub in [((KV, HD), axes)] + narrow:
        for combo in itertools.product(*sub):
            if kv * hd * jnp.dtype(combo[-1]).itemsize % 512 == 0:
                out.append(pytest.param(
                    *combo, kv, hd,
                    id="-".join([*map(str, combo[:-1]), names[combo[-1]],
                                 f"kv{kv}", f"hd{hd}"])))
    return out


_FLOATS = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("bs,g,dtype,kv,hd", _head_cases(
    [16, 256], [1, 4, 12], _FLOATS, narrow=[
        ((8, 64), ([16, 256], [1, 4], _FLOATS)),   # lfm2-8b-a1b: g 4
        ((2, 64), ([16, 256], [4, 12], _FLOATS)),  # one row of the pool
        ((8, 32), ([16], [4], _FLOATS)),           # four heads a row
    ]))
def test_kernel_matches_gather_path_on_ragged_rows(bs, g, dtype, kv, hd):
    """Lengths 1, exactly a block, a block + 1, mid-table and the full
    table; one inactive row between live ones."""
    nblk = 4
    lengths = [1, bs, bs + 1, 0, 2 * bs + bs // 2, nblk * bs]
    active = [n > 0 for n in lengths]
    n_valid = [max(n - 1, 0) for n in lengths]  # the kernel reads n_valid + 1
    got, want = _both(*_case(bs, g, dtype, n_valid, active, nblk, kv=kv,
                             hd=hd))
    live = np.asarray(active)
    np.testing.assert_allclose(got[live], want[live], atol=_tol(dtype),
                               rtol=0)
    assert np.isfinite(got).all()
    assert (got[~live] == 0).all()  # zeros, never NaN


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bs", [16, 256])
def test_kernel_ignores_table_entries_past_a_rows_length(bs, dtype):
    """A table padded past every row's need: the entries past a row's
    length point at blocks full of NaN, which the kernel must never read
    (the gather path is given the same rows over clean blocks)."""
    nblk = 8
    n_valid = [0, bs - 2, bs + 3, 2 * bs - 1]
    active = [True] * 4
    q, pool, tables, nv, act = _case(bs, 4, dtype, n_valid, active, nblk,
                                     seed=1)
    _, want = _both(q, pool, tables, nv, act)
    need = np.asarray(n_valid) // bs + 1
    past = np.asarray(tables)[np.arange(nblk)[None, :] >= need[:, None]]
    poisoned = {name: arr.at[past].set(jnp.nan) for name, arr in pool.items()}
    got, _ = _both(q, poisoned, tables, nv, act)
    np.testing.assert_allclose(got, want, atol=_tol(dtype), rtol=0)


def test_all_rows_inactive_gives_zeros():
    q, pool, tables, nv, act = _case(16, 4, jnp.float32, [0, 0], [False] * 2,
                                     2)
    got, _ = _both(q, pool, tables, nv, act)
    assert (got == 0).all()


def test_decode_plan_compacts_live_rows():
    lengths, order, count = decode_plan(
        jnp.asarray([5, 0, 31, 7], jnp.int32),
        jnp.asarray([True, False, True, True]), 32)
    assert lengths.tolist() == [6, 0, 32, 8]  # n_valid + 1, capped
    assert order.tolist()[:3] == [0, 2, 3] and count.tolist() == [3]


def test_decode_plan_of_a_block_stops_at_its_start():
    """``fresh=0``: the queries of a block see the cache BEFORE it; a row
    whose block starts at 0 has nothing there and is not live."""
    lengths, order, count = decode_plan(
        jnp.asarray([8, 0, 0, 12], jnp.int32),
        jnp.asarray([True, True, False, True]), 32, fresh=0)
    assert lengths.tolist() == [8, 0, 0, 12]
    assert order.tolist()[:2] == [0, 3] and count.tolist() == [2]


def _block_case(bs, g, W, dtype, starts, active, nblk, seed=0, kv=KV,
                hd=HD):
    """``_case`` for a block of ``W`` queries a row at ``starts``, with the
    block's own fresh K/V, which never pass through the pool."""
    q, pool, tables, start, act = _case(bs, g, dtype, starts, active, nblk,
                                        seed=seed, width=W, kv=kv, hd=hd)
    rng = np.random.default_rng(seed + 100)
    k_new, v_new = (jnp.asarray(rng.normal(size=(len(starts), kv, W, hd)),
                                dtype) for _ in "kv")
    return q, pool, tables, start, act, k_new, v_new


def _block_both(q, pool, tables, start, act, k_new, v_new):
    want = _attend_view_and_fresh(
        q, _paged_view(pool, tables, q.shape[-1]), start, k_new, v_new)
    capacity = tables.shape[1] * pool["k"].shape[1]
    got = _attend_pool_and_fresh(
        q, pool, tables, decode_plan(start, act, capacity, fresh=0),
        k_new, v_new, interpret=True)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("bs,W,g,dtype,kv,hd", _head_cases(
    [16, 256], [4, 8], [8, 12], _FLOATS, narrow=[
        ((8, 64), ([16, 256], [4], [4], _FLOATS)),
        ((2, 64), ([16], [4, 8], [8], _FLOATS)),
    ]))
def test_block_of_queries_matches_the_gather_path(bs, W, g, dtype, kv, hd):
    """A diffusion block's ``W`` queries a row over the cache before the
    block's start (the kernel, its statistics) and the block's fresh K/V
    (joined outside it) against ``_attend_view_and_fresh``: a block that
    starts at 0 (nothing in the pool: the fresh part alone), after one
    block of the block length, after exactly one pool block, not on a pool
    block's boundary, several pool blocks in, and one that leaves the table
    just room for itself; an inactive row between live ones."""
    nblk = 4
    starts = [0, W, bs, 0, bs + W, 2 * bs + bs // 2 - bs // 2 % W,
              nblk * bs - W]
    active = [True, True, True, False, True, True, True]
    got, want = _block_both(
        *_block_case(bs, g, W, dtype, starts, active, nblk, kv=kv, hd=hd))
    live = np.asarray(active)
    np.testing.assert_allclose(got[live], want[live], atol=_tol(dtype),
                               rtol=0)
    assert np.isfinite(got).all()          # never NaN, an unread row neither


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_block_of_queries_reads_nothing_from_its_start_on(dtype):
    """What the pool holds from a block's start on is stale (a pass over
    the block writes nothing, the commit writes before it attends): huge
    values there (finite, as stale K/V are: a masked position still meets
    ``0 * v`` on either path), and NaN in every table entry past that pool
    block, which is never fetched, change nothing."""
    bs, W, nblk = 16, 4, 8
    starts = [0, 12, bs + 8, 3 * bs]
    case = _block_case(bs, 8, W, dtype, starts, [True] * 4, nblk, seed=2)
    q, pool, tables, start, act, k_new, v_new = case
    _, want = _block_both(*case)
    poisoned = dict(pool)
    for r, n in enumerate(starts):
        ids = np.asarray(tables)[r]
        for name in ("k", "v"):
            arr = poisoned[name].at[ids[n // bs], n % bs:].set(1e4)
            poisoned[name] = arr.at[ids[n // bs + 1:]].set(jnp.nan)
    got, _ = _block_both(q, poisoned, tables, start, act, k_new, v_new)
    np.testing.assert_allclose(got, want, atol=_tol(dtype), rtol=0)


def test_statistics_of_a_row_that_is_not_live_carry_no_mass():
    """The second output exists only for a caller that asks; an inactive
    row and a row of length 0 give zeros, no mass and a finite peak."""
    q, pool, tables, start, act = _case(
        16, 8, jnp.float32, [0, 20, 0], [True, True, False], 4, width=4)
    plan = decode_plan(start, act, 64, fresh=0)
    out, peak, mass = paged_decode_attention(
        q, pool["k"], pool["v"], tables, *plan, interpret=True, stats=True)
    assert out.shape == (3, KV, 8, 4, HD) and peak.shape == mass.shape == (
        3, KV, 8, 4)
    for r in (0, 2):
        assert not np.asarray(out[r]).any() and not np.asarray(mass[r]).any()
    assert np.isfinite(np.asarray(peak)).all()
    assert (np.asarray(mass[1]) >= 1.0).all()   # the peak's own exp(0)
    plain = paged_decode_attention(q, pool["k"], pool["v"], tables, *plan,
                                   interpret=True)
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(out).reshape(plain.shape), atol=1e-6)


# a diffusion block of 4 queries a row at the second cell's widths: 32 query
# heads over 4 KV heads, 32 padded rows
BLOCK = {"width": 4, "kv_heads": 4, "heads": 32, "rows": 32}


# the third cell's widths: 32 query heads over 8 KV heads, 32 padded rows
LFM2 = {"kv_heads": 8, "heads": 32, "rows": 32}


@pytest.mark.parametrize("kw,want", [
    ({}, True),
    ({"heads": 24, "rows": 32}, True),        # the dense cell, told in full
    # a block too wide to fold: 32 rows x 4 KV heads x 8 x 64 query rows of
    # 128 float32, in and out, is 64 MiB of a 16-MiB vector memory
    ({**BLOCK, "width": 64}, False),
    ({"pool_dtype": jnp.int8}, False),        # quantized pool
    ({"mesh": object()}, False),              # GSPMD cannot partition it
    ({"backend": "cpu"}, False),
    ({"pool_dtype": jnp.float32}, True),
    ({"block_size": 16}, True),
    # two heads of 64 ride one 128-lane row -- where a position's heads
    # fill whole 32-bit rows: two of them in bfloat16 are half a row
    ({"head_dim": 64}, False),
    ({"kv_heads": 6}, False),                 # not a memory tile
    ({"kv_heads": 1}, False),                 # half a word a position
    ({"kv_heads": 1, "pool_dtype": jnp.float32}, True),
    (BLOCK, True),                            # 8 x 4 = 32 query rows a head
    ({**BLOCK, "width": 8}, False),           # twice that: 12 MiB + buffers
    ({**BLOCK, "width": 8, "rows": 16}, True),
    ({**BLOCK, "rows": 128}, False),          # too many rows to hold whole
    ({**BLOCK, "width": 2}, True),
    ({**BLOCK, "width": 0}, False),           # no query at all
    ({**BLOCK, "mesh": object()}, False),
    ({**BLOCK, "pool_dtype": jnp.int8}, False),
    ({**BLOCK, "backend": "cpu"}, False),
    ({**BLOCK, "head_dim": 64}, True),        # 2 rows of 2 heads, 64 queries
    ({"width": 4}, True),                     # sizes untold: the shapes alone
    ({**LFM2, "head_dim": 64}, True),         # lfm2-8b-a1b: 4 rows of 2 heads
    ({"head_dim": 64, "pool_dtype": jnp.float32}, True),
    ({"head_dim": 64, "kv_heads": 4}, True),
    ({"head_dim": 64, "kv_heads": 4, "block_size": 16, "heads": 16,
      "rows": 64}, True),                     # chip_smoke.py's model
    ({"head_dim": 64, "kv_heads": 3}, False),  # a head left over
    ({"head_dim": 64, "kv_heads": 5, "pool_dtype": jnp.float32}, False),
    ({"head_dim": 96, "kv_heads": 4}, False),  # fills no row
    ({**LFM2, "head_dim": 64, "pool_dtype": jnp.int8}, False),
    ({**LFM2, "head_dim": 64, "mesh": object()}, False),
    ({**LFM2, "head_dim": 32}, True),         # four heads a row
    ({"head_dim": 256}, True),                # two rows a head: as ever
], ids=["cell", "cell-sized", "wide", "int8", "mesh", "cpu", "f32", "bs16",
        "hd64", "kv6", "kv1-bf16", "kv1-f32", "block4", "block8-32rows",
        "block8-16rows", "block4-128rows", "block2", "block0", "block-mesh",
        "block-int8", "block-cpu", "block-hd64", "block-unsized", "lfm2",
        "hd64-f32", "hd64-kv4", "hd64-smoke", "hd64-kv3", "hd64-kv5-f32",
        "hd96", "hd64-int8", "hd64-mesh", "hd32", "hd256"])
def test_inplace_supported_chooses_by_what_it_can_observe(kw, want):
    base = dict(width=1, backend="tpu", pool_dtype=jnp.bfloat16, mesh=None,
                block_size=256, kv_heads=2, head_dim=128)
    assert inplace_supported(**{**base, **kw}) is want


def test_chunks_cover_512_positions_within_the_tables_width():
    assert blocks_per_chunk(256, 2, 128, 2, 4) == 2
    assert blocks_per_chunk(16, 2, 128, 2, 8) == 8      # the table's width
    assert blocks_per_chunk(16, 2, 128, 2, 64) == 32
    assert blocks_per_chunk(4096, 8, 128, 4, 4) == 0    # one block > budget


CFG = LMConfig(vocab=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
               d_ff=128, dtype=jnp.float32)


# gated short convolutions and attention at a 64-wide head, two KV heads:
# the pool's K/V are one row of 128 a position (float32: 32-bit words)
HYBRID = LMConfig(vocab=64, d_model=64, n_heads=4, n_kv_heads=2, head_dim=64,
                  n_layers=4, layer_kinds="caca", conv_kernel=3, d_ff=128,
                  qk_norm=True, dtype=jnp.float32)


def _three_rounds(inplace, width, CFG=CFG):
    """Ragged rows prefilled, then three rounds of paged_decode_round over
    tables ``width`` wide (a row owns 8 blocks; the rest is the scheduler's
    zero padding): the tokens, the pool, and the live block ids."""
    params = lm_init(jax.random.key(0), CFG)
    bs, nblk, span, B = 4, 8, 3, 4
    rng = np.random.default_rng(5)
    lens = [3, 6, 9]                       # row 3 is an empty slot
    toks = np.zeros((B, 12), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, CFG.vocab, n)
    tables = np.zeros((B, width), np.int32)
    ids = rng.permutation(np.arange(1, 1 + 3 * nblk))
    for i in range(3):
        tables[i, :nblk] = ids[i * nblk:(i + 1) * nblk]
    lengths = np.asarray(lens + [0], np.int32)
    active = jnp.asarray(lengths > 0)
    pool = init_block_pool(CFG, 1 + 3 * nblk, bs)
    logits, pool = paged_forward_jit(
        params, jnp.asarray(toks), pool, jnp.asarray(tables[:, :nblk]),
        jnp.zeros((B,), jnp.int32), jnp.asarray(lengths), cfg=CFG)
    token = jnp.argmax(logits, -1).astype(jnp.int32)
    n_valid, seen = jnp.asarray(lengths), jnp.zeros((B,), bool)
    keys, out = jnp.zeros((B,), jnp.uint32), []
    for _ in range(3):
        t, pool, token, n_valid, seen, keys = paged_decode_round_jit(
            params, pool, jnp.asarray(tables), token, n_valid, active,
            seen, keys, CFG, span=span, temperature=0.0, top_k=0,
            top_p=0.0, eos_token=-1, inplace=inplace)
        out.append(np.asarray(t))
    return np.concatenate(out, axis=1), pool, tables[:3, :nblk].reshape(-1)


def _assert_same_round(got, want):
    (toks, pool, live), (toks_want, pool_want, _) = got, want
    np.testing.assert_array_equal(toks, toks_want)
    for li in pool_want:                   # live: not the scratch block
        for name in pool_want[li]:         # k and v, or a conv layer's state
            np.testing.assert_allclose(
                np.asarray(pool[li][name])[live],
                np.asarray(pool_want[li][name])[live], atol=1e-5, rtol=0)


@pytest.mark.parametrize("cfg", [CFG, HYBRID], ids=["hd16", "conv-attn-hd64"])
def test_decode_round_in_place_emits_the_gather_paths_tokens(cfg):
    """Three rounds of paged_decode_round with the in-place path forced
    through the function's own argument (interpret mode): the same greedy
    tokens and the same pool as the gather path -- also where conv layers
    stand between attention layers whose two 64-wide KV heads share a row
    of the pool."""
    got = _three_rounds("interpret", 8, cfg)
    _assert_same_round(got, _three_rounds(False, 8, cfg))
    row = 1 if cfg is CFG else 2
    k = next(layer["k"] for layer in got[1].values() if "k" in layer)
    assert k.shape[2:] == (cfg.kv_heads // row, cfg.hd * row)


@pytest.mark.parametrize("kv,hd,dtype,shape", [
    (8, 64, jnp.bfloat16, (4, 128)),       # lfm2-8b-a1b
    (2, 64, jnp.float32, (1, 128)),
    (8, 32, jnp.float32, (2, 128)),
    (3, 64, jnp.float32, (3, 64)),         # a head left over: a head a row
    (2, 128, jnp.float32, (2, 128)),
], ids=["kv8-hd64", "kv2-hd64", "kv8-hd32", "kv3-hd64", "kv2-hd128"])
def test_a_written_chunk_reads_back_bit_for_bit(kv, hd, dtype, shape):
    """``init_block_pool`` -> ``_paged_write`` -> ``_paged_view``: whatever
    shape the pool's rows have, the gather path (prefill, verify) reads
    back the very values a chunk wrote, a head at its own place -- and a
    pad position's went to the scratch block."""
    cfg = LMConfig(vocab=64, d_model=96, n_heads=2 * kv, n_kv_heads=kv,
                   head_dim=hd, n_layers=1, d_ff=64, dtype=dtype)
    bs, W = 4, 6
    pool = init_block_pool(cfg, 9, bs)["l0"]
    stored = pool["k"].dtype        # float32 on the CPU (no bf16 scatter)
    assert pool["k"].shape == (9, bs) + shape
    rng = np.random.default_rng(3)
    k_new, v_new = (jnp.asarray(rng.normal(size=(2, kv, W, hd)), dtype)
                    for _ in "kv")
    tables = jnp.asarray([[5, 2, 7], [3, 8, 1]], jnp.int32)
    start = np.asarray([3, 0])
    pos = jnp.asarray(start[:, None] + np.arange(W)[None, :])
    valid = jnp.asarray(np.arange(W)[None, :] < np.asarray([[6], [4]]))
    pool = _paged_write(pool, tables, pos, valid, k_new, v_new)
    view = _paged_view(pool, tables, hd)
    for name, new in (("k", k_new), ("v", v_new)):
        assert view[name].shape == (2, kv, 3 * bs, hd)
        for r, (lo, n) in enumerate(zip(start, (6, 4))):
            np.testing.assert_array_equal(
                np.asarray(view[name][r, :, lo:lo + n]),
                np.asarray(new[r, :, :n].astype(stored)))
            assert not np.asarray(view[name][r, :, lo + n:]).any()
    assert init_block_pool(cfg, 9, bs, mesh=object())["l0"]["k"].shape == (
        9, bs, kv, hd)                     # a sharded pool: a head a row


def test_decode_round_in_place_is_blind_to_the_tables_padding():
    """The scheduler's one width a row count (genserver._decode_table_width)
    zero-pads the table far past what any row owns; in place that changes
    neither a token nor a live block of the pool."""
    from seldon_core_tpu.runtime.genserver import _decode_table_width

    width = _decode_table_width("interpret", 4, 3, 24)
    assert width == 16                     # the floor, not _pow2(3)
    wide = _three_rounds("interpret", width)
    _assert_same_round(wide, _three_rounds("interpret", 8))
    _assert_same_round(wide, _three_rounds(False, 8))


def test_the_cpu_takes_the_gather_path():
    pool = init_block_pool(CFG, 4, 16)
    assert decode_inplace(pool) is False


def test_private_pool_exact_length():
    """The static lane's private pool holds exactly 1 + B * ceil(total/bs)
    blocks — the scratch block and each row's own, in order — and no
    more: every block past a row's length is attended under a mask."""
    from seldon_core_tpu.models.generate import BLOCK_SIZE, private_pool

    cfg = LMConfig(vocab=64, d_model=64, n_heads=4, n_layers=1, d_ff=128)
    pool, tables = private_pool(cfg, 2, 130)
    n = -(-130 // BLOCK_SIZE)
    assert pool["l0"]["k"].shape[:2] == (1 + 2 * n, BLOCK_SIZE)
    np.testing.assert_array_equal(
        np.asarray(tables), 1 + np.arange(2 * n).reshape(2, n))


@pytest.mark.slow  # heavyweight equivalence check: full-suite/CI-shard coverage; excluded from the tier-1 time budget
def test_generate_matches_teacher_forced_lm_apply():
    """Greedy generate over its private pool equals teacher forcing through
    lm_apply, which has no cache at all."""
    from seldon_core_tpu.models.generate import generate
    from seldon_core_tpu.models.transformer import lm_apply

    cfg = LMConfig(vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                   dtype=jnp.float32)
    params = lm_init(jax.random.key(0), cfg)
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, 64, size=(2, 7)), jnp.int32
    )
    toks = np.asarray(generate(params, prompt, cfg, max_new_tokens=5))
    full = np.asarray(prompt)
    for i in range(5):
        logits = np.asarray(lm_apply(params, jnp.asarray(full), cfg))
        nxt = logits[:, -1, :].argmax(-1)
        np.testing.assert_array_equal(nxt, toks[:, i])
        full = np.concatenate([full, nxt[:, None].astype(np.int32)], axis=1)


# -- compiled for the chip, without the chip ---------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _described(one_chip, compile_of):
    """``compile_of(s)`` with the persistent cache off around it (a compile
    for a described chip is written there but can never be read back
    here); ``s(shape, dtype)`` is an operand on the described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return compile_of(s)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def _makers(text, shape):
    """The ops of a compiled program that PRODUCE a tensor of ``shape``
    (any element type)."""
    return set(re.findall(
        r"= \w+\[%s\]\S* ([\w\-]+)\(" % ",".join(map(str, shape)), text))


@pytest.mark.parametrize("B,H,kv,bs,nblk,dtype,W,hd", [
    (16, 24, 2, 256, 4, jnp.bfloat16, 1, HD),  # the dense cell's decode shape
    (32, 24, 2, 256, 8, jnp.bfloat16, 1, HD),
    (32, 24, 2, 256, 128, jnp.bfloat16, 1, HD),  # its one width since PR 28
    (8, 8, 4, 16, 16, jnp.bfloat16, 1, HD),  # the program's default block size
    (8, 8, 2, 16, 4, jnp.float32, 1, HD),
    (32, 32, 4, 256, 128, jnp.bfloat16, 4, HD),  # sdar-30b-a3b's block of 4
    (1, 32, 4, 256, 128, jnp.bfloat16, 4, HD),
    (16, 32, 4, 256, 128, jnp.bfloat16, 8, HD),  # the widest fold told to fit
    (8, 24, 2, 16, 16, jnp.float32, 4, HD),  # 12 x 4 query rows, float32 pool
    (32, 32, 8, 256, 4, jnp.bfloat16, 1, 64),    # lfm2-8b-a1b: 8 KV x 64
    (32, 32, 8, 256, 8, jnp.bfloat16, 1, 64),
    (32, 32, 8, 256, 128, jnp.bfloat16, 1, 64),  # the one width it is served
    (1, 32, 8, 256, 128, jnp.bfloat16, 1, 64),
    (64, 16, 4, 16, 64, jnp.bfloat16, 1, 64),    # chip_smoke.py's model
    (8, 8, 2, 16, 4, jnp.float32, 4, 64),        # a block of 4 at head 64
    (8, 16, 8, 16, 16, jnp.bfloat16, 1, 32),     # four heads a row
], ids=["cell-16x4", "cell-32x8", "cell-32x128-floor", "bs16-kv4", "f32",
        "block4-32x128", "block4-1x128", "block8-16x128", "block4-f32",
        "lfm2-32x4", "lfm2-32x8", "lfm2-32x128-floor", "lfm2-1x128",
        "smoke-hd64-bs16", "block4-hd64-f32", "hd32"])
def test_kernel_compiles_for_a_described_v5e(one_chip, B, H, kv, bs, nblk,
                                             dtype, W, hd):
    """Mosaic accepts the kernel at real widths: the 32-bit view of the
    interleaved block, the strided head loads, the chunk buffers' VMEM and,
    for a block of ``W`` queries a row, the folded queries, their outputs
    and the statistics beside them — every shape ``inplace_supported``
    says yes to here.  A compile, not a run: it says nothing about results
    or time."""
    assert inplace_supported(width=W, backend="tpu", pool_dtype=dtype,
                             mesh=None, block_size=bs, kv_heads=kv,
                             head_dim=hd, heads=H, rows=B)
    N = 64
    pair = heads_per_row(kv, hd)
    pool = (N, bs, kv // pair, hd * pair)     # as init_block_pool makes it
    compiled = _described(one_chip, lambda s: paged_decode_attention.lower(
        s((B, H, W, hd), dtype), s(pool, dtype), s(pool, dtype),
        s((B, nblk), jnp.int32),
        s((B,), jnp.int32), s((B,), jnp.int32), s((1,), jnp.int32),
        stats=W > 1,
    ).compile())
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,KV,G,hd", [
    (16, 8, 5, 128),    # brumby-14b's widest round: 16 rows of 8 KV x 5
    (1, 8, 5, 128),
    (4, 2, 1, 256),     # a head of two registers: 129 blocks, 43 tiles of 3
], ids=["brumby-16", "brumby-1", "hd256"])
def test_retention_step_compiles_for_a_described_v5e(one_chip, B, KV, G, hd):
    """The other kernel of the decode round (ops/retention.py, here beside
    the attention kernel's compiles because one test file describes the
    chip): Mosaic accepts it at the published widths -- the lane rotations
    of ``phi``, the transposes, the masked pick of a head's normalisers,
    its tiles' vector memory -- and the compiled call writes both state
    operands where they lie.  A compile, not a run."""
    from seldon_core_tpu.ops import retention as R

    assert R.step_supported(backend="tpu", state_dtype=jnp.float32,
                            head_dim=hd, kv_heads=KV, heads=KV * G, rows=B)
    N, P = 17, R.phi_width(hd)
    compiled = _described(one_chip, lambda s: jax.jit(
        R._fused_step, donate_argnums=(4, 5)).lower(
            s((B, KV, G, hd), jnp.bfloat16), s((B, KV, hd), jnp.bfloat16),
            s((B, KV, hd), jnp.bfloat16), s((B, KV), jnp.float32),
            s((N, KV * hd, P), jnp.float32), s((N, KV, P), jnp.float32),
            s((B,), jnp.int32), s((B,), jnp.bool_), s((B,), jnp.int32),
            s((), jnp.int32),
        ).compile())
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing={{1}: (6, {}), {2}: (7, {})}" in text
    # nothing but the call (and the program's own parameters) makes a
    # state-shaped tensor: no copy of the pool around the kernel
    assert _makers(text, (N, KV * hd, P)) <= {"parameter",
                                              "get-tuple-element"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("B,H,P,G,N", [
    (16, 64, 64, 8, 128),   # nemotron3-nano-30b-a3b's widest round
    (1, 64, 64, 8, 128),
    (4, 8, 256, 2, 256),    # a head of two chunks of rows, two registers wide
], ids=["nemotron-16", "nemotron-1", "p256-n256"])
def test_ssm_step_compiles_for_a_described_v5e(one_chip, B, H, P, G, N):
    """The third kernel of a decode round (ops/ssm.py ``_fused_step``, here
    beside the others' compiles because one test file describes the chip):
    Mosaic accepts it at the published widths -- the two transposes a
    chunk of rows, the masked pick of a group's B and C, its tiles' vector
    memory -- the compiled call writes the pool's entries where they lie,
    no op but the parameters produces a state-shaped tensor, and what
    ``jax.numpy`` makes around the call (``dt x``, the decays) is small.  A
    compile, not a run."""
    from seldon_core_tpu.ops import ssm

    assert ssm.step_supported(backend="tpu", state_dtype=jnp.float32,
                              heads=H, head_dim=P, groups=G, state=N, rows=B)
    NB = 128
    bf = jnp.bfloat16
    compiled = _described(one_chip, lambda s: jax.jit(
        ssm._fused_step, donate_argnums=(5,)).lower(
            s((B, H, P), bf), s((B, H), jnp.float32), s((H,), jnp.float32),
            s((B, G, N), bf), s((B, G, N), bf),
            s((NB, H, P, N), jnp.float32), s((B,), jnp.int32),
            s((B,), jnp.bool_), s((B,), jnp.int32), s((), jnp.int32),
        ).compile())
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing={{1}: (8, {})}" in text
    assert _makers(text, (NB, H, P, N)) <= {"parameter", "get-tuple-element"}
    assert not _makers(text, (B, H, P, N))      # no gathered copy of rows
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_decode_round_of_state_space_layers_compiles_around_the_kernel(
        one_chip):
    """A whole decode round of two Mamba-2 layers, an attention layer and
    an expert layer at the published widths, 16 rows, with both kernels
    forced as a TPU decides them: through ``_paged_block``'s inner ``jit``,
    the layer loop, the barrier behind each one-sub-layer block and the
    round's scan, each state-space layer's call still carries the pool
    aliased and no op but the parameters produces a state-shaped tensor --
    nor the ``[16, 64, 64, 128]`` copy of the rows' states that
    ``ssm_step``'s gather made."""
    from seldon_core_tpu.models.generate import (
        TransformerGenerator,
        paged_decode_round_jit,
    )

    B, NB = 16, 32
    unit = TransformerGenerator(
        vocab=512, d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
        n_layers=4, layer_kinds="mtme", conv_kernel=4, ssm_heads=64,
        ssm_head_dim=64, ssm_groups=8, ssm_state=128, d_expert=256,
        n_experts=8, moe_k=2, experts_held=4, router="sigmoid_bias",
        expert_act="relu2", d_shared=256, rope=False, tie_embeddings=False,
        dtype="bfloat16", seed=0)
    cfg = unit.cfg

    def on_chip(s, tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    def compile_of(s):
        params = on_chip(s, jax.eval_shape(
            lambda: unit.init_state(None)["params"]))
        shapes = jax.eval_shape(lambda: init_block_pool(cfg, NB, 256))
        # the chip keeps K/V in the model's dtype (the CPU stores float32)
        pool = {name: {k: s(a.shape, jnp.bfloat16 if k in "kv" else a.dtype)
                       for k, a in entry.items()}
                for name, entry in shapes.items()}
        return paged_decode_round_jit.lower(
            params, pool, s((B, 8), jnp.int32), s((B,), jnp.int32),
            s((B,), jnp.int32), s((B,), jnp.bool_), s((B,), jnp.bool_),
            s((B,), jnp.uint32), cfg, span=8, temperature=0.0, top_k=0,
            top_p=0.0, eos_token=-1, inplace=True,
            ssm_inplace=True).compile()

    text = _described(one_chip, compile_of).as_text()
    assert len(re.findall(
        r"output_to_operand_aliasing=\{\{1\}: \(8, \{\}\)\}", text)) == 2
    assert _makers(text, (NB, 64, 64, 128)) <= {"parameter",
                                                "get-tuple-element"}
    assert not _makers(text, (B, 64, 64, 128))


@pytest.mark.parametrize("B,KV,G,W,hd", [
    (16, 8, 5, 256, 128),   # brumby-14b's widest prefill call
    (1, 8, 5, 256, 128),
    (2, 2, 1, 128, 128),    # one query a KV head, a chunk of 128
], ids=["brumby-16", "brumby-1", "g1-w128"])
def test_retention_chunk_compiles_for_a_described_v5e(one_chip, B, KV, G, W,
                                                      hd):
    """The prefill call's kernel (ops/retention.py ``_fused_chunk``): Mosaic
    accepts it at the published widths -- the lane rotations of ``phi`` by
    a traced amount, ``phi(q) . S^T`` with both operands' lanes contracted,
    the turned cumulative decay, the head's operands and tiles in vector
    memory -- the compiled call writes both state operands where they lie,
    and nothing of ``phi``'s shape (``[G * W, P]``, ``[W, P]``) or the
    state's exists outside it.  A compile, not a run."""
    from seldon_core_tpu.ops import retention as R

    assert R.chunk_supported(backend="tpu", state_dtype=jnp.float32,
                             head_dim=hd, width=W, kv_heads=KV,
                             heads=KV * G)
    N, P = 17, R.phi_width(hd)
    bf = jnp.bfloat16
    compiled = _described(one_chip, lambda s: jax.jit(
        R._fused_chunk, donate_argnums=(5, 6)).lower(
            s((B, KV, G, W, hd), bf), s((B, KV, W, hd), bf),
            s((B, KV, W, hd), bf), s((B, KV, W), jnp.float32),
            s((B,), jnp.int32), s((N, KV * hd, P), jnp.float32),
            s((N, KV, P), jnp.float32), s((B,), jnp.int32),
            s((B,), jnp.bool_), s((B,), jnp.int32), s((), jnp.int32),
        ).compile())
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing={{1}: (10, {}), {2}: (11, {})}" in text
    assert _makers(text, (N, KV * hd, P)) <= {"parameter",
                                              "get-tuple-element"}
    for rows in (G * W, W):
        for lead in ((), (KV,), (B, KV)):
            assert not _makers(text, lead + (rows, P))
    # what jax.numpy makes around the call: the keys weighted, v turned,
    # the cumulative decay -- of the batch, and small
    assert compiled.memory_analysis().temp_size_in_bytes < (
        B * KV * W * hd * 16)


def test_paged_forward_of_retention_layers_compiles_around_the_kernel(
        one_chip):
    """The whole prefill program of two retention layers at the published
    head widths, 16 rows of 256 positions, with the chunk kernel forced as
    a TPU decides it: through ``_paged_block``'s inner ``jit`` and the
    layer loop every call still carries the pool aliased, no op but the
    parameters produces a state-shaped tensor and none a ``phi``-shaped
    one, and the program's temporaries stay under what PERF.md (section 6,
    PR 43) states for the cell's eight layers at 16 rows (217,549,824 B
    where the ``jax.numpy`` chunk form had 245,252,608 B): they are the
    batch's activations, not ``phi``."""
    from seldon_core_tpu.models.generate import TransformerGenerator
    from seldon_core_tpu.ops import retention as R

    B, W, N, KV, G, hd = 16, 256, 17, 8, 5, 128
    unit = TransformerGenerator(
        vocab=512, d_model=5120, n_heads=KV * G, n_kv_heads=KV, head_dim=hd,
        n_layers=2, layer_kinds="rr", dense_layers=2, d_ff=2048,
        qk_norm=True, tie_embeddings=False, norm_eps=1e-6,
        rope_base=1000000.0, dtype="bfloat16", seed=0)
    cfg = unit.cfg
    P = R.phi_width(hd)

    def on_chip(s, tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    def compile_of(s):
        params = on_chip(s, jax.eval_shape(
            lambda: unit.init_state(None)["params"]))
        pool = on_chip(s, jax.eval_shape(
            lambda: init_block_pool(cfg, N, 12288)))
        return paged_forward_jit.lower(
            params, s((B, W), jnp.int32), pool, s((B, 1), jnp.int32),
            s((B,), jnp.int32), s((B,), jnp.int32), cfg=cfg,
            last_only=True, fused=True).compile()

    compiled = _described(one_chip, compile_of)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert len(re.findall(
        r"output_to_operand_aliasing=\{\{1\}: \(10, \{\}\), "
        r"\{2\}: \(11, \{\}\)\}", text)) == 2
    assert _makers(text, (N, KV * hd, P)) <= {"parameter",
                                              "get-tuple-element"}
    for rows in (G * W, W):
        for lead in ((), (KV,), (B, KV), (1, KV)):
            assert not _makers(text, lead + (rows, P))
    assert compiled.memory_analysis().temp_size_in_bytes < 217_549_824



@pytest.mark.parametrize("held,D,F,gated,picks", [
    (128, 2048, 768, True, 1024),   # sdar-30b-a3b: a denoising pass at 32 rows
    (128, 2048, 768, True, 2048),   # ... the pass two blocks share
    (128, 2048, 768, True, 2120),   # ... a prefill call of 265 tokens
    (32, 2048, 1792, True, 128),    # lfm2-8b-a1b: a step at 32 rows
    (64, 2688, 1856, False, 96),    # nemotron3-nano-30b-a3b: 16 rows, 29 x 64
    (64, 2688, 1856, False, 1536),
])
def test_fused_expert_call_compiles_for_a_described_v5e(one_chip, held, D, F,
                                                        gated, picks):
    """The expert layer's ONE Pallas call (parallel/moe.py
    ``_experts_fused``) at the three expert configurations' widths, in
    bfloat16, for the chip the benchmark runs on: two whole experts in
    vector memory under the limit the call states for itself (18.9, 44 and
    40 MB: over the compiler's default 16 MiB), the halves of a gated first
    matmul split at a lane boundary, a width of 29 x 64 whole -- what
    interpret mode cannot refuse."""
    from seldon_core_tpu.parallel import moe

    assert moe.fused_supported(backend="tpu", dtype=jnp.bfloat16, mesh=None,
                               d_model=D, d_expert=F, gated=gated)
    bf16 = jnp.bfloat16

    def compile_of(s):
        return jax.jit(functools.partial(
            moe._experts_fused, gated=gated)).lower(
            s((picks, D), bf16),
            s((held, D, 2 * F) if gated else (held, F, D), bf16),
            s((held, F, D), bf16), s((held,), jnp.int32)).compile()

    text = _described(one_chip, compile_of).as_text()
    assert text.count("tpu_custom_call") == 1
