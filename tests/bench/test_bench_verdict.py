"""``correct``'s numeric verdict (bench/lib/verdict.py) on made-up arrays,
the judged batch (bench/lib/sample.py) as arithmetic, a toy block with a
real top-k router — where bf16 against float32 spoils a few rows wholly
and lost precision spoils every row — and the numerics child on the CPU
with the timed path broken underneath."""

import subprocess
import sys

import bench_paths
import numpy as np
import pytest
from bench_paths import BENCH, REPO
from lib import buckets, sample, traffic
from lib.engine import unit_spec
from lib.manifest import Manifest
from lib.verdict import judge

MAN = Manifest(REPO)


def numerics_section(prefill=None, decode=None):
    share = {k: v for k, v in (("prefill", prefill), ("decode", decode))
             if v is not None}
    return {"tolerance_rms": 0.1,
            **({"discrete_share": share} if share else {})}


FLIPPED = [1.0] * 8 + [0.04] * 24       # 8 of 32 rows took another expert


@pytest.mark.parametrize("prefill, decode, section, ok", [
    ([0.04] * 32, [0.0] * 32, numerics_section(), True),
    (FLIPPED, [0.0] * 32, numerics_section(), False),
    (FLIPPED, [0.0] * 32, numerics_section(prefill=0.5), True),
    (FLIPPED, [0.0] * 32, numerics_section(prefill=0.25), True),
    (FLIPPED, [0.0] * 32, numerics_section(prefill=0.2), False),
    # lost precision: every row, so no share under 1 admits it
    ([0.3] * 32, [0.0] * 32, numerics_section(prefill=0.5), False),
    ([0.3] * 32, [0.0] * 32, numerics_section(prefill=0.99), False),
    ([0.3] * 32, [0.0] * 32, numerics_section(prefill=1.0), True),
    # a share stated for one side says nothing of the other
    ([0.04] * 32, FLIPPED, numerics_section(prefill=0.5), False),
    ([0.04] * 32, FLIPPED, numerics_section(prefill=0.5, decode=0.25), True),
    ([0.04] * 32, [0.19] * 32, numerics_section(), True),   # limit 0.2
    ([0.04] * 31 + [float("nan")], [0.0] * 32, numerics_section(), False),
    ([0.04] * 32, [0.0] * 31 + [float("nan")], numerics_section(), False),
], ids=["sound", "flips-share0", "flips-half", "flips-exact", "flips-under",
        "lost-half", "lost-99", "lost-all", "decode-flips-unstated",
        "decode-flips-stated", "decode-under-twice", "nan-prefill",
        "nan-decode"])
def test_verdict_on_made_up_arrays(prefill, decode, section, ok):
    v = judge(prefill, decode, section)
    assert v["ok"] is ok, v
    assert v["rows"] == 32 and v["tolerance"] == pytest.approx(0.1)
    assert v["decode"]["limit"] == pytest.approx(0.2)


def test_a_missing_share_means_zero_and_the_limit_scales_with_the_rms():
    v = judge([0.5, 0.9], [0.1, 0.3], {"tolerance_rms": 0.1}, ref_rms=10.0)
    assert v["ok"] is True and v["tolerance"] == pytest.approx(1.0)
    assert v["prefill"]["allowed"] == v["decode"]["allowed"] == 0.0
    assert (v["prefill"]["max"], v["prefill"]["median"]) == (0.9, 0.7)
    v = judge([0.5, 1.1], [0.1, 0.3], {"tolerance_rms": 0.1,
                                       "discrete_share": {}}, ref_rms=10.0)
    assert v["ok"] is False
    assert (v["prefill"]["over"], v["prefill"]["share"]) == (1, 0.5)
    with pytest.raises(ValueError, match="one of each"):
        judge([0.1], [], {"tolerance_rms": 0.1})


def test_the_verdict_and_the_sample_import_neither_jax_nor_numpy():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]);"
         "import lib.verdict, lib.sample;"
         "print(sorted(m for m in ('jax', 'numpy') if m in sys.modules))",
         BENCH], capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"


# -- the judged batch ---------------------------------------------------------


def cell_plan(cell_name="starcoder2-3b.codegen.r80"):
    cell = MAN.cell(cell_name)
    cfg = MAN.config(cell["config"])
    mix, dep = MAN.mix(cell["mix"]), MAN.deployment(cell, cfg)
    seconds = MAN.doc["run_seconds"]
    reqs = traffic.open_loop(mix, cell["arrivals"]["rate"], seconds,
                             cell["drain_s"])
    prompts = [r.prompt_len for r in reqs if r.measured]
    return (sample.plan(prompts, dep, buckets.caps(mix)["max_positions"]),
            prompts, dep, buckets.caps(mix), cfg)


def test_codegen_r80_is_judged_on_every_slots_row_at_the_mixs_own_lengths():
    plan, prompts, dep, caps, _ = cell_plan()
    assert len(plan["lens"]) == plan["offered"] == dep["slots"] == 32
    assert plan["lens"][0] == min(prompts)
    assert plan["lens"][-1] == max(prompts) == 1024       # the longest
    assert plan["lens"] == sorted(plan["lens"])
    assert plan["chunks"] == [1, 4]
    assert sum(plan["blocks"]) + 1 <= dep["pool_blocks"]
    # every call the child makes is a program the ladder loaded
    have = buckets.programs(dep, caps)
    C, bs, span = dep["prefill_chunk"], dep["block_size"], dep["span"]
    for k in range(plan["chunks"][1]):
        live = [n for n in plan["lens"] if n > k * C]
        shape = (buckets.pow2(len(live)), C, buckets.pow2(max(
            buckets.blocks(min(n, (k + 1) * C), bs) for n in live)))
        assert shape in have["prefill"], shape
    assert (32, buckets.pow2(buckets.blocks(1024 + span, bs))) == (32, 8)
    assert (32, 8) in have["decode"]


def test_a_pool_too_small_for_every_slot_takes_the_longest_rows_it_holds():
    dep = {"slots": 8, "span": 8, "block_size": 16, "prefill_chunk": 32,
           "pool_blocks": 12}
    plan = sample.plan([10, 20, 30, 40, 50, 60, 70, 200], dep, 120)
    # 200 is cut to 120 - 8; blocks of n + 8: 8, 5, ... : 11 free blocks
    assert plan["offered"] == 8 and plan["lens"] == [112]
    assert plan["blocks"] == [8]
    dep["pool_blocks"] = 8
    with pytest.raises(ValueError, match="holds no row"):
        sample.plan([200], dep, 120)


@pytest.mark.parametrize("n, rows, want", [
    (5, 4, [0, 1, 3, 4]), (3, 4, [0, 1, 2]), (9, 1, [8]), (140, 32, None)])
def test_pick_takes_even_ranks_with_both_ends(n, rows, want):
    got = sample.pick(list(range(n)), rows)
    assert got[0] == (0 if rows > 1 else n - 1) and got[-1] == n - 1
    assert len(got) == min(n, rows) and got == sorted(set(got))
    if want is not None:
        assert got == want


def dense_row_bytes(config):
    from lib.manifest import arch_module

    reference = arch_module(BENCH, {"arch": "dense_gelu"}, "reference")
    return lambda length: reference.row_bytes(config, length, 9)


LONG_ROWS = [4088 - 128 * i for i in range(32)]        # 4,088 ... 120


@pytest.mark.parametrize("vocab, totals, lengths, rows_of_the_longest", [
    (49152, None, [1280, 768, 512, 256], 1),
    (200192, None, [1280, 768, 512, 256], 1),
    (200192, LONG_ROWS, None, 1),
], ids=["codegen", "codegen-at-200k", "rows-to-4088-at-200k"])
def test_reference_groups_hold_every_row_once_within_the_budget(
        vocab, totals, lengths, rows_of_the_longest):
    """Groups by padded length, sized by what the ARCHITECTURE says a row
    holds: at a vocabulary of 200,192 a row of 4,088 positions no longer
    makes every row a group of its own (its [S, V] logits were 3.27 GB, so
    ``GROUP_BYTES // (S V 4)`` was 0 for every row over 1,340)."""
    plan, _, dep, _, cfg = cell_plan()
    totals = totals or [n + 8 for n in plan["lens"]]
    row_bytes = dense_row_bytes({**cfg, "vocab_size": vocab})
    groups = sample.reference_groups(totals, row_bytes, dep["block_size"])
    assert sorted(i for _, g in groups for i in g) == list(range(32))
    assert len(groups[0][1]) == rows_of_the_longest
    if lengths:
        assert [length for length, _ in groups] == lengths
    assert max(len(g) for _, g in groups) > 1
    # a shape the reference compiles is (rows, length): at most one a
    # padded length and a half of what a shape a row came to
    shapes = {(len(g), length) for length, g in groups}
    assert len(shapes) <= len({length for length, _ in groups}) + 1
    assert len(shapes) <= len(totals) // 2
    for length, g in groups:
        assert length % dep["block_size"] == 0
        assert 0 <= length - max(totals[i] for i in g) < dep["block_size"]
        assert (len(g) == 1
                or len(g) * row_bytes(length) <= sample.GROUP_BYTES)
    # neighbours in length together: no group reaches into the next
    for (_, a), (_, b) in zip(groups, groups[1:]):
        assert min(totals[i] for i in a) >= max(totals[i] for i in b)
    # the judged positions' logits are 7 MB of what a row holds at a
    # vocabulary of 200,192, where [S, V] was 3.27 GB at 4,088 positions
    assert 9 * vocab * 4 < 0.02 * row_bytes(max(totals))


# -- a toy block with a real top-k router -------------------------------------

D, E, F, K, LAYERS, V, ROWS = 128, 64, 64, 4, 3, 256, 96
# what this toy "configuration" states: at most a quarter of the rows may
# lie over the limit.  Its readings (CPU, seeds 0-5): the program's
# largest share 0.135, every row of the control over (share 1.0)
TOY_NUMERICS = {"tolerance_rms": 0.1,
                "discrete_share": {"prefill": 0.25, "decode": 0.25}}


def toy_weights(seed):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.key(seed), 4 * LAYERS + 2))

    def w(*shape):
        return (jax.random.normal(next(keys), shape)
                / np.sqrt(shape[-2])).astype(jnp.bfloat16)

    return {"embed": w(V, D) * np.sqrt(V), "head": w(D, V),
            "layers": [{"r": w(D, E) * 2, "w1": w(E, D, F), "w3": w(E, D, F),
                        "w2": w(E, F, D)} for _ in range(LAYERS)]}


def toy_forward(p, toks, dtype):
    """Rows of one token through ``LAYERS`` routed blocks: sigmoid scores,
    the K largest, weights normalised over them, gated-SiLU experts, a
    norm before and after; float32 at ``highest`` or bf16 throughout."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6).astype(x.dtype)

    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(dtype), p)
        x = p["embed"][toks]
        for lp in p["layers"]:
            m = norm(x)
            top, idx = jax.lax.top_k(
                jax.nn.sigmoid((m @ lp["r"]).astype(jnp.float32)), K)
            wgt = (top / top.sum(-1, keepdims=True)).astype(dtype)
            h = (jax.nn.silu(jnp.einsum("td,edf->tef", m, lp["w1"]))
                 * jnp.einsum("td,edf->tef", m, lp["w3"]))
            y = jnp.take_along_axis(
                jnp.einsum("tef,efd->ted", h, lp["w2"]), idx[..., None], 1)
            x = x + norm((y * wgt[..., None]).sum(1))
        return np.asarray((norm(x) @ p["head"]).astype(jnp.float32))


def mantissa_rounded(p, bits):
    import jax
    import jax.numpy as jnp

    def f(a):
        m, e = np.frexp(np.asarray(a, np.float32))
        return jnp.asarray(np.ldexp(np.round(m * 2 ** bits) / 2 ** bits, e),
                           jnp.bfloat16)
    return jax.tree.map(f, p)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_routers_flips_spoil_few_rows_wholly_and_lost_precision_all(seed):
    import jax
    import jax.numpy as jnp

    p = toy_weights(seed)
    toks = jax.random.randint(jax.random.key(100 + seed), (ROWS,), 0, V)
    ref = toy_forward(p, toks, jnp.float32)
    rms = float(np.sqrt((ref ** 2).mean()))
    zeros = [0.0] * ROWS
    # the program: bf16 on the same weights
    err = np.abs(toy_forward(p, toks, jnp.bfloat16) - ref).max(-1)
    over = err > 0.1 * rms
    assert 0 < over.mean() < 0.25, over.mean()     # a minority of the rows
    assert err[over].min() > 0.5 * rms             # ...far over the limit
    assert err[~over].max() < 0.05 * rms           # ...the rest far under
    assert judge(err.tolist(), zeros, TOY_NUMERICS, rms)["ok"] is True
    assert judge(err.tolist(), zeros, {"tolerance_rms": 0.1}, rms)[
        "ok"] is False
    # no single limit does it: one that admits the program's flipped rows
    # admits most rows of the control
    low = np.abs(toy_forward(mantissa_rounded(p, 4), toks, jnp.float32)
                 - ref).max(-1)
    assert np.median(low) < err[over].min()
    # the control: every row over, so the stated share does not admit it
    assert (low > 0.1 * rms).all()
    v = judge(low.tolist(), zeros, TOY_NUMERICS, rms)
    assert v["ok"] is False and v["prefill"]["share"] == 1.0


# -- the numerics child with the timed path broken underneath -----------------


def child_spec(tmp_path):
    root = bench_paths.copy_root(tmp_path)
    cell = MAN.cell("starcoder2-3b.codegen.r80")
    cfg = {**MAN.config("starcoder2-3b"), **bench_paths.TINY_CONFIG,
           "name": "tiny"}
    dep = {**cfg["deployment"], **bench_paths.TINY_DEPLOYMENT}
    return {"repo": REPO, "platforms": ["cpu"], "config": cfg,
            "bench_dir": root + "/bench", "deployment": dep,
            "unit": unit_spec(cfg, dep, 2 ** 31 + 9, 24),
            # four slots, chunks of 32: rows of one, one, two, three chunks
            "sample": sample.plan([9, 31, 50, 64, 90], dep, 120),
            "sample_seed": 23, "cell": cell["name"]}


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_the_child_drives_the_scheduler_s_shapes_and_is_sound(tmp_path,
                                                               monkeypatch):
    from lib import children
    from seldon_core_tpu.models import generate

    calls = []
    real = generate.paged_forward_jit

    def recording(params, toks, pool, tables, start, width, **kw):
        calls.append((toks.shape, tables.shape, np.asarray(start).tolist(),
                      np.asarray(width).tolist()))
        return real(params, toks, pool, tables, start, width, **kw)

    monkeypatch.setattr(generate, "paged_forward_jit", recording)
    spec = child_spec(tmp_path)
    num = children.numerics(spec, CPU)
    assert num["ok"] is True, num["verdict"]
    assert num["lens"] == [9, 31, 64, 90] and num["chunks"] == [1, 3]
    # chunk by chunk over the rows still prefilling, start advancing per
    # row, rows and tables padded to powers of two (block 16, span 8)
    assert calls == [
        ((4, 32), (4, 2), [0, 0, 0, 0], [9, 31, 32, 32]),
        ((2, 32), (2, 4), [32, 32], [32, 32]),
        ((1, 32), (1, 8), [64], [26])]
    assert max(num["by_row"]["prefill_err"]) < 0.5 * num["tolerance"]


@pytest.mark.parametrize("fault", ["later-chunks-at-offset-0",
                                   "a-decoded-token-altered"])
def test_a_fault_in_the_timed_path_comes_out_not_ok(tmp_path, monkeypatch,
                                                    fault):
    """What two rows of one chunk could not see: a prefill that loses a
    later chunk's offset leaves every one-chunk row sound and spoils the
    others; and one altered token of one row's decode round is enough."""
    import jax.numpy as jnp

    from lib import children
    from seldon_core_tpu.models import generate

    if fault == "later-chunks-at-offset-0":
        real = generate.paged_forward_jit

        def broken(params, toks, pool, tables, start, width, **kw):
            return real(params, toks, pool, tables, jnp.zeros_like(start),
                        width, **kw)

        monkeypatch.setattr(generate, "paged_forward_jit", broken)
    else:
        real = generate.paged_decode_round_jit

        def broken(*a, **kw):
            toks, *rest = real(*a, **kw)
            return (toks.at[2, 5].set((toks[2, 5] + 1) % 512), *rest)

        monkeypatch.setattr(generate, "paged_decode_round_jit", broken)
    num = children.numerics(child_spec(tmp_path), CPU)
    assert num["ok"] is False
    v, rows = num["verdict"], num["by_row"]
    if fault == "later-chunks-at-offset-0":
        assert v["prefill"]["over"] == 2          # the rows of 2 and 3 chunks
        assert max(rows["prefill_err"][:2]) < 0.5 * v["tolerance"]
        assert min(rows["prefill_err"][2:]) > 2 * v["tolerance"]
    else:
        assert v["prefill"]["over"] == 0 and v["decode"]["over"] == 1
        assert rows["decode_margin"][2] > v["decode"]["limit"]
