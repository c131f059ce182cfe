"""KV-cache decoding: cached generation must equal naive re-forward
decoding, and the generator unit must serve through the engine."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.models.generate import TransformerGenerator, generate
from seldon_core_tpu.models.transformer import LMConfig, lm_apply, lm_init
from seldon_core_tpu.runtime.engine import EngineService

CFG = LMConfig(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32)


def _naive_greedy(params, prompt, max_new):
    """Recompute the full forward every step — the no-cache reference."""
    tokens = prompt
    out = []
    for _ in range(max_new):
        logits = lm_apply(params, tokens, CFG)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        out.append(nxt)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


@pytest.mark.slow  # heavyweight equivalence check: full-suite/CI-shard coverage; excluded from the tier-1 time budget
def test_cached_generation_matches_naive():
    params = lm_init(jax.random.key(0), CFG)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, size=(2, 7)), jnp.int32
    )
    got = np.asarray(jax.jit(
        lambda p, t: generate(p, t, CFG, max_new_tokens=12)
    )(params, prompt))
    ref = np.asarray(_naive_greedy(params, prompt, 12))
    np.testing.assert_array_equal(got, ref)


def test_static_lane_runs_the_paged_programs(monkeypatch):
    """generate() and stream_chunks() dispatch paged_forward and
    paged_decode_round — the scheduler's programs over a private pool —
    and the module holds no second decoder block or cache layout."""
    import seldon_core_tpu.models.generate as gen_mod
    from seldon_core_tpu.models.generate import stream_chunks

    blocks = [n for n in vars(gen_mod)
              if n.startswith("_") and "block" in n]
    assert blocks == ["_paged_block"], blocks
    for gone in ("segment_forward", "decode_step", "prefill"):
        assert not hasattr(gen_mod, gone), gone
    calls = {"forward": 0, "round": []}
    fwd, rnd = gen_mod.paged_forward_jit, gen_mod.paged_decode_round_jit

    def forward(*a, **kw):
        calls["forward"] += 1
        return fwd(*a, **kw)

    def round_(*a, **kw):
        calls["round"].append(kw["span"])
        return rnd(*a, **kw)

    monkeypatch.setattr(gen_mod, "paged_forward_jit", forward)
    monkeypatch.setattr(gen_mod, "paged_decode_round_jit", round_)
    params = lm_init(jax.random.key(5), CFG)
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(0, 48, size=(2, 6)), jnp.int32
    )
    ref = np.asarray(generate(params, prompt, CFG, max_new_tokens=13))
    assert calls == {"forward": 1, "round": [12]}  # one prefill, one round
    np.testing.assert_array_equal(ref, _naive_greedy(params, prompt, 13))
    chunks = [np.asarray(c) for c in stream_chunks(
        params, prompt, CFG, max_new_tokens=13, chunk=5)]
    # prefill token + 4, then 5, then the 3-token tail
    assert calls == {"forward": 2, "round": [12, 4, 5, 3]}
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), ref)


def test_stream_largest_client_chunk_matches_generate():
    """The engine lets a client ask for chunks of up to 256 tokens: one
    round of 255 steps after the prefill token, then the tail — equal to
    generate() (long and short generations are the same code)."""
    from seldon_core_tpu.models.generate import stream_chunks

    params = lm_init(jax.random.key(9), CFG)
    prompt = jnp.asarray(
        np.random.default_rng(10).integers(0, 48, size=(1, 5)), jnp.int32
    )
    ref = np.asarray(generate(params, prompt, CFG, max_new_tokens=260))
    chunks = [np.asarray(c) for c in stream_chunks(
        params, prompt, CFG, max_new_tokens=260, chunk=256)]
    assert [c.shape[1] for c in chunks] == [256, 4]
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), ref)


def test_int8_kv_attention_close_to_float():
    """Int8 attention over a paged view vs the float formulation:
    per-token absmax rounding bounds the relative error at a few percent."""
    from seldon_core_tpu.models.generate import _attend_paged, _quantize_kv

    rng = np.random.default_rng(3)
    B, KV, g, hd, L = 2, 2, 4, 64, 96
    q = jnp.asarray(rng.normal(size=(B, KV * g, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, L, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, L, hd)), jnp.float32)
    start = jnp.full((B,), 79, jnp.int32)  # the query sees 80 positions
    want = np.asarray(_attend_paged(q, {"k": k, "v": v}, start))
    k_q, k_s = _quantize_kv(k)
    v_q, v_s = _quantize_kv(v)
    got = np.asarray(_attend_paged(
        q, {"k": k_q, "v": v_q, "k_s": k_s, "v_s": v_s}, start
    ))
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.03, f"int8 KV attention rel err {rel:.4f}"


def test_int8_kv_generate_wiring_and_logit_fidelity():
    """kv_quant='int8' end to end: the pool stores int8 values + scale
    planes, prefill and decode logits track the float pool's closely
    (only KV rounding separates them), and generate() runs the whole
    round over the quantized pool."""
    import dataclasses

    from seldon_core_tpu.models.generate import (
        paged_forward_jit, private_pool,
    )

    cfg_q = dataclasses.replace(CFG, kv_quant="int8")
    params = lm_init(jax.random.key(2), CFG)
    prompt = jnp.asarray(
        np.random.default_rng(4).integers(0, 48, size=(2, 9)), jnp.int32
    )
    outs = {}
    for name, cfg in (("f32", CFG), ("int8", cfg_q)):
        pool, tables = private_pool(cfg, 2, 16)
        assert ("k_s" in pool["l0"]) == (name == "int8")
        logits, pool = paged_forward_jit(
            params, prompt, pool, tables, jnp.zeros((2,), jnp.int32),
            jnp.full((2,), 9, jnp.int32), cfg=cfg)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        step_logits, _ = paged_forward_jit(
            params, first[:, None], pool, tables,
            jnp.full((2,), 9, jnp.int32), jnp.ones((2,), jnp.int32),
            cfg=cfg)
        outs[name] = (np.asarray(logits), np.asarray(step_logits))
    assert pool["l0"]["k"].dtype == jnp.int8
    for f, q in zip(outs["f32"], outs["int8"]):
        np.testing.assert_allclose(q, f, rtol=0.1, atol=0.05)
    toks = np.asarray(generate(params, prompt, cfg_q, max_new_tokens=8))
    assert toks.shape == (2, 8)
    assert (toks >= 0).all() and (toks < CFG.vocab).all()


def test_int8_kv_generator_unit_parameter():
    unit = TransformerGenerator(
        vocab=48, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_new_tokens=4, dtype="float32", kv_quant="int8",
    )
    state = unit.init_state(None)
    y = np.asarray(unit.predict(state, jnp.zeros((1, 5), jnp.float32)))
    assert y.shape == (1, 4)


def test_sampled_generation_valid_and_seeded():
    params = lm_init(jax.random.key(1), CFG)
    prompt = jnp.zeros((3, 4), jnp.int32)
    a = np.asarray(generate(params, prompt, CFG, max_new_tokens=8,
                            temperature=1.0, rng=jax.random.key(5)))
    b = np.asarray(generate(params, prompt, CFG, max_new_tokens=8,
                            temperature=1.0, rng=jax.random.key(5)))
    c = np.asarray(generate(params, prompt, CFG, max_new_tokens=8,
                            temperature=1.0, rng=jax.random.key(6)))
    np.testing.assert_array_equal(a, b)  # same key -> same sample
    assert (a != c).any()                # different key -> different path
    assert a.shape == (3, 8)
    assert (0 <= a).all() and (a < 48).all()


def test_generator_unit_serves_through_engine():
    spec = SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "gen", "predictors": [{
            "name": "p",
            "graph": {"name": "g", "type": "MODEL"},
            "components": [{
                "name": "g", "runtime": "inprocess",
                "class_path": "TransformerGenerator",
                "parameters": [
                    {"name": "vocab", "value": "48", "type": "INT"},
                    {"name": "d_model", "value": "32", "type": "INT"},
                    {"name": "n_layers", "value": "1", "type": "INT"},
                    {"name": "d_ff", "value": "64", "type": "INT"},
                    {"name": "max_new_tokens", "value": "6", "type": "INT"},
                    {"name": "dtype", "value": "float32", "type": "STRING"},
                ],
            }],
        }]}
    })
    engine = EngineService(spec)
    from seldon_core_tpu.messages import SeldonMessage

    prompt = np.zeros((2, 5), dtype=np.int64).tolist()
    msg = SeldonMessage.from_json(json.dumps({"data": {"ndarray": prompt}}))
    resp = asyncio.run(engine.predict(msg))
    toks = np.asarray(resp.data.array)
    assert toks.shape == (2, 6)
    assert np.isfinite(toks).all()
    assert ((0 <= toks) & (toks < 48)).all()


def test_generator_inside_a_two_unit_graph_serves_compiled():
    """The scheduler takes single-unit graphs only: behind a TRANSFORMER
    the generator serves through the compiled graph, i.e. generate() is
    traced under the graph's jit — the static lane's reason to exist."""
    from seldon_core_tpu.messages import SeldonMessage
    from seldon_core_tpu.models.generate import sanitize_prompt
    from seldon_core_tpu.models.tabular import MeanTransformer

    spec = SeldonDeploymentSpec.from_json_dict({
        "spec": {"name": "gen2", "predictors": [{
            "name": "p",
            "graph": {"name": "t", "type": "TRANSFORMER",
                      "children": [{"name": "g", "type": "MODEL"}]},
            "components": [
                {"name": "t", "runtime": "inprocess",
                 "class_path": "MeanTransformer"},
                {"name": "g", "runtime": "inprocess",
                 "class_path": "TransformerGenerator",
                 "parameters": [
                     {"name": "vocab", "value": "48", "type": "INT"},
                     {"name": "d_model", "value": "32", "type": "INT"},
                     {"name": "n_layers", "value": "2", "type": "INT"},
                     {"name": "d_ff", "value": "64", "type": "INT"},
                     {"name": "max_new_tokens", "value": "6", "type": "INT"},
                     {"name": "dtype", "value": "float32",
                      "type": "STRING"},
                 ]},
            ],
        }]}
    })
    engine = EngineService(spec)
    assert engine.genserver is None and engine.compiled is not None
    X = np.asarray([[3.0, 9.0, 1.0, 40.0, 7.0], [2.0, 2.0, 30.0, 5.0, 40.0]])
    msg = SeldonMessage.from_json(json.dumps({"data": {"ndarray": X.tolist()}}))
    got = np.asarray(asyncio.run(engine.predict(msg)).data.array)
    unit, state = engine.compiled.units["g"], engine.compiled.states["g"]
    tokens = sanitize_prompt(
        MeanTransformer().transform_input(None, jnp.asarray(X)), 48)
    assert np.asarray(tokens).sum() == 2  # min-max scaled: the 40s -> 1
    ref = np.asarray(generate(state["params"], tokens, unit.cfg,
                              max_new_tokens=6))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(
        ref, _naive_greedy(state["params"], tokens, 6))


def test_single_token_generation():
    params = lm_init(jax.random.key(2), CFG)
    prompt = jnp.zeros((2, 3), jnp.int32)
    y = np.asarray(generate(params, prompt, CFG, max_new_tokens=1))
    ref = np.asarray(_naive_greedy(params, prompt, 1))
    np.testing.assert_array_equal(y, ref)


def test_sampled_generator_declares_batch_coupling():
    """temperature>0 samples depend on row position in the stacked batch,
    so the unit must opt its graphs out of cross-request coalescing."""
    greedy = TransformerGenerator(temperature=0.0)
    sampled = TransformerGenerator(temperature=1.0)
    assert greedy.batch_coupled is False
    assert sampled.batch_coupled is True


def test_sampled_unit_varies_across_requests():
    """temperature>0 must not replay the same continuation for repeated
    identical prompts: the request counter in state varies the key."""
    u = TransformerGenerator(vocab=48, d_model=32, n_heads=4, n_layers=1,
                             d_ff=64, max_new_tokens=8, temperature=1.0,
                             dtype="float32")
    st = u.init_state(jax.random.key(0))
    X = jnp.zeros((2, 4), jnp.float32)
    from seldon_core_tpu.graph.units import normalize_output

    y1, st1, _ = normalize_output(u.predict(st, X), st)
    y2, st2, _ = normalize_output(u.predict(st1, X), st1)
    assert int(st2["requests"]) == 2
    assert (np.asarray(y1) != np.asarray(y2)).any()


def test_out_of_range_prompt_tokens_clamped():
    u = TransformerGenerator(vocab=48, d_model=32, n_heads=4, n_layers=1,
                             d_ff=64, max_new_tokens=4, dtype="float32")
    st = u.init_state(jax.random.key(0))
    wild = jnp.asarray([[-5.0, 3.2, 999.0, 47.0]], jnp.float32)
    tame = jnp.asarray([[0.0, 3.0, 47.0, 47.0]], jnp.float32)
    y_wild = np.asarray(u.predict(st, wild))
    y_tame = np.asarray(u.predict(st, tame))
    np.testing.assert_array_equal(y_wild, y_tame)  # clamp contract
    assert ((0 <= y_wild) & (y_wild < 48)).all()


def test_sample_token_top_k_and_top_p_truncation():
    """top-k must never sample outside the k highest logits; top-p must
    never sample outside the smallest prefix reaching mass p (and always
    keeps at least one token)."""
    from seldon_core_tpu.models.generate import sample_token

    logits = jnp.asarray([[5.0, 4.0, 3.0, -2.0, -3.0, -9.0]] * 4)
    for i in range(8):
        k = jax.random.key(i)
        tk = np.asarray(sample_token(logits, k, temperature=1.0, top_k=2))
        assert set(tk.tolist()) <= {0, 1}, tk
        tp = np.asarray(sample_token(logits, k, temperature=1.0,
                                     top_p=0.5))
        assert set(tp.tolist()) <= {0}, tp  # token 0 alone has mass >0.5
    # extreme top_p still yields a valid token
    t = np.asarray(sample_token(logits, jax.random.key(0),
                                temperature=1.0, top_p=1e-9))
    assert set(t.tolist()) <= {0}
    # greedy path ignores truncation knobs entirely
    g = np.asarray(sample_token(logits, jax.random.key(0)))
    np.testing.assert_array_equal(g, [0, 0, 0, 0])


def test_mask_after_eos_and_generate_eos_contract():
    """Positions strictly after a row's first eos become eos; rows
    without eos are untouched; generate() and stream_chunks() apply the
    same padding, and a stream whose rows have ALL stopped pads from
    the host (the early-stop branch is exercised, not just declared)."""
    from seldon_core_tpu.models.generate import (
        mask_after_eos, stream_chunks,
    )

    toks = jnp.asarray([[3, 7, 7, 5], [1, 2, 3, 4], [7, 1, 7, 2]])
    got = np.asarray(mask_after_eos(toks, 7))
    np.testing.assert_array_equal(
        got, [[3, 7, 7, 7], [1, 2, 3, 4], [7, 7, 7, 7]])
    # disabled sentinel is a no-op
    np.testing.assert_array_equal(np.asarray(mask_after_eos(toks, -1)),
                                  np.asarray(toks))

    # B=1 so one row stopping means ALL rows stopped: pick an eos whose
    # FIRST occurrence in the baseline is mid-sequence, so (a) masking
    # changes real tokens and (b) the stream's host-padding branch runs
    params = lm_init(jax.random.key(0), CFG)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 48, size=(1, 7)), jnp.int32
    )
    # SAMPLED with a fixed key: the untrained greedy baseline is a
    # constant token (eos at position 0 masks nothing); sampling gives a
    # varied, still-deterministic sequence with a usable mid-stream eos
    kw = dict(temperature=1.0, rng=jax.random.key(5))
    base = np.asarray(generate(params, prompt, CFG, max_new_tokens=12,
                               **kw))[0]
    eos = first_at = None
    for j in range(1, 9):
        tok = int(base[j])
        if tok not in base[:j].tolist() and (base[j + 1:] != tok).any():
            eos, first_at = tok, j
            break
    assert eos is not None, f"no usable eos in baseline {base}"
    ref = np.asarray(generate(params, prompt[:1], CFG, max_new_tokens=12,
                              eos_token=eos, **kw))[0]
    np.testing.assert_array_equal(ref[:first_at + 1], base[:first_at + 1])
    assert (ref[first_at:] == eos).all()
    assert (base[first_at + 1:] != eos).any()  # masking changed tokens
    # stream == generate under eos padding, including host-padded chunks
    chunks = [np.asarray(c) for c in stream_chunks(
        params, prompt[:1], CFG, max_new_tokens=12, chunk=3,
        eos_token=eos, **kw)]
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1)[0], ref)
    # the final chunk(s) past the stop are pure eos padding
    assert (chunks[-1] == eos).all()


def _prefix_unit(prefix_tokens="", **kw):
    return TransformerGenerator(
        vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_new_tokens=10, dtype="float32", prefix_tokens=prefix_tokens,
        **kw)


def test_prefix_cache_equals_full_prefill():
    """A shared prefix on the static lane is its ids in front of every
    row: the unit must reproduce the full-prompt generation EXACTLY (f32
    greedy) for batched suffixes, through both predict() and
    stream_tokens()."""
    rng = np.random.default_rng(11)
    prefix_ids = rng.integers(0, 48, size=(6,)).tolist()
    sufs = jnp.asarray(rng.integers(0, 48, size=(3, 5)), jnp.float32)
    unit = _prefix_unit(",".join(map(str, prefix_ids)))
    state = unit.init_state(None)
    full = jnp.concatenate(
        [jnp.broadcast_to(jnp.asarray(prefix_ids, jnp.int32), (3, 6)),
         sufs.astype(jnp.int32)], axis=1)
    ref = np.asarray(generate(state["params"], full, unit.cfg,
                              max_new_tokens=10))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(unit.predict)(state, sufs)), ref)
    chunks = [np.asarray(c) for c in unit.stream_tokens(state, sufs, 4)]
    np.testing.assert_array_equal(np.concatenate(chunks, axis=1), ref)


def test_prefix_cache_unit_serves():
    """prefix_tokens as a deployment parameter: unit state carries the
    prefix's token ids (no KV of its own) and every predict equals the
    no-prefix unit fed the concatenated prompt."""
    plain = TransformerGenerator(
        vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_new_tokens=6, dtype="float32")
    pref = TransformerGenerator(
        vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_new_tokens=6, dtype="float32", prefix_tokens="4, 9, 2")
    sp, s2 = plain.init_state(None), pref.init_state(None)
    assert "prefix_cache" not in s2
    np.testing.assert_array_equal(np.asarray(s2["prefix_ids"]), [4, 9, 2])
    suf = jnp.asarray([[7, 8, 20, 1]], jnp.float32)
    full = jnp.asarray([[4, 9, 2, 7, 8, 20, 1]], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pref.predict(s2, suf)),
        np.asarray(plain.predict(sp, full)))
    import pytest as _pytest

    with _pytest.raises(ValueError, match="outside vocab"):
        TransformerGenerator(vocab=48, prefix_tokens="99")


def test_sampled_state_writeback_preserves_prefix_cache():
    """temperature>0 writes state back (request counter); the write-back
    must carry EVERY state key — dropping the prefix would silently turn
    every later request prefix-less."""
    unit = TransformerGenerator(
        vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_new_tokens=4, dtype="float32", temperature=0.8,
        prefix_tokens="4,9,2")
    state = unit.init_state(None)
    y, aux = unit.predict(state, jnp.asarray([[7, 8]], jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(aux.state["prefix_ids"]), [4, 9, 2])
    y2 = unit.predict(aux.state, jnp.asarray([[7, 8]], jnp.float32))[0]
    assert np.asarray(y2).shape == (1, 4)


def test_prefix_cache_with_int8_kv_serves():
    """A shared prefix composes with kv_quant='int8': the prefix's K/V
    are quantized into the pool like every other position, so the unit's
    tokens equal the concatenated prompt's over the same int8 pool."""
    unit = _prefix_unit("4,9,2,30", kv_quant="int8")
    state = unit.init_state(None)
    sufs = jnp.asarray([[7, 8, 20], [1, 2, 3]], jnp.float32)
    got = np.asarray(unit.predict(state, sufs))
    assert got.shape == (2, 10)
    assert (got >= 0).all() and (got < CFG.vocab).all()
    full = jnp.concatenate(
        [jnp.broadcast_to(jnp.asarray([4, 9, 2, 30], jnp.int32), (2, 4)),
         sufs.astype(jnp.int32)], axis=1)
    np.testing.assert_array_equal(got, np.asarray(generate(
        state["params"], full, unit.cfg, max_new_tokens=10)))
