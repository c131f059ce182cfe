"""What the scheduler is told about a generator (models/served.py), for one
tiny generator of each kind the benchmark's cells serve
(tests/served_kinds.py): the counts of a round and of a prefill call
against values reckoned by hand, every refused lane from BOTH the unit and
the server, a row's state bytes against the pool's own, and the cost
features' weight bytes against ``lm_init``'s own tree."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seldon_core_tpu.models.generate as G
from seldon_core_tpu.models.generate import TransformerGenerator
from seldon_core_tpu.models.served import BRINGS, served
from seldon_core_tpu.models.transformer import lm_init
from seldon_core_tpu.runtime import genserver
from seldon_core_tpu.runtime.genserver import GenServer

import served_kinds  # noqa: E402, I001 - tests/served_kinds.py, beside this file

KINDS = list(served_kinds.KINDS)
EXPERTS = 8


@pytest.fixture(scope="module", params=KINDS)
def generator(request):
    unit = served_kinds.unit_of(request.param)
    return request.param, unit, served(unit.cfg)


# -- how a round is driven ----------------------------------------------------


def test_a_round_is_driven_by_what_the_description_says(generator):
    kind, unit, d = generator
    if kind == "diffusion":
        assert (d.quantum, d.block_passes, d.picks_first) == (4, 5, False)
        assert d.round == {"block_length": 4, "denoising_steps": 4}
        # a row's next round starts where its last whole block ends, and
        # the host holds the block the prompt's remainder goes into
        assert [d.round_base(n) for n in (4, 6, 13)] == [4, 4, 12]
        held = d.held(2)
        assert held.shape == (2, 4) and held.dtype == np.int32
        assert not held.any()
        # ... or says which rows bring the last block of the round before
        # (no id is negative): of four padded rows the second of three
        assert d.held(4, [False, True, False]).tolist() == [
            [0] * 4, [BRINGS] * 4, [0] * 4, [0] * 4]
        assert BRINGS < 0
    else:
        assert (d.quantum, d.block_passes, d.picks_first) == (1, 1, True)
        assert d.round == {"block_length": 1, "denoising_steps": 1}
        assert [d.round_base(n) for n in (4, 6, 13)] == [4, 6, 13]
        assert d.held(2) is None
    assert d.holds == ("state" if kind == "retention" else "KV")
    assert d.stateful == (kind in ("conv", "retention"))
    assert d.routed == {"diffusion": 2, "conv": 3}.get(kind, 0)
    assert d.counts_experts == (kind in ("diffusion", "conv"))


# -- what a call is given -----------------------------------------------------

#: a round of span 8 over live rows holding 6 and 13 positions
ROUND = {
    # a token a step: 8 passes, each step over ~n + 4 positions
    "attention": dict(passes=8, blocks=8, row_passes=16,
                      kv_positions=8 * (6 + 4) + 8 * (13 + 4),
                      expert_slots=0, shared_passes=0),
    # blocks of 4 under 4 denoising passes and the K/V one: 2 blocks, 10
    # passes, each reading the cache up to its block's end (rows start at
    # 4 and 12); the K/V pass of a block skips its last expert layer, and
    # the first block's shares a pass of the device with the second's
    # first; the second's is the next round's to run, so a row in its
    # first round runs 9
    "diffusion": dict(passes=10, blocks=2, row_passes=18,
                      kv_positions=5 * (8 + 12) + 5 * (16 + 20),
                      expert_slots=(10 * 2 - 2) * EXPERTS, shared_passes=1),
    # three of five layers hold experts (two leading dense ones)
    "conv": dict(passes=8, blocks=8, row_passes=16, kv_positions=216,
                 expert_slots=8 * 3 * EXPERTS, shared_passes=0),
    "retention": dict(passes=8, blocks=8, row_passes=16, kv_positions=216,
                      expert_slots=0, shared_passes=0),
}


def test_a_rounds_counts_are_the_hand_reckoned_ones(generator):
    kind, unit, d = generator
    assert d.round_counts([6, 13], 8) == ROUND[kind]


#: who brings a block into a round over rows holding 8 and 16 positions ->
#: (row_passes, shared_passes) by the round's blocks
BRINGING = {
    # rows in their first round: the round's last K/V pass is the next's
    "fresh": ([False, False], lambda blocks: (2 * (5 * blocks - 1),
                                              blocks - 1)),
    # rows that rode the round before: its last block's K/V pass runs here
    "brings": ([True, True], lambda blocks: (2 * 5 * blocks, blocks)),
    # one of each: the pass of the device is shared whoever brings
    "mixed": ([True, False], lambda blocks: (2 * 5 * blocks - 1, blocks)),
}


@pytest.mark.parametrize("who", list(BRINGING))
@pytest.mark.parametrize("span", [4, 8, 12, 16])
def test_a_round_of_diffusion_blocks_counts_the_passes_it_runs(
        generator, span, who):
    """``passes`` and ``expert_slots`` are the round's own blocks' (what the
    benchmark takes a round for: ``block_passes`` a block, the K/V pass an
    expert layer short), whoever runs them; ``row_passes`` what each real
    row RUNS -- its last block's K/V pass is the next round's, the block it
    brings this one's -- and ``shared_passes`` the passes of the device that
    served two: a K/V pass always rides the next block's first denoising
    pass, in this round or the next.  A generator that decodes a token a
    step shares none and is told nothing of blocks, whatever its layers --
    a state-space one too (tests/test_nemotron_block.py's)."""
    kind, unit, d = generator
    brings, want = BRINGING[who]
    counts = d.round_counts([8, 16], span, brings)
    if kind == "diffusion":
        blocks = span // 4
        assert (counts["row_passes"], counts["shared_passes"]) == want(blocks)
        assert counts["passes"] == 5 * blocks
        assert counts["expert_slots"] == (5 * 2 - 1) * blocks * EXPERTS
        # the passes of the device: a block's denoising passes, no other
        assert counts["passes"] - blocks == 4 * blocks
    else:
        assert counts == d.round_counts([8, 16], span)
        assert (counts["row_passes"], counts["shared_passes"]) == (
            2 * span, 0)
        assert counts["passes"] == span
    ssm = served(TransformerGenerator(
        vocab=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
        n_layers=3, layer_kinds="mte", ssm_heads=8, ssm_head_dim=8,
        ssm_groups=2, ssm_state=16, conv_kernel=4, d_expert=24, n_experts=8,
        moe_k=3, rope=False, dtype="float32").cfg)
    assert ssm.round_counts([6, 13], span)["shared_passes"] == 0


def test_a_prefill_calls_counts_are_the_hand_reckoned_ones(generator):
    """Two rows: 8 tokens from position 0, 3 from position 8 (a second
    chunk: where layers keep a state it is carried in)."""
    kind, unit, d = generator
    assert d.prefill_counts([0, 8], [8, 3]) == dict(
        tokens=11, kv_positions=8 + 11,
        # causal: a token attends to what the row holds and to itself
        attended=(8 * 0 + 8 * 9 // 2) + (3 * 8 + 3 * 4 // 2),
        # counted only where the call counts the experts it read: a prefill
        # that chooses no token (two expert layers of eight)
        expert_slots=2 * EXPERTS if kind == "diffusion" else 0,
        carried_rows=1 if kind in ("conv", "retention") else 0)


def test_the_kernels_answer_is_asked_once_and_counted(generator, monkeypatch):
    """On the CPU no kernel serves and nothing is counted; where
    ``decode_inplace`` / ``retention_fused`` / ``experts_fused`` say
    "interpret" (as a TPU says yes) the decode program takes it as
    ``inplace=``, a prefill call of a width as ``fused=`` -- asked once a
    width -- both as ``experts_fused=`` where the generator has expert
    layers (no other is given the argument), and the counts follow."""
    kind, unit, d = generator
    routed = kind in ("diffusion", "conv")
    pool = G.init_block_pool(unit.cfg, 4, 8)
    k = d.kernels(pool, None, 4, jnp.float32)
    assert not k.inplace
    assert k.round_counts(8, 10) == {"inplace_steps": 0,
                                     "retention_fused_steps": 0,
                                     "ssm_fused_steps": 0,
                                     "experts_fused_passes": 0}
    assert k.experts_how == ({"experts_fused": False} if routed else {})
    assert k.round_how == {"inplace": False, "ssm_inplace": False,
                           **k.experts_how}
    assert k.prefill_counts(8, 3) == {"retention_fused_rows": 0,
                                      "experts_fused_calls": 0}
    assert k.fused(8) is (False if kind == "retention" else None)
    asked = []

    def fused(pool, mesh=None, **kw):
        asked.append(kw.get("width", 1))
        return "interpret" if kind == "retention" else False

    monkeypatch.setattr(G, "retention_fused", fused)
    monkeypatch.setattr(
        G, "decode_inplace",
        lambda pool, *a, **kw: kind != "retention" and "interpret")
    monkeypatch.setattr(G, "experts_fused",
                        lambda cfg, mesh=None, dtype=None: "interpret")
    k = d.kernels(pool, None, 4, jnp.float32)
    assert k.inplace == "interpret"
    assert bool(k.attends_inplace) == (kind != "retention")
    assert k.round_counts(8, 10) == {
        "inplace_steps": 0 if kind == "retention" else 8,
        "retention_fused_steps": 8 if kind == "retention" else 0,
        # no kind here has a state-space layer (tests/test_nemotron_block.py
        # has one): the attention layers' answer is not theirs
        "ssm_fused_steps": 0,
        # the round's passes of the model, not its steps
        "experts_fused_passes": 10 if routed else 0}
    assert k.experts_how == ({"experts_fused": "interpret"} if routed
                             else {})
    assert k.round_how == {"inplace": "interpret", "ssm_inplace": False,
                           **k.experts_how}
    for _ in range(2):
        assert k.prefill_counts(8, 3) == {
            "retention_fused_rows": 3 if kind == "retention" else 0,
            "experts_fused_calls": int(routed)}
    assert k.fused(8) == ("interpret" if kind == "retention" else None)
    assert asked == ([1, 8] if kind == "retention" else [1])


# -- the lanes such a generator cannot take -----------------------------------

REFUSED = {
    "attention": {},
    "diffusion": dict.fromkeys(
        ("draft", "prefix", "sampled", "roles"),
        "is served greedy, unified, without a draft model or a shared "
        "prefix"),
    "conv": {
        "draft": "cannot take speculative decoding: a rejected draft would "
                 "have to roll the layers' state back",
        "prefix": "cannot take a shared prefix: its pinned blocks are "
                  "shared by table reference",
        "roles": "cannot take the prefill / decode roles: a handoff "
                 "streams K/V blocks, not the layers' state"},
}
REFUSED["retention"] = {
    **REFUSED["conv"],
    "mesh": "is served on one chip: nothing shards a layer's state over a "
            "mesh yet"}
LANES = ("draft", "prefix", "sampled", "roles", "mesh")


@pytest.fixture(scope="module")
def draft():
    unit = TransformerGenerator(vocab=96, d_model=32, n_heads=4, n_layers=1,
                                d_ff=32, dtype="float32")
    return unit.init_state(None)["params"], unit.cfg


def _message(build):
    try:
        build()
    except ValueError as e:
        return str(e)
    return None


@pytest.mark.parametrize("lane", LANES)
def test_a_refused_lane_says_why_from_the_unit_and_from_the_server(
        generator, draft, lane):
    kind, unit, d = generator
    state = unit.init_state(None)
    if lane == "mesh":
        # the server lays its pool over a real mesh: ask the description
        # what the server asks it (a retention generator never gets there)
        from_server = _message(lambda: d.refuse(mesh=True))
    else:
        spec = {**unit.continuous_spec(state), **{
            "draft": dict(draft_params=draft[0], draft_cfg=draft[1]),
            "prefix": dict(prefix_ids=np.asarray([1, 2, 3])),
            "sampled": dict(temperature=0.7),
            "roles": dict(role="decode")}[lane]}
        srv = None

        def build():
            nonlocal srv
            srv = GenServer(**spec, **served_kinds.SERVER[kind])

        from_server = _message(build)
        if srv is not None:
            srv.stop()
    why = REFUSED[kind].get(lane)
    if why is None:
        assert from_server is None
    else:
        assert why in from_server
    # the unit is asked the lanes a deployment document can put it on, and
    # gives the server's reason in the server's words
    asks = {"prefix": dict(prefix_tokens="1,2"),
            "sampled": dict(temperature=0.7), "mesh": dict(mesh=object())}
    if lane in asks:
        from_unit = _message(lambda: TransformerGenerator(
            **served_kinds.KINDS[kind], **asks[lane]))
        assert from_unit == from_server


def test_a_size_that_is_no_whole_number_of_blocks_is_refused(generator):
    kind, unit, d = generator
    if kind != "diffusion":
        d.whole(span=6, block_size=7, prefill_chunk=9)
        return
    d.whole(span=8, block_size=4, prefill_chunk=16)
    for name in ("span", "block_size", "prefill_chunk"):
        with pytest.raises(ValueError, match=f"{name}=6 is no whole number "
                                             "of diffusion blocks of 4"):
            d.whole(**{"span": 8, "block_size": 4, "prefill_chunk": 8,
                       name: 6})


# -- what the pool holds ------------------------------------------------------


def test_a_rows_state_bytes_are_the_pools_own(generator):
    kind, unit, d = generator
    pool = G.init_block_pool(unit.cfg, 1, 32)
    ret = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert d.retention_row_bytes == (ret if kind == "retention" else 0)


def test_a_pool_the_device_cannot_hold_is_refused_with_what_to_set(
        generator):
    kind, unit, d = generator
    params = jax.eval_shape(lambda: lm_init(jax.random.key(0), unit.cfg))

    def unasked():
        raise AssertionError("only a generator of retention layers asks")

    if kind != "retention":
        d.refuse_pool(1024, params, unasked)
        return
    d.refuse_pool(1024, params, lambda: None)       # the CPU does not say
    d.refuse_pool(4, params, lambda: 1 << 30)
    with pytest.raises(ValueError, match="a BLOCK of the pool.*1024 blocks"
                                         ".*SELDON_TPU_GEN_BLOCK_SIZE.*"
                                         "SELDON_TPU_GEN_POOL_BLOCKS"):
        d.refuse_pool(1024, params, lambda: 1 << 20)


# -- what a token costs -------------------------------------------------------


def test_a_tokens_weight_bytes_are_a_sum_over_the_parameters(generator):
    """The cost features' table of matrix sizes against ``lm_init``'s own
    tree: every matrix a token's step multiplies by -- of an expert layer
    the router and ``moe_k`` of its ``n_experts`` experts -- and nothing
    else (norms, a gate's bias, a router's selection bias, a convolution's
    taps are no matmul)."""
    kind, unit, d = generator
    cfg = unit.cfg
    params = jax.eval_shape(lambda: lm_init(jax.random.key(0), cfg))
    weights = 0
    for i in range(cfg.n_layers):
        for name, leaf in params[f"l{i}"].items():
            if leaf.ndim < 2 or name == "conv_w":
                continue
            share = leaf.size
            if leaf.ndim == 3:              # [experts, ...]: the chosen ones
                assert leaf.shape[0] == cfg.n_experts
                share = share * cfg.moe_k // cfg.n_experts
            weights += share
    head = cfg.d_model * cfg.vocab
    costs = d.decode_costs()
    assert costs["bytes_accessed"] == 2 * weights + 2 * head
    assert costs["flops"] == 2 * (weights + head)
    attending = sum(mixer == "attn" for mixer, _ in cfg.kinds)
    assert attending == {"attention": 2, "diffusion": 2, "conv": 2,
                         "retention": 0}[kind]
    assert costs["kv_bytes_per_position"] == (
        attending * 2 * cfg.kv_heads * cfg.hd * 2)
    assert costs["output_bytes"] == 0.0


def test_the_server_registers_the_descriptions_costs(monkeypatch):
    from seldon_core_tpu.utils.perf import OBSERVATORY

    unit = served_kinds.unit_of("conv")
    srv = served_kinds.server_of("conv", unit)
    try:
        srv._ensure_device()
        assert (OBSERVATORY.cost_features("gen_decode_step")
                == served(unit.cfg).decode_costs())
        assert genserver._device_memory_bytes() is None     # the CPU
    finally:
        srv.stop()
