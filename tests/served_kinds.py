"""Four tiny generators, one of each kind the benchmark's cells serve, and
one scripted run of each through ``GenServer`` -- shared by
tests/test_served.py (what ``models/served.py`` says of each) and
tests/test_genperf.py (that ``/genperf`` and ``/stats`` say of the run what
the commit before the description said: tests/resources/
genperf_parent.json)."""

import numpy as np

from seldon_core_tpu.models.generate import TransformerGenerator
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.utils.genperf import GENPERF
from seldon_core_tpu.utils.hotrecord import SPINE

_SMALL = dict(vocab=96, d_model=32, n_heads=4, n_kv_heads=2,
              dtype="float32", seed=7)
_EXPERTS = dict(d_expert=16, n_experts=8, moe_k=2)

#: ``TransformerGenerator`` arguments by kind: all attention (the two-matrix
#: FFN); dropless experts decoded by diffusion over blocks of 4; gated short
#: convolutions beside attention, two leading dense layers and
#: sigmoid-routed experts after them; power retention in every layer
KINDS = {
    "attention": dict(_SMALL, n_layers=2, d_ff=64),
    "diffusion": dict(_SMALL, n_layers=2, head_dim=16, qk_norm=True,
                      tie_embeddings=False, block_length=4,
                      denoising_steps=4, mask_id=90, **_EXPERTS),
    "conv": dict(_SMALL, n_layers=5, layer_kinds="ccaca", dense_layers=2,
                 d_ff=48, router="sigmoid_bias", qk_norm=True, **_EXPERTS),
    "retention": dict(_SMALL, n_layers=3, layer_kinds="rrr", dense_layers=3,
                      head_dim=16, d_ff=48, qk_norm=True,
                      tie_embeddings=False),
}

#: ``GenServer`` arguments of the scripted run: retention is deployed a
#: block a row (a block holds the longest row), the others page
SERVER = {
    kind: dict(block_size=32 if kind == "retention" else 4,
               num_blocks=6 if kind == "retention" else 48,
               slots=4, span=8, prefill_chunk=8)
    for kind in KINDS
}

#: what a run's documents say of the clock and not of the work
WALL_CLOCK = ("decode_device_s", "served_decode_tok_s_device",
              "served_decode_mfu_pct", "served_decode_hbm_bw_util_pct",
              "pace", "boot_load_s", "boot_trace_s", "boot", "idle", "age_s")


def unit_of(kind):
    return TransformerGenerator(**KINDS[kind])


def server_of(kind, unit=None, state=None, **kw):
    unit = unit or unit_of(kind)
    state = state or unit.init_state(None)
    return GenServer(**unit.continuous_spec(state), **{**SERVER[kind], **kw})


def prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, 90, n).astype(np.int32) for n in lens])


def without_wall_clock(doc):
    """``doc`` without the fields ``WALL_CLOCK`` names, at any depth."""
    if isinstance(doc, dict):
        return {k: without_wall_clock(v) for k, v in doc.items()
                if k not in WALL_CLOCK}
    if isinstance(doc, (list, tuple)):
        return [without_wall_clock(v) for v in doc]
    return doc


def scripted_run(kind, monkeypatch):
    """Three requests, one after the other -- two rows of one chunk, one
    row of three chunks (a state is carried over them), one row streamed --
    through a server of ``kind``.  Which rows ride which call is arithmetic
    where requests do not overlap, so the counts are the same in every
    run.  Returns ``served_decode``, ``served_prefill`` (/genperf) and the
    server's ``/stats`` block, wall-clock fields left out."""
    # the adaptive chunk follows wall time: hold it at the floor
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "8")
    # and no record of programs, whatever cache an earlier test turned on
    monkeypatch.setenv("SELDON_COMPILE_CACHE", "0")
    SPINE.drain()
    SPINE.reset()
    GENPERF.reset()
    srv = server_of(kind)
    try:
        srv.submit(prompts([6, 6], 11), max_new=9).future.result(timeout=240)
        srv.submit(prompts([19], 12), max_new=7).future.result(timeout=240)
        for _ in srv.stream(prompts([5], 13), chunk=3, max_new=12):
            pass
    finally:
        srv.stop()      # joins the scheduler: its last tick has published
    SPINE.drain()
    doc = GENPERF.document()
    out = {"served_decode": doc["served_decode"],
           "served_prefill": doc["served_prefill"],
           "genserver": srv.snapshot()}
    SPINE.reset()
    GENPERF.reset()
    return without_wall_clock(out)
