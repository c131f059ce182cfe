"""The three expert configurations' numerics children at their toy sizes
(tests/bench/test_bench_{sdar,lfm2,nemotron}.py ``tiny`` / ``numerics``,
as they stand) with what a TPU decides for the expert layers forced on as
far as the CPU can run it: an expert's whole feed-forward as ONE Pallas
call (parallel/moe.py ``_experts_fused``) in interpret mode, in the timed
programs the child drives -- ``paged_forward`` chunk by chunk and the
architecture's own round.  The child passes no answer of its own, so the
programs ask ``generate.experts_fused``, which is told to say "interpret"
here."""

import importlib

import pytest


@pytest.fixture()
def experts_fused(monkeypatch):
    """``generate.experts_fused`` answers "interpret"; the programs' traces
    made under the CPU's own answer must not serve this test, nor this
    test's the next."""
    import jax

    from seldon_core_tpu.models import generate
    from seldon_core_tpu.parallel import moe

    calls = []
    real = moe._experts_fused

    def counted(*operands, **how):
        calls.append(how)
        return real(*operands, **how)

    jax.clear_caches()
    monkeypatch.setattr(generate, "experts_fused",
                        lambda cfg, mesh=None, dtype=None: "interpret")
    monkeypatch.setattr(moe, "_experts_fused", counted)
    yield calls
    jax.clear_caches()


@pytest.mark.parametrize("module", ["test_bench_sdar", "test_bench_lfm2",
                                    "test_bench_nemotron"])
def test_numerics_child_is_ok_with_the_fused_expert_call(module,
                                                         experts_fused):
    """``ok`` under the toy's own limits (float32: the dense cell's 0.1 x
    rms, every row held to it), as far from them as the two grouped matmuls
    are -- and the kernel did run, gated where the configuration's experts
    have a gate."""
    own = importlib.import_module(module)
    cfg = own.tiny()
    num = own.numerics(cfg)
    assert num["ok"] is True, num["verdict"]
    assert num["lens"] == [9, 31, 64, 70] and num["chunks"] == [1, 3]
    assert 0.0 < max(num["by_row"]["prefill_err"]) < 0.01 * num["tolerance"]
    assert num["decode_max_margin"] <= 0.01 * num["tolerance"]
    assert experts_fused and all(how["interpret"] for how in experts_fused)
    assert {how["gated"] for how in experts_fused} == {
        module != "test_bench_nemotron"}
