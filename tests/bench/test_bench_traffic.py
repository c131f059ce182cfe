"""The schedule is a pure function of mix and rate, the token ids of the
seed: every seed offers the same work in the same order."""

import bench_paths  # noqa: F401  (puts bench/ on the path)
import pytest
from lib import traffic

MIXES = ["codegen", "complete"]
# a second parameter set for the one generator: long prompt, short answer
# (PERF.md section 7 keeps the cell that would use it for a later PR)
COMPLETE = {
    "name": "complete",
    "prompt_tokens": {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                      "min": 64, "max": 3952},
    "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 4, "max": 128},
    "max_positions": 4080,
}


def mix(name):
    import os
    if name == "complete":
        return COMPLETE
    return bench_paths.load(
        os.path.join(bench_paths.BENCH, "traffic", name + ".json"))


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_a_pure_function_of_mix_and_rate(name):
    a = traffic.open_loop(mix(name), 5.0, 40.0, 10.0)
    assert a == traffic.open_loop(mix(name), 5.0, 40.0, 10.0)
    assert a != traffic.open_loop(mix(name), 5.5, 40.0, 10.0)
    # a longer window is another schedule of the same distributions
    assert len(traffic.open_loop(mix(name), 5.0, 50.0, 10.0)) == 250


@pytest.mark.parametrize("name", MIXES)
def test_measured_part_and_drain_tail_are_dealt_apart(name):
    a = traffic.open_loop(mix(name), 5.0, 40.0, 10.0)
    b = traffic.open_loop(mix(name), 5.0, 45.0, 15.0)
    # the same 30 s of measured arrivals whatever the drain
    ma = [r for r in a if r.measured]
    mb = [r for r in b if r.measured]
    assert ma == mb and len(ma) == 150
    tail = [r for r in a if not r.measured]
    assert tail[0].due_s == pytest.approx(30.0) and len(tail) == 50
    assert sorted(r.out_len for r in ma)[len(ma) // 2] == pytest.approx(
        mix(name)["output_tokens"]["median"], rel=0.1)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_the_mix_clips_and_position_cap(name):
    m = mix(name)
    reqs = traffic.open_loop(m, 8.0, 40.0, 8.0)
    assert len(reqs) == 320
    assert sum(r.measured for r in reqs) == 256
    for r in reqs:
        assert m["prompt_tokens"]["min"] <= r.prompt_len \
            <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= r.out_len \
            <= m["output_tokens"]["max"]
        assert r.prompt_len + r.out_len <= m["max_positions"]
    assert reqs[0].due_s == 0.0
    assert all(x.due_s <= y.due_s for x, y in zip(reqs, reqs[1:]))
    assert reqs[-1].due_s < 40.0
    med = sorted(r.prompt_len for r in reqs)[len(reqs) // 2]
    assert 0.85 * m["prompt_tokens"]["median"] <= med \
        <= 1.15 * m["prompt_tokens"]["median"]


def test_prompt_tokens_are_seeded_uniform_ids_in_range():
    a = traffic.prompt_tokens(2 ** 31 + 5, 3, 200, 49152)
    assert a == traffic.prompt_tokens(2 ** 31 + 5, 3, 200, 49152)
    assert a != traffic.prompt_tokens(2 ** 31 + 5, 4, 200, 49152)
    assert len(a) == 200 and all(0 <= t < 49152 for t in a)
    assert a[:8] != traffic.prompt_tokens(7, 3, 200, 49152)[:8]


@pytest.mark.parametrize("n", [8, 109, 140, 183])
def test_every_block_of_arrivals_holds_one_value_of_each_stratum(n):
    import random

    values = list(range(n))           # sorted: value i lies in stratum
    out = traffic.balanced(values, random.Random(n))
    assert sorted(out) == values
    assert out != traffic.balanced(values, random.Random(n + 1)) or n <= 8
    base, extra = divmod(n, traffic.BLOCK)
    edges, pos = [], 0
    for s in range(traffic.BLOCK):
        pos += base + (1 if s < extra else 0)
        edges.append(pos)

    def stratum(v):
        return next(k for k, e in enumerate(edges) if v < e)

    for b in range(0, n, traffic.BLOCK):
        blk = out[b:b + traffic.BLOCK]
        assert len({stratum(v) for v in blk}) == len(blk)


def test_every_stretch_of_the_window_offers_nearly_the_same_work():
    m = mix("codegen")
    for rate in (3.6, 4.0, 5.0):
        reqs = traffic.open_loop(m, rate, 50.0, 10.0)
        per = [0] * 5
        for r in reqs:
            if r.measured:
                per[int(r.due_s // 8)] += r.out_len
        assert max(per) < 1.35 * min(per), per
