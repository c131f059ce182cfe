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


# what the parent of PR 32 (29b9b78) drew for (seed, index 3, 12 ids of
# 49,152) and, in the numerics child, for rows of 5 and 3 ids from
# seed % 9973: with nothing reserved every seed's traffic is what it was
PARENT_DREW = {
    7: ([20357, 38170, 22512, 48638, 27385, 47248, 31695, 1555, 11404, 10650,
         41895, 29340],
        [[46443, 30724, 33628, 44099, 28424], [38126, 40975, 11069]]),
    2 ** 31 + 5: ([35432, 31034, 7619, 997, 12130, 48314, 25157, 435, 29443,
                   27334, 10233, 368],
                  [[30610, 7523, 21458, 18975, 45905], [26737, 10151, 34943]]),
    2147486311: ([37207, 29683, 31227, 2319, 29417, 39541, 24269, 6508,
                  39110, 9012, 5960, 43444],
                 [[17599, 22361, 23977, 12906, 12310], [44363, 8361, 32965]]),
}


@pytest.mark.parametrize("seed", sorted(PARENT_DREW))
def test_with_nothing_reserved_both_draws_are_the_parents(seed):
    from lib import children

    prompt, rows = PARENT_DREW[seed]
    assert traffic.prompt_tokens(seed, 3, 12, 49152) == prompt
    assert traffic.prompt_tokens(seed, 3, 12, 49152, ()) == prompt
    got = children.sample_tokens([5, 3], 49152, seed % 9973)
    assert [a.tolist() for a in got] == rows
    assert all(a.dtype == "int32" for a in got)


@pytest.mark.parametrize("reserved", [(500,), (0, 511), (3, 4, 5, 200)],
                         ids=["a-mask-id", "both-ends", "a-run-and-one"])
def test_a_reserved_id_is_never_drawn_and_the_others_stay_uniform(reserved):
    """10^5 ids of a vocabulary of 512, by the load generator and by the
    numerics child: none reserved, every other id drawn, about as often."""
    from lib import children

    drawn = traffic.prompt_tokens(2 ** 31 + 9, 0, 100_000, 512, reserved)
    rows = children.sample_tokens([60_000, 40_000], 512, 17, reserved)
    for ids in (drawn, [int(t) for row in rows for t in row]):
        assert len(ids) == 100_000
        counts = [ids.count(t) for t in range(512)]
        assert all(counts[r] == 0 for r in reserved)
        rest = [c for t, c in enumerate(counts) if t not in reserved]
        mean = 100_000 / len(rest)
        assert 0.7 * mean < min(rest) and max(rest) < 1.3 * mean
    # the draw is the unreserved one moved past the reserved ids, in order
    free = traffic.prompt_tokens(2 ** 31 + 9, 0, 64, 512 - len(reserved))
    allowed = [t for t in range(512) if t not in reserved]
    assert drawn[:64] == [allowed[t] for t in free]


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
