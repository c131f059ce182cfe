"""archs/dense_gelu/needs.py and lib/roofline.py against hand-worked bytes
and FLOPs for two sets of published sizes, the peaks table, and the bucket
arithmetic."""

import bench_paths
import pytest
from bench_paths import REPO
from lib import buckets, roofline
from lib.manifest import Manifest, arch_module
from lib.peaks import peaks_for

MAN = Manifest(REPO)
needs = arch_module(MAN.bench, {"name": "a test", "arch": "dense_gelu"}, "needs")


# a second set of published sizes for the arithmetic (StarCoder2-7B's,
# 20 of 32 layers: PERF.md section 7 keeps its cell for a later PR)
SEVEN_B_L20 = dict(
    hidden_size=4608, intermediate_size=18432, num_hidden_layers=20,
    num_attention_heads=36, num_key_value_heads=4, vocab_size=49152)


def config(name):
    if name == "starcoder2-7b-l20":
        return SEVEN_B_L20
    return MAN.config(name)


# worked by hand from the published sizes (bf16)
HAND = {
    "starcoder2-3b": dict(
        layer=3072 * (3072 + 2 * 2 * 128) + 3072 * 3072 + 2 * 3072 * 12288,
        layer_value=95_944_704, embed=49152 * 3072,
        matmul=30 * 95_944_704 + 150_994_944, kv_pos=30_720),
    "starcoder2-7b-l20": dict(
        layer=4608 * (4608 + 2 * 4 * 128) + 4608 * 4608 + 2 * 4608 * 18432,
        layer_value=217_055_232, embed=49152 * 4608,
        matmul=20 * 217_055_232 + 226_492_416, kv_pos=40_960),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_sizes_match_the_hand_worked_numbers(name):
    s = needs.sizes(config(name))
    h = HAND[name]
    assert h["layer"] == h["layer_value"]
    assert s["layer_params"] == h["layer_value"]
    assert s["matmul_params"] == h["matmul"]
    assert s["weight_bytes"] == 2 * h["matmul"]
    assert s["kv_bytes_per_position"] == h["kv_pos"]
    assert s["hd"] == 128


@pytest.mark.parametrize("name,rows,live", [
    ("starcoder2-3b", 48, 48 * 400), ("starcoder2-7b-l20", 64, 64 * 350)])
def test_decode_step_needs_weights_once_and_live_kv_once(name, rows, live):
    cfg = config(name)
    h = HAND[name]
    need = needs.decode_step(cfg, rows, live, {})
    assert need["bytes"] == 2 * h["matmul"] + h["kv_pos"] * (live + rows)
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    assert need["flops"] == (2 * h["matmul"] * rows
                             + 4 * layers * heads * 128 * live)
    least = roofline.least_seconds(need, peaks_for("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(need["bytes"] / 819e9)
    # 3B: 6.06 GB of weights alone are 7.4 ms at 819 GB/s
    if name == "starcoder2-3b":
        assert 7.4e-3 < least["seconds"] < 8.3e-3


@pytest.mark.parametrize("name", sorted(HAND))
def test_prefill_is_compute_bound_on_long_chunks_and_counts_real_tokens(
        name):
    cfg = config(name)
    h = HAND[name]
    tokens, calls = 4 * 256, 1
    attended = 4 * sum(range(1, 257))
    need = needs.prefill(cfg, calls, tokens, attended, {})
    assert need["bytes"] == 2 * h["matmul"] + 2 * h["kv_pos"] * tokens
    body = h["matmul"] - h["embed"]
    assert need["flops"] == pytest.approx(
        2 * body * tokens + 2 * h["embed"] * calls
        + 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * 128
        * attended)
    assert roofline.least_seconds(
        need, peaks_for("TPU v5 lite"))["bound"] == "compute"
    one = needs.prefill(cfg, 1, 64, sum(range(1, 65)), {})
    assert roofline.least_seconds(
        one, peaks_for("TPU v5 lite"))["bound"] == "memory"


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


@pytest.mark.parametrize("n,want", [(0, 1), (1, 1), (2, 2), (3, 4), (5, 8),
                                    (64, 64), (65, 128)])
def test_pow2_is_the_schedulers(n, want):
    from seldon_core_tpu.runtime.genserver import _pow2

    assert buckets.pow2(n) == want == _pow2(n)


@pytest.mark.parametrize("cell", [w["name"] for w in MAN.doc["workloads"]])
def test_the_ladder_takes_every_reachable_bucket_once(cell):
    bench_paths.check_ladder(MAN, cell)


# what lib/buckets.py gave at the parent of PR 32 (29b9b78) under
# starcoder2-3b's deployment, which states neither count of a round
PARENT_BUCKETS = {
    "codegen": {"reachable": ([1, 2, 4], [1, 2, 4, 8]), "programs": (18, 24),
                "ladder": [(16, 2), (249, 2), (513, 2), (1017, 2)]},
    "complete": {"reachable": ([1, 2, 4, 8, 16], [1, 2, 4, 8, 16]),
                 "programs": (30, 30),
                 "ladder": [(64, 2), (249, 2), (505, 2), (1017, 2),
                            (2049, 2)]},
}
PARENT_TOUCHED = {(16, 16): ({1}, {1}), (300, 512): ({1, 2}, {2, 4}),
                  (1024, 512): ({1, 2, 4}, {8}), (257, 2): ({1, 2}, {2})}


@pytest.mark.parametrize("mix", sorted(PARENT_BUCKETS))
def test_where_a_deployment_states_no_count_of_a_round_the_ladder_is_the_parents(
        mix):
    cfg = MAN.config("starcoder2-3b")
    dep = MAN.deployment(MAN.cell("starcoder2-3b.codegen.r80"), cfg)
    assert "prefill_emits" not in dep and "round_quantum" not in dep
    cp = buckets.caps(MAN.mix(mix))
    want = PARENT_BUCKETS[mix]
    pre, dec = buckets.reachable(dep, cp)
    assert (sorted(pre), sorted(dec)) == want["reachable"]
    assert buckets.ladder_rows(dep, cp) == want["ladder"]
    progs = buckets.programs(dep, cp)
    assert (len(progs["prefill"]), len(progs["decode"])) == want["programs"]
    for (p, o), sets in PARENT_TOUCHED.items():
        assert buckets.touched(p, o, dep) == sets
    # ... and stating the program's own counts changes nothing
    same = {**dep, "prefill_emits": 1, "round_quantum": 1}
    assert buckets.ladder_rows(same, cp) == want["ladder"]
    assert buckets.after_prefill(300, dep) == (300, 1)
    assert buckets.after_prefill(302, {"round_quantum": 4, "prefill_emits": 0}
                                 ) == (300, -2)
