"""The reduction from a profiler trace to busy time, program time and
the breakdown: on a hand-made trace and on the small recorded one kept in
bench/testdata/."""

import os

import bench_paths
import pytest
from lib import trace_reduce as tr

US = 1000.0   # ns


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 110)]
    assert tr.union_seconds(iv) == pytest.approx(45e-9 + 0)
    assert tr.gaps(iv) == [(20, 30), (45, 100)]
    assert tr.union_seconds([]) == 0.0 and tr.gaps([]) == []


def test_self_time_takes_nested_events_out_of_their_parent():
    events = [("while", 0.0, 100 * US), ("fusion", 0.0, 30 * US),
              ("gather", 30 * US, 50 * US), ("inner", 40 * US, 10 * US),
              ("copy", 200 * US, 5 * US)]
    got = tr.self_seconds(events)
    assert got == {"while": pytest.approx(20e-6), "fusion": pytest.approx(
        30e-6), "gather": pytest.approx(40e-6), "inner": pytest.approx(
        10e-6), "copy": pytest.approx(5e-6)}
    assert tr.short_name("%fusion.12 = bf16[8,128]{1,0} fusion(%p)") == \
        "%fusion.12"


def hand_trace():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULE_LINE, "events": [
            ("jit_paged_decode_round(123)", 0.0, 400 * US),
            ("jit_paged_forward(77)", 600 * US, 300 * US),
            ("jit_paged_decode_round(123)", 1000 * US, 400 * US)]},
        {"name": tr.OP_LINE, "events": [
            ("fusion.1", 0.0, 250 * US), ("gather.2", 250 * US, 150 * US),
            ("fusion.1", 600 * US, 300 * US),
            ("fusion.1", 1000 * US, 250 * US),
            ("gather.2", 1250 * US, 150 * US)]},
        {"name": "Steps", "events": [("0", 0.0, 1400 * US)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("$genserver.py:1 _tick", 390 * US, 1020 * US),
        ("$genserver.py:2 _prefill_tick", 395 * US, 510 * US),
        ("$genserver.py:3 _retire_finished", 905 * US, 90 * US),
        ("$other.py:9 unrelated", 0.0, 5000 * US)]}]}
    return [dev, host, {"name": "/host:metadata", "lines": []}]


def test_reduce_a_hand_made_trace():
    red = tr.reduce_planes(hand_trace())
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(1100e-6)
    assert red["window_s"] == pytest.approx(1400e-6)
    assert red["programs"]["decode"] == {
        "seconds": pytest.approx(800e-6), "calls": 2}
    assert red["programs"]["prefill"] == {
        "seconds": pytest.approx(300e-6), "calls": 1}
    assert red["device_ops"][0][0] == "fusion.1"
    assert red["device_ops"][0][1] == pytest.approx(800e-6)
    assert dict(red["idle_gaps"]) == {
        "prefill_tick": pytest.approx(200e-6),
        "retire": pytest.approx(100e-6)}
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_a_trace_without_a_device_plane_reports_none():
    assert tr.reduce_planes([{"name": "/host:CPU", "lines": []}]) == {
        "devices": 0}


def test_reduce_the_recorded_trace():
    """bench/testdata/planes_tpu_v5e.json: the first events of a traced
    window of starcoder2-3b.codegen.r80 on one TPU v5 lite chip, as
    ``trace_reduce.load_planes`` read them from the profiler's file."""
    planes = bench_paths.load(os.path.join(
        bench_paths.BENCH, "testdata", "planes_tpu_v5e.json"))
    red = tr.reduce_planes(planes)
    assert red["devices"] == 1
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["programs"]["decode"]["calls"] == 1
    assert red["programs"]["prefill"]["calls"] == 1
    # one decode round of 8 steps on 3B: 142.6 ms of device time
    assert red["programs"]["decode"]["seconds"] == pytest.approx(
        0.1426, rel=0.01)
    assert not red["device_ops"][0][0].startswith("%while")
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] + 1e-9
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    total_gap = sum(s for _, s in red["idle_gaps"])
    assert total_gap <= red["window_s"] - red["busy_s"] + 1e-9
