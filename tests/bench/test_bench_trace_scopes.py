"""The reductions that read the program's own spans: device-idle seconds
by the innermost scheduler annotation, the slack inside a fenced decode
dispatch, device seconds by named scope (lib/trace_scopes.py on hand-made
planes and on the cut-down recorded trace in bench/testdata/), the reader
that finds a run's trace by itself, and the percentile of a cumulative
histogram's window delta (readers/genperf_hist.py)."""

import json
import os

import bench_paths
import pytest
from lib import trace_reduce as tr
from lib import trace_scopes as ts
from readers import genperf_hist
from readers import trace_scopes as reader

US = 1000.0   # ns
DEC = "jit(paged_decode_round)/jit(main)/while/body/closed_call/"
PRE = "jit(paged_forward)/jit(main)/"


def hand_planes():
    """Two decode rounds and one prefill call on one device.  Round 1:
    the fence opens at 100, the module runs 150-550, the fence closes at
    600 (slack 100 = 50 before + 50 after).  Round 2: fence 1000-1500,
    module 1020-1480 (slack 40)."""
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULE_LINE, "events": [
            ["jit_paged_decode_round(1)", 150 * US, 400 * US, None],
            ["jit_paged_forward(2)", 700 * US, 200 * US, None],
            ["jit_paged_decode_round(1)", 1020 * US, 460 * US, None]]},
        {"name": tr.OP_LINE, "events": [
            # round 1: a while op holding its body's ops
            ["while.1", 150 * US, 400 * US, "jit(paged_decode_round)/jit(main)/while"],
            ["fusion.1", 150 * US, 100 * US, DEC + "qkv/dot_general"],
            ["fusion.2", 250 * US, 60 * US, DEC + "kv_gather/gather"],
            ["fusion.3", 310 * US, 40 * US, DEC + "attn/reduce_max"],
            ["fusion.4", 350 * US, 150 * US, DEC + "ffn/dot_general"],
            ["copy.5", 500 * US, 30 * US, None],
            # the prefill call
            ["fusion.9", 700 * US, 150 * US, PRE + "ffn/dot_general"],
            ["fusion.10", 850 * US, 50 * US, PRE + "kv_write/scatter"],
            # round 2: no outer op
            ["fusion.1", 1020 * US, 400 * US, DEC + "qkv/dot_general"],
            ["fusion.2", 1420 * US, 60 * US, DEC + "kv_gather/gather"]]}]}
    tick1 = [
        ["GenServer._tick", 0.0, 960 * US, {}],
        ["GenServer._admit", 5 * US, 40 * US, {}],
        ["GenServer._decode_round", 50 * US, 600 * US, {}],
        ["GenServer._decode_round/capacity", 50 * US, 10 * US, {}],
        ["GenServer._decode_round/build", 60 * US, 40 * US, {}],
        ["GenServer._decode_round/device", 100 * US, 500 * US,
         {"rows": 16, "real_rows": 9, "nblk": 4, "kv_positions": 28000}],
        ["GenServer._decode_round/readback", 600 * US, 20 * US, {}],
        ["GenServer._decode_round/emit", 620 * US, 30 * US, {}],
        ["GenServer._prefill_tick", 660 * US, 280 * US, {}],
        ["GenServer._prefill_tick/device", 690 * US, 220 * US,
         {"rows": 2, "real_rows": 2, "nblk": 1, "tokens": 300}],
        ["GenServer._publish", 945 * US, 10 * US, {}]]
    tick2 = [
        ["GenServer._tick", 980 * US, 560 * US, {}],
        ["GenServer._decode_round", 990 * US, 540 * US, {}],
        ["GenServer._decode_round/build", 990 * US, 10 * US, {}],
        ["GenServer._decode_round/device", 1000 * US, 500 * US,
         {"rows": 16, "real_rows": 11, "nblk": 4, "kv_positions": 30000}]]
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": tick1 + tick2 + [
            ["$other.py:9 unrelated", 0.0, 5000 * US, {}]]}]}
    return [dev, host, {"name": "/host:metadata", "lines": []}]


def test_leaf_segments_name_the_innermost_annotation():
    segs = ts.leaf_segments([
        ("GenServer._tick", 0, 100), ("GenServer._decode_round", 10, 90),
        ("GenServer._decode_round/device", 20, 80)])
    assert segs == [
        (0, 10, "GenServer._tick"), (10, 20, "GenServer._decode_round"),
        (20, 80, "GenServer._decode_round/device"),
        (80, 90, "GenServer._decode_round"), (90, 100, "GenServer._tick")]
    # a gap under two nested annotations goes to the INNER one; what no
    # annotation covers is unattributed
    got = ts.attribute([(30, 50), (85, 120)], segs)
    assert got == {"GenServer._decode_round/device": pytest.approx(20e-9),
                   "GenServer._decode_round": pytest.approx(5e-9),
                   "GenServer._tick": pytest.approx(10e-9),
                   "unattributed": pytest.approx(20e-9)}


def test_scope_of_takes_the_stage_from_the_op_path():
    assert ts.scope_of(DEC + "kv_gather/gather") == "kv_gather"
    assert ts.scope_of("jit(f)/jit(main)/attn/jit(softmax)/exp") == "attn"
    assert ts.scope_of("jit(f)/jit(main)/while") == "unscoped"
    assert ts.scope_of(None) == "unscoped" and ts.scope_of("") == "unscoped"


def test_reduce_hand_made_planes():
    red = ts.reduce_scopes(hand_planes())
    assert red["devices"] == 1 and red["annotations"] == 15
    assert red["window_s"] == pytest.approx(1330e-6)     # 150 .. 1480
    # idle: 550-700 (150) and 900-1020 (120)
    assert red["idle_s"] == pytest.approx(270e-6)
    by = red["idle_by_leaf_s"]
    assert by["GenServer._decode_round/device"] == pytest.approx(
        (50 + 20) * 1e-6)                  # after module 1, before module 2
    assert by["GenServer._decode_round/readback"] == pytest.approx(20e-6)
    assert by["GenServer._decode_round/emit"] == pytest.approx(30e-6)
    assert "GenServer._decode_round" not in by     # its sub-phases cover it
    assert by["GenServer._prefill_tick"] == pytest.approx(
        (30 + 30) * 1e-6)                  # 660-690 and 910-940
    assert by["GenServer._prefill_tick/device"] == pytest.approx(
        (10 + 10) * 1e-6)                  # 690-700, 900-910
    assert by["GenServer._publish"] == pytest.approx(10e-6)
    assert by["GenServer._decode_round/build"] == pytest.approx(10e-6)
    assert by["GenServer._tick"] == pytest.approx(
        (10 + 5 + 5 + 10) * 1e-6)    # 650-660, 940-945, 955-960, 980-990
    assert by["unattributed"] == pytest.approx(20e-6)    # 960-980
    assert sum(by.values()) == pytest.approx(red["idle_s"])
    w = red["window_s"]
    assert red["idle_sched_pct"] == pytest.approx(100 * 20e-6 / w)
    assert red["idle_sync_pct"] == pytest.approx(100 * 140e-6 / w)
    assert red["idle_sched_pct"] + red["idle_sync_pct"] <= \
        100 * red["idle_s"] / w
    # a module event shorter than its /device annotation gives the slack
    fence = red["programs"]["decode"]["fence"]
    assert fence["rounds"] == 2
    assert fence["slack_ms"] == pytest.approx((100 + 40) / 2 / 1000)
    assert fence["before_ms"] == pytest.approx((50 + 20) / 2 / 1000)
    assert fence["after_ms"] == pytest.approx((50 + 20) / 2 / 1000)
    assert red["decode_fence_slack_ms"] == fence["slack_ms"]
    assert red["programs"]["decode"]["device_args"] == {
        "calls": 2, "rows_mean": 16.0, "real_rows_mean": 10.0,
        "nblk_mean": 4.0, "kv_positions_mean": 29000.0}
    # device seconds by scope, per program; ops without one are unscoped
    dec = red["programs"]["decode"]
    assert dec["module_s"] == pytest.approx(860e-6) and dec["calls"] == 2
    assert dec["by_scope_s"] == {
        "qkv": pytest.approx(500e-6), "ffn": pytest.approx(150e-6),
        "kv_gather": pytest.approx(120e-6),
        "unscoped": pytest.approx((20 + 30) * 1e-6),  # while self + copy
        "attn": pytest.approx(40e-6)}
    assert red["decode_kv_share"] == pytest.approx(100 * 160 / 860)
    assert dec["unscoped_share"] == pytest.approx(100 * 50 / 860)
    pre = red["programs"]["prefill"]
    assert pre["by_scope_s"] == {"ffn": pytest.approx(150e-6),
                                 "kv_write": pytest.approx(50e-6)}
    assert red["prefill_kv_share"] == pytest.approx(25.0)


def test_a_trace_without_annotations_or_scopes_reduces_to_no_value():
    """The parent of the PR that brought them: the device planes are there,
    the program wrote neither a phase nor a scope."""
    planes = hand_planes()
    planes[1]["lines"][0]["events"] = [
        ["$genserver.py:1 _tick", 0.0, 960 * US, {}]]   # Python tracer only
    for ev in planes[0]["lines"][1]["events"]:
        ev[3] = None
    red = ts.reduce_scopes(planes)
    for key in ("idle_sched_pct", "idle_sync_pct", "decode_fence_slack_ms",
                "decode_kv_share", "prefill_kv_share"):
        assert key not in red
    assert red["idle_by_leaf_s"] == {"unattributed": pytest.approx(270e-6)}
    assert ts.reduce_scopes([{"name": "/host:CPU", "lines": []}]) == {
        "devices": 0, "annotations": 0}


def recorded_planes():
    """bench/testdata/scopes_tpu_v5e.json: one mixed tick (a prefill chunk,
    then a decode round) and the start of the next, cut from a traced run
    on one TPU v5 lite (chip run, PR 24); a device op's scope path is an
    index into ``paths`` there."""
    doc = bench_paths.load(os.path.join(
        bench_paths.BENCH, "testdata", "scopes_tpu_v5e.json"))
    for plane in doc["planes"]:
        for line in plane["lines"]:
            if line["name"] == tr.OP_LINE:
                for ev in line["events"]:
                    ev[3] = doc["paths"][ev[3]]
    return doc["planes"]


def test_reduce_the_recorded_chip_trace():
    planes = recorded_planes()
    red = ts.reduce_scopes(planes)
    assert red["devices"] == 1 and red["annotations"] == 19
    # the same busy time and window the first reduction reads from it
    base = tr.reduce_planes([
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
            for ln in p["lines"]]} for p in planes])
    assert red["window_s"] == pytest.approx(base["window_s"])
    assert red["idle_s"] == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    assert red["window_s"] == pytest.approx(0.10257860625)
    assert red["idle_s"] == pytest.approx(0.00974446873)
    by = red["idle_by_leaf_s"]
    assert sum(by.values()) == pytest.approx(red["idle_s"])
    assert list(by)[:2] == ["GenServer._decode_round/device",
                            "GenServer._prefill_tick/device"]
    assert by["GenServer._decode_round/device"] == pytest.approx(
        0.00274093, rel=1e-4)
    assert red["idle_sched_pct"] == pytest.approx(0.96479, rel=1e-4)
    assert red["idle_sync_pct"] == pytest.approx(6.14694, rel=1e-4)
    assert red["idle_sched_pct"] + red["idle_sync_pct"] <= \
        100.0 * red["idle_s"] / red["window_s"]
    dec, pre = red["programs"]["decode"], red["programs"]["prefill"]
    # the fence of the one traced round against its module event
    assert dec["fence"]["rounds"] == 1
    assert dec["fence"]["annotation_ms"] == pytest.approx(72.842619)
    assert dec["fence"]["module_ms"] == pytest.approx(70.10787883)
    assert red["decode_fence_slack_ms"] == pytest.approx(2.73474017)
    assert dec["device_args"] == {
        "calls": 1, "rows_mean": 8.0, "real_rows_mean": 6.0,
        "nblk_mean": 4.0, "kv_positions_mean": 17104.0}
    assert pre["device_args"]["tokens_mean"] == 459.0
    # every stage the paged programs name shows up, ffn first; what the
    # compiler's own async weight copies leave unscoped stays under 10%
    assert set(dec["by_scope_s"]) == set(ts.SCOPES) | {ts.UNSCOPED}
    assert set(pre["by_scope_s"]) == (set(ts.SCOPES) - {"sample"}) | {
        ts.UNSCOPED}
    assert list(dec["by_scope_s"])[:2] == ["ffn", "kv_gather"]
    for prog in (dec, pre):
        assert sum(prog["by_scope_s"].values()) <= prog["module_s"]
        assert prog["unscoped_share"] < 10.0
    assert red["decode_kv_share"] == pytest.approx(16.2425946, rel=1e-6)
    assert red["prefill_kv_share"] == pytest.approx(15.999058, rel=1e-6)


@pytest.mark.parametrize("program, scopes, old_key", [
    ("decode", ["kv_write", "kv_gather", "attn"], "decode_kv_share"),
    ("prefill", ["kv_write", "kv_gather", "attn"], "prefill_kv_share"),
    ("decode", ["ffn"], None), ("prefill", ["ffn", "unembed"], None),
    ("decode", ["experts"], None), ("spec", ["attn"], None),
])
def test_a_scopes_share_of_a_program_is_data(monkeypatch, program, scopes,
                                             old_key):
    """``{"program", "scopes"}`` on the recorded chip trace: the two KV
    shares read through it EQUAL their old keys; a stage the program names
    reads its seconds over the module's; a scope no op carries reads 0 of a
    program that has scopes, and a program that did not run reads nothing."""
    red = ts.reduce_scopes(recorded_planes())
    monkeypatch.setitem(reader._REDUCED, "a-trace", red)
    monkeypatch.setattr(reader, "newest_trace", lambda name: "a-trace")
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": "cell.a"}}
    got = reader.read(
        {"formula": {"program": program, "scopes": scopes}}, ctx)
    prog = red["programs"].get(program)
    if prog is None:
        assert got is None
        return
    if old_key:
        assert got == red[old_key] == reader.read(
            bench_paths.load(os.path.join(
                bench_paths.BENCH, "layer_metrics", old_key + ".json")), ctx)
    assert got == 100.0 * sum(
        prog["by_scope_s"].get(k, 0.0) for k in scopes) / prog["module_s"]
    assert (got == 0.0) == (scopes == ["experts"])
    # a program from before the scopes existed: nothing, never 0
    bare = {"module_s": 0.5, "calls": 3,
            "by_scope_s": {ts.UNSCOPED: 0.5}}
    assert ts.scope_share(bare, scopes) is None
    assert ts.scope_share({"module_s": 0.0, "by_scope_s": {}}, scopes) is None


def test_xplane_wire_reader_round_trips_a_hand_encoded_space(tmp_path):
    """lib/xplane.py on a file encoded here by hand: names, line
    timestamps, an event's own stats over its metadata's, a ref value, and
    the filters."""
    from lib import xplane

    def varint(v):
        out = bytearray()
        while True:
            b = v & 0x7F
            v >>= 7
            out.append(b | (0x80 if v else 0))
            if not v:
                return bytes(out)

    def f_var(num, v):
        return varint(num << 3) + varint(v)

    def f_len(num, payload):
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def stat_meta(key, name):
        return f_len(5, f_var(1, key) + f_len(
            2, f_var(1, key) + f_len(2, name.encode())))

    def event_meta(key, name, stats=b""):
        return f_len(4, f_var(1, key) + f_len(
            2, f_var(1, key) + f_len(2, name.encode()) + stats))

    tf_op = f_len(5, f_var(1, 1) + f_len(5, b"jit(f)/attn/dot_general:"))
    category = f_len(5, f_var(1, 2) + f_var(7, 3))          # ref -> "fusion"
    rows = f_len(4, f_var(1, 4) + f_var(4, 16))
    neg = f_len(4, f_var(1, 5) + f_var(4, (1 << 64) - 3))    # int64 -3
    ops = f_len(3, f_len(2, b"XLA Ops") + f_var(3, 1000) + f_len(
        4, f_var(1, 7) + f_var(2, 2_000_000) + f_var(3, 500_000) + rows + neg)
        + f_len(4, f_var(1, 8) + f_var(2, 3_000_000) + f_var(3, 250_000)))
    other = f_len(3, f_len(2, b"Steps") + f_len(4, f_var(1, 8)))
    plane = f_len(1, f_len(2, b"/device:TPU:0") + ops + other
                  + event_meta(7, "%fusion.1 = f32[8] fusion()",
                               tf_op + category)
                  + event_meta(8, "%copy.2 = f32[8] copy()")
                  + stat_meta(1, "tf_op") + stat_meta(2, "hlo_category")
                  + stat_meta(3, "fusion") + stat_meta(4, "rows")
                  + stat_meta(5, "delta"))
    skipped = f_len(1, f_len(2, b"/host:metadata"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(plane + skipped)
    got = xplane.read_planes(
        str(path), want_plane=lambda p: p.startswith("/device"),
        want_line=lambda p, line: line == "XLA Ops")
    assert got == [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8] fusion()", 3000.0, 500.0,
             {"tf_op": "jit(f)/attn/dot_general:",
              "hlo_category": "fusion", "rows": 16, "delta": -3}],
            ["%copy.2 = f32[8] copy()", 4000.0, 250.0, {}]]}]}]
    only = xplane.read_planes(
        str(path), lambda p: True, lambda p, line: True,
        want_event=lambda p, ev: "fusion" in ev, own_stats=lambda p: False)
    assert [p["name"] for p in only] == ["/device:TPU:0", "/host:metadata"]
    assert only[0]["lines"][0]["events"] == [
        ["%fusion.1 = f32[8] fusion()", 3000.0, 500.0,
         {"tf_op": "jit(f)/attn/dot_general:", "hlo_category": "fusion"}]]
    assert only[0]["lines"][1] == {"name": "Steps", "events": []}


def test_reader_finds_the_newest_trace_of_the_cell_and_reads_nothing_else(
        tmp_path, monkeypatch):
    out = tmp_path / "out"
    old = out / "cell.a.1.t1" / "profile" / "window" / "plugins" / "x"
    new = out / "cell.a.2.t1" / "profile" / "window" / "plugins" / "y"
    other = out / "cell.b.1.t1" / "profile" / "w"
    untraced = out / "cell.a.3.t0" / "profile"
    for d in (old, new, other, untraced):
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
    os.utime(old / "vm.xplane.pb", (1, 1))
    os.utime(other / "vm.xplane.pb", (9e9, 9e9))
    os.utime(untraced / "vm.xplane.pb", (9e9, 9e9))
    assert reader.newest_trace("cell.a", str(out)) == str(
        new / "vm.xplane.pb")
    assert reader.newest_trace("cell.c", str(out)) is None
    metric = {"formula": {"value": "decode_kv_share"}}
    # an untraced run, and a cell with no trace on disk: nothing, no raise
    assert reader.read(metric, {"trace": None, "cell": {"name": "x"}}) is None
    assert reader.read(metric, {"trace": {"busy_s": 1.0},
                                "cell": {"name": "no-such-cell"}}) is None
    # the reduction is taken once per trace and read by key
    path = str(new / "vm.xplane.pb")
    monkeypatch.setitem(reader._REDUCED, path, {"decode_kv_share": 12.5,
                                                "programs": {}})
    monkeypatch.setattr(reader, "newest_trace", lambda name: path)
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": "cell.a"}}
    assert reader.read(metric, ctx) == 12.5
    assert reader.read({"formula": {"value": "idle_sync_pct"}}, ctx) is None
    assert reader.read({"formula": {"value": "programs"}}, ctx) is None
    # what the side file says of the request stages over the window
    before = {"requests": {"streams": 4, "admitted": 4, "first_tokens": 4,
                           "ttft_s": 0.8, "stage_s": {
                               "lane_in": 0.004, "queue": 0.16,
                               "prefill": 0.628, "lane_out": 0.008}}}
    after = {"requests": {"streams": 104, "admitted": 105,
                          "first_tokens": 104, "ttft_s": 20.8, "stage_s": {
                              "lane_in": 0.104, "queue": 4.2,
                              "prefill": 16.3, "lane_out": 0.208}}}
    win = reader.requests_window(before, after)
    assert win["streams"] == 100 and win["admitted"] == 101
    assert win["ttft_s"] == pytest.approx(20.0)
    assert win["stages_over_ttft"] == pytest.approx(
        (0.1 + 4.04 + 15.672 + 0.2) / 20.0)
    assert reader.requests_window({}, {"ticks": {}})["ttft_s"] is None


# -- percentile of a cumulative histogram's window delta ----------------------

EDGES = [1.0, 2.0, 4.0, 8.0]          # buckets: <1, 1-2, 2-4, 4-8, >=8


def hist_ctx(before, after):
    def doc(c):
        return {"requests": {"ttft_ms_hist": {"edges_ms": EDGES,
                                              "counts": c}}}
    return {"genperf_before": doc(before) if before else {},
            "genperf_after": doc(after) if after else {}}


@pytest.mark.parametrize("q,want", [
    (50, 3.0),        # 10 in 2-4, target 5: halfway through the bucket
    (90, 3.8),        # target 9 of 10
    (100, 4.0),
])
def test_hist_percentile_is_linear_inside_a_bucket(q, want):
    metric = {"formula": {"hist": "requests.ttft_ms_hist", "percentile": q}}
    ctx = hist_ctx([0, 5, 7, 0, 0], [0, 5, 17, 0, 0])
    assert genperf_hist.read(metric, ctx) == pytest.approx(want)


def test_hist_percentile_over_several_buckets_and_the_open_ends():
    pct = genperf_hist.hist_percentile
    counts = [2, 2, 4, 2, 0]
    assert pct(EDGES, counts, 10) == pytest.approx(0.5)    # under 1: from 0
    assert pct(EDGES, counts, 30) == pytest.approx(1.5)
    assert pct(EDGES, counts, 90) == pytest.approx(6.0)
    assert pct(EDGES, [0, 0, 0, 0, 3], 50) == 8.0          # open last bucket
    assert pct(EDGES, [0, 0, 0, 0, 0], 50) is None
    assert pct(EDGES, [1, 1], 50) is None                  # wrong length


def test_hist_reader_gives_none_for_an_empty_delta_or_a_missing_block():
    metric = {"formula": {"hist": "requests.ttft_ms_hist", "percentile": 90}}
    same = [0, 5, 7, 0, 0]
    assert genperf_hist.read(metric, hist_ctx(same, same)) is None
    assert genperf_hist.read(metric, hist_ctx(None, None)) is None
    assert genperf_hist.read(metric, hist_ctx(same, None)) is None
    # no document before the window: the counts themselves
    assert genperf_hist.read(metric, hist_ctx(None, same)) == pytest.approx(
        2.0 + 2.0 * (10.8 - 5) / 7)
    # a histogram that shrank, or whose edges moved, is not a delta
    assert genperf_hist.read(metric, hist_ctx(same, [0, 4, 9, 0, 0])) is None
    moved = hist_ctx(same, [0, 5, 17, 0, 0])
    moved["genperf_before"]["requests"]["ttft_ms_hist"]["edges_ms"] = [
        1.0, 2.0, 4.0, 9.0]
    assert genperf_hist.read(metric, moved) is None


def test_the_new_genperf_metrics_read_the_requests_block():
    from lib.manifest import Manifest
    from readers import genperf

    man = Manifest(bench_paths.REPO)
    before = {"requests": {"admitted": 10, "first_tokens": 9, "stage_s": {
        "queue": 0.4, "prefill": 1.0, "lane_in": 0.01, "lane_out": 0.02}}}
    after = {"requests": {"admitted": 110, "first_tokens": 109, "stage_s": {
        "queue": 4.4, "prefill": 16.0, "lane_in": 0.11, "lane_out": 0.22}}}
    ctx = {"genperf_before": before, "genperf_after": after, "harness": {}}
    want = {"queue_wait_mean_ms": 40.0, "prefill_wait_mean_ms": 150.0,
            "http_lane_mean_ms": 3.0}
    for name, value in want.items():
        assert genperf.read(man.layer_metric(name), ctx) == pytest.approx(
            value)
        # a program without the block (the parent): nothing to read
        assert genperf.read(man.layer_metric(name), {
            "genperf_before": {}, "genperf_after": {"ticks": {}},
            "harness": {}}) is None
