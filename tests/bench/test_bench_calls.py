"""The reduction that joins every traced call to the work its dispatching
span says it was given (lib/trace_calls.py, on hand-made planes and on the
cut-down recorded traces in bench/testdata/) and the reader that counts a
program's roofline call by call (readers/trace_calls.py)."""

import copy
import json
import os

import bench_paths
import pytest
from lib import roofline
from lib import trace_calls as tc
from lib import trace_reduce as tr
from lib.manifest import Manifest, arch_module
from lib.peaks import peaks_for
from readers import trace_calls as reader

MS = 1e6      # ns
MAN = Manifest(bench_paths.REPO)
DENSE, SPARSE = "starcoder2-3b", "sdar-30b-a3b"
EXPERTS = "jit(paged_decode_round)/jit(main)/while/body/denoise/ffn/experts/"

DECODE_ARGS = {"rows": 8, "real_rows": 5, "nblk": 128, "inplace": 1,
               "passes": 8, "blocks": 8, "expert_slots": 0}


def decode_args(seq, kv_positions):
    return {"seq": seq, "kv_positions": kv_positions, **DECODE_ARGS}


def hand_trace(shift_ms=0.0, drop=(), emits=(12, 13, 14, 15), unlinked=()):
    """A window of 400 ms on one device, the scheduler a round ahead (times
    in ms; ``shift_ms`` moves every DEVICE timestamp: a device clock that
    reads behind the host's).

    device                          host
    D0    0 -  30  cut at the start
    D1   30 -  94  whole, dispatched before the profiler started: no span
    D2   95 - 160                   seq 12  /build    90 -  92
    P   160 - 175  (prefill)        seq 13  /build   150 - 151
    D3  175 - 240                   seq 14  /build   152 - 154
    D4  246.5-311  fenced           seq 15  /device  245 - 315
    D5  319 - 384                   seq 16  /build   316 - 318
    D6  384 - 400  cut at the stop  seq 17  /build   380 - 382

    The trace holds the runtime's own link as a chip trace does: an event
    inside each dispatching span produces an id that an event of the
    executing thread consumes, inside which one produces the id an event
    of the enqueueing thread consumes, inside which one produces the id
    the module event consumes.  The calls named in ``unlinked`` lost the
    middle hop of theirs.
    """
    mods = {"D0": ("decode", 0, 30), "D1": ("decode", 30, 64),
            "D2": ("decode", 95, 65), "P": ("prefill", 160, 15),
            "D3": ("decode", 175, 65), "D4": ("decode", 246.5, 64.5),
            "D5": ("decode", 319, 65), "D6": ("decode", 384, 16)}
    names = {"decode": "jit_paged_decode_round(7)",
             "prefill": "jit_paged_forward(9)"}
    module_events, op_events = [], []
    for key, (kind, start, dur) in mods.items():
        if key in drop:
            continue
        s = (start + shift_ms) * MS
        # D0 and D1 consume ids too: their producers are not in the trace
        seq = LAUNCHED.get(key, 1 if key == "D1" else 0)
        module_events.append([names[kind], s, dur * MS,
                              {"_ct": 12, "_c": -900 - seq, "run_id": seq}])
        # a fifth of every program under the experts' scope, the rest under
        # a while op with no stage of its own; 0.5 ms idle before each
        op_events += [["%while.1", s + 0.5 * MS, (dur - 0.5) * MS,
                       "jit(f)/jit(main)/while"],
                      ["%gmm.2", s + dur * MS / 2, dur * MS / 5,
                       EXPERTS + "pallas_call"]]
    device = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULE_LINE, "events": module_events},
        {"name": tr.OP_LINE, "events": op_events}]}
    fn = tc.FUNCTIONS
    host = [
        [fn["decode"] + "/build", 88 * MS, 2 * MS, {}],
        [fn["decode"] + "/build", 90 * MS, 2 * MS, decode_args(12, 8000)],
        [fn["prefill"] + "/build", 149 * MS, 1 * MS, {}],
        [fn["prefill"] + "/build", 150 * MS, 1 * MS,
         {"seq": 13, "rows": 2, "real_rows": 2, "nblk": 2, "tokens": 300,
          "kv_positions": 420, "attended": 61000, "expert_slots": 0}],
        [fn["decode"] + "/build", 152 * MS, 2 * MS, decode_args(14, 8400)],
        [fn["decode"] + "/device", 245 * MS, 70 * MS, decode_args(15, 8800)],
        [fn["decode"] + "/build", 316 * MS, 2 * MS, decode_args(16, 9200)],
        [fn["decode"] + "/build", 380 * MS, 2 * MS, decode_args(17, 9600)],
        # the scheduler waits for seq 14 from before D3 starts, for seq 16
        # while D5 runs
        [fn["decode"] + "/wait", 170 * MS, 68 * MS, {"seq": 14}],
        [fn["decode"] + "/wait", 330 * MS, 50 * MS, {"seq": 16}],
    ] + [[fn["prefill" if seq == 13 else "decode"] + "/emit",
          (seq * 20 + 3) * MS, 1 * MS, {"seq": seq, "experts_read": 50 + seq}]
         for seq in emits]
    spans = {ev[3]["seq"]: ev for ev in host
             if ev[0].endswith(("/build", "/device")) and "rows" in ev[3]}
    execute, enqueue = [], []
    for key, seq in LAUNCHED.items():
        at = spans[seq][1] + 0.5 * MS
        host.append(["launch", at, 1000.0, {"_pt": 14, "_p": 140 + seq}])
        execute += [["Execute", at + 2000, 0.3 * MS,
                     {"_ct": 14, "_c": 140 + seq}],
                    ["Execute/launch", at + 9000, 0.1 * MS,
                     {"_pt": 7, "_p": 70 + seq}]]
        if key in unlinked:
            continue
        enqueue += [["Issue", at + 0.4 * MS, 0.1 * MS,
                     {"_ct": 7, "_c": 70 + seq}],
                    ["Issue/enqueue", at + 0.41 * MS, 0.05 * MS,
                     {"_pt": 12, "_p": -900 - seq, "run_id": seq}]]
    return [device, {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": host},
        {"name": "", "events": execute},
        {"name": "tfrt-queue/1", "events": enqueue}]}]


#: the module event each dispatch of ``hand_trace`` launched
LAUNCHED = {"D2": 12, "P": 13, "D3": 14, "D4": 15, "D5": 16, "D6": 17}


def by_seq(red):
    return {c["seq"]: c for c in red["calls"]}


def test_cut_calls_and_a_round_from_before_the_profiler_are_left_out():
    red = tc.reduce_calls(hand_trace())
    dec, pre = red["programs"]["decode"], red["programs"]["prefill"]
    assert (dec["module_events"], dec["cut"], dec["before_profiler"],
            dec["whole"], dec["joined"]) == (7, 2, 1, 4, 4)
    assert (pre["module_events"], pre["cut"], pre["whole"],
            pre["joined"]) == (1, 0, 1, 1)
    assert red["joined_share"] == 100.0
    calls = by_seq(red)
    assert sorted(calls) == [12, 13, 14, 15, 16]
    # D1 (64 ms) ran first but no launch of the trace is its: the first
    # span's round is D2
    assert calls[12]["device_s"] == pytest.approx(0.065)
    assert calls[12]["after_span_ms"] == pytest.approx(5.0)
    # both sides leave out the cut calls and the early one
    assert dec["device_s"] == pytest.approx(0.065 * 3 + 0.0645)
    assert sum(c["kv_positions"] for c in red["calls"]
               if c["kind"] == "decode") == 8000 + 8400 + 8800 + 9200
    assert calls[13]["kind"] == "prefill" and calls[13]["tokens"] == 300
    assert calls[13]["device_s"] == pytest.approx(0.015)
    # the fenced call: its module lies inside its span, and what the module
    # starts after the span opened bounds the clocks' offset
    assert [c["seq"] for c in red["calls"] if c["fenced"]] == [15]
    assert calls[15]["inside_fence"] is True
    assert red["clock_offset_ms"] == pytest.approx(1.5)
    assert red["fenced"] == 1
    # what a call counted itself rides its /emit span; seq 16's is not in
    # the trace
    assert calls[14]["experts_read"] == 64 and calls[13]["experts_read"] == 63
    assert calls[16]["experts_read"] is None
    # device self-time by stage, a call: a fifth under the experts
    assert calls[14]["stage_s"]["experts"] == pytest.approx(0.013)
    assert calls[14]["stage_s"][tc.UNSCOPED] == pytest.approx(0.0515)


def test_the_join_does_not_rest_on_the_two_clocks_agreeing():
    """The device clock 2 ms behind the host's: the fenced round's module
    reads as starting BEFORE its span opened.  The link is followed on the
    host's clock alone, so every call is joined as before; the offset and
    the module that does not lie inside its span are reported as read."""
    red = tc.reduce_calls(hand_trace(shift_ms=-2.0))
    assert red["joined_share"] == 100.0
    assert sorted(by_seq(red)) == [12, 13, 14, 15, 16]
    assert by_seq(red)[12]["device_s"] == pytest.approx(0.065)
    assert red["clock_offset_ms"] == pytest.approx(-0.5)
    assert by_seq(red)[15]["inside_fence"] is False
    # with no fenced call in the trace there is no reading of the offset,
    # and the join is the same
    planes = hand_trace(shift_ms=-2.0)
    for ev in planes[1]["lines"][0]["events"]:
        if ev[0].endswith("/device"):
            ev[0] = tc.FUNCTIONS["decode"] + "/build"
    red = tc.reduce_calls(planes)
    assert (red["clock_offset_ms"], red["fenced"]) == (None, 0)
    assert sorted(by_seq(red)) == [12, 13, 14, 15, 16]


def test_a_chain_that_breaks_leaves_its_call_unjoined_and_shows():
    """There is no second way to join: a module event whose link does not
    resolve is left out of the work and the device seconds, alone, and
    ``joined_share`` says so."""
    red = tc.reduce_calls(hand_trace(unlinked=("D3",)))
    dec = red["programs"]["decode"]
    assert (dec["whole"], dec["joined"]) == (4, 3)
    assert sorted(by_seq(red)) == [12, 13, 15, 16]
    assert red["joined_share"] == pytest.approx(80.0)
    assert dec["device_s"] == pytest.approx(0.065 * 2 + 0.0645)
    # the first call's: D2 is then ahead of the first launch the trace
    # followed, like D1 -- not whole, not unjoined
    red = tc.reduce_calls(hand_trace(unlinked=("D2",)))
    dec = red["programs"]["decode"]
    assert (dec["before_profiler"], dec["whole"], dec["joined"]) == (2, 3, 3)
    assert red["joined_share"] == 100.0 and 12 not in by_seq(red)
    # a module event the trace lost takes no other call's work
    red = tc.reduce_calls(hand_trace(drop=("D3",)))
    calls = by_seq(red)
    assert sorted(calls) == [12, 13, 15, 16]
    assert calls[15]["device_s"] == pytest.approx(0.0645)   # D4, its own
    assert calls[16]["kv_positions"] == 9200
    assert red["joined_share"] == 100.0
    # a trace with spans and no link at all (another runtime): nothing is
    # joined, nothing is ahead of a first joined one, and the share is 0
    planes = hand_trace()
    planes[1]["lines"][2]["events"] = []
    red = tc.reduce_calls(planes)
    assert red["calls"] == [] and red["joined_share"] == 0.0
    assert red["programs"]["decode"]["whole"] == 5
    assert red["programs"]["decode"]["before_profiler"] == 0


def test_a_round_the_device_trace_found_running_keeps_its_own_span():
    """The host's trace began 3 ms before the device's (my chip run, PR 36,
    seeds 2147502004, 2147504001 and ...004): the span of D0 is in the
    trace and the link finds it, so D0 is cut WITH its work and no later
    round takes that span."""
    planes = hand_trace()
    at = -3 * MS
    planes[1]["lines"][0]["events"] += [
        [tc.FUNCTIONS["decode"] + "/build", at, 2 * MS, decode_args(10, 7200)],
        ["launch", at + 0.5 * MS, 1000.0, {"_pt": 14, "_p": 140}]]
    planes[1]["lines"][1]["events"] += [
        ["Execute", at + 0.502 * MS, 0.3 * MS, {"_ct": 14, "_c": 140}],
        ["Execute/launch", at + 0.509 * MS, 0.1 * MS, {"_pt": 7, "_p": 70}]]
    planes[1]["lines"][2]["events"] += [
        ["Issue", at + 0.9 * MS, 0.1 * MS, {"_ct": 7, "_c": 70}],
        ["Issue/enqueue", at + 0.91 * MS, 0.05 * MS,
         {"_pt": 12, "_p": -900, "run_id": 0}]]
    red = tc.reduce_calls(planes)
    dec = red["programs"]["decode"]
    # D1 now runs after the first launch the trace saw and has none: it is
    # whole and unjoined, not older than the trace
    assert (dec["cut"], dec["before_profiler"], dec["whole"],
            dec["joined"]) == (2, 0, 5, 4)
    assert sorted(by_seq(red)) == [12, 13, 14, 15, 16]
    assert by_seq(red)[12]["device_s"] == pytest.approx(0.065)        # D2


def test_idle_under_a_wait_names_the_round_that_came_late():
    """``.../wait`` carries the ``seq`` it waits for: the device's idle
    time inside it is that call's.  The scheduler waited for seq 14 from
    170 ms: the device went idle when the prefill ended (175) until D3's
    first op (175.5).  Seq 16's wait lay inside D5's run."""
    calls = by_seq(tc.reduce_calls(hand_trace()))
    assert calls[14]["idle_in_wait_ms"] == pytest.approx(0.5)
    assert calls[16]["idle_in_wait_ms"] == 0.0
    assert calls[12]["idle_in_wait_ms"] == 0.0          # no wait in the trace
    assert tc.idle_inside([(0, 10), (20, 30), (40, 50)],
                          [(5, 25), (45, 60)]) == 5 + 5 + 5


def test_a_trace_without_seq_gives_nothing_and_no_number():
    """A trace of a program from before the spans said their work (the
    recorded one of PR 24): no call, no share, and the reader's metrics are
    left out."""
    old = bench_paths.load(os.path.join(
        bench_paths.BENCH, "testdata", "planes_tpu_v5e.json"))
    red = tc.reduce_calls(old)
    assert red["calls"] == [] and red["joined_share"] is None
    assert red["spans"] == {"decode": 0, "prefill": 0}
    # spans with arguments but no seq are PR 24's fenced ones: no call
    planes = hand_trace()
    for ev in planes[1]["lines"][0]["events"]:
        ev[3].pop("seq", None)
    assert tc.reduce_calls(planes)["calls"] == []
    ctx = reader_ctx(DENSE, red)
    for name in ("decode_calls_roofline", "prefill_calls_roofline",
                 "experts_calls_roofline", "trace_calls_joined_share"):
        assert reader.read(MAN.layer_metric(name), ctx) is None
    assert "bounds" not in ctx


# -- the reader ------------------------------------------------------------


def reader_ctx(config, red, **extra):
    reader._REDUCED["a-trace"] = red
    doc = MAN.config(config)
    return {"trace": {"busy_s": 1.0}, "cell": {"name": "a-cell"},
            "bench_dir": MAN.bench, "config": doc,
            "deployment": {"span": 8}, "device": {"kind": "TPU v5 lite"},
            "genperf_before": {}, "genperf_after": {}, **extra}


@pytest.fixture(autouse=True)
def a_trace(monkeypatch):
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: "a-trace")
    yield
    reader._REDUCED.clear()


def test_each_call_is_held_to_its_own_bound_and_the_least_times_add():
    """``decode_calls_roofline`` is the sum of the calls' least seconds
    over the sum of their device seconds: a call of one row is bound by the
    weights, and its least time is not the mean call's."""
    red = tc.reduce_calls(hand_trace())
    ctx = reader_ctx(DENSE, red)
    doc, peaks = ctx["config"], peaks_for("TPU v5 lite")
    needs = arch_module(MAN.bench, doc, "needs")
    want = dev = 0.0
    for kv in (8000, 8400, 8800, 9200):
        step = needs.decode_step(doc, 5, kv / 8, {})
        want += roofline.least_seconds(
            {k: v * 8 for k, v in step.items()}, peaks)["seconds"]
    dev = 0.065 * 3 + 0.0645
    value = reader.read(MAN.layer_metric("decode_calls_roofline"), ctx)
    assert value == pytest.approx(100.0 * want / dev)
    assert 85.0 < value < 100.0             # 6.06 GB a step at 819 GB/s
    need = needs.prefill(doc, 1, 300, 61000, {})
    least = roofline.least_seconds(need, peaks)
    assert reader.read(MAN.layer_metric("prefill_calls_roofline"), ctx) == \
        pytest.approx(100.0 * least["seconds"] / 0.015)
    assert ctx["bounds"] == {"decode_calls_roofline": "memory",
                             "prefill_calls_roofline": least["bound"]}
    assert reader.read(
        MAN.layer_metric("trace_calls_joined_share"), ctx) == 100.0
    # a dense block has no experts' arithmetic: nothing, never 0
    assert reader.read(
        MAN.layer_metric("experts_calls_roofline"), ctx) is None


def test_the_experts_of_a_call_are_its_own_count_or_a_marked_stand_in():
    planes = hand_trace(emits=(12, 14, 15))
    for ev in planes[1]["lines"][0]["events"]:
        if ev[3].get("passes"):
            ev[3].update(passes=10, blocks=2, expert_slots=68 * 128,
                         real_rows=18)
    red = tc.reduce_calls(planes)
    before = {"served_decode": {"expert_slots": 1000, "experts_read": 100}}
    after = {"served_decode": {"expert_slots": 1000 + 100 * 68 * 128,
                               "experts_read": 100 + 100 * 68 * 96}}
    reader.stand_in(red, before, after)
    calls = by_seq(red)
    assert calls[14]["experts_read_from"] == "emit"
    assert calls[16]["experts_read_from"] == "window"
    assert calls[16]["experts_read"] == pytest.approx(68 * 96)
    assert "experts_read_from" not in calls[13]        # no slots: no experts
    ctx = reader_ctx(SPARSE, red)
    doc, peaks = ctx["config"], peaks_for("TPU v5 lite")
    needs = arch_module(MAN.bench, doc, "needs")
    want = 0.0
    for seq in (12, 14, 15, 16):
        counters = {"served_decode": {
            "expert_slots": 68 * 128,
            "experts_read": calls[seq]["experts_read"]}}
        want += roofline.least_seconds(
            needs.experts(doc, 18, counters), peaks)["seconds"]
    under = (0.065 * 3 + 0.0645) / 5
    assert reader.read(MAN.layer_metric("experts_calls_roofline"), ctx) == \
        pytest.approx(100.0 * want / under)
    # the round's own roofline takes the same counters, and a round that
    # read fewer experts needs less
    few = copy.deepcopy(red)
    for c in few["calls"]:
        if c.get("experts_read"):
            c["experts_read"] = c["experts_read"] / 2
    all_ = reader.read(MAN.layer_metric("decode_calls_roofline"), ctx)
    less = reader.read(MAN.layer_metric("decode_calls_roofline"),
                       reader_ctx(SPARSE, few))
    assert 0.0 < less < all_


def test_a_reader_with_nothing_to_read_returns_nothing():
    for name in ("decode_calls_roofline", "trace_calls_joined_share"):
        metric = MAN.layer_metric(name)
        assert reader.read(metric, {"trace": None}) is None
        assert reader.read(metric, reader_ctx(
            DENSE, {"error": "the child failed"})) is None


def test_the_reduction_runs_as_a_child_and_is_kept_beside_the_trace(
        tmp_path, monkeypatch):
    """The script form, as run.py's reader starts it: on a trace with no
    plane at all it prints the empty reduction, which the reader writes to
    ``calls.json`` in the run's directory and reads no number from."""
    run_dir = tmp_path / "out" / "a-cell.7.t1"
    where = run_dir / "profile" / "window" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(b"")
    path = str(where / "vm.xplane.pb")
    monkeypatch.setattr("readers.trace_scopes.newest_trace",
                        lambda cell: path)
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": "a-cell"},
           "genperf_before": {}, "genperf_after": {}}
    assert reader.read(
        MAN.layer_metric("trace_calls_joined_share"), ctx) is None
    kept = bench_paths.load(str(run_dir / "calls.json"))
    assert kept["calls"] == [] and kept["devices"] == 0
    assert kept["joined_share"] is None and "error" not in kept


# -- the recorded traces -----------------------------------------------------


@pytest.mark.parametrize("config, name, expect", [
    (DENSE, "calls_starcoder2-3b_tpu_v5e.json",
     {"decode": "decode_calls_roofline", "prefill": "prefill_calls_roofline"}),
    (SPARSE, "calls_sdar-30b-a3b_tpu_v5e.json",
     {"decode": "decode_calls_roofline", "prefill": "prefill_calls_roofline",
      "experts": "experts_calls_roofline"}),
])
def test_a_recorded_chip_trace_of_each_kind_of_round_reduces(config, name,
                                                             expect):
    """Cut-down traces of one traced run a cell (my chip run, PR 36): the
    window's module events and spans whole, the ops of a few calls."""
    doc = bench_paths.load(os.path.join(bench_paths.BENCH, "testdata", name))
    red = tc.reduce_calls(doc["planes"])
    # the trace's own link joins every whole call
    assert red["joined_share"] == 100.0
    for kind, prog in red["programs"].items():
        assert prog["whole"] == prog["joined"] > 0
        assert prog["cut"] + prog["before_profiler"] + prog["whole"] == \
            prog["module_events"]
    assert all(c["inside_fence"] for c in red["calls"] if c["fenced"])
    seqs = [c["seq"] for c in red["calls"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # what the fenced calls read is the launch of an unqueued program
    assert red["fenced"] >= 1 and 0.0 < red["clock_offset_ms"] < 5.0
    for c in red["calls"]:
        assert 1 <= c["real_rows"] <= c["rows"] and c["device_s"] > 0
        assert c["after_span_ms"] > 0
    reader.stand_in(red, {}, doc["genperf_window"])
    ctx = reader_ctx(config, red)
    for key, metric in expect.items():
        value = reader.read(MAN.layer_metric(metric), ctx)
        lo, hi = doc["expected"][metric]
        assert lo <= value <= hi <= 100.0, (metric, value)
    assert json.dumps(red)          # calls.json is plain data
