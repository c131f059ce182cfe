"""The ``genperf``, ``stats`` and ``harness`` readers against a LIVE tiny
engine on the CPU — the engine a user runs, booted by the harness's own
child handling and driven by its own client — so that a rename inside the
program fails a test here before it fails a chip run."""

import asyncio
import importlib
import time

import bench_paths
import pytest
from bench_paths import REPO
from lib import buckets, client, traffic
from lib.engine import (
    Engine,
    compile_counters,
    deployment_doc,
    engine_env,
)
from lib.formula import lookup
from lib.manifest import Manifest

MAN = Manifest(REPO)
# a device_trace metric reads a profiler trace of a chip: nothing to read
# from an engine on the CPU (test_bench_trace*.py cover those readers)
LIVE = [m["name"] for m in MAN.doc["per_layer"]
        if m["source"] != "device_trace"]


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench_live")
    config = MAN.config("starcoder2-3b")
    config = {**config, **bench_paths.TINY_CONFIG, "name": "tiny"}
    dep = {**config["deployment"], **bench_paths.TINY_DEPLOYMENT}
    path = str(tmp / "deployment.json")
    bench_paths.dump(path, deployment_doc(config, dep, 2 ** 31 + 3, 24))
    env = {**engine_env(dep, str(tmp / "profile")),
           "JAX_COMPILATION_CACHE_DIR": str(tmp / "xla_cache"),
           "JAX_PLATFORMS": "cpu"}
    eng = Engine(REPO, path, env, str(tmp / "engine.log"),
                 boot_timeout_s=300)
    try:
        yield asyncio.run(_drive(eng, config, dep))
    finally:
        eng.stop()
    assert eng.proc.poll() is not None


async def _drive(eng, config, dep):
    async def get(path):
        status, doc = await client.http_json(eng.port, "GET", path)
        assert status == 200, (path, status, doc)
        return doc

    vocab = config["vocab_size"]
    warm = await client.stream_once(
        eng.port, client.rows_body(
            [traffic.prompt_tokens(1, r, 40, vocab) for r in range(2)],
            10, dep["span"]), time.monotonic, 300.0)
    mix = MAN.mix("codegen")
    mix = {**mix, "max_positions": 88,
           "prompt_tokens": {**mix["prompt_tokens"], "median": 24,
                             "min": 8, "max": 64},
           "output_tokens": {**mix["output_tokens"], "median": 12,
                             "min": 4, "max": 24}}
    # the run's warm-up ladder, from lib/buckets.py's arithmetic alone
    n = 0
    for b in buckets.row_buckets(dep["slots"]):
        for length, max_new in buckets.ladder_rows(dep, buckets.caps(mix)):
            rec = await client.stream_once(eng.port, client.rows_body(
                [traffic.prompt_tokens(7, n * 64 + r, length, vocab)
                 for r in range(b)], max_new, dep["span"]),
                time.monotonic, 300.0)
            assert rec["done"], rec
            n += 1
    before = {"stats": await get("/stats"), "genperf": await get("/genperf")}
    reqs = traffic.open_loop(mix, 12.0, 3.0, 1.0)
    bodies = [client.stream_body(
        traffic.prompt_tokens(5, r.index, r.prompt_len, vocab), r.out_len,
        dep["span"]) for r in reqs]
    records = await client.run_open_loop(
        eng.port, reqs, bodies, vocab, time.monotonic(), 30.0)
    after = {"stats": await get("/stats"), "genperf": await get("/genperf")}
    return {"warm": warm, "records": records, "before": before,
            "after": after, "perf": await get("/perf"), "dep": dep,
            "config": config}


def test_streams_carry_exactly_max_new_ids_in_range(session):
    warm = session["warm"]
    assert warm["done"] and [len(r) for r in warm["tokens"]] == [10, 10]
    recs = session["records"]
    assert len(recs) == 36 and all(r["ok"] for r in recs), [
        r for r in recs if not r["ok"]][:2]
    for r in recs:
        assert r["n_out"] == r["out_len"]
        assert r["ttft_ms"] > 0 and r["late_ms"] > -1.0
        assert (r["tpot_ms"] is None) == (r["n_out"] < 2)
        assert r["t_sent"] <= r["t_first"] <= r["t_last"]


def test_the_ladder_leaves_the_cells_traffic_nothing_to_compile(session):
    """lib/buckets.py re-states the scheduler's padding rules; this holds
    it to the program: after the ladder, traffic at the mix's lengths
    reaches no (rows, chunk, blocks) bucket the engine has not compiled."""
    before = compile_counters(session["before"]["stats"])
    after = compile_counters(session["after"]["stats"])
    assert after["compiles"] == before["compiles"]
    assert before["compiles"] > 0


def test_the_documents_the_run_reads_are_there(session):
    perf = session["perf"]
    assert perf["device"]["platform"] == "cpu"
    assert isinstance(perf["hbm"], list)
    for doc in (session["before"], session["after"]):
        c = compile_counters(doc["stats"])
        assert set(c) == {"compiles", "compile_s", "cache_hits",
                          "cache_misses"}
        g = doc["stats"]["genserver"]
        for key in ("inflight_sequences", "waiting_sequences",
                    "preempted_total", "slots", "span"):
            assert key in g
        assert g["slots"] == session["dep"]["slots"]
        assert g["prefill_chunk_effective"] == session["dep"]["prefill_chunk"]
        assert doc["genperf"]["adaptive_chunk"]["latched"] is True
    assert (session["after"]["stats"]["genserver"]["admitted_total"]
            - session["before"]["stats"]["genserver"]["admitted_total"]) == 36


@pytest.mark.parametrize("name", LIVE)
def test_layer_metric_reads_a_number_from_the_live_engine(session, name):
    metric = MAN.layer_metric(name)
    reader = importlib.import_module("readers." + metric["reader"])
    prompt_tokens = float(sum(r["prompt_len"] for r in session["records"]))
    ctx = {
        "records": session["records"],
        "genperf_before": session["before"]["genperf"],
        "genperf_after": session["after"]["genperf"],
        "stats_before": session["before"]["stats"],
        "stats_after": session["after"]["stats"],
        "harness": {"prompt_tokens_first_token_in_window": prompt_tokens},
    }
    value = reader.read(metric, ctx)
    assert isinstance(value, float) and value >= 0.0, (name, value)
    # every path a formula names exists in the engine's document today
    # (bubble causes appear only once they have occurred)
    doc = session["after"][{"genperf": "genperf", "stats": "stats"}.get(
        metric["reader"], "genperf")]
    terms = metric["formula"].get("num", []) + metric["formula"].get(
        "den", []) if metric["reader"] in ("genperf", "stats") else []
    for term in terms:
        if "path" in term and "by_cause_s" not in term["path"]:
            assert lookup(doc, term["path"]) is not None, term
    if name.startswith("decode_rows_mean"):
        assert 0.5 <= value <= session["dep"]["slots"]
    if name.startswith("tick_host_share"):
        assert 0.0 < value < 100.0
    if name.startswith("decode_step_ms"):
        assert value > 0.0


def test_a_reader_with_nothing_to_read_returns_nothing():
    from readers import genperf, harness, stats, trace

    metric = MAN.layer_metric("decode_step_ms")
    assert genperf.read(metric, {"genperf_before": {}, "genperf_after": {},
                                 "harness": {}}) is None
    assert stats.read(MAN.layer_metric("preempted"), {
        "stats_before": {}, "stats_after": {}, "harness": {}}) is None
    assert harness.read(MAN.layer_metric("gen_late_p95_ms"),
                        {"records": []}) is None
    assert trace.read(MAN.layer_metric("decode_roofline"),
                      {"trace": None}) is None


def test_bubble_ledger_causes_are_the_programs_vocabulary():
    from seldon_core_tpu.utils.genperf import BUBBLE_CAUSES

    metric = MAN.layer_metric("admit_stall_share")
    for term in metric["formula"]["num"]:
        assert term["path"].rsplit(".", 1)[1] in BUBBLE_CAUSES
