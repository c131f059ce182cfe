"""The ``boot`` reader (bench/readers/boot.py): every set-up metric's
arithmetic over a canned ``/stats`` document, nothing where the block is
absent, and the eleven against the live tiny engine of
test_bench_readers.py (its ``session``)."""

import importlib

import bench_paths  # noqa: F401  (puts bench/ on the path)
import pytest
from bench_paths import REPO
from lib.manifest import Manifest
from readers import boot
from test_bench_readers import session  # noqa: F401  (the fixture)

MAN = Manifest(REPO)
SETUP = [m["name"] for m in MAN.doc["per_layer"] if m["moves"] == "setup_s"]

CANNED = {"boot": {
    "clock": "monotonic", "process_start": 1000.0, "uptime_s": 50.0,
    "spans": [
        {"name": "process", "parent": None, "start_s": 0.0, "end_s": 0.5},
        {"name": "imports", "parent": None, "start_s": 0.5, "end_s": 3.0},
        {"name": "deployment", "parent": None, "start_s": 3.0, "end_s": 3.25},
        {"name": "backend", "parent": None, "start_s": 3.25, "end_s": 8.25},
        {"name": "unit/gen", "parent": "units", "start_s": 8.5, "end_s": 9.5},
        {"name": "units", "parent": None, "start_s": 8.25, "end_s": 10.25},
        {"name": "listen", "parent": None, "start_s": 10.25, "end_s": 10.5},
        {"name": "pool", "parent": "device_init", "start_s": 11.0,
         "end_s": 11.5},
        {"name": "carry", "parent": "device_init", "start_s": 11.5,
         "end_s": 13.0},
        {"name": "load", "parent": "device_init", "start_s": 13.0,
         "end_s": 19.0},
        {"name": "device_init", "parent": None, "start_s": 11.0,
         "end_s": 19.0},
    ],
    "programs": [
        {"kind": "prefill", "shape": [1, 64, 2], "trace_s": 1.5,
         "load_s": 0.5, "from_cache": True},
        {"kind": "decode", "shape": [1, 8], "trace_s": 2.0, "load_s": 4.0,
         "from_cache": False},
        {"kind": "decode", "shape": [2, 8], "trace_s": 0.25, "load_s": 0.0,
         "error": "RuntimeError: x"},
    ],
    "first_dispatch": {"loaded": {"n": 2, "host_s": 0.75},
                       "missed": {"n": 2, "host_s": 6.0, "from_cache": 1}},
    "serving_s": 20.0, "waiting_s": 10.0, "accounted_s": 48.5,
}}

WANT = {
    "setup_uptime_s": 50.0,
    "setup_process_s": 0.5 + 2.5 + 0.25 + 5.0,
    "setup_weights_s": 2.0,
    "setup_pool_s": 8.0 - 6.0,
    "setup_load_s": 6.0,
    "setup_trace_s": 3.75,
    # two of the boot's three were not fetched, one of the two missed
    "setup_cache_miss_share": 100.0 * (2 + 2 - 1) / (3 + 2),
    "setup_first_dispatch_s": 0.75,
    "setup_missed_s": 6.0,
    "setup_serving_s": 20.0 - 0.75 - 6.0,
    "setup_accounted_share": 97.0,
}


def _read(name, stats):
    metric = MAN.layer_metric(name)
    assert metric["reader"] == "boot" and metric["layer"] == "set-up"
    return importlib.import_module("readers.boot").read(
        metric, {"stats_before": stats, "stats_after": {}, "harness": {}})


def test_the_benchmark_has_eleven_set_up_metrics_and_only_these():
    assert SETUP == list(WANT)
    for m in MAN.doc["per_layer"]:
        if m["name"] in WANT:
            assert (m["source"], m["layer"]) == ("program_span", "set-up")
            assert "workloads" not in m         # every cell boots


@pytest.mark.parametrize("name", list(WANT))
def test_a_canned_document_reads_what_the_arithmetic_says(name):
    assert _read(name, CANNED) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", list(WANT))
def test_only_a_document_without_the_block_reads_nothing(name):
    for stats in ({}, {"boot": None}, {"genserver": {}}):
        assert _read(name, stats) is None
    assert boot.read(MAN.layer_metric(name), {}) is None
    # an engine that has served nothing yet: no span, no program -- and
    # a share over none of them is 0.0, a difference never under it
    empty = {"boot": {"uptime_s": 2.0, "spans": [], "programs": [],
                      "first_dispatch": {
                          "loaded": {"n": 0, "host_s": 0.5},
                          "missed": {"n": 0, "host_s": 0.0,
                                     "from_cache": 0}},
                      "serving_s": 0.25, "waiting_s": 0.0,
                      "accounted_s": 0.0}}
    value = _read(name, empty)
    assert isinstance(value, float)
    assert value == (2.0 if name == "setup_uptime_s" else
                     0.5 if name == "setup_first_dispatch_s" else 0.0)


def test_a_term_the_block_lacks_counts_nothing():
    assert boot.evaluate({"num": [{"span": "prefix"}, {"path": "a.b"},
                                  {"sum": "absent", "field": "x"},
                                  {"count": "absent"}]}, CANNED["boot"]) == 0.0
    assert boot.evaluate({"num": [{"count": "programs"}]},
                         CANNED["boot"]) == 3.0
    assert boot.evaluate(
        {"num": [{"count": "programs", "unless": {"kind": "decode"}}],
         "den": [{"count": "programs"}], "scale": 3.0},
        CANNED["boot"]) == 1.0


def test_the_live_engines_eleven_add_up(session):  # noqa: F811
    """The engine test_bench_readers.py boots, over a fresh cache
    directory: nothing fetched, every shape missed, and the account
    whole."""
    stats = session["before"]["stats"]
    got = {name: _read(name, stats) for name in WANT}
    assert all(isinstance(v, float) and v >= 0.0 for v in got.values()), got
    assert got["setup_accounted_share"] >= 95.0
    assert got["setup_cache_miss_share"] == 100.0
    assert got["setup_load_s"] == got["setup_trace_s"] == 0.0
    assert got["setup_first_dispatch_s"] == 0.0
    progs = stats["genserver"]["programs"]
    assert stats["boot"]["first_dispatch"]["missed"]["n"] == progs["missed"]
    assert got["setup_missed_s"] > 0.0 and got["setup_serving_s"] > 0.0
    parts = sum(got[k] for k in (
        "setup_process_s", "setup_weights_s", "setup_pool_s",
        "setup_load_s", "setup_missed_s", "setup_serving_s"))
    assert parts <= got["setup_uptime_s"]
    # what is left is the harness's pace between its requests and the
    # lanes' binding: the idle waits
    assert got["setup_uptime_s"] - parts <= (
        stats["boot"]["waiting_s"] + 0.05 * got["setup_uptime_s"] + 0.5)
