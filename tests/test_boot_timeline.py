"""The boot timeline (utils/genperf.py ``BOOT``; ``GET /stats`` ``boot``):
what a process did from its start to the request it is serving now.

Two ways in: a tiny engine PROCESS booted twice over one cache directory
(the first run of it, then a warm one) and read over HTTP, as an operator
reads it; and schedulers inside this process, where a test can count the
recorder's calls and break a job."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.transformer import LMConfig, lm_init
from seldon_core_tpu.runtime import genserver as gs_mod
from seldon_core_tpu.runtime.genserver import GenServer
from seldon_core_tpu.utils.genperf import BOOT, BootTimeline, _union_s

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK_KEYS = {"clock", "process_start", "uptime_s", "spans", "programs",
              "first_dispatch", "serving_s", "waiting_s", "accounted_s"}
TOP_LEVEL = ["process", "imports", "deployment", "backend", "units", "listen",
             "device_init"]


# -- an engine process, cold then warm ---------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _deployment() -> dict:
    params = dict(vocab=96, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
                  d_ff=64, max_new_tokens=6)
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha2",
        "kind": "SeldonDeployment", "metadata": {"name": "tiny"},
        "spec": {"name": "tiny", "predictors": [{
            "name": "main", "replicas": 1,
            "components": [{
                "name": "gen", "runtime": "inprocess",
                "class_path": "TransformerGenerator",
                "parameters": [
                    {"name": k, "value": str(v), "type": "INT"}
                    for k, v in params.items()] + [
                    {"name": "dtype", "value": "float32", "type": "STRING"}],
            }],
            "graph": {"name": "gen", "type": "MODEL", "children": []},
        }]},
    }


def _http(port: int, path: str, body=None, timeout=240.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _boot_and_serve(tmp, n: int) -> dict:
    """One engine process over ``tmp``'s cache directory: two requests (one
    row, then two: several shapes), then ``/stats`` once the scheduler is
    idle, and the process's log."""
    dep = tmp / "deployment.json"
    dep.write_text(json.dumps(_deployment()))
    port, log_path = _free_port(), tmp / f"engine{n}.log"
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp / "xla_cache"),
        # the CPU's quick compiles enter the cache too
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        SELDON_TPU_GEN_BLOCK_SIZE="4", SELDON_TPU_GEN_POOL_BLOCKS="48",
        SELDON_TPU_GEN_SLOTS="4", SELDON_TPU_GEN_SPAN="3",
        SELDON_TPU_GEN_PREFILL_CHUNK="8",
        SELDON_TPU_GEN_PREFILL_CHUNK_MAX="8",
        ENGINE_DISPATCH_TIMEOUT_S="240", ENGINE_SHUTDOWN_DRAIN_S="1")
    env.pop("SELDON_COMPILE_CACHE", None)
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.runtime.engine_main",
             "--file", str(dep), "--host", "127.0.0.1",
             "--rest-port", str(port), "--grpc-port", str(_free_port())],
            stdout=log, stderr=subprocess.STDOUT, cwd=ROOT, env=env,
            start_new_session=True)
    try:
        deadline = time.monotonic() + 240
        while "engine up:" not in log_path.read_text():
            assert proc.poll() is None, log_path.read_text()[-2000:]
            assert time.monotonic() < deadline, "engine not up"
            time.sleep(0.1)
        for rows in (1, 2):
            out = _http(port, "/api/v0.1/predictions", {"data": {"ndarray": [
                [float((7 * r + i) % 90) for i in range(11)]
                for r in range(rows)]}})
            assert np.asarray(out["data"]["ndarray"]).shape == (rows, 6), out
        while True:
            stats = _http(port, "/stats")
            g = stats["genserver"]
            if not g["inflight_sequences"] + g["waiting_sequences"]:
                break
            assert time.monotonic() < deadline, "engine not idle"
            time.sleep(0.05)
        # the line is written when the scheduler first goes idle
        while "boot timeline:" not in log_path.read_text():
            assert time.monotonic() < deadline, "no boot timeline line"
            time.sleep(0.05)
        stats = _http(port, "/stats")
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
    return {"stats": stats, "log": log_path.read_text()}


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("boot_timeline")
    return {"cold": _boot_and_serve(tmp, 0), "warm": _boot_and_serve(tmp, 1)}


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_spans_nest_by_parent_and_do_not_overlap_within_one(boots, which):
    boot = boots[which]["stats"]["boot"]
    assert set(boot) == BLOCK_KEYS and boot["clock"] == "monotonic"
    spans = boot["spans"]
    by_name = {s["name"]: s for s in spans}
    assert len(by_name) == len(spans)           # one process, one server
    assert [s["name"] for s in spans if s["parent"] is None] == TOP_LEVEL
    assert by_name["process"]["start_s"] == 0.0
    for s in spans:
        assert 0.0 <= s["start_s"] <= s["end_s"] <= boot["uptime_s"], s
        if s["parent"] is not None:
            parent = by_name[s["parent"]]
            assert parent["start_s"] <= s["start_s"], (s, parent)
            assert s["end_s"] <= parent["end_s"], (s, parent)
    for parent in {s["parent"] for s in spans}:
        children = sorted((s for s in spans if s["parent"] == parent),
                          key=lambda s: s["start_s"])
        for a, b in zip(children, children[1:]):
            assert a["end_s"] <= b["start_s"], (a, b)
    assert by_name["unit/gen"]["parent"] == "units"
    inside = {s["name"] for s in spans if s["parent"] == "device_init"}
    assert inside == {"pool", "kernels", "carry"} | (
        {"load"} if which == "warm" else set())
    assert by_name["backend"]["platform"] == "cpu"


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_the_timeline_accounts_for_the_processes_life(boots, which):
    """Top-level spans, ticks and idle waits leave under a twentieth of
    the time from the process's start to the scrape unnamed."""
    boot = boots[which]["stats"]["boot"]
    top = [(s["start_s"], s["end_s"]) for s in boot["spans"]
           if s["parent"] is None]
    assert boot["accounted_s"] == pytest.approx(
        _union_s(top) + boot["serving_s"] + boot["waiting_s"], abs=1e-5)
    assert boot["serving_s"] > 0.0 and boot["waiting_s"] > 0.0
    assert 0.95 * boot["uptime_s"] <= boot["accounted_s"] <= boot["uptime_s"]
    # the clock is the one a harness that spawned the process reads
    assert 0.0 < time.monotonic() - boot["process_start"] < 3600.0


def test_a_first_run_fetches_nothing_and_a_warm_one_everything(boots):
    cold, warm = (boots[k]["stats"] for k in ("cold", "warm"))
    n = cold["genserver"]["programs"]["missed"]
    assert n >= 4 and cold["boot"]["programs"] == []
    assert cold["boot"]["first_dispatch"] == {
        "loaded": {"n": 0, "host_s": 0.0},
        "missed": {"n": n, "from_cache": 0,
                   "host_s": cold["boot"]["first_dispatch"]["missed"]["host_s"]}}
    assert cold["boot"]["first_dispatch"]["missed"]["host_s"] > 0.0
    programs = warm["boot"]["programs"]
    assert len(programs) == n == warm["genserver"]["programs"]["loaded_at_boot"]
    for entry in programs:
        assert set(entry) == {"kind", "shape", "trace_s", "load_s",
                              "from_cache", "stored"}, entry
        # the program store handed every one over: nothing was traced
        assert entry["from_cache"] is True and entry["stored"] is True
        assert entry["trace_s"] == 0.0 < entry["load_s"]
    # prefill before decode, as the boot asks for them
    assert [e["kind"] for e in programs] == sorted(
        (e["kind"] for e in programs), key=lambda k: k != "prefill")
    assert warm["genserver"]["programs"]["missed"] == 0
    first = warm["boot"]["first_dispatch"]
    assert first["missed"] == {"n": 0, "host_s": 0.0, "from_cache": 0}
    assert first["loaded"]["n"] == n and first["loaded"]["host_s"] > 0.0
    assert warm["genserver"]["programs"]["stored_at_boot"] == n
    assert cold["genserver"]["programs"]["stored_at_boot"] == 0
    assert f", {n} from the program store" in boots["warm"]["log"]


def test_boot_load_s_and_boot_trace_s_are_the_timelines_sums(boots):
    for which in ("cold", "warm"):
        stats = boots[which]["stats"]
        progs, boot = stats["genserver"]["programs"], stats["boot"]
        load = sum(s["end_s"] - s["start_s"] for s in boot["spans"]
                   if s["name"] == "load")
        trace = sum(e["trace_s"] for e in boot["programs"])
        assert progs["boot_load_s"] == round(load, 3)
        assert progs["boot_trace_s"] == round(trace, 3)
    # (the warm boot's: every program came from the store)
    assert 0.0 == progs["boot_trace_s"] < progs["boot_load_s"]


def test_the_pods_log_holds_the_block_as_one_line(boots):
    for which in ("cold", "warm"):
        lines = [ln for ln in boots[which]["log"].splitlines()
                 if ln.startswith("boot timeline: ")]
        assert len(lines) == 1, lines
        doc = json.loads(lines[0].split(": ", 1)[1])
        assert set(doc) == BLOCK_KEYS
        final = boots[which]["stats"]["boot"]
        # written at the first idle moment: what had ended by then
        assert doc["spans"] == final["spans"]
        assert doc["programs"] == final["programs"]
        assert doc["uptime_s"] < final["uptime_s"]
    # ... and the load's own line, which nothing used to print
    assert "of the record's" in boots["warm"]["log"]
    assert "of the record's" not in boots["cold"]["log"]


# -- schedulers inside this process -------------------------------------------

CFG = LMConfig(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32)
PROMPTS = np.random.default_rng(33).integers(0, 48, size=(3, 7))


@pytest.fixture(scope="module")
def params():
    return lm_init(jax.random.key(3), CFG)


def _server(params, **kw):
    return GenServer(params, CFG, **{**dict(
        max_new_tokens=10, block_size=4, num_blocks=64, slots=8, span=3,
        prefill_chunk=4), **kw})


def _settle(srv, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = srv.snapshot()
        if not s["inflight_sequences"] and not s["waiting_sequences"]:
            return s
        time.sleep(0.01)
    raise AssertionError("scheduler did not settle")


def _serve(srv, rows):
    srv.submit(PROMPTS[:rows].astype(float)).future.result(timeout=240)
    return _settle(srv)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """JAX's persistent cache on, in a directory of the test's own, keeping
    the CPU's quick compiles too (tests/test_genserver.py has the same
    two fixtures)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "4")
    monkeypatch.delenv("SELDON_COMPILE_CACHE", raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = [getattr(jax.config, name) for name in names]
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0.0)
    compilation_cache.reset_cache()
    yield tmp_path
    for name, value in zip(names, before):
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_a_tick_over_a_known_shape_calls_no_timeline_code(
        params, monkeypatch):
    """The recorder counts what is written to it: a request whose shapes
    were all dispatched before adds nothing, however many ticks it takes;
    one that brings new shapes adds one entry a shape."""
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "4")
    monkeypatch.setenv("SELDON_COMPILE_CACHE", "0")
    srv = _server(params)
    try:
        _serve(srv, 1)
        shapes = sum(map(len, srv._programs.values()))
        first = BOOT.document(srv.boot_server)["first_dispatch"]
        assert first["missed"]["n"] == shapes >= 2
        assert first["loaded"]["n"] == 0
        calls, ticks = BOOT.calls, srv.steps_total.copy()
        for _ in range(3):
            _serve(srv, 1)
        assert sum(srv.steps_total.values()) > sum(ticks.values())
        assert BOOT.calls == calls
        assert sum(map(len, srv._programs.values())) == shapes
        _serve(srv, 3)          # more rows: new shapes, one entry each
        more = sum(map(len, srv._programs.values()))
        assert more > shapes and BOOT.calls == calls + more - shapes
        progs = srv.snapshot()["programs"]
        first = srv.boot_document()["first_dispatch"]
        assert first["missed"]["n"] == progs["missed"] == more
    finally:
        srv.stop()


def test_first_dispatch_splits_as_missed_counts(params, cache_dir):
    """A boot over another's record: the listed shapes' first dispatches
    are ``loaded``, the shapes new to it ``missed``, as /stats
    ``programs.missed`` counts them, fetched or compiled."""
    jax.clear_caches()
    first = _server(params)
    try:
        _serve(first, 1)
        few = sum(map(len, first._programs.values()))
    finally:
        first.stop()
    jax.clear_caches()                          # as a new process would be
    second = _server(params)
    try:
        _serve(second, 1)
        _serve(second, 3)
        _serve(second, 1)
        progs = _settle(second)["programs"]
        boot = second.boot_document()
    finally:
        second.stop()
    assert progs["loaded_at_boot"] == few == len(boot["programs"])
    assert all(e["from_cache"] for e in boot["programs"])
    split = boot["first_dispatch"]
    assert split["loaded"]["n"] == few
    assert split["missed"]["n"] == progs["missed"] > 0
    assert split["missed"]["from_cache"] == 0       # compiled, never seen
    assert few + progs["missed"] == progs["prefill"] + progs["decode"]
    assert boot["serving_s"] >= (split["loaded"]["host_s"]
                                 + split["missed"]["host_s"]) > 0.0
    names = [s["name"] for s in boot["spans"]]
    assert names == ["pool", "kernels", "carry", "load", "device_init"]
    load = next(s for s in boot["spans"] if s["name"] == "load")
    assert progs["boot_load_s"] == round(load["end_s"] - load["start_s"], 3)
    assert progs["boot_trace_s"] == round(
        sum(e["trace_s"] for e in boot["programs"]), 3)


def test_a_job_that_raises_is_an_entry_with_its_seconds_and_its_error(
        params, cache_dir, monkeypatch, caplog):
    """The tracer's seconds in a ``.lower()`` that raised are counted, the
    entry says what it raised, and the shape is left to its first
    request."""
    jax.clear_caches()
    first = _server(params)
    try:
        _serve(first, 1)
        listed = {k: set(v) for k, v in first._programs.items()}
    finally:
        first.stop()
    for path in cache_dir.glob("genserver-program-*.pkl"):
        path.unlink()           # the traced path: nothing is stored
    jax.clear_caches()
    real = GenServer._program

    def program(self, kind, *operands, state=None):
        fn, args, kw = real(self, kind, *operands, state=state)
        if kind == "decode" and state is not None:     # the boot's lowering

            class Broken:
                @staticmethod
                def lower(*a, **k):
                    time.sleep(0.02)
                    raise RuntimeError("no such program")
            return Broken, args, kw
        return fn, args, kw

    monkeypatch.setattr(GenServer, "_program", program)
    second = _server(params)
    try:
        with caplog.at_level("WARNING"):
            second._ensure_device()
        boot = second.boot_document()
        progs = second.snapshot()["programs"]
        assert second._loaded == {"prefill": listed["prefill"],
                                  "decode": set()}
    finally:
        second.stop()
    broken = [e for e in boot["programs"] if "error" in e]
    assert len(broken) == len(listed["decode"]) >= 1
    for e in broken:
        assert e["kind"] == "decode" and "from_cache" not in e
        assert e["error"] == "RuntimeError: no such program"
        assert e["trace_s"] >= 0.02 and e["load_s"] == 0.0
    assert progs["boot_trace_s"] == round(
        sum(e["trace_s"] for e in boot["programs"]), 3)
    assert progs["loaded_at_boot"] == len(listed["prefill"])
    assert sum("did not load ahead" in r.getMessage()
               for r in caplog.records) == len(broken)


def test_servers_of_one_process_read_their_own_entries(params, monkeypatch):
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "4")
    monkeypatch.setenv("SELDON_COMPILE_CACHE", "0")
    a, b = _server(params), _server(params)
    try:
        _serve(a, 1)
        _serve(b, 3)
        docs = [s.boot_document() for s in (a, b)]
    finally:
        a.stop()
        b.stop()
    assert a.boot_server != b.boot_server
    for srv, doc in zip((a, b), docs):
        assert [s["name"] for s in doc["spans"]].count("device_init") == 1
        assert doc["first_dispatch"]["missed"]["n"] == sum(
            map(len, srv._programs.values()))
        assert srv.snapshot()["programs"]["boot_load_s"] == 0.0


# -- the recorder alone -------------------------------------------------------


def test_union_counts_an_instant_once():
    assert _union_s([]) == 0.0
    assert _union_s([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]) == 3.0
    assert _union_s([(2.0, 3.0), (0.0, 5.0)]) == 5.0


def test_a_timeline_keeps_what_it_is_given_whole():
    tl = BootTimeline()
    t0 = tl.process_start
    assert 0.0 <= time.monotonic() - t0
    one, two = tl.server(), tl.server()
    tl.span("process", None, t0, t0 + 1.0)
    tl.span("device_init", None, t0 + 2.0, t0 + 4.0, one)
    tl.span("load", "device_init", t0 + 2.5, t0 + 4.0, one, note="x")
    tl.span("device_init", None, t0 + 10.0, t0 + 11.0, two)
    tl.program(one, {"kind": "decode", "shape": [1, 4], "trace_s": 0.25,
                     "load_s": 0.5, "from_cache": True})
    tl.first_dispatch(one, {"kind": "decode", "shape": [1, 4],
                            "loaded": True, "host_s": 0.125})
    tl.first_dispatch(two, {"kind": "decode", "shape": [2, 4],
                            "loaded": False, "host_s": 2.0,
                            "from_cache": True})
    assert tl.calls == 7
    assert tl.load_seconds(one) == (1.5, 0.25)
    assert tl.load_seconds(two) == (0.0, 0.0)
    doc = tl.document(one, serving_s=3.0, waiting_s=0.5)
    assert [s["name"] for s in doc["spans"]] == ["process", "device_init",
                                                 "load"]
    assert doc["spans"][2] == {"name": "load", "parent": "device_init",
                               "start_s": 2.5, "end_s": 4.0, "note": "x"}
    assert doc["accounted_s"] == 1.0 + 2.0 + 3.0 + 0.5
    assert doc["first_dispatch"] == {
        "loaded": {"n": 1, "host_s": 0.125},
        "missed": {"n": 0, "host_s": 0.0, "from_cache": 0}}
    assert tl.document(two)["first_dispatch"]["missed"] == {
        "n": 1, "host_s": 2.0, "from_cache": 1}
    assert [s["name"] for s in tl.document()["spans"]] == ["process"]
    json.dumps(doc)


def test_the_phase_of_a_boot_is_a_phase(monkeypatch):
    """``_BootPhase`` is ``_Phase`` (the profiler's annotation, ``into``'s
    seconds) and hands its two stamps on, raised through or not."""
    assert issubclass(gs_mod._BootPhase, gs_mod._Phase)
    got = []
    with pytest.raises(KeyError):
        with gs_mod._BootPhase("GenServer._init_device/x",
                               lambda *a: got.append(a), rows=1):
            time.sleep(0.01)
            raise KeyError("x")
    (start, end, hits), = got
    assert end - start >= 0.01 and hits == 0
