"""The program store (runtime/compilecache.py ``ProgramStore``): the
executables of a deployment's two paged programs kept beside the program
record under a key that needs no trace, and the table ``GenServer``
dispatches from (``_load``, ``_program``, ``_bring_up``).

Schedulers inside this process over a cache directory of the test's own;
``jax.clear_caches()`` between two of them stands for a new process."""

import logging
import os
import pickle
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_kinds  # tests/served_kinds.py, beside this file
from seldon_core_tpu.models.transformer import LMConfig, lm_init
from seldon_core_tpu.runtime import compilecache as cc_mod
from seldon_core_tpu.runtime.compilecache import (
    ProgramStore,
    package_digest,
    trace_environment,
)
from seldon_core_tpu.runtime.genserver import GenServer

CFG = LMConfig(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32)
PROMPTS = np.random.default_rng(53).integers(0, 48, size=(3, 7))
_LOWERED = "jaxpr_to_mlir_module_duration"


@pytest.fixture(scope="module")
def params():
    return lm_init(jax.random.key(5), CFG)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """JAX's persistent cache configured with a directory of the test's
    own, as ``enable_compile_cache()`` configures it in an engine (the
    CPU's quick compiles stay out of it: a program a boot compiles is
    compiled, not fetched).  The prefill chunk is pinned, so which shapes
    a server dispatches is arithmetic, not timing."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "4")
    monkeypatch.delenv("SELDON_COMPILE_CACHE", raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = [getattr(jax.config, name) for name in names]
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 1e9)    # however loaded the machine is
    compilation_cache.reset_cache()
    jax.clear_caches()      # what an earlier test compiled is in no file
    yield tmp_path
    for name, value in zip(names, before):
        jax.config.update(name, value)
    compilation_cache.reset_cache()


_PROGRAMS = ("jit(paged_forward)", "jit(paged_decode_round)")


@pytest.fixture
def lowerings():
    """The thread of every lowering of one of the two paged programs while
    the test runs (JAX's own monitoring event), to clear and to count."""
    import threading

    import jax.monitoring
    from jax._src import monitoring

    names: list = []

    def on_duration(name, secs, fun_name="", **kw):
        if name.endswith(_LOWERED) and fun_name in _PROGRAMS:
            names.append(threading.current_thread().name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    yield names
    monitoring.unregister_event_duration_listener(on_duration)


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


def _serve(srv, prompts=PROMPTS):
    """Three rows together, then one alone: several shapes of each
    program.  Returns every token served."""
    out = [srv.submit(prompts.astype(float)).future.result(timeout=240),
           srv.submit(prompts[:1].astype(float)).future.result(timeout=240)]
    _settle(srv)
    return [np.asarray(o).tolist() for o in out]


def _boot_and_serve(build, serve=_serve):
    """One boot: its tokens, its settled ``programs`` block and its boot
    timeline's ``programs`` entries.  ``stop`` waits for the store."""
    srv = build()
    try:
        got = serve(srv)
        return got, _settle(srv)["programs"], srv.boot_document()["programs"]
    finally:
        srv.stop()


def _stored(cache_dir):
    return sorted(cache_dir.glob("genserver-program-*"))


# -- a cold boot stores what it compiles; the boot after it traces nothing ---


def _kind_server(kind):
    if kind == "sampled":       # a round that draws: per-row keys ride it
        return lambda: GenServer(
            lm_init(jax.random.key(5), CFG), CFG, max_new_tokens=10,
            block_size=4, num_blocks=64, slots=8, span=3, prefill_chunk=4,
            temperature=0.8, top_k=8, seed=11)
    unit = served_kinds.unit_of(kind)
    state = unit.init_state(None)
    return lambda: served_kinds.server_of(kind, unit, state)


def _kind_serve(kind):
    if kind == "sampled":
        return _serve

    def serve(srv):
        out = [srv.submit(served_kinds.prompts([6, 6], 11),
                          max_new=9).future.result(timeout=240),
               srv.submit(served_kinds.prompts([19], 12),
                          max_new=7).future.result(timeout=240)]
        _settle(srv)
        return [np.asarray(o).tolist() for o in out]
    return serve


@pytest.mark.parametrize("kind", ["sampled", *served_kinds.KINDS])
def test_the_boot_after_a_cold_one_traces_nothing_and_serves_the_same_tokens(
        kind, cache_dir, lowerings, monkeypatch):
    """Every kind of generator the cells serve, and a round that samples:
    the first boot compiles each shape when a request first needs it and
    stores it; the second reads ``stored`` true on every entry, Σ
    ``trace_s`` 0.0, ``missed`` 0, lowers no program, and its ticks -- which
    call a ``Compiled``, which converts nothing -- hand it arguments it
    takes: the same tokens."""
    monkeypatch.setenv("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", "8")
    build, serve = _kind_server(kind), _kind_serve(kind)
    want, cold, entries = _boot_and_serve(build, serve)
    n = cold["prefill"] + cold["decode"]
    assert cold["missed"] == n >= 2 and entries == []
    assert (cold["loaded_at_boot"], cold["stored_at_boot"]) == (0, 0)
    n_stored = len(_stored(cache_dir))
    # (XLA:CPU serialises no sort comparator: a program that sorts -- a
    # retention layer's prefill, now and then a top-k -- is traced by every
    # boot here; on the chip every program of every cell is stored)
    assert n_stored == n or (kind != "attention" and 0 < n_stored < n)
    jax.clear_caches()                          # as a new process would be
    del lowerings[:]
    got, warm, entries = _boot_and_serve(build, serve)
    assert got == want
    assert len(entries) == n and sum(e["stored"] for e in entries) == n_stored
    for e in entries:
        assert e["load_s"] > 0.0 and (e["trace_s"] == 0.0) == e["stored"], e
        assert e["from_cache"] is e["stored"]
    assert (warm["loaded_at_boot"], warm["stored_at_boot"]) == (n, n_stored)
    assert warm["missed"] == 0 and len(lowerings) == n - n_stored
    if n_stored == n:
        assert warm["boot_trace_s"] == 0.0


def test_the_pool_is_still_donated_through_a_table_dispatch(
        params, cache_dir):
    """A stored executable carries the donation it was compiled with: the
    pool a dispatch was handed is gone after it, as after a ``jit`` call."""
    _boot_and_serve(lambda: _server(params))
    jax.clear_caches()
    srv = _server(params)
    try:
        srv._ensure_device()
        assert len(srv._executables) == len(_stored(cache_dir)) > 0
        before = jax.tree_util.tree_leaves(srv._pool)
        assert not any(x.is_deleted() for x in before)
        _serve(srv)
        assert all(x.is_deleted() for x in before)
        assert srv.snapshot()["programs"]["missed"] == 0
    finally:
        srv.stop()


def test_a_shape_the_record_never_saw_is_brought_up_once_and_stored(
        params, cache_dir, lowerings):
    """... by the tick that first needs it, on the scheduler thread, booked
    ``missed`` as ever; the boot after it loads that one from the store too."""
    build = lambda: _server(params)                       # noqa: E731
    one = lambda srv: _serve(srv, PROMPTS[:1])            # noqa: E731
    _, few, _ = _boot_and_serve(build, one)
    n_few = few["prefill"] + few["decode"]
    jax.clear_caches()
    del lowerings[:]
    want, more, entries = _boot_and_serve(build)
    n = more["prefill"] + more["decode"]
    assert more["stored_at_boot"] == n_few == len(entries)
    assert more["missed"] == n - n_few > 0
    assert set(lowerings) == {"genserver"}      # the scheduler's own thread
    assert len(lowerings) >= more["missed"]
    assert len(_stored(cache_dir)) == n
    jax.clear_caches()
    got, third, _ = _boot_and_serve(build)
    assert got == want
    assert (third["stored_at_boot"], third["missed"]) == (n, 0)


# -- what does not load, what cannot be written -------------------------------


@pytest.mark.parametrize("damage", ["truncated", "garbage", "another_pickle"])
def test_a_damaged_file_takes_the_traced_path_with_one_warning_and_is_removed(
        params, cache_dir, damage, caplog):
    build = lambda: _server(params)                       # noqa: E731
    want, cold, _ = _boot_and_serve(build)
    n = cold["prefill"] + cold["decode"]
    victim = _stored(cache_dir)[0]
    body = victim.read_bytes()
    victim.write_bytes({
        "truncated": body[:len(body) // 2], "garbage": b"\x00\xffprogram",
        "another_pickle": pickle.dumps({"not": "a program"})}[damage])
    jax.clear_caches()
    with caplog.at_level(logging.WARNING):
        got, warm, entries = _boot_and_serve(build)
    warned = [r.getMessage() for r in caplog.records
              if "stored program" in r.getMessage()]
    assert len(warned) == 1 and str(victim) in warned[0]
    assert got == want
    traced = [e for e in entries if not e["stored"]]
    assert len(traced) == 1 and traced[0]["trace_s"] > 0.0
    assert (warm["loaded_at_boot"], warm["stored_at_boot"]) == (n, n - 1)
    assert warm["missed"] == 0
    # compiled in its place, and stored anew, whole
    assert _stored(cache_dir)[0] == victim and victim.read_bytes() != body
    assert len(pickle.loads(victim.read_bytes())) == 3


def test_an_unwritable_directory_stores_nothing_and_serves(
        params, cache_dir, caplog, monkeypatch):
    """One warning ends the storing, as one ends the recording; the
    executables a request compiled stay in the table."""
    def replace(src, dst):
        raise PermissionError(13, "read-only file system", dst)

    monkeypatch.setattr(cc_mod.os, "replace", replace)
    srv = _server(params)
    try:
        with caplog.at_level(logging.WARNING):
            got = _serve(srv)
            again = _serve(srv)
            assert len(srv._executables) == sum(
                map(len, srv._programs.values())) >= 4
    finally:
        srv.stop()
    assert got == again
    warned = [r.getMessage() for r in caplog.records
              if "not stored" in r.getMessage()]
    assert len(warned) == 1 and "stores no more" in warned[0]
    assert not [p for p in _stored(cache_dir) if p.suffix == ".pkl"]


@pytest.fixture(scope="module")
def compiled():
    return jax.jit(lambda x: x * 2.0 + 1.0).lower(
        np.ones((4,), np.float32)).compile()


def _store(directory, identity="dep", **kw):
    return ProgramStore(str(directory), identity, jax.devices()[:1], **kw)


def test_a_store_round_trips_an_executable(tmp_path, compiled, caplog):
    store = _store(tmp_path)
    path = store.path("decode", (4, 8), {"span": 3})
    with caplog.at_level(logging.WARNING):
        assert store.load(path) is None         # absent: in silence
    assert not caplog.records
    assert store.save(path, compiled)
    assert store.files() == [path] == [str(p) for p in tmp_path.iterdir()]
    loaded = _store(tmp_path).load(path)
    np.testing.assert_array_equal(
        loaded(np.arange(4, dtype=np.float32)), [1.0, 3.0, 5.0, 7.0])
    # a directory that is not there: False, one warning, and no more tries
    absent = _store(tmp_path / "absent")
    with caplog.at_level(logging.WARNING):
        assert not absent.save(absent.path("decode", (4, 8), {}), compiled)
        assert not absent.save(absent.path("decode", (2, 8), {}), compiled)
    assert len([r for r in caplog.records
                if "not stored" in r.getMessage()]) == 1


def test_writing_removes_the_same_deployments_files_of_other_package_digests(
        tmp_path, compiled):
    """The directory holds one copy a deployment, not one a source change;
    another deployment's files are nobody else's to remove."""
    old = _store(tmp_path, package="a" * 16)
    old_paths = [old.path("decode", (rows, 8), {}) for rows in (1, 2)]
    other = _store(tmp_path, identity="another", package="a" * 16)
    other_path = other.path("decode", (1, 8), {})
    for store, path in [(old, old_paths[0]), (old, old_paths[1]),
                        (other, other_path)]:
        assert store.save(path, compiled)
    new = _store(tmp_path, package="b" * 16)
    new_path = new.path("decode", (1, 8), {})
    assert new_path not in old_paths and new.load(new_path) is None
    assert sorted(new.files()) == sorted(old_paths)
    assert new.save(new_path, compiled)
    assert new.files() == [new_path]
    assert sorted(map(str, tmp_path.iterdir())) == sorted(
        [new_path, other_path])


def test_a_boot_sweeps_other_digests_files_before_any_write(
        tmp_path, compiled, caplog, monkeypatch):
    """... so a boot that stores nothing (every program loaded, or handed
    over by a cache whose executables do not serialise again) leaves no
    copy of a package that is gone.  A file that cannot be removed ends
    the storing with one warning."""
    old = _store(tmp_path, package="a" * 16)
    old_path = old.path("decode", (1, 8), {})
    assert old.save(old_path, compiled)
    new = _store(tmp_path, package="b" * 16)
    new.sweep()
    assert not new.files()
    assert old.save(old_path, compiled)     # (it swept long ago)
    new.sweep()                             # once a process
    assert new.files() == [old_path]

    def remove(path):
        raise PermissionError(13, "read-only file system", path)

    third = _store(tmp_path, package="c" * 16)
    monkeypatch.setattr(cc_mod.os, "remove", remove)
    with caplog.at_level(logging.WARNING):
        third.sweep()
        assert not third.save(third.path("decode", (1, 8), {}), compiled)
    assert len([r for r in caplog.records
                if r.levelno == logging.WARNING]) == 1
    assert third.files() == [old_path]


def test_the_digest_is_taken_when_the_module_is_loaded():
    """... not when a store is built, twenty seconds into a boot: files
    replaced in place meanwhile must not lend their digest to executables
    of the code that is running."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "from seldon_core_tpu.runtime import compilecache as c;"
         "print(c.package_digest.cache_info().currsize, c.package_digest())"],
        capture_output=True, text=True, timeout=120, check=True,
        cwd=os.path.dirname(cc_mod._PACKAGE_DIR),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.split() == ["1", package_digest()]
    assert _store("nowhere")._package == package_digest()


# -- the key: no trace goes into it, and any edit to the package moves it ----


@pytest.fixture(scope="module")
def package_copy(tmp_path_factory):
    """The installed package's files, copied."""
    root = tmp_path_factory.mktemp("package") / "seldon_core_tpu"
    shutil.copytree(cc_mod._PACKAGE_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    return root


def _edit(root, edit):
    """Apply ``edit`` to the copy at ``root``; returns how to undo it."""
    if edit == "one_byte_of_a_kernel":
        path = root / "ops" / "paged_attention.py"
    elif edit == "one_byte_of_a_module_no_program_imports":
        path = root / "gateway" / "fleet.py"
    elif edit == "a_new_file":
        path = root / "models" / "new_module.py"
        path.write_text("")
        return path.unlink
    elif edit == "a_file_moved":
        path = root / "utils" / "genperf.py"
        moved = path.with_name("genperf2.py")
        path.rename(moved)
        return lambda: moved.rename(path)
    else:
        raise AssertionError(edit)
    body = path.read_bytes()
    i = len(body) // 2
    path.write_bytes(body[:i] + bytes([body[i] ^ 1]) + body[i + 1:])
    return lambda: path.write_bytes(body)


@pytest.mark.parametrize("edit", [
    "one_byte_of_a_kernel", "one_byte_of_a_module_no_program_imports",
    "a_new_file", "a_file_moved"])
def test_one_changed_byte_anywhere_in_the_package_is_another_key(
        package_copy, edit, tmp_path):
    """So no later change to the source is ever run -- or measured -- on
    the executable its parent compiled."""
    assert package_digest(str(package_copy)) == package_digest()
    undo = _edit(package_copy, edit)
    try:
        package_digest.cache_clear()
        changed = package_digest(str(package_copy))
    finally:
        undo()
        package_digest.cache_clear()
    assert changed != package_digest()
    assert package_digest(str(package_copy)) == package_digest()
    ours, theirs = _store(tmp_path), _store(tmp_path, package=changed)
    key = ("decode", (4, 8), {"span": 8})
    assert ours.path(*key) != theirs.path(*key)
    assert theirs.path(*key) not in ours.files()


def test_what_the_interpreter_derives_is_no_part_of_the_package(package_copy):
    cache = package_copy / "runtime" / "__pycache__"
    cache.mkdir()
    try:
        (cache / "genserver.cpython-312.pyc").write_bytes(b"derived")
        (package_copy / "stray.pyc").write_bytes(b"derived")
        package_digest.cache_clear()
        assert package_digest(str(package_copy)) == package_digest()
    finally:
        shutil.rmtree(cache)
        (package_copy / "stray.pyc").unlink()
        package_digest.cache_clear()


_TOOLCHAIN = {"jax": "0.9.0", "jaxlib": "0.9.0", "platform": "tpu",
              "platform_version": "libtpu 0.0.34", "device_kind": "TPU v5 lite",
              "device_count": 1, "devices": [0]}
_KEY = dict(identity="dep", kind="decode", shape=(4, 8),
            statics={"span": 8, "temperature": 0.0, "inplace": True},
            toolchain=_TOOLCHAIN, environ={"SELDON_TPU_GEN_SPAN": "8"})


def _path(tmp_path, **change):
    k = {**_KEY, **change}
    store = ProgramStore(str(tmp_path), k["identity"], jax.devices()[:1],
                         package="p" * 16, toolchain=k["toolchain"],
                         environ=k["environ"])
    return store.path(k["kind"], k["shape"], k["statics"])


@pytest.mark.parametrize("change", [
    {"identity": "another deployment"},
    {"kind": "prefill"},
    {"shape": (8, 8)},
    {"statics": {**_KEY["statics"], "span": 4}},
    {"statics": {**_KEY["statics"], "inplace": "interpret"}},
    {"statics": {**_KEY["statics"], "fused": True}},
    {"environ": {"SELDON_TPU_GEN_SPAN": "4"}},
    {"environ": {**_KEY["environ"], "SELDON_TPU_GRAPH_FUSE": "0"}},
    {"environ": {**_KEY["environ"], "XLA_FLAGS": "--xla_dump_to=/tmp/x"}},
    {"environ": {**_KEY["environ"], "LIBTPU_INIT_ARGS": "--flag"}},
    {"environ": {**_KEY["environ"], "JAX_DEFAULT_MATMUL_PRECISION": "highest"}},
    {"toolchain": {**_TOOLCHAIN, "jaxlib": "0.9.1"}},
    {"toolchain": {**_TOOLCHAIN, "jax": "0.9.1"}},
    {"toolchain": {**_TOOLCHAIN, "platform_version": "libtpu 0.0.35"}},
    {"toolchain": {**_TOOLCHAIN, "device_kind": "TPU v6 lite"}},
    {"toolchain": {**_TOOLCHAIN, "device_count": 4}},
], ids=["identity", "kind", "shape", "span", "inplace", "fused",
        "seldon_tpu_value", "seldon_tpu_name", "xla_flags", "libtpu_init_args",
        "jax_name", "jaxlib", "jax", "platform_version", "device_kind",
        "device_count"])
def test_the_key_moves_with_everything_a_program_is_made_of(tmp_path, change):
    assert _path(tmp_path, **change) != _path(tmp_path)
    assert _path(tmp_path, **change) == _path(tmp_path, **change)


@pytest.mark.parametrize("name", [
    "SELDON_TPU_PROFILE_DIR", "JAX_COMPILATION_CACHE_DIR",
    "JAX_COMPILATION_CACHE_MAX_SIZE", "SELDON_COMPILE_CACHE", "HOME",
    "BENCH_RUN"])
def test_where_files_go_is_no_part_of_the_key(tmp_path, name):
    """A harness that gives every run a profile directory of its own (and
    the cache's own placement and size) must still boot warm."""
    environ = {**_KEY["environ"], name: "/somewhere/else/7"}
    assert name not in trace_environment(environ)
    assert _path(tmp_path, environ=environ) == _path(tmp_path)


def test_the_environment_of_a_key_is_read_from_the_process(monkeypatch):
    monkeypatch.setenv("SELDON_TPU_GEN_SPAN", "5")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    env = trace_environment()
    assert env["SELDON_TPU_GEN_SPAN"] == "5" and "XLA_FLAGS" in env
    assert all(k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
               or k.startswith(("SELDON_TPU_", "JAX_")) for k in env)
    assert not [k for k in env if k.endswith("_DIR")]
    assert os.environ.get("HOME") is None or "HOME" not in env
