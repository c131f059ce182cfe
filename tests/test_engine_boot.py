"""Boot-time rules that keep a failure loud: the serving path either starts
the way it says or does not start.

  * a generator whose continuous-batching scheduler cannot be BUILT fails
    engine construction (the static lane is never a silent substitute);
  * the HTTP lane is chosen from the graph by a stated rule and named on
    the ``engine up:`` line — a generator takes the fast lane without any
    "unavailable" warning, and on a native-eligible graph a plane that
    cannot load is fatal;
  * ``chip_smoke.py`` never passes off-chip.
"""

import asyncio
import os
import signal
import socket
import subprocess
import sys

import pytest

from seldon_core_tpu.graph.defaulting import default_and_validate
from seldon_core_tpu.graph.spec import SeldonDeploymentSpec
from seldon_core_tpu.runtime import engine_main
from seldon_core_tpu.runtime.engine import EngineService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _example(name: str) -> SeldonDeploymentSpec:
    with open(os.path.join(ROOT, "examples", name)) as f:
        return default_and_validate(SeldonDeploymentSpec.from_json(f.read()))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_genserver_that_cannot_be_built_fails_engine_construction(
        monkeypatch):
    """A speculative generator cannot run under a disaggregated role
    (GenServer raises); the engine used to log it and serve the static
    per-request lane instead."""
    spec = _example("speculative_deployment.json")
    with pytest.raises(ValueError, match="does not compose"):
        EngineService(spec, gen_role="decode")
    # the one explicit way to the static lane still works
    monkeypatch.setenv("SELDON_TPU_GEN_CONTINUOUS", "0")
    assert EngineService(spec, gen_role="decode").genserver is None


def _serve_until_up(deployment, capsys):
    """Run ``engine_main.serve`` in-process until its ``engine up:`` line,
    then SIGTERM it through its own drain path.  Returns captured stdout."""
    out = []

    async def run():
        task = asyncio.ensure_future(engine_main.serve(
            deployment, host="127.0.0.1", rest_port=_free_port(),
            grpc_port=_free_port()))
        for _ in range(1200):
            await asyncio.sleep(0.05)
            out.append(capsys.readouterr().out)
            if task.done() or "engine up:" in "".join(out):
                break
        if not task.done():
            os.kill(os.getpid(), signal.SIGTERM)
        await asyncio.wait_for(task, 60)

    asyncio.run(run())
    out.append(capsys.readouterr().out)
    return "".join(out)


def test_generator_engine_up_line_names_the_fast_lane(capsys):
    log = _serve_until_up(_example("generator_deployment.json"), capsys)
    up = next(ln for ln in log.splitlines() if ln.startswith("engine up:"))
    assert " http=fast " in up and " grpc-lane=fast " in up, up
    assert "kernels=none" in up  # no Pallas kernel on this CPU backend
    # announced by rule, with the reason — not through a caught exception
    assert "http lane: fast — generator graph" in log
    assert "unavailable" not in log
    assert "engine stopped" in log


def test_native_plane_that_cannot_load_is_fatal_on_an_eligible_graph(
        capsys, monkeypatch):
    """The stub graph is native-eligible: when the plane cannot be built
    or loaded the engine must not come up on another lane."""
    from seldon_core_tpu.runtime import nativeplane

    monkeypatch.setattr(nativeplane, "_load", lambda: None)
    with pytest.raises(RuntimeError, match="could not be built"):
        _serve_until_up(_example("stub_deployment.json"), capsys)
    assert "engine up:" not in capsys.readouterr().out


def test_chip_smoke_never_passes_off_chip(tmp_path):
    """On the CPU the smoke exits non-zero at the platform check and
    prints no result line; alone in a directory it fails too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"ok"' not in out.stdout
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    out = subprocess.run([sys.executable, str(lone)], capture_output=True,
                         text=True, env=env, timeout=300, cwd=tmp_path)
    assert out.returncode != 0 and '"ok"' not in out.stdout


def test_chip_parents_do_not_import_jax():
    """One process per chip: chip_smoke.py's parent starts chip-needing
    children, so it may not load jax (it also asserts it at the end of a
    run; bench/run.py's parent is held to the same in tests/bench)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import chip_smoke; "
         "from seldon_core_tpu.runtime import compilecache, wire; "
         "sys.exit('jax' in sys.modules)"],
        capture_output=True, text=True, cwd=ROOT, timeout=120)
    assert out.returncode == 0, out.stderr
