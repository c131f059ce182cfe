"""The engine child: ``python -m seldon_core_tpu.runtime.engine_main --file
<deployment>`` as its own process (the entry point a user runs), its log
under the run's output directory.  The process handling is chip_smoke.py's:
the parent never imports JAX, one engine at a time, its whole session is
killed when it stops."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

from .manifest import ManifestError


class EngineFailure(Exception):
    pass


def raise_stack_limit() -> None:
    """Run in a child before exec: a 1 GiB stack limit, which glibc also
    takes as the default stack of every thread the child starts.  XLA's
    TPU compiler overflows the usual 8 MiB thread stack on the 20- and
    30-layer unrolled paged programs (first chip run, PR 23: SIGSEGV
    "stack overflow" in a compiling thread), and the engine compiles on
    its scheduler thread."""
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_STACK)
    want = 1 << 30
    if hard != resource.RLIM_INFINITY:
        want = min(want, hard)
    if soft == resource.RLIM_INFINITY or soft >= want:
        return
    resource.setrlimit(resource.RLIMIT_STACK, (want, hard))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_PARAM_TYPES = {int: "INT", float: "FLOAT", str: "STRING", bool: "BOOL"}


def unit_spec(config: dict, dep: dict, seed: int, max_new: int) -> dict:
    """The generator component a run serves: the configuration's ``unit``
    section — ``class_path`` and ``parameters``, a map from the unit's
    keyword to ``{"from": "<published key of the same file>"}`` or a
    literal — and after it only what a run owns: the answer's length cap,
    the seed of the weights, and sampling and dtype from the deployment."""
    unit = config["unit"]
    params = {}
    for keyword, value in unit["parameters"].items():
        if isinstance(value, dict):
            if list(value) != ["from"] or value["from"] not in config:
                raise KeyError(
                    f"config {config.get('name')!r}: unit parameter "
                    f"{keyword!r} names {value!r}, not a key of the file")
            value = config[value["from"]]
        if type(value) not in _PARAM_TYPES:
            raise ManifestError(
                f"config {config.get('name')!r}: unit parameter {keyword!r} "
                f"is a {type(value).__name__}; a deployment document "
                "carries INT, FLOAT, STRING and BOOL only (bench/README.md: "
                "a unit derives a list from the published scalars)")
        params[keyword] = value
    params.update({
        "max_new_tokens": max_new, "seed": int(seed) % (2 ** 31 - 1),
        "temperature": dep["temperature"], "eos_token": dep["eos_token"],
        "dtype": dep["dtype"]})
    return {
        "class_path": unit["class_path"],
        "parameters": [
            {"name": k, "value": str(v), "type": _PARAM_TYPES[type(v)]}
            for k, v in params.items()]}


def deployment_doc(config: dict, dep: dict, seed: int, max_new: int) -> dict:
    """The SeldonDeployment a run boots: one in-process generator, the
    unit the configuration names, weights from ``seed``."""
    return {
        "apiVersion": "machinelearning.seldon.io/v1alpha2",
        "kind": "SeldonDeployment",
        "metadata": {"name": config["name"]},
        "spec": {"name": config["name"], "predictors": [{
            "name": "main", "replicas": 1,
            "components": [{
                "name": "gen", "runtime": "inprocess",
                **unit_spec(config, dep, seed, max_new),
            }],
            "graph": {"name": "gen", "type": "MODEL", "children": []},
        }]},
    }


def cache_env(repo: str) -> dict:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, unbounded: a cell's ~100 programs are ~200 MB, and under a
    size cap an LRU cache that is scanned in order never hits (my chip
    runs, PR 23: the chip tool's 200 MB cache re-compiled all 96 paged
    programs in every run)."""
    return {"JAX_COMPILATION_CACHE_DIR": os.path.join(repo, ".xla_cache"),
            "JAX_COMPILATION_CACHE_MAX_SIZE": "-1"}


def engine_env(dep: dict, profile_dir: str) -> dict:
    """The deployment's scheduler settings as the environment the program
    reads them from.  The prefill chunk is PINNED (floor == ceiling): the
    adaptive chunk would make the compiled program set depend on timing."""
    return {
        "SELDON_TPU_GEN_BLOCK_SIZE": str(dep["block_size"]),
        "SELDON_TPU_GEN_POOL_BLOCKS": str(dep["pool_blocks"]),
        "SELDON_TPU_GEN_SLOTS": str(dep["slots"]),
        "SELDON_TPU_GEN_SPAN": str(dep["span"]),
        "SELDON_TPU_GEN_PREFILL_CHUNK": str(dep["prefill_chunk"]),
        "SELDON_TPU_GEN_PREFILL_CHUNK_MAX": str(
            dep.get("prefill_chunk_max", dep["prefill_chunk"])),
        "SELDON_TPU_PROFILE_DIR": profile_dir,
        "ENGINE_DISPATCH_TIMEOUT_S": "900",
        "ENGINE_SHUTDOWN_DRAIN_S": "2",
    }


class Engine:
    def __init__(self, repo: str, deployment_path: str, env: dict,
                 log_path: str, boot_timeout_s: float = 900.0):
        self.port = free_port()
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.runtime.engine_main",
             "--file", deployment_path, "--host", "127.0.0.1",
             "--rest-port", str(self.port),
             "--grpc-port", str(free_port())],
            stdout=self._log, stderr=subprocess.STDOUT, cwd=repo,
            env={**os.environ, **env}, start_new_session=True,
            preexec_fn=raise_stack_limit,
        )
        self.up_line = ""
        deadline = self.t_spawn + boot_timeout_s
        try:
            while not self.up_line:
                for line in self.log_text().splitlines():
                    if line.startswith("engine up:"):
                        self.up_line = line
                if self.up_line:
                    break
                if self.proc.poll() is not None:
                    raise EngineFailure(
                        f"engine exited at boot (code {self.proc.returncode})")
                if time.monotonic() > deadline:
                    raise EngineFailure(
                        f"engine not up after {boot_timeout_s:.0f}s")
                time.sleep(0.1)
        except BaseException:
            self.stop()
            raise
        self.boot_s = time.monotonic() - self.t_spawn

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def log_tail(self, n: int = 60) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                pass
        try:  # the whole session: nothing the engine started outlives it
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        if not self._log.closed:
            self._log.close()


def compile_counters(stats: dict) -> dict:
    """Backend compiles so far (``seldon_tpu_compile_seconds`` count and
    sum) and the persistent cache's hits and misses."""
    tel = stats["telemetry"]
    cs = tel["perf"]["compile_s"]
    ev = tel["compile_cache_events"]
    return {"compiles": int(cs["count"]),
            "compile_s": float(cs["count"] * cs["mean"]),
            "cache_hits": int(ev.get("hit", 0)),
            "cache_misses": int(ev.get("miss", 0))}


def run_child(repo: str, argv: list, env: dict, timeout: float) -> dict:
    """Run a child that prints one JSON object as its last stdout line (the
    numerics child, the pre-compile child, the trace reduction)."""
    out = subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True, cwd=repo,
        env={**os.environ, **env}, timeout=timeout,
        preexec_fn=raise_stack_limit)
    if out.returncode != 0:
        raise EngineFailure(
            f"child {argv[0]} exited {out.returncode}:\n"
            f"{out.stderr[-3000:]}")
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as e:
        raise EngineFailure(
            f"child {argv[0]} printed no result: {e}\n{out.stdout[-1000:]}"
            f"\n{out.stderr[-2000:]}") from e
