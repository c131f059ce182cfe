"""Layer metrics from the program's own spans in the profiler trace
(lib/trace_scopes.py): device-idle seconds by scheduler phase, the slack
inside a fenced decode dispatch, a program's device time by stage.

    {"value": "<key of the reduction>"}
    {"program": "decode" | "prefill", "scopes": ["<jax.named_scope>", ...]}

The second is the share of one program's device time under the named
scopes, 100 x sum of ``programs.<program>.by_scope_s[scope]`` over its
``module_s``: a block that names its own stages (``experts``, ``router``)
asks for their share with a data file.

run.py hands a reader no path to the trace, so this one finds the
window's ``*.xplane.pb`` itself: the newest under ``bench/out/<cell
name>.*.t1/profile/`` (run.py empties a run's directory when it starts).
The reduction runs once per process, in a child with ``JAX_PLATFORMS=cpu``,
and is kept beside the trace as ``scopes.json`` (the side file of a traced
run: idle seconds by every leaf annotation, device seconds by every scope,
per program, and what the window added to ``/genperf`` ``requests`` — the
four stages beside the ``ttft_s`` they sum to).  A run that was not traced,
a trace without the annotations or the scopes (a program from before they
existed), or a reduction that fails gives None: the metric is left out,
nothing raises."""

from __future__ import annotations

import glob
import json
import os
import subprocess

from lib.engine import EngineFailure, run_child
from lib.formula import delta
from lib.trace_scopes import scope_share

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REDUCED: dict = {}          # trace path -> reduction


def newest_trace(cell_name: str, out_dir: str = ""):
    found = glob.glob(os.path.join(
        out_dir or os.path.join(BENCH_DIR, "out"),
        glob.escape(cell_name) + ".*.t1", "profile", "**", "*.xplane.pb"),
        recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def requests_window(before: dict, after: dict) -> dict:
    """The window delta of ``/genperf`` ``requests``: counts, ``ttft_s``,
    the four stages and their sum over ``ttft_s`` (1 but for the requests
    in flight at either end of the window)."""
    out = {k: delta(before, after, "requests." + k) for k in (
        "streams", "admitted", "first_tokens", "ttft_s", "stage_s.lane_in",
        "stage_s.queue", "stage_s.prefill", "stage_s.lane_out")}
    if out["ttft_s"]:
        out["stages_over_ttft"] = sum(
            v or 0.0 for k, v in out.items()
            if k.startswith("stage_s.")) / out["ttft_s"]
    return out


def reduction(path: str, ctx: dict) -> dict:
    if path not in _REDUCED:
        try:
            red = run_child(
                os.path.dirname(BENCH_DIR),
                [os.path.join(BENCH_DIR, "lib", "trace_scopes.py"), path],
                {"JAX_PLATFORMS": "cpu"}, 600.0)
            red["requests_window"] = requests_window(
                ctx.get("genperf_before") or {},
                ctx.get("genperf_after") or {})
            run_dir = path.split(os.sep + "profile" + os.sep)[0]
            with open(os.path.join(run_dir, "scopes.json"), "w") as f:
                json.dump(red, f, indent=1)
        except (EngineFailure, OSError, ValueError,
                subprocess.TimeoutExpired) as e:
            red = {"error": str(e)[-500:]}
        _REDUCED[path] = red
    return _REDUCED[path]


def read(metric: dict, ctx: dict):
    if not ctx.get("trace"):
        return None
    path = newest_trace(ctx["cell"]["name"])
    if path is None:
        return None
    red, formula = reduction(path, ctx), metric["formula"]
    if "scopes" in formula:
        return scope_share(
            (red.get("programs") or {}).get(formula["program"]) or {},
            formula["scopes"])
    value = red.get(formula["value"])
    return float(value) if isinstance(value, (int, float)) else None
