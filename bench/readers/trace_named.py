"""Layer metrics from ANY stages a block names for itself in the profiler
trace: the ``jax.named_scope``s the metric's own formula lists, so a block
that brings a stage brings a data file and no reader
(``readers/trace_stages.py`` knows a fixed tuple of them).

    {"program": "decode" | "prefill", "scopes": ["<scope>", ...]}
        100 x the program's device self-seconds under the scopes over its
        device seconds;
    {"program": ..., "scopes": [...], "needs": "<function>"}
        a share of a roofline: the least time the chip could take for what
        ``archs/<arch>/needs.py`` ``<function>(config, rows, counters)``
        says ONE CALL of the program needs under those scopes
        (lib/roofline.py, lib/peaks.py), times the program's calls, over
        the device self-seconds under them.

The reduction is lib/trace_scopes.py's own (``reduce_scopes``), run once a
trace and a set of scopes, in a child, with the formula's scopes added to
the list it sorts a device op's scope path by — the innermost name it
knows wins.  It is kept beside the trace as ``named.<scopes>.json``.  A
run that was not traced, a trace without these scopes (a program from
before they existed: the parent of the PR that brought them) or a
reduction that fails gives None: the metric is left out, nothing raises."""

from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REDUCED: dict = {}          # (trace path, scopes) -> reduction


def reduction(path: str, scopes: tuple) -> dict:
    from lib.engine import EngineFailure, run_child

    key = (path, scopes)
    if key not in _REDUCED:
        try:
            red = run_child(os.path.dirname(BENCH_DIR),
                            [os.path.abspath(__file__), path, *scopes],
                            {"JAX_PLATFORMS": "cpu"}, 600.0)
            run_dir = path.split(os.sep + "profile" + os.sep)[0]
            with open(os.path.join(
                    run_dir, "named." + "+".join(scopes) + ".json"),
                    "w") as f:
                json.dump(red, f, indent=1)
        except (EngineFailure, OSError, ValueError,
                subprocess.TimeoutExpired) as e:
            red = {"error": str(e)[-500:]}
        _REDUCED[key] = red
    return _REDUCED[key]


def read(metric: dict, ctx: dict):
    from lib import roofline
    from lib.formula import deltas
    from lib.manifest import arch_module
    from lib.peaks import peaks_for
    from readers.trace_scopes import newest_trace

    if not ctx.get("trace"):
        return None
    path = newest_trace(ctx["cell"]["name"])
    if path is None:
        return None
    f = metric["formula"]
    red = reduction(path, tuple(sorted(f["scopes"])))
    prog = (red.get("programs") or {}).get(f["program"]) or {}
    by = prog.get("by_scope_s") or {}
    under = sum(by.get(k, 0.0) for k in f["scopes"])
    if not prog.get("module_s", 0.0) > 0 or not under > 0:
        return None
    if "needs" not in f:
        return 100.0 * under / prog["module_s"]
    rows = (ctx.get("traced") or {}).get("decode_rows_mean")
    if not rows:
        return None
    needs = arch_module(ctx["bench_dir"], ctx["config"], "needs")
    need = getattr(needs, f["needs"])(ctx["config"], rows, deltas(
        ctx.get("genperf_before") or {}, ctx.get("genperf_after") or {}))
    least = roofline.least_seconds(need, peaks_for(ctx["device"]["kind"]))
    ctx.setdefault("bounds", {})[metric["name"]] = least["bound"]
    return 100.0 * least["seconds"] * prog["calls"] / under


def stages(planes: list, scopes) -> dict:
    """lib/trace_scopes.py's reduction of ``planes`` (its own format) with
    ``scopes`` known beside its own: its list is a fixed tuple of the
    module, set for the length of the call."""
    from lib import trace_scopes

    known = trace_scopes.SCOPES
    trace_scopes.SCOPES = tuple(known) + tuple(
        s for s in scopes if s not in known)
    try:
        return trace_scopes.reduce_scopes(planes)
    finally:
        trace_scopes.SCOPES = known


if __name__ == "__main__":
    sys.path.insert(0, BENCH_DIR)
    from lib.trace_scopes import load_planes

    print(json.dumps(stages(load_planes(sys.argv[1]), sys.argv[2:])),
          flush=True)
