"""Layer metrics from the traced calls' own work (lib/trace_calls.py): a
program's share of its roofline, counted call by call.

    {"program": "decode" | "prefill", "needs": "<function>"}
        sum over the joined whole calls of the program of the least time
        the chip could take for what ``archs/<arch>/needs.py`` says THAT
        call needs, over the sum of their device seconds:
          decode_step(config, real_rows, kv_positions / passes, counters)
                                                        x the round's span
          prefill(config, 1, tokens, attended, counters)
    {"program": "decode", "needs": "<function>", "scopes": ["<stage>"]}
        ``<function>(config, real_rows, counters)`` of each joined call
        over its device self-seconds under the named stages
    {"value": "<key of the reduction>"}
        ``joined_share``: the whole module events joined to their
        arguments over all whole ones, the guard of the three above

Every number of a call comes from the span that dispatched it; ``counters``
is built from the call's own ``experts_read`` (its ``.../emit`` span) over
its ``expert_slots``.  Where the trace stopped before a call's ``.../emit``
the window's ``/genperf`` mean share of its slots stands in and the call
is marked ``experts_read_from: "window"``.  The least time is taken a
call, so each is held to its own bound.

The reduction runs once a trace, in a child, and is kept beside the trace
as ``calls.json``.  A run that was not traced, a trace whose spans carry
no ``seq`` (a program from before they did) or a reduction that fails
gives None: the metric is left out, nothing raises."""

from __future__ import annotations

import json
import os
import subprocess
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REDUCED: dict = {}          # trace path -> reduction


def stand_in(red: dict, before: dict, after: dict) -> None:
    """Mark where a call's own count of the experts it read came from; a
    call with expert slots whose ``.../emit`` the trace does not hold gets
    the window's mean share of them."""
    from lib.formula import delta

    for call in red.get("calls") or []:
        if not call.get("expert_slots"):
            continue
        if call.get("experts_read") is not None:
            call["experts_read_from"] = "emit"
            continue
        served = "served_" + call["kind"]
        slots = delta(before, after, served + ".expert_slots")
        read = delta(before, after, served + ".experts_read")
        if slots and read is not None:
            call["experts_read"] = call["expert_slots"] * read / slots
            call["experts_read_from"] = "window"


def reduction(path: str, ctx: dict) -> dict:
    from lib.engine import EngineFailure, run_child

    if path not in _REDUCED:
        try:
            t0 = time.monotonic()
            red = run_child(
                os.path.dirname(BENCH_DIR),
                [os.path.join(BENCH_DIR, "lib", "trace_calls.py"), path],
                {"JAX_PLATFORMS": "cpu"}, 600.0)
            red["reduce_s"] = time.monotonic() - t0     # what a traced run
            #                                             pays for this file
            stand_in(red, ctx.get("genperf_before") or {},
                     ctx.get("genperf_after") or {})
            run_dir = path.split(os.sep + "profile" + os.sep)[0]
            with open(os.path.join(run_dir, "calls.json"), "w") as f:
                json.dump(red, f, indent=1)
        except (EngineFailure, OSError, ValueError,
                subprocess.TimeoutExpired) as e:
            red = {"error": str(e)[-500:]}
        _REDUCED[path] = red
    return _REDUCED[path]


def counters_of(call: dict) -> dict:
    """What ``needs`` reads the experts of ONE call from: the call's own
    count over its own slots (nothing for a block without experts)."""
    if not call.get("expert_slots") or call.get("experts_read") is None:
        return {}
    return {"served_" + call["kind"]: {
        "expert_slots": call["expert_slots"],
        "experts_read": call["experts_read"]}}


def need_of(needs, function: str, call: dict, ctx: dict):
    """Bytes and FLOPs of one call by the architecture's own arithmetic,
    or None where the block has no such function or the call no work."""
    fn = getattr(needs, function, None)
    if fn is None or not call.get("real_rows"):
        return None
    counters = counters_of(call)
    if function == "prefill":
        if not call.get("tokens"):
            return None
        return fn(ctx["config"], 1, call["tokens"], call["attended"],
                  counters)
    if function == "decode_step":
        if not call.get("passes"):
            return None
        span = ctx["deployment"]["span"]
        step = fn(ctx["config"], call["real_rows"],
                  call["kv_positions"] / call["passes"], counters)
        return {key: value * span for key, value in step.items()}
    return fn(ctx["config"], call["real_rows"], counters)


def share(calls: list, function: str, scopes, ctx: dict):
    """``(100 x sum of the calls' least seconds / sum of their device
    seconds, the bound that sets most of the least seconds)``."""
    from lib import roofline
    from lib.manifest import arch_module
    from lib.peaks import peaks_for

    needs = arch_module(ctx["bench_dir"], ctx["config"], "needs")
    peaks = peaks_for(ctx["device"]["kind"])
    least_s = device_s = 0.0
    by_bound: dict = {}
    for call in calls:
        need = need_of(needs, function, call, ctx)
        under = (sum((call.get("stage_s") or {}).get(k, 0.0) for k in scopes)
                 if scopes else call["device_s"])
        if need is None or not under > 0:
            continue
        least = roofline.least_seconds(need, peaks)
        least_s += least["seconds"]
        device_s += under
        by_bound[least["bound"]] = (by_bound.get(least["bound"], 0.0)
                                    + least["seconds"])
    if not device_s > 0:
        return None, None
    return 100.0 * least_s / device_s, max(by_bound, key=by_bound.get)


def read(metric: dict, ctx: dict):
    from readers.trace_scopes import newest_trace

    if not ctx.get("trace"):
        return None
    path = newest_trace(ctx["cell"]["name"])
    if path is None:
        return None
    red, f = reduction(path, ctx), metric["formula"]
    if "value" in f:
        value = red.get(f["value"])
        return float(value) if isinstance(value, (int, float)) else None
    calls = [c for c in red.get("calls") or []
             if c["kind"] == f["program"]]
    value, bound = share(calls, f["needs"], f.get("scopes"), ctx)
    if value is not None:
        ctx.setdefault("bounds", {})[metric["name"]] = bound
    return value
