"""From a profiler trace (``*.xplane.pb``) to what the program's own spans
say about it: every device-idle second under the scheduler phase that was
open (``jax.profiler.TraceAnnotation``, runtime/genserver.py ``_Phase``),
how much of a fenced decode dispatch is not device time, and the device
seconds of each stage of the paged programs (``jax.named_scope``,
models/generate.py).

Runs in a child with ``JAX_PLATFORMS=cpu`` (it only reads a file):

    python bench/lib/trace_scopes.py <trace.xplane.pb> [<planes.json>]

prints one JSON object; the second argument keeps the cut-down planes (the
tests' recorded trace).  ``reduce_scopes`` is the pure part, over plain
lists like ``trace_reduce.reduce_planes``: an event is ``[name, start_ns,
dur_ns, extra]`` where ``extra`` is a device op's scope path (its
``tf_op`` stat: the ``jax.named_scope`` path of the HLO op, the fusion's
ROOT op for a fusion that spans two scopes) or a host annotation's
arguments.  A trace of a program that writes neither annotations nor
scopes (the parent of the PR that brought them) reduces to no value: the
readers then leave their metrics out."""

from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, List, Optional, Tuple

try:                                    # as bench/lib/... and as a script
    from lib.trace_reduce import (MODULE_LINE, OP_LINE, PROGRAMS, _is_device,
                                  gaps, self_seconds, short_name)
except ImportError:                     # pragma: no cover - script form
    from trace_reduce import (MODULE_LINE, OP_LINE, PROGRAMS, _is_device,
                              gaps, self_seconds, short_name)

#: every scheduler phase starts with this (runtime/genserver.py)
ANNOTATION_PREFIX = "GenServer."
#: leaf phases whose idle seconds are scheduling work that could overlap
#: the device, and those that are the synchronous hand-over
SCHED_LEAVES = ("GenServer._admit", "GenServer._retire", "GenServer._publish")
SCHED_SUFFIXES = ("/capacity", "/build")
SYNC_SUFFIXES = ("/device", "/readback", "/emit")
FENCES = {"decode": "GenServer._decode_round/device",
          "prefill": "GenServer._prefill_tick/device"}
#: the stages models/generate.py names, and the ones a paged-attention
#: kernel would replace
SCOPES = ("embed", "qkv", "rope", "kv_write", "kv_gather", "attn", "wo",
          "ffn", "unembed", "sample")
KV_SCOPES = ("kv_write", "kv_gather", "attn")
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"


def scope_share(prog: dict, scopes) -> Optional[float]:
    """100 x the device seconds of one program (an entry of the
    reduction's ``programs``) under the named ``scopes`` over the program's
    device seconds.  None where the program did not run or wrote no named
    scope at all (a program from before the scopes): never 0 for that."""
    by = prog.get("by_scope_s") or {}
    if not prog.get("module_s", 0.0) > 0 or not any(
            v > 0 for k, v in by.items() if k != UNSCOPED):
        return None
    return 100.0 * sum(by.get(k, 0.0) for k in scopes) / prog["module_s"]


def leaf_segments(annotations: list) -> List[Tuple[float, float, str]]:
    """Nested ``(name, start, end)`` annotations of one thread ->
    non-overlapping ``(start, end, name)`` segments, each named by the
    INNERMOST annotation open over it, in time order."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []
    cur = 0.0

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end

    for name, s, e in sorted(annotations, key=lambda a: (a[1], a[1] - a[2])):
        close_until(s)
        if stack:
            if s > cur:
                segs.append((cur, s, stack[-1][0]))
            e = min(e, stack[-1][1])       # a child never outlives its parent
        cur = max(cur, s) if stack else s
        stack.append((name, e))
    close_until(float("inf"))
    return segs


def attribute(gap_list: list, segs: list) -> Dict[str, float]:
    """Seconds of each ``(start, end)`` gap by the leaf segment over it;
    what no segment covers is ``unattributed``."""
    out: Dict[str, float] = {}
    starts = [s for s, _, _ in segs]
    for gs, ge in gap_list:
        left = ge - gs
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(segs) and segs[i][0] < ge:
            s, e, name = segs[i]
            cover = min(e, ge) - max(s, gs)
            if cover > 0:
                out[name] = out.get(name, 0.0) + cover / 1e9
                left -= cover
            i += 1
        if left > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + left / 1e9
    return out


def scope_of(path: Optional[str]) -> str:
    """The stage a device op belongs to: the last component of its scope
    path that is one of ours (``jit(f)/jit(main)/while/body/attn/dot_general``
    -> ``attn``)."""
    for part in reversed((path or "").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def self_by_scope(op_events: list) -> Dict[str, float]:
    """Device self-seconds by stage: each op counted for the time no op
    nested inside it covers (a ``while`` contains its body's ops)."""
    return self_seconds([(scope_of(ev[3] if len(ev) > 3 else None),
                          ev[1], ev[2]) for ev in op_events])


def _inside(events: list, spans: list) -> list:
    """The events that start inside one of the sorted, disjoint
    ``(start, end)`` spans."""
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < spans[i][1]:
            out.append(ev)
    return out


def fence_slack(fences: list, modules: list) -> Optional[dict]:
    """Per fenced dispatch (annotation ``(start, end)``) the module events
    that START inside it: the mean annotation, module, and the slack before
    the module starts and after it ends, in ms.  None when no fence has a
    module inside."""
    mods = sorted(modules)
    starts = [s for s, _ in mods]
    rows = []
    for fs, fe in fences:
        lo = bisect.bisect_left(starts, fs)
        hi = bisect.bisect_left(starts, fe)
        if hi <= lo:
            continue
        inside = mods[lo:hi]
        dev = sum(d for _, d in inside)
        rows.append((fe - fs, dev, inside[0][0] - fs,
                     fe - (inside[-1][0] + inside[-1][1])))
    if not rows:
        return None
    n = len(rows)
    ann, dev, before, after = (sum(r[k] for r in rows) / n / 1e6
                               for k in range(4))
    return {"rounds": n, "annotation_ms": ann, "module_ms": dev,
            "slack_ms": ann - dev, "before_ms": before, "after_ms": after}


def _mean_args(events: list) -> dict:
    """Means of the numeric arguments a ``/device`` annotation carried."""
    sums: Dict[str, float] = {}
    n = 0
    for ev in events:
        args = ev[3] if len(ev) > 3 and isinstance(ev[3], dict) else None
        if not args:
            continue
        n += 1
        for k, v in args.items():
            try:
                sums[k] = sums.get(k, 0.0) + float(v)
            except (TypeError, ValueError):
                pass
    return {"calls": n, **{k + "_mean": v / n for k, v in sums.items()}} \
        if n else {"calls": 0}


def reduce_scopes(planes: list) -> dict:
    """``planes`` as in the module docstring.  One device plane is what a
    one-chip cell has; with several, times are averaged over them."""
    devices = [p for p in planes if _is_device(p["name"])]
    annotations = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                annotations += [ev for ev in line["events"] if ev[2] > 0
                                and ev[0].startswith(ANNOTATION_PREFIX)]
    segs = leaf_segments([(ev[0], ev[1], ev[1] + ev[2])
                          for ev in annotations])
    out: dict = {"devices": len(devices), "annotations": len(annotations)}
    idle_by: Dict[str, float] = {}
    scopes: Dict[str, dict] = {}
    fences: Dict[str, Optional[dict]] = {}
    windows, n = 0.0, 0
    for p in devices:
        lines = {line["name"]: line["events"] for line in p["lines"]}
        ops = [ev for ev in lines.get(OP_LINE, []) if ev[2] > 0]
        mods = [ev for ev in lines.get(MODULE_LINE, []) if ev[2] > 0]
        iv = [(ev[1], ev[1] + ev[2]) for ev in (ops or mods)]
        if not iv:
            continue
        n += 1
        windows += (max(e for _, e in iv) - min(s for s, _ in iv)) / 1e9
        gap_list = gaps(iv)
        for name, secs in attribute(gap_list, segs).items():
            idle_by[name] = idle_by.get(name, 0.0) + secs
        for key, needle in PROGRAMS.items():
            mine = sorted((ev[1], ev[2]) for ev in mods if needle in ev[0])
            prog = scopes.setdefault(key, {"module_s": 0.0, "calls": 0,
                                           "by_scope_s": {}})
            prog["module_s"] += sum(d for _, d in mine) / 1e9
            prog["calls"] += len(mine)
            spans = [(s, s + d) for s, d in mine]
            for scope, secs in self_by_scope(_inside(ops, spans)).items():
                prog["by_scope_s"][scope] = (
                    prog["by_scope_s"].get(scope, 0.0) + secs)
            if key not in fences:
                fences[key] = fence_slack(
                    [(ev[1], ev[1] + ev[2]) for ev in annotations
                     if ev[0] == FENCES[key]], mine)
    if not n:
        return out
    window_s = windows / n
    idle_s = sum(idle_by.values()) / n
    out.update(window_s=window_s, idle_s=idle_s,
               idle_by_leaf_s={k: v / n for k, v in sorted(
                   idle_by.items(), key=lambda kv: -kv[1])})
    if annotations and window_s > 0:
        sched = sum(v for k, v in idle_by.items()
                    if k in SCHED_LEAVES or k.endswith(SCHED_SUFFIXES)) / n
        sync = sum(v for k, v in idle_by.items()
                   if k.endswith(SYNC_SUFFIXES)) / n
        out.update(idle_sched_pct=100.0 * sched / window_s,
                   idle_sync_pct=100.0 * sync / window_s,
                   idle_attributed_share=(
                       1.0 - idle_by.get(UNATTRIBUTED, 0.0) / n / idle_s
                       if idle_s > 0 else None))
    for key, prog in scopes.items():
        prog["module_s"] /= n
        prog["calls"] /= n
        prog["by_scope_s"] = {k: v / n for k, v in sorted(
            prog["by_scope_s"].items(), key=lambda kv: -kv[1])}
        kv = scope_share(prog, KV_SCOPES)
        if kv is not None:
            prog["kv_share"] = out[key + "_kv_share"] = kv
            prog["unscoped_share"] = scope_share(prog, (UNSCOPED,))
        prog["fence"] = fences.get(key)
        prog["device_args"] = _mean_args(
            [ev for ev in annotations if ev[0] == FENCES[key]])
    out["programs"] = scopes
    if fences.get("decode"):
        out["decode_fence_slack_ms"] = fences["decode"]["slack_ms"]
    return out


#: the stat of a device op's METADATA that carries its scope path
SCOPE_STAT = "tf_op"


def load_planes(path: str) -> list:
    """The device planes' module and op events (an op's ``extra`` is its
    scope path) and the scheduler's annotations on the host planes (their
    ``extra`` is their arguments), through lib/xplane.py:
    ``jax.profiler.ProfileData`` does not give an op's metadata stats."""
    try:
        from lib import xplane
    except ImportError:                 # pragma: no cover - script form
        import xplane

    planes = xplane.read_planes(
        path,
        want_plane=lambda p: _is_device(p) or p.startswith("/host:"),
        want_line=lambda p, line: (line in (MODULE_LINE, OP_LINE)
                                   or not _is_device(p)),
        want_event=lambda p, ev: (_is_device(p)
                                  or ev.startswith(ANNOTATION_PREFIX)),
        own_stats=lambda p: not _is_device(p))
    for plane in planes:
        device = _is_device(plane["name"])
        for line in plane["lines"]:
            for ev in line["events"]:
                if device:
                    ev[0] = short_name(ev[0])
                    ev[3] = ev[3].get(SCOPE_STAT)
        plane["lines"] = [ln for ln in plane["lines"] if ln["events"]]
    return planes


if __name__ == "__main__":
    _planes = load_planes(sys.argv[1])
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as _f:
            json.dump(_planes, _f)
    print(json.dumps(reduce_scopes(_planes)), flush=True)
