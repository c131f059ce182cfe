"""From a profiler trace (``*.xplane.pb``) to what the benchmark reports:
device busy seconds and traced window, device seconds and calls per paged
program, the device operations that took most time, and the longest idle
gaps with what the host was doing in them.

Runs in a child with ``JAX_PLATFORMS=cpu`` (it only reads a file; the
parent never imports JAX):

    python bench/lib/trace_reduce.py <trace.xplane.pb>

prints one JSON object.  ``reduce_planes`` is the pure part, over plain
lists, so the tests feed it a recorded trace or a hand-made one."""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Tuple

#: device plane line names, as the TPU profiler writes them
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
#: which jitted program a module event belongs to, by its name
PROGRAMS = {"decode": "paged_decode_round", "prefill": "paged_forward"}
#: host functions of the scheduler a gap is attributed to, innermost first
HOST_PHASES = ("_decode_round", "_prefill_tick", "_admit", "_retire",
               "_publish", "_emit_tokens", "_tick", "_run")


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals, ns -> s."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The idle intervals between merged busy intervals."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def self_seconds(events: list) -> Dict[str, float]:
    """Seconds by operation name, each event counted for the time no event
    nested inside it covers (a ``while`` op's events contain its body's)."""
    out: Dict[str, float] = {}
    stack: list = []      # [name, end, self_ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + max(item[2], 0.0) / 1e9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        close(stack.pop())
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0][:64]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CUSTOM" not in plane_name


def _host_phase(host_events: list, s: float, e: float) -> str:
    """The innermost known scheduler function covering most of a gap."""
    best, best_rank, best_cover = "unattributed", len(HOST_PHASES), 0.0
    for name, hs, he in host_events:
        cover = min(e, he) - max(s, hs)
        if cover <= 0.5 * (e - s):
            continue
        for rank, key in enumerate(HOST_PHASES):
            if key in name and (rank < best_rank
                                or (rank == best_rank and cover > best_cover)):
                best, best_rank, best_cover = key.lstrip("_"), rank, cover
                break
    return best


def reduce_planes(planes: list) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns), ...]}]}].  Device numbers are averaged over device planes."""
    devices = [p for p in planes if _is_device(p["name"])]
    if not devices:
        return {"devices": 0}
    host_events = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                for name, s, d in line["events"]:
                    if d > 0 and any(k in name for k in HOST_PHASES):
                        host_events.append((name, s, s + d))
    busy, windows = [], []
    programs: Dict[str, dict] = {k: {"seconds": 0.0, "calls": 0}
                                 for k in PROGRAMS}
    ops: Dict[str, float] = {}
    gap_by: Dict[str, float] = {}
    for p in devices:
        lines = {line["name"]: line["events"] for line in p["lines"]}
        op_events = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        iv = [(s, s + d) for _, s, d in op_events if d > 0]
        if not iv:
            continue
        busy.append(union_seconds(iv))
        windows.append((max(e for _, e in iv) - min(s for s, _ in iv)) / 1e9)
        for name, secs in self_seconds(
                [e for e in op_events if e[2] > 0]).items():
            ops[name] = ops.get(name, 0.0) + secs
        for name, _, d in lines.get(MODULE_LINE, []):
            for key, needle in PROGRAMS.items():
                if needle in name:
                    programs[key]["seconds"] += d / 1e9
                    programs[key]["calls"] += 1
        for s, e in gaps(iv):
            phase = _host_phase(host_events, s, e)
            gap_by[phase] = gap_by.get(phase, 0.0) + (e - s) / 1e9
    n = len(busy)
    if not n:
        return {"devices": len(devices), "busy_s": 0.0}
    for prog in programs.values():
        prog["seconds"] /= n
        prog["calls"] /= n
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(devices),
        "busy_s": sum(busy) / n,
        "window_s": sum(windows) / n,
        "programs": programs,
        "device_ops": [[k, v / n] for k, v in top],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gap_by.items(), key=lambda kv: -kv[1])[:10]],
    }


def load_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        keep_all = _is_device(plane.name)
        lines = []
        for line in plane.lines:
            if keep_all and line.name not in (MODULE_LINE, OP_LINE):
                continue
            events = [(short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)) for e in line.events]
            if not keep_all:
                events = [ev for ev in events
                          if any(k in ev[0] for k in HOST_PHASES)]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


if __name__ == "__main__":
    _planes = load_planes(sys.argv[1])
    if len(sys.argv) > 2:   # keep a cut-down copy (the tests' recorded trace)
        with open(sys.argv[2], "w") as _f:
            json.dump(_planes, _f)
    print(json.dumps(reduce_planes(_planes)), flush=True)
