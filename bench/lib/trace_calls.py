"""From a profiler trace (``*.xplane.pb``) to the work of each traced call:
every whole module event of the two paged programs joined to the span that
dispatched it, and through that span to what the program was given.

The scheduler (runtime/genserver.py) numbers every program it dispatches
(``seq``) and writes the call's work as arguments of the span that wraps
the ``jit`` call -- ``GenServer._decode_round/device`` or
``GenServer._prefill_tick/device`` where the dispatch is fenced, the
second ``.../build`` of the function otherwise -- what the program counted
itself (``experts_read``) on the ``.../emit`` span of the same ``seq``,
and the ``seq`` it waits for on every ``.../wait``.  The device plane
holds one ``XLA Modules`` event a call.  This file pairs the two, so that
a roofline reader divides the work of exactly the calls whose device
seconds it sums:

* a module event is WHOLE unless it touches the bounds of its plane's
  device events (it starts within ``EDGE_NS`` of the first device
  timestamp or ends within it of the last): the profiler's start and stop
  cut whatever ran across them.  Told from the bounds, never from a
  duration;
* the JOIN is the link the trace itself holds (``Flows``): a chip trace's
  module event carries a consumer id (``_c`` of type ``_ct``) whose
  producer (``_p`` / ``_pt``) is a host event of the runtime's enqueueing
  thread, itself inside an event that consumes what the executing thread
  produced, and so on back -- three hops on a TPU v5e under this JAX
  (PERF.md section 6, PR 36) -- to an event inside the dispatching span on
  the scheduler's own thread.  Nothing of it is known here by name: only
  that a producer and a consumer share an id, and that a thread's events
  nest.  There is no second way: a module event whose chain does not
  resolve into a dispatching span is UNJOINED and lowers ``joined_share``;
  a cut or unjoined call is left out of BOTH the work and the device
  seconds;
* the device runs one stream, so the whole module events AHEAD of the
  first joined one on their plane were launched before the host's trace
  began (``before_profiler``): they are no span's, and count neither as
  whole nor as unjoined.  Where nothing is joined nothing is ahead of it,
  and the share reads 0;
* every fenced call is its own check: its module event lies inside its
  ``.../device`` span (``inside_fence``), and what it starts after the
  span opened is the launch of a program with nothing queued ahead.  The
  least of those over the trace is written as ``clock_offset_ms``: the
  most the host's and the device's clocks can differ by.  Nothing here
  depends on it;
* the device's idle time inside the ``.../wait`` spans that name a call's
  ``seq`` is kept with the call (``idle_in_wait_ms``): the scheduler was
  waiting for that round while the device had nothing to run, so the round
  came too late.

Runs in a child with ``JAX_PLATFORMS=cpu`` (it only reads a file):

    python bench/lib/trace_calls.py <trace.xplane.pb> [<planes.json>]

prints one JSON object; the second argument keeps the cut-down planes (the
tests' recorded traces).  ``reduce_calls`` is the pure part, over plain
lists like ``trace_scopes.reduce_scopes``: an event is ``[name, start_ns,
dur_ns, extra]``, ``extra`` a device op's scope path, a module event's
or a runtime host event's flow stats, or a host annotation's arguments.
A trace whose spans carry no ``seq`` (a program from before they did:
the parent of the PR that brought them) reduces to
no call and no share: the readers then leave their metrics out."""

from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, List, Optional

try:                                    # as bench/lib/... and as a script
    from lib.trace_reduce import (MODULE_LINE, OP_LINE, PROGRAMS, _is_device,
                                  gaps, self_seconds)
    from lib.trace_scopes import (ANNOTATION_PREFIX, SCOPE_STAT, SCOPES,
                                  UNSCOPED)
except ImportError:                     # pragma: no cover - script form
    from trace_reduce import (MODULE_LINE, OP_LINE, PROGRAMS, _is_device,
                              gaps, self_seconds)
    from trace_scopes import (ANNOTATION_PREFIX, SCOPE_STAT, SCOPES,
                              UNSCOPED)

#: the scheduler function that dispatches and reads back each program
FUNCTIONS = {"decode": "GenServer._decode_round",
             "prefill": "GenServer._prefill_tick"}
#: how near a plane's first or last device timestamp a module event may
#: come and still count as whole (the profiler's own granularity is 1 ns;
#: an event the window cut starts or ends ON the bound)
EDGE_NS = 1000.0
#: the stages a routed block names beside lib/trace_scopes.py's list
#: (readers/trace_stages.py knows the same three)
STAGES = tuple(SCOPES) + ("qk_norm", "router", "experts")


def _args(ev) -> dict:
    """A host annotation's numeric arguments as integers."""
    out = {}
    extra = ev[3] if len(ev) > 3 and isinstance(ev[3], dict) else {}
    for k, v in extra.items():
        try:
            out[k] = int(v)
        except (TypeError, ValueError):
            pass
    return out


def stage_of(path: Optional[str]) -> str:
    """The innermost stage of ``STAGES`` in a device op's scope path."""
    for part in reversed((path or "").split("/")):
        if part in STAGES:
            return part
    return UNSCOPED


def spans_of(annotations: list, kind: str) -> List[dict]:
    """The dispatching spans of one program by start, each with what its
    ``.../emit`` span of the same ``seq`` brought back and the
    ``(start, end)`` of the ``.../wait`` spans that name it."""
    fn = FUNCTIONS[kind]
    read: Dict[int, Optional[int]] = {}
    waits: Dict[int, list] = {}
    for ev in annotations:
        a = _args(ev)
        if "seq" not in a:
            continue
        if ev[0] == fn + "/emit":
            read[a["seq"]] = a.get("experts_read")
        elif ev[0] == fn + "/wait":
            waits.setdefault(a["seq"], []).append((ev[1], ev[1] + ev[2]))
    out = []
    for ev in annotations:
        if ev[0] in (fn + "/device", fn + "/build"):
            a = _args(ev)
            if "seq" not in a:
                continue        # the first /build: batch building
            seq = a.pop("seq")
            out.append({"seq": seq, "kind": kind,
                        "fenced": ev[0].endswith("/device"),
                        "start": ev[1], "end": ev[1] + ev[2], "args": a,
                        "experts_read": read.get(seq),
                        "waits": waits.get(seq, [])})
    return sorted(out, key=lambda s: s["start"])


def _flow(ev, role: str):
    """``(type, id)`` an event produces (``role`` ``p``) or consumes
    (``c``), or None."""
    extra = ev[3] if len(ev) > 3 and isinstance(ev[3], dict) else {}
    if extra.get("_" + role) is None or extra.get("_" + role + "t") is None:
        return None
    return int(extra["_" + role + "t"]), int(extra["_" + role]) % (1 << 64)


class Flows:
    """The ids the host events of a trace produce and consume, and the way
    back along them from what a device event consumes to the scheduler's
    own thread."""

    def __init__(self, planes: list):
        self.producers: Dict[tuple, tuple] = {}   # id -> (line, event)
        self.consumers: Dict[tuple, list] = {}    # line -> consuming events
        self.own = set()          # lines that carry the scheduler's spans
        for p in planes:
            if not p["name"].startswith("/host:"):
                continue
            for n, line in enumerate(p["lines"]):
                key = (p["name"], n)
                for ev in line["events"]:
                    if ev[0].startswith(ANNOTATION_PREFIX):
                        self.own.add(key)
                    made = _flow(ev, "p")
                    if made is not None:
                        self.producers[made] = (key, ev)
                    if _flow(ev, "c") is not None:
                        self.consumers.setdefault(key, []).append(ev)

    def launch(self, want, hops: int = 8) -> Optional[float]:
        """The host time, on the scheduler's thread, of the event the
        launch of whatever consumes ``want`` goes back to: from the
        producer of an id, to the innermost event of the same thread that
        holds it and consumes another id, to that id's producer, until the
        producer lies on a thread that carries the scheduler's
        annotations.  None where the chain breaks."""
        for _ in range(hops):
            if want not in self.producers:
                return None
            key, ev = self.producers[want]
            if key in self.own:
                return ev[1]
            holding = [c for c in self.consumers.get(key, ())
                       if c[1] <= ev[1] and ev[1] + ev[2] <= c[1] + c[2]]
            if not holding:
                return None
            want = _flow(min(holding, key=lambda c: c[2]), "c")
        return None


def link(spans: List[dict], events: list, flows: Flows) -> Dict[int, int]:
    """``{index of a module event: index of its span}`` for the module
    events whose launch the trace follows back into a dispatching span
    (``spans`` sorted by start; a span launches one program)."""
    starts = [sp["start"] for sp in spans]
    out: Dict[int, int] = {}
    taken = set()
    for m, ev in enumerate(events):
        at = flows.launch(_flow(ev, "c"))
        if at is None:
            continue
        i = bisect.bisect_right(starts, at) - 1
        if i >= 0 and at < spans[i]["end"] and i not in taken:
            out[m] = i
            taken.add(i)
    return out


def idle_inside(gap_list: list, spans: list) -> float:
    """Nanoseconds of the sorted idle gaps ``(start, end)`` that lie inside
    the ``(start, end)`` spans."""
    ends = [e for _, e in gap_list]
    total = 0.0
    for s, e in spans:
        i = bisect.bisect_right(ends, s)
        while i < len(gap_list) and gap_list[i][0] < e:
            total += min(gap_list[i][1], e) - max(gap_list[i][0], s)
            i += 1
    return total


def reduce_calls(planes: list) -> dict:
    """``planes`` as in the module docstring; one device plane is what a
    one-chip cell has (with several, each is joined to the same spans and
    the calls name their plane)."""
    annotations = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                annotations += [ev for ev in line["events"] if ev[2] > 0
                                and ev[0].startswith(ANNOTATION_PREFIX)]
    spans = {kind: spans_of(annotations, kind) for kind in PROGRAMS}
    devices = [p for p in planes if _is_device(p["name"])]
    out: dict = {"devices": len(devices),
                 "spans": {k: len(v) for k, v in spans.items()},
                 "clock_offset_ms": None, "fenced": 0,
                 "joined_share": None, "programs": {}, "calls": []}
    if not any(spans.values()):
        return out          # a program from before the spans said their work
    flows = Flows(planes)
    for p in devices:
        lines = {line["name"]: line["events"] for line in p["lines"]}
        ops = sorted((ev for ev in lines.get(OP_LINE, []) if ev[2] > 0),
                     key=lambda ev: ev[1])
        mods_all = [ev for ev in lines.get(MODULE_LINE, []) if ev[2] > 0]
        every = ops + mods_all
        if not every:
            continue
        t_first = min(ev[1] for ev in every)
        t_last = max(ev[1] + ev[2] for ev in every)
        op_starts = [ev[1] for ev in ops]
        idle = gaps([(ev[1], ev[1] + ev[2]) for ev in (ops or mods_all)])
        events = {kind: sorted((ev for ev in mods_all if needle in ev[0]),
                               key=lambda ev: (ev[1], ev[2]))
                  for kind, needle in PROGRAMS.items()}
        linked = {kind: link(spans[kind], events[kind], flows)
                  for kind in PROGRAMS}
        # what ran ahead of the first launch the host's trace saw
        seen_from = min((events[kind][m][1] for kind in PROGRAMS
                         for m in linked[kind]), default=t_first)
        for kind in PROGRAMS:
            prog = out["programs"].setdefault(kind, {
                "module_events": 0, "cut": 0, "before_profiler": 0,
                "whole": 0, "joined": 0, "device_s": 0.0})
            prog["module_events"] += len(events[kind])
            for m, ev in enumerate(events[kind]):
                s, d = ev[1], ev[2]
                if s <= t_first + EDGE_NS or s + d >= t_last - EDGE_NS:
                    prog["cut"] += 1
                    continue
                if m not in linked[kind]:
                    if s < seen_from:
                        prog["before_profiler"] += 1
                    else:
                        prog["whole"] += 1
                    continue
                sp = spans[kind][linked[kind][m]]
                prog["whole"] += 1
                prog["joined"] += 1
                prog["device_s"] += d / 1e9
                lo = bisect.bisect_left(op_starts, s)
                hi = bisect.bisect_left(op_starts, s + d)
                under = self_seconds([
                    (stage_of(op[3] if len(op) > 3 else None), op[1], op[2])
                    for op in ops[lo:hi]])
                call = {"seq": sp["seq"], "kind": kind,
                        "fenced": sp["fenced"], "plane": p["name"],
                        "device_s": d / 1e9,
                        "after_span_ms": (s - sp["start"]) / 1e6,
                        "idle_in_wait_ms":
                            idle_inside(idle, sp["waits"]) / 1e6,
                        **sp["args"],
                        "experts_read": sp["experts_read"],
                        "stage_s": {k: v for k, v in sorted(
                            under.items(), key=lambda kv: -kv[1]) if v > 0}}
                if sp["fenced"]:
                    call["inside_fence"] = bool(
                        s >= sp["start"] and s + d <= sp["end"])
                out["calls"].append(call)
    out["calls"].sort(key=lambda c: (c["plane"], c["seq"]))
    fenced = [c["after_span_ms"] for c in out["calls"] if c["fenced"]]
    if fenced:
        out.update(clock_offset_ms=min(fenced), fenced=len(fenced))
    whole = sum(prog["whole"] for prog in out["programs"].values())
    if whole:
        out["joined_share"] = 100.0 * sum(
            prog["joined"] for prog in out["programs"].values()) / whole
    return out


#: the flow stats a module event and the runtime's host events carry
FLOW_STATS = ("_p", "_pt", "_c", "_ct", "run_id")


def load_planes(path: str) -> list:
    """The device planes' module events with their flow stats and op events
    with their scope path, and of the host planes the scheduler's
    annotations with their arguments and every event that produces or
    consumes an id.  Each event is decoded once: the op line in a call of
    its own, because lib/xplane.py decodes an event's own stats a plane or
    not at all and a device plane's few hundred thousand ops have some that
    nothing reads."""
    try:
        from lib import xplane
        from lib.trace_reduce import short_name
    except ImportError:                 # pragma: no cover - script form
        import xplane
        from trace_reduce import short_name

    ops = xplane.read_planes(
        path, want_plane=_is_device,
        want_line=lambda p, line: line == OP_LINE,
        own_stats=lambda p: False)
    planes = xplane.read_planes(
        path,
        want_plane=lambda p: _is_device(p) or p.startswith("/host:"),
        want_line=lambda p, line: line == MODULE_LINE or not _is_device(p))
    for plane in ops:
        for line in plane["lines"]:
            for ev in line["events"]:
                ev[0], ev[3] = short_name(ev[0]), ev[3].get(SCOPE_STAT)
    for plane in planes:
        device = _is_device(plane["name"])
        for line in plane["lines"]:
            kept = []
            for ev in line["events"]:
                if device:
                    ev[0] = short_name(ev[0])
                elif ev[0].startswith(ANNOTATION_PREFIX):
                    kept.append(ev)
                    continue
                ev[3] = {k: ev[3][k] for k in FLOW_STATS if k in ev[3]}
                if device or "_p" in ev[3] or "_c" in ev[3]:
                    kept.append(ev)
            line["events"] = kept
        if device:
            plane["lines"] += [ln for o in ops if o["name"] == plane["name"]
                               for ln in o["lines"]]
        plane["lines"] = [ln for ln in plane["lines"] if ln["events"]]
    return planes


if __name__ == "__main__":
    _planes = load_planes(sys.argv[1])
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as _f:
            json.dump(_planes, _f)
    print(json.dumps(reduce_calls(_planes)), flush=True)
