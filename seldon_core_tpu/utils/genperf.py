"""Generation-lane flight recorder — the aggregator behind ``GET /genperf``.

The continuous-batching scheduler (runtime/genserver.py) stamps ONE fused
record per tick into the telemetry spine (utils/hotrecord.py
HOP_GEN_STEP).  This PR enriches that record with a full per-tick
decomposition — host-schedule wall vs fenced device wall, admit/prefill/
decode/retire phase splits, real-vs-padded rows, KV blocks touched, and
an explicit **bubble ledger** (device-idle time between consecutive
ticks, classified by cause) — and the spine's off-path drainer folds it
HERE.  Nothing in this module ever runs on the scheduler's hot path: the
tick loop's only added cost is a handful of ``perf_counter()`` stamps
around waits it makes anyway (``booked_device_s`` says how a program's
device seconds are taken from them now that the scheduler keeps a round
queued ahead of the one it reads).

What the aggregator answers (docs/operations.md "reading the /genperf
page"):

  * per-tick-kind latency percentiles (prefill / decode / spec / mixed /
    idle) and per-phase host/device totals;
  * the bubble ledger — seconds of scheduler wall not covered by any
    tick, by cause:
      - ``host``: the scheduler loop's own bookkeeping between ticks;
      - ``admission_stall``: sequences were waiting but none admitted
        (slots full);
      - ``pool_exhaustion``: admission broke on a dry KV pool;
      - ``idle``: no work anywhere (the 5 ms backoff / blocking wait);
  * served decode MFU and HBM-BW utilization — the perf observatory's
    analytic cost features for the decode step
    (``OBSERVATORY.cost_features("gen_decode_step")``, registered by the
    scheduler at device init) priced against REAL (unpadded) tokens over
    the fenced decode device time, normalized by ``OBSERVATORY.peaks()``;
  * ``served_decode.kv_positions`` -- positions ATTENDED OVER: in a
    generator whose layers are not all attention (``LMConfig.layer_kinds``)
    only the attention layers hold K/V at them, and a reader prices the
    bytes a position by those layers alone; the short-convolution layers'
    fixed-size state is per row and per step, not per position.
    ``served_prefill.carried_rows`` of ``.rows`` are the prefill rows that
    began from such a state left by an earlier chunk;
  * an idle-poll duty cycle (idle tick wall / scheduler wall) so a
    hot-spinning scheduler reads as a bubble, not as silence;
  * a KV-block age histogram (block residency at release) for pool
    sizing;
  * the ``requests`` block — per STREAMED request, the four stages of its
    time to first token on ``perf_counter`` only (``lane_in`` recv ->
    submit, ``queue`` submit -> admit, ``prefill`` admit -> first chunk on
    the request's queue, ``lane_out`` that chunk -> handed to the lane's
    writer), as CUMULATIVE sums and counts plus a fixed-edge histogram of
    recv -> writer, so a window delta of two documents reads them.  The
    scheduler's two stages ride the tick record; the lane's two are folded
    by ``engine.generate_stream`` where it takes its ``ttft_s``.

The host+device+bubble ledger accounts for scheduler wall BY
CONSTRUCTION: per-tick host time is defined as tick wall minus the device
seconds booked in the tick, and the bubble is the inter-tick gap — the
demo artifact's >= 95 % accounting criterion checks the arithmetic stayed
wired, not a lucky measurement.

Kill switches: ``SELDON_TPU_TELEMETRY=0`` stops the spine record at the
source (``record_gen_step`` returns before any ring write), and
``SELDON_TPU_GEN_CONTINUOUS=0`` removes the scheduler entirely — either
way this module sees zero observations.
"""

from __future__ import annotations

import bisect
import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from seldon_core_tpu.utils.telemetry import RECORDER, Reservoir

__all__ = ["GenPerf", "GENPERF", "BUBBLE_CAUSES", "TICK_PHASES",
           "booked_device_s", "BootTimeline", "BOOT"]

#: the bubble ledger's closed cause vocabulary (labels on
#: seldon_tpu_gen_bubble_seconds_total)
BUBBLE_CAUSES = ("host", "admission_stall", "pool_exhaustion", "idle")

#: per-tick phase vocabulary (labels on seldon_tpu_gen_step_seconds)
TICK_PHASES = ("admit", "prefill", "decode", "retire", "host_other")

#: the stages a streamed request's time to first token splits into
REQUEST_STAGES = ("lane_in", "queue", "prefill", "lane_out")

#: ``served_decode``'s counts, each with the tick record's name for it (what
#: runtime/genserver.py counts a call under, most of it given by
#: models/served.py), folded from decode / spec / mixed ticks; docs/
#: operations.md "reading the /genperf page" says how to read each.
#: ``real_tokens`` emitted; ``device_steps``, the single-token steps run, of
#: which ``inplace_steps`` attended over the block pool in place,
#: ``retention_fused_steps`` updated the retention states where they lie,
#: ``ssm_fused_steps`` the Mamba-2 states (the kernel of ops/ssm.py)
#: and ``ahead_steps`` were dispatched before the program ahead was read;
#: ``kv_positions`` attended over (module docstring); ``passes`` of the model
#: (one a step, or a block's denoising passes and the K/V one), of which
#: ``shared_passes`` passes of the device served two (the K/V one of a
#: diffusion block with the next block's first denoising pass: passes -
#: shared_passes went over the weights), ``experts_fused_passes`` took each
#: expert's feed-forward as one kernel (parallel/moe.py) and
#: ``row_passes``, summed over the real rows of each (real_tokens /
#: row_passes = tokens fixed a row-pass: 1, or 4/5 for blocks of four under
#: four denoising passes); ``experts_read`` by the expert layers (the rounds'
#: own count) of ``expert_slots``, the experts held x layers x passes, and,
#: where a layer holds a share of the experts it routes over,
#: ``expert_slots_held``: the real rows' picks that fell on a held expert
#: (the rounds' own count too; a row-pass picks ``moe_k`` a layer in all)
SERVED_DECODE = dict(
    {"real_tokens": "tokens", "device_steps": "steps"},
    **{name: name for name in (
        "inplace_steps", "retention_fused_steps", "ssm_fused_steps",
        "ahead_steps",
        "kv_positions", "passes", "shared_passes", "experts_fused_passes",
        "row_passes", "experts_read",
        "expert_slots", "expert_slots_held")})

#: ``served_prefill``'s, folded from every tick (a chunk is read back a tick
#: after it was dispatched, whatever that tick's kind): ``calls``, the
#: ``experts_read`` (where a prefill returns the count) of ``expert_slots``,
#: the prompt ``tokens`` and real ``rows`` given, of which ``carried_rows``
#: began from a state an earlier chunk left and ``retention_fused_rows``
#: worked on the retention states where they lie (the chunk kernel), and
#: the ``experts_fused_calls`` whose expert layers ran that one kernel
SERVED_PREFILL = {name: "prefill_" + name for name in (
    "calls", "experts_fused_calls", "experts_read", "expert_slots", "tokens",
    "rows", "carried_rows", "retention_fused_rows")}

#: ``<kind>_state_bytes`` of both sections: the bytes of matrix state a
#: generator of such layers read + wrote, 2 x a row's bytes over the layers
#: (the tick record's ``<kind>_row_bytes``, models/served.py) x the decode
#: steps' real rows (``row_passes``) or the prefill calls' (``rows``: a row
#: of a call is one chunk through every layer)
STATE_KINDS = ("retention", "ssm")
STATE_BYTES = tuple(kind + "_state_bytes" for kind in STATE_KINDS)
#: a count only a generator of one kind makes: a section shows it once it
#: was made, so the documents of the other kinds read as they did
OF_ONE_KIND = ("ssm_state_bytes",)


def _section(counts, names) -> Dict[str, Any]:
    return {name: counts[name] for name in names
            if name not in OF_ONE_KIND or counts[name]}

#: fixed log-spaced edges of the TTFT histogram, 1 ms ... 60 s at a ratio
#: of 60000 ** (1 / 79) = 1.1494 (<= 1.15): fixed, so two documents of one
#: process always subtract bucket by bucket
TTFT_EDGES_MS = tuple(round(60000.0 ** (i / 79.0), 4) for i in range(80))


def booked_device_s(t_dispatch: float, t_done: float,
                    prev_done: float) -> float:
    """The device seconds the scheduler books for one program: its observed
    completion minus the LATER of its dispatch and the previous program's
    observed completion.  A program the host fenced alone reads its whole
    dispatch -> ready interval, as ever; one that sat on the device's queue
    behind another is booked from the moment that one was seen to end, so
    two queued programs never book the same interval twice and the sum
    over a stretch of rounds is the stretch the device was seen busy."""
    return max(t_done - max(t_dispatch, prev_done), 0.0)


def _fold(into, names: Dict[str, str], detail: Dict[str, Any]) -> None:
    """Add the tick record's counts ``names`` lists to a section's."""
    for name, key in names.items():
        into[name] += int(detail.get(key, 0) or 0)


class GenPerf:
    """Process-global per-tick generation-lane accounting.  All observe
    methods are called from the telemetry spine's off-path drainer only;
    they are cheap and never raise."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._zero()

    def _zero(self) -> None:
        self.ticks: Dict[str, int] = {}             # kind -> count
        self.tick_wall: Dict[str, Reservoir] = {}   # kind -> wall seconds
        #: host/device seconds by (kind, phase); "host_other" is the
        #: tick-wall residual no named phase covers
        self.phase_host_s: Dict[Tuple[str, str], float] = {}
        self.phase_device_s: Dict[Tuple[str, str], float] = {}
        self.wall_s = 0.0            # sum of tick walls
        self.host_s = 0.0            # wall - booked device time
        self.device_s = 0.0          # device time (booked_device_s)
        self.bubble_s: Dict[str, float] = {}        # cause -> seconds
        self.bubble_ticks: Dict[str, int] = {}
        self.idle_ticks = 0
        self.idle_wall_s = 0.0
        self.rows = 0                # padded rows dispatched
        self.real_rows = 0           # real rows dispatched
        self.kv_blocks_touched = 0
        # served-decode accounting (decode/spec/mixed ticks only)
        self.decode_device_s = 0.0
        #: a section's counts by its own names (``SERVED_DECODE``,
        #: ``SERVED_PREFILL``) and, beside them, ``STATE_BYTES``
        self.decode: collections.Counter = collections.Counter()
        self.prefill: collections.Counter = collections.Counter()
        self.kv_block_age = Reservoir(1024)   # seconds held at release
        self.kv_blocks_released = 0
        self.tick_errors = 0
        # streamed requests, cumulative (the ``requests`` block)
        self.req_streams = 0         # first chunk handed to the writer
        self.req_admitted = 0        # first sequence admitted
        self.req_first_tokens = 0    # first chunk put on the queue
        self.req_ttft_s = 0.0        # recv -> handed to the writer
        self.req_stage_s = dict.fromkeys(REQUEST_STAGES, 0.0)
        #: counts[i] holds edges[i-1] <= ttft < edges[i]; the first bucket
        #: is everything under 1 ms, the last everything from 60 s up
        self.req_ttft_hist = [0] * (len(TTFT_EDGES_MS) + 1)

    # -- feeding (spine drainer only) ------------------------------------

    def observe_tick(self, kind: str, detail: Dict[str, Any]) -> None:
        """Fold one enriched HOP_GEN_STEP record.  ``detail`` is the
        dict the scheduler attached to ``SPINE.record_gen_step`` — see
        runtime/genserver.py ``_publish`` for the producing side."""
        wall = float(detail.get("wall_s", 0.0))
        device = float(detail.get("device_s", 0.0))
        host = max(wall - device, 0.0)
        bubble = float(detail.get("bubble_s", 0.0))
        cause = str(detail.get("bubble_cause", "") or "")
        phases = detail.get("phases") or {}
        dev_phases = detail.get("device_phases") or {}
        kv_ages = detail.get("kv_ages") or ()
        with self._lock:
            self.ticks[kind] = self.ticks.get(kind, 0) + 1
            res = self.tick_wall.get(kind)
            if res is None:
                res = self.tick_wall[kind] = Reservoir(512)
            self.wall_s += wall
            self.host_s += host
            self.device_s += device
            if kind == "idle":
                self.idle_ticks += 1
                self.idle_wall_s += wall
            if cause in BUBBLE_CAUSES and bubble > 0:
                self.bubble_s[cause] = self.bubble_s.get(cause, 0.0) + bubble
                self.bubble_ticks[cause] = self.bubble_ticks.get(cause, 0) + 1
            named_host = 0.0
            for phase, secs in phases.items():
                dev = float(dev_phases.get(phase, 0.0))
                h = max(float(secs) - dev, 0.0)
                named_host += float(secs)
                key = (kind, phase)
                self.phase_host_s[key] = self.phase_host_s.get(key, 0.0) + h
                if dev > 0:
                    self.phase_device_s[key] = (
                        self.phase_device_s.get(key, 0.0) + dev)
            residual = max(wall - named_host, 0.0)
            if residual > 0:
                key = (kind, "host_other")
                self.phase_host_s[key] = (
                    self.phase_host_s.get(key, 0.0) + residual)
            self.rows += int(detail.get("rows", 0) or 0)
            self.real_rows += int(detail.get("real_rows", 0) or 0)
            self.kv_blocks_touched += int(detail.get("kv_blocks", 0) or 0)
            if kind in ("decode", "spec", "mixed"):
                self.decode_device_s += float(
                    dev_phases.get("decode", 0.0))
                _fold(self.decode, SERVED_DECODE, detail)
            _fold(self.prefill, SERVED_PREFILL, detail)
            for kind in STATE_KINDS:
                state = 2 * int(detail.get(kind + "_row_bytes", 0) or 0)
                self.decode[kind + "_state_bytes"] += state * int(
                    detail.get("row_passes", 0) or 0)
                self.prefill[kind + "_state_bytes"] += state * int(
                    detail.get("prefill_rows", 0) or 0)
            for n_blocks, age_s in kv_ages:
                self.kv_blocks_released += int(n_blocks)
                self.kv_block_age.observe(float(age_s))
            # streamed requests' scheduler-side stages, one value per
            # request that was admitted / got its first chunk in this tick
            waits = detail.get("req_queue_s")
            if waits:
                self.req_admitted += len(waits)
                self.req_stage_s["queue"] += float(sum(waits))
            waits = detail.get("req_prefill_s")
            if waits:
                self.req_first_tokens += len(waits)
                self.req_stage_s["prefill"] += float(sum(waits))
        # reservoirs take their own lock; observe outside ours
        res.observe(wall)

    def observe_stream_first(self, lane_in_s: float, lane_out_s: float,
                             ttft_s: float) -> None:
        """Fold the HTTP lane's side of one stream's first chunk
        (``engine.generate_stream``, once per stream, off the scheduler
        thread): recv -> submit, first chunk queued -> handed to the
        writer, and the whole recv -> writer time."""
        with self._lock:
            self.req_streams += 1
            self.req_ttft_s += ttft_s
            self.req_stage_s["lane_in"] += lane_in_s
            self.req_stage_s["lane_out"] += lane_out_s
            self.req_ttft_hist[
                bisect.bisect_right(TTFT_EDGES_MS, ttft_s * 1e3)] += 1

    def observe_tick_error(self) -> None:
        with self._lock:
            self.tick_errors += 1

    # -- derived figures --------------------------------------------------

    def served_decode(self) -> Dict[str, Any]:
        """Served decode MFU / HBM-BW utilization over the fenced decode
        device time, priced with the perf observatory's registered
        decode-step cost features against REAL tokens.  All-null when the
        scheduler never registered features or no decode tick ran."""
        from seldon_core_tpu.utils.perf import OBSERVATORY

        with self._lock:
            dev_s = self.decode_device_s
            counts = _section(self.decode, (*SERVED_DECODE, *STATE_BYTES))
        tokens, steps = counts["real_tokens"], counts["device_steps"]
        kv_pos = counts["kv_positions"]
        out: Dict[str, Any] = {
            "decode_device_s": round(dev_s, 4),
            **counts,
            "served_decode_mfu_pct": None,
            "served_decode_hbm_bw_util_pct": None,
            "served_decode_tok_s_device": (
                round(tokens / dev_s, 1) if dev_s > 0 else None
            ),
        }
        cost = OBSERVATORY.cost_features("gen_decode_step")
        if not cost or dev_s <= 0 or tokens <= 0:
            return out
        peaks = OBSERVATORY.peaks()
        flops = tokens * float(cost.get("flops", 0.0))
        if flops > 0 and peaks.get("peak_bf16_tflops"):
            out["served_decode_mfu_pct"] = round(
                100.0 * flops / dev_s / (peaks["peak_bf16_tflops"] * 1e12),
                4)
        # bytes: every device step streams the matmul'd weights once,
        # plus the cache positions the batch's block tables cover
        nbytes = (steps * float(cost.get("bytes_accessed", 0.0))
                  + kv_pos * float(cost.get("kv_bytes_per_position", 0.0)))
        if nbytes > 0 and peaks.get("peak_hbm_gbs"):
            out["served_decode_hbm_bw_util_pct"] = round(
                100.0 * nbytes / dev_s / (peaks["peak_hbm_gbs"] * 1e9), 4)
        return out

    def bubble_fraction(self) -> Optional[float]:
        """Bubble seconds / (tick wall + bubble seconds) — the share of
        scheduler wall the device spent waiting between ticks."""
        with self._lock:
            bubble = sum(self.bubble_s.values())
            total = self.wall_s + bubble
        if total <= 0:
            return None
        return bubble / total

    def document(self) -> Dict[str, Any]:
        """The aggregator's half of the ``GET /genperf`` body."""
        with self._lock:
            bubble = sum(self.bubble_s.values())
            total_wall = self.wall_s + bubble
            doc: Dict[str, Any] = {
                "ticks": dict(self.ticks),
                "tick_wall_ms": {
                    kind: {
                        k: round(v * 1e3, 3)
                        for k, v in res.snapshot().items()
                        if k in ("mean", "p50", "p95", "p99", "max")
                    }
                    for kind, res in self.tick_wall.items()
                },
                "phases": {
                    "host_s": {
                        f"{kind}/{phase}": round(v, 4)
                        for (kind, phase), v in self.phase_host_s.items()
                    },
                    "device_s": {
                        f"{kind}/{phase}": round(v, 4)
                        for (kind, phase), v in self.phase_device_s.items()
                    },
                },
                "accounting": {
                    # host + device + bubble vs scheduler wall — the demo
                    # artifact's >= 95 % criterion reads this block
                    "scheduler_wall_s": round(total_wall, 4),
                    "host_s": round(self.host_s, 4),
                    "device_s": round(self.device_s, 4),
                    "bubble_s": round(bubble, 4),
                    "accounted_fraction": (
                        round((self.host_s + self.device_s + bubble)
                              / total_wall, 4)
                        if total_wall > 0 else None
                    ),
                },
                "bubbles": {
                    "by_cause_s": {
                        k: round(v, 4) for k, v in self.bubble_s.items()
                    },
                    "by_cause_ticks": dict(self.bubble_ticks),
                    "fraction": (
                        round(bubble / total_wall, 4)
                        if total_wall > 0 else None
                    ),
                },
                "idle": {
                    "ticks": self.idle_ticks,
                    "wall_s": round(self.idle_wall_s, 4),
                    # a hot-spinning scheduler pushes this toward 1.0
                    "duty_cycle": (
                        round(self.idle_wall_s / total_wall, 4)
                        if total_wall > 0 else None
                    ),
                },
                "rows": {
                    "padded_total": self.rows,
                    "real_total": self.real_rows,
                    "real_fraction": (
                        round(self.real_rows / self.rows, 4)
                        if self.rows > 0 else None
                    ),
                },
                "kv": {
                    "blocks_touched_total": self.kv_blocks_touched,
                    "blocks_released_total": self.kv_blocks_released,
                    "block_age_s": self.kv_block_age.snapshot(),
                },
                "tick_errors_total": self.tick_errors,
                # cumulative, never rounded: a reader takes the delta of
                # two documents (docs/operations.md "reading /genperf")
                "requests": {
                    "streams": self.req_streams,
                    "admitted": self.req_admitted,
                    "first_tokens": self.req_first_tokens,
                    "ttft_s": self.req_ttft_s,
                    "stage_s": dict(self.req_stage_s),
                    "ttft_ms_hist": {
                        "edges_ms": list(TTFT_EDGES_MS),
                        "counts": list(self.req_ttft_hist),
                    },
                },
            }
            doc["served_prefill"] = _section(
                self.prefill, (*SERVED_PREFILL, *STATE_BYTES))
        doc["served_decode"] = self.served_decode()
        return doc

    def publish_gauges(self) -> None:
        """Refresh the derived Prometheus gauges — called from the
        spine's throttled ``_refresh_gauges`` (~1/s), never per tick."""
        served = self.served_decode()
        mfu = served.get("served_decode_mfu_pct")
        if mfu is not None:
            RECORDER.set_gen_served_mfu(mfu / 100.0)

    def reset(self) -> None:
        """Fresh state — tests only."""
        with self._lock:
            self._zero()


GENPERF = GenPerf()


# ---------------------------------------------------------------------------
# The boot timeline: what a process did from its start to the request it is
# serving now, behind ``GET /stats`` ``boot`` (docs/operations.md "Watching a
# rolling update").  Written a few dozen times a boot -- by the entry point
# (runtime/engine_main.py), the engine's construction (runtime/engine.py) and
# the scheduler's ``_init_device`` and first dispatches (runtime/genserver.py
# ``_BootPhase``: the same interval is a profiler annotation) -- and never by
# a tick that dispatches a shape it has dispatched before.
# ---------------------------------------------------------------------------


def _process_start() -> float:
    """When this process started, on ``time.monotonic()``'s axis: the
    kernel's own stamp (``/proc/self/stat`` field 22, against ``/proc/
    uptime`` of the same filesystem, 10 ms fine) where there is one, else
    now -- the first stamp this module can take."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            age = float(f.read().split()[0]) - started / os.sysconf(
                "SC_CLK_TCK")
        return now - age if age >= 0.0 else now
    except (OSError, ValueError, IndexError):
        return now


def _union_s(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of ``(start, end)``."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class BootTimeline:
    """Spans ``{name, parent, start_s, end_s}`` in seconds since the process
    started, on ``time.monotonic()`` (system-wide on Linux: a harness that
    spawned the process reads them on its own clock with no offset), the
    programs a scheduler's boot loaded ahead ``{kind, shape, trace_s, load_s,
    from_cache | error}`` and each shape's first dispatch ``{kind, shape,
    loaded, host_s, from_cache}``.  An entry is appended whole, under the
    lock, when what it describes has ended.  What a scheduler writes carries
    its ``server`` number (``server()``: one a ``GenServer._init_device``),
    so that a process of several -- the tests' -- reads each one's own."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.process_start = _process_start()
        #: writer (None: the process itself) -> its "spans", "programs" and
        #: "first" dispatches, each in the order they ended
        self._kept: Dict[Optional[int], Dict[str, list]] = {}
        self._servers = 0
        #: entries written so far (tests: a tick over a known shape adds 0)
        self.calls = 0

    def server(self) -> int:
        with self._lock:
            self._servers += 1
            return self._servers

    def span(self, name: str, parent: Optional[str], start: float,
             end: float, server: Optional[int] = None, **note: Any) -> None:
        """``start`` and ``end`` are ``time.monotonic()`` stamps."""
        entry = {"name": name, "parent": parent,
                 "start_s": round(start - self.process_start, 6),
                 "end_s": round(end - self.process_start, 6), **note}
        self._append("spans", entry, server)

    def program(self, server: int, entry: dict) -> None:
        self._append("programs", entry, server)

    def first_dispatch(self, server: int, entry: dict) -> None:
        self._append("first", entry, server)

    def _append(self, which: str, entry: dict, server: Optional[int]) -> None:
        with self._lock:
            self.calls += 1
            self._kept.setdefault(server, {}).setdefault(
                which, []).append(entry)

    def _of(self, which: str, server: Optional[int]) -> List[dict]:
        """The process's own entries with ``server``'s behind them, as a
        new list; the lock is held."""
        return [e for owner in dict.fromkeys((None, server))
                for e in self._kept.get(owner, {}).get(which, ())]

    def load_seconds(self, server: int) -> Tuple[float, float]:
        """``(boot_load_s, boot_trace_s)`` of /stats ``genserver.programs``:
        the ``load`` span's wall and the tracer thread's seconds in it."""
        with self._lock:
            kept = self._kept.get(server, {})
            load = sum((e["end_s"] - e["start_s"]
                        for e in kept.get("spans", ())
                        if e["name"] == "load"), 0.0)
            trace = sum((e["trace_s"] for e in kept.get("programs", ())), 0.0)
        return load, trace

    def document(self, server: Optional[int] = None, serving_s: float = 0.0,
                 waiting_s: float = 0.0) -> Dict[str, Any]:
        """The ``boot`` block of ``GET /stats``, built from the kept lists:
        the process's own spans and those of scheduler ``server``, whose
        seconds inside ticks and inside idle waits since its
        ``device_init`` ended are ``serving_s`` and ``waiting_s``."""
        uptime = time.monotonic() - self.process_start
        with self._lock:
            spans, programs, first = (
                self._of(which, server)
                for which in ("spans", "programs", "first"))
        split = {}
        for key, loaded in (("loaded", True), ("missed", False)):
            mine = [e for e in first if e["loaded"] is loaded]
            split[key] = {"n": len(mine),
                          "host_s": round(
                              sum((e["host_s"] for e in mine), 0.0), 6)}
        split["missed"]["from_cache"] = sum(
            1 for e in first if not e["loaded"] and e.get("from_cache"))
        top = [(e["start_s"], e["end_s"]) for e in spans
               if e["parent"] is None]
        return {
            "clock": "monotonic",
            "process_start": round(self.process_start, 6),
            "uptime_s": round(uptime, 6),
            "spans": spans,
            "programs": programs,
            "first_dispatch": split,
            "serving_s": round(serving_s, 6),
            "waiting_s": round(waiting_s, 6),
            "accounted_s": round(_union_s(top) + serving_s + waiting_s, 6),
        }


BOOT = BootTimeline()
