"""TPU performance observatory — per-executable cost analysis, MFU and
roofline accounting, HBM watermarks, and metric↔trace exemplars.

The flight recorder (utils/telemetry.py) says how many requests flow and
the causal tracer (utils/tracing.py) says where time goes, but neither
says whether the TPU itself is being used well: a dispatch running at 4%
MFU looks identical to one at 55%.  This module closes that gap with the
cost features XLA already computes for free:

  * **Compile time**: every compiled executable's static cost features —
    FLOPs, bytes accessed, output bytes — come from
    ``lowered.compile().cost_analysis()`` ("A Learned Performance Model
    for TPUs", arxiv 2008.01040, and "TpuGraphs", arxiv 2308.13490, both
    show these graph-level features predict real latency well).  Backends
    where cost analysis yields nothing degrade to latency-only rows.
    Compile wall time is recorded per executable alongside.
  * **Dispatch time**: measured wall time combines with the static
    features into achieved TFLOP/s, achieved GB/s, MFU against the
    device-kind-matched advertised peak (utils/chips.py), and a roofline
    classification:
    compute-bound vs memory-bound by which peak binds first,
    overhead-bound when measured time exceeds the roofline prediction by
    ``SELDON_TPU_PERF_OVERHEAD_X`` (the dispatch is dominated by
    host overhead, not device work).
  * **Anomalies**: ``seldon_tpu_perf_anomaly_total{kind}`` fires when a
    dispatch drifts past ``SELDON_TPU_PERF_ANOMALY_FACTOR`` x its own
    executable's rolling p50 (``kind="slow_dispatch"``) or its rolling
    measured/predicted ratio (``kind="ratio_drift"``) — per-executable
    baselines, so the detector needs no hardware-specific tuning.
  * **HBM watermarks**: ``device.memory_stats()`` (bytes in use, peak,
    limit) polled into ``seldon_tpu_hbm_*`` gauges, tolerating backends
    (CPU) where it returns nothing.

Surfaces: ``GET /perf`` (engine + unit, every REST lane) renders the
per-executable table; ``seldon_tpu_dispatch_seconds`` histogram
observations carry OpenMetrics exemplars with the active ``trace_id`` so
a slow bucket links straight to its PR-3 trace; dispatch spans gain
``flops`` / ``mfu`` / ``bound`` attributes so ``/trace`` critical paths
show hardware efficiency inline.

Everything is process-global (module global ``OBSERVATORY``, the
``RECORDER``/``TRACER`` pattern) and never raises into the hot path.
``SELDON_TPU_PERF=0`` disables capture entirely.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from seldon_core_tpu.utils.chips import chip_peak_hbm_gbs, chip_peak_tflops
from seldon_core_tpu.utils.telemetry import RECORDER, Reservoir

__all__ = [
    "PerfObservatory",
    "OBSERVATORY",
    "executable_key",
    "extract_cost_features",
]


@functools.lru_cache(maxsize=1024)
def executable_key(name: str, shape, dtype) -> str:
    """Canonical per-executable identity: program name + input shape +
    post-canonicalization dtype (x64 demotion means the dtype that actually
    compiled, not the dtype the client sent).  Shared by the compile-time
    capture (graph/compiled.py) and the dispatch-time observation
    (runtime/engine.py) so both sides name the same executable.  Cached:
    the dispatch hot path names its executable twice per batch (once per
    side), and dtype canonicalization + string building should cost a
    dict hit, not a jax call."""
    try:
        from jax import dtypes as _jdt

        dtype = _jdt.canonicalize_dtype(np.dtype(dtype))
    except Exception:  # noqa: BLE001 - jax unavailable: raw dtype is fine
        pass
    return "%s[%s/%s]" % (
        name, "x".join(str(int(d)) for d in shape), np.dtype(dtype).name
    )


def extract_cost_features(cost: Any) -> Optional[Dict[str, float]]:
    """Normalize whatever ``cost_analysis()`` returned — a dict, a list of
    dicts (one per partition), or nothing — into
    ``{flops, bytes_accessed, output_bytes}``.  Returns None when the
    backend yields no usable features (the caller degrades to
    latency-only accounting); negative/zero FLOPs count as absent (some
    backends report -1 for "unknown")."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    flops = cost.get("flops")
    bytes_accessed = cost.get("bytes accessed")
    output_bytes = None
    for k in ("bytes accessed output", "bytes accessedout{}"):
        if k in cost:
            output_bytes = cost[k]
            break
    out: Dict[str, float] = {}
    if flops is not None and float(flops) > 0:
        out["flops"] = float(flops)
    if bytes_accessed is not None and float(bytes_accessed) > 0:
        out["bytes_accessed"] = float(bytes_accessed)
    if output_bytes is not None and float(output_bytes) > 0:
        out["output_bytes"] = float(output_bytes)
    return out or None


class _ExecutableStats:
    """Everything the observatory knows about one compiled executable."""

    __slots__ = (
        "key", "cost", "compile_s", "calls", "rows_total", "latency",
        "ratio", "calibration", "last", "anomalies", "phases",
    )

    def __init__(self, key: str):
        self.key = key
        self.cost: Optional[Dict[str, float]] = None
        self.compile_s: Optional[float] = None
        #: fused-graph per-node phase decomposition ({node: share of the
        #: program's FLOPs}, graph/fuse.py) — how a one-program-per-graph
        #: executable still itemizes on the /perf table
        self.phases: Optional[Dict[str, float]] = None
        self.calls = 0
        self.rows_total = 0
        self.latency = Reservoir(512)
        #: rolling measured/predicted ratios — the drift baseline
        self.ratio = Reservoir(512)
        #: rolling measured / (overhead-adjusted roofline) ratios — the
        #: per-pad-bucket calibration the autopilot's seed prior uses
        self.calibration = Reservoir(256)
        #: most recent derived figures (mfu, tflops, gbs, bound, ratio)
        self.last: Dict[str, Any] = {}
        self.anomalies = 0


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


class PerfObservatory:
    """Process-global per-executable performance accounting.  All record
    methods are cheap and never raise — instrumentation must not grow
    failure modes on the dispatch hot path."""

    #: bounded executable table: an exploding shape set must not grow
    #: memory; overflow dispatches aggregate under one key
    MAX_EXECUTABLES = 64
    OVERFLOW_KEY = "other"

    def __init__(
        self,
        enabled: Optional[bool] = None,
        anomaly_factor: Optional[float] = None,
        overhead_x: Optional[float] = None,
        min_calls: int = 10,
        hbm_poll_interval_s: float = 5.0,
    ):
        if enabled is None:
            enabled = os.environ.get("SELDON_TPU_PERF", "1") != "0"
        self.enabled = bool(enabled)
        #: a dispatch beyond factor x its executable's rolling p50 (or
        #: rolling ratio median) is an anomaly
        self.anomaly_factor = (
            anomaly_factor
            if anomaly_factor is not None
            else _env_float("SELDON_TPU_PERF_ANOMALY_FACTOR", 3.0)
        )
        #: measured/predicted beyond this classifies overhead-bound: the
        #: device work the roofline prices is a sliver of the wall time
        self.overhead_x = (
            overhead_x
            if overhead_x is not None
            else _env_float("SELDON_TPU_PERF_OVERHEAD_X", 10.0)
        )
        self.min_calls = int(min_calls)
        self.hbm_poll_interval_s = float(hbm_poll_interval_s)
        self._lock = threading.Lock()
        self._execs: Dict[str, _ExecutableStats] = {}
        #: micro-batcher padding accounting (runtime/batching.py): pad rows
        #: are pure waste FLOPs — the compiler fodder share of device work
        self.real_rows_total = 0
        self.pad_rows_total = 0
        self._peaks: Optional[Dict[str, Any]] = None
        self._hbm_last_poll = 0.0
        self._hbm_last: List[Dict[str, Any]] = []
        #: telemetry-spine wiring (utils/hotrecord.py), set on the global
        #: OBSERVATORY only: dispatch observations arrive via the fused
        #: per-hop record, so query surfaces fold pending records first
        self.drain_hook = None

    def _drain(self) -> None:
        if self.drain_hook is not None:
            self.drain_hook()

    # -- device peaks ------------------------------------------------------

    def peaks(self) -> Dict[str, Any]:
        """Device identity + advertised peaks of this process's backend
        (lazy; cached).  A ``device_kind`` that is not in the chip table
        (utils/chips.py) has NO peak: both peak fields are ``None`` and
        every figure normalized against them is absent from /perf
        rather than computed against another chip's numbers."""
        if self._peaks is not None:
            return self._peaks
        import jax

        dev = jax.devices()[0]
        device_kind = str(getattr(dev, "device_kind", dev))
        self._peaks = {
            "device_kind": device_kind,
            "platform": str(getattr(dev, "platform", "")),
            "peak_bf16_tflops": chip_peak_tflops(device_kind),
            "peak_hbm_gbs": chip_peak_hbm_gbs(device_kind),
        }
        return self._peaks

    # -- recording ---------------------------------------------------------

    def _entry(self, key: str) -> _ExecutableStats:
        ent = self._execs.get(key)
        if ent is None:
            with self._lock:
                ent = self._execs.get(key)
                if ent is None:
                    if len(self._execs) >= self.MAX_EXECUTABLES:
                        key = self.OVERFLOW_KEY
                        ent = self._execs.get(key)
                        if ent is None:
                            ent = self._execs[key] = _ExecutableStats(key)
                        return ent
                    ent = self._execs[key] = _ExecutableStats(key)
        return ent

    def record_compile(
        self,
        key: str,
        cost: Optional[Dict[str, float]],
        compile_s: Optional[float],
    ) -> None:
        """Static cost features + compile wall time for one executable
        (called once per compiled shape, graph/compiled.py)."""
        if not self.enabled:
            return
        ent = self._entry(key)
        with self._lock:
            # the shared overflow entry must not carry any one shape's
            # cost features — derived figures for unrelated shapes would
            # divide by the wrong FLOP count
            if cost is not None and ent.key != self.OVERFLOW_KEY:
                ent.cost = dict(cost)
            if compile_s is not None:
                ent.compile_s = float(compile_s)
        if compile_s is not None:
            # when the jax.monitoring listener is live (compile cache
            # enabled in this process) it already observed this backend
            # compile — recording here too would double-count every AOT
            # compile in seldon_tpu_compile_seconds
            from seldon_core_tpu.utils import telemetry as _telemetry

            if not _telemetry._compile_listener_installed:
                RECORDER.record_compile_seconds(compile_s)

    def note_phases(self, key: str, phases: Dict[str, float]) -> None:
        """Attach a fused graph's per-node phase decomposition to one
        executable row (graph/fuse.py) so the /perf table itemizes a
        one-program-per-graph dispatch per node."""
        if not self.enabled or not phases:
            return
        ent = self._entry(key)
        with self._lock:
            if ent.key != self.OVERFLOW_KEY:
                ent.phases = dict(phases)

    def observe_dispatch(
        self,
        key: str,
        seconds: float,
        rows: Optional[int] = None,
        trace_id: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Combine one measured dispatch with the executable's static cost
        features.  Returns the derived figures (mfu/bound/flops/...) so
        the caller can stamp them onto its dispatch span; {} when the
        observatory is disabled."""
        if not self.enabled or seconds <= 0:
            return {}
        ent = self._entry(key)
        overflow = ent.key == self.OVERFLOW_KEY
        # anomaly baselines BEFORE this observation joins the window
        base = ent.latency.snapshot() if ent.calls >= self.min_calls else None
        ratio_base = (
            ent.ratio.snapshot() if len(ent.ratio) >= self.min_calls else None
        )
        ent.latency.observe(seconds)
        with self._lock:
            ent.calls += 1
            if rows:
                ent.rows_total += int(rows)
            cost = None if overflow else ent.cost
        derived: Dict[str, Any] = {}
        slowdown = None  # measured time as a multiple of the roofline
        peaks = self.peaks()
        if cost:
            flops = cost.get("flops", 0.0)
            nbytes = cost.get("bytes_accessed", 0.0)
            if flops:
                derived["flops"] = flops
                derived["achieved_tflops"] = flops / seconds / 1e12
            if nbytes:
                derived["achieved_gbs"] = nbytes / seconds / 1e9
                if flops:
                    derived["arithmetic_intensity"] = flops / nbytes
            # everything below normalizes against the chip's peaks; a
            # device kind with no table row gets none of it
            predicted_s = 0.0
            if peaks["peak_bf16_tflops"] and peaks["peak_hbm_gbs"]:
                peak_flops_s = peaks["peak_bf16_tflops"] * 1e12
                peak_bytes_s = peaks["peak_hbm_gbs"] * 1e9
                t_compute = flops / peak_flops_s if flops else 0.0
                t_memory = nbytes / peak_bytes_s if nbytes else 0.0
                predicted_s = max(t_compute, t_memory)
                if flops:
                    derived["mfu"] = flops / seconds / peak_flops_s
            if predicted_s > 0:
                slowdown = seconds / predicted_s
                derived["predicted_s"] = predicted_s
                # the WALL-time prior is the overhead-adjusted roofline:
                # raw roofline prices device work only, and overhead_x is
                # already the configured device-vs-wall factor (the same
                # one the overhead-bound classification below uses).
                # Using it on BOTH sides keeps this ratio, the per-bucket
                # calibration, and the autopilot's seed prior
                # (seed_predicted_s) in agreement — before this fix the
                # /perf page showed raw-roofline ratios while the
                # overhead classification judged the adjusted time
                adjusted_s = predicted_s * self.overhead_x
                derived["adjusted_predicted_s"] = adjusted_s
                # reads in name order: predicted over measured, 1.0 =
                # wall time exactly at the overhead-adjusted roofline
                derived["predicted_vs_measured"] = adjusted_s / seconds
                ent.calibration.observe(seconds / adjusted_s)
                ent.ratio.observe(slowdown)
                if slowdown > self.overhead_x:
                    derived["bound"] = "overhead"
                else:
                    derived["bound"] = (
                        "compute" if t_compute >= t_memory else "memory"
                    )
        RECORDER.observe_dispatch(
            ent.key, seconds,
            mfu=derived.get("mfu"), trace_id=trace_id,
        )
        # drift detection against the executable's OWN history — no
        # hardware-dependent thresholds.  The shared overflow entry mixes
        # unrelated shapes, so its baselines mean nothing: never fire
        anomaly = None
        if overflow:
            base = ratio_base = None
        if base is not None and base["p50"] > 0:
            if (
                seconds > self.anomaly_factor * base["p50"]
                and seconds - base["p50"] > 1e-3
            ):
                anomaly = "slow_dispatch"
        if (
            anomaly is None
            and slowdown is not None
            and ratio_base is not None
            and ratio_base["p50"] > 0
            and slowdown > self.anomaly_factor * ratio_base["p50"]
        ):
            anomaly = "ratio_drift"
        if anomaly is not None:
            with self._lock:
                ent.anomalies += 1
            derived["anomaly"] = anomaly
            RECORDER.record_perf_anomaly(anomaly)
        with self._lock:
            ent.last = dict(derived)
        return derived

    def seed_predicted_s(self, key: str) -> Optional[float]:
        """The autopilot's seed prior for one executable/pad bucket:
        overhead-adjusted roofline time (``cost_analysis()`` features x
        ``SELDON_TPU_PERF_OVERHEAD_X`` — the same adjusted time
        ``predicted_vs_measured`` reports) scaled by the measured
        calibration ratio — this key's own rolling median when it has
        dispatched, else the median across every calibrated executable
        (so a never-dispatched pad bucket inherits the box's measured
        wall-vs-roofline behaviour).  None when the key has no cost
        features (the autopilot then waits for measurements)."""
        if not self.enabled:
            return None
        ent = self._execs.get(key)
        if ent is None or ent.key == self.OVERFLOW_KEY or not ent.cost:
            return None
        cost = ent.cost
        peaks = self.peaks()
        if not (peaks["peak_bf16_tflops"] and peaks["peak_hbm_gbs"]):
            return None  # no peak for this device kind: no roofline prior
        t_compute = cost.get("flops", 0.0) / (
            peaks["peak_bf16_tflops"] * 1e12
        )
        t_memory = cost.get("bytes_accessed", 0.0) / (
            peaks["peak_hbm_gbs"] * 1e9
        )
        roofline = max(t_compute, t_memory)
        if roofline <= 0:
            return None
        adjusted = roofline * self.overhead_x
        cal = ent.calibration.snapshot()
        if cal["count"]:
            return adjusted * cal["p50"]
        # cross-bucket transfer: the median of every calibrated key's
        # median — one slow shape cannot skew it the way a mean would
        with self._lock:
            entries = list(self._execs.values())
        medians = sorted(
            c["p50"] for c in (e.calibration.snapshot() for e in entries)
            if c["count"]
        )
        if medians:
            return adjusted * medians[len(medians) // 2]
        return adjusted

    def cost_features(self, key: str) -> Optional[Dict[str, float]]:
        """One executable's registered static cost features (or None) —
        the read side of ``record_compile`` for derived-figure consumers
        (the generation flight recorder prices served decode MFU off the
        ``gen_decode_step`` features the scheduler registers)."""
        if not self.enabled:
            return None
        ent = self._execs.get(key)
        if ent is None or not ent.cost:
            return None
        with self._lock:
            return dict(ent.cost)

    def note_padding(self, real_rows: int, padded_rows: int) -> None:
        """Micro-batcher padding accounting: pad rows burn FLOPs without
        serving traffic (runtime/batching.py reports each padded chunk)."""
        if not self.enabled:
            return
        with self._lock:
            self.real_rows_total += int(real_rows)
            self.pad_rows_total += int(padded_rows) - int(real_rows)

    # -- HBM watermarks ----------------------------------------------------

    def hbm_watermarks(self, force: bool = False) -> List[Dict[str, Any]]:
        """Per-device HBM watermarks from ``device.memory_stats()``,
        throttled (memory_stats can be a backend round-trip; scrapes and
        /perf polls share one cached reading per interval).  Backends
        without memory stats (CPU) report ``memory_stats: null`` rows and
        set no gauges — never raises.  ``SELDON_TPU_PERF=0`` really is
        the kill switch: disabled, no backend call happens even from the
        scrape path."""
        if not self.enabled:
            return []
        now = time.monotonic()
        if not force and now - self._hbm_last_poll < self.hbm_poll_interval_s:
            return self._hbm_last
        self._hbm_last_poll = now
        out: List[Dict[str, Any]] = []
        try:
            import jax

            devices = jax.devices()
        except Exception:  # noqa: BLE001 - no backend at all
            self._hbm_last = out
            return out
        for dev in devices:
            label = f"{getattr(dev, 'platform', 'dev')}:{getattr(dev, 'id', 0)}"
            stats = None
            try:
                stats = dev.memory_stats()
            except Exception:  # noqa: BLE001 - backend without memory stats
                stats = None
            if not stats:
                out.append({"device": label, "memory_stats": None})
                continue
            row = {
                "device": label,
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            }
            out.append(row)
            RECORDER.set_hbm(
                label,
                bytes_in_use=row["bytes_in_use"],
                peak_bytes_in_use=row["peak_bytes_in_use"],
                bytes_limit=row["bytes_limit"],
            )
        self._hbm_last = out
        return out

    # -- snapshots ---------------------------------------------------------

    def _row(self, ent: _ExecutableStats) -> Dict[str, Any]:
        lat = ent.latency.snapshot()
        row: Dict[str, Any] = {
            "executable": ent.key,
            "calls": ent.calls,
            "rows": ent.rows_total,
            "latency_ms": {
                k: round(lat[k] * 1e3, 3)
                for k in ("mean", "p50", "p95", "p99", "max")
            },
            "compile_s": (
                None if ent.compile_s is None else round(ent.compile_s, 4)
            ),
            "anomalies": ent.anomalies,
        }
        if ent.phases:
            row["phases"] = dict(ent.phases)
        cost = ent.cost
        if cost:
            row["flops"] = cost.get("flops")
            row["bytes_accessed"] = cost.get("bytes_accessed")
            row["output_bytes"] = cost.get("output_bytes")
            if cost.get("flops") and cost.get("bytes_accessed"):
                row["arithmetic_intensity"] = round(
                    cost["flops"] / cost["bytes_accessed"], 3
                )
        cal = ent.calibration.snapshot()
        if cal["count"]:
            # measured wall / overhead-adjusted roofline, rolling median
            # per pad bucket — 1.0 = the adjusted prior prices this
            # bucket exactly; the autopilot seed (seed_predicted_s) and
            # this figure agree by construction
            row["calibration_ratio"] = float("%.4g" % cal["p50"])
        last = ent.last
        if last:
            for k in ("mfu", "achieved_tflops", "achieved_gbs",
                      "predicted_vs_measured"):
                if k in last:
                    # significant figures, not decimal places: a tiny
                    # dispatch's MFU is legitimately ~1e-8 and must not
                    # round to 0
                    row[k] = float("%.4g" % float(last[k]))
            if "bound" in last:
                row["bound"] = last["bound"]
            if cost and not self.peaks()["peak_bf16_tflops"]:
                # a device kind with no peak: the peak-normalized fields
                # are stated as null, not left for a reader to assume
                row.update(mfu=None, predicted_vs_measured=None, bound=None)
        return row

    def document(self) -> Dict[str, Any]:
        """The ``GET /perf`` body: device identity + peaks, per-executable
        table (calls, latency percentiles, MFU, arithmetic intensity,
        predicted-vs-measured, compile time), batching pad overhead, and
        HBM watermarks."""
        self._drain()
        with self._lock:
            entries = list(self._execs.values())
            real, pad = self.real_rows_total, self.pad_rows_total
        rows = sorted(
            (self._row(e) for e in entries),
            key=lambda r: r["calls"], reverse=True,
        )
        doc: Dict[str, Any] = {
            "enabled": self.enabled,
            "device": self.peaks(),
            "executables": rows,
            "hbm": self.hbm_watermarks(),
            "anomaly_factor": self.anomaly_factor,
            "overhead_x": self.overhead_x,
        }
        if real or pad:
            doc["batching"] = {
                "real_rows_total": real,
                "pad_rows_total": pad,
                "pad_overhead_pct": round(100.0 * pad / max(real + pad, 1), 2),
            }
        return doc

    def snapshot(self) -> Dict[str, Any]:
        """Compact health block for ``/stats`` — the full table lives on
        ``/perf``."""
        self._drain()
        with self._lock:
            n = len(self._execs)
            calls = sum(e.calls for e in self._execs.values())
            anomalies = sum(e.anomalies for e in self._execs.values())
        return {
            "enabled": self.enabled,
            "executables": n,
            "dispatches": calls,
            "anomalies": anomalies,
        }

    def reset(self) -> None:
        """Fresh state — tests only."""
        self._drain()  # pending records fold into the pre-reset state
        with self._lock:
            self._execs = {}
            self.real_rows_total = 0
            self.pad_rows_total = 0
            self._hbm_last_poll = 0.0
            self._hbm_last = []


OBSERVATORY = PerfObservatory()
