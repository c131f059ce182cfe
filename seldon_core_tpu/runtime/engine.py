"""Engine service — the per-predictor orchestrator.

The reference injects one Java engine pod per predictor that interprets the
graph over the network (engine PredictionService.java:69-90,
PredictiveUnitBean.java:58-168).  This engine instead *chooses an execution
strategy* per graph:

  * every node in-process + pure  ->  ``CompiledGraph`` — the whole graph is
    one jitted XLA program on the TPU; per-request overhead is one device
    dispatch.
  * any remote/impure node        ->  host ``GraphExecutor`` with async
    fan-out; remote nodes get pooled REST/gRPC clients (runtime/client.py).

Request handling mirrors the reference: puid assigned if absent and restored
onto the response (PredictionService.java:52-90), pause/ready gating for
graceful drain (engine RestClientController.java:57-99), feedback counters
(PredictiveUnitBean.java:239-242).
"""

from __future__ import annotations

import asyncio
import json as _json
import logging
import os
import time
from typing import Dict, Optional

import numpy as np

from seldon_core_tpu.graph.compiled import CompiledGraph
from seldon_core_tpu.graph.interpreter import GraphExecutor, NodeRuntime, pythonize_tags
from seldon_core_tpu.runtime.batching import (
    GenLane,
    MicroBatcher,
    graph_is_batchable,
)
from seldon_core_tpu.graph.spec import (
    GraphSpecError,
    PredictorSpec,
    SeldonDeploymentSpec,
)
from seldon_core_tpu.messages import (
    DeadlineExceededError,
    DispatchTimeoutError,
    Feedback,
    LoadShedError,
    Meta,
    SeldonMessage,
    SeldonMessageError,
    new_puid,
)
from seldon_core_tpu.runtime.autopilot import (
    AUTOPILOT,
    SHED_INFO_PREFIX,
    autopilot_enabled,
    shed_margin,
)
from seldon_core_tpu.runtime.resilience import (
    CircuitBreaker,
    RetryBudget,
    maybe_deadline_scope,
    remaining_s,
)
from seldon_core_tpu.utils.genperf import BOOT
from seldon_core_tpu.utils.hotrecord import SPINE
from seldon_core_tpu.utils.metrics import MetricsRegistry
from seldon_core_tpu.utils.perf import OBSERVATORY
from seldon_core_tpu.utils.quality import QUALITY, router_quality
from seldon_core_tpu.utils.telemetry import RECORDER, AuditLog

__all__ = ["EngineService"]

logger = logging.getLogger(__name__)


def _brownout_snapshot() -> dict:
    from seldon_core_tpu.runtime.brownout import BROWNOUT

    return BROWNOUT.snapshot()


def _meta_shape_ok(meta_in: dict) -> bool:
    """Fast-path precondition: the request meta must be representable by
    Meta.from_json_dict without coercion errors, otherwise we fall back so
    the object path returns its 400 'malformed meta' (parity with the
    non-native codepath)."""
    if not isinstance(meta_in.get("puid", ""), str):
        return False
    tags = meta_in.get("tags", {}) or {}
    routing = meta_in.get("routing", {}) or {}
    request_path = meta_in.get("requestPath", {}) or {}
    if not (
        isinstance(tags, dict)
        and isinstance(routing, dict)
        and isinstance(request_path, dict)
    ):
        return False
    # the object path coerces routing values via int(v); only plain ints
    # echo back unchanged, so anything else takes the object path
    return all(type(v) is int for v in routing.values())


class EngineService:
    """One engine per predictor; thread-safe for a single asyncio loop."""

    def __init__(
        self,
        deployment: SeldonDeploymentSpec,
        predictor_name: Optional[str] = None,
        extra_runtimes: Optional[Dict[str, NodeRuntime]] = None,
        rng=None,
        force_host: bool = False,
        batching: bool = True,
        max_batch: int = 1024,
        max_wait_ms: float = 2.0,
        pipeline_depth: int = 8,
        dispatch_timeout_s: float = 30.0,
        audit: Optional[AuditLog] = None,
        gen_role: Optional[str] = None,
        decode_peers: Optional[list] = None,
    ):
        from seldon_core_tpu.utils.tracing import TRACER

        # this engine's spans of the boot timeline (utils/genperf.py BOOT;
        # GET /stats ``boot``) are written under its own number, its
        # scheduler's too: a process of several engines reads each one's
        self._boot_owner, t_units = BOOT.server(), time.monotonic()
        self.deployment = deployment
        self.tracer = TRACER
        self.predictor: PredictorSpec = deployment.predictor(predictor_name)
        self.metrics = MetricsRegistry(
            deployment_name=deployment.name,
            predictor_name=self.predictor.name,
            project_name=str(deployment.annotations.get("project_name", "")),
        )
        # request-audit firehose (flight recorder): off unless configured —
        # AuditLog() reads SELDON_TPU_AUDIT / SELDON_TPU_AUDIT_DIR
        self.audit = audit if audit is not None else AuditLog()
        self._graph_path = "/".join(
            n.name for n in self.predictor.graph.walk()
        )
        # boot epoch: a fresh random id per EngineService construction.
        # The gateway's scrape compares it across passes — a CHANGE at
        # the same URL means the process restarted, so every per-replica
        # signal learned about the dead process (EWMA, failure streaks,
        # scraped load) resets instead of poisoning picks
        import secrets as _secrets

        self.boot_id = _secrets.token_hex(8)
        # /stats assembly cache (see stats()): the four observatory walks
        # are rebuilt only when the folded state actually moved
        self._stats_cache = None
        try:
            self._stats_ttl_s = float(
                os.environ.get("SELDON_TPU_STATS_TTL_S", "") or 1.0
            )
        except ValueError:
            self._stats_ttl_s = 1.0
        # quality observatory identity: the compiled lane dispatches the
        # WHOLE graph as one program, so its drift windows key on the
        # graph root (host mode / unit pods record per node instead)
        self._quality_node = self.predictor.graph.name
        self.paused = False
        # compiled-mode state advances via read-modify-write of
        # CompiledGraph.states; serialize device dispatches so concurrent
        # requests can't double-spend a PRNG key or drop a bandit update.
        # Stateless graphs get a semaphore instead (set below): device
        # dispatch has a fixed sync cost, and the runtime overlaps several
        # in-flight batches to hide it (throughput ~= depth x single-stream)
        self.dispatch_timeout_s = float(dispatch_timeout_s)
        self._device_lock = asyncio.Lock()
        self._pipelined = False
        # feature widths that have served successfully: a dispatch failure
        # on a known-good width is a server bug (500), on a novel width a
        # client shape error (400)
        self._known_good_widths: set = set()
        self.mode = "host"
        self.compiled: Optional[CompiledGraph] = None
        self.executor: Optional[GraphExecutor] = None
        # whole-graph fusion (graph/fuse.py): the default dispatch path
        # for fuse-eligible graphs — one XLA program per predictor, with
        # in-program autopilot branch demotion.  SELDON_TPU_GRAPH_FUSE=0
        # is the kill switch: fully-eligible graphs fall back to the
        # legacy compiled executor, everything else to the pure
        # interpreter — the pre-fusion dispatch, bit-for-bit.
        from seldon_core_tpu.graph.fuse import FusedGraph, fuse_enabled

        self._fuse = fuse_enabled() and not force_host
        self.fusion_plan = None
        multi_node = bool(self.predictor.graph.children)
        if not force_host and not extra_runtimes:
            if self._fuse and multi_node:
                # multi-node graphs get the fused program (single
                # nodes have no hops to fuse — the legacy compiled
                # executor is already one program for those)
                try:
                    fg = FusedGraph(self.predictor, rng=rng)
                    self.compiled = fg
                    self.fusion_plan = fg.plan
                    self.mode = "fused"
                except GraphSpecError:
                    # not fully fuse-eligible (opt-out annotation, an
                    # impure unit, a degradation policy): the legacy
                    # compiled executor is still the right one-program
                    # path whenever it applies — fall through, keeping
                    # the plan so /stats names what blocked fusion
                    from seldon_core_tpu.graph.fuse import plan_fusion

                    self.fusion_plan = plan_fusion(self.predictor)
            if self.compiled is None:
                try:
                    self.compiled = CompiledGraph(self.predictor, rng=rng)
                    self.mode = "compiled"
                except GraphSpecError:
                    pass
        # resilience layer: ONE retry budget shared by every node client of
        # this predictor (retries cannot amplify an outage across the
        # fan-out) and one circuit breaker per remote node
        self.retry_budget = RetryBudget()
        self.breakers: Dict[str, CircuitBreaker] = {}
        if self.compiled is None:
            # remote rest/grpc bindings get pooled clients automatically
            runtimes = dict(extra_runtimes or {})
            comp_map = self.predictor.component_map()
            for node in self.predictor.graph.walk():
                binding = comp_map.get(node.name)
                if (
                    node.name not in runtimes
                    and binding is not None
                    and binding.runtime in ("rest", "grpc")
                ):
                    from seldon_core_tpu.runtime.client import make_node_runtime

                    breaker = CircuitBreaker(node.name)
                    self.breakers[node.name] = breaker
                    runtimes[node.name] = make_node_runtime(
                        node, binding,
                        breaker=breaker, retry_budget=self.retry_budget,
                    )
            # runtimes supplied by the caller may carry their own breaker
            # (e.g. tests wiring RestNodeRuntime directly) — surface those
            # through /stats and /ready too
            for name, rt in runtimes.items():
                br = getattr(rt, "breaker", None)
                if br is not None and name not in self.breakers:
                    self.breakers[name] = br
            self.executor = GraphExecutor(
                self.predictor, extra_runtimes=runtimes, rng=rng,
                # partial fusion: maximal fuse-eligible subtrees (a
                # remote/rest-bound leaf, quorum/fallback policy, or
                # impure unit keeps ITS subtree on the interpreter)
                # collapse to one device dispatch each
                fuse=self._fuse,
            )
            self.fusion_plan = self.executor.fusion_plan
        # continuous-batching generation lane (runtime/genserver.py): a
        # single-generator graph serves through a paged-KV per-step
        # scheduler instead of per-request generate() — streams admit into
        # the in-flight decode batch, prompts prefill in chunks, and the
        # int8-KV/prefix/speculative levers ride the actual serving path.
        # SELDON_TPU_GEN_CONTINUOUS=0 is the kill switch (static path).
        # disaggregated serving mesh (runtime/servingmesh.py): this
        # replica's generation role.  "unified" is the PR-7 scheduler;
        # "prefill" exports finished KV blocks to decode peers over the
        # relay; "decode" only imports handoffs.  SELDON_TPU_DISAGG=0
        # forces unified — the kill switch, bit-for-bit.
        from seldon_core_tpu.runtime.servingmesh import (
            parse_decode_peers,
            resolve_gen_role,
        )

        self.gen_role = resolve_gen_role(gen_role)
        self._decode_peers = (
            list(decode_peers) if decode_peers is not None
            else parse_decode_peers()
        )
        self.genserver = None
        if (
            self.compiled is not None
            and len(self.compiled.units) == 1
            and os.environ.get("SELDON_TPU_GEN_CONTINUOUS", "1") != "0"
        ):
            uname, unit = next(iter(self.compiled.units.items()))
            spec_fn = getattr(unit, "continuous_spec", None)
            # a unit that returns None declares it cannot be continuously
            # scheduled (MoE capacity routing couples co-batched rows) and
            # keeps the static lane; a spec whose scheduler cannot be
            # BUILT fails engine construction — the static lane is never
            # a silent substitute for a broken main path
            cs = (spec_fn(self.compiled.states[uname])
                  if spec_fn is not None else None)
            if cs is not None:
                from seldon_core_tpu.runtime.genserver import GenServer

                coordinator = None
                if self.gen_role == "prefill" and self._decode_peers:
                    from seldon_core_tpu.runtime.servingmesh import (
                        DisaggCoordinator,
                    )

                    coordinator = DisaggCoordinator(
                        self._decode_peers,
                        event_sink=self._handoff_event,
                    )
                self.genserver = GenServer(
                    **cs, role=self.gen_role, coordinator=coordinator,
                )
                self.genserver.boot_server = self._boot_owner
                # deployment identity for the cost ledger's per-tick
                # attribution (utils/costledger.py)
                self.genserver.cost_deployment = self.deployment.name
        if self.genserver is None:
            # a role without a scheduler cannot serve its contract —
            # surface as unified so routing/metrics stay truthful
            self.gen_role = "unified"
        # micro-batching: coalesce concurrent requests into one device
        # dispatch (router-free compiled graphs only — routing is a
        # per-request decision in the reference semantics).  Generator
        # graphs with a scheduler take the GenLane bypass instead: the
        # MicroBatcher's whole-batch dispatch unit is exactly what
        # continuous batching replaces.
        self.batcher = None
        use_gen_lane = self.genserver is not None and batching
        if use_gen_lane:
            self.batcher = GenLane(self.genserver, max_batch=max_batch)
        if self.batcher is None and (
            self.compiled is not None
            and batching
            and graph_is_batchable(self.predictor.graph)
            # cross-row-coupled units (batch-global reductions) would let one
            # caller's rows change another caller's answer if coalesced
            and not any(u.batch_coupled for u in self.compiled.units.values())
        ):
            # padding to power-of-two batch shapes avoids per-size retraces,
            # but must not feed fake rows into streaming statistics
            pad_ok = not any(
                u.updates_state_on_predict for u in self.compiled.units.values()
            )
            # when no unit updates state on predict (pad_ok), dispatches are
            # order-independent reads — the batcher pipelines several
            # in-flight stacks to hide dispatch RTT, and predict_arrays skips
            # its state write-back so a stale write can't clobber a
            # concurrent feedback update (weights-only state is read-only at
            # predict time).  Streaming-stats graphs keep max_inflight=1 +
            # the exclusive device lock
            self._pipelined = pad_ok and pipeline_depth > 1
            self.batcher = MicroBatcher(
                self._batched_predict,
                max_batch=max_batch,
                max_wait_ms=max_wait_ms,
                pad_to_buckets=pad_ok,
                max_inflight=pipeline_depth if self._pipelined else 1,
                # backstop slightly above the per-request deadline: frees
                # the in-flight slot of a wedged dispatch after callers got
                # their 504s.  Safe for stateful graphs too: abandonment
                # happens at 1.5x the deadline, so any late write-back is
                # post-deadline and the completion-forcing state gate
                # vetoes it
                dispatch_timeout_s=self.dispatch_timeout_s * 1.5,
                # stateful graphs must apply state atomically per request
                atomic_chunks=not pad_ok,
                # learned cost-model autopilot: predictive flush sizing
                # reads per-pad-bucket latency predictions through this
                # hook (kill switch checked inside the batcher, so
                # SELDON_TPU_AUTOPILOT=0 keeps flush-all bit-for-bit)
                predict_s_fn=self._predict_dispatch_s,
            )
            # deployment identity for flush-record cost attribution
            self.batcher.cost_deployment = self.deployment.name
        if self.batcher is not None:
            # batchable graphs have no routers, so the executed path — and
            # therefore the output names — never varies per request
            self._static_names = self.compiled._output_names(
                self.predictor.graph, {}
            )
            # precomputed fragments for the wire-to-wire fast path
            import json as _json

            self._names_fragment = (
                '"names":%s,' % _json.dumps(list(self._static_names))
                if self._static_names
                else ""
            )
            from seldon_core_tpu.native.protowire import (
                build_tensor_response,
                names_fragment,
                parse_tensor_request,
            )

            self._proto_names_frag = names_fragment(self._static_names or [])
            # bound once: these sit on the per-request proto hot path
            self._parse_tensor_request = parse_tensor_request
            self._build_tensor_response = build_tensor_response
            # build/load the native codec NOW (engine startup) — a first-call
            # build inside a request coroutine would block the event loop for
            # the duration of the g++ run
            from seldon_core_tpu.native.fastcodec import native_available

            native_available()
        # warm-start the autopilot from the persisted perf corpus so a
        # restarted engine prices previously-seen shapes before its first
        # dispatch (no-op when SELDON_TPU_CORPUS_DIR is unset)
        try:
            from seldon_core_tpu.utils.perfcorpus import CORPUS

            CORPUS.warm_start_autopilot()
        except Exception:  # noqa: BLE001 - corpus must never block serving
            logger.exception("perf-corpus warm start failed (serving anyway)")
        for name, at in getattr(self.compiled, "init_at", {}).items():
            BOOT.span("unit/" + name, "units", *at, self._boot_owner)
        BOOT.span(
            "units", None, t_units, time.monotonic(), self._boot_owner,
            # init_state returns with its arrays' making still queued on
            # the device (no block_until_ready here or there): what is
            # left of it ends under the spans that follow
            note="unit/*: init_state, dispatched, not awaited")

    # -- flight recorder -----------------------------------------------

    def _audit_request(self, puid: str, method: str, status: int, t0: float,
                       rows: Optional[int] = None, **extra) -> None:
        """One puid-correlated audit entry per served request; a disabled
        logger costs one attribute load."""
        if not self.audit.enabled:
            return
        from seldon_core_tpu.utils.tracing import current_trace_context

        # stamp the trace id so an audit line links straight to its
        # /trace tree (sampled requests only — an unsampled trace has no
        # spans to link to)
        ctx = current_trace_context()
        if ctx is not None and ctx.sampled and "trace_id" not in extra:
            extra["trace_id"] = ctx.trace_id
        # quality state inline: an audit line shows the drift score the
        # same way its dispatch span does (utils/quality.py)
        if method == "predict" and "drift" not in extra:
            drift = QUALITY.last_drift(self._quality_node)
            if drift is not None:
                extra["drift"] = drift
        self.audit.record(
            puid=puid,
            deployment=self.deployment.name,
            predictor=self.predictor.name,
            graph=self._graph_path,
            method=method,
            status=int(status),
            rows=rows,
            latency_ms=round((time.perf_counter() - t0) * 1e3, 3),
            mode=self.mode,
            **extra,
        )

    def stats(self) -> dict:
        """Zero-dependency JSON snapshot behind ``GET /stats`` — batcher
        occupancy/bucket state, in-flight dispatch slots, rolling latency
        percentiles, generation SLO telemetry, tracer and audit status.

        The four observatory walks (telemetry / perf / quality / tracer)
        are served from a cached assembly built off the drainer's folded
        state: after draining pending records, the cache is reused while
        nothing underneath it moved (spine fold generation + recorder
        mutation generation unchanged) and it is younger than
        ``SELDON_TPU_STATS_TTL_S``.  ``staleness_s`` reports the cache
        age so scrapers can see exactly how fresh the walks are.  The
        live engine/batcher/breaker blocks are always current — they are
        cheap and must never lag a pause or a breaker flip."""
        from seldon_core_tpu.utils.tracing import TRACER

        SPINE.drain()
        now = time.monotonic()
        key = (
            SPINE.fold_generation, RECORDER._gen,
            TRACER.enabled, TRACER.sample,
            OBSERVATORY.enabled, QUALITY.enabled,
        )
        cached = self._stats_cache
        if (
            cached is not None
            and cached[0] == key
            and now - cached[1] < self._stats_ttl_s
        ):
            walks, staleness = cached[2], now - cached[1]
        else:
            walks = {
                "telemetry": RECORDER.snapshot(),
                "perf": OBSERVATORY.snapshot(),
                "quality": QUALITY.snapshot(),
                "tracer": TRACER.snapshot(),
            }
            self._stats_cache = (key, now, walks)
            staleness = 0.0
        return {
            "boot_id": self.boot_id,
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
                "paused": self.paused,
                "pipelined": self._pipelined,
                "dispatch_timeout_s": self.dispatch_timeout_s,
                "known_good_widths": sorted(
                    str(w) for w in self._known_good_widths
                ),
                # whole-graph fusion state (graph/fuse.py): whether the
                # pass is on, and the plan (fused roots / blocked nodes /
                # per-request dispatch hops eliminated) when one exists
                "graph_fuse": {
                    "enabled": self._fuse,
                    "plan": (
                        None if self.fusion_plan is None
                        else self.fusion_plan.summary()
                    ),
                },
            },
            "batcher": None if self.batcher is None else self.batcher.snapshot(),
            # continuous-batching generation scheduler: in-flight/waiting
            # sequences, paged-KV-pool occupancy, admission/retirement flow
            "genserver": (
                None if self.genserver is None else self.genserver.snapshot()
            ),
            # the boot timeline (utils/genperf.py BOOT): process start ->
            # the request served now, as spans on time.monotonic()
            "boot": (
                BOOT.document(self._boot_owner) if self.genserver is None
                else self.genserver.boot_document()
            ),
            "resilience": {
                "retry_budget": self.retry_budget.snapshot(),
                "breakers": {
                    name: br.snapshot() for name, br in self.breakers.items()
                },
            },
            **walks,
            # MAB router state read back out of the pytree (per-branch
            # success/tries — utils/quality.py router_quality)
            "routers": router_quality(self.states()),
            # learned cost-model health (full table on GET /autopilot)
            "autopilot": AUTOPILOT.snapshot(),
            # brownout ladder state (runtime/brownout.py): stage, live
            # signals, recent typed transitions
            "brownout": _brownout_snapshot(),
            "audit": self.audit.snapshot(),
            "staleness_s": round(staleness, 3),
        }

    def overhead_document(self) -> dict:
        """The ``GET /overhead`` body: the telemetry overhead budget as a
        self-observed SLO — per-subsystem framework-time decomposition
        derived from the fused hop records themselves
        (utils/hotrecord.py; docs/operations.md runbook)."""
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            **SPINE.overhead_document(),
        }

    def perf_document(self) -> dict:
        """The ``GET /perf`` body: the process-global performance
        observatory (per-executable cost/MFU/roofline table + HBM
        watermarks, utils/perf.py) under this engine's identity."""
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            **OBSERVATORY.document(),
        }

    def genperf_document(self) -> dict:
        """The ``GET /genperf`` body: the generation-lane flight
        recorder (utils/genperf.py — per-tick-kind latency percentiles,
        host/device phase splits, the bubble ledger, served decode
        MFU/HBM-BW over real rows, idle duty cycle, KV-block residency)
        under this engine's identity, plus the live scheduler picture
        and the adaptive-chunk state the percentiles should be read
        against.  Served whether or not the scheduler exists — a
        kill-switched lane answers an empty recorder, not a 500."""
        from seldon_core_tpu.utils.genperf import GENPERF

        SPINE.drain()  # pending gen_step records fold into GENPERF first
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            "scheduler": (
                None if self.genserver is None
                else self.genserver.snapshot()
            ),
            "adaptive_chunk": (
                None if self.genserver is None
                else self.genserver.chunk_history()
            ),
            **GENPERF.document(),
        }

    def autopilot_document(self) -> dict:
        """The ``GET /autopilot`` body: the process-global learned
        cost-model (per-executable/pad-bucket latency table, knobs,
        misprediction distribution, shed/decision counters —
        runtime/autopilot.py) under this engine's identity."""
        SPINE.drain()  # pending dispatch records train the model first
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            **AUTOPILOT.document(),
        }

    def corpus_document(self) -> dict:
        """The ``GET /corpus`` body: the durable per-process perf corpus
        (per-key quantile sketches, segment/rotation state, warm-start
        counters — utils/perfcorpus.py) under this engine's identity."""
        from seldon_core_tpu.utils.perfcorpus import CORPUS

        SPINE.drain()  # pending dispatch records land in the corpus first
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            **CORPUS.document(),
        }

    def costs_document(self) -> dict:
        """The ``GET /costs`` body: the process-global resource ledger
        (per-tenant x deployment x phase device-seconds, pad tax,
        KV-block-seconds, attributed bytes, the accounting identity and
        the capacity block — utils/costledger.py) under this engine's
        identity."""
        from seldon_core_tpu.utils.costledger import LEDGER

        try:  # capacity block: available chip-seconds = devices x wall
            import jax

            LEDGER.devices = max(1, jax.local_device_count())
        except Exception:  # noqa: BLE001 - capacity keeps devices=1
            pass
        SPINE.drain()  # pending flush/tick records land in the ledger first
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            **LEDGER.document(),
        }

    def postmortems_document(self, puid: str = "") -> dict:
        """The ``GET /postmortems`` body: the tail-sampled postmortem
        recorder (utils/postmortem.py — kept worst-request exemplars
        with their automatic explanations, retention counters, pending
        buffer state) under this engine's identity.  ``puid`` (or a
        trace_id) answers the full immutable exemplar document."""
        from seldon_core_tpu.utils.postmortem import POSTMORTEM

        SPINE.drain()  # pending request spans complete their verdicts first
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            **POSTMORTEM.document(puid=puid),
        }

    def quality_document(self) -> dict:
        """The ``GET /quality`` body: the process-global quality
        observatory (per-node drift table, feedback reward/accuracy,
        outlier bridge, SLO burn rates — utils/quality.py) under this
        engine's identity, plus per-branch MAB router state read out of
        the graph's pytrees."""
        return {
            "engine": {
                "deployment": self.deployment.name,
                "predictor": self.predictor.name,
                "mode": self.mode,
            },
            "routers": router_quality(self.states()),
            **QUALITY.document(),
        }

    def open_breakers(self) -> "list[str]":
        """Remote nodes whose circuit breaker is not closed — surfaced in
        ``/ready`` so orchestration sees partial degradation without
        scraping Prometheus."""
        return sorted(
            name
            for name, br in self.breakers.items()
            if br.state != CircuitBreaker.CLOSED
        )

    # -- streaming generation ------------------------------------------

    def can_stream(self) -> bool:
        """True when the graph is a single streaming-capable unit: a
        generator exposing ``stream_tokens``, or any unit the continuous
        scheduler runs (the scheduler streams natively — speculative
        graphs gain SSE this way)."""
        if self.genserver is not None:
            return True
        return (
            self.compiled is not None
            and len(self.compiled.units) == 1
            and hasattr(
                next(iter(self.compiled.units.values())), "stream_tokens"
            )
        )

    def prepare_stream_request(self, text: str) -> "tuple[str, int]":
        """Validate a streaming request BEFORE any response bytes exist, so
        every lane can answer a plain 400 instead of a 200 that dies.
        Returns ``(payload_text_without_chunk, chunk)``; raises
        SeldonMessageError on any problem (bad JSON, bad chunk, non-
        streamable graph, missing numeric prompt)."""
        import json as _json

        chunk = 8
        try:
            doc = _json.loads(text)
        except ValueError as e:
            raise SeldonMessageError(f"invalid JSON: {e}")
        if isinstance(doc, dict) and "chunk" in doc:
            try:
                chunk = max(1, min(256, int(doc.pop("chunk"))))
            except (TypeError, ValueError):
                raise SeldonMessageError("chunk must be an integer")
            text = _json.dumps(doc)
        if not self.can_stream():
            raise SeldonMessageError(
                "graph does not support streaming generation "
                "(need a single generator node)"
            )
        msg = SeldonMessage.from_json(text)
        if msg.data is None or msg.data.array is None:
            raise SeldonMessageError("streaming needs a numeric prompt")
        return text, chunk

    async def generate_stream(self, raw, chunk: int = 8,
                              t_recv: Optional[float] = None):
        """Incremental generation: yields SSE-able JSON strings —
        ``{"tokens": [[...]], "done": false}`` per chunk, then a terminal
        ``{"done": true, "meta": {...}}``.  Beyond-reference surface (the
        reference predates sequence models); greedy streams concatenate to
        exactly the ``predict_json`` output.

        ``t_recv`` is the HTTP lane's ``perf_counter`` at handler entry
        (this call's own entry when the lane gives none): the origin of
        the request stages ``/genperf`` reports under ``requests``.

        Streams bypass the batcher (a stream holds the device for its
        chunk dispatches; concurrent streams interleave at chunk
        granularity) and never write unit state back."""
        import json as _json

        if t_recv is None:
            t_recv = time.perf_counter()
        if not self.can_stream():
            raise SeldonMessageError(
                "graph does not support streaming generation "
                "(need a single generator node)"
            )
        # optional per-request token budget: a top-level "max_new" key
        # in the payload (the gateway's stream-failover resume sets it
        # to the REMAINING budget when it re-prefills on a peer).
        # Popped before message parsing, like the rest lane's "chunk"
        max_new = None
        try:
            doc = _json.loads(raw)
        except (TypeError, ValueError):
            doc = None  # from_json owns the error behaviour below
        if isinstance(doc, dict) and doc.get("max_new") is not None:
            try:
                max_new = max(1, int(doc.pop("max_new")))
            except (TypeError, ValueError):
                raise SeldonMessageError("max_new must be an integer")
            raw = _json.dumps(doc)
        msg = SeldonMessage.from_json(raw)
        if msg.data is None or msg.data.array is None:
            raise SeldonMessageError("streaming needs a numeric prompt")
        rows = np.asarray(msg.data.array, dtype=np.float64)
        if rows.ndim < 2:
            rows = rows.reshape(1, -1)
        puid = msg.meta.puid or new_puid()
        loop = asyncio.get_running_loop()
        req = None
        if self.genserver is not None:
            # continuous lane: the stream joins the in-flight decode
            # batch at the next scheduler step (chunked prefill first),
            # instead of holding the device for a private generate()
            req, gen = self.genserver.open_stream(
                rows, chunk=chunk, max_new=max_new)
        else:
            name, unit = next(iter(self.compiled.units.items()))
            state = self.compiled.states[name]
            gen = unit.stream_tokens(state, rows, chunk=chunk)
        t0 = time.perf_counter()
        ttft_s = None
        tokens = 0
        status = 200
        audit_extra = {}
        try:
            with self.metrics.time_server("generate-stream", "POST"), \
                    self.tracer.span(puid, "request", kind="request",
                                     method="generate_stream"):
                # captured while the span is open: the finally-audit runs
                # after the span context has been reset
                from seldon_core_tpu.utils.tracing import current_trace_context

                ctx = current_trace_context()
                if ctx is not None and ctx.sampled:
                    audit_extra["trace_id"] = ctx.trace_id
                try:
                    while True:
                        toks = await loop.run_in_executor(
                            None, next, gen, None
                        )
                        if toks is None:
                            break
                        arr = np.asarray(toks)  # materialized for serialization
                        if ttft_s is None:
                            # engine-truth TTFT for the audit entry (prefill +
                            # first decode scan + readback); the Prometheus
                            # ttft/decode-rate families are recorded ONCE, by
                            # stream_chunks itself — recording here too would
                            # double-count every stream
                            t_out = time.perf_counter()
                            ttft_s = t_out - t0
                            if req is not None and req.t_first is not None:
                                # the lane's two request stages, at the
                                # instant the chunk goes to the writer
                                from seldon_core_tpu.utils.genperf import (
                                    GENPERF,
                                )

                                GENPERF.observe_stream_first(
                                    req.t_submit - t_recv,
                                    t_out - req.t_first, t_out - t_recv)
                        tokens += int(arr.shape[0] * arr.shape[1])
                        yield _json.dumps({
                            "tokens": arr.astype(float).tolist(),
                            "done": False,
                        })
                except GeneratorExit:
                    # stamped INSIDE the span (the outer handlers run
                    # after it closed) so the postmortem retention policy
                    # sees the abandoned/failed stream on its root span
                    self.tracer.annotate(status=499)
                    raise
                except Exception as e:
                    self.tracer.annotate(status=500,
                                         error=type(e).__name__)
                    raise
        except GeneratorExit:
            status = 499  # client abandoned the stream mid-flight
            raise
        except Exception:
            status = 500  # surfaced in-band by the SSE error frame
            raise
        finally:
            # failed/abandoned streams consumed device work and hold a
            # puid — they must appear in the audit log like unary errors
            elapsed = time.perf_counter() - t0
            self._audit_request(
                puid, "generate_stream", status, t0,
                rows=int(rows.shape[0]),
                tokens=tokens,
                ttft_ms=None if ttft_s is None else round(ttft_s * 1e3, 3),
                tokens_per_s=(
                    None if elapsed <= 0 else round(tokens / elapsed, 1)
                ),
                **audit_extra,
            )
        yield _json.dumps({"done": True, "meta": {"puid": puid}})

    def prewarm(self, widths) -> int:
        """Compile every batch-bucket shape for the given feature widths
        before serving (boot-time analogue of the reference's JVM/Tomcat
        warm-up concern; the readiness probe only flips after this returns).

        Padding batchers dispatch power-of-two sizes capped at max_batch
        (runtime/batching.py:_dispatch_chunked), so the compiled-shape set
        per width is {1, 2, 4, ..., max_batch}; compiling them here (backed
        by the persistent compile cache) means no first-request XLA compile
        ever stalls live traffic.  Stateful graphs run UNPADDED
        (pad_to_buckets=False: fake rows must not enter streaming
        statistics), so their live batch sizes are arbitrary and cannot be
        enumerated — for those only the single-row shape is compiled and
        first-burst compiles may still occur.  Returns the number of shapes
        compiled."""
        if self.compiled is None:
            return 0
        if self.genserver is not None:
            # the continuous lane's serving shapes are the scheduler's
            # (prefill-chunk + decode-round executables), not generate()'s
            # — probe requests through the scheduler compile those.
            # Checked before the batcher: streams serve through the
            # scheduler even when unary batching is disabled
            return self.genserver.prewarm(widths)
        if self.batcher is None:
            return 0
        import numpy as _np

        max_batch = self.batcher.max_batch
        if self.batcher.pad_to_buckets:
            # powers of two capped at max_batch; a non-power-of-two
            # max_batch is itself a bucket shape and must be compiled too
            sizes = [1 << i for i in range(max_batch.bit_length())
                     if (1 << i) < max_batch] + [max_batch]
        else:
            sizes = [1]
        compiled = 0
        for width in widths:
            shape = (width,) if isinstance(width, int) else tuple(width)
            # probe the smallest batch first: an annotation width that is
            # syntactically valid but incompatible with the graph (e.g. 16
            # on a 784-input model) must not crash-loop the pod out of
            # serve() — reconcile-time validation can only check integer
            # syntax, not width compatibility.  A width the graph rejects
            # at TRACE time (TypeError/ValueError on the first probe, the
            # same rule _batched_predict_sync answers 400 by) is logged
            # and skipped; any other failure — a kernel that does not
            # lower, a device error, a width that traced and then broke
            # at a larger batch — is the serving path failing and stops
            # the boot.
            for b in sizes:
                x = _np.zeros((b,) + shape, dtype=_np.float64)
                try:
                    self.compiled.predict_arrays(x, update_states=False)
                except (TypeError, ValueError) as e:
                    if b != sizes[0]:
                        raise
                    logger.warning(
                        "prewarm: width %s rejected by the graph at trace "
                        "time (%s: %s); skipping this width",
                        shape, type(e).__name__, e,
                    )
                    break
                self._known_good_widths.add(x.shape[1:])
                compiled += 1
        return compiled

    def _handoff_event(self, **fields) -> None:
        """Handoff visibility in the flight recorder: one firehose line
        per completed prefill->decode handoff (skipped when the audit
        log is off — same contract as request lines).  The coordinator
        stamps ``trace_id``/``puid``/``tenant``/``tier`` into ``fields``
        so firehose consumers can join handoff lines to federated
        traces and tenant accounting."""
        if not self.audit.enabled:
            return
        self.audit.record(
            puid=fields.pop("puid", "") or "",
            deployment=self.deployment.name,
            predictor=self.predictor.name,
            graph=self._graph_path,
            method="kv_handoff",
            status=200,
            rows=None,
            latency_ms=fields.pop("latency_ms", None),
            mode=self.mode,
            **fields,
        )

    def process_track_name(self) -> str:
        """This replica's Perfetto process-track label
        (deployment/predictor + generation role) — stamps the engine's
        ``/trace/export`` so mesh-merged exports render legibly."""
        return (f"{self.deployment.name}/{self.predictor.name} "
                f"({self.gen_role})")

    def trace_json(self, query: str) -> str:
        """The relay lane's trace surface (udsrelay.py ``OP_TRACE``):
        the local trace document for a JSON query
        ``{"trace_id"|"puid"|"limit"}`` — how federated trace assembly
        (gateway/fleet.py) reaches replicas that serve no HTTP lane
        (uds-only endpoints, relay-spec decode peers)."""
        import json as _json

        from seldon_core_tpu.utils.tracing import TRACER, trace_document

        try:
            q = _json.loads(query) if query.strip() else {}
            if not isinstance(q, dict):
                q = {}
        except ValueError:
            q = {}
        doc = trace_document(
            TRACER,
            puid=str(q.get("puid", "") or ""),
            trace_id=str(q.get("trace_id", "") or ""),
            limit=int(q.get("limit", 100) or 100),
        )
        return _json.dumps(doc)

    # -- disaggregated KV handoff (relay OP_KVSTREAM) --------------------

    async def kv_frame(self, payload: bytes) -> "tuple[int, bytes]":
        """One KV-stream frame (runtime/kvstream.py wire format) off the
        relay lane.  Only decode-role replicas accept block imports —
        anything else is a typed 503 role misconfig.  KV_STATS answers
        on every role (it is how peers and demos probe pool headroom)."""
        import asyncio

        from seldon_core_tpu.runtime import kvstream

        try:
            sub_op, hid, body = kvstream.parse_frame(payload)
        except kvstream.KvWireError as e:
            return 400, str(e).encode()
        gs = self.genserver
        if gs is None:
            return 503, (b"this replica runs no generation scheduler "
                         b"(KV handoffs need --gen-role decode)")
        if sub_op == kvstream.KV_STATS:
            s = gs.kv_stats()
            return 200, kvstream.pack_stats(
                s["free"], s["total"], s["waiting"], s["inflight"])
        if gs.role != "decode":
            RECORDER.record_kv_handoff("refused")
            return 503, (
                f"role misconfig: this replica is {gs.role!r}, KV "
                f"handoffs import only at --gen-role decode replicas"
            ).encode()
        try:
            if sub_op == kvstream.KV_BEGIN:
                gs.kv_reserve(hid, kvstream.parse_begin(body))
                return 200, b""
            if sub_op == kvstream.KV_BLOCKS:
                imp = gs._imports.get(hid)
                if imp is None:
                    raise kvstream.KvWireError(
                        "unknown or expired handoff id")
                first, layers = kvstream.parse_blocks(body, imp.meta)
                gs.kv_receive(hid, first, layers)
                return 200, b""
            if sub_op == kvstream.KV_COMMIT:
                req = gs.kv_commit(hid)
                toks = await asyncio.wrap_future(req.future)
                return 200, kvstream.pack_tokens(toks[0])
            if sub_op == kvstream.KV_ABORT:
                gs.kv_abort(hid)
                return 200, b""
        except LoadShedError as e:
            return 503, str(e).encode()
        except kvstream.KvWireError as e:
            return 409, str(e).encode()
        except Exception as e:  # noqa: BLE001 - surface typed, keep serving
            logger.exception("KV handoff frame failed")
            return 500, f"{type(e).__name__}: {e}".encode()
        return 400, f"unknown KV sub-op {sub_op}".encode()

    def _predict_dispatch_s(self, padded_rows, x):
        """Autopilot prediction hook: the dispatch wall the learned model
        expects for this graph at one pad bucket of x's feature shape —
        the SAME executable identity the perf observatory keys on, so
        seed priors and measured corrections land on one table row."""
        from seldon_core_tpu.utils.perf import executable_key

        key = executable_key(
            "predict",
            (int(padded_rows),) + tuple(np.shape(x)[1:]),
            getattr(x, "dtype", np.float64),
        )
        return AUTOPILOT.predict_s(key)

    async def _submit(self, rows):
        """Batched dispatch under the engine deadline — the reference's
        per-call budget (5 s gRPC deadlines,
        InternalPredictionService.java:77) applied to the device hop.  A
        hung device surfaces as a 504 FAILURE instead of a request
        that never returns.  A request-level deadline budget
        (Seldon-Deadline-Ms / gRPC deadline, runtime/resilience.py) clamps
        the wait further: the device hop draws from the same budget as
        every other hop.

        Deadline-aware admission (runtime/autopilot.py): when the learned
        cost model predicts queue + dispatch latency beyond the remaining
        budget, shed with a typed 503 BEFORE the request burns a dispatch
        slot or device time — the answer could never arrive in time, and
        the 503 is retryable so another replica can still serve it."""
        from seldon_core_tpu.runtime.brownout import (
            BROWNOUT,
            BROWNOUT_INFO_PREFIX,
        )
        from seldon_core_tpu.runtime.qos import current_tier

        BROWNOUT.maybe_tick()
        tier = current_tier()
        if BROWNOUT.sheds_tier(tier):
            # staged degradation (runtime/brownout.py): lower latency
            # tiers shed with the same typed retryable 503 the autopilot
            # uses, BEFORE queue or device time is spent
            RECORDER.record_brownout_shed(tier)
            raise LoadShedError(
                f"{BROWNOUT_INFO_PREFIX}: {tier!r}-tier request shed at "
                f"brownout stage {BROWNOUT.stage()} — retry later"
            )
        timeout = self.dispatch_timeout_s
        rem = remaining_s()
        if rem is not None:
            if rem <= 0:
                RECORDER.record_deadline_exceeded("dispatch")
                raise DeadlineExceededError(
                    "request deadline exhausted before device dispatch"
                )
            if autopilot_enabled():
                predictor = getattr(
                    self.batcher, "predicted_latency_s", None
                )
                est = predictor(rows) if predictor is not None else None
                # brownout stage 3 tightens the margin (scale < 1):
                # marginal requests shed earlier, certain ones still run
                if est is not None and est > (
                    rem * shed_margin() * BROWNOUT.shed_margin_scale()
                ):
                    RECORDER.record_autopilot_shed("admission")
                    self.tracer.event(
                        "autopilot_shed",
                        predicted_ms=round(est * 1e3, 3),
                        remaining_ms=round(rem * 1e3, 3),
                    )
                    raise LoadShedError(
                        f"{SHED_INFO_PREFIX}: predicted queue+dispatch "
                        f"{est * 1e3:.1f} ms exceeds the remaining "
                        f"deadline budget ({rem * 1e3:.1f} ms)"
                    )
            timeout = min(timeout, rem)
        try:
            return await asyncio.wait_for(self.batcher.submit(rows), timeout)
        except asyncio.TimeoutError:
            if timeout < self.dispatch_timeout_s:
                # the caller's budget, not the engine ceiling, ran out
                RECORDER.record_deadline_exceeded("dispatch")
                raise DeadlineExceededError(
                    f"request deadline ({timeout:.2f}s remaining) exceeded "
                    f"during device dispatch"
                ) from None
            raise DispatchTimeoutError(
                f"device dispatch exceeded {self.dispatch_timeout_s:.0f}s"
            ) from None

    async def _batched_predict(self, stacked, real_rows=None):
        deadline = time.monotonic() + self.dispatch_timeout_s
        if self._pipelined:
            # concurrency is bounded by the batcher's in-flight slots
            return await asyncio.get_running_loop().run_in_executor(
                None, self._batched_predict_sync, stacked, deadline,
                real_rows,
            )
        async with self._device_lock:
            return await asyncio.get_running_loop().run_in_executor(
                None, self._batched_predict_sync, stacked, deadline,
                real_rows,
            )

    def _batched_predict_sync(self, stacked, deadline=None, real_rows=None):
        # runs on an executor thread: no request context here by design —
        # a stacked dispatch serves many requests, so its span stands
        # alone (per-request causality is the queue-wait span).
        #
        # Observability is ONE fused telemetry record per dispatch hop
        # (utils/hotrecord.py): the unified per-batch sample verdict is
        # decided once, the record carries span identity + measured wall +
        # executable key + references to the stacked batch and its
        # readback, and the TRACER/OBSERVATORY/QUALITY folds — span
        # append, MFU/roofline derivation, the one fused drift summarize —
        # all happen in the drainer, off this path.
        wants = SPINE.dispatch_wants()
        cc_before = (
            dict(RECORDER.compile_cache_events) if wants.trace else None
        )
        t_dispatch = time.perf_counter()
        start_s = time.time()
        width = stacked.shape[1:]
        # state write-back is vetoed AFTER the device round-trip if the
        # request already timed out (client saw 504; a late update
        # would double-apply on retry) — evaluated post-dispatch via
        # the callable form of update_states
        gate = (
            (lambda: time.monotonic() < deadline)
            if (not self._pipelined and deadline is not None)
            else (not self._pipelined)
        )
        try:
            y, routing, tags = self.compiled.predict_arrays(
                stacked, update_states=gate
            )
        except BaseException as e:
            if wants.trace:
                SPINE.record_failed_dispatch(
                    executable=self.compiled.executable_key(stacked),
                    seconds=time.perf_counter() - t_dispatch,
                    start_s=start_s, rows=len(stacked),
                    method="predict", error=type(e).__name__,
                )
            if isinstance(e, (TypeError, ValueError)):
                if width in self._known_good_widths:
                    # this feature width has served before: the failure
                    # is a server-side defect, not bad client input —
                    # surface it
                    raise
                # never-seen width failing at trace time = wrong feature
                # width from the client: typed 400
                raise SeldonMessageError(
                    f"graph rejected input of shape {stacked.shape}: {e}"
                ) from e
            raise
        self._known_good_widths.add(width)
        # the readback is the serving path's own need (jax dispatch is
        # async; the device round-trip is paid here) — and the ONLY
        # array touch observability requires: the record holds references,
        # the summarize runs in the drainer
        y = np.asarray(y)
        seconds = time.perf_counter() - t_dispatch
        n_real = real_rows if real_rows is not None else len(stacked)
        # outlier-score bridge stays inline: a dict-key check when absent,
        # and the scores are per-response tags the caller slices anyway
        if QUALITY.enabled and tags:
            QUALITY.record_outlier_tags(tags, real_rows=n_real)
        if wants.any:
            cc = None
            if cc_before is not None:
                # compile-cache traffic during this dispatch (fresh shape
                # -> XLA compile): visible per-span, not just as counters
                for outcome in ("miss", "hit"):
                    if RECORDER.compile_cache_events.get(
                        outcome, 0
                    ) > cc_before.get(outcome, 0):
                        cc = outcome
                        break
            SPINE.record_dispatch(
                wants,
                executable=self.compiled.executable_key(stacked),
                seconds=seconds, start_s=start_s,
                rows=len(stacked), real_rows=n_real, method="predict",
                quality_node=self._quality_node, X=stacked, Y=y,
                deadline_remaining_s=(
                    deadline - time.monotonic()
                    if deadline is not None else None
                ),
                compile_cache=cc,
                # fused mode: ONE record for the whole graph's dispatch,
                # carrying the per-node phase decomposition so the span
                # still explains where the program's time goes
                phases=getattr(self.compiled, "phases", None),
            )
        return y, (routing, tags)

    # ------------------------------------------------------------------

    async def predict_json(self, raw) -> "tuple[str, int]":
        """Wire-to-wire predict: JSON in, ``(JSON out, http_status)``.

        The REST hot path.  For batchable compiled graphs with a numeric
        payload the native codec parses straight to an array and the
        response document is composed from precomputed fragments — no
        SeldonMessage object churn (~3x the per-request Python of
        from_json -> predict -> to_json).  Everything else falls back to
        the object path with identical semantics."""
        fast = None
        if self.batcher is not None:
            from seldon_core_tpu.native.fastcodec import (
                format_data_fragment,
                parse_message_fast,
            )

            fast = parse_message_fast(raw)
        if fast is not None:
            envelope, kind, arr = fast
            meta_in = envelope.get("meta") or {}
            if (
                kind is not None
                and isinstance(meta_in, dict)
                and _meta_shape_ok(meta_in)
                and "binData" not in envelope
                and "strData" not in envelope
            ):
                puid = meta_in.get("puid") or new_puid()
                t0 = time.perf_counter()
                with self.metrics.time_server(
                    "predictions", "POST"
                ) as code, self.tracer.span(
                    puid, "request", kind="request", method="predict",
                    mode=self.mode,
                ):
                    rows = arr if arr.ndim >= 2 else arr.reshape(1, -1)
                    try:
                        y_rows, (routing, tags) = await self._submit(rows)
                    except (SeldonMessageError, GraphSpecError) as e:
                        code["code"] = str(e.http_code)
                        # a shed is flow control, not an SLO error
                        # (utils/metrics.py time_server)
                        code["shed"] = isinstance(e, LoadShedError)
                        self.tracer.annotate(
                            status=e.http_code, error=type(e).__name__,
                            shed=isinstance(e, LoadShedError))
                        self._audit_request(
                            puid, "predict", e.http_code, t0,
                            rows=len(rows), lane="rest",
                        )
                        return (
                            SeldonMessage.failure(
                                str(e), code=e.http_code,
                                meta=Meta(puid=puid),
                            ).to_json(),
                            e.http_code,
                        )
                    self._audit_request(
                        puid, "predict", 200, t0, rows=len(rows), lane="rest",
                    )
                    meta_out = dict(meta_in)
                    meta_out["puid"] = puid
                    if tags or routing:
                        if tags:
                            meta_out["tags"] = {
                                **(meta_in.get("tags") or {}),
                                **pythonize_tags(tags),
                            }
                        if routing:
                            meta_out["routing"] = {
                                **(meta_in.get("routing") or {}),
                                **routing,
                            }
                    frag = format_data_fragment(
                        np.ascontiguousarray(y_rows, dtype=np.float64), kind
                    )
                    if frag is not None:
                        import json as _json

                        if len(meta_out) == 1 and "puid" not in meta_in:
                            # only OUR generated puid (base32 [a-z2-7], never
                            # needs escaping) — skip the ~20us dumps call.  A
                            # client-supplied puid goes through dumps: it can
                            # contain quotes/backslashes
                            meta_json = '{"puid":"%s"}' % puid
                        else:
                            meta_json = _json.dumps(
                                meta_out, separators=(",", ":")
                            )
                        return (
                            '{"meta":%s,"status":{"code":200,"status":"SUCCESS"},'
                            '"data":{%s%s}}'
                            % (
                                meta_json,
                                self._names_fragment,
                                frag.decode("ascii"),
                            ),
                            200,
                        )
                    # native formatter declined (NaN/Inf in the result) —
                    # serialize the SAME result through the object codec; a
                    # re-dispatch would double-update streaming-stats state
                    from seldon_core_tpu.messages import DefaultData, Status

                    resp = SeldonMessage(
                        meta=Meta.from_json_dict(meta_out),
                        status=Status(),
                        data=DefaultData(
                            array=y_rows,
                            names=list(self._static_names),
                            kind=kind,
                        ),
                    )
                    return resp.to_json(), 200
            # fall through to object path

        msg = SeldonMessage.from_json(raw)
        resp = await self.predict(msg)
        ok = resp.status is None or resp.status.status == "SUCCESS"
        return resp.to_json(), 200 if ok else (resp.status.code or 400)

    async def predict_wire(self, payload) -> "tuple[int, list]":
        """Binary-lane wire-to-wire predict (runtime/wire.py): one frame
        in, ``(http_status, response frame parts)`` out.

        The request tensor is an ``np.frombuffer`` VIEW over the wire
        bytes — no JSON round trip, no value-by-value materialization —
        and the response is framed straight from the device readback
        buffer (the parts list keeps header and payload separate so the
        transport writes them writev-style).  A MULTI frame (the
        gateway's coalesced hop) fans its sub-frames out concurrently;
        the MicroBatcher re-coalesces the rows into one device dispatch
        exactly as it would have for separate arrivals, so de/coalescing
        is a pure hop-cost optimization, never a numerics change.

        Raises :class:`~seldon_core_tpu.runtime.wire.WireError` (400) /
        ``WireFrameTooLarge`` (413) for bytes that cannot be parsed as a
        frame at all; a parseable frame always answers with a typed
        response frame, per-sub-request on the coalesced path."""
        from seldon_core_tpu.runtime import wire

        frame = wire.decode_frame(payload)
        if frame.is_multi:
            results = await asyncio.gather(
                *(self._predict_wire_sub(sub) for sub in frame.subframes)
            )
            subs = [wire.join_parts(parts) for _status, parts in results]
            return 200, wire.encode_multi(subs)
        return await self._predict_wire_single(frame)

    async def _predict_wire_sub(self, buf) -> "tuple[int, list]":
        """One coalesced sub-frame: ANY failure — torn bytes, an
        unexpected model exception, an unencodable result — answers ITS
        slot with a typed error frame instead of failing its
        co-travellers (up to COALESCE_MAX requests ride one frame; one
        bad slot must never 502 the batch)."""
        from seldon_core_tpu.runtime import wire

        try:
            frame = wire.decode_frame(buf)
            if frame.is_multi:
                raise wire.WireError("nested multi frames are not allowed")
        except wire.WireError as e:
            return e.http_code, wire.encode_frame(
                None, status=e.http_code, response=True,
                meta_bytes=wire.pack_wire_meta(extra={"error": str(e)}),
            )
        try:
            return await self._predict_wire_single(frame)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 - slot-isolated 500
            return 500, wire.encode_frame(
                None, status=500, response=True,
                meta_bytes=wire.pack_wire_meta(
                    puid=frame.meta.get("puid"),
                    extra={"error": str(e)},
                ),
            )

    def _wire_error_frame(self, puid: str, e: Exception,
                          code: int) -> "tuple[int, list]":
        from seldon_core_tpu.runtime import wire

        return code, wire.encode_frame(
            None, status=code, response=True,
            meta_bytes=wire.pack_wire_meta(puid=puid,
                                           extra={"error": str(e)}),
        )

    async def _predict_wire_single(self, frame) -> "tuple[int, list]":
        from seldon_core_tpu.runtime import wire
        from seldon_core_tpu.runtime.qos import qos_scope
        from seldon_core_tpu.utils.tracing import (
            parse_traceparent,
            trace_scope,
        )

        meta = frame.meta
        puid = meta.get("puid") or new_puid()
        t0 = time.perf_counter()
        # the sidecar binds exactly like the HTTP lanes bind headers:
        # deadline clamps tighten-only, trace joins the caller's tree.
        # QoS binds ONLY when the sidecar names an identity — a bare
        # scope would reset what an HTTP header already bound
        from contextlib import ExitStack
        with ExitStack() as stack:
            dl = meta.get("deadline_ms")
            stack.enter_context(
                maybe_deadline_scope(dl / 1e3 if dl else None))
            stack.enter_context(
                trace_scope(parse_traceparent(meta.get("traceparent"))))
            if meta.get("tenant") is not None or meta.get("tier") is not None:
                stack.enter_context(
                    qos_scope(meta.get("tenant"), meta.get("tier")))
            code = stack.enter_context(
                self.metrics.time_server("predictions", "POST"))
            stack.enter_context(self.tracer.span(
                puid, "request", kind="request", method="predict",
                mode=self.mode,
            ))
            try:
                rows = frame.rows()
            except wire.WireError as e:
                code["code"] = "400"
                return self._wire_error_frame(puid, e, 400)
            from seldon_core_tpu.utils.costledger import (
                LEDGER,
                costledger_enabled,
            )
            if costledger_enabled():
                # tenant-attributed wire-lane ingress bytes: the sidecar
                # identity is bound by qos_scope above, so the ledger
                # rows land on the tenant that shipped the tensor
                from seldon_core_tpu.runtime.qos import current_tenant

                LEDGER.note_bytes(
                    current_tenant() or "", self.deployment.name,
                    "wire", int(getattr(rows, "nbytes", 0)))
            try:
                y_rows, (routing, tags) = await self._submit(rows)
            except (SeldonMessageError, GraphSpecError) as e:
                http_code = getattr(e, "http_code", 400)
                code["code"] = str(http_code)
                code["shed"] = isinstance(e, LoadShedError)
                self.tracer.annotate(
                    status=http_code, error=type(e).__name__,
                    shed=isinstance(e, LoadShedError))
                self._audit_request(
                    puid, "predict", http_code, t0,
                    rows=len(rows), lane="wire",
                )
                return self._wire_error_frame(puid, e, http_code)
            self._audit_request(
                puid, "predict", 200, t0, rows=len(rows), lane="wire",
            )
            in_extra = frame.extra()
            extra: dict = {}
            if self._static_names:
                extra["names"] = list(self._static_names)
            if in_extra.get("kind"):
                extra["kind"] = in_extra["kind"]
            if tags or in_extra.get("tags"):
                extra["tags"] = {
                    **(in_extra.get("tags") or {}),
                    **pythonize_tags(tags or {}),
                }
            if routing or in_extra.get("routing"):
                extra["routing"] = {
                    **(in_extra.get("routing") or {}),
                    **{k: int(v) for k, v in (routing or {}).items()},
                }
            return 200, wire.encode_frame(
                np.asarray(y_rows), status=200, response=True,
                meta_bytes=wire.pack_wire_meta(puid=puid,
                                               extra=extra or None),
            )

    async def predict_proto_wire(self, wire: bytes) -> bytes:
        """Proto wire bytes -> proto wire bytes — the zero-object gRPC lane.

        Common tensor requests are scanned at the wire level (packed doubles
        -> np.frombuffer, native/protowire.py) and the response is composed
        as bytes; anything unusual falls back to real protobuf parsing via
        ``predict_proto``."""
        if self.batcher is not None:
            parsed = self._parse_tensor_request(wire)
            if parsed is not None:
                puid, rows = parsed
                puid = puid or new_puid()
                t0 = time.perf_counter()
                # method=GRPC: the gRPC surface records its own metric
                # children (native h2 lane matches — nativeplane merge)
                with self.metrics.time_server(
                    "predictions", "GRPC"
                ) as code, self.tracer.span(
                    puid, "request", kind="request", method="predict",
                    mode=self.mode,
                ):
                    try:
                        y, (routing, tags) = await self._submit(rows)
                    except (SeldonMessageError, GraphSpecError) as e:
                        code["code"] = str(e.http_code)
                        # a shed is flow control, not an SLO error
                        # (utils/metrics.py time_server)
                        code["shed"] = isinstance(e, LoadShedError)
                        self.tracer.annotate(
                            status=e.http_code, error=type(e).__name__,
                            shed=isinstance(e, LoadShedError))
                        self._audit_request(
                            puid, "predict", e.http_code, t0,
                            rows=len(rows), lane="grpc",
                        )
                        from seldon_core_tpu.protoconv import msg_to_proto

                        # echo the request puid, like the object path does
                        return msg_to_proto(
                            SeldonMessage.failure(
                                str(e), code=e.http_code, meta=Meta(puid=puid)
                            )
                        ).SerializeToString()
                    self._audit_request(
                        puid, "predict", 200, t0, rows=len(rows), lane="grpc",
                    )
                    if not routing and not tags:
                        return self._build_tensor_response(
                            puid, y, self._proto_names_frag
                        )
                    # routing/tags present (rare on batchable graphs):
                    # compose via protobuf objects for full fidelity
                    return self._compose_proto_response(
                        puid, y, routing, tags
                    ).SerializeToString()
        from seldon_core_tpu.proto_gen import prediction_pb2 as pb

        resp = await self.predict_proto(pb.SeldonMessage.FromString(wire))
        return resp.SerializeToString()

    async def predict_proto(self, req):
        """Proto-to-proto predict — the gRPC hot path (the reference's
        faster wire: its published gRPC throughput is 2.3x its REST,
        the reference's docs/benchmarking.md:44,58).  Tensor-kind requests with a bare meta
        skip the SeldonMessage object layer entirely: packed values ->
        batched dispatch -> packed response.  Everything else goes through
        the object path with identical semantics."""
        from seldon_core_tpu.protoconv import msg_from_proto, msg_to_proto

        fast = (
            self.batcher is not None
            and req.WhichOneof("data_oneof") == "data"
            and req.data.WhichOneof("data_oneof") == "tensor"
            and (not req.HasField("meta") or not (
                req.meta.tags or req.meta.routing or req.meta.requestPath
            ))
        )
        if fast:
            t = req.data.tensor
            values = np.asarray(t.values, dtype=np.float64)
            shape = tuple(t.shape) or (values.size,)
            if int(np.prod(shape)) == values.size:
                rows = values.reshape(shape)
                if rows.ndim < 2:
                    rows = rows.reshape(1, -1)
                puid = req.meta.puid or new_puid()
                t0 = time.perf_counter()
                with self.metrics.time_server(
                    "predictions", "GRPC"
                ) as code, self.tracer.span(
                    puid, "request", kind="request", method="predict",
                    mode=self.mode,
                ):
                    try:
                        y, (routing, tags) = await self._submit(rows)
                    except (SeldonMessageError, GraphSpecError) as e:
                        code["code"] = str(e.http_code)
                        # a shed is flow control, not an SLO error
                        # (utils/metrics.py time_server)
                        code["shed"] = isinstance(e, LoadShedError)
                        self.tracer.annotate(
                            status=e.http_code, error=type(e).__name__,
                            shed=isinstance(e, LoadShedError))
                        self._audit_request(
                            puid, "predict", e.http_code, t0,
                            rows=len(rows), lane="grpc",
                        )
                        return msg_to_proto(
                            SeldonMessage.failure(
                                str(e), code=e.http_code, meta=Meta(puid=puid)
                            )
                        )
                    self._audit_request(
                        puid, "predict", 200, t0, rows=len(rows), lane="grpc",
                    )
                    return self._compose_proto_response(puid, y, routing, tags)
        resp_msg = await self.predict(msg_from_proto(req))
        return msg_to_proto(resp_msg)

    def _compose_proto_response(self, puid, y, routing, tags):
        """SUCCESS SeldonMessage proto with tensor payload + meta merge —
        shared by both proto fast lanes."""
        from seldon_core_tpu.proto_gen import prediction_pb2 as pb
        from seldon_core_tpu.protoconv import _py_to_value

        resp = pb.SeldonMessage()
        resp.status.code = 200
        resp.status.status = pb.Status.SUCCESS
        resp.meta.puid = puid
        for k_, v_ in (routing or {}).items():
            resp.meta.routing[k_] = int(v_)
        for k_, v_ in pythonize_tags(tags or {}).items():
            resp.meta.tags[k_].CopyFrom(_py_to_value(v_))
        if self._static_names:
            resp.data.names.extend(self._static_names)
        y = np.ascontiguousarray(y, dtype=np.float64)
        resp.data.tensor.shape.extend(int(s) for s in y.shape)
        resp.data.tensor.values.extend(y.reshape(-1).tolist())
        return resp

    async def predict(self, msg: SeldonMessage) -> SeldonMessage:
        if not msg.meta.puid:
            msg.meta.puid = new_puid()
        t0 = time.perf_counter()
        n_rows = None
        with self.metrics.time_server("predictions", "POST") as code, self.tracer.span(
            msg.meta.puid, "request", kind="request", method="predict",
            mode=self.mode,
        ):
            try:
                if self.compiled is not None and msg.data is not None:
                    # device graphs need numeric payloads; a ragged/string
                    # ndarray parses to an object array and must fail as a
                    # 400 FAILURE message, not an opaque dispatch error
                    if msg.array().dtype == object:
                        raise SeldonMessageError(
                            "data payload is not a numeric rectangular tensor"
                        )
                if self.batcher is not None and msg.data is not None:
                    rows = np.atleast_2d(msg.array())
                    n_rows = len(rows)
                    y_rows, (routing, tags) = await self._submit(rows)
                    resp = msg.with_array(y_rows, names=self._static_names)
                    # fresh Meta/Status: with_array shares the request's meta
                    # object, and the response must match the unbatched
                    # compiled path exactly (compiled.CompiledGraph.predict)
                    from seldon_core_tpu.messages import Meta, Status

                    resp.meta = Meta(
                        puid=msg.meta.puid,
                        tags={**msg.meta.tags, **pythonize_tags(tags)},
                        routing={**msg.meta.routing, **routing},
                        requestPath=dict(msg.meta.requestPath),
                    )
                    resp.status = Status()
                    self._audit_request(
                        msg.meta.puid, "predict", 200, t0, rows=n_rows,
                        lane="object",
                    )
                    return resp
                if self.compiled is not None:
                    # device dispatch is synchronous but brief; keep the loop
                    # responsive by running it in the default executor
                    if self.mode == "fused":
                        # the demotion budget reads the deadline
                        # contextvar, which does not cross the executor
                        # thread — capture it here so in-program branch
                        # demotion sees the caller's remaining budget
                        budget = remaining_s()
                        call = lambda: self.compiled.predict(  # noqa: E731
                            msg, budget_s=budget
                        )
                    else:
                        call = lambda: self.compiled.predict(msg)  # noqa: E731
                    async with self._device_lock:
                        resp = await asyncio.get_running_loop().run_in_executor(
                            None, call
                        )
                else:
                    resp = await self.executor.predict(msg)
            except (SeldonMessageError, GraphSpecError) as e:
                http_code = getattr(e, "http_code", 400)
                code["code"] = str(http_code)
                # a shed is flow control, not an SLO error
                # (utils/metrics.py time_server)
                code["shed"] = isinstance(e, LoadShedError)
                self.tracer.annotate(
                    status=http_code, error=type(e).__name__,
                    shed=isinstance(e, LoadShedError))
                self._audit_request(
                    msg.meta.puid, "predict", http_code, t0, rows=n_rows,
                    lane="object",
                )
                return SeldonMessage.failure(
                    str(e), code=http_code, meta=msg.meta
                )
            resp.meta.puid = msg.meta.puid
            self._audit_request(
                msg.meta.puid, "predict", 200, t0, rows=n_rows, lane="object",
            )
            return resp

    async def send_feedback(self, feedback: Feedback) -> SeldonMessage:
        fb_puid = feedback.puid()
        t0 = time.perf_counter()
        truth_arr = feedback.truth_array()
        with self.metrics.time_server("feedback", "POST") as code, self.tracer.span(
            fb_puid, "request", kind="request", method="feedback",
        ):
            try:
                if self.compiled is not None:
                    routing = (
                        feedback.response.meta.routing
                        if feedback.response is not None
                        else {}
                    )
                    X = None
                    if feedback.request is not None and feedback.request.data is not None:
                        X = feedback.request.array()
                    async with self._device_lock:
                        await asyncio.get_running_loop().run_in_executor(
                            None,
                            lambda: self.compiled.feedback_arrays(
                                X, routing, feedback.reward, truth_arr
                            ),
                        )
                    ack = SeldonMessage()
                    if feedback.response is not None:
                        ack.meta.puid = feedback.response.meta.puid
                else:
                    ack = await self.executor.send_feedback(feedback)
            except (SeldonMessageError, GraphSpecError) as e:
                code["code"] = "400"
                # feedback requests consumed work and must leave a
                # telemetry trace like unary prediction errors do
                self._audit_request(
                    fb_puid, "feedback", 400, t0,
                    reward=float(feedback.reward),
                )
                return SeldonMessage.failure(str(e), code=400)
        self.metrics.record_feedback(feedback.reward)
        # quality observatory: rolling per-predictor reward + truth-vs-
        # prediction accuracy (+ the seldon_tpu_feedback_* families)
        QUALITY.record_feedback(
            self.predictor.name, feedback.reward,
            truth=truth_arr, prediction=feedback.prediction_array(),
        )
        self._audit_request(
            fb_puid, "feedback", 200, t0,
            reward=float(feedback.reward),
            truth_provided=truth_arr is not None,
        )
        return ack

    async def close(self) -> None:
        """Release pooled remote-node clients (host mode) and flush the
        request-audit firehose."""
        if self.genserver is not None:
            self.genserver.stop()
        if self.executor is not None:
            for rt in self.executor.runtimes.values():
                closer = getattr(rt, "close", None)
                if closer is not None:
                    await closer()
        await self.audit.stop()

    # -- admin (engine RestClientController.java:57-99) -----------------

    def ready(self) -> bool:
        return not self.paused

    def pause(self) -> None:
        self.paused = True

    def unpause(self) -> None:
        self.paused = False

    def drained(self) -> bool:
        """No work left anywhere in the process — the shutdown drain's
        early-exit probe (engine_main polls this instead of always
        sleeping out the full ``ENGINE_SHUTDOWN_DRAIN_S`` window)."""
        if self.batcher is not None:
            b = self.batcher.snapshot()
            if b.get("inflight_dispatches", 0):
                return False
            if any(v.get("requests", 0) for v in b.get("buckets", {}).values()):
                return False
        if self.genserver is not None:
            g = self.genserver.snapshot()
            if g.get("inflight_sequences", 0) or g.get("waiting_sequences", 0):
                return False
        return True

    # -- state persistence handoff --------------------------------------

    def states(self):
        if self.compiled is not None:
            return dict(self.compiled.states)
        return self.executor.states()

    def load_states(self, states) -> None:
        if self.compiled is not None:
            self.compiled.states.update(states)
        else:
            self.executor.load_states(states)
