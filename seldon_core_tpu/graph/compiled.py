"""Compiled-graph executor — the whole inference graph as ONE XLA program.

This is the TPU-native answer to the reference engine's per-node microservice
hops (engine PredictiveUnitBean.java:69-124 fans out over HTTP/gRPC with
per-call JSON marshalling): when every graph node is an in-process *pure*
JAX unit, the recursive evaluation

    transform_input -> route -> children -> aggregate -> transform_output

is traced once into a single jitted function over an explicit state pytree.
ROUTER branch choice becomes ``lax.switch`` (one branch executes on device,
no host round-trip), COMBINER fan-out becomes a stacked evaluation XLA is
free to fuse/parallelise, and unit state transitions (bandit counters, PRNG
keys, streaming statistics) thread functionally through the program.  The
feedback pass compiles the same way: ``meta.routing`` replays as traced
branch gates (``lax.cond``), so online learning updates also run on-device.

Structure conventions inside the traced program:
  * ``states``  — dict node-name -> state pytree, threaded through every call;
    all ``lax.switch`` branches return the full dict so structures match.
  * ``routing`` — dict router-name -> int32; routers not on the executed path
    report the sentinel ``NOT_ROUTED`` (-2), filtered out host-side (the
    reference only records visited routers in ``meta.routing``).
  * ``tags``    — flat dict tag-name -> traced value, later writers win
    (the reference's tag-merge rule, engine PredictiveUnitBean.java:252-264).
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from seldon_core_tpu.messages import Meta, SeldonMessage, Status
from seldon_core_tpu.graph.interpreter import (
    effective_type,
    methods_for,
    pythonize_tags,
    unit_rngs,
)
from seldon_core_tpu.graph.spec import (
    GraphSpecError,
    PredictiveUnit,
    PredictorSpec,
    UnitMethod,
    UnitType,
    params_to_kwargs,
)
from seldon_core_tpu.graph.units import (
    Unit,
    UNIT_REGISTRY,
    normalize_output,
    resolve_unit_class,
)

__all__ = ["CompiledGraph", "NOT_ROUTED", "build_units"]

# sentinel for "router not on executed path" — far outside any plausible
# branch index so a buggy router's negative return can't collide with it
NOT_ROUTED = -(2**30)


def _set_state(states: Dict[str, Any], name: str, new_state) -> Dict[str, Any]:
    """Functional state write.  The states-dict *structure* must be stable
    across traced branches, so a unit may only write state if it declared one
    via ``init_state`` (its key already exists)."""
    if new_state is None:
        return states
    if name not in states:
        raise GraphSpecError(
            f"unit {name!r} returned a state update but init_state() was None; "
            f"declare initial state so the compiled program can thread it"
        )
    out = dict(states)
    out[name] = new_state
    return out


def build_units(predictor: PredictorSpec, rng=None) -> Dict[str, Unit]:
    """Instantiate a pure in-process Unit for every graph node that needs one.
    Raises if any node is remote or impure — such graphs must use the host
    interpreter."""
    units: Dict[str, Unit] = {}
    comp_map = predictor.component_map()
    for node in predictor.graph.walk():
        unit: Optional[Unit] = None
        if node.implementation.value in UNIT_REGISTRY:
            unit = UNIT_REGISTRY[node.implementation.value](
                **params_to_kwargs(node.parameters)
            )
        else:
            binding = comp_map.get(node.name)
            if binding is None or binding.runtime != "inprocess":
                raise GraphSpecError(
                    f"node {node.name!r} is not an in-process unit; compiled mode "
                    f"requires every node in-process (use the host interpreter)"
                )
            from seldon_core_tpu.graph.units import instantiate_bound_unit

            unit = instantiate_bound_unit(binding, node)
        if not unit.pure:
            raise GraphSpecError(
                f"unit {node.name!r} ({type(unit).__name__}) is not pure; compiled "
                f"mode requires traceable units"
            )
        units[node.name] = unit
    return units


def _routers_in(node: PredictiveUnit) -> List[str]:
    return [
        u.name for u in node.walk() if UnitMethod.ROUTE in methods_for(u) and u.children
    ]


class CompiledGraph:
    """Compile a PredictorSpec's graph into jitted predict/feedback programs.

    Usage::

        cg = CompiledGraph(predictor)
        y, routing, tags = cg.predict_arrays(x)     # updates cg.states
        cg.feedback_arrays(x, routing, reward)      # on-device state update
        resp = cg.predict(msg)                      # SeldonMessage in/out
    """

    def __init__(self, predictor: PredictorSpec, rng=None, mesh=None):
        self.predictor = predictor
        self.units = build_units(predictor, rng)
        rngs = unit_rngs(list(self.units), rng)
        self.states: Dict[str, Any] = {}
        #: unit -> (start, end) of its ``init_state`` on time.monotonic():
        #: the engine's boot timeline names them (runtime/engine.py)
        self.init_at: Dict[str, tuple] = {}
        for name, unit in sorted(self.units.items()):
            t0 = time.monotonic()
            st = unit.init_state(rngs[name])
            self.init_at[name] = (t0, time.monotonic())
            if st is not None:
                self.states[name] = st
        self._all_routers = _routers_in(predictor.graph)
        self._router_children = {
            u.name: len(u.children)
            for u in predictor.graph.walk()
            if u.name in self._all_routers
        }
        self.mesh = mesh

        predict_fn = self._build_predict(predictor.graph)

        def run(states, X):
            y, states2, routing, tags = predict_fn(states, X)
            routing = {
                r: routing.get(r, jnp.int32(NOT_ROUTED)) for r in self._all_routers
            }
            return y, states2, routing, tags

        feedback_fn = self._build_feedback(predictor.graph)

        def run_fb(states, X, routing, reward, truth):
            return feedback_fn(states, X, routing, reward, truth)

        #: pure (states, X) -> (Y, states', routing, tags); re-jittable by
        #: callers that want custom shardings/donation
        self.predict_fn = run
        self.feedback_fn = run_fb
        self._jit_predict = jax.jit(run)
        self._jit_feedback = jax.jit(run_fb)
        # performance observatory (utils/perf.py): per-shape AOT-compiled
        # executables, keyed by executable_key.  The explicit
        # lower().compile() path measures the compile wall time and owns
        # the executable whose cost_analysis() yields the static FLOP /
        # byte features — None marks a shape where AOT failed and
        # dispatch stays on _jit_predict
        self._aot: Dict[str, Optional[Any]] = {}
        self._aot_building: set = set()
        self._aot_lock = threading.Lock()
        # bounded like the observatory's executable table: an exploding
        # shape set (including adversarial bad widths, which cache a
        # failed None) must not grow memory — past the cap novel shapes
        # ride the jit path uncaptured
        self._aot_cap = 128

    # ------------------------------------------------------------------
    # trace-time builders
    # ------------------------------------------------------------------

    def _build_predict(
        self, node: PredictiveUnit
    ) -> Callable[[Dict[str, Any], Any], Tuple[Any, Dict, Dict, Dict]]:
        unit = self.units[node.name]
        methods = methods_for(node)
        is_model = effective_type(node) is UnitType.MODEL
        child_fns = [self._build_predict(c) for c in node.children]
        name = node.name
        static_tags = dict(unit.static_tags or {})

        def fn(states, X):
            routing: Dict[str, Any] = {}
            tags: Dict[str, Any] = dict(static_tags)
            y = X
            if UnitMethod.TRANSFORM_INPUT in methods:
                m = unit.predict if is_model else unit.transform_input
                out = m(states.get(name), y)
                y, new_state, t = normalize_output(out, states.get(name))
                states = _set_state(states, name, new_state)
                tags.update(t)

            if node.children:
                if UnitMethod.ROUTE in methods:
                    out = unit.route(states.get(name), y)
                    branch, new_state, _ = normalize_output(out, states.get(name))
                    states = _set_state(states, name, new_state)
                    # record the RAW branch (predict_arrays raises post-hoc on
                    # out-of-range / broadcast values — XLA can't raise
                    # mid-program); clamp only the switch index
                    raw_branch = jnp.asarray(branch, dtype=jnp.int32)
                    branch = jnp.clip(raw_branch, 0, len(child_fns) - 1)
                    sub_routers = sorted(
                        {r for c in node.children for r in _routers_in(c)}
                    )

                    def make_branch(cf):
                        def bf(operand):
                            states_, x_ = operand
                            yc, s2, r, t = cf(states_, x_)
                            full_r = {
                                rn: r.get(rn, jnp.int32(NOT_ROUTED))
                                for rn in sub_routers
                            }
                            return yc, s2, full_r, t
                        return bf

                    try:
                        y, states, child_routing, child_tags = jax.lax.switch(
                            branch,
                            [make_branch(cf) for cf in child_fns],
                            (states, y),
                        )
                    except TypeError as e:
                        if "structure" in str(e) or "pytree" in str(e):
                            raise GraphSpecError(
                                f"router {name!r}: children return mismatched "
                                f"structures (shapes/tags must agree across "
                                f"branches for compiled routing): {e}"
                            ) from e
                        raise GraphSpecError(f"in subgraph of {name!r}: {e}") from e
                    routing[name] = raw_branch
                    routing.update(child_routing)
                    tags.update(child_tags)
                else:
                    ys = []
                    for cf in child_fns:
                        yc, states, r, t = cf(states, y)
                        ys.append(yc)
                        routing.update(r)
                        tags.update(t)
                    if UnitMethod.AGGREGATE in methods:
                        stacked = jnp.stack(ys, axis=0)
                        out = unit.aggregate(states.get(name), stacked)
                        y, new_state, t = normalize_output(out, states.get(name))
                        states = _set_state(states, name, new_state)
                        tags.update(t)
                    elif len(ys) == 1:
                        y = ys[0]
                    else:
                        raise GraphSpecError(
                            f"node {name!r} has {len(ys)} children but no "
                            f"AGGREGATE method to merge them"
                        )

            if UnitMethod.TRANSFORM_OUTPUT in methods:
                out = unit.transform_output(states.get(name), y)
                y, new_state, t = normalize_output(out, states.get(name))
                states = _set_state(states, name, new_state)
                tags.update(t)
            return y, states, routing, tags

        return fn

    def _build_feedback(self, node: PredictiveUnit):
        unit = self.units[node.name]
        methods = methods_for(node)
        child_fbs = [self._build_feedback(c) for c in node.children]
        name = node.name
        is_router = UnitMethod.ROUTE in methods and bool(node.children)

        def fn(states, X, routing, reward, truth):
            if UnitMethod.SEND_FEEDBACK in methods:
                branch = routing.get(name, jnp.int32(-1))
                new_state = unit.send_feedback(
                    states.get(name), X, branch, reward, truth
                )
                states = _set_state(states, name, new_state)
            for idx, cfb in enumerate(child_fbs):
                if is_router:
                    branch = routing.get(name, jnp.int32(-1))
                    selected = jnp.logical_or(branch == idx, branch == -1)
                    states = jax.lax.cond(
                        selected,
                        lambda s: cfb(s, X, routing, reward, truth),
                        lambda s: s,
                        states,
                    )
                else:
                    states = cfb(states, X, routing, reward, truth)
            return states

        return fn

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def executable_key(self, X) -> str:
        """Stable per-shape executable identity (perf observatory key) —
        reads only ``.shape``/``.dtype`` metadata, so naming a device
        array's executable never forces a device-to-host transfer."""
        from seldon_core_tpu.utils.perf import executable_key

        dtype = getattr(X, "dtype", None)
        if dtype is None:  # plain lists etc. — cold paths only
            dtype = np.asarray(X).dtype
        return executable_key("predict", np.shape(X), dtype)

    def _ensure_executable(self, X):
        """AOT-compile this shape once (measuring true compile wall time
        and capturing ``compile().cost_analysis()`` features into the
        observatory); returns (key, executable-or-None).  None means a
        concurrent build is in flight or AOT failed — the caller
        dispatches through ``_jit_predict`` with identical semantics."""
        from seldon_core_tpu.utils.perf import OBSERVATORY

        if not OBSERVATORY.enabled:
            return "", None
        key = self.executable_key(X)
        return key, self._aot_build(key, self._jit_predict, (self.states, X))

    def _aot_build(self, key: str, jitted, args: tuple):
        """The shared per-shape AOT path: ``jitted(*args)`` lowered and
        compiled once under ``key``, compile wall + cost features folded
        into the perf observatory, result cached in the bounded ``_aot``
        table.  Shared by this executor and the fused executor
        (graph/fuse.py) so both ride one compile-cache discipline."""
        from seldon_core_tpu.utils.perf import (
            OBSERVATORY,
            extract_cost_features,
        )

        with self._aot_lock:
            if key in self._aot:
                return self._aot[key]
            if key in self._aot_building or len(self._aot) >= self._aot_cap:
                # first dispatch of this shape is mid-compile in another
                # thread (ride the jit path rather than wait), or the
                # bounded cache is full (novel shapes go uncaptured)
                return None
            self._aot_building.add(key)
        compiled = None
        features = None
        compile_s = None
        try:
            t0 = time.perf_counter()
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            try:
                features = extract_cost_features(compiled.cost_analysis())
            except Exception:  # noqa: BLE001 - backend without the API
                features = None
            if features is None:
                # pre-optimization HLO features beat no features at all
                try:
                    features = extract_cost_features(lowered.cost_analysis())
                except Exception:  # noqa: BLE001
                    features = None
        except Exception:  # noqa: BLE001 - AOT unsupported: jit path serves
            compiled = None
        finally:
            with self._aot_lock:
                self._aot[key] = compiled
                self._aot_building.discard(key)
        OBSERVATORY.record_compile(key, features, compile_s)
        return compiled

    def predict_arrays(
        self, X, update_states=True
    ) -> Tuple[Any, Dict[str, int], Dict[str, Any]]:
        """Run the compiled graph; returns (Y, routing, tags) and advances the
        held unit states.

        ``update_states=False`` skips the state write-back: when no unit
        updates state on predict the returned states equal the inputs, and
        skipping the read-modify-write lets the engine pipeline several
        in-flight dispatches without a stale write-back clobbering a
        concurrent feedback update.  A callable is evaluated AFTER the
        device round-trip, letting the engine veto a write-back whose
        request already timed out (the client saw a 504 — a late state
        update would double-apply on retry)."""
        X = jnp.asarray(X)
        key, executable = self._ensure_executable(X)
        if executable is not None:
            try:
                y, new_states, routing, tags = executable(self.states, X)
            except Exception:  # noqa: BLE001 - aval drift (e.g. weak-typed
                # state leaves strengthened by an update): permanently fall
                # back to the jit path for this shape, same program
                with self._aot_lock:
                    self._aot[key] = None
                y, new_states, routing, tags = self._jit_predict(
                    self.states, X
                )
        else:
            y, new_states, routing, tags = self._jit_predict(self.states, X)
        routing_py = {
            k: int(v) for k, v in routing.items() if int(v) != NOT_ROUTED
        }
        # compiled routing cannot broadcast (-1) or raise mid-program; surface
        # invalid branches here instead of returning clamped garbage (the host
        # interpreter raises the same error inline,
        # interpreter.GraphExecutor._get_output)
        for r, v in routing_py.items():
            if v < 0 or v >= self._router_children[r]:
                raise GraphSpecError(
                    f"router {r!r} chose branch {v} but has "
                    f"{self._router_children[r]} children (broadcast routing is "
                    f"host-mode only)"
                )
        if callable(update_states):
            # the gate decides based on wall time AFTER the device work
            # finished — JAX dispatch is async, so without forcing here the
            # gate would fire microseconds after enqueue and always pass
            jax.block_until_ready(new_states)
            do_update = update_states()
        else:
            do_update = update_states
        if do_update:
            self.states = new_states
        return y, routing_py, tags

    def feedback_arrays(
        self,
        X,
        routing: Dict[str, int],
        reward: float,
        truth=None,
    ) -> None:
        """On-device feedback/state update, replaying the recorded routing."""
        routing_traced = {
            r: jnp.int32(routing.get(r, -1)) for r in self._all_routers
        }
        if X is not None:
            X = jnp.asarray(X)
        self.states = self._jit_feedback(
            self.states, X, routing_traced, jnp.float32(reward), truth
        )

    # -- SeldonMessage API (drop-in for GraphExecutor at the edge) ----------

    def predict(self, msg: SeldonMessage) -> SeldonMessage:
        # 1-D wire payloads mean a single sample; units assume a leading
        # batch axis (same normalisation as the micro-batched engine path)
        y, routing, tags = self.predict_arrays(
            jnp.atleast_2d(jnp.asarray(msg.array()))
        )
        leaf_names = self._output_names(self.predictor.graph, routing)
        resp = msg.with_array(y, names=leaf_names)
        resp.meta = Meta(
            puid=msg.meta.puid,
            tags={**msg.meta.tags, **pythonize_tags(tags)},
            routing={**msg.meta.routing, **routing},
            requestPath=dict(msg.meta.requestPath),
        )
        resp.status = Status()
        return resp

    def _output_names(
        self, node: PredictiveUnit, routing: Dict[str, int]
    ) -> Optional[list]:
        """Names of the unit that actually produced the output, following the
        recorded routing — matches the host interpreter, where each response
        carries the names set by the last unit on the executed path."""
        unit = self.units[node.name]
        methods = methods_for(node)
        names: Optional[list] = None
        if UnitMethod.TRANSFORM_INPUT in methods and unit.class_names is not None:
            names = list(unit.class_names)
        if node.children:
            if UnitMethod.ROUTE in methods and node.name in routing:
                child = node.children[routing[node.name]]
                names = self._output_names(child, routing) or names
            elif UnitMethod.AGGREGATE in methods:
                if unit.class_names is not None:
                    names = list(unit.class_names)
                else:
                    names = self._output_names(node.children[0], routing) or names
            else:
                names = self._output_names(node.children[0], routing) or names
        if UnitMethod.TRANSFORM_OUTPUT in methods and unit.class_names is not None:
            names = list(unit.class_names)
        return names

    # -- compilation introspection ------------------------------------------

    def lower_text(self, X) -> str:
        """StableHLO of the predict program (debugging/benchmark evidence)."""
        return self._jit_predict.lower(self.states, jnp.asarray(X)).as_text()
