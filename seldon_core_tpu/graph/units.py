"""Predictive-unit implementations: the functional, JAX-first unit protocol
plus the reference's built-in (hardcoded) units.

The reference models a unit as a stateful object with request/response methods
(engine PredictiveUnitImpl subclasses; wrappers' user classes).  TPU-first, a
unit is a **pure function bundle over an explicit state pytree**, so any unit
can be traced into the graph's single XLA program and any state update
(bandit counters, streaming statistics) is an explicit ``state -> state``
transition that the executor threads — there is no hidden Python mutation to
break under ``jit``.

Method protocol (all arrays are jax arrays, leading batch axis):

    init_state(rng)                  -> state pytree (None if stateless)
    predict(state, X)                -> Y            | (Y, UnitAux)
    transform_input(state, X)        -> X'           | (X', UnitAux)
    transform_output(state, Y)       -> Y'           | (Y', UnitAux)
    route(state, X)                  -> branch int32 | (branch, UnitAux)
    aggregate(state, Ys)             -> Y            | (Y, UnitAux)   # Ys stacked [n_children, ...]
    send_feedback(state, X, branch, reward, truth) -> state

``UnitAux(state=..., tags=...)`` lets a method update unit state and/or attach
data-dependent meta tags (e.g. an outlier score) without breaking purity: both
travel as traced pytrees.  Built-ins mirrored from the reference:

  * SimpleModelUnit  — fixed [0.1, 0.9, 0.5] / class0..2 stub
    (engine SimpleModelUnit.java:29-44)
  * SimpleRouterUnit — always branch 0 (engine SimpleRouterUnit.java:24-31)
  * RandomABTestUnit — uniform draw <= ratioA => branch 0, exactly 2 children
    (engine RandomABTestUnit.java:27-58); PRNG is a threaded jax.random key
    instead of a hidden java.util.Random(1337)
  * AverageCombinerUnit — shape-checked element-wise mean over child outputs
    (engine AverageCombinerUnit.java:30-95); on an ensemble mesh axis this
    lowers to a psum over ICI (see parallel/ensemble.py)
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Type

import jax
import jax.numpy as jnp

__all__ = [
    "UnitAux",
    "Unit",
    "normalize_output",
    "register_unit",
    "resolve_unit_class",
    "instantiate_bound_unit",
    "UNIT_REGISTRY",
    "SimpleModelUnit",
    "SimpleRouterUnit",
    "RandomABTestUnit",
    "AverageCombinerUnit",
]


class UnitAux(NamedTuple):
    """Optional second return value of any unit method."""

    state: Any = None  # replacement state pytree, or None = unchanged
    tags: Optional[Dict[str, Any]] = None  # data-dependent meta tags


def normalize_output(out, old_state):
    """Normalize ``Y`` or ``(Y, UnitAux)`` to ``(Y, state, tags)``."""
    if isinstance(out, tuple) and len(out) == 2 and isinstance(out[1], UnitAux):
        y, aux = out
        state = aux.state if aux.state is not None else old_state
        return y, state, (aux.tags or {})
    return out, old_state, {}


class Unit:
    """Base class for in-process units.  Subclasses override the methods for
    their unit type; unimplemented methods raise, which the engine surfaces
    as a graph-spec error (the reference's dispatch table guards the same way,
    engine PredictorConfigBean.java:33-96)."""

    #: True if every implemented method is jax-traceable (pure); the compiled
    #: executor refuses impure units, the host interpreter accepts both.
    pure: bool = True
    #: True when the predict/transform path returns state updates that depend
    #: on the rows seen (streaming statistics).  Engines must not pad batches
    #: through such units (padding rows would enter the statistics).
    updates_state_on_predict: bool = False
    #: True when outputs couple rows across the batch (cross-row reductions,
    #: e.g. batch-global min/max normalisation).  Engines must not coalesce
    #: concurrent requests through such units — one caller's rows would
    #: change another caller's answer.
    batch_coupled: bool = False
    #: optional output feature names (the wrappers' class_names)
    class_names: Optional[list] = None
    #: static meta tags merged into every response this unit touches
    static_tags: Optional[dict] = None
    #: Pallas kernels this unit's predict path takes, decided at
    #: construction (static under jit); the engine names them on its
    #: ``engine up:`` line so the path in use is stated, never inferred.
    #: Kernels chosen per call from the shapes (flash attention) are not
    #: listed here.
    kernels: Tuple[str, ...] = ()

    def init_state(self, rng) -> Any:
        return None

    # -- request-path methods (pure, traceable) -----------------------------

    def predict(self, state, X):
        raise NotImplementedError(f"{type(self).__name__} does not implement predict")

    def transform_input(self, state, X):
        raise NotImplementedError(
            f"{type(self).__name__} does not implement transform_input"
        )

    def transform_output(self, state, Y):
        raise NotImplementedError(
            f"{type(self).__name__} does not implement transform_output"
        )

    def route(self, state, X):
        raise NotImplementedError(f"{type(self).__name__} does not implement route")

    def aggregate(self, state, Ys):
        raise NotImplementedError(f"{type(self).__name__} does not implement aggregate")

    # -- feedback path (pure state transition) ------------------------------

    def send_feedback(self, state, X, branch, reward, truth):
        """Return the new state.  ``branch`` is the child index this unit
        routed the original request to (-1 if not a router)."""
        return state


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

UNIT_REGISTRY: Dict[str, Type[Unit]] = {}


def register_unit(name: str) -> Callable[[Type[Unit]], Type[Unit]]:
    def deco(cls: Type[Unit]) -> Type[Unit]:
        UNIT_REGISTRY[name] = cls
        return cls

    return deco


def resolve_unit_class(class_path: str) -> Type[Unit]:
    """Resolve ``registered-name`` or ``module:Class`` to a unit class —
    the TPU equivalent of the wrappers' importlib loading
    (wrappers/python/microservice.py:154-155)."""
    if class_path not in UNIT_REGISTRY:
        # built-in model families register on import; load them lazily so a
        # bare registered name like "MnistClassifier" resolves
        import importlib

        importlib.import_module("seldon_core_tpu.models")
    if class_path in UNIT_REGISTRY:
        return UNIT_REGISTRY[class_path]
    if ":" in class_path:
        mod_name, _, cls_name = class_path.partition(":")
        import importlib

        try:
            mod = importlib.import_module(mod_name)
        except ImportError as e:
            raise ValueError(f"cannot import unit module {mod_name!r}: {e}") from e
        try:
            return getattr(mod, cls_name)
        except AttributeError as e:
            raise ValueError(f"module {mod_name!r} has no class {cls_name!r}") from e
    raise ValueError(
        f"unknown unit {class_path!r}: not registered and not a module:Class path"
    )


def instantiate_bound_unit(binding, node) -> Unit:
    """Build the in-process Unit for a component binding, honouring the
    binding's ``mesh_axes``: a declared mesh (e.g. ``{"tp": 4}`` or
    ``{"ens": 8}``) is constructed over the local devices and handed to the
    unit, so one graph node spans a multi-chip mesh through the standard
    deployment JSON (SURVEY.md §2.7's graph-node-spans-a-mesh design).
    Units that cannot shard reject the binding loudly."""
    from seldon_core_tpu.graph.spec import GraphSpecError, params_to_kwargs

    cls = resolve_unit_class(binding.class_path)
    kwargs = params_to_kwargs(binding.parameters or node.parameters)
    if binding.mesh_axes:
        import inspect

        from seldon_core_tpu.parallel.mesh import build_mesh

        if "mesh" not in inspect.signature(cls.__init__).parameters:
            raise GraphSpecError(
                f"component {binding.name!r} declares mesh_axes "
                f"{dict(binding.mesh_axes)} but unit {cls.__name__} takes no "
                f"mesh; drop mesh_axes or use a mesh-capable unit"
            )
        kwargs["mesh"] = build_mesh(dict(binding.mesh_axes))
    # reference-style plain user objects (predict(X, names) / route / ...)
    # get the SAME adapter the microservice wrapper applies (as_unit), so
    # ANY model-library class (torch/sklearn-style) binds inprocess too —
    # the engine serves it host-mode (UserObjectUnit.pure = False keeps it
    # out of the compiled XLA program, exactly like a remote wrapper node)
    from seldon_core_tpu.graph.interpreter import effective_type
    from seldon_core_tpu.runtime.microservice import as_unit

    # effective_type resolves implementation-implied types the same way
    # the interpreter's method dispatch does (a node may omit `type`)
    etype = effective_type(node)
    service_type = etype.name if etype is not None else "MODEL"
    return as_unit(cls(**kwargs), service_type)


# ---------------------------------------------------------------------------
# Built-in (hardcoded) units
# ---------------------------------------------------------------------------


@register_unit("SIMPLE_MODEL")
class SimpleModelUnit(Unit):
    """Test stub: returns the fixed row [0.1, 0.9, 0.5] per batch element
    (engine SimpleModelUnit.java:33-44)."""

    values = (0.1, 0.9, 0.5)
    class_names = ["class0", "class1", "class2"]

    def predict(self, state, X):
        batch = X.shape[0] if X.ndim >= 1 else 1
        row = jnp.asarray(self.values, dtype=jnp.float32)
        return jnp.tile(row[None, :], (batch, 1))

    # MODEL nodes are dispatched via TRANSFORM_INPUT in the reference
    # (PredictorConfigBean: MODEL => [TRANSFORM_INPUT]); the engine maps that
    # to predict for MODEL-typed units, so only predict needs implementing.


@register_unit("SIMPLE_ROUTER")
class SimpleRouterUnit(Unit):
    """Always routes to child 0 (engine SimpleRouterUnit.java:24-31)."""

    def route(self, state, X):
        return jnp.int32(0)


@register_unit("RANDOM_ABTEST")
class RandomABTestUnit(Unit):
    """Seeded random A/B split: uniform <= ratioA => branch 0
    (engine RandomABTestUnit.java:35-58).  State is the PRNG key, threaded
    explicitly — deterministic for a fixed seed like the reference's
    ``Random(1337)``."""

    def __init__(self, ratioA: float = 0.5, seed: int = 1337):
        self.ratioA = float(ratioA)
        self.seed = int(seed)

    def init_state(self, rng):
        if rng is None:
            rng = jax.random.key(self.seed)
        return rng

    def route(self, state, X):
        key, sub = jax.random.split(state)
        comparator = jax.random.uniform(sub)
        branch = jnp.where(comparator <= self.ratioA, jnp.int32(0), jnp.int32(1))
        return branch, UnitAux(state=key)


@register_unit("AVERAGE_COMBINER")
class AverageCombinerUnit(Unit):
    """Element-wise mean over child outputs (engine AverageCombinerUnit.java:30-95).
    ``Ys`` arrives stacked on a leading children axis; the shape agreement the
    reference checks row-by-row is enforced structurally by the stacking."""

    def aggregate(self, state, Ys):
        return jnp.mean(Ys, axis=0)
