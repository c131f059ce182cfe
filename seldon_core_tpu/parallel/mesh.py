"""Device-mesh construction and sharding helpers.

Axis conventions used across the framework:

  ``dp``  — data parallel (batch sharding; gradients psum here)
  ``tp``  — tensor parallel (weight matrices sharded; activations all-reduce)
  ``sp``  — sequence/context parallel (ring attention rotates K/V here)
  ``ens`` — ensemble/expert parallel (COMBINER members, one per slice;
            reduction = psum over ICI — the TPU equivalent of the reference
            engine broadcasting to child microservices and averaging,
            engine PredictiveUnitBean.java:96-118)

Meshes come from ``jax.make_mesh`` so axis order maps onto the physical ICI
topology; on CPU test platforms the same code runs over
``--xla_force_host_platform_device_count`` virtual devices (SURVEY.md §4's
minikube-replacement strategy)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MeshSpec", "build_mesh", "local_device_count", "shard_batch",
           "shard_map"]


#: every shard_map call site in the repo routes through this name
shard_map = jax.shard_map


def local_device_count() -> int:
    return len(jax.devices())


@dataclass
class MeshSpec:
    """Declarative mesh request, e.g. ``MeshSpec({'dp': 2, 'ens': 4})``.
    A -1 axis absorbs the remaining devices (like a reshape wildcard)."""

    axes: Dict[str, int] = field(default_factory=dict)

    def resolve(self, n_devices: Optional[int] = None) -> Dict[str, int]:
        n = n_devices or local_device_count()
        axes = dict(self.axes) or {"dp": -1}
        wildcards = [k for k, v in axes.items() if v == -1]
        if len(wildcards) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wildcards}")
        fixed = int(np.prod([v for v in axes.values() if v != -1]))
        if wildcards:
            if n % fixed != 0:
                raise ValueError(
                    f"cannot fill axis {wildcards[0]!r}: {n} devices not "
                    f"divisible by {fixed}"
                )
            axes[wildcards[0]] = n // fixed
            fixed = n
        if fixed > n:
            raise ValueError(f"mesh {axes} needs {fixed} devices, have {n}")
        return axes


def build_mesh(
    spec: MeshSpec | Dict[str, int] | None = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a Mesh over (a prefix of) the available devices."""
    if isinstance(spec, dict):
        spec = MeshSpec(spec)
    spec = spec or MeshSpec()
    devs = list(devices) if devices is not None else jax.devices()
    axes = spec.resolve(len(devs))
    names = tuple(axes)
    shape = tuple(axes[n] for n in names)
    n_used = int(np.prod(shape))
    dev_array = np.asarray(devs[:n_used]).reshape(shape)
    return Mesh(dev_array, names)


def shard_batch(mesh: Mesh, x, axis: str = "dp"):
    """Device-put a host batch sharded along the leading axis."""
    if axis not in mesh.axis_names:
        return jax.device_put(x, NamedSharding(mesh, P()))
    return jax.device_put(
        x, NamedSharding(mesh, P(axis, *([None] * (np.ndim(x) - 1))))
    )
