"""Ambient-mesh-aware sharding constraints.

``constrain(x, spec_axes)`` applies ``with_sharding_constraint`` only when a
mesh is ambient (inside ``with jax.set_mesh(mesh):``) AND every requested axis
exists AND the corresponding dim divides evenly — so model code can express
its preferred layout once and still run un-meshed (CPU tests) or on meshes
where a dim doesn't divide (falls back to unconstrained for that dim).
"""

from __future__ import annotations

from typing import Sequence, Tuple, Union

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

__all__ = ["constrain", "ambient_mesh", "axis_size", "abstract_mesh", "host_mesh"]


def host_mesh(shards: int, axis: str = "model"):
    """A physical 1-D ``(axis,)`` mesh over the first ``shards`` devices.

    The entry point for the sharded verifier and its tests.  On a TPU host
    the devices are its chips (a 2x2 v5e host gives four).  On a CPU host
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, set before jax
    initializes its backends, exposes N host devices, so a multi-shard
    ``shard_map`` launch runs (and is proven bit-exact) without chips.
    Raises with both remedies spelled out when the process sees fewer
    devices than requested.
    """
    from jax.sharding import Mesh

    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    devices = jax.devices()
    if len(devices) < shards:
        raise RuntimeError(
            f"need {shards} devices for a {shards}-shard mesh but only "
            f"{len(devices)} {devices[0].platform} devices are visible; run on a "
            f"host with {shards} chips, or on a CPU host set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={shards} "
            "in the environment before jax initializes"
        )
    return Mesh(np.asarray(devices[:shards]), (axis,))


def abstract_mesh(sizes: Sequence[int], names: Sequence[str]):
    """A device-free ``AbstractMesh`` with Auto axes (for spec planning)."""
    from jax.sharding import AbstractMesh, AxisType

    return AbstractMesh(tuple(sizes), tuple(names), axis_types=(AxisType.Auto,) * len(names))


def ambient_mesh():
    """The mesh made current by ``jax.set_mesh(mesh)``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def axis_size(mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    names = axis if isinstance(axis, (tuple, list)) else (axis,)
    return int(np.prod([dict(mesh.shape)[n] for n in names]))


def constrain(x: jax.Array, axes: Sequence[Axis]) -> jax.Array:
    """Constrain dims of x to the given mesh axes where possible."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax is None:
            spec.append(None)
            continue
        # Keep only axes present in the ambient mesh (e.g. 'pod' exists only
        # on the multi-pod mesh; ('pod','data') degrades to ('data',)).
        ax_names = tuple(a for a in (ax if isinstance(ax, (tuple, list)) else (ax,)) if a in names)
        if not ax_names:
            spec.append(None)
            continue
        if dim % axis_size(mesh, ax_names) != 0:
            spec.append(None)
            continue
        spec.append(ax_names if len(ax_names) > 1 else ax_names[0])
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
