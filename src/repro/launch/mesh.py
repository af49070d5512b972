"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device counts are locked on first jax init, and
only ``launch/dryrun.py`` sets the 512-host-device XLA flag.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes, so ``with_sharding_constraint`` (and
    ``shardctx.constrain``) may name them; the default is Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
