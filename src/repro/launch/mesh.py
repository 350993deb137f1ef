"""Mesh construction for the production systems.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before the first jax call, while smoke tests must
see the single real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.hardware import SystemSpec


def _auto_mesh(shape, axes) -> Mesh:
    """Mesh over the first ``prod(shape)`` devices whose axes GSPMD
    partitions (``with_sharding_constraint`` in ``distributed.constraints``
    needs Auto axes; ``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(system: SystemSpec) -> Mesh:
    return _auto_mesh(system.mesh_shape, system.mesh_axes)


def make_smoke_mesh() -> Mesh:
    """1x1 mesh over device 0 (tests, CPU benches, the one-chip reference)."""
    return _auto_mesh((1, 1), ("data", "model"))
