"""Production mesh construction (deliverable e).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count locks on first jax init, and smoke
tests must keep seeing 1 device).

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the leading
"pod" axis is the slow (DCN/inter-pod) dimension; gradient reductions are
hierarchical across it (DESIGN.md §5).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_test_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over the first devices, every axis Auto-sharded."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
