"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis maps to
the DCN dimension and composes with ``data`` for batch/gradient reduction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic re-mesh, tests on few host devices)."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def dp_axes(mesh) -> tuple:
    """Axes that carry the global batch (pod composes with data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
