"""Mesh construction.

A multi-pod mesh has a leading ``pod`` axis, which maps to the DCN dimension
and composes with ``data`` for batch/gradient reduction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


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
