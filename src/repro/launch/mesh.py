"""Production mesh construction (DESIGN.md section 5).

Defined as functions - importing this module never touches jax device state.
Single pod: 16 x 16 = 256 chips ("data", "model"); multi-pod: 2 x 16 x 16 =
512 chips with the leading "pod" axis spanning the cross-pod (DCI) links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0) -> Mesh:
    """Small mesh for tests (uses however many devices exist)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def mesh_name(mesh: Mesh) -> str:
    return "x".join(f"{k}{v}" for k, v in mesh.shape.items())
