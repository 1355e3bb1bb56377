"""Production meshes.

A function (never a module-level constant) so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (v5e pod),
("data", "model"). Multi-pod: 2×16×16 = 512 chips with a leading pure-DP
"pod" axis — scaling to N pods extends that axis only (gradient all-reduce
crosses DCI once per step; no model collective ever leaves a pod).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def model_axis(mesh):
    return "model" if "model" in mesh.shape else None
