"""Production mesh construction (DESIGN.md §5).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first jax
init, and smoke tests / benches must keep seeing 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def _auto_mesh(shape, axes):
    # the model stack shards by GSPMD constraints (models/sharding.py),
    # which take Auto axes; jax.make_mesh defaults to Explicit ones
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ('data','model') single-pod, or 2x16x16 ('pod','data','model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(mc: MeshConfig):
    return _auto_mesh(mc.shape, mc.axes)


def describe(mesh) -> str:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return "x".join(f"{a}={n}" for a, n in sizes.items())
