"""Logical-axis sharding rules (GSPMD style, MaxText-like).

Tensors are annotated with *logical* axis names; `rules()` maps them onto
mesh axes.  A context variable holds the active mesh so the same model code
runs un-sharded in CPU smoke tests (constraints become no-ops) and fully
sharded under the production mesh.

Physical mapping (DESIGN.md §5):
  batch   -> ('pod', 'data')   DP
  fsdp    -> ('data',)         parameter/optimizer sharding (ZeRO-3)
  model   -> ('model',)        TP: heads / ffn hidden / vocab / experts
  seq_kv  -> ('model',)        KV-cache sequence sharding for small-kv decode
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def _axes(mesh: Mesh, logical: Optional[str]):
    if logical is None:
        return None
    names = set(mesh.axis_names)
    table = {
        "batch": tuple(a for a in ("pod", "data") if a in names),
        "fsdp": ("data",) if "data" in names else (),
        "expert": ("model",) if "model" in names else (),
        "model": ("model",) if "model" in names else (),
        "seq_kv": ("model",) if "model" in names else (),
        # sequence over the data axes (long-context, batch too small to DP)
        "seq_data": tuple(a for a in ("pod", "data") if a in names),
        "seq_all": tuple(a for a in ("pod", "data", "model") if a in names),
    }
    ax = table.get(logical, ())
    return ax if ax else None


def spec(*logical: Optional[str]) -> Optional[P]:
    """PartitionSpec for logical axes under the current mesh (None w/o mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return None
    return P(*[_axes(mesh, l) for l in logical])


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint if a mesh is active; identity otherwise.

    Divisibility guard: a logical mapping is dropped (replicated) when the
    dim does not divide the mapped axes — e.g. kv_heads=8 over model=16.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    assert x.ndim == len(logical), (x.shape, logical)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    used: set = set()
    for dim, l in zip(x.shape, logical):
        ax = _axes(mesh, l)
        if ax is not None:
            # a mesh axis may appear on at most one dim (first taker wins;
            # e.g. seq_kv and kv-heads both want 'model' when batch=1)
            ax = tuple(a for a in ax if a not in used)
        if not ax:
            out.append(None)
            continue
        n = 1
        for a in ax:
            n *= sizes[a]
        if n and dim % n == 0:
            out.append(ax)
            used.update(ax)
        else:
            out.append(None)
    # a GSPMD constraint names Auto axes; a mesh from jax.make_mesh has
    # Explicit ones, so constrain on the same devices viewed as Auto
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    return jax.lax.with_sharding_constraint(x, NamedSharding(auto, P(*out)))


def named_sharding(*logical: Optional[str]) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, spec(*logical))
