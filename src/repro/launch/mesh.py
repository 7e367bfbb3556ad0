"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
only the cross-pod gradient all-reduce (DESIGN.md §6), making pods the
fault/elasticity domain at 1000+ node scale.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

# the sparse path's shard mesh, owned by ``repro.sparse`` (DESIGN.md §10)
from ..sparse.partition import SHARD_AXIS, make_shard_mesh  # noqa: F401


def auto_mesh(shape: Sequence[int], axes: Sequence[str], devices: Sequence):
    """``jax.make_mesh`` with every axis ``Auto``: the partitioner places
    arrays and ``with_sharding_constraint`` accepts specs over any axis
    (``Explicit`` axes, the ``make_mesh`` default, refuse such hints)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if devices is None:
        n = 1
        for s in shape:
            n *= s
        devices = jax.devices()[:n]
    return auto_mesh(shape, axes, devices)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests)."""
    devices = jax.devices()[: data * model]
    return auto_mesh((data, model), ("data", "model"), devices)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod included when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
