"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ('data','model');
multi-pod: 2x16x16 = 512 chips ('pod','data','model') — the 'pod' axis
composes with 'data' for batch/FSDP sharding, so the multi-pod compile
proves the pod axis shards.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2)).  Axes are
    ``Auto``: the sharding rules in ``repro.dist`` place arrays with
    ``with_sharding_constraint``, which binds only to Auto axes (JAX's
    ``make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def mesh_axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def dp_degree(mesh) -> int:
    return mesh_axis_size(mesh, "pod") * mesh_axis_size(mesh, "data")
