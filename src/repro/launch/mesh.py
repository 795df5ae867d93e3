"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  Single pod: (data=16, model=16) = 256 chips.  Multi-pod:
(pod=2, data=16, model=16) = 512 chips, `pod` as the slow (DCN/ICI-bridge)
axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.config import ParallelConfig


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """jax.make_mesh with every axis explicitly Auto-sharded."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(parallel: ParallelConfig):
    """Mesh matching an arbitrary ParallelConfig (tests use small ones)."""
    return make_mesh(parallel.mesh_shape(), parallel.mesh_axes())


def parallel_for_mesh(mesh) -> ParallelConfig:
    s = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ParallelConfig(pods=s.get("pod", 1), data=s.get("data", 1),
                          model=s.get("model", 1))
