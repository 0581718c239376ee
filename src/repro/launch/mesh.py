"""Mesh construction for single-pod and multi-pod deployments.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required so smoke tests see a
single CPU device while the dry-run process sees 512 placeholder devices.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

from repro import compat
from repro.compat import AxisType
from repro.configs.base import MeshConfig, MULTI_POD, SINGLE_POD


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes,
                            axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(cfg: MeshConfig) -> Mesh:
    return compat.make_mesh(
        cfg.shape, cfg.axes, axis_types=(AxisType.Auto,) * len(cfg.axes)
    )


def make_test_mesh(shape: Sequence[int] = (1, 1),
                   axes: Sequence[str] = ("data", "model")) -> Mesh:
    """A mesh sized for whatever devices exist (CPU tests)."""
    return compat.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_cache_mesh(stripes: int, *, axis: str = "cache") -> Mesh:
    """1-D mesh for the striped HPS L1 payload over ``min(stripes,
    devices)`` devices, stripe ``i`` on device ``i * size / stripes``.

    Raises when the stripes cannot tile that many devices evenly: a
    smaller mesh would quietly hold the payload on fewer chips than the
    deployment asked for."""
    import numpy as np

    devices = jax.devices()
    size = min(stripes, len(devices))
    if stripes < 1 or stripes % size:
        raise ValueError(
            f"{stripes} cache stripes cannot tile {size} of the "
            f"{len(devices)} devices evenly; use a stripe count that is "
            f"a multiple of {len(devices)} or at most it")
    return Mesh(np.asarray(devices[:size]), (axis,))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes carrying the batch dimension (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def all_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def mesh_config_for(mesh: Mesh) -> MeshConfig:
    return MeshConfig(tuple(mesh.devices.shape), tuple(mesh.axis_names))
