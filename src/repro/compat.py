"""The few JAX APIs the repo reaches through one place.

The repo targets the installed JAX (0.9) only. Call sites import
``shard_map``, ``make_mesh`` and ``AxisType`` from here, so a future API
move is one edit:

* ``shard_map``  — ``jax.shard_map``. It takes a concrete ``Mesh`` both
                   eagerly and staged under ``jit``.
* ``make_mesh``  — ``jax.make_mesh`` with optional ``axis_types``.
* ``AxisType``   — ``jax.sharding.AxisType``.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["AxisType", "make_mesh", "shard_map"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Sequence] = None) -> Mesh:
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=None if axis_types is None
                         else tuple(axis_types))


def shard_map(f: Callable, *, mesh: Mesh, in_specs, out_specs,
              check_vma: bool = True) -> Callable:
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
