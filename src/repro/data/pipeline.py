"""Host batches onto the device: ``batch_shardings`` splits a batch's rows
over the mesh's data-parallel axes, and ``put_batch`` is ``jax.device_put``
in those shardings, so each host materializes only its addressable shards.
The trainer overlaps the next batch's build and put with the running step
(``train/trainer.py``).
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_shardings(mesh: Mesh, dp_axes=None) -> Dict[str, NamedSharding]:
    dp = dp_axes or tuple(a for a in mesh.axis_names if a != "model")
    return {
        "dense": NamedSharding(mesh, P(dp, None)),
        "cat": NamedSharding(mesh, P(dp, None, None)),
        "label": NamedSharding(mesh, P(dp)),
    }


def put_batch(batch: Dict[str, np.ndarray], mesh: Mesh) -> Dict:
    sh = batch_shardings(mesh)
    return {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
