"""Striped physical storage for the L1 device payload.

``DeviceEmbeddingCache`` resolves ids to *logical slots*; this module owns
where a slot physically lives. The companion HPS paper (arXiv 2210.08804)
stripes the GPU embedding cache across devices so the hot working set
scales past one device's HBM — here slot ``s`` lives on stripe ``s % N``
at local row ``s // N``, and the stripes are laid out over a 1-D mesh
axis (``launch.mesh.make_cache_mesh``) when one is available, or kept as
host shards of a single stacked array otherwise. Because callers only
ever see logical slots, the cache's index/eviction machinery is entirely
layout-agnostic.

``shards=1`` reproduces the original single-payload behavior bit-exactly:
same physical padding, same one-scatter write path, same
``ops.cache_gather`` read path.

Payload precision is a storage knob (``payload_dtype``): ``"f32"`` is the
bit-exact baseline, ``"f16"`` halves the row bytes, ``"int8"`` stores
per-row absmax-quantized rows plus an f32 scale vector striped alongside
the payload — at a fixed HBM byte budget that is 2-4x more resident hot
rows, which is the cheapest L1 hit-rate lever there is (ScaleFreeCTR,
arXiv 2104.08542). Quantization happens host-side on insert/refresh;
reads dequantize inside the fused Pallas gather kernel, so the serving
path stays a single f32 dispatch regardless of storage precision.

Snapshots are immutable jax arrays: ``scatter`` rebinds the payload, so a
reader holding a snapshot is never affected by concurrent writes — the
property the cache's lock-consistent query path relies on. A snapshot is
the pair ``(payload, scales)`` with ``scales is None`` outside int8 mode.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.kernels import ops
from repro.kernels.ops import _round_up

PAYLOAD_DTYPES = ("f32", "f16", "int8")

_STORAGE = {"f32": jnp.float32, "f16": jnp.float16, "int8": jnp.int8}


def row_bytes(dim: int, payload_dtype: str = "f32") -> int:
    """HBM bytes one resident row costs in a given storage mode (int8
    includes its 4-byte per-row f32 scale)."""
    if payload_dtype == "f32":
        return 4 * dim
    if payload_dtype == "f16":
        return 2 * dim
    if payload_dtype == "int8":
        return dim + 4
    raise ValueError(f"unknown payload_dtype {payload_dtype!r}; "
                     f"expected one of {PAYLOAD_DTYPES}")


def quantize_rows(rows: np.ndarray, payload_dtype: str
                  ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Host-side insert-path quantization: ``rows [n, D]`` f32 ->
    ``(stored_rows, scales_or_None)``.

    int8 uses per-row absmax: ``scale = max|row| / 127`` (1.0 for all-zero
    rows so dequantization is always ``q * scale``), symmetric clip to
    [-127, 127]. f16 is a plain downcast; f32 passes through untouched.
    """
    rows = np.asarray(rows, np.float32)
    if payload_dtype == "f32":
        return rows, None
    if payload_dtype == "f16":
        return rows.astype(np.float16), None
    if payload_dtype == "int8":
        absmax = np.abs(rows).max(axis=1)
        scales = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.rint(rows / scales[:, None]), -127, 127)
        return q.astype(np.int8), scales
    raise ValueError(f"unknown payload_dtype {payload_dtype!r}; "
                     f"expected one of {PAYLOAD_DTYPES}")


class ShardedPayloadStore:
    """Physical slot storage: single ``[C, D]`` payload (``shards=1``) or
    ``[N, Cl, D]`` stripes (``shards=N``), optionally mesh-placed, in any
    of the ``PAYLOAD_DTYPES`` storage modes."""

    def __init__(self, capacity: int, dim: int, *, shards: int = 1,
                 mesh=None, axis: str = "cache",
                 payload_dtype: str = "f32"):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if shards > capacity:
            raise ValueError(
                f"shards={shards} exceeds capacity={capacity}")
        if payload_dtype not in _STORAGE:
            raise ValueError(f"unknown payload_dtype {payload_dtype!r}; "
                             f"expected one of {PAYLOAD_DTYPES}")
        if mesh is not None:
            size = mesh.shape.get(axis, 1)
            if shards % size:
                raise ValueError(
                    f"shards={shards} does not tile mesh axis "
                    f"'{axis}' of size {size}")
        self.capacity = capacity
        self.dim = dim
        self.shards = shards
        self.mesh = mesh
        self.axis = axis
        self.payload_dtype = payload_dtype
        store_dt = _STORAGE[payload_dtype]
        scaled = payload_dtype == "int8"
        if shards == 1:
            # physical rows padded to the gather kernel's tile so the
            # jitted gather never copies the payload to pad it
            bc = min(512, _round_up(capacity, 8))
            self.phys_rows = _round_up(capacity, bc)
            self._payload = jnp.zeros((self.phys_rows, dim), store_dt)
            self._scales = (jnp.ones((self.phys_rows,), jnp.float32)
                            if scaled else None)
        else:
            local_cap = -(-capacity // shards)        # rows per stripe
            bc = min(512, _round_up(local_cap, 8))
            self.local_rows = _round_up(local_cap, bc)
            self.phys_rows = shards * self.local_rows
            stripes = jnp.zeros((shards, self.local_rows, dim), store_dt)
            scales = (jnp.ones((shards, self.local_rows), jnp.float32)
                      if scaled else None)
            if mesh is not None and mesh.shape.get(axis, 1) > 1:
                from jax.sharding import NamedSharding, PartitionSpec
                sharding = NamedSharding(mesh, PartitionSpec(axis))
                stripes = jax.device_put(stripes, sharding)
                if scales is not None:
                    # the scale vector stripes WITH its payload rows, so
                    # the fused dequantize-gather never moves it
                    scales = jax.device_put(scales, sharding)
            self._payload = stripes
            self._scales = scales

    # -- write (the ONE device scatter per cache mutation) -------------------

    def scatter(self, slots: np.ndarray, rows: np.ndarray) -> None:
        """One ``at[...].set`` over the stripes, size-bucketed so XLA
        compiles O(log) scatter shapes instead of one per miss count
        (padding repeats the first slot — idempotent under ``set``).
        In compressed modes the f32 rows quantize host-side first; int8
        additionally rebinds the scale vector at the same slots."""
        with tracing.span("hps.l1_scatter", rows=len(slots)):
            rows, scales = quantize_rows(np.asarray(rows),
                                         self.payload_dtype)
            pad = _round_up(len(slots), 64) - len(slots)
            if pad:
                slots = np.concatenate([slots, np.full(pad, slots[0])])
                rows = np.concatenate(
                    [rows, np.broadcast_to(rows[:1], (pad, rows.shape[1]))])
                if scales is not None:
                    scales = np.concatenate(
                        [scales, np.broadcast_to(scales[:1], (pad,))])
            if self.shards == 1:
                idx = jnp.asarray(slots, jnp.int32)
                self._payload = self._payload.at[idx].set(jnp.asarray(rows))
                if scales is not None:
                    self._scales = self._scales.at[idx].set(
                        jnp.asarray(scales))
            else:
                stripe = jnp.asarray(slots % self.shards, jnp.int32)
                local = jnp.asarray(slots // self.shards, jnp.int32)
                self._payload = self._payload.at[stripe, local].set(
                    jnp.asarray(rows))
                if scales is not None:
                    self._scales = self._scales.at[stripe, local].set(
                        jnp.asarray(scales))

    # -- read ----------------------------------------------------------------

    def snapshot(self):
        """The current immutable ``(payload, scales)`` pair (``[C, D]`` or
        ``[N, Cl, D]`` payload; ``scales`` is None outside int8 mode).
        Gather from the snapshot you were handed, never from a re-read:
        a later scatter rebinds the store but can never mutate it."""
        return (self._payload, self._scales)

    def gather(self, snapshot, slots) -> jax.Array:
        """Logical ``slots [n]`` (-1 = hole) -> ``[n, D]`` f32 rows off a
        snapshot taken from THIS store (dequantized in-kernel when the
        storage mode is compressed)."""
        payload, scales = snapshot
        if self.shards == 1:
            return ops.cache_gather(payload, slots, scales=scales)
        return ops.sharded_cache_gather(payload, slots, scales=scales,
                                        mesh=self.mesh, axis=self.axis)
