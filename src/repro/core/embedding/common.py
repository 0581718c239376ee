"""Shared machinery for the embedding engine.

All strategies operate on a *mega-table* layout: the tables of a group are
concatenated along the row axis into one ``[sum(V_t), D]`` array with
per-table row offsets. Ids use ``-1`` padding for variable hotness.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EmbeddingTableConfig


@dataclasses.dataclass(frozen=True)
class TableGroup:
    """A group of tables sharing one mega-table and one strategy."""
    strategy: str
    tables: Tuple[EmbeddingTableConfig, ...]
    #: row offset of each table within the mega-table
    offsets: Tuple[int, ...]
    total_rows: int
    dim: int
    #: index of each table in the *original* collection order
    table_indices: Tuple[int, ...]

    @property
    def num_tables(self) -> int:
        return len(self.tables)


def build_group(strategy: str,
                tables: Sequence[EmbeddingTableConfig],
                table_indices: Sequence[int],
                rows_fn=None) -> TableGroup:
    """Concatenate ``tables`` into one mega-table layout.

    ``rows_fn(table) -> int`` overrides the per-table row count (used by the
    hybrid strategy to build hot-only / cold-only groups).
    """
    rows_fn = rows_fn or (lambda t: t.vocab_size)
    dims = {t.dim for t in tables}
    if len(dims) != 1:
        raise ValueError(f"grouped tables must share dim, got {dims}")
    offsets, total = [], 0
    for t in tables:
        offsets.append(total)
        total += rows_fn(t)
    return TableGroup(strategy, tuple(tables), tuple(offsets), total,
                      dims.pop(), tuple(table_indices))


def init_mega_table(key: jax.Array, group: TableGroup,
                    dtype=jnp.float32,
                    rows: Optional[jax.Array] = None) -> jax.Array:
    """Uniform(-1/sqrt(V), 1/sqrt(V)) per table, HugeCTR-style init.

    Logical row ``r`` is a pure function of ``(key, r)``, so ``rows``
    (logical ids in any physical order; ids outside ``[0, total_rows)``
    become zero padding) yields a striped or padded layout directly, and
    under ``jit`` with a sharded output each device draws only its own
    rows — the logical table is never materialized whole.
    """
    if rows is None:
        rows = jnp.arange(group.total_rows, dtype=jnp.int32)
    if not group.num_tables:
        return jnp.zeros((rows.shape[0], group.dim), dtype)
    table = jnp.searchsorted(jnp.asarray(group.offsets[1:], jnp.int32),
                             rows, side="right")
    scales = jnp.asarray([1.0 / np.sqrt(max(t.vocab_size, 1))
                          for t in group.tables], jnp.float32)
    valid = (rows >= 0) & (rows < group.total_rows)
    scale = jnp.where(valid, scales[table], 0.0)
    u = jax.vmap(lambda r: jax.random.uniform(
        jax.random.fold_in(key, r), (group.dim,), jnp.float32,
        minval=-1.0, maxval=1.0))(rows)
    return (u * scale[:, None]).astype(dtype)


def global_row_ids(ids: jax.Array, group: TableGroup) -> jax.Array:
    """Map per-table ids ``[..., T, H]`` to mega-table row ids (keep -1)."""
    offs = jnp.asarray(group.offsets, jnp.int32).reshape(
        (1,) * (ids.ndim - 2) + (group.num_tables, 1))
    return jnp.where(ids >= 0, ids + offs, -1)


def pooled_local_lookup(mega: jax.Array, rows: jax.Array,
                        combiner: str = "sum",
                        compute_dtype=None) -> jax.Array:
    """Gather + pool: ``rows [B, T, H]`` (-1 = pad) -> ``[B, T, D]``.

    Pure-jnp path. The Pallas kernel in ``repro.kernels`` implements the
    same contract for the perf-critical recsys path.
    """
    valid = rows >= 0
    safe = jnp.where(valid, rows, 0)
    vecs = jnp.take(mega, safe, axis=0)           # [B, T, H, D]
    if compute_dtype is not None:
        vecs = vecs.astype(compute_dtype)
    vecs = jnp.where(valid[..., None], vecs, 0)
    pooled = vecs.sum(axis=-2)                    # [B, T, D]
    if combiner == "mean":
        denom = jnp.maximum(valid.sum(axis=-1, keepdims=True), 1)
        pooled = pooled / denom.astype(pooled.dtype)
    return pooled


def masked_range_lookup(local: jax.Array, rows: jax.Array, v0: int,
                        combiner: str = "sum",
                        compute_dtype=None) -> jax.Array:
    """Partial pooled lookup against a row-range shard ``[v0, v0+len)``.

    Rows outside the shard contribute zero; summing partials across shards
    reconstructs the full pooled lookup (plus mean renorm done by caller).
    """
    vlen = local.shape[0]
    rel = rows - v0
    valid = (rows >= 0) & (rel >= 0) & (rel < vlen)
    safe = jnp.where(valid, rel, 0)
    vecs = jnp.take(local, safe, axis=0)
    if compute_dtype is not None:
        vecs = vecs.astype(compute_dtype)
    vecs = jnp.where(valid[..., None], vecs, 0)
    return vecs.sum(axis=-2)


def combiner_mask_denom(rows: jax.Array) -> jax.Array:
    """Denominator for mean-combining given padded rows ``[..., H]``."""
    return jnp.maximum((rows >= 0).sum(axis=-1, keepdims=True), 1)
