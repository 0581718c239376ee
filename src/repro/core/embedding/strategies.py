"""The paper's three embedding placement/communication strategies.

All functions here run *inside* ``shard_map`` over the full device mesh.
Batch is sharded over the DP axes (``("pod", "data")`` / ``("data",)``) and
replicated over ``"model"``; embedding shards use **all** mesh axes — the
paper's point is that the sparse layer consumes every device's memory.

Conventions (see DESIGN.md §4):
  - ``rows``: mega-table row ids ``[B_dp, T, H]`` int32, ``-1`` = padding.
  - distributed shards are **mod-striped** (``owner = row % N``) for the
    all-to-all path — the TPU analogue of HugeCTR's hash sharding — and
    **block-striped** for the allgather+reduce-scatter path.
  - every collective is differentiable, so table gradients flow through
    the same communication pattern in reverse (all-to-all is self-adjoint,
    all-gather <-> reduce-scatter).
  - each strategy runs under ``jax.named_scope("mp.exchange")``, so the
    device trace's op metadata groups its ops (and their transposes), and
    returns its pooled output with its counters (:func:`exchange_stats`).
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.embedding.common import (
    masked_range_lookup,
    pooled_local_lookup,
)


#: the exchange's counters and the one rule by which two readings combine,
#: over devices, groups and steps alike: ids and drops add, the peak load
#: is the larger
EXCHANGE_COUNTERS = {"exchange_ids": "sum", "exchange_dropped": "sum",
                     "exchange_peak_load": "max"}


def exchange_stats(ids=0, dropped=0, peak_load=0.0) -> Dict[str, jax.Array]:
    """One exchange's counters: ``ids``, the non-padding ids it routed to
    an owner; ``dropped``, those of them that overflowed their owner's
    bucket and read a zero vector; ``peak_load``, the fullest bucket's ids
    over the mean bucket's (0 where no bucketed all-to-all ran)."""
    return {"exchange_ids": jnp.asarray(ids, jnp.int32),
            "exchange_dropped": jnp.asarray(dropped, jnp.int32),
            "exchange_peak_load": jnp.asarray(peak_load, jnp.float32)}


def merge_stats(a: Dict, b: Dict, maximum=jnp.maximum) -> Dict:
    """Counters of two exchanges by :data:`EXCHANGE_COUNTERS`' rule
    (``maximum=max`` for host numbers)."""
    return {k: maximum(a[k], b[k]) if how == "max" else a[k] + b[k]
            for k, how in EXCHANGE_COUNTERS.items()}


def psum_stats(stats: Dict, axes: Tuple[str, ...]) -> Dict[str, jax.Array]:
    """Per-device counters combined over ``axes`` by the same rule."""
    return {k: (jax.lax.pmax if how == "max" else jax.lax.psum)(stats[k],
                                                                 axes)
            for k, how in EXCHANGE_COUNTERS.items()}


# ---------------------------------------------------------------------------
# Distributed slot embedding — all-gather + reduce-scatter path
# ---------------------------------------------------------------------------

def distributed_ag_rs(local_table: jax.Array, rows: jax.Array, *,
                      dp_axes: Tuple[str, ...], all_axes: Tuple[str, ...],
                      model_axis: str, shard_rows: int,
                      compute_dtype=None) -> Tuple[jax.Array, Dict]:
    """Block-striped MP lookup.

    1. all-gather ids over ``dp_axes`` (ids are tiny: int32) — SKIPPED
       when the shard axes exclude DP (``shard_axes="model"``): each DP
       row then resolves only its own batch shard,
    2. every device resolves the (gathered) batch against its row range,
    3. reduce-scatter the partial pooled tensor over the shard axes,
    4. all-gather over the model axis to restore the DP batch block.

    Nothing is dropped: ``exchange_ids`` counts this device's own ids.
    """
    with jax.named_scope("mp.exchange"):
        rows_all = jax.lax.all_gather(rows, dp_axes, axis=0, tiled=True) \
            if dp_axes else rows
        idx = jax.lax.axis_index(all_axes)
        v0 = idx * shard_rows
        partial = masked_range_lookup(local_table, rows_all, v0,
                                      compute_dtype=compute_dtype)
        summed = jax.lax.psum_scatter(partial, all_axes,
                                      scatter_dimension=0, tiled=True)
        if model_axis in all_axes:
            summed = jax.lax.all_gather(summed, model_axis, axis=0,
                                        tiled=True)
        return summed, exchange_stats(ids=(rows >= 0).sum())


# ---------------------------------------------------------------------------
# Distributed slot embedding — bucketed all-to-all path (HugeCTR-faithful)
# ---------------------------------------------------------------------------

def _bucket_by_owner(flat_rows: jax.Array, n_shards: int, capacity: int):
    """Assign each id a slot in a ``[n_shards, capacity]`` send buffer.

    Returns ``(send_buf, slot_of, valid, fill)`` where ``send_buf`` holds
    *local* row ids (``row // n_shards``) with ``-1`` padding, ``slot_of[i]``
    is the flat slot each input id landed in (or ``n_shards*capacity`` if
    dropped), ``valid`` marks ids that were neither padding nor overflow,
    and ``fill[o]`` counts the non-padding ids owned by ``o``, before the
    capacity cut.
    """
    m = flat_rows.shape[0]
    owner = jnp.where(flat_rows >= 0, flat_rows % n_shards, n_shards)
    order = jnp.argsort(owner, stable=True)
    sorted_owner = owner[order]
    # rank of each element within its owner bucket
    start = jnp.searchsorted(sorted_owner, jnp.arange(n_shards + 1))
    pos_sorted = jnp.arange(m) - start[sorted_owner]
    in_cap = (pos_sorted < capacity) & (sorted_owner < n_shards)
    slot_sorted = jnp.where(in_cap,
                            sorted_owner * capacity + pos_sorted,
                            n_shards * capacity)
    slot_of = jnp.zeros((m,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    local_rows = jnp.where(flat_rows >= 0, flat_rows // n_shards, -1)
    send_buf = jnp.full((n_shards * capacity,), -1, jnp.int32)
    send_buf = send_buf.at[slot_of].set(local_rows, mode="drop")
    valid = (flat_rows >= 0) & (slot_of < n_shards * capacity)
    fill = start[1:] - start[:-1]
    return send_buf.reshape(n_shards, capacity), slot_of, valid, fill


def distributed_a2a(local_table: jax.Array, rows: jax.Array, *,
                    all_axes: Tuple[str, ...], n_shards: int,
                    capacity_factor: float = 2.0,
                    compute_dtype=None) -> Tuple[jax.Array, Dict]:
    """Mod-striped MP lookup with bucketed all-to-all exchange.

    The faithful port of HugeCTR's distributed-slot pattern: ids are routed
    to their owner shard, the owner gathers vectors, and a second all-to-all
    returns them. Static shapes come from a capacity factor (overflow ids
    fall back to zero vectors; the planner sizes capacity so this does not
    happen for uniform batches — same trade as MoE token dropping). The
    counters say whether it did: ``exchange_dropped`` counts the overflow
    ids, and ``exchange_peak_load`` (the fullest bucket over the mean,
    ``ceil(m / n_shards)``) passes ``capacity / mean``, which is
    ``capacity_factor`` up to rounding, exactly when some are dropped.
    """
    with jax.named_scope("mp.exchange"):
        b, t, h = rows.shape
        m = b * t * h
        mean = (m + n_shards - 1) // n_shards
        capacity = max(1, int(mean * capacity_factor))
        flat = rows.reshape(-1)
        send_buf, slot_of, valid, fill = _bucket_by_owner(flat, n_shards,
                                                          capacity)
        sent = flat >= 0
        stats = exchange_stats(ids=sent.sum(),
                               dropped=(sent & ~valid).sum(),
                               peak_load=fill.max() / mean)

        # requests travel to owners ...
        recv = jax.lax.all_to_all(send_buf, all_axes, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv = recv.reshape(n_shards, capacity)
        req_valid = recv >= 0
        safe = jnp.where(req_valid, recv, 0)
        resp = jnp.take(local_table, safe, axis=0)
        if compute_dtype is not None:
            resp = resp.astype(compute_dtype)
        resp = jnp.where(req_valid[..., None], resp, 0)
        # ... vectors travel back to requesters
        resp_back = jax.lax.all_to_all(resp, all_axes, split_axis=0,
                                       concat_axis=0, tiled=False)
        resp_flat = resp_back.reshape(n_shards * capacity, -1)
        # pad row so dropped/overflow slots read zeros
        resp_flat = jnp.concatenate(
            [resp_flat, jnp.zeros((1, resp_flat.shape[1]), resp_flat.dtype)],
            0)
        gathered = resp_flat[jnp.where(valid, slot_of, n_shards * capacity)]
        return gathered.reshape(b, t, h, -1).sum(axis=2), stats


# ---------------------------------------------------------------------------
# Localized slot embedding
# ---------------------------------------------------------------------------

def localized(local_tables: jax.Array, ids: jax.Array, *,
              dp_axes: Tuple[str, ...], all_axes: Tuple[str, ...],
              model_axis: str, tables_per_shard: int,
              compute_dtype=None) -> Tuple[jax.Array, Dict]:
    """Whole tables per device; all-to-all exchanges pooled vectors.

    ``local_tables``: ``[T/N, V_max, D]`` — this shard's tables (padded).
    ``ids``: per-table ids ``[B_dp, T, H]`` (NOT mega-row ids).

    Per the paper: intra-slot (multi-hot) reduction is entirely local; the
    only communication is one all-to-all of pooled vectors along the batch
    dimension (plus the id all-gather that stands in for HugeCTR's
    table-aware data reader). Nothing is dropped: ``exchange_ids``
    counts this device's own ids.
    """
    with jax.named_scope("mp.exchange"):
        ids_all = jax.lax.all_gather(ids, dp_axes, axis=0, tiled=True)
        idx = jax.lax.axis_index(all_axes)
        t0 = idx * tables_per_shard
        my_ids = jax.lax.dynamic_slice_in_dim(ids_all, t0, tables_per_shard,
                                              axis=1)       # [B_g, T/N, H]
        pooled = jax.vmap(
            lambda tab, r: pooled_local_lookup(
                tab, r[:, None, :], compute_dtype=compute_dtype)[:, 0],
            in_axes=(0, 1), out_axes=1,
        )(local_tables, my_ids)                               # [B_g, T/N, D]
        out = jax.lax.all_to_all(pooled, all_axes, split_axis=0,
                                 concat_axis=1, tiled=True)   # [B_g/N, T, D]
        if model_axis in all_axes:
            out = jax.lax.all_gather(out, model_axis, axis=0, tiled=True)
        return out, exchange_stats(ids=(ids >= 0).sum())
