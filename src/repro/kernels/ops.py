"""Jit-ready wrappers around the Pallas kernels (padding + custom_vjp).

``interpret`` defaults to True off-TPU so the same call sites validate on
CPU and run the compiled kernel on hardware.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dot_interaction as _di
from repro.kernels import embedding_lookup as _el


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Fused embedding lookup
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_embedding_lookup(table: jax.Array, rows: jax.Array,
                           block_b: int = 128, block_v: int = 512
                           ) -> jax.Array:
    """``table [V, D]``, ``rows [B, H]`` (-1 pad) -> sum-pooled ``[B, D]``."""
    return _lookup_impl(table, rows, block_b, block_v)


def _lookup_impl(table, rows, block_b, block_v):
    v, d = table.shape
    b, h = rows.shape
    bb = min(block_b, _round_up(b, 8))
    bv = min(block_v, _round_up(v, 8))
    vp, bp = _round_up(v, bv), _round_up(b, bb)
    tpad = jnp.pad(table, ((0, vp - v), (0, 0)))
    rpad = jnp.pad(rows, ((0, bp - b), (0, 0)), constant_values=-1)
    out = _el.lookup_fwd(tpad, rpad, block_b=bb, block_v=bv,
                         interpret=_interpret())
    return out[:b]


def _lookup_fwd_rule(table, rows, block_b, block_v):
    return _lookup_impl(table, rows, block_b, block_v), (table.shape, rows)


def _lookup_bwd_rule(block_b, block_v, res, dpooled):
    table_shape, rows = res
    v, d = table_shape
    b, h = rows.shape
    bb = min(block_b, _round_up(b, 8))
    bv = min(block_v, _round_up(v, 8))
    vp, bp = _round_up(v, bv), _round_up(b, bb)
    rpad = jnp.pad(rows, ((0, bp - b), (0, 0)), constant_values=-1)
    dpad = jnp.pad(dpooled.astype(jnp.float32), ((0, bp - b), (0, 0)))
    dtab = _el.lookup_bwd((vp, d), rpad, dpad, block_b=bb, block_v=bv,
                          interpret=_interpret())[:v]
    return dtab.astype(jnp.float32), None


fused_embedding_lookup.defvjp(_lookup_fwd_rule, _lookup_bwd_rule)


def kernel_pool(mega: jax.Array, rows: jax.Array, *, combiner: str = "sum",
                compute_dtype=None) -> jax.Array:
    """Drop-in for ``common.pooled_local_lookup`` backed by the kernel.

    ``rows [B, T, H]`` -> ``[B, T, D]`` (mega-table row ids, -1 pad).
    """
    b, t, h = rows.shape
    out = fused_embedding_lookup(mega, rows.reshape(b * t, h))
    out = out.reshape(b, t, -1)
    if combiner == "mean":
        denom = jnp.maximum((rows >= 0).sum(-1, keepdims=True), 1)
        out = out / denom.astype(out.dtype)
    if compute_dtype is not None:
        out = out.astype(compute_dtype)
    return out


# ---------------------------------------------------------------------------
# HPS cache gather (serving hot path: payload[slots] in one dispatch)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_c", "use_kernel"))
def _cache_gather_jit(payload, slots, block_n, block_c, use_kernel):
    if not use_kernel:
        # off-TPU: the test oracle IS the implementation (the one-hot-
        # matmul kernel only pays off on the MXU; interpreting it on CPU
        # would turn the serving hot path into a dense matmul per query)
        from repro.kernels import ref as _ref
        return _ref.cache_gather_ref(payload, slots)
    from repro.kernels import hps_gather as _hg
    c, d = payload.shape
    n = slots.shape[0]
    bn = min(block_n, _round_up(n, 8))
    bc = min(block_c, _round_up(c, 8))
    cp, np_ = _round_up(c, bc), _round_up(n, bn)
    ppad = jnp.pad(payload, ((0, cp - c), (0, 0)))
    spad = jnp.pad(slots.astype(jnp.int32), (0, np_ - n),
                   constant_values=-1)[:, None]
    out = _hg.gather_rows(ppad, spad, block_n=bn, block_c=bc,
                          interpret=_interpret())
    return out[:n]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_c", "use_kernel"))
def _dequant_cache_gather_jit(payload, scales, slots, block_n, block_c,
                              use_kernel):
    """Compressed twin of ``_cache_gather_jit``: ``payload [C, D]`` in its
    storage dtype plus a per-row f32 ``scales [C]`` — one fused
    dequantize-gather dispatch (the scale is applied inside the kernel's
    one-hot matmul, never as a second pass over the rows)."""
    if not use_kernel:
        from repro.kernels import ref as _ref
        return _ref.dequant_gather_ref(payload, scales, slots)
    from repro.kernels import hps_gather as _hg
    c, d = payload.shape
    n = slots.shape[0]
    bn = min(block_n, _round_up(n, 8))
    bc = min(block_c, _round_up(c, 8))
    cp, np_ = _round_up(c, bc), _round_up(n, bn)
    ppad = jnp.pad(payload, ((0, cp - c), (0, 0)))
    scpad = jnp.pad(scales.astype(jnp.float32), (0, cp - c))[:, None]
    spad = jnp.pad(slots.astype(jnp.int32), (0, np_ - n),
                   constant_values=-1)[:, None]
    out = _hg.dequant_gather_rows(ppad, scpad, spad, block_n=bn, block_c=bc,
                                  interpret=_interpret())
    return out[:n]


def pooled_cache_lookup(payload: jax.Array, slots: jax.Array,
                        scales=None) -> jax.Array:
    """Serving-path pooled gather: ``payload [C, D]``, ``slots [B, H]``
    (-1 = hole) -> sum-pooled ``[B, D]``.

    Inference-only (no vjp): the ``hps_gather`` one-hot-matmul kernel on
    TPU, the equivalent XLA take elsewhere — same switch as
    ``cache_gather`` — with the pooling sum inside the same jit. With
    per-row ``scales`` (int8 payloads) the gather is the fused dequantize
    kernel.
    """
    b, h = slots.shape
    flat = slots.reshape(-1)
    if scales is not None:
        rows = _dequant_cache_gather_jit(payload, scales, flat, 256, 512,
                                         not _interpret())
    else:
        rows = _cache_gather_jit(payload, flat, 256, 512, not _interpret())
    return rows.reshape(b, h, -1).sum(axis=1)


def cache_gather(payload: jax.Array, slots, *, scales=None,
                 block_n: int = 256, block_c: int = 512,
                 use_kernel=None) -> jax.Array:
    """``payload [C, D]``, ``slots [N]`` (-1 = hole -> zero row) -> ``[N, D]``.

    Jitted wrapper: one device dispatch per call after the first trace,
    so ``DeviceEmbeddingCache.query`` costs O(1) dispatches per batch.
    On TPU the read is the ``hps_gather`` Pallas kernel; elsewhere the
    same jit lowers to the equivalent XLA gather (``use_kernel=True``
    forces the kernel in interpret mode — how tests validate it).
    ``scales`` (per-row f32, int8 payloads) switches to the fused
    dequantize-gather kernel — still one dispatch.
    """
    if use_kernel is None:
        use_kernel = not _interpret()
    if scales is not None:
        return _dequant_cache_gather_jit(payload, scales, jnp.asarray(slots),
                                         block_n, block_c, use_kernel)
    return _cache_gather_jit(payload, jnp.asarray(slots), block_n, block_c,
                             use_kernel)


# ---------------------------------------------------------------------------
# Striped (sharded) L1 payload: stripes [N, Cl, D], slot s at [s % N, s // N]
# ---------------------------------------------------------------------------

def flatten_striped_slots(stripes: jax.Array, slots: jax.Array) -> jax.Array:
    """Remap GLOBAL slot ids onto the row-major flattening of ``stripes``
    (``[N, Cl, D] -> [N * Cl, D]``), preserving -1 holes — the
    single-device ("host shard") view of the striped layout."""
    n_stripes, local_rows = stripes.shape[0], stripes.shape[1]
    return jnp.where(slots >= 0,
                     (slots % n_stripes) * local_rows + slots // n_stripes,
                     -1)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _sharded_gather_flat(stripes, slots, use_kernel):
    flat = stripes.reshape(-1, stripes.shape[-1])
    return _cache_gather_jit(flat, flatten_striped_slots(stripes, slots),
                             256, 512, use_kernel)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def _dequant_sharded_gather_flat(stripes, scales, slots, use_kernel):
    flat = stripes.reshape(-1, stripes.shape[-1])
    return _dequant_cache_gather_jit(flat, scales.reshape(-1),
                                     flatten_striped_slots(stripes, slots),
                                     256, 512, use_kernel)


def sharded_cache_gather(stripes: jax.Array, slots, *, scales=None,
                         mesh=None, axis: str = "cache",
                         use_kernel=None) -> jax.Array:
    """``stripes [N, Cl, D]``, GLOBAL ``slots [n]`` (-1 = hole) ->
    ``[n, D]`` f32.

    With a ``mesh`` whose ``axis`` the stripes are laid out over, this is
    the ``hps_gather.sharded_gather_rows`` shard_map (per-device gather +
    one psum — the payload never moves). Without one, the same striped
    layout is served from host-shard stripes in a single jitted dispatch
    via the flattened-slot remap, which is bit-identical row-wise.
    ``scales [N, Cl]`` (int8 payloads) rides the same stripe layout —
    the fused dequantize kernel runs per device, same single psum.
    """
    if use_kernel is None:
        use_kernel = not _interpret()
    slots = jnp.asarray(slots)
    if mesh is not None and axis in mesh.shape and mesh.shape[axis] > 1:
        from repro.kernels import hps_gather as _hg
        if scales is not None:
            return _hg.sharded_dequant_gather_rows(
                stripes, scales, slots, mesh=mesh, axis=axis,
                use_kernel=use_kernel, interpret=_interpret())
        return _hg.sharded_gather_rows(stripes, slots, mesh=mesh, axis=axis,
                                       use_kernel=use_kernel,
                                       interpret=_interpret())
    if scales is not None:
        return _dequant_sharded_gather_flat(stripes, scales, slots,
                                            use_kernel)
    return _sharded_gather_flat(stripes, slots, use_kernel)


def sharded_pooled_lookup(stripes: jax.Array, slots: jax.Array, *,
                          scales=None, mesh=None,
                          axis: str = "cache") -> jax.Array:
    """Pooled serving gather off the striped payload: ``stripes
    [N, Cl, D]``, GLOBAL ``slots [B, H]`` (-1 = hole) -> sum-pooled
    ``[B, D]`` — the striped twin of ``pooled_cache_lookup``."""
    if mesh is not None and axis in mesh.shape and mesh.shape[axis] > 1:
        from repro.kernels import hps_gather as _hg
        b, h = slots.shape
        if scales is not None:
            rows = _hg.sharded_dequant_gather_rows(
                stripes, scales, slots.reshape(-1), mesh=mesh, axis=axis,
                use_kernel=not _interpret(), interpret=_interpret())
        else:
            rows = _hg.sharded_gather_rows(stripes, slots.reshape(-1),
                                           mesh=mesh, axis=axis,
                                           use_kernel=not _interpret(),
                                           interpret=_interpret())
        return rows.reshape(b, h, -1).sum(axis=1)
    return pooled_cache_lookup(stripes.reshape(-1, stripes.shape[-1]),
                               flatten_striped_slots(stripes, slots),
                               None if scales is None else scales.reshape(-1))


# ---------------------------------------------------------------------------
# DLRM dot interaction
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def dot_interaction(x: jax.Array, self_interaction: bool = False,
                    block_b: int = 128) -> jax.Array:
    """``x [B, F, D]`` -> pairwise-dot triangle ``[B, P]``."""
    return _interaction_impl(x, self_interaction, block_b)


def _interaction_impl(x, self_interaction, block_b):
    b, f, d = x.shape
    s = jnp.asarray(_di.selection_matrix(f, self_interaction))
    bb = min(block_b, _round_up(b, 8))
    bp = _round_up(b, bb)
    xpad = jnp.pad(x, ((0, bp - b), (0, 0), (0, 0)))
    out = _di.interaction_fwd(xpad, s, block_b=bb, interpret=_interpret())
    return out[:b]


def _interaction_fwd_rule(x, self_interaction, block_b):
    return _interaction_impl(x, self_interaction, block_b), x


def _interaction_bwd_rule(self_interaction, block_b, x, dtri):
    b, f, d = x.shape
    s = jnp.asarray(_di.selection_matrix(f, self_interaction))
    bb = min(block_b, _round_up(b, 8))
    bp = _round_up(b, bb)
    xpad = jnp.pad(x, ((0, bp - b), (0, 0), (0, 0)))
    dpad = jnp.pad(dtri.astype(jnp.float32), ((0, bp - b), (0, 0)))
    # note: the symmetrization inside the bwd kernel doubles the diagonal,
    # which is exactly d(x.x)/dx = 2x — correct for self_interaction too.
    dx = _di.interaction_bwd(xpad, dpad, s, block_b=bb,
                             interpret=_interpret())[:b]
    return (dx.astype(x.dtype),)


dot_interaction.defvjp(_interaction_fwd_rule, _interaction_bwd_rule)


# ---------------------------------------------------------------------------
# Flash attention (fwd + bwd Pallas kernels)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 512, block_k: int = 512) -> jax.Array:
    """``q [B, S, Hq, D]``, ``k/v [B, S, Hkv, D]`` -> ``[B, S, Hq, D]``.

    Scores never touch HBM (VMEM-resident online softmax) — the Pallas
    replacement for ``transformer.chunked_attention`` on TPU.
    """
    o, _ = _flash_fwd_impl(q, k, v, causal, window, block_q, block_k)
    return o


def _bhsd(x):
    """[B, S, H, D] -> [B·H, S, D]."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unbhsd(x, b):
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _flash_fwd_impl(q, k, v, causal, window, block_q, block_k):
    from repro.kernels import flash_attention as fa
    b = q.shape[0]
    o, lse = fa.flash_fwd(_bhsd(q), _bhsd(k), _bhsd(v), causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          interpret=_interpret())
    return _unbhsd(o, b), lse


def _flash_fwd_rule(q, k, v, causal, window, block_q, block_k):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, window, block_q, block_k, res, do):
    from repro.kernels import flash_attention as fa
    q, k, v, o, lse = res
    b = q.shape[0]
    dq, dk, dv = fa.flash_bwd(
        _bhsd(q), _bhsd(k), _bhsd(v), _bhsd(o), lse, _bhsd(do),
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=_interpret())
    return _unbhsd(dq, b), _unbhsd(dk, b), _unbhsd(dv, b)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
