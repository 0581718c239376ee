"""Pallas TPU kernel: batched row gather from the HPS L1 device payload.

The serving hot path reads ``payload[slots]`` for a whole query at once.
Like ``embedding_lookup``, random row access is reformulated as a
streaming one-hot matmul so the MXU does the work and the payload streams
HBM -> VMEM tile by tile — no per-row gather, no host round-trips:

    out[n, :] = sum_{c-tiles} onehot(slots[n], c-tile) @ payload[c-tile, :]

Negative slots (query padding / ids not resident) produce zero rows, which
the cache's overflow path overwrites separately.

Grid layout: the payload-tile reduction dim is trailing (Pallas TPU
requirement for output-block accumulation): grid = (N/bN, C/bC).

The matmul runs at ``Precision.HIGHEST`` (f32 contract precision on the
MXU): at the default precision Mosaic rounds f32 operands through bf16,
and a one-hot product must return every payload bit unchanged. f16
payloads reach the kernel as their raw ``uint16`` bit patterns (a free
bitcast; Mosaic on v5e cannot load an f16 vector) and are widened to f32
exactly in VMEM, so the payload is still read at 2 bytes a value.

``sharded_gather_rows`` is the multi-device entry point for the striped
L1 payload (companion HPS paper, arXiv 2210.08804 §4): slot ``s`` lives
on stripe ``s % n_stripes``, stripes are laid out over a 1-D mesh axis,
and every device runs the same local gather over the stripes it owns —
non-owned slots become holes (zero rows) — so ONE ``psum`` reassembles
the full batch. The payload never leaves its owning device; only the
``[n, D]`` result crosses the interconnect.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.kernels.ops import _round_up


def _f16_bits_to_f32(bits: jax.Array) -> jax.Array:
    """Exact f16 -> f32 widening from raw ``uint16`` bit patterns, in
    integer ops Mosaic lowers (zero, subnormals, inf and nan included)."""
    h = bits.astype(jnp.int32)
    sign = (h & 0x8000) << 16
    exp = (h >> 10) & 0x1F
    man = h & 0x3FF
    normal = sign | ((exp + 112) << 23) | (man << 13)
    special = sign | 0x7F800000 | (man << 13)           # inf / nan
    val = jax.lax.bitcast_convert_type(
        jnp.where(exp == 31, special, normal), jnp.float32)
    tiny = man.astype(jnp.float32) * (2.0 ** -24)       # zero / subnormal
    tiny = jnp.where(sign != 0, -tiny, tiny)
    return jnp.where(exp == 0, tiny, val)


def _rows_f32(tile: jax.Array) -> jax.Array:
    """A payload tile widened to f32 in VMEM."""
    if tile.dtype == jnp.uint16:
        return _f16_bits_to_f32(tile)
    return tile.astype(jnp.float32)


def _kernel_payload(payload: jax.Array) -> jax.Array:
    """The payload as the kernel reads it: f16 as its uint16 bits."""
    if payload.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(payload, jnp.uint16)
    return payload


def _gather_kernel(slots_ref, payload_ref, o_ref, *, bc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slots = slots_ref[...][:, 0]                      # [bN]
    bn = slots.shape[0]
    rel = slots - c * bc
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, bc), 1)
    onehot = ((rel[:, None] == iota) & (slots >= 0)[:, None])
    o_ref[...] += jnp.dot(onehot.astype(jnp.float32),
                          _rows_f32(payload_ref[...]),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def gather_rows(payload: jax.Array, slots: jax.Array, *,
                block_n: int = 256, block_c: int = 512,
                interpret: bool = False) -> jax.Array:
    """``payload [C, D]`` (C % block_c == 0), ``slots [N, 1]`` int32
    (N % block_n == 0, -1 = hole) -> ``[N, D]`` f32."""
    c, d = payload.shape
    n = slots.shape[0]
    grid = (n // block_n, c // block_c)
    payload = _kernel_payload(payload)
    return pl.pallas_call(
        functools.partial(_gather_kernel, bc=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(slots, payload)


def _dq_gather_kernel(slots_ref, payload_ref, scales_ref, o_ref, *, bc: int):
    """Fused dequantize-gather: the per-row scale folds into the one-hot
    BEFORE the matmul, so ``onehot_scaled @ q_tile`` yields already-
    dequantized f32 rows in the same single MXU pass — the compressed
    tile never materializes at f32 width in VMEM."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    slots = slots_ref[...][:, 0]                      # [bN]
    bn = slots.shape[0]
    rel = slots - c * bc
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, bc), 1)
    onehot = ((rel[:, None] == iota) & (slots >= 0)[:, None])
    scales = scales_ref[...][:, 0]                    # [bC] f32
    scaled = onehot.astype(jnp.float32) * scales[None, :]
    o_ref[...] += jnp.dot(scaled, _rows_f32(payload_ref[...]),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def dequant_gather_rows(payload: jax.Array, scales: jax.Array,
                        slots: jax.Array, *,
                        block_n: int = 256, block_c: int = 512,
                        interpret: bool = False) -> jax.Array:
    """``payload [C, D]`` compressed rows (int8/f16; C % block_c == 0),
    ``scales [C, 1]`` f32 per-row dequant scale, ``slots [N, 1]`` int32
    (N % block_n == 0, -1 = hole) -> ``[N, D]`` dequantized f32.

    One dispatch: scale is applied inside the gather matmul (see
    ``_dq_gather_kernel``), not as a second elementwise pass."""
    c, d = payload.shape
    n = slots.shape[0]
    grid = (n // block_n, c // block_c)
    payload = _kernel_payload(payload)
    return pl.pallas_call(
        functools.partial(_dq_gather_kernel, bc=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_c, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(slots, payload, scales)


def _local_stripe_gather(stripes: jax.Array, slots: jax.Array,
                         n_stripes: int, axis: str, *,
                         use_kernel: bool, block_n: int, block_c: int,
                         interpret: bool) -> jax.Array:
    """Per-device body: gather the slots whose stripe this device owns.

    ``stripes [k, Cl, D]`` is the local block of the striped payload
    (``k = n_stripes / mesh_axis_size``); global slot ``s`` maps to
    stripe ``s % n_stripes``, local row ``s // n_stripes``. Slots owned
    elsewhere turn into -1 holes, so the cross-device ``psum`` of the
    per-device gathers is exact (holes contribute zero rows).
    """
    k, cl, d = stripes.shape
    idx = jax.lax.axis_index(axis)
    first = idx * k                                   # first stripe owned
    stripe_of = jnp.where(slots >= 0, slots % n_stripes, -1)
    mine = (stripe_of >= first) & (stripe_of < first + k)
    flat = stripes.reshape(k * cl, d)
    local = (stripe_of - first) * cl + slots // n_stripes
    local = jnp.where(mine, local, -1)
    if not use_kernel:
        valid = local >= 0
        rows = jnp.take(flat, jnp.where(valid, local, 0), axis=0)
        rows = jnp.where(valid[:, None], rows, 0.0).astype(jnp.float32)
    else:
        n = local.shape[0]
        bn = min(block_n, _round_up(n, 8))
        bc = min(block_c, _round_up(k * cl, 8))
        npad, cpad = _round_up(n, bn), _round_up(k * cl, bc)
        fpad = jnp.pad(flat, ((0, cpad - k * cl), (0, 0)))
        lpad = jnp.pad(local.astype(jnp.int32), (0, npad - n),
                       constant_values=-1)[:, None]
        rows = gather_rows(fpad, lpad, block_n=bn, block_c=bc,
                           interpret=interpret)[:n]
    return jax.lax.psum(rows, axis)


def sharded_gather_rows(stripes: jax.Array, slots: jax.Array, *,
                        mesh: Mesh, axis: str = "cache",
                        use_kernel: bool = True, block_n: int = 256,
                        block_c: int = 512,
                        interpret: bool = False) -> jax.Array:
    """Striped-payload gather: ``stripes [N, Cl, D]`` laid out over the
    mesh's ``axis`` (stripe ``i`` on device ``i * size / N``), ``slots
    [n]`` GLOBAL slot ids (-1 = hole) -> ``[n, D]`` f32, replicated.

    Each device gathers only the stripes it holds (one kernel dispatch)
    and one ``psum`` over ``axis`` combines the partial batches.
    """
    n_stripes = stripes.shape[0]
    size = mesh.shape[axis]
    if n_stripes % size:
        raise ValueError(
            f"{n_stripes} stripes do not tile mesh axis '{axis}' "
            f"of size {size}")
    body = functools.partial(
        _local_stripe_gather, n_stripes=n_stripes, axis=axis,
        use_kernel=use_kernel, block_n=block_n, block_c=block_c,
        interpret=interpret)
    spec = P(axis) if size > 1 else P()
    fn = compat.shard_map(body, mesh=mesh,
                          in_specs=(spec, P()), out_specs=P(),
                          check_vma=False)
    return fn(stripes, slots.astype(jnp.int32))


def _local_stripe_dequant_gather(stripes: jax.Array, scales: jax.Array,
                                 slots: jax.Array, n_stripes: int,
                                 axis: str, *, use_kernel: bool,
                                 block_n: int, block_c: int,
                                 interpret: bool) -> jax.Array:
    """Per-device body of the compressed striped gather: identical slot
    routing to ``_local_stripe_gather``, but the local dispatch is the
    fused dequantize-gather kernel (``scales [k, Cl]`` shards with its
    stripes, so dequantization happens before the SAME single ``psum`` —
    no extra collectives)."""
    k, cl, d = stripes.shape
    idx = jax.lax.axis_index(axis)
    first = idx * k
    stripe_of = jnp.where(slots >= 0, slots % n_stripes, -1)
    mine = (stripe_of >= first) & (stripe_of < first + k)
    flat = stripes.reshape(k * cl, d)
    flat_sc = scales.reshape(k * cl).astype(jnp.float32)
    local = (stripe_of - first) * cl + slots // n_stripes
    local = jnp.where(mine, local, -1)
    if not use_kernel:
        valid = local >= 0
        safe = jnp.where(valid, local, 0)
        rows = jnp.take(flat, safe, axis=0).astype(jnp.float32)
        rows = rows * jnp.take(flat_sc, safe)[:, None]
        rows = jnp.where(valid[:, None], rows, 0.0)
    else:
        n = local.shape[0]
        bn = min(block_n, _round_up(n, 8))
        bc = min(block_c, _round_up(k * cl, 8))
        npad, cpad = _round_up(n, bn), _round_up(k * cl, bc)
        fpad = jnp.pad(flat, ((0, cpad - k * cl), (0, 0)))
        spad = jnp.pad(flat_sc, (0, cpad - k * cl))[:, None]
        lpad = jnp.pad(local.astype(jnp.int32), (0, npad - n),
                       constant_values=-1)[:, None]
        rows = dequant_gather_rows(fpad, spad, lpad, block_n=bn,
                                   block_c=bc, interpret=interpret)[:n]
    return jax.lax.psum(rows, axis)


def sharded_dequant_gather_rows(stripes: jax.Array, scales: jax.Array,
                                slots: jax.Array, *,
                                mesh: Mesh, axis: str = "cache",
                                use_kernel: bool = True,
                                block_n: int = 256, block_c: int = 512,
                                interpret: bool = False) -> jax.Array:
    """Compressed striped gather: ``stripes [N, Cl, D]`` (int8/f16) and
    ``scales [N, Cl]`` f32 both laid out over the mesh's ``axis``,
    ``slots [n]`` GLOBAL slot ids (-1 = hole) -> ``[n, D]`` dequantized
    f32, replicated. Same one-psum reassembly as ``sharded_gather_rows``;
    the scale vector rides its stripe shard, so compression adds zero
    collectives."""
    n_stripes = stripes.shape[0]
    size = mesh.shape[axis]
    if n_stripes % size:
        raise ValueError(
            f"{n_stripes} stripes do not tile mesh axis '{axis}' "
            f"of size {size}")
    body = functools.partial(
        _local_stripe_dequant_gather, n_stripes=n_stripes, axis=axis,
        use_kernel=use_kernel, block_n=block_n, block_c=block_c,
        interpret=interpret)
    spec = P(axis) if size > 1 else P()
    fn = compat.shard_map(body, mesh=mesh,
                          in_specs=(spec, spec, P()), out_specs=P(),
                          check_vma=False)
    return fn(stripes, scales, slots.astype(jnp.int32))
