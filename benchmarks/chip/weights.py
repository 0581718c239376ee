"""The benchmark's own seeded weights.

Every value is a pure function of ``(seed, stream, row, column)`` through a
32-bit integer hash, so

* the program's parameters are built on the device in one jitted call, in
  whatever physical layout the program keeps its tables (each device draws
  only its own rows), and
* the reference regenerates exactly the rows it needs, without reading
  anything the program made.

Streams: embedding table ``t`` is stream ``t``; a dense leaf is the CRC32 of
its path, with the top bit set so it never meets a table index.
"""
from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: embedding values are uniform in [-EMB_SCALE, EMB_SCALE): large enough
#: that every table moves the prediction, so a wrong row shows
EMB_SCALE = 0.05
#: dense biases are uniform in [-BIAS_SCALE, BIAS_SCALE)
BIAS_SCALE = 0.01


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """Split a seed of up to 64 bits into two uint32 words, passed to the
    jitted generators as arguments (one compiled program for all seeds)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _fmix(h):
    """murmur3's 32-bit finalizer."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _u32(x):
    if isinstance(x, (int, np.integer)):
        return jnp.uint32(int(x) & 0xFFFFFFFF)
    return jnp.asarray(x).astype(jnp.uint32)


def unit(lo, hi, stream, row, col):
    """Uniform in [-1, 1) for each broadcast ``(stream, row, col)``."""
    u32 = _u32
    k = _fmix(u32(lo) ^ _fmix(u32(hi) + jnp.uint32(0x9E3779B9)))
    h = _fmix(k ^ (u32(stream) * jnp.uint32(0x27D4EB2F)))
    h = _fmix(h ^ u32(row))
    h = _fmix(h + u32(col) * jnp.uint32(0x165667B1))
    return (h >> 8).astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0


def table_rows(lo, hi, table, rows, dim: int):
    """Rows ``rows`` (any int shape) of embedding table ``table``:
    ``rows.shape + (dim,)`` float32; negative rows give zeros (padding)."""
    rows = jnp.asarray(rows)
    cols = jnp.arange(dim, dtype=jnp.uint32)
    safe = jnp.maximum(rows, 0)
    v = unit(lo, hi, jnp.asarray(table)[..., None], safe[..., None], cols)
    return jnp.where((rows >= 0)[..., None], EMB_SCALE * v, 0.0)


def dense_stream(path: str) -> int:
    return zlib.crc32(path.encode()) | 0x80000000


def dense_leaf(lo, hi, path: str, shape: Sequence[int]):
    """One dense parameter. ``.../b<i>``: small biases. A 2-D weight:
    He-scaled uniform over its fan-in (``shape[0]``). A 1-D weight (a
    cross layer's): uniform with variance ``1 / len``."""
    shape = tuple(int(s) for s in shape)
    n = int(np.prod(shape))
    v = unit(lo, hi, dense_stream(path), jnp.arange(n, dtype=jnp.uint32),
             0).reshape(shape)
    name = path.rsplit("/", 1)[-1]
    if name.startswith("b"):
        return BIAS_SCALE * v
    if len(shape) == 2:
        return np.sqrt(6.0 / shape[0]) * v          # var 2 / fan_in
    return np.sqrt(3.0 / shape[0]) * v              # var 1 / dim


def dense_params(lo, hi, spec: Dict[str, Sequence[int]]) -> Dict:
    """The reference's dense tree, nested by ``/`` in the spec's paths."""
    out: Dict = {}
    for path, shape in spec.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = dense_leaf(lo, hi, path, shape)
    return out


def flatten(tree: Dict, prefix: str = "") -> Dict[str, object]:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


# ---------------------------------------------------------------------------
# placing the benchmark's weights into the program's own layout
# ---------------------------------------------------------------------------

class Placement:
    """Where each logical table row sits in the program's parameters.

    Found by exporting an index probe through the program's public
    ``logical_tables`` (the export the bundle writer uses), for every
    embedding collection: each physical embedding leaf gets a table map
    and a row map (``-1``: padding), the same shape as its leading axes
    and sharded like its rows. Tables are numbered as ``table_names``
    lists them (the configuration's, every collection's tables), which is
    also their seeded stream.
    """

    def __init__(self, model, table_names: Sequence[str]):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.recsys.model import logical_tables
        colls = model.collections()
        shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        self.shardings = model.param_shardings()
        index = {n: i for i, n in enumerate(table_names)}
        # one flat index per physical row, over every leaf in order
        offsets, off, found = {}, 0, {}
        for c in colls:
            probe = {}
            for g, sh in shapes[c].items():
                n = int(np.prod(sh.shape[:-1]))
                offsets[c, g] = off
                probe[g] = (jnp.arange(n, dtype=jnp.int32) + off).reshape(
                    sh.shape[:-1] + (1,))
                off += n
            found.update(logical_tables(colls[c], probe))
        if set(found) != set(index):
            raise ValueError(f"program tables {sorted(found)} != "
                             f"reference tables {sorted(index)}")
        tmap = np.full(off, -1, np.int32)
        rmap = np.full(off, -1, np.int32)
        for name, flat in found.items():
            flat = np.asarray(flat).reshape(-1)
            tmap[flat] = index[name]
            rmap[flat] = np.arange(len(flat), dtype=np.int32)
        self.maps, self.dims = {}, {}
        for c in colls:
            specs = colls[c].param_specs()
            self.maps[c], self.dims[c] = {}, {}
            for g, sh in shapes[c].items():
                lead = sh.shape[:-1]
                sl = slice(offsets[c, g], offsets[c, g] + int(np.prod(lead)))
                shd = NamedSharding(model.mesh,
                                    P(*tuple(specs[g])[:len(lead)]))
                self.maps[c][g] = tuple(
                    jax.device_put(m[sl].reshape(lead), shd)
                    for m in (tmap, rmap))
                self.dims[c][g] = sh.shape[-1]
        self.dense_shapes = {
            path: tuple(v.shape) for path, v in flatten(
                {k: v for k, v in shapes.items() if k not in colls}).items()}
        self.num_tables = len(table_names)

    def check_dense(self, spec: Dict[str, Sequence[int]]) -> None:
        want = {k: tuple(v) for k, v in spec.items()}
        if want != self.dense_shapes:
            raise ValueError(
                "the program's dense parameters differ from the "
                f"reference's: program {sorted(self.dense_shapes.items())}"
                f", reference {sorted(want.items())}")

    def embedding(self, params: Dict) -> Dict:
        """The embedding collections of a parameter (or state) tree."""
        return {c: params[c] for c in self.maps}

    def dense(self, params: Dict) -> Dict:
        return {k: v for k, v in params.items() if k not in self.maps}

    def build(self, seed: int, spec: Dict[str, Sequence[int]]):
        """The program's parameter tree for ``seed``: one jitted call on
        the device, in the program's shardings."""
        self.check_dense(spec)
        lo, hi = seed_words(seed)
        dims = self.dims

        def make(lo, hi, maps):
            emb = {c: {g: table_rows(lo, hi, tm, jnp.where(tm >= 0, rm, -1),
                                     dims[c][g])
                       for g, (tm, rm) in groups.items()}
                   for c, groups in maps.items()}
            return {**emb, **dense_params(lo, hi, spec)}

        return jax.jit(make, out_shardings=self.shardings)(lo, hi, self.maps)

    def table_sums(self, fn, leaves: Dict, *args):
        """Per-table sums of ``fn(leaf, tmap, rmap, *args)`` (one value per
        physical row), over the embedding leaves ``{collection: {group:
        leaf}}``: ``[num_tables]``."""
        t = self.num_tables

        def go(leaves, maps, *args):
            total = jnp.zeros((t + 1,), jnp.float32)
            for c, groups in maps.items():
                for g, (tm, rm) in groups.items():
                    v = fn(leaves[c][g], tm, rm, *args)
                    if v.shape != tm.shape:
                        raise ValueError(f"leaf {c}/{g}: per-row values "
                                         f"{v.shape} do not match its rows "
                                         f"{tm.shape}")
                    seg = jnp.where(tm >= 0, tm, t).reshape(-1)
                    total = total + jax.ops.segment_sum(
                        v.reshape(-1), seg, num_segments=t + 1)
            return total[:t]

        return np.asarray(jax.jit(go)(leaves, self.maps, *args))

    def change_sq(self, leaves: Dict, seed: int):
        """Per-table squared norm of ``leaves - the seed's weights``."""
        lo, hi = seed_words(seed)

        def fn(leaf, tm, rm, lo, hi):
            w0 = table_rows(lo, hi, tm, jnp.where(tm >= 0, rm, -1),
                            leaf.shape[-1])
            return jnp.sum(jnp.square(leaf.astype(jnp.float32) - w0),
                           axis=-1)

        return self.table_sums(fn, leaves, lo, hi)
