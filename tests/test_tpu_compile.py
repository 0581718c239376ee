"""Compile-only checks of the serving kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds each program for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip
would refuse (unsupported vector loads, shape casts, VMEM overuse). The
shapes are DCN-Criteo serving widths: D=16, a 1M-row L1 payload per
table, 1024 ids per request.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and pytest-xdist
workers all import this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.hps import hps as hps_mod
from repro.kernels import hps_gather as hg
from repro.kernels import ops

D, C, N = 16, 1 << 20, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cache_mesh(topo):
    return Mesh(np.asarray(topo.devices), ("cache",))


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the ops layer onto its TPU branch (compiled kernels, no
    interpret mode) while the process's backend is still the CPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn).lower(*args, **kwargs).compile().as_text()


def test_gather_rows_f32(one_chip):
    text = _compiled_text(
        lambda p, s: hg.gather_rows(p, s, block_n=256, block_c=512),
        _sds((C, D), jnp.float32, one_chip),
        _sds((N, 1), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float16])
def test_dequant_gather_rows(one_chip, dtype):
    text = _compiled_text(
        lambda p, sc, s: hg.dequant_gather_rows(p, sc, s, block_n=256,
                                                block_c=512),
        _sds((C, D), dtype, one_chip),
        _sds((C, 1), jnp.float32, one_chip),
        _sds((N, 1), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_gather_rows_f16(one_chip):
    """f16 rows are read as uint16 bits: the bitcast must not become a
    per-call conversion of the whole payload."""
    text = _compiled_text(
        lambda p, s: hg.gather_rows(p, s, block_n=256, block_c=512),
        _sds((C, D), jnp.float16, one_chip),
        _sds((N, 1), jnp.int32, one_chip))
    assert "tpu_custom_call" in text
    assert f"f32[{C},{D}]" not in text


@pytest.mark.parametrize("payload_dtype", ["f32", "f16", "int8"])
def test_pooled_stack(one_chip, on_tpu, payload_dtype):
    """The whole serving dispatch, two tables (one at the 1M-row L1 cap,
    one small), compiles to the kernel for every payload dtype."""
    store = {"f32": jnp.float32, "f16": jnp.float16,
             "int8": jnp.int8}[payload_dtype]
    payloads, slots = [], []
    for rows in (C, 1536):
        scales = _sds((rows,), jnp.float32, one_chip) \
            if payload_dtype == "int8" else None
        payloads.append((_sds((rows, D), store, one_chip), scales))
        slots.append(_sds((N, 1), jnp.int32, one_chip))
    text = hps_mod._pooled_stack.lower(
        tuple(payloads), tuple(slots), combiners=("sum", "mean")
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("scaled", [False, True])
def test_sharded_gathers(cache_mesh, scaled):
    """Both striped gathers over a described 4-chip cache mesh: one
    kernel per device and one all-reduce."""
    stripes = NamedSharding(cache_mesh, P("cache"))
    rep = NamedSharding(cache_mesh, P())
    cl = C // 4
    args = [_sds((4, cl, D), jnp.int8 if scaled else jnp.float32, stripes)]
    if scaled:
        args.append(_sds((4, cl), jnp.float32, stripes))
        fn = lambda st, sc, s: hg.sharded_dequant_gather_rows(
            st, sc, s, mesh=cache_mesh)
    else:
        fn = lambda st, s: hg.sharded_gather_rows(st, s, mesh=cache_mesh)
    args.append(_sds((N,), jnp.int32, rep))
    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
