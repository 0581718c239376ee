"""The sharded, pipelined HPS serving engine: striped payload store
equivalence (N>1 host shards == N=1, bit-exact), the sharded gather
kernel entry points (flat remap + shard_map over real devices), the
hotness-scheduled refresh (hot-before-cold, per-cycle budget), and the
double-buffered lookup pipeline (pipelined == sequential, stream ==
sequential, server-loop-driven refresh)."""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import EmbeddingTableConfig
from repro.core.hps.embedding_cache import DeviceEmbeddingCache
from repro.core.hps.hps import HPS
from repro.core.hps.payload_store import ShardedPayloadStore
from repro.core.hps.persistent_db import PersistentDB
from repro.kernels import ops, ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _store(vocab=200, dim=8, seed=0):
    return np.random.default_rng(seed).normal(
        size=(vocab, dim)).astype(np.float32)


def _hps(tmp_path, tag, vocab=120, dim=8, n_tables=3, hotness=4, **kw):
    pdb = PersistentDB(str(tmp_path / f"pdb_{tag}"))
    tabs = []
    for i in range(n_tables):
        rows = _store(vocab, dim, seed=50 + i)
        pdb.create_table("m", f"t{i}", vocab, dim, initial=rows)
        tabs.append(EmbeddingTableConfig(
            f"t{i}", vocab, dim, hotness=hotness,
            combiner="mean" if i % 2 else "sum"))
    return HPS("m", tabs, pdb, **kw)


# ---------------------------------------------------------------------------
# sharded gather entry points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_stripes,cl,d,n", [(2, 24, 8, 7), (4, 16, 32, 64),
                                              (8, 8, 4, 200)])
def test_sharded_gather_matches_ref(n_stripes, cl, d, n):
    rng = np.random.default_rng(n_stripes * 100 + n)
    stripes = jnp.asarray(rng.normal(size=(n_stripes, cl, d))
                          .astype(np.float32))
    slots = rng.integers(-1, n_stripes * cl, size=n)
    want = ref.sharded_gather_ref(stripes, jnp.asarray(slots))
    got = ops.sharded_cache_gather(stripes, slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_k = ops.sharded_cache_gather(stripes, slots, use_kernel=True)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_sharded_pooled_matches_ref():
    rng = np.random.default_rng(3)
    stripes = jnp.asarray(rng.normal(size=(4, 16, 8)).astype(np.float32))
    slots = rng.integers(-1, 64, size=(6, 5))
    rows = np.asarray(ref.sharded_gather_ref(
        stripes, jnp.asarray(slots.reshape(-1)))).reshape(6, 5, 8)
    got = ops.sharded_pooled_lookup(stripes, jnp.asarray(slots))
    np.testing.assert_allclose(np.asarray(got), rows.sum(axis=1),
                               rtol=1e-5, atol=1e-5)


def test_sharded_store_scatter_gather_roundtrip():
    rng = np.random.default_rng(4)
    for shards in (1, 3, 4):
        st = ShardedPayloadStore(60, 8, shards=shards)
        slots = np.arange(0, 60, 3, dtype=np.int64)
        rows = rng.normal(size=(len(slots), 8)).astype(np.float32)
        st.scatter(slots, rows)
        probe = np.concatenate([slots, [-1]])
        out = np.asarray(st.gather(st.snapshot(), jnp.asarray(probe)))
        np.testing.assert_array_equal(out[:-1], rows)
        assert (out[-1] == 0).all()


@pytest.mark.parametrize("n_stripes,cl,d,n", [(2, 24, 8, 7), (4, 16, 32, 64)])
def test_sharded_dequant_gather_matches_ref(n_stripes, cl, d, n):
    """Striped int8 stripes + per-row scales through the same flat-remap
    and kernel entry points, vs the dequantizing oracle."""
    rng = np.random.default_rng(n_stripes * 10 + n)
    stripes = jnp.asarray(
        rng.integers(-127, 128, size=(n_stripes, cl, d)).astype(np.int8))
    scales = jnp.asarray(
        rng.uniform(0.01, 2.0, size=(n_stripes, cl)).astype(np.float32))
    slots = rng.integers(-1, n_stripes * cl, size=n)
    want = ref.dequant_sharded_gather_ref(stripes, scales,
                                          jnp.asarray(slots))
    got = ops.sharded_cache_gather(stripes, slots, scales=scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    got_k = ops.sharded_cache_gather(stripes, slots, scales=scales,
                                     use_kernel=True)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["f16", "int8"])
def test_sharded_store_compressed_roundtrip(dtype):
    """Striped compressed store: scatter quantizes, gather dequantizes
    in-kernel; every shard count serves the same values within the
    mode's bound, and the -1 sentinel row stays exactly zero."""
    rng = np.random.default_rng(6)
    slots = np.arange(0, 60, 3, dtype=np.int64)
    rows = rng.normal(size=(len(slots), 8)).astype(np.float32)
    bound = 1e-2 if dtype == "f16" else \
        float(np.abs(rows).max()) / 254.0 + 1e-6
    for shards in (1, 3, 4):
        st = ShardedPayloadStore(60, 8, shards=shards, payload_dtype=dtype)
        st.scatter(slots, rows)
        probe = np.concatenate([slots, [-1]])
        out = np.asarray(st.gather(st.snapshot(), jnp.asarray(probe)))
        assert out.dtype == np.float32
        assert np.abs(out[:-1] - rows).max() <= bound
        assert (out[-1] == 0).all()


def test_sharded_hps_compressed_matches_f32_oracle(tmp_path):
    """Striped + compressed end-to-end: HPS with cache_shards=4 and an
    int8 L1 vs the same-stream f32 striped oracle."""
    h32 = _hps(tmp_path, "c32", cache_capacity=32, cache_shards=4)
    h8 = _hps(tmp_path, "c8", cache_capacity=32, cache_shards=4,
              payload_dtype="int8")
    rng = np.random.default_rng(14)
    for _ in range(6):
        cat = rng.integers(-1, 120, size=(8, 3, 4)).astype(np.int32)
        a = np.asarray(h32.lookup(cat))
        b = np.asarray(h8.lookup(cat))
        assert np.abs(a - b).max() <= 1e-1
    # identical index decisions: compression changes bytes, not policy
    assert {k: c.hits for k, c in h32.caches.items()} == \
        {k: c.hits for k, c in h8.caches.items()}


def test_sharded_store_validation():
    with pytest.raises(ValueError, match="shards"):
        ShardedPayloadStore(4, 8, shards=8)
    with pytest.raises(ValueError, match="shards"):
        ShardedPayloadStore(16, 8, shards=0)


@pytest.mark.parametrize("stripes", [0, 6, 10])
def test_make_cache_mesh_refuses_untileable_stripes(monkeypatch, stripes):
    """More stripes than devices must tile them evenly: the mesh is
    never quietly shrunk onto fewer devices than the stripes span."""
    from repro.launch import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod.jax, "devices", lambda: [object()] * 4)
    with pytest.raises(ValueError, match="cannot tile"):
        mesh_mod.make_cache_mesh(stripes)


def test_sharded_gather_over_real_devices():
    """The shard_map path: stripes distributed over 4 virtual CPU
    devices, per-device gather + one psum, vs the oracle (subprocess so
    the main pytest process keeps its single real device)."""
    body = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.kernels import ops, ref
from repro.core.hps.payload_store import ShardedPayloadStore
from repro.launch.mesh import make_cache_mesh
assert len(jax.devices()) == 4
rng = np.random.default_rng(1)
stripes = jnp.asarray(rng.normal(size=(8, 16, 8)).astype(np.float32))
slots = rng.integers(-1, 128, size=37)
want = np.asarray(ref.sharded_gather_ref(stripes, jnp.asarray(slots)))
mesh = make_cache_mesh(8)
assert mesh.shape["cache"] == 4
assert make_cache_mesh(3).shape["cache"] == 3
try:                      # 6 stripes cannot tile 4 devices evenly
    make_cache_mesh(6)
except ValueError:
    pass
else:
    raise AssertionError("make_cache_mesh(6) over 4 devices must raise")
for kw in ({}, {"use_kernel": True}):
    got = np.asarray(ops.sharded_cache_gather(stripes, slots, mesh=mesh,
                                              **kw))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
s2 = jnp.asarray(rng.integers(-1, 128, size=(6, 5)))
pw = np.asarray(ref.sharded_gather_ref(
    stripes, s2.reshape(-1))).reshape(6, 5, 8).sum(1)
pg = np.asarray(ops.sharded_pooled_lookup(stripes, s2, mesh=mesh))
np.testing.assert_allclose(pg, pw, rtol=1e-5, atol=1e-5)
st = ShardedPayloadStore(120, 8, shards=8, mesh=mesh)
sl = np.arange(0, 120, 3, dtype=np.int64)
rows = rng.normal(size=(len(sl), 8)).astype(np.float32)
st.scatter(sl, rows)
out = np.asarray(st.gather(st.snapshot(), jnp.asarray(sl)))
np.testing.assert_array_equal(out, rows)
# compressed stripes over the same mesh: scales shard with their
# stripes through the one-psum path, values stay within the int8 bound
sq = ShardedPayloadStore(120, 8, shards=8, mesh=mesh,
                         payload_dtype="int8")
sq.scatter(sl, rows)
qout = np.asarray(sq.gather(sq.snapshot(), jnp.asarray(sl)))
assert qout.dtype == np.float32
assert np.abs(qout - rows).max() <= np.abs(rows).max() / 254.0 + 1e-6
print("multi-device striped gather OK")
"""
    code = ("import os\nos.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n" + body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"subprocess failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    assert "multi-device striped gather OK" in proc.stdout


# ---------------------------------------------------------------------------
# (a) striped cache == single-payload cache on the same query stream
# ---------------------------------------------------------------------------

def test_sharded_cache_matches_unsharded_under_churn():
    store = _store(vocab=300, dim=8)
    caches = {n: DeviceEmbeddingCache(32, 8, shards=n,
                                      fetch_fn=lambda ids: store[ids])
              for n in (1, 4)}
    rng = np.random.default_rng(11)
    for _ in range(25):
        ids = rng.integers(-1, 300, size=rng.integers(1, 64))
        outs = {n: np.asarray(c.query(ids)) for n, c in caches.items()}
        # same stream, same index decisions -> bit-identical rows
        np.testing.assert_array_equal(outs[1], outs[4])
    assert caches[1].hits == caches[4].hits
    np.testing.assert_array_equal(caches[1].resident_ids(),
                                  caches[4].resident_ids())


def test_sharded_hps_matches_unsharded_pooled(tmp_path):
    h1 = _hps(tmp_path, "n1", cache_capacity=32)
    h4 = _hps(tmp_path, "n4", cache_capacity=32, cache_shards=4)
    rng = np.random.default_rng(12)
    for _ in range(6):
        cat = rng.integers(-1, 120, size=(8, 3, 4)).astype(np.int32)
        np.testing.assert_array_equal(np.asarray(h1.lookup(cat)),
                                      np.asarray(h4.lookup(cat)))


# ---------------------------------------------------------------------------
# (b) hotness-scheduled refresh
# ---------------------------------------------------------------------------

def test_refresh_hot_row_before_cold_row_within_budget():
    store = _store(vocab=20, dim=4)
    c = DeviceEmbeddingCache(8, 4, fetch_fn=lambda ids: store[ids])
    for _ in range(5):
        c.query(np.asarray([3]))              # id 3 becomes hot
    c.query(np.asarray([7]))                  # id 7 stays cold
    orig7 = store[7].copy()
    store[3] = 111.0                          # both rows go stale below
    store[7] = 222.0
    assert c.mark_dirty(np.asarray([3, 7])) == 2
    assert c.refresh_backlog() == 2

    assert c.refresh_chunk(budget=1) == 1     # budget respected
    # the HOT dirty row was refreshed first; the cold one still stale
    np.testing.assert_allclose(np.asarray(c.query(np.asarray([3])))[0],
                               111.0)
    np.testing.assert_allclose(np.asarray(c.query(np.asarray([7])))[0],
                               orig7)
    assert c.refresh_backlog() == 1
    assert c.refresh_chunk(budget=4) == 1     # drains the cold row
    np.testing.assert_allclose(np.asarray(c.query(np.asarray([7])))[0],
                               222.0)
    assert c.refresh_backlog() == 0
    assert c.rows_refreshed == 2 and c.refresh_chunks == 2


def test_refresh_chunk_never_exceeds_budget():
    store = _store(vocab=64, dim=4)
    c = DeviceEmbeddingCache(32, 4, fetch_fn=lambda ids: store[ids])
    c.query(np.arange(32))
    fetched = []
    orig = c.fetch_fn
    c.fetch_fn = lambda ids: fetched.append(len(ids)) or orig(ids)
    c.mark_all_dirty()
    while c.refresh_backlog():
        c.refresh_chunk(budget=5)
    assert max(fetched) <= 5                  # per-cycle fetch bounded
    assert sum(fetched) == 32                 # every resident row covered
    assert c.rows_refreshed == 32


def test_mark_dirty_only_touches_resident():
    store = _store(vocab=30, dim=4)
    c = DeviceEmbeddingCache(8, 4, fetch_fn=lambda ids: store[ids])
    c.query(np.asarray([1, 2]))
    assert c.mark_dirty(np.asarray([1, 25, 26])) == 1
    assert c.refresh_backlog() == 1


def test_insertion_clears_dirty():
    """A slot reused by a fresh insertion must not inherit the old
    row's dirty bit (the new row just came from the lower levels)."""
    store = _store(vocab=30, dim=4)
    c = DeviceEmbeddingCache(2, 4, fetch_fn=lambda ids: store[ids])
    c.query(np.asarray([1, 2]))
    c.mark_all_dirty()
    c.query(np.asarray([3, 3, 3]))            # evicts one dirty slot
    assert c.refresh_backlog() == 1           # only the survivor is dirty


def test_refresh_once_still_full_repull():
    store = _store(vocab=10, dim=4)
    c = DeviceEmbeddingCache(8, 4, fetch_fn=lambda ids: store[ids],
                             refresh_chunk_rows=2)   # forces chunking
    c.query(np.asarray([0, 1, 2, 3, 4]))
    store[:5] = 77.0
    assert c.refresh_once() == 5
    np.testing.assert_allclose(
        np.asarray(c.query(np.arange(5))), 77.0)


def test_hps_refresh_step_and_stats(tmp_path):
    hps = _hps(tmp_path, "rs", n_tables=2, cache_capacity=16)
    cat = np.asarray([[[1, -1, -1, -1], [2, -1, -1, -1]]], np.int32)
    hps.lookup(cat)
    assert hps.schedule_refresh() == 2        # one resident row per table
    assert hps.refresh_backlog() == 2
    assert hps.refresh_step(budget=8) == 2
    st = hps.stats()
    assert st["refresh"]["rows_refreshed"] == 2
    assert st["refresh"]["backlog"] == 0
    assert st["refresh"]["chunks"] == 2
    assert sum(st["l3_fetches"]["calls"].values()) >= 2
    assert "tables" in st["l2"]


# ---------------------------------------------------------------------------
# (c) pipelined lookup == sequential lookup
# ---------------------------------------------------------------------------

def test_pipelined_matches_sequential_randomized(tmp_path):
    """Mixed combiners + hotness + eviction churn + overflow, two
    instances fed the identical stream: the double-buffered path must be
    bit-identical to the sequential one."""
    h_seq = _hps(tmp_path, "seq", cache_capacity=24)
    h_pipe = _hps(tmp_path, "pipe", cache_capacity=24)
    rng = np.random.default_rng(21)
    for step in range(10):
        b = int(rng.integers(1, 12))
        cat = rng.integers(-1, 120, size=(b, 3, 4)).astype(np.int32)
        hot = [int(x) for x in rng.integers(1, 5, size=3)] \
            if step % 2 else None
        a = np.asarray(h_seq.lookup(cat, hot, pipelined=False))
        p = np.asarray(h_pipe.lookup(cat, hot, pipelined=True))
        np.testing.assert_array_equal(a, p)
    assert {k: c.hits for k, c in h_seq.caches.items()} == \
        {k: c.hits for k, c in h_pipe.caches.items()}


def test_lookup_stream_matches_sequential(tmp_path):
    h_seq = _hps(tmp_path, "sseq", cache_capacity=24)
    h_str = _hps(tmp_path, "sstr", cache_capacity=24)
    rng = np.random.default_rng(22)
    queries = [rng.integers(-1, 120, size=(6, 3, 4)).astype(np.int32)
               for _ in range(8)]
    outs = list(h_str.lookup_stream(iter(queries)))
    assert len(outs) == len(queries)
    for q, o in zip(queries, outs):
        np.testing.assert_array_equal(np.asarray(h_seq.lookup(q)), o)


def test_lookup_stream_autotunes_depth_in_deep_rtt(tmp_path):
    """The ROADMAP open item: the stream lookahead is no longer a
    hard-coded 2 — in a deep-RTT regime (every coalesced miss fetch
    pays a remote-L2-style round trip) the auto-tuner admits MORE
    in-flight queries (bounded by the cap), and a warm fetch-free
    stream stays at the classic double buffer."""
    hps = _hps(tmp_path, "auto", cache_capacity=16)   # tiny L1: misses
    for c in hps.caches.values():                     # every fetch pays
        orig = c.fetch_fn                             # an RTT

        def slow(ids, _orig=orig):
            time.sleep(0.02)
            return _orig(ids)

        c.fetch_fn = slow
    rng = np.random.default_rng(7)
    queries = [rng.integers(0, 120, size=(4, 3, 4)).astype(np.int32)
               for _ in range(12)]
    outs = list(hps.lookup_stream(iter(queries)))
    assert len(outs) == len(queries)
    assert hps.stream_depth_peak > 2        # deepened past the classic 2
    assert hps.stream_depth_peak <= 8       # ...within the bounded cap
    assert hps.stats()["stream"]["depth_peak"] == hps.stream_depth_peak
    # results stay bit-identical to the unpipelined path under the
    # deepened lookahead
    ref_hps = _hps(tmp_path, "auto_ref", cache_capacity=16)
    for q, o in zip(queries, outs):
        np.testing.assert_array_equal(np.asarray(ref_hps.lookup(q)), o)

    # warm regime: resident ids, near-zero fetch -> classic depth
    warm_hps = _hps(tmp_path, "warm", cache_capacity=200)
    warm = [np.full((4, 3, 4), 5, np.int32) for _ in range(10)]
    list(warm_hps.lookup_stream(iter(warm)))
    assert warm_hps.stream_depth == 2


def test_lookup_stream_explicit_depth_is_pinned(tmp_path):
    """Passing depth=<int> disables the auto-tuner (the pre-redesign
    contract) even when fetches are slow."""
    hps = _hps(tmp_path, "pin", cache_capacity=16)
    for c in hps.caches.values():
        orig = c.fetch_fn

        def slow(ids, _orig=orig):
            time.sleep(0.01)
            return _orig(ids)

        c.fetch_fn = slow
    rng = np.random.default_rng(8)
    queries = [rng.integers(0, 120, size=(4, 3, 4)).astype(np.int32)
               for _ in range(6)]
    list(hps.lookup_stream(iter(queries), depth=2))
    assert hps.stream_depth_peak == 2


def test_lookup_stream_propagates_errors(tmp_path):
    hps = _hps(tmp_path, "err", cache_capacity=16)
    bad = [np.zeros((2, 2), np.int32)]        # 2-D without hotness
    with pytest.raises(ValueError, match="hotness"):
        list(hps.lookup_stream(bad))


def test_lookup_stream_validates_dims_like_lookup(tmp_path):
    """Mismatched table dims must fail with the same clear error on the
    streamed path as on lookup(), not deep inside the pooled stack."""
    pdb = PersistentDB(str(tmp_path / "pdb_dims"))
    tabs = []
    for name, dim in (("a", 4), ("b", 8)):
        pdb.create_table("m", name, 20, dim,
                         initial=np.zeros((20, dim), np.float32))
        tabs.append(EmbeddingTableConfig(name, 20, dim, hotness=1))
    hps = HPS("m", tabs, pdb)
    cat = np.zeros((2, 2, 1), np.int32)
    with pytest.raises(ValueError, match="equal table dims"):
        hps.lookup(cat)
    with pytest.raises(ValueError, match="equal table dims"):
        list(hps.lookup_stream([cat]))


def test_hps_close_releases_and_recreates_workers(tmp_path):
    hps = _hps(tmp_path, "close", cache_capacity=24)
    rng = np.random.default_rng(30)
    cat = rng.integers(-1, 120, size=(4, 3, 4)).astype(np.int32)
    a = np.asarray(hps.lookup(cat, pipelined=True))
    hps.close()
    hps.close()                               # idempotent
    b = np.asarray(hps.lookup(cat, pipelined=True))   # workers recreated
    np.testing.assert_array_equal(a, b)       # second pass: all hits


def test_pipelined_sharded_combined(tmp_path):
    """The full tentpole stack at once: striped payload + pipelined
    two-stage lookup, against the plain sequential single-payload HPS."""
    h_base = _hps(tmp_path, "base", cache_capacity=24)
    h_full = _hps(tmp_path, "full", cache_capacity=24, cache_shards=3)
    rng = np.random.default_rng(23)
    for _ in range(8):
        cat = rng.integers(-1, 120, size=(6, 3, 4)).astype(np.int32)
        np.testing.assert_array_equal(
            np.asarray(h_base.lookup(cat, pipelined=False)),
            np.asarray(h_full.lookup(cat, pipelined=True)))


# ---------------------------------------------------------------------------
# serve-loop-driven refresh (no bare timer thread)
# ---------------------------------------------------------------------------

def test_server_loop_drives_refresh(tmp_path):
    from repro.core.hps.message_bus import MessageBus, Producer

    bus = MessageBus()
    hps = _hps(tmp_path, "srv", n_tables=2, cache_capacity=16, bus=bus)

    class _Model:
        def apply_dense(self, p, d, e, w):
            return e.sum(axis=(1, 2))

    from repro.serve.server import InferenceServer
    server = InferenceServer(_Model(), {}, hps, refresh_budget=8)
    cat = np.asarray([[[5, -1, -1, -1], [6, -1, -1, -1]]], np.int32)
    before = server.predict(np.zeros((1, 1), np.float32), cat)

    prod = Producer(bus, "m")
    prod.send("t0", np.asarray([5]), np.full((1, 8), 42.0, np.float32))
    prod.flush()
    server.start()
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if server.updates_applied and server.rows_refreshed:
                break
            time.sleep(0.05)
    finally:
        server.stop()
    assert server.updates_applied >= 1        # bus polled by the loop
    assert server.rows_refreshed >= 1         # dirty row drained by loop
    after = server.predict(np.zeros((1, 1), np.float32), cat)
    assert not np.allclose(before, after)     # update reached serving


# ---------------------------------------------------------------------------
# refresh / stream / update concurrency stress
# ---------------------------------------------------------------------------

def test_refresh_concurrent_with_stream_under_update_hammer(tmp_path):
    """``refresh_chunk`` driven concurrently with ``lookup_stream``
    while a third thread hammers ``apply_updates``: no deadlock, and
    every materialized row binds a CONSISTENT id->slot view — each
    returned row is exactly one published version of exactly the id
    that was queried (value = id + version*VSTEP, constant across the
    row), never a torn row and never another id's slot."""
    import threading
    from repro.core.hps.message_bus import MessageBus, Producer

    vocab, dim, T, VSTEP = 64, 8, 2, 100000.0
    bus = MessageBus()
    pdb = PersistentDB(str(tmp_path / "pdb_stress"))
    tabs = []
    for t in range(T):
        init = np.repeat(np.arange(vocab, dtype=np.float32)[:, None],
                         dim, axis=1)           # version 0: value == id
        pdb.create_table("m", f"t{t}", vocab, dim, initial=init)
        tabs.append(EmbeddingTableConfig(f"t{t}", vocab, dim, hotness=1))
    hps = HPS("m", tabs, pdb, cache_capacity=32, bus=bus)
    from repro.analysis import LockOrderRecorder
    rec = LockOrderRecorder()
    rec.instrument_hps(hps)         # record every lock the hammer takes
    stop = threading.Event()
    failures = []

    def updater():
        try:
            prod = Producer(bus, "m")
            rng = np.random.default_rng(5)
            v = 0
            while not stop.is_set():
                v = (v % 99) + 1                # keep values f32-exact
                ids = np.unique(rng.integers(0, vocab, size=8))
                rows = np.broadcast_to(
                    ids.astype(np.float32)[:, None] + v * VSTEP,
                    (len(ids), dim)).copy()
                for t in range(T):
                    prod.send(f"t{t}", ids, rows)
                prod.flush()
                hps.apply_updates()             # L2/L3 writes + marks
        except Exception as e:                  # pragma: no cover
            failures.append(e)

    def refresher():
        try:
            while not stop.is_set():
                hps.refresh_step(budget=8)
                hps.schedule_refresh()          # keep the backlog alive
        except Exception as e:                  # pragma: no cover
            failures.append(e)

    threads = [threading.Thread(target=updater, daemon=True),
               threading.Thread(target=refresher, daemon=True)]
    for t in threads:
        t.start()
    rng = np.random.default_rng(7)
    queries = [rng.integers(0, vocab, size=(6, T, 1)).astype(np.int32)
               for _ in range(50)]
    try:
        for q, out in zip(queries, hps.lookup_stream(iter(queries))):
            out = np.asarray(out)
            for b in range(q.shape[0]):
                for t in range(T):
                    row = out[b, t]
                    assert np.all(row == row[0]), f"torn row: {row}"
                    assert row[0] % VSTEP == q[b, t, 0], \
                        f"wrong id's slot: {row[0]} for id {q[b, t, 0]}"
                    assert 0 <= row[0] // VSTEP <= 99
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "deadlocked threads"
    assert not failures, failures
    # the OBSERVED global lock-acquisition graph must be a DAG: the
    # stream/refresh/update hammer really contended (edges exist), and
    # no two threads ever ordered any pair of locks both ways
    assert rec.edges(), "hammer never held two locks at once"
    rec.assert_acyclic()
    hps.close()
