"""Hierarchical Parameter Server: 3-level fall-through, dynamic insertion,
LFU eviction, async refresh, and the Kafka-style online-update path."""
import numpy as np
import pytest

from repro.configs.base import EmbeddingTableConfig
from repro.core.hps.embedding_cache import DeviceEmbeddingCache
from repro.core.hps.hps import HPS
from repro.core.hps.message_bus import Consumer, MessageBus, Producer
from repro.core.hps.persistent_db import PersistentDB
from repro.core.hps.volatile_db import VolatileDB
from serving_reference import lookup_stage_sync


def _pdb_with_table(tmp_path, model="m", table="t0", vocab=100, dim=4):
    pdb = PersistentDB(str(tmp_path / "pdb"))
    rows = np.arange(vocab * dim, dtype=np.float32).reshape(vocab, dim)
    pdb.create_table(model, table, vocab, dim, initial=rows)
    return pdb, rows


# ---------------------------------------------------------------------------
# L1 device cache
# ---------------------------------------------------------------------------

def test_l1_hit_miss_and_dynamic_insertion():
    store = np.arange(400, dtype=np.float32).reshape(100, 4)
    fetches = []

    def fetch(ids):
        fetches.append(list(ids))
        return store[ids]

    c = DeviceEmbeddingCache(8, 4, fetch_fn=fetch)
    out = np.asarray(c.query(np.asarray([3, 5, 3])))
    np.testing.assert_allclose(out, store[[3, 5, 3]])
    # miss accounting is per-incoming-id (both 3s miss: insertion happens
    # after the index probe); the duplicate is deduped before the fetch
    assert c.hits == 0 and c.misses == 3
    out2 = np.asarray(c.query(np.asarray([3, 5])))
    np.testing.assert_allclose(out2, store[[3, 5]])
    assert c.hits == 2 and c.misses == 3      # second query: all hits
    assert fetches == [[3, 5]]                # one batched, deduped fetch


def test_l1_lfu_eviction_keeps_hot():
    store = np.arange(400, dtype=np.float32).reshape(100, 4)
    c = DeviceEmbeddingCache(4, 4, fetch_fn=lambda ids: store[ids])
    for _ in range(5):
        c.query(np.asarray([0]))              # id 0 becomes hot
    c.query(np.asarray([1, 2, 3]))            # fill
    c.query(np.asarray([10, 11, 12]))         # force 3 evictions
    assert 0 in c.resident_ids()              # the hot id survived


def test_l1_refresh_propagates_updates():
    store = np.zeros((10, 4), np.float32)
    c = DeviceEmbeddingCache(8, 4, fetch_fn=lambda ids: store[ids])
    c.query(np.asarray([1, 2]))
    store[1] = 9.0                            # lower level updated
    n = c.refresh_once()
    assert n == 2
    np.testing.assert_allclose(np.asarray(c.query(np.asarray([1])))[0], 9.0)
    # refresh itself must not count as queries: 2 misses from the first
    # query, 1 hit from the probe above
    assert c.hits == 1 and c.misses == 2


# ---------------------------------------------------------------------------
# 3-level fall-through
# ---------------------------------------------------------------------------

def test_hps_fallthrough_and_promotion(tmp_path):
    pdb, rows = _pdb_with_table(tmp_path)
    vdb = VolatileDB()
    tabs = [EmbeddingTableConfig("t0", 100, 4)]
    hps = HPS("m", tabs, pdb, vdb=vdb, cache_capacity=16)
    cat = np.asarray([[[3, -1]], [[7, 3]]], np.int32)
    out = np.asarray(hps.lookup(cat))
    np.testing.assert_allclose(out[0, 0], rows[3])
    np.testing.assert_allclose(out[1, 0], rows[7] + rows[3])
    # missed ids were promoted into the VDB, under the model-scoped key
    # (one shared L2 can back several deployed models)
    assert vdb.size("m/t0") == 2
    # second lookup hits L1 entirely
    h0 = hps.caches["t0"].hits
    hps.lookup(cat)
    assert hps.caches["t0"].hits > h0
    assert hps.stats()["l1_hit_rate"]["t0"] > 0


def test_hps_vdb_hit_avoids_pdb(tmp_path):
    pdb, rows = _pdb_with_table(tmp_path)
    vdb = VolatileDB()
    vdb.insert("m/t0", np.asarray([5]), np.ones((1, 4), np.float32) * 123)
    tabs = [EmbeddingTableConfig("t0", 100, 4)]
    hps = HPS("m", tabs, pdb, vdb=vdb, cache_capacity=4)
    out = np.asarray(hps.lookup(np.asarray([[[5]]], np.int32)))
    # VDB value (123) wins over the PDB ground truth — L2 is authoritative
    np.testing.assert_allclose(out[0, 0], 123.0)


# ---------------------------------------------------------------------------
# online updates (Kafka-style)
# ---------------------------------------------------------------------------

def test_online_update_path(tmp_path):
    pdb, rows = _pdb_with_table(tmp_path)
    bus = MessageBus()
    tabs = [EmbeddingTableConfig("t0", 100, 4)]
    hps = HPS("m", tabs, pdb, cache_capacity=16, bus=bus)
    cat = np.asarray([[[7]]], np.int32)
    old = np.asarray(hps.lookup(cat))[0, 0]
    np.testing.assert_allclose(old, rows[7])

    # trainer publishes an update
    prod = Producer(bus, "m")
    prod.send("t0", np.asarray([7]), np.full((1, 4), 55.0, np.float32))
    prod.flush()

    n = hps.apply_updates()
    assert n == 1
    # PDB (ground truth) updated
    np.testing.assert_allclose(pdb.fetch("m", "t0", np.asarray([7]))[0], 55.0)
    # L1 still stale until refresh (poll-based, per the paper)
    np.testing.assert_allclose(np.asarray(hps.lookup(cat))[0, 0], rows[7])
    hps.refresh_caches()
    np.testing.assert_allclose(np.asarray(hps.lookup(cat))[0, 0], 55.0)


def test_producer_batching_and_consumer_offsets():
    bus = MessageBus()
    prod = Producer(bus, "m", max_batch_rows=4)
    for i in range(6):
        prod.send("t0", np.asarray([i]), np.ones((1, 2), np.float32) * i)
    prod.flush()
    cons = Consumer(bus, "m")
    seen = []
    cons.poll(lambda t, ids, rows: seen.extend(ids.tolist()))
    assert sorted(seen) == list(range(6))
    # second poll: nothing new
    again = []
    cons.poll(lambda t, ids, rows: again.extend(ids.tolist()))
    assert again == []


def test_vdb_lru_capacity():
    vdb = VolatileDB(shards=2, capacity_per_shard=2)
    for i in range(8):
        vdb.insert("t", np.asarray([i]), np.ones((1, 2), np.float32))
    assert vdb.size("t") == 4          # 2 shards × 2 capacity
    mask, _ = vdb.query("t", np.asarray([0, 1]))
    assert not mask.any()              # oldest evicted


def test_message_roundtrip_serialization():
    from repro.core.hps.message_bus import _deserialize, _serialize
    ids = np.asarray([1, 99, 12345], np.int64)
    rows = np.random.default_rng(0).normal(size=(3, 16)).astype(np.float32)
    i2, r2 = _deserialize(_serialize(ids, rows))
    np.testing.assert_array_equal(ids, i2)
    np.testing.assert_array_equal(rows, r2)


# ---------------------------------------------------------------------------
# bucket-shaped lookups (the server's dense net runs at the same bucket)
# ---------------------------------------------------------------------------

def test_bucket_rows_is_the_next_power_of_two():
    from repro.core.hps.hps import bucket_rows
    assert [bucket_rows(b) for b in (0, 1, 2, 3, 5, 8, 9, 1025)] \
        == [0, 1, 2, 4, 8, 8, 16, 2048]


@pytest.mark.parametrize("capacity", [64, 2], ids=["cached", "overflow"])
@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_padded_lookup_is_lookup_then_zero_rows(tmp_path, combiner,
                                                capacity):
    """``padded=True`` hands back the bucket-shaped block: its first
    ``b`` rows are ``lookup()``'s, bit for bit, and the padded rows are
    zero — through every lookup driver, and through the overflow fix (a query
    with more unique ids than the L1 can take)."""
    pdb = PersistentDB(str(tmp_path / "pdb"))
    rng = np.random.default_rng(7)
    for name in ("t0", "t1"):
        pdb.create_table("m", name, 100, 4, initial=rng.normal(
            size=(100, 4)).astype(np.float32))
    tabs = [EmbeddingTableConfig("t0", 100, 4, hotness=3,
                                 combiner=combiner),
            EmbeddingTableConfig("t1", 100, 4, hotness=3)]
    hps = HPS("m", tabs, pdb, cache_capacity=capacity)
    cat = rng.integers(0, 100, size=(5, 2, 3)).astype(np.int32)
    cat[1, 0, 1:] = -1                       # a short row for the mean
    overflowed = []
    finalize = hps._finalize

    def spy(payloads, slot_blocks, blocks, overflow, b, padded=False):
        overflowed.append(len(overflow))
        return finalize(payloads, slot_blocks, blocks, overflow, b, padded)

    hps._finalize = spy
    want = np.asarray(hps.lookup(cat))
    assert want.shape == (5, 2, 4)
    blocks = {
        "lookup": hps.lookup(cat, padded=True),
        "pipelined": hps.lookup(cat, pipelined=True, padded=True),
        "stage_sync": lookup_stage_sync(hps, cat),
        "stream": next(iter(hps.lookup_stream([cat], materialize=False,
                                              padded=True))),
    }
    hps.close()
    assert all(overflowed) if capacity == 2 else not any(overflowed)
    for engine, got in blocks.items():
        got = np.asarray(got)
        assert got.shape == (8, 2, 4), engine
        np.testing.assert_array_equal(got[:5], want, err_msg=engine)
        np.testing.assert_array_equal(got[5:], 0.0, err_msg=engine)
