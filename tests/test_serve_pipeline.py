"""The stream-fed serve engine: ``submit()`` batching feeds the dense
net directly from ``HPS.lookup_stream`` (no caller-thread
materialization), and its predictions must be BIT-EXACT with the
unpipelined server across dlrm and wdl (the two-HPS wide branch) —
including under concurrent submits from multiple threads."""
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DataReaderParams, DenseLayer, Input, Model, Solver,
                       SparseEmbedding)
from repro.data.synthetic import SyntheticCTR
from repro.serve.server import InferenceServer


def _build(arch):
    import importlib
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_"))
    m = mod.build_model(smoke=True,
                        solver=Solver(batch_size=16, lr=1e-2))
    m.compile()
    m.fit(steps=2)
    return m


@pytest.fixture(scope="module", params=["dlrm-criteo", "wdl-criteo"])
def served(request, tmp_path_factory):
    """One trained model + its deployed HPS, behind TWO servers over the
    SAME storage: the stream-fed engine under test and the unpipelined
    reference. Embedding values are identical at every storage level, so
    any prediction difference is the pipeline's fault."""
    m = _build(request.param)
    dep = str(tmp_path_factory.mktemp("dep_" + request.param))
    stream = m.deploy(dep, cache_capacity=256, max_batch=8)
    assert stream.engine == "stream"            # the default engine
    sync = InferenceServer(m.model, m.dense_params(), stream.hps,
                           wide_hps=stream.wide_hps, max_batch=8,
                           engine="sync")
    return m, stream, sync


def _requests(cfg, n, rows):
    data = [SyntheticCTR(cfg, rows, seed=100 + i) for i in range(n)]
    return [(d.batch(i)["dense"], d.batch(i)["cat"])
            for i, d in enumerate(data)]


def test_stream_submit_bitexact_with_sequential(served):
    """Pre-queued requests coalesce into deterministic groups of
    max_batch rows; every group's predictions must be bit-identical to
    the sequential server run on the same coalesced group."""
    m, stream, sync = served
    reqs = _requests(m.cfg, 6, 4)               # coalesce 2-by-2 into 8
    handles = [stream.submit(d, c) for d, c in reqs]
    stream.start()
    try:
        got = [h.get(timeout=120) for h in handles]
    finally:
        stream.stop()
    for i in range(0, 6, 2):                    # the drained groups
        dense = np.concatenate([reqs[i][0], reqs[i + 1][0]])
        cat = np.concatenate([reqs[i][1], reqs[i + 1][1]])
        want = sync.predict(dense, cat)
        np.testing.assert_array_equal(got[i], want[:4])
        np.testing.assert_array_equal(got[i + 1], want[4:])


def test_stream_submit_bitexact_under_concurrent_submits(served):
    """Multiple threads submitting at once: every response bit-exact
    with the sequential server's prediction for that request (max_batch
    == request rows, so each request is one device batch)."""
    m, stream, sync = served
    stream.max_batch = 8
    n_threads, per_thread, rows = 4, 5, 8
    results = {}
    errors = []

    def client(tid):
        try:
            data = SyntheticCTR(m.cfg, rows, seed=500 + tid)
            out = []
            for i in range(per_thread):
                b = data.batch(i)
                h = stream.submit(b["dense"], b["cat"])
                out.append((b, h.get(timeout=120)))
            results[tid] = out
        except Exception as e:                  # surfaced after join
            errors.append(e)

    stream.start()
    try:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        stream.stop()
    assert not errors, errors
    assert len(results) == n_threads
    for tid, out in results.items():
        for b, got in out:
            assert isinstance(got, np.ndarray), got
            want = sync.predict(b["dense"], b["cat"])
            np.testing.assert_array_equal(got, want)


def test_stream_predict_path_unchanged(served):
    """The synchronous predict() entry point stays bit-identical across
    engines (it never enters the pipeline)."""
    m, stream, sync = served
    b = SyntheticCTR(m.cfg, 8, seed=9).batch(0)
    np.testing.assert_array_equal(stream.predict(b["dense"], b["cat"]),
                                  sync.predict(b["dense"], b["cat"]))


def test_stage_sync_engine_bitexact(served):
    """The no-overlap benchmark reference engine serves the same bits."""
    m, stream, sync = served
    ss = InferenceServer(m.model, m.dense_params(), stream.hps,
                         wide_hps=stream.wide_hps, max_batch=8,
                         engine="stage_sync")
    b = SyntheticCTR(m.cfg, 8, seed=11).batch(3)
    want = sync.predict(b["dense"], b["cat"])
    h = ss.submit(b["dense"], b["cat"])
    ss.start()
    try:
        np.testing.assert_array_equal(h.get(timeout=120), want)
    finally:
        ss.stop()


def _engines_at(m, stream, sync, rows, seed):
    """One request of ``rows`` rows through the stream engine, the
    ``predict()`` path and the ``stage_sync`` engine: the three answers
    must be bit-identical, and equal to the dense net applied to the
    unpadded lookups to float tolerance."""
    b = SyntheticCTR(m.cfg, rows, seed=seed).batch(0)
    h = stream.submit(b["dense"], b["cat"])
    stream.start()
    try:
        got = h.get(timeout=120)
    finally:
        stream.stop()
    ss = InferenceServer(m.model, m.dense_params(), stream.hps,
                         wide_hps=stream.wide_hps,
                         extra_hps=stream.extra_hps, hotness=stream.hotness,
                         max_batch=8, engine="stage_sync")
    assert isinstance(got, np.ndarray) and got.shape == (rows,)
    want = sync.predict(b["dense"], b["cat"])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        ss._predict_stage_sync(b["dense"], b["cat"]), want)
    emb, wide, extras = stream._lookups(
        b["cat"], lambda hps, c, hot: hps.lookup(c, hot))
    assert emb.shape[0] == rows                 # unpadded blocks
    ref = jax.nn.sigmoid(m.model.apply_dense(
        m.dense_params(), jnp.asarray(b["dense"]), emb, wide,
        extras=extras or None))
    np.testing.assert_allclose(want, np.asarray(ref), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rows", [3, 5, 7])
def test_engines_agree_at_uneven_row_counts(served, rows):
    """Row counts that are not a power of two run padded to their bucket
    (dense, deep and wide blocks alike) and come back trimmed."""
    m, stream, sync = served
    _engines_at(m, stream, sync, rows, seed=60 + rows)


def test_engines_agree_at_uneven_row_counts_n_group(tmp_path):
    """The same for an N-group model, whose extra groups' blocks are
    padded too."""
    m = Model(Solver(batch_size=16, lr=1e-2),
              DataReaderParams(num_dense_features=4), name="ngroup")
    m.add(Input(dense_dim=4))
    m.add(SparseEmbedding(vocab_sizes=[300, 100], dim=8, top_name="a"))
    m.add(SparseEmbedding(vocab_sizes=[60], dim=4, top_name="b"))
    m.add(SparseEmbedding(vocab_sizes=[40, 20, 10], dim=2, top_name="c"))
    m.add(DenseLayer("concat", ["dense", "a", "b", "c"], ["flat"]))
    m.add(DenseLayer("mlp", ["flat"], ["logit"], units=(16, 1)))
    m.add(DenseLayer("sigmoid", ["logit"], ["prob"]))
    m.compile()
    m.fit(SyntheticCTR(m.cfg, 16).batch, steps=2)
    stream = m.deploy(str(tmp_path / "dep"), cache_capacity=256,
                      max_batch=8)
    assert stream.extra_hps
    sync = InferenceServer(m.model, m.dense_params(), stream.hps,
                           extra_hps=stream.extra_hps,
                           hotness=stream.hotness, max_batch=8,
                           engine="sync")
    for rows in (3, 6):
        _engines_at(m, stream, sync, rows, seed=80 + rows)


def test_stream_burst_error_reaches_every_handle(served):
    """A poisoned request group must surface its exception to the
    waiting handles instead of hanging the callers or the loop."""
    m, stream, sync = served
    bad_cat = np.zeros((4, 2), np.int32)        # 2-D without hotness
    h = stream.submit(np.zeros((4, 1), np.float32), bad_cat)
    stream.start()
    try:
        out = h.get(timeout=120)
        assert isinstance(out, Exception)
        # and the loop survived: a good request still serves
        b = SyntheticCTR(m.cfg, 8, seed=21).batch(0)
        h2 = stream.submit(b["dense"], b["cat"])
        got = h2.get(timeout=120)
    finally:
        stream.stop()
    np.testing.assert_array_equal(got, sync.predict(b["dense"], b["cat"]))


def test_stream_dense_stage_error_reaches_own_handle(served):
    """A group that fails AFTER its lookup — in the dense net (dense
    rows != cat rows) — must still deliver the exception to its own
    handles: the group sits between fifo and in_flight when it dies."""
    m, stream, sync = served
    good = SyntheticCTR(m.cfg, 8, seed=31).batch(0)
    bad_dense = good["dense"][:3]               # 3 dense rows, 8 cat rows
    h = stream.submit(bad_dense, good["cat"])
    stream.start()
    try:
        out = h.get(timeout=120)
        assert isinstance(out, Exception), out
        h2 = stream.submit(good["dense"], good["cat"])  # loop survived
        got = h2.get(timeout=120)
    finally:
        stream.stop()
    np.testing.assert_array_equal(
        got, sync.predict(good["dense"], good["cat"]))


@pytest.mark.parametrize("engine", ["stream", "sync"])
def test_uncoalesceable_requests_error_all_handles(served, engine):
    """Requests whose widths cannot concatenate into one group must
    error BOTH handles and leave the serve loop alive — on every
    engine (the coalescer itself owns that delivery)."""
    m, stream, sync = served
    srv = InferenceServer(m.model, m.dense_params(), stream.hps,
                          wide_hps=stream.wide_hps, max_batch=64,
                          engine=engine)
    T = len(m.cfg.tables)
    h1 = srv.submit(np.zeros((4, 13), np.float32),
                    np.zeros((4, T, 1), np.int32))
    h2 = srv.submit(np.zeros((4, 13), np.float32),
                    np.zeros((4, T, 2), np.int32))    # width mismatch
    srv.start()
    try:
        assert isinstance(h1.get(timeout=120), Exception)
        assert isinstance(h2.get(timeout=120), Exception)
        b = SyntheticCTR(m.cfg, 8, seed=41).batch(0)  # loop survived
        got = srv.submit(b["dense"], b["cat"]).get(timeout=120)
    finally:
        srv.stop()
    np.testing.assert_array_equal(got, sync.predict(b["dense"], b["cat"]))


def test_engine_validated():
    with pytest.raises(ValueError, match="engine"):
        InferenceServer(object(), {}, None, engine="warp")
