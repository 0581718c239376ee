"""Admission-controlled serving: the deadline batch-cut decision never
busts the SLO budget, bounded queues shed with a typed rejection
IMMEDIATELY (exact counters), ``close()`` never strands a handle even
under in-flight load, and the two serving locks stay cycle-free under
concurrent submit/stats traffic."""
import queue
import threading
import time

import jax
import numpy as np
import pytest

from repro.analysis import LockOrderRecorder
from repro.configs.base import TrainConfig  # noqa: F401  (registry dep)
from repro.configs.registry import RECSYS_ARCHS, reduce_recsys_for_smoke
from repro.core.hps.hps import HPS
from repro.core.hps.persistent_db import PersistentDB
from repro.data.synthetic import SyntheticCTR
from repro.launch.mesh import make_test_mesh
from repro.models.recsys.model import RecsysModel
from repro.serve.server import (InferenceServer, ServerOverloaded,
                                deadline_batch_target,
                                deploy_from_training)


class _NoModel:
    """Stands in where the dense net is never reached: admission-path
    tests never let a request group through to the device."""

    def apply_dense(self, p, d, e, w):
        raise AssertionError("admission test served a request group")


def _req(rows=1):
    return (np.zeros((rows, 2), np.float32),
            np.zeros((rows, 1, 1), np.int32))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A real (untrained) dlrm deployment for the tests that must serve
    actual predictions; each test builds its own server from it."""
    cfg = reduce_recsys_for_smoke(RECSYS_ARCHS["dlrm-criteo"])
    mesh = make_test_mesh((1, 1))
    with mesh:
        model = RecsysModel(cfg, mesh, global_batch=16)
        params = model.init(jax.random.PRNGKey(0))
        pdb = PersistentDB(str(tmp_path_factory.mktemp("pdb")))
        deploy_from_training(model, params, pdb, "m")
        hps = HPS("m", cfg.tables, pdb, cache_capacity=64)
        dense = {k: v for k, v in params.items() if k != "embedding"}
    return cfg, model, dense, hps


# ---------------------------------------------------------------------------
# the deadline batch-cut decision (pure, property-tested)
# ---------------------------------------------------------------------------

def test_deadline_target_never_busts_the_budget():
    """For any (age, slo, max_batch, estimate): the target is in
    [1, max_batch], and either it is the floor 1 (ship the oldest
    request now) or the predicted completion fits the SLO."""
    rng = np.random.default_rng(0)
    for _ in range(1000):
        slo = float(rng.uniform(1.0, 200.0))
        age = float(rng.uniform(0.0, 2.0 * slo))
        max_batch = int(rng.integers(1, 257))
        per_row = None if rng.random() < 0.2 \
            else float(rng.uniform(0.01, 10.0))
        t = deadline_batch_target(age, slo, max_batch, per_row)
        assert 1 <= t <= max_batch
        if t > 1 and per_row is not None:
            assert age + t * per_row <= slo, (age, slo, per_row, t)


def test_deadline_target_edges():
    # expired head: ship the smallest possible group immediately
    assert deadline_batch_target(100.0, 50.0, 64, 1.0) == 1
    # no estimate yet (cold server): coalesce freely until the deadline
    assert deadline_batch_target(10.0, 50.0, 64, None) == 64
    # ample slack: grow to max_batch
    assert deadline_batch_target(0.0, 1000.0, 64, 1.0) == 64
    # tight slack: (50 - 40) / 5 = 2 rows fit
    assert deadline_batch_target(40.0, 50.0, 64, 5.0) == 2


# ---------------------------------------------------------------------------
# bounded-queue shedding: typed, immediate, exactly counted
# ---------------------------------------------------------------------------

def test_full_queue_sheds_exactly_the_overflow():
    depth, extra = 5, 3
    s = InferenceServer(_NoModel(), {}, None, queue_depth=depth)
    admitted = [s.submit(*_req()) for _ in range(depth)]
    rejected = [s.submit(*_req()) for _ in range(extra)]
    # the overflow handles resolve IMMEDIATELY with the typed rejection
    for h in rejected:
        out = h.get_nowait()
        assert isinstance(out, ServerOverloaded)
        assert "queue full" in str(out)
    # the admitted handles are still pending (server never started)
    for h in admitted:
        with pytest.raises(queue.Empty):
            h.get_nowait()
    assert s.counters()["requests_shed"] == extra


def test_submit_after_close_is_typed_rejection():
    s = InferenceServer(_NoModel(), {}, None, queue_depth=4)
    pending = s.submit(*_req())
    s.close()
    # close() drained the queued handle with the rejection...
    assert isinstance(pending.get_nowait(), ServerOverloaded)
    # ...and later submits are refused at the gate, immediately
    out = s.submit(*_req()).get_nowait()
    assert isinstance(out, ServerOverloaded)
    assert "closed" in str(out)
    assert s.counters()["requests_shed"] == 2
    with pytest.raises(RuntimeError, match="closed"):
        s.start()


def test_set_admission_requires_stopped_server():
    s = InferenceServer(_NoModel(), {}, None)
    s.start()
    try:
        with pytest.raises(RuntimeError, match="stopped"):
            s.set_admission(queue_depth=2)
    finally:
        s.stop()
    s.set_admission(queue_depth=2, slo_ms=50.0)
    assert s.queue_depth == 2 and s.slo_ms == 50.0


def test_set_admission_shrink_sheds_overflow():
    s = InferenceServer(_NoModel(), {}, None)
    handles = [s.submit(*_req()) for _ in range(5)]
    s.set_admission(queue_depth=2)
    resolved = [h for h in handles
                if not h.empty()
                and isinstance(h.get_nowait(), ServerOverloaded)]
    assert len(resolved) == 3
    assert s.counters()["requests_shed"] == 3
    assert s._q.qsize() == 2    # the carried-over admissions


# ---------------------------------------------------------------------------
# close() under live load: every handle resolves, none hangs
# ---------------------------------------------------------------------------

def test_close_never_strands_a_handle_under_load(tiny):
    cfg, model, dense, hps = tiny
    s = InferenceServer(model, dense, hps, max_batch=8,
                        queue_depth=None, slo_ms=None)
    ds = SyntheticCTR(cfg, 4)
    s.start()
    handles = []
    try:
        for i in range(30):
            b = ds.batch(i)
            handles.append(s.submit(b["dense"], b["cat"]))
    finally:
        s.close()   # mid-flight: some groups served, the rest queued
    served = shed = 0
    for h in handles:
        out = h.get(timeout=60)     # a hung handle fails the test here
        if isinstance(out, ServerOverloaded):
            shed += 1
        else:
            assert not isinstance(out, BaseException)
            assert out.shape == (4,) and np.isfinite(out).all()
            served += 1
    assert served + shed == len(handles)
    c = s.counters()
    assert c["requests_delivered"] == served
    assert c["requests_shed"] == shed


def test_closed_multi_model_resolves_every_member(tiny):
    from repro.serve.server import MultiModelServer
    cfg, model, dense, hps = tiny
    members = {n: InferenceServer(model, dense, hps, max_batch=8,
                                  queue_depth=8)
               for n in ("a", "b")}
    mm = MultiModelServer(members)
    handles = [mm.submit(n, *_tiny_batch(cfg, i))
               for i, n in enumerate(("a", "b", "a"))]
    mm.close()      # never started: everything queued must resolve
    for h in handles:
        assert isinstance(h.get(timeout=10), ServerOverloaded)
    st = mm.stats()
    assert st["a"]["requests_shed"] == 2
    assert st["b"]["requests_shed"] == 1


def _tiny_batch(cfg, i):
    b = SyntheticCTR(cfg, 2, seed=i).batch(0)
    return b["dense"], b["cat"]


# ---------------------------------------------------------------------------
# lock discipline: the two serving locks stay cycle-free under load
# ---------------------------------------------------------------------------

def test_admission_and_stats_locks_acyclic(tiny):
    """Dynamic lock-order check over the REAL serving path: submit
    threads (admission gate), the serve loop (stats + delivery) and
    stats readers all run concurrently; the recorder must observe no
    lock-order cycle between ``_admit_lock`` and ``_stats_lock``."""
    cfg, model, dense, hps = tiny
    s = InferenceServer(model, dense, hps, max_batch=8, queue_depth=16,
                        slo_ms=10_000.0)
    rec = LockOrderRecorder()
    rec.wrap(s, "_admit_lock", "InferenceServer._admit_lock")
    rec.wrap(s, "_stats_lock", "InferenceServer._stats_lock")
    ds = SyntheticCTR(cfg, 2)
    s.start()
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            s.counters()
            s.latency_percentiles()
            time.sleep(1e-3)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        handles = [s.submit(*_tiny_batch(cfg, i)) for i in range(40)]
        for h in handles:
            out = h.get(timeout=60)
            assert not isinstance(out, BaseException) \
                or isinstance(out, ServerOverloaded)
    finally:
        stop.set()
        t.join()
        s.stop()
    assert s.counters()["requests_delivered"] > 0
    # the two serving locks are by design never NESTED — the recorder
    # must see no acquisition edges at all (an even stronger statement
    # than acyclicity, which must of course also hold)
    assert rec.edges() == set()
    rec.assert_acyclic()
