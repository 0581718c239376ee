"""The hot-path sanitizer (SYNC001/SYNC002) and the serving contract it
exists to pin: after warmup the stream serve engine performs exactly ONE
device->host sync per delivered request group and ZERO recompiles — for
a canonical recipe (dlrm) and a novel graph arch (twotower) — while the
no-overlap ``stage_sync`` reference engine, by construction, syncs far
more (the positive control proving the monitor actually measures)."""
import ast
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import HotPathMonitor, active_monitor
from repro.api import Solver
from repro.data.synthetic import SyntheticCTR
from repro.serve.server import InferenceServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# hook mechanics: zero overhead when disarmed, exact restore, no nesting
# ---------------------------------------------------------------------------

def test_hooks_are_noops_when_disarmed():
    orig_asarray = np.asarray
    orig_block = jax.block_until_ready
    assert active_monitor() is None
    assert not hasattr(orig_asarray, "_hotpath_orig")
    with HotPathMonitor() as mon:
        assert active_monitor() is mon
        assert np.asarray is not orig_asarray
        assert jax.block_until_ready is not orig_block
    # restored to the SAME function objects: disarmed cost is zero
    assert np.asarray is orig_asarray
    assert jax.block_until_ready is orig_block
    assert active_monitor() is None


def test_monitor_does_not_nest():
    with HotPathMonitor():
        with pytest.raises(RuntimeError, match="does not nest"):
            HotPathMonitor().__enter__()
    assert active_monitor() is None


def test_counts_d2h_only_for_device_values():
    x = jnp.arange(4.0)
    host = np.ones(4)
    with HotPathMonitor() as mon:
        np.asarray(host)               # host->host: free, not counted
        np.asarray(x)                  # device->host: counted
        np.array(x)                    # counted (the other entry point)
    evs = mon.events()
    assert [e.kind for e in evs] == ["d2h", "d2h"]
    assert {e.via for e in evs} == {"numpy.asarray", "numpy.array"}


def test_counts_blocking_sync():
    x = jnp.arange(4.0)
    with HotPathMonitor() as mon:
        jax.block_until_ready(x)
    assert mon.summary()["block"] == 1 and mon.summary()["d2h"] == 0


def test_counts_fresh_compiles_not_cache_hits():
    f = jax.jit(lambda v: v * 2.0 + 1.0)
    x = jnp.arange(8.0)
    with HotPathMonitor() as warm:
        np.asarray(f(x))
    assert warm.compiles >= 1          # fresh lowering happened armed
    with HotPathMonitor() as again:
        np.asarray(f(x))               # same shape: jit cache hit
    assert again.compiles == 0
    assert again.sync_count == 1


def test_hidden_sync_fixture_leaky_vs_clean():
    spec = importlib.util.spec_from_file_location(
        "bad_hidden_sync",
        os.path.join(ROOT, "tests", "analysis_fixtures",
                     "bad_hidden_sync.py"))
    fx = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fx)
    fx.leaky_pipeline(1)               # warm both jit paths unarmed
    fx.clean_pipeline(1)
    with HotPathMonitor() as leaky:
        fx.leaky_pipeline(3)
    with HotPathMonitor() as clean:
        fx.clean_pipeline(3)
    assert leaky.sync_count == 3       # one hidden d2h per step
    assert clean.sync_count == 1       # the one final materialization


# ---------------------------------------------------------------------------
# the serving contract
# ---------------------------------------------------------------------------

def _build(arch):
    import importlib
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_"))
    m = mod.build_model(smoke=True,
                        solver=Solver(batch_size=16, lr=1e-2))
    m.compile()
    m.fit(steps=2)
    return m


@pytest.fixture(scope="module",
                params=["dlrm-criteo", "twotower-criteo"])
def served(request, tmp_path_factory):
    """A deployed stream-engine server for a canonical recipe AND a
    novel graph arch — the pipeline contract must hold for both."""
    m = _build(request.param)
    dep = str(tmp_path_factory.mktemp("san_" + request.param))
    server = m.deploy(dep, cache_capacity=256, max_batch=8)
    assert server.engine == "stream"
    return m, server


def test_stream_engine_one_sync_per_group_zero_recompiles(served):
    m, server = served
    rows, warm_rounds, k = 8, 3, 5
    server.start()
    try:
        for i in range(warm_rounds):   # warm jit + L1 over the loop path
            d = SyntheticCTR(m.cfg, rows, seed=500 + i).batch(i)
            server.submit(d["dense"], d["cat"]).get(timeout=120)
        server.reset_latencies()
        with HotPathMonitor("stream") as mon:
            for i in range(k):
                d = SyntheticCTR(m.cfg, rows, seed=900 + i).batch(i)
                out = server.submit(d["dense"], d["cat"]).get(timeout=120)
                assert not isinstance(out, Exception)
    finally:
        server.stop()
    assert server.counters()["groups_served"] == k
    summ = mon.summary()
    assert summ["syncs"] == k, summ    # ONE host sync per group
    assert summ["compiles"] == 0, summ  # ZERO post-warmup recompiles


def test_stream_engine_zero_recompiles_within_a_bucket(served):
    """Groups of 5, 6, 7 and 8 rows all run at the row bucket 8, from
    pooled block to probability: once that bucket is warm (and the L1
    holds every id below), they compile nothing, sync once each, and the
    dense net sees one row count; the host trims each answer."""
    m, server = served
    base = SyntheticCTR(m.cfg, 8, seed=1200).batch(0)
    sizes = (5, 6, 7, 8)
    server.start()
    try:
        for _ in range(2):             # warm the bucket and the L1
            out = server.submit(base["dense"], base["cat"]).get(timeout=120)
            assert not isinstance(out, Exception)
        server.reset_serving_stats()
        with HotPathMonitor("stream-bucket") as mon:
            for n in sizes:
                out = server.submit(base["dense"][:n],
                                    base["cat"][:n]).get(timeout=120)
                assert isinstance(out, np.ndarray) and out.shape == (n,)
    finally:
        server.stop()
    c = server.counters()
    summ = mon.summary()
    assert summ["compiles"] == 0, summ  # ZERO recompiles across row counts
    assert summ["syncs"] == len(sizes), summ
    assert c["groups_served"] == len(sizes)
    assert c["dense_shapes"] == 1
    assert c["rows_padded"] == sum(8 - n for n in sizes)


def test_admission_control_preserves_hotpath_contract(served):
    """Arming the admission controller (bounded queue + declared SLO +
    deadline batching) must not change the hot path: the batch-cut
    decision is pure host arithmetic, so the one-sync-per-group /
    zero-recompile contract holds with admission ON. Request rows are
    pinned to ``max_batch`` so every delivered group keeps one shape."""
    m, server = served
    ctl = InferenceServer(m.model, m.dense_params(), server.hps,
                          wide_hps=server.wide_hps, max_batch=8,
                          engine="stream", queue_depth=64,
                          slo_ms=10_000.0, deadline_batching=True)
    rows, k = 8, 5
    ctl.start()
    try:
        for i in range(3):             # warm THIS server's jit wrappers
            d = SyntheticCTR(m.cfg, rows, seed=600 + i).batch(i)
            out = ctl.submit(d["dense"], d["cat"]).get(timeout=120)
            assert not isinstance(out, Exception)
        ctl.reset_serving_stats()
        with HotPathMonitor("stream+admission") as mon:
            for i in range(k):
                d = SyntheticCTR(m.cfg, rows, seed=950 + i).batch(i)
                out = ctl.submit(d["dense"], d["cat"]).get(timeout=120)
                assert not isinstance(out, Exception)
    finally:
        ctl.stop()
    c = ctl.counters()
    assert c["groups_served"] == k and c["requests_delivered"] == k
    assert c["requests_shed"] == 0 and c["requests_expired"] == 0
    summ = mon.summary()
    assert summ["syncs"] == k, summ     # ONE host sync per group
    assert summ["compiles"] == 0, summ  # ZERO recompiles, admission on


def test_stage_sync_reference_syncs_more(served):
    """Positive control: the no-overlap engine blocks every device
    stage, so the monitor must see MANY more syncs than groups — proof
    the one-sync result above is measurement, not a dead monitor."""
    m, server = served
    ref = InferenceServer(m.model, m.dense_params(), server.hps,
                          wide_hps=server.wide_hps, max_batch=8,
                          engine="stage_sync")
    k, rows = 3, 8
    d = SyntheticCTR(m.cfg, rows, seed=77)
    ref._predict_stage_sync(d.batch(0)["dense"], d.batch(0)["cat"])
    with HotPathMonitor("stage_sync") as mon:
        for i in range(1, k + 1):
            ref._predict_stage_sync(d.batch(i)["dense"],
                                    d.batch(i)["cat"])
    assert mon.sync_count > k          # per-table blocks + final asarray


def test_benchmark_arms_run_uninstrumented():
    """The speedup benchmark's timed arms must not import the sanitizer:
    monitoring overhead is opt-in and never taxes reported numbers."""
    path = os.path.join(ROOT, "benchmarks", "hps_speedup.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            assert not any(a.name.startswith("repro.analysis")
                           for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert not (node.module or "").startswith("repro.analysis")
