"""The static-analysis subsystem: rule detection on adversarial
fixtures, waivers, the shrink-only baseline, import-graph reachability,
the repo-wide clean gate, and regression tests for the data races the
lock lint surfaced (counter snapshots in the cache / HPS / server)."""
import os
import threading
import time

import numpy as np
import pytest

from repro.analysis import concurrency, deadcode
from repro.analysis.findings import apply_baseline, load_baseline
from repro.analysis.__main__ import main as analysis_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "analysis_fixtures")
SRC = os.path.join(ROOT, "src", "repro")


def _lint_fixture(name):
    return concurrency.lint_paths([os.path.join(FIXTURES, name)], ROOT)


# ---------------------------------------------------------------------------
# rule detection on the adversarial fixtures
# ---------------------------------------------------------------------------

def test_guarded_write_without_lock_trips_lock001_only():
    fs = _lint_fixture("bad_guarded_write.py")
    assert [f.rule for f in fs] == ["LOCK001", "LOCK001"]
    assert {f.symbol for f in fs} == {"Counter.bump", "Counter.peek"}
    assert not any(f.waived for f in fs)


def test_fetch_under_lock_trips_lock002_only():
    fs = _lint_fixture("bad_fetch_under_lock.py")
    assert [f.rule for f in fs] == ["LOCK002"] * 3
    msgs = " | ".join(f.message for f in fs)
    assert "fetch_fn" in msgs and "time.sleep" in msgs
    assert "device->host" in msgs          # the np.asarray(snapshot())


def test_lock_order_cycle_trips_lock003_only():
    fs = _lint_fixture("bad_lock_cycle.py")
    assert fs and all(f.rule == "LOCK003" for f in fs)
    assert any("cycle" in f.message for f in fs)


def test_locked_suffix_call_without_lock_trips_lock004_only():
    fs = _lint_fixture("bad_locked_call.py")
    assert [f.rule for f in fs] == ["LOCK004"]
    assert fs[0].symbol == "Index.get_fast"


def test_clean_fixture_trips_nothing():
    fs = _lint_fixture("clean_guarded.py")
    live = [f for f in fs if not f.waived]
    assert live == []
    # ... and its one intentional site is waived, not missed
    assert [f.rule for f in fs if f.waived] == ["LOCK002"]


# ---------------------------------------------------------------------------
# waivers + baseline
# ---------------------------------------------------------------------------

def _lint_source(tmp_path, src):
    p = tmp_path / "mod.py"
    p.write_text(src)
    return concurrency.lint_paths([str(p)], str(tmp_path))


BAD = """import threading
class C:
    _GUARDED_BY = {"x": "_lock"}
    def __init__(self):
        self._lock = threading.Lock()
        self.x = 0
    def peek(self):
        @ABOVE@
        return self.x@INLINE@
"""


def _bad(line_above="pass", inline=""):
    return BAD.replace("@ABOVE@", line_above).replace("@INLINE@", inline)


def test_waiver_on_offending_line(tmp_path):
    fs = _lint_source(tmp_path, _bad(
        inline="  # lock-ok: LOCK001 test read"))
    assert [f.rule for f in fs] == ["LOCK001"] and fs[0].waived
    assert fs[0].waive_reason == "test read"


def test_waiver_on_line_above(tmp_path):
    fs = _lint_source(tmp_path, _bad(
        line_above="# lock-ok: LOCK001 torn read accepted"))
    assert [f.rule for f in fs] == ["LOCK001"] and fs[0].waived


def test_waiver_wrong_rule_does_not_apply(tmp_path):
    fs = _lint_source(tmp_path, _bad(
        line_above="# lock-ok: LOCK002 wrong rule"))
    assert [f.rule for f in fs] == ["LOCK001"] and not fs[0].waived


def test_baseline_roundtrip_and_staleness(tmp_path):
    base = tmp_path / "baseline.toml"
    base.write_text(
        '# comment\n'
        '[[allow]]\n'
        'rule = "LOCK001"\n'
        'file = "mod.py"\n'
        'reason = "grandfathered"\n'
        '\n'
        '[[allow]]\n'
        'rule = "LOCK004"\n'
        'file = "other.py"\n'
        'line = 12\n')
    entries = load_baseline(str(base))
    assert len(entries) == 2 and entries[1]["line"] == 12

    fs = _lint_source(tmp_path, _bad())
    failing, stale = apply_baseline(fs, entries)
    assert failing == []                   # LOCK001 entry absorbed it
    assert len(stale) == 1                 # the LOCK004 entry is stale
    assert stale[0]["rule"] == "LOCK004"


def test_baseline_rejects_garbage(tmp_path):
    p = tmp_path / "b.toml"
    p.write_text("[[allow]]\nrule = LOCK001\n")   # unquoted value
    with pytest.raises(ValueError):
        load_baseline(str(p))


# ---------------------------------------------------------------------------
# deadcode reachability
# ---------------------------------------------------------------------------

def test_deadcode_on_synthetic_tree(tmp_path):
    src = tmp_path / "src" / "pkg"
    for rel, body in {
        "api.py": "import pkg.used\n",
        "used.py": "x = 1\n",
        "testutil.py": "y = 2\n",
        "orphan.py": "z = 3\n",
        "plugins/alpha.py": "w = 4\n",
        "loader.py": 'NAME = "pkg.plugins." + "alpha"\n',
    }.items():
        p = src / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(body)
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    (tests_dir / "test_it.py").write_text("import pkg.testutil\n")

    rep = deadcode.reachability(str(tmp_path), str(src))
    assert "pkg.api" in rep.runtime and "pkg.used" in rep.runtime
    # loader is NOT a runtime seed (not api/launch/benchmarks) => its
    # prefix edge only matters once something reaches it
    assert rep.test_only == {"pkg.testutil"}
    assert "pkg.orphan" in rep.orphans

    fs = deadcode.lint(str(tmp_path), str(src))
    dead1 = [f for f in fs if f.rule == "DEAD001"]
    assert any("pkg.orphan" in f.message for f in dead1)


def test_deadcode_dynamic_prefix_marks_subpackage(tmp_path):
    src = tmp_path / "src" / "pkg"
    for rel, body in {
        "api.py": 'MOD = "pkg.plugins." + NAME\n',
        "plugins/alpha.py": "w = 4\n",
        "plugins/beta.py": "v = 5\n",
    }.items():
        p = src / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(body)
    rep = deadcode.reachability(str(tmp_path), str(src))
    # the "pkg.plugins." literal in a runtime root marks BOTH plugins
    # reachable, even though pkg/plugins has no __init__.py
    assert {"pkg.plugins.alpha", "pkg.plugins.beta"} <= rep.runtime
    assert rep.orphans == set()


# ---------------------------------------------------------------------------
# the repo itself is clean (the CI gate, exercised in-process)
# ---------------------------------------------------------------------------

def test_repo_lock_lint_is_clean():
    fs = concurrency.lint_tree(SRC, ROOT)
    live = [f.format() for f in fs if not f.waived]
    assert live == []


def test_repo_has_no_orphan_modules():
    fs = deadcode.lint(ROOT, SRC)
    dead1 = [f.format() for f in fs if f.rule == "DEAD001"]
    assert dead1 == []


def test_cli_check_gate_passes():
    assert analysis_main(["--check"]) == 0


def test_guard_contracts_declared_on_serving_classes():
    from repro.core.hps.embedding_cache import DeviceEmbeddingCache
    from repro.core.hps.hps import HPS
    from repro.core.hps.message_bus import MessageBus
    from repro.core.hps.persistent_db import PersistentDB
    from repro.core.hps.volatile_db import VolatileDB
    from repro.serve.server import InferenceServer
    for cls, attr in [(DeviceEmbeddingCache, "_id_of"),
                      (VolatileDB, "_store"),
                      (PersistentDB, "_maps"),
                      (MessageBus, "_topics"),
                      (HPS, "_l3_fetch_calls"),
                      (InferenceServer, "latency_hist"),
                      (InferenceServer, "requests_shed")]:
        assert attr in cls._GUARDED_BY, cls.__name__
    assert "fetch_fn" in DeviceEmbeddingCache._LOCKS_OF


# ---------------------------------------------------------------------------
# dynamic lock-order recorder
# ---------------------------------------------------------------------------

class _TwoLocks:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()


def test_lockorder_recorder_detects_abba_cycle():
    from repro.analysis import LockOrderRecorder
    obj = _TwoLocks()
    rec = LockOrderRecorder()
    rec.wrap(obj, "_a", "A")
    rec.wrap(obj, "_b", "B")
    with obj._a:
        with obj._b:                 # A -> B
            pass
    rec.assert_acyclic()             # one direction only: fine
    with obj._b:
        with obj._a:                 # B -> A: now both ways
            pass
    assert rec.edges() == {("A", "B"), ("B", "A")}
    with pytest.raises(AssertionError, match="lock-order cycle"):
        rec.assert_acyclic()


def test_lockorder_recorder_reentrant_and_idempotent():
    from repro.analysis import LockOrderRecorder
    from repro.analysis.lockorder import _RecordingLock
    obj = _TwoLocks()
    obj._a = threading.RLock()
    rec = LockOrderRecorder()
    w1 = rec.wrap(obj, "_a", "A")
    w2 = rec.wrap(obj, "_a", "A")    # second wrap returns the wrapper
    assert w1 is w2 and isinstance(obj._a, _RecordingLock)
    with obj._a:
        with obj._a:                 # reentrant re-acquire: no edge
            pass
    assert rec.edges() == set()
    rec.assert_acyclic()


# ---------------------------------------------------------------------------
# regression tests for the races the lint surfaced
# ---------------------------------------------------------------------------

def _cache(vocab=300, dim=8, capacity=32):
    from repro.core.hps.embedding_cache import DeviceEmbeddingCache
    store = np.random.default_rng(0).normal(
        size=(vocab, dim)).astype(np.float32)
    return DeviceEmbeddingCache(capacity, dim,
                                fetch_fn=lambda ids: store[ids])


def test_hit_rate_consistent_under_query_hammer():
    c = _cache()
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            hr = c.hit_rate
            if not (0.0 <= hr <= 1.0):
                bad.append(hr)

    t = threading.Thread(target=reader)
    t.start()
    rng = np.random.default_rng(1)
    try:
        for _ in range(60):
            c.query(rng.integers(0, 300, size=16))
    finally:
        stop.set()
        t.join()
    assert bad == []
    snap = c.counters()
    assert snap["hits"] + snap["misses"] >= 60 * 1   # counted under lock


def test_hps_stats_snapshot_under_lookup_hammer(tmp_path):
    from repro.configs.base import EmbeddingTableConfig
    from repro.core.hps.hps import HPS
    from repro.core.hps.persistent_db import PersistentDB

    pdb = PersistentDB(str(tmp_path / "pdb"))
    tabs = []
    for i in range(2):
        rows = np.random.default_rng(i).normal(
            size=(100, 4)).astype(np.float32)
        pdb.create_table("m", f"t{i}", 100, 4, initial=rows)
        tabs.append(EmbeddingTableConfig(f"t{i}", 100, 4, hotness=2))
    hps = HPS("m", tabs, pdb, cache_capacity=16)

    stop = threading.Event()
    errs = []

    def hammer():
        rng = np.random.default_rng(7)
        while not stop.is_set():
            hps.lookup(rng.integers(0, 100, size=(4, 2, 2)))

    t = threading.Thread(target=hammer)
    t.start()
    last = -1
    try:
        for _ in range(40):
            st = hps.stats()
            calls = sum(st["l3_fetches"]["calls"].values())
            if calls < last:               # monotonic counter snapshot
                errs.append((last, calls))
            last = calls
            assert set(st) >= {"l1_hit_rate", "l2_hits", "l3_fetches",
                               "refresh", "stream"}
    finally:
        stop.set()
        t.join()
    hps.close()
    assert errs == []


def test_server_counters_thread_safe():
    from repro.serve.server import InferenceServer

    class _NoModel:
        def apply_dense(self, p, d, e, w):  # never called in this test
            raise AssertionError

    s = InferenceServer(_NoModel(), {}, None)
    n_threads, per = 8, 200

    def writer():
        for _ in range(per):
            s._record_latency(time.perf_counter())

    ts = [threading.Thread(target=writer) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert s.counters()["groups_served"] == n_threads * per
    pct = s.latency_percentiles()
    assert set(pct) == {"p50", "p95", "p99", "p999", "mean"}
    s.reset_latencies()
    assert s.counters()["groups_served"] == 0
    assert s.latency_percentiles() == {}
