"""Serving cells: the deployed bundle, the server, the request stream and
the check of what the window delivered.

Set-up: the benchmark's seeded weights go into a graph-API model
(``build_model`` -> ``compile``), ``Model.deploy`` writes the bundle (kept
under the work directory, keyed by configuration and seed), then
``launch.serve.build_server_from_config`` rebuilds the server from its
``ps.json`` alone and a fixed number of warm-up requests, drawn from their
own seed stream, go through ``InferenceServer.submit``.

Requests: rows per request log-uniform over ``[rows_min, rows_max]``; ids
Zipf(``zipf_a``) by popularity rank, mapped rank -> id by a seeded affine
permutation of each table; dense features ``log1p(lognormal)``. Every seed
gets the same multiset of sizes (and, open loop, of arrival gaps), drawn
from the mix's ``base_seed``, in its own order; ids and dense values come
from the run's seed.
"""
from __future__ import annotations

import math
import os
import queue
import shutil
import time
from typing import Dict, List, Tuple

import numpy as np

import refcommon
import weights

#: requests whose predictions the reference recomputes, besides the longest
CHECK_SAMPLE = 24
#: seconds to wait past the window's close for answers still due
DRAIN_S = 60.0
#: bundles kept in the work directory (2.2 GB each at full size)
KEEP_BUNDLES = 8


def check_recipe(cfg: Dict, ref) -> None:
    """At the published sizes the declared graph must lower to exactly the
    config of the configuration's own recipe (``recipe``): the same
    content hash, so the benchmark runs the model users build."""
    import importlib
    from repro.configs.base import recsys_config_hash
    recipe = importlib.import_module(cfg["recipe"])
    want = recsys_config_hash(recipe.build_model().to_recsys_config())
    got = ref.declare(cfg).to_recsys_config()
    if recsys_config_hash(got) != want or got.model != cfg["model"]:
        raise ValueError(f"{cfg['name']}: the sizes in its file do not "
                         f"lower to {cfg['recipe']}'s model")


def build_model(run, batch: int, mesh, reader_seed: int = 0,
                zipf_a: float = 1.1):
    """The configuration's model (its module's ``declare``), compiled,
    holding the benchmark's weights for the run's seed; returns
    ``(model, placement)``."""
    from repro.api import DataReaderParams, Solver
    cfg = run.config
    tc = cfg.get("training", {})
    solver = Solver(batch_size=batch, lr=tc.get("lr", 1e-3),
                    optimizer=tc.get("optimizer", "adamw"),
                    sparse_optimizer=tc.get("sparse_optimizer",
                                            "rowwise_adagrad"),
                    weight_decay=tc.get("weight_decay", 0.0),
                    grad_clip=tc.get("grad_clip", 1.0),
                    mode=tc.get("mode", "gspmd"), comm=tc.get("comm", "auto"),
                    mesh_shape=tuple(mesh), seed=0)
    ref = refcommon.load_reference(cfg["name"])
    if not run.rehearse:
        check_recipe(cfg, ref)
    m = ref.declare(
        cfg, solver, DataReaderParams(
            num_dense_features=cfg["num_dense_features"], seed=reader_seed,
            zipf_a=zipf_a))
    m.compile()
    place = weights.Placement(
        m.model, [e["name"] for e in refcommon.tables(cfg)])
    # the Model API has no public way to take initial weights
    m._params = place.build(run.seed, ref.param_spec(cfg))
    return m, place


def ensure_bundle(run) -> str:
    """The deployed bundle for (configuration, seed): reused when the work
    directory has it, else written. Returns its ``ps.json``."""
    cfg, srv = run.config, run.config["serving"]
    root = os.path.join(run.work_dir, "bundles")
    key = f"{run.cell.config['name']}-{run.seed}" + \
        ("-rehearsal" if run.rehearse else "")
    final = os.path.join(root, key)
    ps = os.path.join(final, "ps.json")
    if os.path.exists(ps):
        os.utime(final)
        return ps
    os.makedirs(root, exist_ok=True)
    old = sorted((os.path.getmtime(os.path.join(root, d)), d)
                 for d in os.listdir(root))
    for _, d in old[:max(0, len(old) - KEEP_BUNDLES + 1)]:
        shutil.rmtree(os.path.join(root, d), ignore_errors=True)
    tmp = final + ".partial"
    shutil.rmtree(tmp, ignore_errors=True)
    m, _ = build_model(run, batch=srv["max_batch"], mesh=(1, 1))
    server = m.deploy(tmp, cache_capacity=srv["cache_capacity"],
                      cache_shards=srv.get("cache_shards", 1),
                      max_batch=srv["max_batch"],
                      payload_dtype=srv["payload_dtype"])
    server.close()
    server.hps.close()
    del server, m
    os.replace(tmp, final)
    return ps


class Requests:
    """The mix's request stream for one seed (see the module docstring)."""

    def __init__(self, cfg: Dict, mix: Dict, seed: int, count: int):
        # one id column per primary table and per further column an extra
        # table reads, each over the vocabulary of the tables that read it
        cols = {}
        for e in refcommon.tables(cfg):
            cols.setdefault(e["column"], e["vocab"])
        self.vocab = np.asarray([cols[c] for c in range(len(cols))],
                                np.int64)
        self.nd = cfg["num_dense_features"]
        self.seed = seed
        self.mix = mix
        base = np.random.default_rng(mix["base_seed"])
        self.count = count
        sizes = self._sizes(base, count)
        order = np.random.default_rng((seed, 1)).permutation(count)
        self.sizes = sizes[order]
        rng = np.random.default_rng((seed, 2))
        self.mult = np.empty(len(self.vocab), np.int64)
        for t, v in enumerate(self.vocab):
            a = int(rng.integers(1, max(2, v)))
            while math.gcd(a, int(v)) != 1:
                a = int(rng.integers(1, max(2, v)))
            self.mult[t] = a
        self.shift = rng.integers(0, self.vocab)

    def _sizes(self, rng, n: int) -> np.ndarray:
        lo, hi = self.mix["rows_min"], self.mix["rows_max"]
        r = np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n))
        return np.clip(np.floor(r).astype(np.int64), lo, hi)

    def make(self, i: int, stream: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Request ``i`` of the window (stream 0) or of warm-up (stream 1):
        ``(dense [rows, Nd] f32, cat [rows, T, 1] int32)``."""
        rng = np.random.default_rng((self.seed, 3 + stream, i))
        rows = int(self.sizes[i % self.count]) if stream == 0 \
            else int(self._sizes(rng, 1)[0])
        ranks = (rng.zipf(self.mix["zipf_a"], (rows, len(self.vocab))) - 1) \
            % self.vocab
        ids = (ranks * self.mult + self.shift) % self.vocab
        dense = np.log1p(rng.lognormal(size=(rows, self.nd))) \
            .astype(np.float32)
        return dense, ids.astype(np.int32)[:, :, None]


def _get(handle, timeout: float):
    out = handle.get(timeout=timeout)
    if isinstance(out, BaseException):
        raise out
    return out


class Serving:
    """The rebuilt server with its warm-up done."""

    def __init__(self, run, count: int):
        from repro.launch.serve import build_server_from_config
        self.run = run
        cfg = run.config
        ps = ensure_bundle(run)
        self.server, _ = build_server_from_config(ps)
        self.hps = self.server.hps
        self.requests = Requests(cfg, run.mix, run.seed, count)
        self.server.start()
        for i in range(run.mix["warmup_requests"]):
            dense, cat = self.requests.make(i, stream=1)
            _get(self.server.submit(dense, cat), timeout=600)

    def l1_counts(self) -> Tuple[int, int]:
        hits = misses = 0
        for c in self.hps.caches.values():
            cnt = c.counters()
            hits += cnt["hits"]
            misses += cnt["misses"]
        return hits, misses

    def close(self) -> None:
        self.server.close()
        self.hps.close()

    def check(self, done: List[Tuple[int, np.ndarray]],
              failed: int) -> Dict[str, Dict]:
        """Compare a seeded sample of the delivered answers, the longest
        among them, with the reference's predictions."""
        run = self.run
        limits = run.config["limits"]
        checks = {"answers_failed": {"value": failed, "limit": 0}}
        if not done:            # nothing to compare: the widest gap there is
            checks["pred_max_abs_dev"] = {
                "value": 1.0, "limit": limits["pred_max_abs_dev"]}
            return checks
        rng = np.random.default_rng((run.seed, 9))
        pick = set(rng.choice(len(done), min(CHECK_SAMPLE, len(done)),
                              replace=False).tolist())
        pick.add(max(range(len(done)), key=lambda k: len(done[k][1])))
        dense, ids, got = [], [], []
        for k in sorted(pick):
            i, pred = done[k]
            d, c = self.requests.make(i)
            dense.append(d)
            ids.append(c[:, :, 0])
            got.append(pred)
        ref = refcommon.load_reference(run.cell.config["name"])
        want = refcommon.predictions(ref, run.config, run.seed,
                                     np.concatenate(dense),
                                     np.concatenate(ids))
        dev = float(np.max(np.abs(np.concatenate(got) - want)))
        checks["pred_max_abs_dev"] = {"value": dev,
                                      "limit": limits["pred_max_abs_dev"]}
        return checks


class Collector:
    """Completion times of outstanding requests, swept without blocking
    (as ``loadgen``'s open-loop harness collects them), so one slow answer
    never delays the reading of those behind it."""

    def __init__(self, poll_s: float = 1e-3):
        import threading
        self.poll_s = poll_s
        self.lock = threading.Lock()
        self.pending: Dict[int, object] = {}
        self.done: Dict[int, Tuple[float, object]] = {}
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def add(self, i: int, handle) -> None:
        with self.lock:
            self.pending[i] = handle

    def _sweep(self) -> None:
        with self.lock:
            items = list(self.pending.items())
        now = time.perf_counter()
        fin = []
        for i, h in items:
            try:
                out = h.get_nowait()
            except queue.Empty:
                continue
            fin.append((i, out))
        with self.lock:
            for i, out in fin:
                self.pending.pop(i, None)
                self.done[i] = (now, out)

    def _loop(self) -> None:
        while not self.stop.is_set():
            self._sweep()
            time.sleep(self.poll_s)
        self._sweep()

    def outstanding(self) -> int:
        with self.lock:
            return len(self.pending)

    def drain(self, deadline: float) -> None:
        while time.perf_counter() < deadline and self.outstanding():
            time.sleep(self.poll_s)
        self.stop.set()
        self.thread.join()

    def answers(self):
        """``(delivered [(i, t_done, pred)], failed ids)``: an exception,
        a shed request and one still outstanding all count as failed."""
        ok, bad = [], []
        with self.lock:
            for i, (t, out) in sorted(self.done.items()):
                if isinstance(out, BaseException):
                    bad.append(i)
                else:
                    ok.append((i, t, np.asarray(out)))
            bad.extend(self.pending)
        return ok, bad


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile over raw values (``inf`` for a failure)."""
    v = sorted(values)
    if not v:
        return float("inf")
    k = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[k])


def layer_record(run, delivered_rows: int, window_s: float,
                 hits0: Tuple[int, int], hits1: Tuple[int, int]) -> Dict:
    import work
    cfg = run.config
    h, m = hits1[0] - hits0[0], hits1[1] - hits0[1]
    return {
        "rows_delivered": delivered_rows,
        "window_s": window_s,
        "l1_hits": h, "l1_misses": m,
        "dense_flops_per_row": work.dense_forward_flops(cfg),
        "gather_bytes_per_row": work.gather_bytes_per_row(
            cfg, cfg["serving"]["payload_dtype"]),
    }
