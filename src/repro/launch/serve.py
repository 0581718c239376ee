"""Config-driven serving launcher (paper Figure 2, the ps.json path).

A deployment bundle written by ``api.Model.deploy`` — ``ps.json`` +
``graph.json`` + ``dense.npz`` + the ``pdb/`` table files — is all this
launcher needs: no Python object from training is required.
``build_server_from_config`` reconstructs the model graph from JSON,
re-lowers it (config hash verified), reloads the dense weights, reopens
the PDB tables (wide twins included) and stands up the
``HPS`` + ``InferenceServer``.

An ENSEMBLE bundle written by ``api.deploy_ensemble`` holds several
models behind one ps.json (format ``repro-ps-ensemble-v1``); the same
entry point then stands up a ``MultiModelServer`` — per-model L1 caches
and serve loops over ONE shared PersistentDB, ONE shared VolatileDB and
ONE shared message bus — bit-exact with per-model in-process servers.

  # serve an existing bundle (single-model or ensemble)
  PYTHONPATH=src python -m repro.launch.serve --config /path/ps.json \
      --requests 50 --batch 64

  # demo: train a recipe for a few steps, deploy, then serve THROUGH
  # the written bundle (wdl exercises the two-HPS wide path; --smoke
  # trains the reduced CPU-sized config, without it the full widths)
  PYTHONPATH=src python -m repro.launch.serve --arch dlrm-criteo \
      --smoke --requests 50 --batch 64

  # demo: 2-model ensemble bundle, one storage backend, per-model stats
  PYTHONPATH=src python -m repro.launch.serve \
      --arch dlrm-criteo,dcn-criteo --smoke --requests 10 --batch 32
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import tempfile
import time
from typing import Optional

import numpy as np

from repro.configs.base import (
    EnsembleConfig, HPSConfig, ps_config_from_dict, recsys_config_hash,
)
from repro.configs.registry import RECSYS_RECIPES
from repro.launch.compile_cache import enable_compile_cache


def load_ps_config(path: str):
    """ps.json -> :class:`HPSConfig` or :class:`EnsembleConfig`."""
    with open(path) as f:
        return ps_config_from_dict(json.load(f))


def _build_model_server(base: str, hcfg: HPSConfig, pdb, *, mesh=None,
                        vdb=None, bus=None,
                        cache_capacity: Optional[int] = None,
                        payload_dtype: Optional[str] = None):
    """One model's HPS(+wide)+InferenceServer over an open PDB: reload
    the graph + dense weights from the bundle, then hand off to the same
    ``Model._build_server`` wiring the in-process deploy path uses."""
    import dataclasses

    from repro.api import Model
    from repro.models.recsys.model import wide_tables
    from repro.train import checkpoint as ck
    from repro.train.train_step import is_sparse_key

    import jax

    if cache_capacity is not None:      # operator override of the
        hcfg = dataclasses.replace(     # bundle's (hotness-sized) L1
            hcfg, cache_capacity=cache_capacity)
    if payload_dtype is not None:       # operator override of the L1
        hcfg = dataclasses.replace(     # storage precision (safe: the
            hcfg, payload_dtype=payload_dtype)  # PDB/VDB rows stay f32)
    m = Model.from_json(os.path.join(base, hcfg.graph_path), mesh=mesh)
    m.compile()
    if hcfg.config_hash and \
            recsys_config_hash(m.cfg) != hcfg.config_hash:
        raise ValueError(f"model {hcfg.model!r}: graph does not lower "
                         "to the deployed config (hash mismatch)")
    if m.name != hcfg.model:    # storage is namespaced by this name
        raise ValueError(f"{hcfg.graph_path}: graph name {m.name!r} != "
                         f"deployed model name {hcfg.model!r}")

    # dense weights: flat key-paths -> the model's param tree (minus
    # embeddings, which live in the parameter server)
    data = np.load(os.path.join(base, hcfg.dense_weights_path))
    flat = {k: data[k] for k in data.files}
    with m.mesh:
        dummy = jax.eval_shape(
            lambda: m.model.init(jax.random.PRNGKey(0)))
    template = {k: v for k, v in dummy.items() if not is_sparse_key(k)}
    dense = ck.unflatten_like(template, flat)

    for t in hcfg.tables:
        pdb.open_table(hcfg.model, t.name)
    if hcfg.wide:
        for t in wide_tables(m.cfg):
            pdb.open_table(hcfg.model, t.name)
    for g in m.cfg.extra_groups:        # N-group models: one table set
        for t in g.tables:              # (and later one HPS) per group
            pdb.open_table(hcfg.model, t.name)
    return m._build_server(pdb, hcfg, dense, vdb=vdb, bus=bus), m


def build_server_from_config(ps_path: str, *, mesh=None, vdb=None,
                             bus=None, cache_capacity=None,
                             payload_dtype: Optional[str] = None,
                             cache_budget: Optional[int] = None,
                             rebalance_interval_s: Optional[float] = None):
    """ps.json -> ready server (the Triton-ensemble analogue).

    Single-model bundles return ``(InferenceServer, api.Model)``;
    ensemble bundles return ``(MultiModelServer, {name: api.Model})`` —
    every member model served from ONE PersistentDB process, one shared
    VolatileDB and one shared message bus. The models are handed back so
    the caller can cross-check predictions or introspect the graphs.

    ``cache_capacity`` overrides the bundle's per-model L1 sizes (an
    ensemble bundle carries hotness-proportional sizes by default): an
    ``int`` applies to every model, a ``{model_name: rows}`` dict pins
    specific members and leaves the rest on their bundled value.

    ``payload_dtype`` overrides the bundle's L1 storage precision for
    every member (bundles deployed before the knob existed read back as
    ``"f32"``). ``cache_budget`` + ``rebalance_interval_s`` arm the
    ensemble's observed-miss-pressure budget rebalancer (opt-in, see
    :class:`~repro.serve.server.MultiModelServer`); single-model bundles
    ignore them.
    """
    from repro.core.hps.persistent_db import PersistentDB
    from repro.core.hps.volatile_db import VolatileDB
    from repro.serve.server import MultiModelServer

    base = os.path.dirname(os.path.abspath(ps_path))
    cfg = load_ps_config(ps_path)

    def _cap(model_name):
        if isinstance(cache_capacity, dict):
            return cache_capacity.get(model_name)
        return cache_capacity

    if isinstance(cfg, HPSConfig):
        pdb = PersistentDB(os.path.join(base, cfg.pdb_root))
        return _build_model_server(base, cfg, pdb, mesh=mesh, vdb=vdb,
                                   bus=bus, cache_capacity=_cap(cfg.model),
                                   payload_dtype=payload_dtype)

    assert isinstance(cfg, EnsembleConfig)
    pdb = PersistentDB(os.path.join(base, cfg.models[0].pdb_root))
    vdb = vdb if vdb is not None else VolatileDB()    # shared L2
    from repro.core.hps.message_bus import MessageBus
    bus = bus if bus is not None else MessageBus()    # shared bus
    servers, models = {}, {}
    for hcfg in cfg.models:
        servers[hcfg.model], models[hcfg.model] = _build_model_server(
            base, hcfg, pdb, mesh=mesh, vdb=vdb, bus=bus,
            cache_capacity=_cap(hcfg.model), payload_dtype=payload_dtype)
    return MultiModelServer(servers, vdb=vdb, pdb=pdb, bus=bus,
                            cache_budget=cache_budget,
                            rebalance_interval_s=rebalance_interval_s), \
        models


def _train_model(arch: str, train_steps: int, batch: int, *,
                 smoke: bool):
    """Train one recipe briefly via the graph API (novel graph archs
    included — they compile through the generic dense-graph program);
    ``smoke`` picks the reduced config over the published widths."""
    from repro.api import Solver
    mod = importlib.import_module(RECSYS_RECIPES[arch])
    m = mod.build_model(smoke=smoke,
                        solver=Solver(batch_size=batch, lr=1e-2))
    m.compile()
    hist = m.fit(steps=train_steps)
    print(f"[{m.name}] trained {train_steps} steps, "
          f"loss={hist[-1]['loss']:.4f}")
    return m


def _train_and_deploy(archs, train_steps: int, batch: int,
                      deploy_dir: str,
                      cache_capacity: Optional[int],
                      payload_dtype: str = "f32", *,
                      smoke: bool) -> str:
    """Demo path: train the recipes briefly, write ONE deployment
    bundle (single-model or ensemble), return the ps.json path.
    ``cache_capacity=None`` lets ensembles size per-model L1 caches
    from table hotness; ``payload_dtype`` persists in the bundle's
    ps.json, so the rebuilt server serves the same precision mode."""
    models = [_train_model(a, train_steps, batch, smoke=smoke)
              for a in archs]
    if len(models) == 1:
        models[0].deploy(deploy_dir,
                         cache_capacity=cache_capacity or 2048,
                         payload_dtype=payload_dtype)
    else:
        from repro.api import deploy_ensemble
        deploy_ensemble(models, deploy_dir,
                        cache_capacity=cache_capacity,
                        payload_dtype=payload_dtype)
    return os.path.join(deploy_dir, "ps.json")


def _serve_bundle(ps_path: str, requests: int, batch: int, *,
                  sanitize: bool = False,
                  payload_dtype: Optional[str] = None) -> None:
    """Stand the bundle back up, push requests through ``submit`` and
    print the serving picture (per model for ensembles).

    ``sanitize=True`` arms the hot-path sanitizer over the measured
    phase and fails the run unless the serve loops performed exactly ONE
    device->host sync per delivered group and ZERO post-warmup
    recompiles — the pipeline invariants, enforced in CI.
    ``payload_dtype`` overrides the bundle's L1 storage precision."""
    from contextlib import nullcontext

    from repro.data.synthetic import SyntheticCTR
    from repro.serve.server import MultiModelServer

    built, loaded = build_server_from_config(ps_path,
                                             payload_dtype=payload_dtype)
    if isinstance(built, MultiModelServer):
        servers = {name: built[name] for name in built.models}
        models = loaded
    else:
        servers, models = {loaded.name: built}, {loaded.name: loaded}

    data = {n: SyntheticCTR(m.cfg, batch) for n, m in models.items()}
    outs = {n: [] for n in servers}
    with next(iter(models.values())).mesh:
        for n, s in servers.items():          # warm jit off the clock
            warm = data[n].batch(10_000)
            s.predict(warm["dense"], warm["cat"])
            if sanitize:
                # pin one request per coalesced group so "one sync per
                # group" is countable against the delivered groups
                s.max_batch = batch
            s.start()
        if sanitize:                          # warm the serve-loop path
            for r in range(2):
                warm_handles = [
                    s.submit(req["dense"], req["cat"])
                    for n, s in servers.items()
                    for req in (data[n].batch(30_000 + r),)]
                for h in warm_handles:
                    h.get(timeout=300)
        for s in servers.values():
            s.reset_latencies()

        if sanitize:
            from repro.analysis import HotPathMonitor
            mon = HotPathMonitor("serve-smoke")
        else:
            mon = None
        t0 = time.time()
        with mon if mon is not None else nullcontext():
            handles = []
            for r in range(requests):
                for n, s in servers.items():
                    req = data[n].batch(20_000 + r)
                    handles.append((n, s.submit(req["dense"],
                                                req["cat"])))
            for n, h in handles:
                out = h.get(timeout=300)
                if isinstance(out, Exception):  # a failed group delivers
                    raise out                   # its exception — surface
                outs[n].append(out)
        dt = time.time() - t0
        for s in servers.values():
            s.stop()

    if mon is not None:
        groups = sum(s.counters()["groups_served"]
                     for s in servers.values())
        summ = mon.summary()
        if summ["syncs"] != groups or summ["compiles"] != 0:
            raise SystemExit(
                f"hot-path sanitizer: expected {groups} host syncs (one "
                f"per served group) and 0 recompiles; observed "
                f"{summ['syncs']} syncs ({summ['d2h']} d2h, "
                f"{summ['block']} block) and {summ['compiles']} "
                "compile(s)")
        print(f"sanitizer: {summ['syncs']} host syncs over {groups} "
              "served groups, 0 post-warmup recompiles")

    total = sum(len(o) for os_ in outs.values() for o in os_)
    print(f"served {total} predictions over {len(servers)} model(s) "
          f"in {dt:.2f}s ({total / dt:.0f} qps)")
    for n, s in servers.items():
        # one full prediction batch per model from the rebuilt server,
        # or the bundle round-trip is broken — the CI serve-smoke job's
        # pass/fail signal, so an explicit raise (asserts vanish
        # under python -O)
        if not outs[n] or any(len(o) != batch for o in outs[n]):
            raise SystemExit(
                f"model {n!r}: expected {requests} responses of "
                f"{batch} rows, got {[len(o) for o in outs[n]]}")
        pct = s.latency_percentiles()
        stats = s.hps.stats()
        hit = np.mean(list(stats["l1_hit_rate"].values()))
        print(f"[{n}] {len(outs[n])} responses; latency ms: "
              f"p50={pct['p50']:.1f} p95={pct['p95']:.1f} "
              f"p99={pct['p99']:.1f}; L1 hit rate {hit:.3f}; "
              f"L2 hits={stats['l2_hits']} misses={stats['l2_misses']}; "
              f"L3 fetches={sum(stats['l3_fetches']['calls'].values())}")

    _crosscheck_compressed(ps_path, servers, models, data,
                           override=payload_dtype)


#: max-abs prediction deviation a compressed bundle may show against an
#: f32-reference rebuild of the same bundle (post-sigmoid outputs)
_PAYLOAD_TOL = {"f16": 0.05, "int8": 0.1}


def _crosscheck_compressed(ps_path: str, servers, models, data, *,
                           override: Optional[str] = None) -> None:
    """Compressed-payload bundles: rebuild an f32-reference server from
    the SAME bundle (the dtype override re-pulls full-precision rows
    from the shared PDB) and require one prediction batch per compressed
    model to stay within quantization tolerance. Runs after the measured
    phase, so its extra compiles/syncs never trip the sanitizer."""
    cfg = load_ps_config(ps_path)
    members = cfg.models if isinstance(cfg, EnsembleConfig) else (cfg,)
    dtypes = {m.model: override or m.payload_dtype for m in members}
    if all(dt == "f32" for dt in dtypes.values()):
        return
    from repro.serve.server import MultiModelServer
    ref_built, _ = build_server_from_config(ps_path, payload_dtype="f32")
    if isinstance(ref_built, MultiModelServer):
        refs = {name: ref_built[name] for name in ref_built.models}
    else:
        refs = {next(iter(servers)): ref_built}
    with next(iter(models.values())).mesh:
        for n, s in servers.items():
            if dtypes[n] == "f32":
                continue
            req = data[n].batch(77_000)
            got = s.predict(req["dense"], req["cat"])
            want = refs[n].predict(req["dense"], req["cat"])
            dev = float(np.abs(got - want).max())
            tol = _PAYLOAD_TOL[dtypes[n]]
            if dev > tol:       # explicit raise: asserts vanish under -O
                raise SystemExit(
                    f"model {n!r}: {dtypes[n]} payload predictions "
                    f"deviate {dev:.4f} from the f32 reference "
                    f"(tolerance {tol})")
            print(f"[{n}] {dtypes[n]} payload within {tol} of the f32 "
                  f"reference rebuild (max abs dev {dev:.5f})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="ps.json of an existing deployment bundle")
    ap.add_argument("--arch", default="dlrm-criteo",
                    help="demo mode: train+deploy these recipes first "
                         "(comma-separated list of "
                         f"{'|'.join(sorted(RECSYS_RECIPES))}; 2+ archs "
                         "deploy an ensemble bundle; twotower/crossdeep "
                         "are novel graphs served via the generic "
                         "compiler)")
    ap.add_argument("--train-steps", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="demo mode: train the reduced config "
                         "(CPU-runnable) instead of the published widths")
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--cache-capacity", type=int, default=None,
                    help="per-model L1 rows (default: 2048 for a single "
                         "model; hotness-proportional for ensembles)")
    ap.add_argument("--payload-dtype", default=None,
                    choices=("f32", "f16", "int8"),
                    help="L1 payload storage precision: baked into the "
                         "bundle in demo mode, or an override when "
                         "serving an existing --config bundle; non-f32 "
                         "modes additionally cross-check one prediction "
                         "per model against an f32-reference rebuild")
    ap.add_argument("--deploy-dir", default=None)
    ap.add_argument("--sanitize", action="store_true",
                    help="arm the hot-path sanitizer over the measured "
                         "phase: fail unless every served group cost "
                         "exactly one device->host sync and zero "
                         "post-warmup recompiles")
    args = ap.parse_args()
    enable_compile_cache()

    ps_path = args.config
    if ps_path is None:
        archs = [a.strip() for a in args.arch.split(",") if a.strip()]
        known = tuple(sorted(RECSYS_RECIPES))
        bad = [a for a in archs if a not in known]
        if bad:
            ap.error(f"unknown arch(es) {bad}; choose from {known}")
        deploy_dir = args.deploy_dir or tempfile.mkdtemp(prefix="hps_")
        ps_path = _train_and_deploy(archs, args.train_steps, args.batch,
                                    deploy_dir, args.cache_capacity,
                                    payload_dtype=args.payload_dtype
                                    or "f32", smoke=args.smoke)
        print(f"deployment bundle: {deploy_dir}")
        payload_override = None          # the bundle already carries it
    else:
        payload_override = args.payload_dtype

    _serve_bundle(ps_path, args.requests, args.batch,
                  sanitize=args.sanitize,
                  payload_dtype=payload_override)


if __name__ == "__main__":
    main()
