#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: train -> deploy -> serve.

  python chip_smoke.py               # one chip: dcn-criteo, full width
  python chip_smoke.py --chips 4     # four chips: model-parallel
                                     # dlrm-criteo fit + striped L1
  python chip_smoke.py --smoke       # CPU rehearsal at reduced size
  python chip_smoke.py --smoke --chips 4   # ... on 4 virtual CPU devices

One chip: ``dcn-criteo`` at its published widths (26 Criteo tables,
33.76M rows at dim 16, 6 cross layers, a 1024-1024 deep tower) goes
through the user entry points — graph API ``build_model`` -> ``compile``
-> ``fit`` -> ``deploy`` -> ``launch.serve.build_server_from_config`` ->
``InferenceServer.submit`` — once per L1 payload dtype (f32, int8, f16).
Served f32 predictions are checked against the pure-numpy float32
executor of ``repro.export`` (no HPS, no kernels), the f32 pooled lookup
bit for bit against the table rows, and int8/f16 against the f32 rebuild.

Four chips: ``dlrm-criteo`` at full width (17.3 GB of f32 tables) trains
on a 2x2 mesh, then a dcn-criteo bundle deployed with 4 cache stripes is
served with its stripes on 4 devices and compared bit for bit with the
same bundle served from one payload.

Everything runs in this one process (a chip belongs to one process).
Without ``--smoke`` the script exits non-zero unless JAX sees TPU chips;
``--smoke`` pins JAX to the CPU and never reports a TPU. The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: max-abs deviation of served f32 probabilities from the float32 numpy
#: reference. The served dense tower runs its matmuls on bf16 operands
#: (``Solver.mixed_precision``): each operand rounds at 2**-9 relative,
#: which moves a post-sigmoid probability by about 1e-3 per layer; 2e-2
#: is the bound the repo's own bf16-vs-f32 parity tests hold.
F32_REF_TOL = 2e-2

SEED = 0        #: weights, synthetic training data and requests
STEPS = 5       #: fit steps; the first one compiles
REQUESTS = 5    #: measured requests per payload dtype


def _parse(argv):
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the train->deploy->serve path on one chip; "
                         "4: model-parallel training and the striped L1 "
                         "across four chips, and nothing else")
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse on the CPU at reduced size")
    return ap.parse_args(argv)


def _sizes(smoke: bool) -> dict:
    if smoke:
        return {"batch": 256, "rows": 64, "cache": 256}
    return {"batch": 4096, "rows": 1024, "cache": 1_048_576}


def _get(handle, timeout: float = 900.0):
    """A served request's rows; a failed group delivers its exception,
    which is raised here."""
    out = handle.get(timeout=timeout)
    if isinstance(out, BaseException):
        raise out
    return out


def _peak_bytes(devices) -> str:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if any(p is None for p in peaks):
        return "not reported by this backend"
    return " ".join(str(p) for p in peaks)


def _train(recipe, args, sizes, *, mesh_shape=None):
    """Graph API front door: build_model -> compile -> fit."""
    from repro.api import DataReaderParams, Solver
    m = recipe.build_model(
        smoke=args.smoke,
        solver=Solver(batch_size=sizes["batch"], lr=1e-3, seed=SEED,
                      mesh_shape=mesh_shape),
        reader=DataReaderParams(num_dense_features=13, seed=SEED))
    m.compile()
    cfg = m.cfg
    t0 = time.perf_counter()
    hist = m.fit(steps=STEPS)
    total = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    if len(losses) != STEPS or \
            not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{m.name}: non-finite or missing losses "
                           f"{losses}")
    steady = sorted(h["time"] for h in hist[1:])
    step_s = steady[len(steady) // 2] if steady else float("nan")
    print(f"[train] {m.name}: {cfg.num_tables} tables, "
          f"{sum(t.vocab_size for t in cfg.tables)} rows, dim "
          f"{cfg.embedding_dim}, batch {sizes['batch']}, mesh "
          f"{dict(m.mesh.shape)}", flush=True)
    print(f"[train] fit {STEPS} steps in {total:.2f}s; first step "
          f"(trace+compile+run) {hist[0]['time']:.2f}s; compile about "
          f"{hist[0]['time'] - step_s:.2f}s; median step {step_s:.4f}s",
          flush=True)
    print(f"[train] losses {losses}", flush=True)
    return m


def _requests(cfg, sizes):
    from repro.data.synthetic import SyntheticCTR
    data = SyntheticCTR(cfg, sizes["rows"], seed=SEED + 1)
    return [data.batch(i) for i in range(REQUESTS + 1)]


def _serve(ps_path, reqs, *, payload_dtype=None):
    """Rebuild the server from ps.json alone and push the requests
    through ``submit``; the first one (compiles) is off the clock.
    Returns ``(server, predictions)``; the caller closes the server."""
    from repro.analysis import HotPathMonitor
    from repro.launch.serve import build_server_from_config
    server, _ = build_server_from_config(ps_path,
                                         payload_dtype=payload_dtype)
    server.start()
    try:
        _get(server.submit(reqs[0]["dense"], reqs[0]["cat"]))
        server.reset_latencies()
        with HotPathMonitor("chip-smoke") as mon:
            handles = [server.submit(r["dense"], r["cat"])
                       for r in reqs[1:]]
            preds = [_get(h) for h in handles]
        for p, r in zip(preds, reqs[1:]):
            if p.shape != (r["dense"].shape[0],) or \
                    not np.isfinite(p).all():
                raise RuntimeError(f"bad prediction batch: shape "
                                   f"{p.shape}")
    except BaseException:
        _close(server)
        raise
    s = mon.summary()
    print(f"[serve] {s['compiles']} XLA compile(s), "
          f"{s['compile_secs']:.2f}s, during the measured requests",
          flush=True)
    return server, np.concatenate(preds)


def _close(server):
    server.close()
    server.hps.close()


def _serving_program_text(server, rows: int) -> str:
    """The lowered pooled-gather dispatch the server runs, over its live
    payload snapshots."""
    import jax.numpy as jnp
    from repro.core.hps.hps import _pooled_stack
    hps = server.hps
    payloads = tuple(hps.caches[t.name].payload for t in hps.tables)
    slots = tuple(jnp.zeros((rows, t.hotness), jnp.int32)
                  for t in hps.tables)
    combiners = tuple("mean" if t.combiner == "mean" else "sum"
                      for t in hps.tables)
    return _pooled_stack.lower(payloads, slots, combiners,
                               shards=hps.cache_shards,
                               mesh=hps.cache_mesh).as_text()


def _l1_hit_rate(server) -> float:
    return float(np.mean(list(
        server.hps.stats()["l1_hit_rate"].values())))


def _pooled_reference(weights, tables, cat) -> np.ndarray:
    """Sum-pooled rows straight from the exported tables: [B, T, D]."""
    out = []
    for ti, t in enumerate(tables):
        ids = cat[:, ti, :]
        rows = weights[f"table/{t.name}"][np.clip(ids, 0, None)]
        out.append((rows * (ids >= 0)[..., None]).sum(axis=1))
    return np.stack(out, axis=1).astype(np.float32)


def one_chip(args, devices) -> None:
    from repro.configs import dcn_criteo
    from repro.export import export_recsys, load_exported, run_exported
    from repro.launch.serve import _PAYLOAD_TOL

    sizes = _sizes(args.smoke)
    m = _train(dcn_criteo, args, sizes)
    print(f"[train] peak_bytes_in_use {_peak_bytes(devices[:1])}",
          flush=True)
    reqs = _requests(m.cfg, sizes)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        bundle = os.path.join(tmp, "bundle")
        t0 = time.perf_counter()
        m.deploy(bundle, cache_capacity=sizes["cache"],
                 max_batch=sizes["rows"])
        ps_path = os.path.join(bundle, "ps.json")
        l1_rows = sum(min(sizes["cache"], t.vocab_size)
                      for t in m.cfg.tables)
        print(f"[deploy] bundle written in {time.perf_counter() - t0:.1f}s"
              f" ({sorted(os.listdir(bundle))}); L1 {l1_rows} rows at "
              f"cache_capacity {sizes['cache']} per table", flush=True)

        # the float32 reference: the portable export's numpy executor
        export_recsys(m.model, m.params, os.path.join(tmp, "export"),
                      m.name)
        graph, weights = load_exported(os.path.join(tmp, "export"))
        want = np.concatenate([run_exported(graph, weights, r)
                               for r in reqs[1:]])

        served = {}
        for dtype in ("f32", "int8", "f16"):
            server, preds = _serve(ps_path, reqs, payload_dtype=dtype)
            try:
                pct = server.latency_percentiles()
                hit = _l1_hit_rate(server)
                text = _serving_program_text(server, sizes["rows"])
                kernel = "tpu_custom_call" in text
                if dtype == "f32":
                    got = np.asarray(server.hps.lookup(reqs[1]["cat"]))
                    exact = np.array_equal(got, _pooled_reference(
                        weights, m.cfg.tables, reqs[1]["cat"]))
            finally:
                _close(server)
            served[dtype] = preds
            print(f"[serve] {dtype}: rebuilt from ps.json, "
                  f"{REQUESTS} x {sizes['rows']}-row requests via "
                  f"submit; p50 {pct['p50']:.2f} ms p99 "
                  f"{pct['p99']:.2f} ms; L1 hit rate {hit:.4f}; "
                  f"tpu_custom_call in serving program: {kernel}",
                  flush=True)
            if not args.smoke and not kernel:
                raise RuntimeError(f"{dtype}: the serving program has no "
                                   "tpu_custom_call (gather not compiled)")
            if dtype == "f32":
                print(f"[serve] f32 pooled lookup bit-exact against the "
                      f"table rows: {exact}", flush=True)
                if not exact:
                    raise RuntimeError("f32 pooled lookup is not "
                                       "bit-exact")
                dev = float(np.abs(preds - want).max())
                print(f"[check] f32 served vs float32 numpy reference: "
                      f"max abs dev {dev:.6f} (tolerance {F32_REF_TOL})",
                      flush=True)
                if dev > F32_REF_TOL:
                    raise RuntimeError("f32 predictions off the "
                                       "reference")
            else:
                dev = float(np.abs(preds - served["f32"]).max())
                tol = _PAYLOAD_TOL[dtype]
                print(f"[check] {dtype} vs f32 rebuild: max abs dev "
                      f"{dev:.6f} (tolerance {tol})", flush=True)
                if dev > tol:
                    raise RuntimeError(f"{dtype} predictions off f32")


def four_chips(args, devices) -> None:
    from repro.configs import dcn_criteo, dlrm_criteo

    sizes = _sizes(args.smoke)
    # -- model-parallel training at full width ------------------------------
    m = _train(dlrm_criteo, args, sizes, mesh_shape=(2, 2))
    emb = m.params["embedding"]
    sharded = sorted(k for k, v in emb.items()
                     if len(v.sharding.device_set) == len(devices)
                     and not v.sharding.is_fully_replicated)
    print(f"[train] embedding groups {sorted(emb)}; sharded over "
          f"{len(devices)} devices: {sharded}; per-device "
          f"peak_bytes_in_use {_peak_bytes(devices)}", flush=True)
    if not args.smoke and not sharded:   # smoke tables all replicate
        raise RuntimeError("no embedding group is sharded over the "
                           "devices")
    del m, emb
    gc.collect()

    # -- striped L1 over four devices vs one payload -------------------------
    m = _train(dcn_criteo, args, sizes, mesh_shape=(1, 1))
    reqs = _requests(m.cfg, sizes)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        bundle = os.path.join(tmp, "bundle")
        m.deploy(bundle, cache_capacity=sizes["cache"], cache_shards=4,
                 max_batch=sizes["rows"])
        ps4 = os.path.join(bundle, "ps.json")
        with open(ps4) as f:     # the same bundle, served from 1 stripe
            cfg = json.load(f)
        cfg["cache_shards"] = 1
        ps1 = os.path.join(bundle, "ps_one_stripe.json")
        with open(ps1, "w") as f:
            json.dump(cfg, f)

        outs, pooled = {}, {}
        for name, ps in (("shards=4", ps4), ("shards=1", ps1)):
            server, outs[name] = _serve(ps, reqs)
            try:
                pct = server.latency_percentiles()
                pooled[name] = np.asarray(
                    server.hps.lookup(reqs[1]["cat"]))
                placed = {len(server.hps.caches[t.name].payload[0]
                              .sharding.device_set)
                          for t in server.hps.tables}
                kernel = "tpu_custom_call" in _serving_program_text(
                    server, sizes["rows"])
            finally:
                _close(server)
            print(f"[serve] {name}: payload stripes on {placed} "
                  f"device(s) per table; p50 {pct['p50']:.2f} ms; "
                  f"tpu_custom_call in serving program: {kernel}",
                  flush=True)
            want = {len(devices)} if name == "shards=4" else {1}
            if placed != want:
                raise RuntimeError(f"{name}: stripes on {placed} devices, "
                                   f"expected {want}")
            if not args.smoke and not kernel:
                raise RuntimeError(f"{name}: no tpu_custom_call in the "
                                   "serving program")
        exact = np.array_equal(outs["shards=4"], outs["shards=1"]) and \
            np.array_equal(pooled["shards=4"], pooled["shards=1"])
        print(f"[check] striped L1 on {len(devices)} devices bit-exact "
              f"against shards=1 (predictions and pooled lookup): {exact}",
              flush=True)
        if not exact:
            raise RuntimeError("striped L1 differs from shards=1")


def main(argv=None) -> int:
    args = _parse(argv)
    if args.smoke:               # rehearsal: never touch an accelerator
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if args.smoke:
        if len(devices) < args.chips:
            print(f"chip_smoke: --smoke --chips {args.chips} sees "
                  f"{len(devices)} CPU device(s)", file=sys.stderr)
            return 1
    else:
        if platform != "tpu" or len(devices) < args.chips:
            print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX sees "
                  f"{len(devices)} {platform} device(s)", file=sys.stderr)
            return 1
        print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"devices: {len(devices)} x {devices[0].device_kind} "
          f"({platform})", flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args, devices)
    else:
        four_chips(args, devices)
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
