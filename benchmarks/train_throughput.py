"""Paper table 1 analogue — DLRM training throughput.

The paper: HugeCTR on 8x A100 is 24.6x faster than PyTorch on 4x4-socket
CPU nodes. That ratio is hardware (HBM/MXU vs CPU) and cannot reproduce
on one CPU. What CAN be measured here, honestly:

  1. this module — the cost of the distribution engine itself at one
     device (framework step vs a plain-gather reference, both f32+SGD,
     both jitted): the overhead you pay when you don't need sharding;
  2. `embedding_strategies.py` (8 devices) — the paper's actual point:
     placement strategy changes step time ~4.6x at fixed work;
  3. `roofline_report.py` — the projected TPU-pod step time.

``dlrm_train.engine_overhead`` < ~1.15x is the target: the sharding
machinery (shard_map, mega-table indirection, mean-mask handling) must
be nearly free when degenerate.

The GENERIC-EXECUTOR arm: since the graph-API redesign every model's
dense net executes as a compiled ``DenseGraphProgram`` (one traced node
loop) instead of the hand-written fixed pipeline. Both lower to the
same jitted XLA computation, so ``dlrm_train.graph_overhead`` ~ 1.0x is
the regression bar; the pair of step times is persisted to
``artifacts/train_graph.json`` so a compile-path regression is visible
run over run."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Report, time_fn
from repro.configs.base import TrainConfig
from repro.configs.registry import RECSYS_ARCHS
from repro.data.synthetic import SyntheticCTR
from repro.launch.mesh import make_test_mesh
from repro.models.recsys.model import RecsysModel
from repro.train.train_step import build_train_step, init_opt_state


def _shrink(cfg, vocab_cap=40000, batch=2048):
    tables = tuple(dataclasses.replace(t, vocab_size=min(t.vocab_size,
                                                         vocab_cap))
                   for t in cfg.tables)
    return dataclasses.replace(cfg, tables=tables), batch


def _naive_f32_step(cfg, mesh):
    """Reference implementation: per-table python-loop gathers, f32.

    All tables are pinned data_parallel so the naive per-table loop can
    read one replicated mega-table (the planner would otherwise shard
    the larger ones)."""
    tables = tuple(dataclasses.replace(t, strategy="data_parallel")
                   for t in cfg.tables)
    model = RecsysModel(
        dataclasses.replace(cfg, dtype="f32", tables=tables), mesh,
        global_batch=2048)

    def loss_fn(params, batch):
        # per-table loop of gathers (no mega-table, no pooling fusion)
        outs = []
        logical = model.embedding.export_logical(params["embedding"])
        mega = logical.get("dp")
        offs = model.embedding.groups["dp"].offsets
        for i, t in enumerate(cfg.tables):
            ids = batch["cat"][:, i, :]
            valid = ids >= 0
            rows = jnp.where(valid, ids + offs[i], 0)
            vecs = mega[rows] * valid[..., None]
            outs.append(vecs.sum(1))
        emb = jnp.stack(outs, axis=1)
        logits = model.apply_dense(params, batch["dense"], emb)
        from repro.models.recsys.layers import bce_with_logits
        return bce_with_logits(logits, batch["label"])

    tcfg = TrainConfig(mixed_precision=False)
    from repro.optim.optimizers import make
    opt = make("sgd", tcfg)

    def step(params, opt_state, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        p, s = opt.update(g, opt_state, params)
        return p, s, loss

    return model, jax.jit(step), opt


def run(report: Report):
    mesh = make_test_mesh((1, 1))
    cfg0 = RECSYS_ARCHS["dlrm-criteo"]
    cfg, batch_size = _shrink(cfg0)
    ds = SyntheticCTR(cfg, batch_size)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    with mesh:
        # optimized path (f32 on CPU; same SGD as the naive reference)
        model = RecsysModel(dataclasses.replace(cfg, dtype="f32"), mesh,
                            global_batch=batch_size)
        params = model.init(jax.random.PRNGKey(0))
        tcfg = TrainConfig(dense_optimizer="sgd", sparse_optimizer="sgd",
                           mixed_precision=False)
        step = jax.jit(build_train_step(model, tcfg))
        opt_state = init_opt_state(params, tcfg)

        def opt_step():
            return step(params, opt_state, batch)

        t_opt = time_fn(opt_step, iters=4)["min_s"]
        report.add("dlrm_train.optimized", t_opt,
                   f"samples_per_s={batch_size / t_opt:.0f}")

        # naive reference
        nmodel, nstep, nopt = _naive_f32_step(cfg, mesh)
        nparams = nmodel.init(jax.random.PRNGKey(0))
        nopt_state = nopt.init(nparams)

        def naive_step():
            return nstep(nparams, nopt_state, batch)

        t_naive = time_fn(naive_step, iters=4)["min_s"]
        report.add("dlrm_train.naive_f32", t_naive,
                   f"samples_per_s={batch_size / t_naive:.0f}")
        report.add("dlrm_train.engine_overhead", t_opt / t_naive,
                   f"framework_vs_plain_x={t_opt / t_naive:.2f} "
                   "(1-device degenerate case; see embedding_strategies "
                   "for the multi-device win)")

        # generic executor vs the pre-refactor fixed pipeline: same
        # model, same params/opt state/batch — only the dense forward
        # differs (compiled DenseGraphProgram vs apply_dense_reference)
        rmodel = RecsysModel(dataclasses.replace(cfg, dtype="f32"), mesh,
                             global_batch=batch_size,
                             dense_executor="reference")
        rstep = jax.jit(build_train_step(rmodel, tcfg))

        def ref_step():
            return rstep(params, opt_state, batch)

        t_ref = time_fn(ref_step, iters=4)["min_s"]
        ratio = t_opt / t_ref
        report.add("dlrm_train.compiled_graph", t_opt,
                   f"samples_per_s={batch_size / t_opt:.0f}")
        report.add("dlrm_train.fixed_pipeline", t_ref,
                   f"samples_per_s={batch_size / t_ref:.0f}")
        report.add("dlrm_train.graph_overhead", ratio,
                   f"compiled_vs_fixed_x={ratio:.2f}")
    scaling = _mp_scaling(report)
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/train_graph.json", "w") as f:
        json.dump({"batch": batch_size,
                   "compiled_graph_s": t_opt,
                   "fixed_pipeline_s": t_ref,
                   "graph_overhead_x": ratio,
                   "mp_scaling": scaling}, f, indent=1)


#: subprocess body for one mesh arm: forced host devices must be set
#: before jax imports, so each mesh size gets its own interpreter
_MP_ARM = r"""
import os
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count={n_dev}"
import json, time
import importlib
import jax
from repro.api import Solver

mod = importlib.import_module("repro.configs.dlrm_criteo")
m = mod.build_model(smoke=True, solver=Solver(
    batch_size={batch}, lr=1e-2, mesh_shape={shape}))
m.compile()
m.fit(steps=2)                       # warm the jitted sharded step
t0 = time.perf_counter()
hist = m.fit(steps={steps})
dt = (time.perf_counter() - t0) / {steps}
print("MP_ARM_RESULT " + json.dumps(
    {{"mesh": "{shape}", "devices": {n_dev}, "step_s": dt}}))
"""


def _mp_scaling(report: Report, batch: int = 512, steps: int = 8):
    """Multi-device scaling arm: the same graph-API ``fit()`` on forced
    host meshes of 1 / 2 / 4 devices. Host devices share the machine's
    cores, so the honest signal is the distribution-engine overhead per
    step staying bounded as the mesh grows — not a speedup (that needs
    real accelerators; see roofline_report for the projection)."""
    rows = []
    for shape in ((1, 1), (2, 1), (2, 2)):
        n_dev = shape[0] * shape[1]
        code = _MP_ARM.format(n_dev=n_dev, shape=shape, batch=batch,
                              steps=steps)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.setdefault("PYTHONPATH", "src")
        # virtual CPU devices by design: the child never reaches for an
        # accelerator this process may hold
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"dlrm_train.mp_{n_dev}dev arm failed:\n"
                f"{proc.stderr.strip()[-2000:]}")
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("MP_ARM_RESULT ")][-1]
        row = json.loads(line[len("MP_ARM_RESULT "):])
        rows.append(row)
        report.add(f"dlrm_train.mp_{n_dev}dev", row["step_s"],
                   f"mesh={row['mesh']} "
                   f"samples_per_s={batch / row['step_s']:.0f}")
    if len(rows) > 1:
        base = rows[0]["step_s"]
        worst = max(r["step_s"] / base for r in rows[1:])
        report.add("dlrm_train.mp_overhead", worst,
                   f"worst_mesh_vs_1dev_x={worst:.2f} (host devices "
                   "share cores; bounded overhead is the bar)")
    return rows
