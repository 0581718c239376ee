"""Fault-tolerant training loop.

Production behaviours implemented (and unit-tested):
  * checkpoint/restart — async atomic checkpoints every ``ckpt_interval``;
    on (injected or real) step failure the trainer restores the newest
    valid checkpoint and *replays* — the data pipeline is stateless
    (``batch(step)``), so replay is deterministic.
  * straggler mitigation — per-step wall-time watchdog: steps slower than
    ``straggler_factor ×`` the running median are counted and surfaced in
    metrics (at pod scale this signal feeds the scheduler; here it is the
    bookkeeping + hook).
  * elastic scaling — checkpoints store logical (mesh-independent) arrays;
    ``Trainer.restore`` re-imports them for whatever mesh it runs on.

Input lookahead: each step dispatches on a batch already on the device,
then builds and puts the next step's batch while the device runs, then
waits for its loss. Only the first step of a call (or of a replay that
moves off the held batch) builds its batch with nothing in flight; no
batch is built for a step the call does not run.

``counters()`` combines the embedding exchange's counters over the steps
since the trainer was built, read with the loss at each step's sync;
``input_counters()`` counts the steps whose batch was built ahead.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro import tracing
from repro.configs.base import TrainConfig
from repro.core.embedding.strategies import EXCHANGE_COUNTERS, merge_stats
from repro.data.pipeline import put_batch
from repro.train import checkpoint as ckpt_lib
from repro.train.train_step import (
    build_manual_train_step, build_train_step, init_opt_state,
    jit_train_step,
)


class Trainer:

    def __init__(self, model, tcfg: TrainConfig, mesh, data_fn: Callable,
                 *, ckpt_dir: Optional[str] = None, ckpt_interval: int = 50,
                 mode: str = "gspmd", straggler_factor: float = 3.0):
        self.model = model
        self.tcfg = tcfg
        self.mesh = mesh
        self.data_fn = data_fn            # step -> host batch dict
        self.ckpt_dir = ckpt_dir
        self.ckpt_interval = ckpt_interval
        self.saver = ckpt_lib.AsyncSaver(ckpt_dir) if ckpt_dir else None
        self.straggler_factor = straggler_factor
        self.step_times: List[float] = []
        self.stragglers = 0
        self._counters: Dict[str, float] = dict.fromkeys(EXCHANGE_COUNTERS,
                                                         0)
        self._input = {"batches_ahead": 0, "batches_in_line": 0}
        n_dev = int(np.prod(mesh.devices.shape))
        #: model-parallel placement: on a real multi-device mesh the
        #: params live in their per-strategy shardings and the step is
        #: jitted with explicit in/out shardings, so the embedding
        #: collectives actually span devices (ROADMAP item: MP training
        #: through the graph API)
        self._shardings = model.param_shardings() \
            if n_dev > 1 and hasattr(model, "param_shardings") else None
        if mode == "manual":
            step_fn = build_manual_train_step(model, tcfg, mesh)
            self._step = jax.jit(step_fn, donate_argnums=(0, 1))
        elif self._shardings is not None:
            self._step = jit_train_step(model, tcfg, mesh)
        else:
            step_fn = build_train_step(model, tcfg)
            self._step = jax.jit(step_fn, donate_argnums=(0, 1))
        #: test hook: callable(step) that may raise to simulate a failure
        self.failure_injector: Optional[Callable[[int], None]] = None

    # -- state ----------------------------------------------------------------

    def _place(self, params):
        """Move params into their MP shardings (no-op on one device)."""
        if self._shardings is None:
            return params
        return jax.device_put(params, self._shardings)

    def init_state(self, seed: int = 0):
        # one compiled program (eager init dispatches, and on a TPU
        # compiles, op by op); sharded, each device draws only its own
        # rows of every table
        params = jax.jit(self.model.init, out_shardings=self._shardings)(
            jax.random.PRNGKey(seed))
        opt_state = init_opt_state(params, self.tcfg)
        return params, opt_state

    def _export(self, params):
        from repro.models.recsys.model import export_logical_params
        return export_logical_params(self.model, params)

    def _import(self, params):
        from repro.models.recsys.model import import_logical_params
        return import_logical_params(self.model, params)

    def save(self, step: int, params, opt_state):
        if self.saver is None:
            return
        tree = {"params": self._export(params), "opt": opt_state}
        self.saver.save(step, tree, meta={"step": step})

    def restore(self, params_template, opt_template):
        """Load newest checkpoint; returns (step, params, opt_state) or None.

        Templates may be real arrays OR ShapeDtypeStructs — only the tree
        structure is used (safe even after buffer donation).
        """
        if self.ckpt_dir is None:
            return None
        step = ckpt_lib.latest_step(self.ckpt_dir)
        if step is None:
            return None
        flat, manifest = ckpt_lib.load(self.ckpt_dir, step)
        template = {
            "params": jax.eval_shape(self._export, params_template),
            "opt": opt_template,
        }
        tree = ckpt_lib.unflatten_like(template, flat)
        params = self._place(self._import(tree["params"]))
        return step, params, tree["opt"]

    # -- loop -----------------------------------------------------------------

    def train(self, num_steps: int, *, seed: int = 0,
              log_every: int = 0, initial_state=None) -> Dict:
        """``initial_state=(params, opt_state)`` seeds the loop with
        already-loaded weights (``opt_state=None`` re-inits the
        optimizer) — the ``Model.load`` resume path. A newer checkpoint
        in ``ckpt_dir`` still takes precedence."""
        if initial_state is not None:
            params, opt_state = initial_state
            params = self._place(params)
            if opt_state is None:
                opt_state = init_opt_state(params, self.tcfg)
        else:
            params, opt_state = self.init_state(seed)
        start = 0
        restored = self.restore(params, opt_state)
        if restored is not None:
            start, params, opt_state = restored
            start += 1
        history = []
        step = start
        ahead = None        # (step, device batch) built while a step ran
        while step < num_steps:
            try:
                with tracing.span("train.step", step=step):
                    if self.failure_injector is not None:
                        self.failure_injector(step)
                    t0 = time.perf_counter()
                    if ahead is not None and ahead[0] == step:
                        batch, key = ahead[1], "batches_ahead"
                    else:
                        batch, key = self._build(step), "batches_in_line"
                    with tracing.span("train.dispatch", step=step):
                        params, opt_state, metrics = self._step(
                            params, opt_state, batch)
                    self._input[key] += 1
                    # the next step's batch, while this one runs
                    if step + 1 < num_steps:
                        ahead = (step + 1, self._build(step + 1))
                    with tracing.span("train.sync", step=step):
                        loss, stats = jax.device_get(
                            (metrics["loss"],
                             {k: metrics[k] for k in EXCHANGE_COUNTERS
                              if k in metrics}))
                    loss = float(loss)
                    if stats:
                        self._counters = merge_stats(
                            self._counters,
                            {k: v.item() for k, v in stats.items()}, max)
                    dt = time.perf_counter() - t0
                    self._watch_stragglers(dt)
                    history.append({"step": step, "loss": loss, "time": dt})
                    if log_every and step % log_every == 0:
                        print(f"step {step}: loss={loss:.4f} "
                              f"({dt*1e3:.1f} ms)")
                    if self.saver and step % self.ckpt_interval == 0:
                        with tracing.span("train.checkpoint", step=step):
                            self.save(step, params, opt_state)
                step += 1
            except (ckpt_lib.os.error, RuntimeError, ValueError) as e:
                # node failure path: restore + replay
                restored = self.restore(params, opt_state)
                if restored is None:
                    params, opt_state = self.init_state(seed)
                    step = 0
                else:
                    rstep, params, opt_state = restored
                    step = rstep + 1
        if self.saver:
            with tracing.span("train.checkpoint", step=num_steps - 1):
                self.save(num_steps - 1, params, opt_state)
            self.saver.wait()
        return {"params": params, "opt_state": opt_state,
                "history": history, "stragglers": self.stragglers}

    def _build(self, step: int):
        """``data_fn(step)``, put on the device in the batch's shardings."""
        with tracing.span("train.data", step=step):
            host = self.data_fn(step)
        with tracing.span("train.put_batch", step=step):
            return put_batch(host, self.mesh)

    def input_counters(self) -> Dict[str, int]:
        """Steps dispatched since the trainer was built: ``batches_ahead``
        on a batch built while the step before ran, ``batches_in_line``
        on one built with no step in flight (each call's first step, and
        the first after a restore that moves off the held batch)."""
        return dict(self._input)

    def counters(self) -> Dict[str, float]:
        """The embedding exchange's counters since the trainer was built:
        ``exchange_ids`` and ``exchange_dropped`` summed over the steps,
        ``exchange_peak_load`` the largest of any step; zeros on one
        device, where the step returns none."""
        return dict(self._counters)

    def _watch_stragglers(self, dt: float):
        if len(self.step_times) >= 5:
            med = float(np.median(self.step_times[-50:]))
            if dt > self.straggler_factor * med:
                self.stragglers += 1
        self.step_times.append(dt)
