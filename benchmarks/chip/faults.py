"""Faults planted in the timed path, to show that ``correct`` catches them.

Each is planted before a run starts (the tests run a cell with one planted
and expect ``correct`` false; ``readings.py`` reads the compared numbers of
planted runs on the chip):

* ``answer_altered`` — every served group has its first prediction
  replaced (flipped across 0.5, by at least 0.5), where it is delivered;
* ``state_unchanged`` — the train step returns its parameters and
  optimizer state as it got them;
* ``half_batch`` — the train step sees only the first half of each batch,
  and so takes its mean over that half;
* ``exchange_left_out`` — the embedding exchange between chips is skipped:
  all-to-all returns what it was given, and the reduce-scatter keeps each
  chip's own partial sums;
* ``control`` — the reference, computed one precision below the
  configuration's (float8 e4m3 where it computes in bfloat16), put in the
  program's place: the first training steps' readings, or every delivered
  answer, are the control's. The run's own comparison then judges it.
"""
from __future__ import annotations


def _answer_altered():
    import numpy as np
    from repro.serve import server
    real = server.InferenceServer._deliver

    def deliver(self, reqs, preds):
        preds = np.array(preds, copy=True)
        if len(preds):
            preds[0] = np.float32(0.0 if preds[0] > 0.5 else 1.0)
        return real(self, reqs, preds)

    server.InferenceServer._deliver = deliver


def _wrap_step(make):
    """Replace the train step every trainer builds by ``make(step)``."""
    from repro.train import train_step, trainer
    real = train_step.build_train_step

    def build(model, tcfg):
        return make(real(model, tcfg))

    train_step.build_train_step = build
    trainer.build_train_step = build


def _state_unchanged():
    def make(step):
        def fn(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return fn
    _wrap_step(make)


def _half_batch():
    def make(step):
        def fn(params, opt_state, batch):
            n = batch["label"].shape[0] // 2
            return step(params, opt_state,
                        {k: v[:n] for k, v in batch.items()})
        return fn
    _wrap_step(make)


def _exchange_left_out():
    import jax

    def all_to_all(x, axis_name, split_axis, concat_axis, **kw):
        return x

    def psum_scatter(x, axis_name, *, scatter_dimension=0, tiled=False,
                     **kw):
        n = jax.lax.psum(1, axis_name)
        k = x.shape[scatter_dimension] // n
        own = jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(axis_name) * k, k, axis=scatter_dimension)
        return own if tiled else own.squeeze(scatter_dimension)

    jax.lax.all_to_all = all_to_all
    jax.lax.psum_scatter = psum_scatter


def _control():
    import numpy as np
    import refcommon
    import serving
    import training
    first_steps = training.Training._first_steps

    def control_steps(self, k):
        first_steps(self, k)          # the window goes on from these steps
        batches = [self.reader(s) for s in range(k)]
        return refcommon.train_steps(self.ref, self.run.config,
                                     self.run.seed, batches, "fp8")

    check = serving.Serving.check

    def control_check(self, done, failed):
        run = self.run
        made = [self.requests.make(i) for i, _ in done]
        if made:
            got = refcommon.predictions(
                refcommon.load_reference(run.config["name"]), run.config,
                run.seed, np.concatenate([d for d, _ in made]),
                np.concatenate([c[:, :, 0] for _, c in made]), "fp8")
            ends = np.cumsum([len(d) for d, _ in made])[:-1]
            done = list(zip([i for i, _ in done], np.split(got, ends)))
        return check(self, done, failed)

    training.Training._first_steps = control_steps
    serving.Serving.check = control_check


FAULTS = {
    "answer_altered": _answer_altered,
    "state_unchanged": _state_unchanged,
    "half_batch": _half_batch,
    "exchange_left_out": _exchange_left_out,
    "control": _control,
}


def plant(name: str) -> None:
    FAULTS[name]()


def prelude(name: str) -> str:
    """Code a child process runs before the cell, planting ``name``."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    return (f"sys.path.insert(0, {here!r}); import faults; "
            f"faults.plant({name!r})")
