"""Training cells: ``Model.fit`` on the reader's synthetic source, the
first steps checked against the reference, then whole steps in the window.

Set-up builds one trainer: the configuration's graph-API recipe holding the
benchmark's weights, ``fit(steps=0)`` (the trainer with its compiled step
and optimizer state), then the first ``FIRST_STEPS`` steps through
``Trainer.train`` on the reader's batches 0, 1, 2, ... The window calls the
same ``Trainer.train`` on the same state, on the batches that follow.

What is compared with the reference: the first step's loss; every leaf's
first gradient as the optimizer gets it, read back from its state after
one step (AdamW's first moment is ``(1 - beta1) g``; row-wise AdaGrad's
accumulator is ``mean(g^2)`` per row); every leaf's change over the first
steps. Leaves are the dense parameters and the tables.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import refcommon
import serving
import weights

#: steps run in set-up and compared with the reference
FIRST_STEPS = 3

class Training:

    def __init__(self, run):
        self.run = run
        cfg = run.config
        tc = cfg["training"]
        self.batch = tc["batch"]
        self.model, self.place = serving.build_model(
            run, batch=tc["batch"], mesh=tc["mesh"], reader_seed=run.seed,
            zipf_a=run.mix["zipf_a"])
        self.ref = refcommon.load_reference(run.cell.config["name"])
        self.spec = self.ref.param_spec(cfg)
        m = self.model
        m.fit(steps=0)
        self.trainer = m._trainer          # fit's trainer: step + state
        self.reader = self.trainer.data_fn
        self.next_batch = 0
        self.params, self.opt = m.params, None
        m._params = None                   # the trainer owns the state now
        self.readings = self._first_steps(FIRST_STEPS)

    def steps(self, n: int) -> List[float]:
        """``n`` more steps through ``Trainer.train``, on the batches that
        follow; returns their losses."""
        off = self.next_batch

        def feed(s):
            with jax.profiler.TraceAnnotation("bench/train.data"):
                return self.reader(off + s)

        self.trainer.data_fn = feed
        with jax.profiler.TraceAnnotation("bench/train.call"):
            out = self.trainer.train(n, initial_state=(self.params,
                                                       self.opt))
        self.params, self.opt = out["params"], out["opt_state"]
        self.next_batch += n
        return [h["loss"] for h in out["history"]]

    def _first_steps(self, k: int) -> Dict:
        cfg = self.run.config
        tc = cfg["training"]
        tabs = refcommon.tables(cfg)
        place = self.place
        losses = self.steps(1)
        grad = {}
        for path, mu in weights.flatten(self.opt["dense"]["mu"]).items():
            grad[path] = float(jnp.linalg.norm(mu)) / (1 - tc["beta1"])
        acc = place.embedding(self.opt["sparse"]["acc"])
        sums = place.table_sums(lambda a, tm, rm: a, acc)
        for e, s in zip(tabs, sums):
            grad[e["name"]] = float(np.sqrt(e["dim"] * s))
        losses += self.steps(k - 1)
        lo, hi = weights.seed_words(self.run.seed)
        p0 = weights.flatten(weights.dense_params(lo, hi, self.spec))
        change = {}
        for path, p in weights.flatten(place.dense(self.params)).items():
            change[path] = float(jnp.linalg.norm(p - p0[path]))
        sq = place.change_sq(place.embedding(self.params), self.run.seed)
        for e, s in zip(tabs, sq):
            change[e["name"]] = float(np.sqrt(s))
        return {"loss": losses, "grad": grad, "change": change}

    def free(self) -> None:
        self.params = self.opt = None
        self.trainer = None
        self.model = None

    def check(self) -> Dict[str, Dict]:
        """The reference's first steps on the reader's first batches."""
        run = self.run
        cfg = run.config
        k = len(self.readings["loss"])
        batches = [self.reader(s) for s in range(k)]
        want = refcommon.train_steps(self.ref, cfg, run.seed, batches)
        return compare(self.readings, want, cfg["limits"])


def compare(got: Dict, want: Dict, limits: Dict) -> Dict[str, Dict]:
    """The first step's loss (the later steps' losses swing with the
    trajectory: Adam's first updates amplify rounding from seed to seed),
    the worst leaf's first gradient, the worst moving leaf's change. Each
    number the configuration gives a limit is compared; the others are
    returned with the limit None and only reported."""
    keep = refcommon.moving_leaves(want["grad"])
    values = {
        "loss_rel_gap": abs(got["loss"][0] - want["loss"][0])
        / abs(want["loss"][0]),
        "grad_gap": refcommon.leaf_gap(got["grad"], want["grad"]),
        "change_gap": refcommon.leaf_gap(got["change"], want["change"],
                                         keep),
    }
    return {k: {"value": v, "limit": limits.get(k)}
            for k, v in values.items()}
