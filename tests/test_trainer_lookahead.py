"""The trainer's input lookahead: step s+1's batch is built and put while
step s runs, with the same batches reaching the same step program in the
same order as a plain serial loop, no batch built for a step the call does
not run, and replay after a failure on each step's own batch."""
import jax
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import RECSYS_ARCHS, reduce_recsys_for_smoke
from repro.core.embedding.strategies import EXCHANGE_COUNTERS
from repro.data.pipeline import put_batch
from repro.data.synthetic import SyntheticCTR
from repro.launch.mesh import make_test_mesh
from repro.models.recsys.model import RecsysModel
from repro.train.trainer import Trainer

BATCH = 16


@pytest.fixture(scope="module")
def smoke():
    cfg = reduce_recsys_for_smoke(RECSYS_ARCHS["dlrm-criteo"])
    mesh = make_test_mesh((1, 1))
    model = RecsysModel(cfg, mesh, global_batch=BATCH)
    return cfg, mesh, model


class Recorder:
    """``data_fn`` that records the steps it was asked for."""

    def __init__(self, cfg):
        self.source = SyntheticCTR(cfg, BATCH)
        self.calls = []

    def __call__(self, step):
        self.calls.append(step)
        return self.source.batch(step)


def _trainer(smoke, data_fn, **kw):
    cfg, mesh, model = smoke
    return Trainer(model, TrainConfig(learning_rate=1e-2), mesh, data_fn,
                   **kw)


def _record_batches(tr):
    """Wrap the step so that each dispatch records its batch's labels."""
    seen = []
    step = tr._step

    def wrapped(params, opt_state, batch):
        seen.append(np.asarray(batch["label"]))
        return step(params, opt_state, batch)

    tr._step = wrapped
    return seen


def test_matches_a_serial_loop_bit_for_bit(smoke):
    cfg, mesh, _ = smoke
    data = SyntheticCTR(cfg, BATCH)
    tr = _trainer(smoke, data.batch)
    n = 6
    with mesh:
        params, opt = tr.init_state(0)
        want = []
        for s in range(n):
            batch = put_batch(data.batch(s), mesh)
            params, opt, metrics = tr._step(params, opt, batch)
            want.append(float(jax.device_get(metrics["loss"])))
        out = tr.train(n)
    assert [h["loss"] for h in out["history"]] == want
    got_leaves = jax.tree_util.tree_leaves(out["params"])
    want_leaves = jax.tree_util.tree_leaves(params)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("num_steps", [0, 1, 5])
def test_data_fn_once_per_step_in_order(smoke, num_steps):
    cfg, mesh, _ = smoke
    rec = Recorder(cfg)
    tr = _trainer(smoke, rec)
    with mesh:
        tr.train(num_steps)
    assert rec.calls == list(range(num_steps))


def test_resumed_call_builds_only_its_own_steps(smoke, tmp_path):
    cfg, mesh, _ = smoke
    rec = Recorder(cfg)
    with mesh:
        _trainer(smoke, rec, ckpt_dir=str(tmp_path)).train(4)
        rec.calls.clear()
        out = _trainer(smoke, rec, ckpt_dir=str(tmp_path)).train(7)
    assert [h["step"] for h in out["history"]] == [4, 5, 6]
    assert rec.calls == [4, 5, 6]


@pytest.mark.parametrize("fail_at", [7, 8])
def test_replay_after_failure_runs_each_step_on_its_batch(smoke, tmp_path,
                                                          fail_at):
    """Checkpoints every third step, the one at step 6 finished before
    the failure, so both failures restart at step 7: the one at 7 runs on
    the batch held for it, the one at 8 drops the held batch of step 8
    and builds anew."""
    cfg, mesh, _ = smoke
    n = 12
    rec = Recorder(cfg)
    tr = _trainer(smoke, rec, ckpt_dir=str(tmp_path), ckpt_interval=3)
    seen = _record_batches(tr)
    armed = [True]

    def inject(step):
        if step == fail_at and armed[0]:
            armed[0] = False
            tr.saver.wait()
            raise RuntimeError("injected node failure")

    tr.failure_injector = inject
    with mesh:
        out = tr.train(n)
    steps = [h["step"] for h in out["history"]]
    restart = 7
    assert steps == list(range(fail_at)) + list(range(restart, n))
    # every dispatched step ran on data_fn(step)'s batch
    assert len(seen) == len(steps)
    for s, labels in zip(steps, seen):
        np.testing.assert_array_equal(labels, rec.source.batch(s)["label"])
    # built up to the failing step's batch, then from the restart on
    # (a restart at the failing step keeps its held batch)
    after = range(fail_at + 1 if restart == fail_at else restart, n)
    assert rec.calls == list(range(fail_at + 1)) + list(after)
    in_line = 1 if restart == fail_at else 2
    assert tr.input_counters() == {"batches_ahead": len(steps) - in_line,
                                   "batches_in_line": in_line}
    clean = _trainer(smoke, SyntheticCTR(cfg, BATCH).batch)
    with mesh:
        want = clean.train(n)["history"][-1]["loss"]
    assert out["history"][-1]["loss"] == want


def test_input_counters_sum_over_calls(smoke):
    cfg, mesh, _ = smoke
    tr = _trainer(smoke, SyntheticCTR(cfg, BATCH).batch)
    assert tr.input_counters() == {"batches_ahead": 0, "batches_in_line": 0}
    with mesh:
        tr.train(4)
        assert tr.input_counters() == {"batches_ahead": 3,
                                       "batches_in_line": 1}
        tr.train(3)
    assert tr.input_counters() == {"batches_ahead": 5, "batches_in_line": 2}
    # the exchange's counters stay apart
    assert set(tr.counters()) == set(EXCHANGE_COUNTERS)
