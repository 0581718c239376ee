"""The embedding exchange's counters on 4 virtual CPU devices (a 2x2
mesh): ids routed, ids dropped past their owner's bucket, the fullest
bucket's load; summed by the train step and by ``Trainer.counters()``.

Hand counts: a ``[16, 2, 1]`` batch of two 64-row DISTRIBUTED tables over
the bucketed all-to-all gives each device ``m = 8 * 2 = 16`` ids, a mean
bucket of ``16 / 4 = 4`` and, at capacity factor 2, buckets of 8. Every
id a multiple of 4 sends all 16 to owner 0: 8 drop on each device, 32 in
all, and the peak load is 16 / 4 = 4. Ids ``b % 64`` fill every bucket
with 4: nothing drops, peak 1. With the batch replicated over ``model``
each id is counted on both of its devices: 64 ids a step.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BODY = r"""
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import DISTRIBUTED, TrainConfig
from repro.configs.registry import RECSYS_ARCHS, reduce_recsys_for_smoke
from repro.launch.mesh import make_test_mesh
from repro.models.recsys.model import RecsysModel
from repro.core.embedding.strategies import EXCHANGE_COUNTERS, merge_stats
from repro.train.train_step import (build_manual_train_step,
                                    build_train_step, init_opt_state,
                                    jit_train_step)
from repro.train.trainer import Trainer

B = 16
base = reduce_recsys_for_smoke(RECSYS_ARCHS["dlrm-criteo"])
cfg = dataclasses.replace(base, tables=tuple(
    dataclasses.replace(t, vocab_size=64, strategy=DISTRIBUTED)
    for t in base.tables[:2]))
mesh = make_test_mesh((2, 2))
rng = np.random.default_rng(0)

def batch(ids):
    return {"dense": rng.standard_normal((B, cfg.num_dense_features)
                                         ).astype(np.float32),
            "cat": np.stack([ids, ids], axis=1)[:, :, None].astype(np.int32),
            "label": rng.integers(0, 2, B).astype(np.float32)}

balanced = batch(np.arange(B) % 64)
overflow = batch(4 * np.arange(B))
out = {}
host = lambda s: {k: int(v) if v.dtype.kind == "i" else float(v)
                  for k, v in jax.device_get(s).items()}
with mesh:
    model = RecsysModel(cfg, mesh, global_batch=B, comm="all_to_all")
    coll = model.embedding
    params = jax.jit(model.init, out_shardings=model.param_shardings())(
        jax.random.PRNGKey(0))
    for name, b in (("balanced", balanced), ("overflow", overflow)):
        ids = jnp.asarray(b["cat"])
        got, stats = jax.jit(coll.lookup_with_stats)(params["embedding"],
                                                    ids)
        want = coll.lookup_reference(params["embedding"], ids)
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        wrong = ~np.isclose(got, want, atol=1e-2).all(axis=-1)
        out[name] = dict(host(stats), wrong_rows=int(wrong.sum()),
                         wrong_read_zero=bool((got[wrong] == 0).all()))

    # counters ride beside the loss without touching it: bit-identical
    # loss and gradients with and without the aux output
    b = {k: jnp.asarray(v) for k, v in overflow.items()}
    l1, g1 = jax.jit(jax.value_and_grad(model.loss_fn))(params, b)
    (l2, _), g2 = jax.jit(jax.value_and_grad(model.loss_and_stats,
                                             has_aux=True))(params, b)
    out["loss_bits_equal"] = bool(np.asarray(l1) == np.asarray(l2))
    out["grad_bits_equal"] = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))

    tcfg = TrainConfig(learning_rate=1e-2)
    steps = [balanced, overflow, balanced]
    tr = Trainer(model, tcfg, mesh, lambda s: steps[s])
    tr.train(len(steps), initial_state=(params, None))
    out["trainer"] = tr.counters()

    # the step's metrics, gspmd and manual; the scopes in its HLO
    opt = init_opt_state(params, tcfg)
    step = jit_train_step(model, tcfg, mesh)
    hlo = step.lower(params, opt, b).compile().as_text()
    out["scopes"] = {s: s in hlo for s in ("mp.exchange",
                                            "mp.sparse_update")}
    fresh = lambda: jax.jit(model.init, out_shardings=model.param_shardings()
                            )(jax.random.PRNGKey(0))
    _, _, m = step(fresh(), init_opt_state(params, tcfg), b)
    out["gspmd_step"] = host({k: v for k, v in m.items()
                              if k.startswith("exchange")})
    # the one rule, on the device and on the host: the same numbers
    two = merge_stats({k: m[k] for k in EXCHANGE_COUNTERS},
                      {k: m[k] for k in EXCHANGE_COUNTERS})
    once = host({k: m[k] for k in EXCHANGE_COUNTERS})
    out["sync_matches"] = host(two) == merge_stats(once, once, max)
    manual = jax.jit(build_manual_train_step(model, tcfg, mesh))
    _, _, m = manual(fresh(), init_opt_state(params, tcfg), b)
    out["manual_step"] = host({k: v for k, v in m.items()
                               if k.startswith("exchange")})

# one device: nothing crosses devices, the step returns no counters and
# the trainer's stay at zero
one = make_test_mesh((1, 1))
with one:
    model = RecsysModel(cfg, one, global_batch=B, comm="all_to_all")
    p1 = jax.jit(model.init)(jax.random.PRNGKey(0))
    b = {k: jnp.asarray(v) for k, v in overflow.items()}
    _, _, m = jax.jit(build_train_step(model, tcfg))(
        p1, init_opt_state(p1, tcfg), b)
    out["one_device_keys"] = sorted(m)
    tr = Trainer(model, tcfg, one, lambda s: overflow)
    tr.train(2, initial_state=(p1, None))
    out["one_device_trainer"] = tr.counters()
print("RESULT " + json.dumps(out))
"""

OVERFLOW = {"exchange_ids": 64, "exchange_dropped": 32,
            "exchange_peak_load": 4.0}


@pytest.fixture(scope="module")
def counted():
    code = ("import os\nos.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n" + BODY)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_overflow_drops_the_hand_count_and_reads_zeros(counted):
    got = counted["overflow"]
    assert {k: got[k] for k in OVERFLOW} == OVERFLOW
    # 8 ids dropped on each of a row's two model-axis devices alike: 16
    # (row, table) lookups of the global batch read zero vectors
    assert got["wrong_rows"] == 16 and got["wrong_read_zero"]


def test_balanced_batch_drops_nothing(counted):
    got = counted["balanced"]
    assert got["exchange_ids"] == 64
    assert got["exchange_dropped"] == 0 and got["wrong_rows"] == 0
    assert got["exchange_peak_load"] == 1.0


def test_trainer_counters_sum_the_steps(counted):
    # balanced, overflow, balanced
    assert counted["trainer"] == {"exchange_ids": 3 * 64,
                                  "exchange_dropped": 32,
                                  "exchange_peak_load": 4.0}


def test_counters_leave_loss_and_gradients_bit_identical(counted):
    assert counted["loss_bits_equal"] and counted["grad_bits_equal"]


def test_both_step_modes_report_them_under_their_scopes(counted):
    assert counted["gspmd_step"] == OVERFLOW
    assert counted["sync_matches"]
    assert counted["manual_step"] == OVERFLOW
    assert counted["scopes"] == {"mp.exchange": True,
                                 "mp.sparse_update": True}


def test_one_device_step_returns_no_counters(counted):
    assert counted["one_device_keys"] == ["grad_norm", "loss"]
    assert counted["one_device_trainer"] == {"exchange_ids": 0,
                                             "exchange_dropped": 0,
                                             "exchange_peak_load": 0}
