"""Train-step builders.

Two distribution modes:

``gspmd``  — value_and_grad under jit with NamedShardings; XLA inserts the
             gradient all-reduce for replicated dense params and the
             embedding collectives come from the collection's shard_map.

``manual`` — the whole grad computation runs inside ONE shard_map over the
             full mesh: dense-gradient psum is explicit (so its dtype is a
             config knob — ``grad_allreduce_dtype="bf16"`` is the paper's
             "compressed parameter" idea applied to gradient traffic), and
             every embedding collective is the strategy's own.

Both return ``(params, opt_state, metrics)``: ``metrics`` holds ``loss``,
``grad_norm`` and, on a mesh of more than one device, the embedding
exchange's counters (``core/embedding/strategies.py::EXCHANGE_COUNTERS``),
summed over the devices. The sparse optimizer's update runs under
``jax.named_scope("mp.sparse_update")``.

Loss-scaling convention for manual mode (see the derivation in this file's
history / DESIGN.md §4): each device contributes ``local_mean / N_devices``;
MP-sharded embedding grads are then correct *without* any psum (the
collective transposes accumulate across devices), while replicated params
need one psum over ALL mesh axes.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat

from repro.configs.base import TrainConfig
from repro.core.embedding.strategies import exchange_stats, merge_stats
from repro.optim import optimizers as dense_opt_lib
from repro.optim.sparse import make_sparse
from repro.optim.optimizers import clip_by_global_norm

SPARSE_KEYS = ("embedding", "wide_embedding")


def is_sparse_key(k: str) -> bool:
    """True for param-tree keys owned by an embedding collection: the
    two legacy keys plus the N-group ``embedding@<group>`` keys."""
    return k in SPARSE_KEYS or k.startswith("embedding@")


def split_params(params: Dict) -> Tuple[Dict, Dict]:
    sparse = {k: v for k, v in params.items() if is_sparse_key(k)}
    dense = {k: v for k, v in params.items() if not is_sparse_key(k)}
    return sparse, dense


def build_optimizers(tcfg: TrainConfig):
    return (dense_opt_lib.make(tcfg.dense_optimizer, tcfg),
            make_sparse(tcfg.sparse_optimizer, tcfg))


def _apply_updates(params, grads, opt_state, dense_opt, sparse_opt, tcfg):
    sparse_p, dense_p = split_params(params)
    sparse_g = {k: grads[k] for k in sparse_p}
    dense_g = {k: grads[k] for k in dense_p}
    dense_g, gnorm = clip_by_global_norm(dense_g, tcfg.grad_clip)
    new_dense, dstate = dense_opt.update(dense_g, opt_state["dense"],
                                         dense_p)
    with jax.named_scope("mp.sparse_update"):
        new_sparse, sstate = sparse_opt.update(sparse_g,
                                               opt_state["sparse"], sparse_p)
    new_params = {**new_dense, **new_sparse}
    return new_params, {"dense": dstate, "sparse": sstate}, gnorm


def _metrics(model, loss, gnorm, stats) -> Dict[str, jax.Array]:
    """The step's metrics: the loss, the gradient norm and, where the mesh
    spans devices, the exchange's counters. On one device nothing crosses
    between devices and the step returns none: three more outputs moved
    the one-chip step's buffers, and its table-gradient scatter then took
    2 ms a step longer on a v5e chip (dcn-criteo, batch 16384)."""
    out = {"loss": loss, "grad_norm": gnorm}
    if model.mesh.devices.size > 1:
        out.update(stats)
    return out


def init_opt_state(params: Dict, tcfg: TrainConfig) -> Dict:
    dense_opt, sparse_opt = build_optimizers(tcfg)
    sparse_p, dense_p = split_params(params)
    return {"dense": dense_opt.init(dense_p),
            "sparse": sparse_opt.init(sparse_p)}


# ---------------------------------------------------------------------------
# GSPMD mode
# ---------------------------------------------------------------------------

def build_train_step(model, tcfg: TrainConfig) -> Callable:
    dense_opt, sparse_opt = build_optimizers(tcfg)

    def train_step(params, opt_state, batch):
        if tcfg.microbatches > 1:
            loss, grads, stats = _accumulated_grads(model, params, batch,
                                                    tcfg.microbatches)
        else:
            (loss, stats), grads = jax.value_and_grad(
                model.loss_and_stats, has_aux=True)(params, batch)
        new_params, new_state, gnorm = _apply_updates(
            params, grads, opt_state, dense_opt, sparse_opt, tcfg)
        return new_params, new_state, _metrics(model, loss, gnorm, stats)

    return train_step


def _accumulated_grads(model, params, batch, k: int):
    b = batch["label"].shape[0]
    mb = b // k

    def one(i):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
        micro = {kk: sl(v) for kk, v in batch.items()}
        return jax.value_and_grad(model.loss_and_stats, has_aux=True)(
            params, micro)

    def body(carry, i):
        loss_acc, grad_acc, stats_acc = carry
        (loss, stats), grads = one(i)
        grad_acc = jax.tree.map(lambda a, g: a + g / k, grad_acc, grads)
        return (loss_acc + loss / k, grad_acc,
                merge_stats(stats_acc, stats)), ()

    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads, stats), _ = jax.lax.scan(
        body, (jnp.zeros(()), zero_g, exchange_stats()), jnp.arange(k))
    return loss, grads, stats


def jit_train_step(model, tcfg: TrainConfig, mesh):
    """Fully-sharded jit: params/opt by their shardings, batch by DP."""
    from repro.data.pipeline import batch_shardings
    step = build_train_step(model, tcfg)
    p_sh = model.param_shardings()
    rep = NamedSharding(mesh, P())

    def opt_shardings(params_sh):
        sparse_sh, dense_sh = split_params(params_sh)
        acc_sh = {
            k: {kk: NamedSharding(
                mesh, P(*vv.spec[:1]))  # row-wise state follows rows
                for kk, vv in v.items()}
            for k, v in sparse_sh.items()}
        return {
            "dense": jax.tree.map(lambda _: rep, {"_": 0}) and {
                "step": rep,
                **({"mu": jax.tree.map(lambda s: s, dense_sh),
                    "nu": jax.tree.map(lambda s: s, dense_sh)}
                   if tcfg.dense_optimizer in ("adam", "adamw") else {}),
            },
            "sparse": {"acc": acc_sh},
        }

    o_sh = opt_shardings(p_sh)
    b_sh = batch_shardings(mesh)
    return jax.jit(
        step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------------------
# Manual mode (explicit collectives; compressed gradient all-reduce)
# ---------------------------------------------------------------------------

def build_manual_train_step(model, tcfg: TrainConfig, mesh) -> Callable:
    dense_opt, sparse_opt = build_optimizers(tcfg)
    n_dev = int(np.prod(mesh.devices.shape))
    all_axes = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    ar_dtype = jnp.bfloat16 if tcfg.grad_allreduce_dtype == "bf16" \
        else jnp.float32

    emb_specs = {key: coll.param_specs()
                 for key, coll in model.collections().items()}

    def param_specs(params):
        specs = {}
        for k, v in params.items():
            if k in emb_specs:
                specs[k] = emb_specs[k]
            else:
                specs[k] = jax.tree.map(lambda _: P(), v)
        return specs

    def grad_shard_fn(params, batch):
        # per-device loss scaled so that summing over every device gives
        # the global-mean loss (see module docstring)
        def scaled_loss(p):
            loss, stats = model.loss_and_stats(p, batch, manual=True)
            return loss / n_dev, stats

        (loss, stats), grads = jax.value_and_grad(scaled_loss,
                                                  has_aux=True)(params)
        # replicated params: explicit (optionally compressed) all-reduce;
        # MP-sharded embedding tables are already correct.
        def fix(path_key, g, spec):
            if spec == P() or all(s is None for s in spec):
                return jax.lax.psum(g.astype(ar_dtype),
                                    all_axes).astype(jnp.float32)
            return g

        specs = param_specs(params)
        grads = jax.tree.map(
            lambda g, s: fix(None, g, s), grads, specs,
            is_leaf=lambda x: isinstance(x, P))
        loss = jax.lax.psum(loss, all_axes)
        return loss, grads, stats       # stats: already summed (lookup)

    def train_step(params, opt_state, batch):
        specs = param_specs(params)
        b_spec = {"dense": P(dp_axes, None), "cat": P(dp_axes, None, None),
                  "label": P(dp_axes)}
        loss, grads, stats = compat.shard_map(
            grad_shard_fn, mesh=mesh,
            in_specs=(specs, b_spec),
            out_specs=(P(), specs, P()),
            check_vma=False,
        )(params, batch)
        new_params, new_state, gnorm = _apply_updates(
            params, grads, opt_state, dense_opt, sparse_opt, tcfg)
        return new_params, new_state, _metrics(model, loss, gnorm, stats)

    return train_step
