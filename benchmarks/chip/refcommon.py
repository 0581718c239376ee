"""Plain float32 reference pieces shared by the configurations' references.

Nothing here imports the program. A configuration's reference module
(``configs/<config>.py``) defines ``param_spec(cfg)`` and
``forward(params, dense, emb, q, dot)``; this module supplies the rounding
``q`` and the matmul ``dot`` for a precision, the embedding rows from the
benchmark's seeded weights, the loss, the optimizers, and the two
comparisons (served predictions, first training steps).

``precision``: ``"f32"`` is the reference (no rounding, matmuls at
``Precision.HIGHEST``); ``"fp8"`` is the control, float8 e4m3 rounding at
every site where the configuration computes in bfloat16.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST
_ROUND = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def rounding(precision: str) -> Callable:
    dt = _ROUND[precision]
    if dt is None:
        return lambda x: x.astype(jnp.float32)
    return lambda x: x.astype(dt).astype(jnp.float32)


def matmul(q: Callable) -> Callable:
    """``dot(a, b)``: operands rounded by ``q``, exact products, f32 sums."""
    def dot(a, b, spec: str = None):
        if spec is None:
            return jnp.dot(q(a), q(b), precision=HIGHEST)
        return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)
    return dot


def load_reference(config_name: str):
    """``configs/<config>.py``: the configuration's plain reference."""
    path = os.path.join(HERE, "configs", f"{config_name}.py")
    spec = importlib.util.spec_from_file_location(
        f"reference_{config_name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bce(logits, labels):
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def tables(cfg: Dict) -> List[Dict]:
    """Every embedding table the configuration states, numbered as their
    seeded streams are: the primary collection's (``table_names``,
    ``vocab_sizes``, ``embedding_dim``; table t reads id column t), then
    ``extra_tables``, each with its ``name``, ``vocab``, ``dim`` and the
    ``column`` of the ids it reads (a wide twin reads its primary's)."""
    t = len(cfg["vocab_sizes"])
    out = [{"name": n, "vocab": int(v), "dim": cfg["embedding_dim"],
            "column": i}
           for i, (n, v) in enumerate(zip(cfg["table_names"][:t],
                                          cfg["vocab_sizes"]))]
    return out + [dict(e) for e in cfg.get("extra_tables", [])]


def apply(ref, cfg: Dict, params, dense, rows: Sequence, q, dot):
    """``ref.forward`` on one row per table (``rows[k]``: ``[N, dim_k]``):
    the primary tables stacked ``[N, T, D]``, and the extra tables, where
    the configuration has any, as ``extra={name: rows}``."""
    t = len(cfg["vocab_sizes"])
    emb = jnp.stack(rows[:t], axis=1)
    extra = {e["name"]: r for e, r in zip(tables(cfg)[t:], rows[t:])}
    kw = {"extra": extra} if extra else {}
    return ref.forward(params, dense, emb, q, dot, **kw)


# ---------------------------------------------------------------------------
# serving: predictions for delivered requests
# ---------------------------------------------------------------------------

def predictions(ref, cfg: Dict, seed: int, dense: np.ndarray,
                ids: np.ndarray, precision: str = "f32",
                block: int = 4096) -> np.ndarray:
    """Sigmoid outputs for rows ``dense [N, Nd]``, ``ids [N, C]`` (one id
    column per primary table, and any further columns extra tables read),
    in fixed-size blocks (one compiled program whatever N is)."""
    lo, hi = weights.seed_words(seed)
    spec = ref.param_spec(cfg)
    q = rounding(precision)
    tabs = tables(cfg)

    @jax.jit
    def run(lo, hi, dense, ids):
        params = weights.dense_params(lo, hi, spec)
        rows = [weights.table_rows(lo, hi, k, ids[:, e["column"]], e["dim"])
                for k, e in enumerate(tabs)]
        return jax.nn.sigmoid(apply(ref, cfg, params, dense, rows, q,
                                    matmul(q)))

    n = dense.shape[0]
    pad = -n % block
    dense = np.concatenate([dense, np.zeros((pad,) + dense.shape[1:],
                                            dense.dtype)])
    ids = np.concatenate([ids, np.zeros((pad,) + ids.shape[1:], ids.dtype)])
    out = [np.asarray(run(lo, hi, dense[i:i + block], ids[i:i + block]))
           for i in range(0, n + pad, block)]
    return np.concatenate(out)[:n]


# ---------------------------------------------------------------------------
# training: the first steps
# ---------------------------------------------------------------------------

def _adamw(p, g, mu, nu, step, tc):
    b1, b2, eps = tc["beta1"], tc["beta2"], tc["eps"]
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    c1 = 1 - b1 ** step
    c2 = 1 - b2 ** step
    upd = (mu / c1) / (jnp.sqrt(nu / c2) + eps) + tc["weight_decay"] * p
    return p - tc["lr"] * upd, mu, nu


def _rowwise_adagrad(e, g, acc, tc):
    acc = acc + jnp.mean(g * g, axis=1)
    return e - (tc["lr"] / (jnp.sqrt(acc) + 1e-10))[:, None] * g, acc


def _clip(tree, max_norm):
    leaves = jax.tree.leaves(tree)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda x: x * scale, tree)


def train_steps(ref, cfg: Dict, seed: int, batches: Sequence[Dict],
                precision: str = "f32") -> Dict:
    """The reference's first ``len(batches)`` steps from the benchmark's
    weights: dense AdamW after global-norm clipping, row-wise AdaGrad on
    the embedding rows of every table, on the touched rows only (an
    untouched row has no gradient and does not move).

    Returns the loss of each step, every leaf's first gradient as the
    optimizer gets it (dense: after clipping) and every leaf's change over
    all the steps, as ``{"loss": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}``; table leaves are named by table.
    """
    tc = cfg["training"]
    tabs = tables(cfg)
    names = [e["name"] for e in tabs]
    lo, hi = weights.seed_words(seed)
    spec = ref.param_spec(cfg)
    q = rounding(precision)
    dot = matmul(q)

    cats = [np.asarray(b["cat"])[:, :, 0] for b in batches]
    uniq = [np.unique(np.concatenate([c[:, e["column"]] for c in cats]))
            for e in tabs]
    local = [np.stack([np.searchsorted(uniq[k], c[:, e["column"]])
                       for k, e in enumerate(tabs)], axis=1).astype(np.int32)
             for c in cats]
    dense0 = weights.dense_params(lo, hi, spec)
    emb0 = [weights.table_rows(lo, hi, k, jnp.asarray(u, jnp.int32),
                               e["dim"])
            for k, (e, u) in enumerate(zip(tabs, uniq))]

    def loss_fn(dense_p, emb_p, x, ids, y):
        rows = [emb_p[k][ids[:, k]] for k in range(len(emb_p))]
        return bce(apply(ref, cfg, dense_p, x, rows, q, dot), y)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))

    @jax.jit
    def update(dense_p, emb_p, gd, ge, mu, nu, acc, step):
        gd = _clip(gd, tc["grad_clip"])
        out = jax.tree.map(
            lambda p, g, m, v: _adamw(p, g, m, v, step, tc),
            dense_p, gd, mu, nu)
        dense_p = jax.tree.map(lambda o: o[0], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree.map(lambda o: o[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree.map(lambda o: o[2], out,
                          is_leaf=lambda x: isinstance(x, tuple))
        new = [_rowwise_adagrad(e, g, a, tc)
               for e, g, a in zip(emb_p, ge, acc)]
        return (dense_p, [n[0] for n in new], mu, nu, [n[1] for n in new],
                gd)

    with jax.default_matmul_precision("highest"):
        dense_p, emb_p = dense0, list(emb0)
        mu = jax.tree.map(jnp.zeros_like, dense_p)
        nu = jax.tree.map(jnp.zeros_like, dense_p)
        acc = [jnp.zeros((len(u),), jnp.float32) for u in uniq]
        losses, grad = [], {}
        for s, b in enumerate(batches):
            loss, (gd, ge) = grad_fn(dense_p, emb_p,
                                     jnp.asarray(b["dense"]), local[s],
                                     jnp.asarray(b["label"]))
            dense_p, emb_p, mu, nu, acc, gdc = update(
                dense_p, emb_p, gd, ge, mu, nu, acc, jnp.float32(s + 1))
            losses.append(float(loss))
            if s == 0:
                for path, g in weights.flatten(gdc).items():
                    grad[path] = float(jnp.linalg.norm(g))
                for t, g in enumerate(ge):
                    grad[names[t]] = float(jnp.linalg.norm(g))
        change = {}
        flat0 = weights.flatten(dense0)
        for path, p in weights.flatten(dense_p).items():
            change[path] = float(jnp.linalg.norm(p - flat0[path]))
        for t in range(len(names)):
            change[names[t]] = float(jnp.linalg.norm(emb_p[t] - emb0[t]))
    return {"loss": losses, "grad": grad, "change": change}


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Sequence[str] = None) -> float:
    """Worst leaf's gap between two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    keys = list(keep if keep is not None else ref)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


def moving_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's: the others move by round-off alone and are not compared."""
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v > 1e-3 * med]
