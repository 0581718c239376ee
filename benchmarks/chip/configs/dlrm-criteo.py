"""Plain reference of DLRM (Naumov et al., arXiv:1906.00091) as the
``dlrm-criteo`` configuration states it: a ReLU bottom MLP over the dense
features (ReLU on its last layer too, width D), the strict lower triangle of
the pairwise dot products among ``[bottom, T embeddings]`` (row-major:
(1,0), (2,0), (2,1), ...), and a top MLP over ``[bottom, dots]`` whose last
unit is the logit.

``q`` rounds to the compute precision at every site where the configuration
computes in bfloat16; the reference passes the identity, the control a lower
precision.

``declare`` is the one function here that touches the program: it builds
the same model through the program's graph API, for the harness to run.
``dense_forward_flops`` is the yardstick's count of the dense net's work.
"""
import jax.numpy as jnp
import numpy as np


def declare(cfg, solver=None, reader=None):
    """The configuration's model through the graph API (``Model.add``)."""
    from repro.api import DenseLayer, Input, Model, SparseEmbedding
    t = len(cfg["vocab_sizes"])
    m = Model(solver, reader, name=cfg["name"])
    m.add(Input(dense_dim=cfg["num_dense_features"]))
    m.add(SparseEmbedding(vocab_sizes=cfg["vocab_sizes"],
                          dim=cfg["embedding_dim"], top_name="emb",
                          hotness=cfg.get("hotness", 1),
                          table_names=cfg["table_names"][:t]))
    m.add(DenseLayer("mlp", ["dense"], ["bot"], units=cfg["bottom_mlp"],
                     final_activation=True))
    m.add(DenseLayer("dot_interaction", ["bot", "emb"], ["interaction"]))
    m.add(DenseLayer("concat", ["bot", "interaction"], ["top_in"]))
    m.add(DenseLayer("mlp", ["top_in"], ["logit"], units=cfg["top_mlp"]))
    m.add(DenseLayer("sigmoid", ["logit"], ["prob"]))
    return m


def _mlp_flops(in_dim, units):
    flops, d = 0, in_dim
    for u in units:
        flops += 2 * d * u
        d = u
    return flops


def dense_forward_flops(cfg):
    """Dense-net forward FLOPs per sample (a multiply-add is 2): the
    bottom MLP, the ``F(F-1)/2`` pairwise dots of width D over
    ``F = T + 1`` features, and the top MLP over ``[bottom, dots]``.
    Elementwise activations are not counted."""
    f = len(cfg["vocab_sizes"]) + 1
    pairs = f * (f - 1) // 2
    return (_mlp_flops(cfg["num_dense_features"], cfg["bottom_mlp"])
            + 2 * pairs * cfg["embedding_dim"]
            + _mlp_flops(cfg["bottom_mlp"][-1] + pairs, cfg["top_mlp"]))


def param_spec(cfg):
    spec = {}
    d = cfg["num_dense_features"]
    for i, u in enumerate(cfg["bottom_mlp"]):
        spec[f"bottom/w{i}"] = (d, u)
        spec[f"bottom/b{i}"] = (u,)
        d = u
    f = len(cfg["vocab_sizes"]) + 1
    d = cfg["bottom_mlp"][-1] + f * (f - 1) // 2
    for i, u in enumerate(cfg["top_mlp"]):
        spec[f"top/w{i}"] = (d, u)
        spec[f"top/b{i}"] = (u,)
        d = u
    return spec


def mlp(p, x, q, dot, final=False):
    n = len(p) // 2
    h = q(x)
    for i in range(n):
        h = dot(h, p[f"w{i}"]) + p[f"b{i}"]
        if i < n - 1 or final:
            h = jnp.maximum(h, 0.0)
        h = q(h)
    return h


def forward(p, dense, emb, q, dot):
    bot = mlp(p["bottom"], dense, q, dot, final=True)
    feats = jnp.concatenate([bot[:, None, :], q(emb)], axis=1)
    gram = dot(feats, feats, "bfd,bgd->bfg")
    i, j = np.tril_indices(feats.shape[1], -1)
    top_in = jnp.concatenate([bot, gram[:, i, j]], axis=1)
    return mlp(p["top"], top_in, q, dot)[:, 0]
