"""Plain reference of DCN (Wang et al., arXiv:1708.05123) as the
``dcn-criteo`` configuration states it: the dense features and the T
embeddings of width D are concatenated into x0; a cross network of L layers,
``x_{l+1} = x0 * (x_l . w_l) + b_l + x_l``, and a ReLU deep tower run side by
side over x0; a 1-unit layer over ``[x_L, deep]`` gives the logit.

``q`` rounds to the compute precision at every site where the configuration
computes in bfloat16 (the embeddings, each layer's input and output); the
reference passes the identity, the control a lower precision.

``declare`` is the one function here that touches the program: it builds
the same model through the program's graph API, for the harness to run.
``dense_forward_flops`` is the yardstick's count of the dense net's work.
"""
import jax.numpy as jnp


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
    m.add(DenseLayer("concat", ["dense", "emb"], ["flat"]))
    m.add(DenseLayer("cross", ["flat"], ["crossed"],
                     num_layers=cfg["num_cross_layers"]))
    m.add(DenseLayer("mlp", ["flat"], ["deep"], units=cfg["deep_mlp"]))
    m.add(DenseLayer("concat", ["crossed", "deep"], ["both"]))
    m.add(DenseLayer("mlp", ["both"], ["logit"], units=(1,)))
    m.add(DenseLayer("sigmoid", ["logit"], ["prob"]))
    return m


def _mlp_flops(in_dim, units):
    flops, d = 0, in_dim
    for u in units:
        flops += 2 * d * u
        d = u
    return flops


def dense_forward_flops(cfg):
    """Dense-net forward FLOPs per sample (a multiply-add is 2): the cross
    layers (x.w, then x0 * s + b + x: 2 + 3 per feature) and the deep
    tower over the ``13 + T * D`` concat, then the 1-unit combine over
    ``[crossed, deep]``. Elementwise activations are not counted."""
    x = cfg["num_dense_features"] + len(cfg["vocab_sizes"]) * \
        cfg["embedding_dim"]
    cross = cfg["num_cross_layers"] * 5 * x
    deep = _mlp_flops(x, cfg["deep_mlp"])
    return cross + deep + _mlp_flops(x + cfg["deep_mlp"][-1], [1])


def param_spec(cfg):
    x = cfg["num_dense_features"] + len(cfg["vocab_sizes"]) * \
        cfg["embedding_dim"]
    spec = {}
    for i in range(cfg["num_cross_layers"]):
        spec[f"cross/w{i}"] = (x,)
        spec[f"cross/b{i}"] = (x,)
    d = x
    for i, u in enumerate(cfg["deep_mlp"]):
        spec[f"deep/w{i}"] = (d, u)
        spec[f"deep/b{i}"] = (u,)
        d = u
    spec["combine/w0"] = (x + d, 1)
    spec["combine/b0"] = (1,)
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
    b = dense.shape[0]
    flat = jnp.concatenate([dense, q(emb).reshape(b, -1)], axis=1)
    x0 = q(flat)
    x = x0
    for i in range(len(p["cross"]) // 2):
        xw = q(dot(x, p["cross"][f"w{i}"]))
        x = q(x0 * xw[:, None] + q(p["cross"][f"b{i}"]) + x)
    deep = mlp(p["deep"], flat, q, dot)
    both = jnp.concatenate([x, deep], axis=1)
    return mlp(p["combine"], both, q, dot)[:, 0]
