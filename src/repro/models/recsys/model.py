"""The paper's model zoo: DLRM, DCN, DeepFM, Wide&Deep.

One functional ``RecsysModel`` facade owns:
  * the sparse part — an :class:`EmbeddingCollection` (the paper's MP
    embedding engine), plus a dim-1 "wide" collection for WDL/DeepFM
    first-order terms, and
  * the dense part — model-specific MLP/cross/interaction layers, which are
    replicated (DP) exactly as the paper prescribes.

``apply(params, batch)`` returns logits ``[B]``; ``loss_fn`` adds BCE.
batch = {"dense": [B, Nd] f32, "cat": [B, T, H] int32 (-1 pad), "label": [B]}
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import RecsysConfig, EmbeddingTableConfig
from repro.core.embedding import EmbeddingCollection, resolve_strategies
from repro.core.embedding.strategies import merge_stats
from repro.launch.mesh import mesh_config_for
from repro.models.recsys import dense_graph, layers
from repro.kernels import ops as kops


def wide_tables(cfg: RecsysConfig):
    """The dim-1 first-order ("wide") twin of every table — WDL/DeepFM
    derive their wide branch from the deep tables, so the serving side
    (object- or config-driven deploy) can reconstruct it from the
    RecsysConfig alone."""
    return tuple(
        dataclasses.replace(t, name=f"{t.name}_wide", dim=1,
                            strategy="data_parallel")
        for t in cfg.tables)


_wide_tables = wide_tables  # legacy alias


def export_logical_params(model, params: Dict) -> Dict:
    """Param tree with embedding groups in LOGICAL (mesh-independent)
    layout — the checkpoint format shared by Trainer and api.Model."""
    out = dict(params)
    for key, coll in model.collections().items():
        if key in out:
            out[key] = coll.export_logical(out[key])
    return out


def import_logical_params(model, params: Dict) -> Dict:
    """Inverse of :func:`export_logical_params` for ``model``'s mesh."""
    out = dict(params)
    for key, coll in model.collections().items():
        if key in out:
            out[key] = coll.import_logical(out[key])
    return out


def logical_tables(collection, emb_params) -> Dict[str, np.ndarray]:
    """Per-table LOGICAL weights (unpadded, de-striped, hot+cold merged)
    keyed by table name — the export shape the PDB and the portable
    converter both consume."""
    logical = collection.export_logical(emb_params)
    out: Dict[str, np.ndarray] = {}
    for gname, group in collection.groups.items():
        if gname == "cold":
            continue               # merged into "hot" below
        for i, (t, off) in enumerate(zip(group.tables, group.offsets)):
            end = group.offsets[i + 1] if i + 1 < group.num_tables \
                else group.total_rows
            if gname == "hot":
                cg = collection.groups["cold"]
                coff = cg.offsets[i]
                cend = cg.offsets[i + 1] if i + 1 < cg.num_tables \
                    else cg.total_rows
                full = np.concatenate(
                    [np.asarray(logical["hot"])[off:end],
                     np.asarray(logical["cold"])[coff:cend]], axis=0)
            elif gname == "loc":
                full = np.asarray(logical["loc"][i])[:t.vocab_size]
            else:
                full = np.asarray(logical[gname])[off:end]
            out[t.name] = full
    return out


def import_logical_tables(collection, emb_params,
                          tables: Dict[str, np.ndarray]) -> Dict:
    """Inverse of :func:`logical_tables`: write per-table FULL weight
    arrays back into the collection's logical layout and import for this
    mesh. ``emb_params`` supplies the layout template (and the values of
    any table absent from ``tables``) — the ETC trainer uses this to
    fold parameter-server contents back into a servable param tree."""
    logical = {}
    for k, v in collection.export_logical(emb_params).items():
        if isinstance(v, list):
            logical[k] = [np.array(x) for x in v]
        else:
            logical[k] = np.array(v)
    for gname, group in collection.groups.items():
        if gname == "cold":
            continue               # written through "hot" below
        for i, (t, off) in enumerate(zip(group.tables, group.offsets)):
            if t.name not in tables:
                continue
            full = np.asarray(tables[t.name], np.float32)
            if full.shape != (t.vocab_size, t.dim):
                raise ValueError(
                    f"table {t.name}: got {full.shape}, want "
                    f"({t.vocab_size}, {t.dim})")
            end = group.offsets[i + 1] if i + 1 < group.num_tables \
                else group.total_rows
            if gname == "hot":
                cg = collection.groups["cold"]
                coff = cg.offsets[i]
                cend = cg.offsets[i + 1] if i + 1 < cg.num_tables \
                    else cg.total_rows
                nhot = end - off
                logical["hot"][off:end] = full[:nhot]
                logical["cold"][coff:cend] = full[nhot:]
            elif gname == "loc":
                logical["loc"][i][:t.vocab_size] = full
            else:
                logical[gname][off:end] = full
    return collection.import_logical(
        {k: ([jnp.asarray(x) for x in v] if isinstance(v, list)
             else jnp.asarray(v)) for k, v in logical.items()})


class RecsysModel:

    def __init__(self, cfg: RecsysConfig, mesh: Mesh, *,
                 global_batch: int,
                 comm: str = "allgather_rs",
                 a2a_threshold: int = 65536,
                 embed_shard_axes: str = "all",
                 use_kernels: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        if cfg.model == "dlrm" and cfg.bottom_mlp[-1] != cfg.embedding_dim:
            raise ValueError(
                "DLRM needs bottom_mlp[-1] == embedding_dim for the "
                f"interaction, got {cfg.bottom_mlp[-1]} != "
                f"{cfg.embedding_dim}")
        mesh_cfg = mesh_config_for(mesh)
        tables = resolve_strategies(cfg.tables, mesh_cfg, global_batch)
        cd = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
        pool = kops.kernel_pool if use_kernels else None

        def pick_comm(group_tables):
            # "auto" resolves PER COLLECTION: each independently-
            # dimensioned group gets the comm pattern its table sizes
            # want (hybrid recipe — all_to_all only for large one-hot).
            if comm != "auto":
                return comm
            from repro.core.embedding.planner import choose_comm
            return choose_comm(group_tables, threshold=a2a_threshold)

        self.embedding = EmbeddingCollection(
            tables, mesh, comm=pick_comm(tables), compute_dtype=cd,
            shard_axes=embed_shard_axes, pool_fn=pool)
        self.compute_dtype = cd
        self.use_kernels = use_kernels
        #: the compiled dense program — ONE executor for every model
        #: kind: canonical recipes bind their historical params,
        #: model="graph" compiles the embedded DAG
        self.program = dense_graph.program_for(cfg,
                                               use_kernels=use_kernels)
        self.wide: Optional[EmbeddingCollection] = None
        if cfg.model in ("wdl", "deepfm") or \
                (cfg.model == "graph" and cfg.wide_branch):
            wt = wide_tables(cfg)
            self.wide = EmbeddingCollection(wt, mesh, comm=pick_comm(wt),
                                            compute_dtype=cd)
        #: extra N-group collections, param-tree key "embedding@<name>"
        self.extra: Dict[str, EmbeddingCollection] = {}
        for g in getattr(cfg, "extra_groups", ()):
            gt = resolve_strategies(g.tables, mesh_cfg, global_batch)
            self.extra[g.name] = EmbeddingCollection(
                gt, mesh, comm=pick_comm(gt), compute_dtype=cd,
                shard_axes=embed_shard_axes, pool_fn=pool)
        #: cat column span per collection key, in declared order —
        #: batches lay out cat as [primary tables | group1 | group2 ...]
        cols: Dict[str, tuple] = {"embedding": (0, len(cfg.tables))}
        off = len(cfg.tables)
        for g in getattr(cfg, "extra_groups", ()):
            cols[f"embedding@{g.name}"] = (off, off + len(g.tables))
            off += len(g.tables)
        self._group_cols = cols

    def collections(self) -> Dict[str, EmbeddingCollection]:
        """Every embedding collection keyed by its param-tree key."""
        out: Dict[str, EmbeddingCollection] = {"embedding": self.embedding}
        if self.wide is not None:
            out["wide_embedding"] = self.wide
        for name, coll in self.extra.items():
            out[f"embedding@{name}"] = coll
        return out

    def group_columns(self) -> Dict[str, tuple]:
        """``cat`` column ``(start, stop)`` per lookup key (the wide
        twin reads the primary columns, so it is not listed)."""
        return dict(self._group_cols)

    # -- init ----------------------------------------------------------------

    def init(self, key: jax.Array) -> Dict:
        cfg = self.cfg
        k_emb, k_wide, k1, k2, k3, k4 = jax.random.split(key, 6)
        params: Dict = {"embedding": self.embedding.init(k_emb)}
        if self.wide is not None:
            params["wide_embedding"] = self.wide.init(k_wide)
        for i, (name, coll) in enumerate(sorted(self.extra.items())):
            params[f"embedding@{name}"] = coll.init(
                jax.random.fold_in(k_emb, i + 1))
        d, t = cfg.embedding_dim, cfg.num_tables
        nd = cfg.num_dense_features
        if cfg.model == "graph":
            # per-layer params from the compiled program, keyed by each
            # layer's output tensor (the trainer's dense/sparse split is
            # by the reserved embedding keys, so any layer name works)
            params.update(self.program.init(k1))
        elif cfg.model == "dlrm":
            params["bottom"] = layers.mlp_init(k1, nd, cfg.bottom_mlp)
            f = t + 1
            top_in = cfg.bottom_mlp[-1] + f * (f - 1) // 2
            params["top"] = layers.mlp_init(k2, top_in, cfg.top_mlp)
        elif cfg.model == "dcn":
            in_dim = nd + t * d
            params["cross"] = layers.cross_init(k1, in_dim,
                                                cfg.num_cross_layers)
            params["deep"] = layers.mlp_init(k2, in_dim, cfg.top_mlp)
            params["combine"] = layers.mlp_init(
                k3, in_dim + cfg.top_mlp[-1], (1,))
        elif cfg.model == "deepfm":
            in_dim = nd + t * d
            params["deep"] = layers.mlp_init(k1, in_dim, cfg.top_mlp + (1,))
            params["dense_w"] = jax.random.normal(k2, (nd,)) * 0.01
            params["bias"] = jnp.zeros(())
        elif cfg.model == "wdl":
            in_dim = nd + t * d
            params["deep"] = layers.mlp_init(k1, in_dim, cfg.top_mlp + (1,))
            params["dense_w"] = jax.random.normal(k2, (nd,)) * 0.01
            params["bias"] = jnp.zeros(())
        else:
            raise ValueError(cfg.model)
        return params

    # -- shardings -------------------------------------------------------------

    def param_shardings(self) -> Dict:
        """NamedShardings: embeddings per strategy, dense replicated (DP)."""
        rep = NamedSharding(self.mesh, P())
        shardings: Dict = {key: coll.param_shardings()
                           for key, coll in self.collections().items()}
        # structure only — eval_shape, NEVER a real init (tables can be
        # tens of GB; allocating them here stalled the dry-run for 20 min)
        dummy = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

        def fill(tree):
            return jax.tree.map(lambda _: rep, tree)

        for k, v in dummy.items():
            if k in shardings:
                continue
            shardings[k] = fill(v)
        return shardings

    # -- forward ---------------------------------------------------------------

    def apply(self, params: Dict, batch: Dict, *,
              manual: bool = False) -> jax.Array:
        return self.apply_with_stats(params, batch, manual=manual)[0]

    def apply_with_stats(self, params: Dict, batch: Dict, *,
                         manual: bool = False
                         ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """:meth:`apply` and the embedding exchange's counters, summed over
        every collection (``EmbeddingCollection.lookup_with_stats``)."""
        cat = batch["cat"]
        # single-group models keep the whole-cat trace they always had;
        # N-group models slice each collection's column span
        cat_p = cat if not self.extra \
            else cat[:, slice(*self._group_cols["embedding"]), :]
        emb, stats = self.embedding.lookup_with_stats(
            params["embedding"], cat_p, manual=manual)
        wide = None
        if self.wide is not None:
            wide, s = self.wide.lookup_with_stats(       # [B, T, 1]
                params["wide_embedding"], cat_p, manual=manual)
            stats = merge_stats(stats, s)
        extras = None
        if self.extra:
            extras = {}
            for name, coll in self.extra.items():
                key = f"embedding@{name}"
                sl = slice(*self._group_cols[key])
                extras[name], s = coll.lookup_with_stats(
                    params[key], cat[:, sl, :], manual=manual)
                stats = merge_stats(stats, s)
        return self.apply_dense(params, batch["dense"], emb, wide,
                                extras=extras), stats

    def apply_dense(self, params: Dict, dense: jax.Array, emb: jax.Array,
                    wide: Optional[jax.Array] = None, *,
                    extras: Optional[Dict[str, jax.Array]] = None
                    ) -> jax.Array:
        """Dense-only forward from precomputed pooled embeddings.

        This is the inference entry point: the HPS resolves ``emb`` (and
        ``wide``) on the host, the replicated dense net runs on device.

        Execution is the compiled :class:`DenseGraphProgram` — the same
        node loop for the canonical recipes and for novel graphs
        (bit-exact with the historical fixed pipeline, which survives as
        :meth:`apply_dense_reference` for the parity tests).
        """
        env = self.program.make_env(dense, emb, wide, self.compute_dtype,
                                    extras=extras)
        return self.program.apply(params, env, self.compute_dtype)

    def apply_dense_reference(self, params: Dict, dense: jax.Array,
                              emb: jax.Array,
                              wide: Optional[jax.Array] = None
                              ) -> jax.Array:
        """The pre-compiler fixed pipeline (canonical recipes only) —
        kept as the bit-exactness reference for the generic executor."""
        cfg = self.cfg
        cd = self.compute_dtype
        emb = emb.astype(cd)                       # [B, T, D]
        dense = dense.astype(jnp.float32)
        b = dense.shape[0]
        if cfg.model == "dlrm":
            bot = layers.mlp_apply(params["bottom"], dense,
                                   final_activation=True, compute_dtype=cd)
            feats = jnp.concatenate([bot[:, None, :], emb], axis=1)
            if self.use_kernels:
                tri = kops.dot_interaction(feats)
            else:
                from repro.kernels.ref import dot_interaction_ref
                tri = dot_interaction_ref(feats)
            top_in = jnp.concatenate([bot.astype(jnp.float32), tri], axis=1)
            logit = layers.mlp_apply(params["top"], top_in, compute_dtype=cd)
            return logit[:, 0]
        flat = jnp.concatenate(
            [dense, emb.reshape(b, -1).astype(jnp.float32)], axis=1)
        if cfg.model == "dcn":
            crossed = layers.cross_apply(params["cross"], flat,
                                         compute_dtype=cd)
            deep = layers.mlp_apply(params["deep"], flat, compute_dtype=cd)
            both = jnp.concatenate([crossed, deep], axis=1)
            return layers.mlp_apply(params["combine"], both,
                                    compute_dtype=cd)[:, 0]
        if cfg.model == "deepfm":
            first = wide.sum(axis=(1, 2)) \
                + dense @ params["dense_w"] + params["bias"]
            second = layers.fm_second_order(emb).sum(axis=1)
            deep = layers.mlp_apply(params["deep"], flat,
                                    compute_dtype=cd)[:, 0]
            return first + second + deep
        if cfg.model == "wdl":
            wide_logit = wide.sum(axis=(1, 2)) \
                + dense @ params["dense_w"] + params["bias"]
            deep = layers.mlp_apply(params["deep"], flat,
                                    compute_dtype=cd)[:, 0]
            return wide_logit + deep
        raise ValueError(cfg.model)

    def loss_fn(self, params: Dict, batch: Dict, *,
                manual: bool = False) -> jax.Array:
        return self.loss_and_stats(params, batch, manual=manual)[0]

    def loss_and_stats(self, params: Dict, batch: Dict, *,
                       manual: bool = False
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """The loss and the exchange's counters (:meth:`apply_with_stats`),
        for ``jax.value_and_grad(..., has_aux=True)``."""
        logits, stats = self.apply_with_stats(params, batch, manual=manual)
        return layers.bce_with_logits(logits, batch["label"]), stats
