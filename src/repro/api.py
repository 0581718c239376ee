"""HugeCTR-style declarative graph API (paper §2).

HugeCTR's Python API is a *model graph*, not a two-slot facade: a
``Solver`` carries the run-level knobs, ``DataReaderParams`` describes
the input source, and the network is a list of named layers wired by
``bottom_names``/``top_names`` — serialized to JSON and consumed
verbatim by the inference side. Same shape here:

    from repro.api import (CreateSolver, DataReaderParams, DenseLayer,
                           Input, Model, SparseEmbedding)

    solver = CreateSolver(batch_size=256, lr=1e-2)
    reader = DataReaderParams(source="synthetic", num_dense_features=13)
    m = Model(solver, reader, name="dlrm-demo")
    m.add(Input(dense_dim=13))
    m.add(SparseEmbedding(vocab_sizes=[1000, 500, 200], dim=16,
                          top_name="emb"))
    m.add(DenseLayer("mlp", ["dense"], ["bot"], units=(32, 16),
                     final_activation=True))
    m.add(DenseLayer("dot_interaction", ["bot", "emb"], ["inter"]))
    m.add(DenseLayer("concat", ["bot", "inter"], ["top_in"]))
    m.add(DenseLayer("mlp", ["top_in"], ["logit"], units=(32, 1)))
    m.add(DenseLayer("sigmoid", ["logit"], ["prob"]))
    m.compile()
    m.summary()
    m.fit(steps=100)                       # reader-driven data
    m.graph_to_json("graph.json")          # round-trip: Model.from_json
    m.save("ckpt_dir")                     # graph + weights; Model.load
    server = m.deploy("deploy_dir")        # writes ps.json bundle, too

**Generic compilation.** ``compile()`` does NOT pattern-match a menu of
recipes: the lowering pass validates the ``DenseLayer`` DAG (unknown
tensors, duplicate names, cycles, arity, shape agreement, a single
terminal, no unused layers), topologically sorts it (layers may be
added in any order), infers every tensor's shape, and emits a
``DenseGraphProgram`` (``models/recsys/dense_graph.py``) — per-layer
parameter init plus one jitted apply that the training and serving
stacks execute for ANY valid graph. A graph that happens to be one of
the four paper recipes lowers to that recipe's canonical
``RecsysConfig`` (``model="dlrm"|"dcn"|"deepfm"|"wdl"``, bit-exact with
the registry configs, paper semantics preserved — e.g. the WDL wide
head pools the wide branch with fixed weight 1); every other graph
lowers to ``model="graph"`` with the DAG embedded in the config, and
trains / round-trips / deploys / exports with zero per-architecture
code.

**Layer vocabulary and shape rules.** Shapes are written per sample
(the batch axis is implicit): ``[n]`` is a 2-D feature block,
``[T, D]`` a 3-D pooled-embedding block, ``[]`` a logit column.
Inputs: the ``Input``'s dense tensor is ``[dense_dim]``; each
``SparseEmbedding`` group's top is ``[T, D]`` (the dim-1 wide group is
``[T, 1]``). 3-D blocks flatten to ``[T*D]`` wherever a 2-D view is
needed.

====================  =====================================================
``mlp``               1+ bottoms, flattened + concatenated -> ``[units[-1]]``;
                      ``units`` per layer, ``final_activation`` keeps the
                      last ReLU.
``cross``             1 bottom ``[n]`` -> ``[n]``; DCN cross net,
                      ``num_layers`` deep.
``dot_interaction``   ``[D]`` + ``[T, D]`` -> ``[(T+1)T/2]``; DLRM pairwise
                      dots (the 2-D bottom must end at the embedding dim).
``fm``                ``[n]`` + ``[T, 1]`` + ``[T, D]`` (any order) ->
                      ``[]``; factorization-machine first+second order.
``concat``            1+ bottoms, flattened -> ``[sum of dims]``.
``add``               2+ bottoms of identical shape -> same (elementwise).
``multiply``          2+ bottoms of identical shape -> same (elementwise).
``relu``              1 bottom -> same shape.
``slice``             1 bottom ``[n]`` -> ``[stop-start]`` (feature axis).
``reduce_sum``        1 bottom -> ``[]`` (sums all non-batch axes).
``sigmoid``           terminal only: sums its logit-shaped (``[]`` or
                      ``[1]``) bottoms and emits the probability.
====================  =====================================================

The graph must end in exactly ONE terminal tensor (produced, never
consumed): a ``sigmoid`` layer, or a logit-shaped tensor.

**N-group embeddings.** A model may declare ANY number of
``SparseEmbedding`` groups, each with its own dim / vocab sizes /
hotness — the NeuMF/two-tower shape with separate user and item
embedding dims. The first declared group is the primary collection;
every further group lowers to its own ``EmbeddingCollection`` (param
key ``embedding@<top_name>``), its own column span in the ``cat``
input (columns follow declaration order: primary tables first, then
each group's), and its own HPS table set at deploy time. Table names
must be globally unique; a group without explicit ``table_names``
defaults to ``<top_name>_f<i>`` (the primary keeps ``f<i>``). One
special case is kept for the paper recipes: exactly two groups where
one is the dim-1 exact twin of the other (same vocab sizes,
``combiner="sum"``) lower as deep + wide branch — WDL/DeepFM and any
novel graph wanting a first-order term.

**Model parallelism.** ``Solver`` carries the mesh intent and
``fit()`` honors it end to end: ``mesh_shape=(r, c)`` lays the visible
devices out as a ``("data", "model")`` mesh (validated up front
against the visible device count), embeddings shard over the mesh per
the placement planner while the dense net stays data-parallel, and the
sharded train step runs under either ``mode="gspmd"`` (XLA inserts the
collectives) or ``mode="manual"`` (explicit psum, compressed gradient
all-reduce via ``grad_allreduce_dtype``). ``comm`` picks the embedding
exchange per collection: ``"allgather_rs"``, ``"all_to_all"``, or
``"auto"`` (the default — all-to-all only for groups of large one-hot
tables, threshold ``a2a_threshold``; pooled or small tables keep
allgather + reduce-scatter). Checkpoints store mesh-independent
logical arrays, so ``save()`` on one mesh and ``load()`` on another
just works.

``graph_to_json`` embeds a hash of the lowered config;
``Model.from_json`` re-lowers and verifies it. ``deploy(directory)``
writes a relocatable serving bundle — ``pdb/`` (all tables, wide twins
included), ``graph.json``, ``dense.npz`` and a ps.json-style
``HPSConfig`` — and ``launch/serve.py`` reconstructs the
``HPS`` + ``InferenceServer`` from that bundle alone, novel graphs
included, no Python object from training in hand.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    EmbeddingTableConfig, EnsembleConfig, ETCParams, HPSConfig,
    RecsysConfig, SparseGroupConfig, TrainConfig,
    ensemble_config_to_dict, hps_config_to_dict, recsys_config_hash,
)

from repro.models.recsys.dense_graph import (
    GraphError, RESERVED_NAMES, compile_layers, graph_spec,
    spec_from_layer,
)

GRAPH_FORMAT = "repro-graph-v1"
PS_FORMAT = "repro-ps-v1"


# ---------------------------------------------------------------------------
# Run-level declarations
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Solver:
    """Run-level knobs (HugeCTR's ``CreateSolver``): batch, mesh, mode,
    and both optimizers — everything ``compile()`` used to take as
    keyword soup."""
    batch_size: int = 256
    lr: float = 1e-3
    optimizer: str = "adamw"                  # dense tower optimizer
    sparse_optimizer: str = "rowwise_adagrad"  # embedding optimizer
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    grad_allreduce_dtype: str = "f32"
    mixed_precision: bool = True
    mode: str = "gspmd"                       # "gspmd" | "manual"
    #: None = size the mesh to the visible devices; (r, c) = test mesh
    mesh_shape: Optional[Tuple[int, ...]] = None
    #: embedding exchange per collection: "auto" picks all_to_all for
    #: groups of large one-hot tables (>= a2a_threshold rows) and
    #: allgather_rs otherwise; or pin "allgather_rs" / "all_to_all"
    comm: str = "auto"
    a2a_threshold: int = 65536
    ckpt_interval: int = 50
    seed: int = 0
    #: ETC-staged training (HugeCTR's Embedding Training Cache): set to
    #: ``ETCParams(cache_rows=..., ps="staged"|"cached", passes=N)`` and
    #: ``fit()`` trains through a fixed-capacity device row cache backed
    #: by a parameter server instead of full in-device tables —
    #: ``cache_rows`` bounds device rows per table, ``ps`` picks the
    #: durable tier ("cached" needs ``ps_root``, survives restarts and
    #: fsyncs on flush), ``passes`` splits the run into keyset-staged
    #: passes whose boundaries flush the cache and (via
    #: ``repro.online``) publish versioned updates to live servers.
    #: None (default) keeps the in-memory trainer.
    etc: Optional[ETCParams] = None

    def __post_init__(self):
        if self.etc is not None and not isinstance(self.etc, ETCParams):
            if not isinstance(self.etc, dict):
                raise GraphError(
                    f"Solver.etc must be an ETCParams (or its dict "
                    f"form), got {type(self.etc).__name__}")
            try:                   # JSON round-trip: Solver(**d["solver"])
                self.etc = ETCParams(**self.etc)
            except (TypeError, ValueError) as e:
                raise GraphError(f"Solver.etc: {e}")
        if self.mode not in ("gspmd", "manual"):
            raise GraphError(
                f"Solver.mode must be 'gspmd' or 'manual', got "
                f"{self.mode!r}")
        if self.comm not in ("auto", "allgather_rs", "all_to_all"):
            raise GraphError(
                f"Solver.comm must be 'auto', 'allgather_rs' or "
                f"'all_to_all', got {self.comm!r}")
        if self.mesh_shape is not None:
            shape = tuple(self.mesh_shape)
            if not shape or any(not isinstance(s, int) or
                                isinstance(s, bool) or s <= 0
                                for s in shape):
                raise GraphError(
                    f"Solver.mesh_shape must be a non-empty tuple of "
                    f"positive ints, got {self.mesh_shape!r}")
            want = 1
            for s in shape:
                want *= s
            visible = len(jax.devices())
            if want > visible:
                raise GraphError(
                    f"Solver.mesh_shape={shape} asks for {want} devices "
                    f"but only {visible} are visible; shrink the mesh "
                    f"or force host devices with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={want} "
                    "(set before jax initializes)")
            self.mesh_shape = shape

    def to_train_config(self) -> TrainConfig:
        return TrainConfig(
            learning_rate=self.lr, dense_optimizer=self.optimizer,
            sparse_optimizer=self.sparse_optimizer,
            weight_decay=self.weight_decay, grad_clip=self.grad_clip,
            mixed_precision=self.mixed_precision,
            grad_allreduce_dtype=self.grad_allreduce_dtype)


def CreateSolver(**kwargs) -> Solver:  # noqa: N802 — HugeCTR spelling
    return Solver(**kwargs)


@dataclasses.dataclass
class DataReaderParams:
    """Input source + feature spec. ``synthetic`` draws the stateless
    Zipf CTR stream; ``criteo`` reads the TSV format at ``path``."""
    source: str = "synthetic"
    num_dense_features: int = 13
    path: Optional[str] = None
    seed: int = 0
    zipf_a: float = 1.1

    def __post_init__(self):
        if self.source not in ("synthetic", "criteo"):
            raise GraphError(f"unknown reader source {self.source!r}")


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Input:
    """Declares the named input tensors every other layer wires to."""
    dense_dim: int
    dense_name: str = "dense"
    sparse_name: str = "cat"
    label_name: str = "label"


@dataclasses.dataclass
class SparseEmbedding:
    """One embedding group: a set of tables sharing dim / combiner /
    placement strategy. Repeatable — WDL/DeepFM add a second, dim-1
    group for the wide branch."""
    vocab_sizes: Sequence[int]
    dim: int
    top_name: str = "emb"
    bottom_name: str = "cat"
    #: ids per sample, scalar or per-table
    hotness: Union[int, Sequence[int]] = 1
    combiner: str = "sum"
    strategy: str = "auto"
    hot_fraction: float = 0.05
    table_names: Optional[Sequence[str]] = None

    def __post_init__(self):
        self.vocab_sizes = tuple(int(v) for v in self.vocab_sizes)
        if not isinstance(self.hotness, int):
            self.hotness = tuple(int(h) for h in self.hotness)
        if self.table_names is not None:
            self.table_names = tuple(self.table_names)
            if len(self.table_names) != len(self.vocab_sizes):
                raise GraphError(
                    f"{len(self.table_names)} table_names for "
                    f"{len(self.vocab_sizes)} vocab_sizes")

    def to_tables(self, *, default_prefix: str = ""
                  ) -> Tuple[EmbeddingTableConfig, ...]:
        names = self.table_names or tuple(
            f"{default_prefix}f{i}" for i in range(len(self.vocab_sizes)))
        hot = self.hotness if not isinstance(self.hotness, int) else \
            (self.hotness,) * len(self.vocab_sizes)
        return tuple(
            EmbeddingTableConfig(names[i], v, self.dim, hotness=hot[i],
                                 combiner=self.combiner,
                                 strategy=self.strategy,
                                 hot_fraction=self.hot_fraction)
            for i, v in enumerate(self.vocab_sizes))


DENSE_LAYER_TYPES = ("mlp", "cross", "dot_interaction", "fm", "concat",
                     "sigmoid", "add", "multiply", "relu", "slice",
                     "reduce_sum")


@dataclasses.dataclass
class DenseLayer:
    """One named dense layer, wired by tensor names.

    The full vocabulary and its shape rules are documented in the module
    docstring. Highlights:

    ``mlp``              — MLP over the (implicitly concatenated)
                           bottoms; ``units`` per layer,
                           ``final_activation`` keeps the last ReLU.
    ``cross``            — DCN cross net, ``num_layers`` deep.
    ``dot_interaction``  — DLRM pairwise dots over
                           ``[bottom_mlp_out, emb]``.
    ``fm``               — factorization-machine first+second order term
                           over ``[dense, wide, emb]``.
    ``concat``           — multi-input feature concatenation (3-D
                           embeddings flatten).
    ``add`` / ``multiply`` — elementwise over same-shaped bottoms.
    ``relu``             — elementwise activation.
    ``slice``            — ``[start:stop]`` on the feature axis.
    ``reduce_sum``       — sums all non-batch axes to a logit column.
    ``sigmoid``          — terminal: sums its bottom logits, emits the
                           probability.
    """
    type: str
    bottom_names: Sequence[str]
    top_names: Sequence[str]
    units: Sequence[int] = ()
    num_layers: int = 0                 # cross only
    final_activation: bool = False      # mlp only
    start: int = 0                      # slice only
    stop: int = 0                       # slice only

    def __post_init__(self):
        if self.type not in DENSE_LAYER_TYPES:
            raise GraphError(
                f"unknown DenseLayer type {self.type!r}; expected one "
                f"of {DENSE_LAYER_TYPES}")
        self.bottom_names = tuple(self.bottom_names)
        self.top_names = tuple(self.top_names)
        self.units = tuple(int(u) for u in self.units)
        if len(self.top_names) != 1:
            raise GraphError(
                f"DenseLayer({self.type}) must produce exactly one "
                f"output, got top_names={self.top_names}")

    @property
    def top(self) -> str:
        return self.top_names[0]


# ---------------------------------------------------------------------------
# Lowering: layer graph -> RecsysConfig (generic compile + recognition)
# ---------------------------------------------------------------------------

def _check_embeddings(inp: Input, embs: List[SparseEmbedding]) -> None:
    produced = {inp.dense_name}
    for e in embs:
        if e.bottom_name != inp.sparse_name:
            raise GraphError(
                f"SparseEmbedding {e.top_name!r} reads "
                f"{e.bottom_name!r} but the Input's sparse tensor is "
                f"{inp.sparse_name!r}")
        if e.top_name in produced:
            raise GraphError(f"duplicate tensor name {e.top_name!r}")
        if e.top_name in RESERVED_NAMES or \
                e.top_name.startswith("embedding@"):
            raise GraphError(
                f"SparseEmbedding top_name {e.top_name!r} is reserved "
                "for the embedding parameter groups")
        produced.add(e.top_name)


def _split_embeddings(embs: List[SparseEmbedding]
                      ) -> Tuple[SparseEmbedding,
                                 Optional[SparseEmbedding],
                                 List[SparseEmbedding]]:
    """Split declared groups into (deep, wide, extras).

    The one shape the paper recipes rely on is preserved: exactly TWO
    groups where one is the dim-1 exact twin of the other (same vocab
    sizes, ``combiner="sum"``) classify as deep + wide branch. Every
    other combination lowers as N independent groups: the first
    declared is the primary collection, the rest are extras with their
    own dims, collections and HPS table sets.
    """
    if len(embs) == 1:
        return embs[0], None, []
    if len(embs) == 2:
        wides = [e for e in embs if e.dim == 1]
        if len(wides) == 1:
            wide = wides[0]
            deep = next(e for e in embs if e is not wide)
            if wide.vocab_sizes == deep.vocab_sizes and \
                    wide.combiner == "sum":
                return deep, wide, []
    return embs[0], None, list(embs[1:])


# -- canonical-recipe recognition -------------------------------------------
#
# Recognition is NOT required for execution (any valid DAG compiles);
# it only maps the four paper recipes onto their canonical RecsysConfigs
# so they stay bit-exact with the registry entries, keep their
# historical parameter names, and keep the paper's semantics (e.g. the
# WDL wide head pools the wide branch with fixed weight 1). A graph
# that misses a canonical shape by any detail simply lowers generically.

def _find(layers: List[DenseLayer], type_: str,
          bottoms: Optional[Tuple[str, ...]] = None) -> List[DenseLayer]:
    return [l for l in layers if l.type == type_ and
            (bottoms is None or tuple(l.bottom_names) == tuple(bottoms))]


def _take_sigmoid(layers: List[DenseLayer], logits: Tuple[str, ...],
                  used: List[DenseLayer], *, required: bool) -> bool:
    sigs = _find(layers, "sigmoid")
    if len(sigs) > 1:
        return False
    if not sigs:
        return not required
    # set AND length: a duplicated bottom (e.g. ['logit', 'logit'])
    # means 2x-logit semantics under the generic executor, so it must
    # NOT classify as the canonical recipe
    if len(sigs[0].bottom_names) != len(logits) or \
            set(sigs[0].bottom_names) != set(logits):
        return False
    used.append(sigs[0])
    return True


def _classify_dlrm(name, inp, deep, layers):
    inters = _find(layers, "dot_interaction")
    if len(inters) != 1:
        return None
    inter = inters[0]
    if len(inter.bottom_names) != 2 or \
            inter.bottom_names[1] != deep.top_name:
        return None
    bots = [l for l in layers if l.top == inter.bottom_names[0]]
    if len(bots) != 1:
        return None
    bot = bots[0]
    if bot.type != "mlp" or tuple(bot.bottom_names) != (inp.dense_name,) \
            or not bot.final_activation or not bot.units \
            or bot.units[-1] != deep.dim:
        return None
    used = [bot, inter]
    top_bottoms = (bot.top, inter.top)
    cats = _find(layers, "concat", top_bottoms)
    if cats:
        if len(cats) != 1:
            return None
        used.append(cats[0])
        top_bottoms = (cats[0].top,)
    tops = [l for l in layers if l.type == "mlp" and l is not bot]
    if len(tops) != 1:
        return None
    top = tops[0]
    if tuple(top.bottom_names) != top_bottoms or not top.units or \
            top.units[-1] != 1 or top.final_activation:
        return None
    used.append(top)
    if not _take_sigmoid(layers, (top.top,), used, required=False):
        return None
    if len(used) != len(layers):
        return None
    return RecsysConfig(
        name=name, model="dlrm", tables=deep.to_tables(),
        num_dense_features=inp.dense_dim, bottom_mlp=bot.units,
        top_mlp=top.units, embedding_dim=deep.dim)


def _classify_dcn(name, inp, deep, layers):
    flats = _find(layers, "concat", (inp.dense_name, deep.top_name))
    if len(flats) != 1:
        return None
    flat = flats[0]
    used = [flat]
    crosses = _find(layers, "cross")
    if len(crosses) > 1:
        return None
    crossed = flat.top
    cross = crosses[0] if crosses else None
    if cross is not None:
        if tuple(cross.bottom_names) != (flat.top,):
            return None
        crossed = cross.top
        used.append(cross)
    mlps = [l for l in layers if l.type == "mlp"]
    deeps = [l for l in mlps if tuple(l.bottom_names) == (flat.top,)]
    if len(deeps) != 1:
        return None
    deep_mlp = deeps[0]
    if deep_mlp.final_activation or not deep_mlp.units:
        return None
    used.append(deep_mlp)
    boths = _find(layers, "concat", (crossed, deep_mlp.top))
    if len(boths) != 1:
        return None
    used.append(boths[0])
    combines = [l for l in mlps
                if tuple(l.bottom_names) == (boths[0].top,)]
    if len(combines) != 1:
        return None
    combine = combines[0]
    if combine.units != (1,) or combine.final_activation:
        return None
    used.append(combine)
    if not _take_sigmoid(layers, (combine.top,), used, required=False):
        return None
    if len(used) != len(layers):
        return None
    return RecsysConfig(
        name=name, model="dcn", tables=deep.to_tables(),
        num_dense_features=inp.dense_dim, bottom_mlp=(),
        top_mlp=deep_mlp.units, embedding_dim=deep.dim,
        num_cross_layers=cross.num_layers if cross is not None else 0)


def _classify_flat_deep(inp, deep, layers):
    """The concat + 1-logit deep-tower pair DeepFM and WDL share."""
    flats = _find(layers, "concat", (inp.dense_name, deep.top_name))
    if len(flats) != 1:
        return None
    flat = flats[0]
    deeps = [l for l in layers if l.type == "mlp"
             and tuple(l.bottom_names) == (flat.top,)]
    if len(deeps) != 1:
        return None
    deep_mlp = deeps[0]
    if deep_mlp.final_activation or not deep_mlp.units or \
            deep_mlp.units[-1] != 1:
        return None
    return flat, deep_mlp


def _classify_deepfm(name, inp, deep, wide, layers):
    pair = _classify_flat_deep(inp, deep, layers)
    if pair is None:
        return None
    flat, deep_mlp = pair
    fms = _find(layers, "fm")
    if len(fms) != 1:
        return None
    fm = fms[0]
    if len(fm.bottom_names) != 3 or set(fm.bottom_names) != \
            {inp.dense_name, wide.top_name, deep.top_name}:
        return None
    used = [flat, deep_mlp, fm]
    if not _take_sigmoid(layers, (fm.top, deep_mlp.top), used,
                         required=True):
        return None
    if len(used) != len(layers):
        return None
    return RecsysConfig(
        name=name, model="deepfm", tables=deep.to_tables(),
        num_dense_features=inp.dense_dim, bottom_mlp=(),
        top_mlp=deep_mlp.units[:-1], embedding_dim=deep.dim)


def _classify_wdl(name, inp, deep, wide, layers):
    pair = _classify_flat_deep(inp, deep, layers)
    if pair is None:
        return None
    flat, deep_mlp = pair
    heads = [l for l in layers if l.type == "mlp"
             and set(l.bottom_names) == {inp.dense_name, wide.top_name}]
    if len(heads) != 1:
        return None
    head = heads[0]
    if head.units != (1,) or head.final_activation:
        return None
    used = [flat, deep_mlp, head]
    if not _take_sigmoid(layers, (head.top, deep_mlp.top), used,
                         required=True):
        return None
    if len(used) != len(layers):
        return None
    return RecsysConfig(
        name=name, model="wdl", tables=deep.to_tables(),
        num_dense_features=inp.dense_dim, bottom_mlp=(),
        top_mlp=deep_mlp.units[:-1], embedding_dim=deep.dim)


def _classify_canonical(name, inp, deep, wide, layers):
    types = {l.type for l in layers}
    if types - {"mlp", "cross", "dot_interaction", "fm", "concat",
                "sigmoid"}:
        return None                     # extended vocabulary -> generic
    if "dot_interaction" in types:
        if wide is not None:
            return None
        return _classify_dlrm(name, inp, deep, layers)
    if "fm" in types:
        if wide is None:
            return None
        return _classify_deepfm(name, inp, deep, wide, layers)
    if wide is not None:
        return _classify_wdl(name, inp, deep, wide, layers)
    return _classify_dcn(name, inp, deep, layers)


def lower_graph(name: str, inp: Optional[Input],
                embs: List[SparseEmbedding],
                layers: List[DenseLayer]) -> RecsysConfig:
    """Compile the layer graph: validate the DAG (wiring, shapes, single
    terminal), then lower it — onto the canonical config when it IS one
    of the four paper recipes, onto a generic ``model="graph"`` config
    (DAG embedded) for everything else. :class:`GraphError` names the
    offending layer/tensor on any invalid graph."""
    if inp is None:
        raise GraphError("the graph needs an Input layer")
    if not embs:
        raise GraphError("the graph needs at least one SparseEmbedding")
    _check_embeddings(inp, embs)
    deep, wide, extras = _split_embeddings(embs)
    specs = [spec_from_layer(l) for l in layers]
    extra_embs = {e.top_name: (len(e.vocab_sizes), e.dim)
                  for e in extras}
    # the generic compile IS the validation: every graph must pass it
    compile_layers(
        specs, dense_name=inp.dense_name, num_dense=inp.dense_dim,
        emb_name=deep.top_name, num_tables=len(deep.vocab_sizes),
        emb_dim=deep.dim,
        wide_name=wide.top_name if wide is not None else None,
        extra_embs=extra_embs)
    if not extras:
        cfg = _classify_canonical(name, inp, deep, wide, layers)
        if cfg is not None:
            return cfg
    extra_groups = tuple(
        SparseGroupConfig(
            name=e.top_name,
            tables=e.to_tables(default_prefix=f"{e.top_name}_"),
            dim=e.dim)
        for e in extras)
    all_names = [t.name for t in deep.to_tables()] \
        + [t.name for g in extra_groups for t in g.tables]
    seen = set()
    for tn in all_names:
        if tn in seen:
            raise GraphError(
                f"table name {tn!r} is used by more than one "
                "SparseEmbedding group; table names must be globally "
                "unique (set table_names explicitly)")
        seen.add(tn)
    return RecsysConfig(
        name=name, model="graph", tables=deep.to_tables(),
        num_dense_features=inp.dense_dim, bottom_mlp=(), top_mlp=(),
        embedding_dim=deep.dim,
        dense_graph=graph_spec(
            inp.dense_name, deep.top_name,
            wide.top_name if wide is not None else None, specs,
            extras=tuple(e.top_name for e in extras)),
        wide_branch=wide is not None,
        extra_groups=extra_groups)


# ---------------------------------------------------------------------------
# The model graph
# ---------------------------------------------------------------------------

def _auto_mesh(mesh_shape: Optional[Tuple[int, ...]]):
    from repro.launch.mesh import make_production_mesh, make_test_mesh
    if mesh_shape is not None:
        return make_test_mesh(tuple(mesh_shape))
    n_dev = len(jax.devices())
    return make_test_mesh((n_dev, 1)) if n_dev < 256 \
        else make_production_mesh()


def _validate_mesh_fit(cfg: RecsysConfig, mesh, batch_size: int) -> None:
    """Up-front mesh / batch / table divisibility validation.

    Everything checked here used to surface as an inscrutable shape
    error deep inside ``shard_map`` on the first ``fit()`` step; now it
    raises a :class:`GraphError` at ``compile()`` naming the offending
    axis or table group.
    """
    axes = tuple(mesh.axis_names)
    model_axis = "model" if "model" in axes else axes[-1]
    dp_axes = tuple(a for a in axes if a != model_axis)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    n_dev = int(np.prod(mesh.devices.shape))
    if batch_size % max(1, n_dp) != 0:
        raise GraphError(
            f"batch_size={batch_size} is not divisible by the data-"
            f"parallel device count {n_dp} (mesh axes {dp_axes} of mesh "
            f"shape {dict(mesh.shape)}); batches shard over the data "
            "axes, so pick a batch size the data extent divides")
    from repro.core.embedding.planner import resolve_strategies
    from repro.launch.mesh import mesh_config_for
    from repro.models.recsys.model import wide_tables
    groups = [("emb", cfg.tables)]
    if cfg.model in ("wdl", "deepfm") or \
            (cfg.model == "graph" and cfg.wide_branch):
        groups.append(("wide", wide_tables(cfg)))
    for g in cfg.extra_groups:
        groups.append((g.name, g.tables))
    mc = mesh_config_for(mesh)
    for gname, tabs in groups:
        resolved = resolve_strategies(tabs, mc, batch_size)
        loc = [t for t in resolved if t.strategy == "localized"]
        if loc and len(loc) % n_dev != 0:
            raise GraphError(
                f"embedding group {gname!r}: {len(loc)} localized "
                f"table(s) {[t.name for t in loc]} cannot spread evenly "
                f"over {n_dev} devices; localized placement needs the "
                "table count divisible by the device count")


class Model:
    """A declarative model graph; ``compile()`` lowers it, everything
    else (fit / predict / save / load / deploy) drives the lowered
    stack."""

    def __init__(self, solver: Optional[Solver] = None,
                 reader: Optional[DataReaderParams] = None, *,
                 name: str = "model", mesh=None):
        self.solver = solver or Solver()
        self.reader = reader
        self.name = name
        self._mesh_override = mesh
        self._input: Optional[Input] = None
        self._embeddings: List[SparseEmbedding] = []
        self._dense_layers: List[DenseLayer] = []
        self.cfg: Optional[RecsysConfig] = None
        self.mesh = None
        self._model = None            # lowered RecsysModel
        self._apply_jit = None
        self._tcfg: Optional[TrainConfig] = None
        self._params = None
        self._opt_state = None
        self._trainer = None
        self._online = None           # OnlineTrainer after an ETC fit()
        self.stragglers = 0

    # -- graph construction ---------------------------------------------------

    def add(self, layer) -> "Model":
        if isinstance(layer, Input):
            if self._input is not None:
                raise GraphError("the graph already has an Input layer")
            self._input = layer
        elif isinstance(layer, SparseEmbedding):
            self._embeddings.append(layer)
        elif isinstance(layer, DenseLayer):
            self._dense_layers.append(layer)
        else:
            raise GraphError(
                f"model.add() takes Input, SparseEmbedding or "
                f"DenseLayer, got {type(layer).__name__}")
        return self

    def to_recsys_config(self) -> RecsysConfig:
        """The lowering pass (pure — no devices touched)."""
        return lower_graph(self.name, self._input, self._embeddings,
                           self._dense_layers)

    # -- compile ---------------------------------------------------------------

    def compile(self, *, mesh=None) -> "Model":
        from repro.models.recsys.model import RecsysModel
        self.cfg = self.to_recsys_config()
        if self.reader is not None and \
                self.reader.num_dense_features != self._input.dense_dim:
            raise GraphError(
                f"reader num_dense_features="
                f"{self.reader.num_dense_features} != Input dense_dim="
                f"{self._input.dense_dim}")
        self._tcfg = self.solver.to_train_config()
        self.batch_size = self.solver.batch_size
        self.mesh = mesh or self._mesh_override \
            or _auto_mesh(self.solver.mesh_shape)
        _validate_mesh_fit(self.cfg, self.mesh, self.batch_size)
        with self.mesh:
            self._model = RecsysModel(
                self.cfg, self.mesh, global_batch=self.batch_size,
                comm=self.solver.comm,
                a2a_threshold=self.solver.a2a_threshold)
        self._apply_jit = None        # one jitted forward, built lazily
        return self

    @property
    def model(self):
        """The lowered RecsysModel (compile() first)."""
        return self._model

    @property
    def params(self):
        return self._params

    def _require_compiled(self):
        if self._model is None:
            self.compile()

    # -- train ------------------------------------------------------------------

    def _reader_data_fn(self) -> Callable[[int], Dict]:
        r = self.reader or DataReaderParams(
            num_dense_features=self.cfg.num_dense_features)
        if r.source == "synthetic":
            from repro.data.synthetic import SyntheticCTR
            return SyntheticCTR(self.cfg, self.batch_size, seed=r.seed,
                                zipf_a=r.zipf_a).batch
        from repro.data import criteo
        if r.path is None:
            raise GraphError("DataReaderParams(source='criteo') needs "
                             "a path")
        # seekable batch(step): criteo runs get the same deterministic
        # failure-replay contract as the synthetic reader — the trainer
        # can restore mid-epoch and replay the exact batches
        return criteo.CriteoReader(r.path, self.cfg, self.batch_size).batch

    def fit(self, data_fn: Optional[Callable[[int], Dict]] = None,
            steps: int = 100, *, ckpt_dir: Optional[str] = None,
            log_every: int = 0, seed: Optional[int] = None,
            failure_injector: Optional[Callable[[int], None]] = None
            ) -> List[Dict]:
        """Train; ``data_fn(step) -> {"dense", "cat", "label"}`` host
        batches (defaults to the reader's source). Resumes from a newer
        checkpoint in ``ckpt_dir`` if present, else from weights already
        held (e.g. after :meth:`load`)."""
        self._require_compiled()
        if data_fn is None:
            data_fn = self._reader_data_fn()
        if self.solver.etc is not None:
            return self._fit_etc(data_fn, steps, ckpt_dir=ckpt_dir,
                                 log_every=log_every, seed=seed,
                                 failure_injector=failure_injector)
        from repro.train.trainer import Trainer
        with self.mesh:
            self._trainer = Trainer(
                self._model, self._tcfg, self.mesh, data_fn,
                ckpt_dir=ckpt_dir,
                ckpt_interval=self.solver.ckpt_interval,
                mode=self.solver.mode)
            if failure_injector is not None:
                self._trainer.failure_injector = failure_injector
            init = (self._params, self._opt_state) \
                if self._params is not None else None
            out = self._trainer.train(
                steps, seed=self.solver.seed if seed is None else seed,
                log_every=log_every, initial_state=init)
        self._params = out["params"]
        self._opt_state = out["opt_state"]
        self.stragglers = out["stragglers"]
        return out["history"]

    def _fit_etc(self, data_fn, steps, *, ckpt_dir, log_every, seed,
                 failure_injector, publisher=None) -> List[Dict]:
        """``fit()`` through the Embedding Training Cache (Solver.etc):
        keyset-staged passes over a fixed-capacity device cache, the
        parameter server as the durable tier, and — when ``publisher``
        is attached — one versioned online update per pass boundary.
        After training the PS contents are imported back into
        ``params``, so predict/save/deploy see a normal model."""
        if ckpt_dir is not None:
            raise GraphError(
                "ETC-staged fit() does not take ckpt_dir: durability "
                "goes through the parameter server — use "
                "ETCParams(ps='cached', ps_root=...) instead")
        if failure_injector is not None:
            raise GraphError(
                "ETC-staged fit() does not support failure_injector")
        from repro.online.trainer import OnlineTrainer
        with self.mesh:
            ot = OnlineTrainer(
                self, self.solver.etc, publisher=publisher,
                seed=self.solver.seed if seed is None else seed)
            history = ot.fit(data_fn, steps, log_every=log_every)
            self._params = ot.export_params()
        self._opt_state = None
        self._trainer = None
        self._online = ot
        return history

    # -- inference ----------------------------------------------------------------

    def predict(self, batch: Dict) -> np.ndarray:
        if self._params is None:
            raise RuntimeError("fit() or load() before predict()")
        if self._apply_jit is None:
            self._apply_jit = jax.jit(self._model.apply)
        with self.mesh:
            logits = self._apply_jit(
                self._params,
                {k: jnp.asarray(v) for k, v in batch.items()
                 if k in ("dense", "cat")})
        return np.asarray(jax.nn.sigmoid(logits))

    # -- introspection ------------------------------------------------------------

    def summary(self) -> str:
        cfg = self.to_recsys_config()
        lines = [f'Model "{self.name}" -> {cfg.model} '
                 f'({cfg.num_tables} tables, '
                 f'{cfg.total_embedding_params / 1e6:.2f}M embedding '
                 f'params)']
        i = self._input
        lines.append(f"  Input              {i.dense_name}[{i.dense_dim}]"
                     f" {i.sparse_name} {i.label_name}")
        for e in self._embeddings:
            hot = e.hotness if isinstance(e.hotness, int) \
                else f"{min(e.hotness)}..{max(e.hotness)}"
            lines.append(
                f"  SparseEmbedding    {e.bottom_name} -> {e.top_name}"
                f"  T={len(e.vocab_sizes)} D={e.dim} hot={hot} "
                f"combiner={e.combiner} strategy={e.strategy}")
        for l in self._dense_layers:
            extra = ""
            if l.type == "mlp":
                extra = f"  units={l.units}"
            elif l.type == "cross":
                extra = f"  num_layers={l.num_layers}"
            lines.append(
                f"  DenseLayer {l.type:<15} "
                f"{list(l.bottom_names)} -> {l.top}{extra}")
        out = "\n".join(lines)
        print(out)
        return out

    # -- JSON round-trip ------------------------------------------------------------

    def graph_dict(self) -> Dict:
        layers: List[Dict] = []
        if self._input is not None:
            layers.append({"kind": "input",
                           **dataclasses.asdict(self._input)})
        for e in self._embeddings:
            layers.append({"kind": "sparse_embedding",
                           **dataclasses.asdict(e)})
        for l in self._dense_layers:
            layers.append({"kind": "dense", **dataclasses.asdict(l)})
        return {
            "format": GRAPH_FORMAT,
            "name": self.name,
            "solver": dataclasses.asdict(self.solver),
            "reader": dataclasses.asdict(self.reader)
            if self.reader is not None else None,
            "layers": layers,
            "config_hash": recsys_config_hash(self.to_recsys_config()),
        }

    def graph_to_json(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.graph_dict(), f, indent=1)
        return path

    @classmethod
    def from_json(cls, path: str, *, mesh=None) -> "Model":
        with open(path) as f:
            d = json.load(f)
        if d.get("format") != GRAPH_FORMAT:
            raise GraphError(
                f"{path}: unknown graph format {d.get('format')!r}")
        m = cls(Solver(**d["solver"]),
                DataReaderParams(**d["reader"])
                if d.get("reader") else None,
                name=d["name"], mesh=mesh)
        kinds = {"input": Input, "sparse_embedding": SparseEmbedding,
                 "dense": DenseLayer}
        for ld in d["layers"]:
            ld = dict(ld)
            kind = ld.pop("kind")
            if kind not in kinds:
                raise GraphError(f"{path}: unknown layer kind {kind!r}")
            m.add(kinds[kind](**ld))
        got = recsys_config_hash(m.to_recsys_config())
        if d.get("config_hash") and got != d["config_hash"]:
            raise GraphError(
                f"{path}: graph lowers to config hash {got} but the "
                f"file claims {d['config_hash']} — the file was edited "
                "or written by an incompatible version")
        return m

    # -- persistence -----------------------------------------------------------------

    def _export_params(self, params):
        from repro.models.recsys.model import export_logical_params
        return export_logical_params(self._model, params)

    def _import_params(self, params):
        from repro.models.recsys.model import import_logical_params
        return import_logical_params(self._model, params)

    def save(self, directory: str, step: int = 0) -> str:
        """Write the graph (graph.json) + a logical-layout checkpoint —
        everything :meth:`load` needs to reconstruct the model."""
        if self._params is None:
            raise RuntimeError("nothing to save: fit() or load() first")
        from repro.train import checkpoint as ck
        os.makedirs(directory, exist_ok=True)
        self.graph_to_json(os.path.join(directory, "graph.json"))
        with self.mesh:
            tree = {"params": self._export_params(self._params)}
        ck.save(directory, step, tree)
        return directory

    @classmethod
    def load(cls, directory: str, *, mesh=None) -> "Model":
        """Rebuild a model from :meth:`save` output alone: graph JSON +
        newest checkpoint. ``predict()`` works immediately; ``fit()``
        continues from the loaded weights."""
        from repro.train import checkpoint as ck
        m = cls.from_json(os.path.join(directory, "graph.json"),
                          mesh=mesh)
        m.compile()
        step = ck.latest_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint under {directory}")
        flat, _ = ck.load(directory, step)
        with m.mesh:
            dummy = jax.eval_shape(
                lambda: m._model.init(jax.random.PRNGKey(0)))
            template = {"params": jax.eval_shape(m._export_params,
                                                 dummy)}
            tree = ck.unflatten_like(template, flat)
            m._params = m._import_params(tree["params"])
        return m

    # -- deployment -------------------------------------------------------------------

    def dense_params(self) -> Dict:
        from repro.train.train_step import is_sparse_key
        return {k: v for k, v in self._params.items()
                if not is_sparse_key(k)}

    def _write_bundle_member(self, pdb, bundle_dir: str, sub: str, *,
                             cache_capacity: int, cache_shards: int,
                             refresh_budget: int, max_batch: int,
                             payload_dtype: str = "f32") -> HPSConfig:
        """Export THIS model into a deployment bundle: tables into the
        (possibly shared) PDB, graph.json + dense.npz under
        ``bundle_dir/sub``, returning the relocatable HPSConfig whose
        paths are relative to ``bundle_dir``."""
        from repro.serve.server import deploy_from_training
        from repro.train import checkpoint as ck
        out_dir = os.path.join(bundle_dir, sub) if sub else bundle_dir
        os.makedirs(out_dir, exist_ok=True)
        with self.mesh:
            deploy_from_training(self._model, self._params, pdb,
                                 self.name)
        self.graph_to_json(os.path.join(out_dir, "graph.json"))
        np.savez(os.path.join(out_dir, "dense.npz"),
                 **ck.flatten_tree(self.dense_params()))
        rel = (lambda p: f"{sub}/{p}" if sub else p)
        return HPSConfig(
            model=self.name, pdb_root="pdb", graph_path=rel("graph.json"),
            dense_weights_path=rel("dense.npz"), tables=self.cfg.tables,
            wide=self._model.wide is not None,
            cache_capacity=cache_capacity, cache_shards=cache_shards,
            refresh_budget=refresh_budget, max_batch=max_batch,
            payload_dtype=payload_dtype,
            config_hash=recsys_config_hash(self.cfg))

    def _build_server(self, pdb, hcfg: HPSConfig, dense: Dict, *,
                      vdb=None, bus=None):
        """Stand up the HPS(+wide) + InferenceServer for this model over
        already-populated storage — the ONE place the serving stack is
        wired, shared by in-process ``deploy()``/``deploy_ensemble()``
        and the config-driven ``launch.serve`` rebuild (``dense`` is the
        dense param tree: live for the former, reloaded from the
        bundle's npz for the latter)."""
        from repro.core.hps.hps import HPS
        from repro.launch.mesh import make_cache_mesh
        from repro.models.recsys.model import wide_tables
        from repro.serve.server import InferenceServer
        # striped L1: the stripes spread over as many devices as they tile
        cache_mesh = make_cache_mesh(hcfg.cache_shards) \
            if hcfg.cache_shards > 1 else None
        hps = HPS(self.name, self.cfg.tables, pdb, vdb=vdb, bus=bus,
                  cache_capacity=hcfg.cache_capacity,
                  cache_shards=hcfg.cache_shards, cache_mesh=cache_mesh,
                  payload_dtype=hcfg.payload_dtype)
        wide_hps = None
        if hcfg.wide:
            # the wide branch shares the bus (its *_wide topics mark its
            # own L1 dirty), the VDB namespace and the striping config —
            # otherwise online updates never reach the wide L1
            wide_hps = HPS(self.name, wide_tables(self.cfg), pdb,
                           vdb=vdb, bus=bus,
                           cache_capacity=hcfg.cache_capacity,
                           cache_shards=hcfg.cache_shards,
                           cache_mesh=cache_mesh,
                           payload_dtype=hcfg.payload_dtype)
        # one HPS per extra N-group collection — its tables are derived
        # from the lowered config, so the ps.json schema is unchanged
        extra_hps = {
            g.name: HPS(self.name, g.tables, pdb, vdb=vdb, bus=bus,
                        cache_capacity=hcfg.cache_capacity,
                        cache_shards=hcfg.cache_shards,
                        cache_mesh=cache_mesh,
                        payload_dtype=hcfg.payload_dtype)
            for g in self.cfg.extra_groups}
        return InferenceServer(self._model, dense, hps,
                               wide_hps=wide_hps,
                               extra_hps=extra_hps or None,
                               max_batch=hcfg.max_batch,
                               refresh_budget=hcfg.refresh_budget)

    def deploy(self, directory: str, *, cache_capacity: int = 4096,
               cache_shards: int = 1, refresh_budget: int = 512,
               max_batch: int = 1024, payload_dtype: str = "f32",
               vdb=None, bus=None):
        """Write the serving bundle and return a ready InferenceServer.

        The bundle — ``pdb/`` (every table, wide twins included),
        ``graph.json``, ``dense.npz``, ``ps.json`` — is all
        ``launch/serve.py`` needs: the same server can be reconstructed
        later with no Python object from this process. To serve SEVERAL
        models from one bundle/storage backend, see
        :func:`deploy_ensemble`.

        ``payload_dtype`` sets the L1 storage precision and persists in
        ps.json, so a config-driven rebuild serves the exact same mode:

        * ``"f32"`` (default) — bit-exact with the uncompressed store.
        * ``"f16"`` — half the HBM bytes per resident row; rows downcast
          on insert/refresh and widen to f32 inside the gather.
        * ``"int8"`` — ~4x fewer payload bytes (plus one f32 scale per
          row): rows are per-row absmax-quantized on insert/refresh and
          dequantized INSIDE the fused Pallas gather kernel, so the
          pooled ``[B, T, D]`` output stays f32 and a single jitted
          dispatch. At a fixed HBM budget that is 2-4x more resident hot
          rows — a direct L1 hit-rate (and therefore qps) lever.

        The PDB/VDB always hold full-precision rows; only the L1 payload
        is compressed, and dirty-row refreshes requantize from the
        full-precision lower levels (never from their own rounded rows).
        """
        if self._params is None:
            raise RuntimeError("fit() or load() before deploy()")
        from repro.core.hps.persistent_db import PersistentDB
        os.makedirs(directory, exist_ok=True)
        pdb = PersistentDB(os.path.join(directory, "pdb"))
        hcfg = self._write_bundle_member(
            pdb, directory, "", cache_capacity=cache_capacity,
            cache_shards=cache_shards, refresh_budget=refresh_budget,
            max_batch=max_batch, payload_dtype=payload_dtype)
        with open(os.path.join(directory, "ps.json"), "w") as f:
            json.dump(hps_config_to_dict(hcfg), f, indent=1)
        return self._build_server(pdb, hcfg, self.dense_params(),
                                  vdb=vdb, bus=bus)


# ---------------------------------------------------------------------------
# Ensemble deployment: several models, one storage backend
# ---------------------------------------------------------------------------

def _hotness_demand(tables) -> int:
    """A model's L1 working-set proxy from its table hotness stats:
    ids per sample x expected hot rows (the ``hot_fraction`` share of
    each vocab the planner already treats as the hot set)."""
    return max(1, sum(
        t.hotness * max(1, min(t.vocab_size,
                               round(t.vocab_size * t.hot_fraction)))
        for t in tables))


def hotness_cache_capacities(models: Sequence["Model"],
                             budget: int) -> Dict[str, int]:
    """Split one total L1 row ``budget`` across ensemble members in
    proportion to their table-hotness working sets (each model gets at
    least 64 rows so a cold member still serves)."""
    demand = {m.name: _hotness_demand(m.cfg.all_tables) for m in models}
    total = sum(demand.values())
    return {name: max(64, int(round(budget * d / total)))
            for name, d in demand.items()}


def deploy_ensemble(models: Sequence[Model], directory: str, *,
                    cache_capacity: Union[int, Dict[str, int],
                                          None] = None,
                    cache_budget: Optional[int] = None,
                    cache_shards: int = 1,
                    refresh_budget: int = 512, max_batch: int = 1024,
                    payload_dtype: str = "f32",
                    rebalance_interval_s: Optional[float] = None,
                    vdb=None, bus=None):
    """Write ONE multi-model serving bundle and return a ready
    :class:`~repro.serve.server.MultiModelServer`.

    All member models' tables land in a single shared ``pdb/`` (the PDB
    namespaces tables per model on disk) and the in-process server
    shares one VolatileDB and one message bus across models — the
    ensemble deployment unit of the GPU-specialized inference parameter
    server (arXiv 2210.08804): one parameter-server process, several
    models, per-model L1 caches. The bundle's ``ps.json`` holds one
    :class:`EnsembleConfig` (several HPSConfigs, shared ``pdb_root``)
    and ``launch/serve.py::build_server_from_config`` reconstructs the
    whole multi-model server from it, bit-exact with per-model
    in-process servers.

    Per-model L1 sizing: by default the total row budget
    (``cache_budget``, default ``4096 * len(models)``) is split across
    members in proportion to their table-hotness working sets
    (:func:`hotness_cache_capacities`) instead of handing every model
    one global knob. Explicit overrides still work: pass
    ``cache_capacity=<int>`` for a uniform per-model capacity, or a
    ``{model_name: rows}`` dict to pin specific members (unpinned ones
    keep their hotness share).

    ``rebalance_interval_s`` (opt-in, default off) re-splits that shared
    row budget periodically from *observed* per-model L1 miss pressure
    instead of the static declared hotness: the serving loop feeds the
    :class:`~repro.serve.server.MultiModelServer` rebalancer, which
    resizes member caches (hottest rows retained) at most once per
    interval. Leave it ``None`` for latency-critical serving — a resize
    recompiles the pooled gather for the new payload shape.

    ``payload_dtype`` applies to every member's L1 (see
    :meth:`Model.deploy` for the precision modes).
    """
    from repro.core.hps.message_bus import MessageBus
    from repro.core.hps.persistent_db import PersistentDB
    from repro.core.hps.volatile_db import VolatileDB
    from repro.serve.server import MultiModelServer
    if not models:
        raise GraphError("deploy_ensemble needs at least one model")
    names = [m.name for m in models]
    if len(set(names)) != len(names):
        raise GraphError(f"ensemble model names must be unique: {names}")
    for m in models:
        if m._params is None:
            raise RuntimeError(
                f"model {m.name!r}: fit() or load() before deploy")
    for m in models:
        m._require_compiled()
    budget = cache_budget if cache_budget is not None \
        else 4096 * len(models)
    capacities = hotness_cache_capacities(models, budget)
    if isinstance(cache_capacity, int):
        capacities = {m.name: cache_capacity for m in models}
    elif isinstance(cache_capacity, dict):
        unknown = set(cache_capacity) - {m.name for m in models}
        if unknown:
            raise GraphError(
                f"cache_capacity overrides for unknown models: "
                f"{sorted(unknown)}")
        capacities.update(cache_capacity)
    os.makedirs(directory, exist_ok=True)
    pdb = PersistentDB(os.path.join(directory, "pdb"))   # shared L3
    vdb = vdb if vdb is not None else VolatileDB()       # shared L2
    bus = bus if bus is not None else MessageBus()       # shared bus
    hcfgs = []
    servers = {}
    for m in models:
        hcfg = m._write_bundle_member(
            pdb, directory, m.name, cache_capacity=capacities[m.name],
            cache_shards=cache_shards, refresh_budget=refresh_budget,
            max_batch=max_batch, payload_dtype=payload_dtype)
        hcfgs.append(hcfg)
        servers[m.name] = m._build_server(pdb, hcfg, m.dense_params(),
                                          vdb=vdb, bus=bus)
    ens = EnsembleConfig(models=tuple(hcfgs))
    with open(os.path.join(directory, "ps.json"), "w") as f:
        json.dump(ensemble_config_to_dict(ens), f, indent=1)
    return MultiModelServer(servers, vdb=vdb, pdb=pdb, bus=bus,
                            cache_budget=budget,
                            rebalance_interval_s=rebalance_interval_s)
