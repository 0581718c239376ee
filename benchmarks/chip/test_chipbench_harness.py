"""The harness itself: no result without a TPU, no result outside a full
checkout, and new configurations, mixes and metrics as files alone."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import rehearse  # noqa: E402


def test_no_tpu_no_result(tmp_path):
    rc, out, err = rehearse.run_cell(
        "dcn-serve-saturate", rehearse=False, work_dir=str(tmp_path),
        env={"JAX_PLATFORMS": "cpu"})
    assert rc != 0
    assert out.strip() == ""
    assert "TPU" in err


def _copy_benchmark(dst):
    """``BENCHMARK.json`` and the files under its paths, nothing else."""
    return rehearse.checkout(dst)


def test_fails_without_the_program(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    _copy_benchmark(str(root))
    rc, out, err = rehearse.run_cell(
        "dcn-serve-saturate", root=str(root), work_dir=str(tmp_path / "w"),
        env={"PYTHONPATH": ""})
    assert rc != 0
    assert out.strip() == ""


_METRIC = '''"""A throwaway reader: the window's L1 misses per delivered row."""
SOURCE = "program_counter"


def read(run, trace, peaks, kind):
    return run.layer["l1_misses"] / max(1, run.layer["rows_delivered"])
'''


def test_new_config_mix_and_metric_are_files(tmp_path):
    """A copy of the benchmark gains a configuration, a mix, a metric and
    a cell by adding files and entries only; every existing file stays
    byte for byte, and the new cell runs."""
    root = tmp_path / "checkout"
    root.mkdir()
    bench = rehearse.checkout(
        str(root), end_to_end=rehearse.KEPT["dcn-serve-zipf"]["end_to_end"])
    chip = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}

    with open(chip / "configs" / "dcn-criteo.json") as f:
        cfg = json.load(f)
    cfg["name"] = "dcn-tiny"
    cfg["serving"]["payload_dtype"] = "f32"
    (chip / "configs" / "dcn-tiny.json").write_text(json.dumps(cfg))
    shutil.copy(chip / "configs" / "dcn-criteo.py",
                chip / "configs" / "dcn-tiny.py")
    with open(chip / "traffic" / "open-zipf.json") as f:
        mix = json.load(f)
    mix["zipf_a"] = 1.3
    mix["rehearsal"]["rate_rps"] = 4.0
    (chip / "traffic" / "open-steep.json").write_text(json.dumps(mix))
    (chip / "metrics" / "misses_per_row.py").write_text(_METRIC)
    bench["configs"].append({
        "name": "dcn-tiny", "source": "https://arxiv.org/abs/1708.05123",
        "file": "benchmarks/chip/configs/dcn-tiny.json", "reduced": [],
        "why": "throwaway"})
    bench["workloads"].append({
        "name": "dcn-tiny-steep", "config": "dcn-tiny",
        "traffic": "open-steep", "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({
        "name": "misses_per_row", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "throwaway",
        "moves": "serve_p95_ms", "workloads": ["dcn-tiny-steep"]})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_p95_ms":
            m["workloads"].append("dcn-tiny-steep")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    for trace in (0, 1):
        rc, out, err = rehearse.run_cell(
            "dcn-tiny-steep", root=str(root), trace=trace,
            work_dir=str(tmp_path / "w"))
        assert rc == 0, err[-3000:]
        res = rehearse.last_json(out)
        want = ["misses_per_row"] if trace else ["serve_p95_ms", "setup_s"]
        rehearse.check_result(res, chips=1, trace=trace, metrics=want)
        assert res["correct"] is True, res["checks"]
    assert {p: p.read_bytes() for p in before} == before


_CROSSDEEP = '''"""A throwaway architecture: cross and deep branches with a logit head
each, and a linear head over the first four dense features; the sigmoid
takes the three logits' sum."""
import jax.numpy as jnp


def declare(cfg, solver=None, reader=None):
    from repro.api import DenseLayer, Input, Model, SparseEmbedding
    m = Model(solver, reader, name=cfg["name"])
    m.add(Input(dense_dim=cfg["num_dense_features"]))
    m.add(SparseEmbedding(vocab_sizes=cfg["vocab_sizes"],
                          dim=cfg["embedding_dim"], top_name="emb",
                          table_names=cfg["table_names"]))
    m.add(DenseLayer("concat", ["dense", "emb"], ["flat"]))
    m.add(DenseLayer("cross", ["flat"], ["crossed"], num_layers=2))
    m.add(DenseLayer("mlp", ["crossed"], ["cross_logit"], units=(1,)))
    m.add(DenseLayer("mlp", ["flat"], ["deep_h"], units=cfg["deep_mlp"],
                     final_activation=True))
    m.add(DenseLayer("mlp", ["deep_h"], ["deep_logit"], units=(1,)))
    m.add(DenseLayer("slice", ["dense"], ["dense_lo"], start=0, stop=4))
    m.add(DenseLayer("mlp", ["dense_lo"], ["lin_logit"], units=(1,)))
    m.add(DenseLayer("sigmoid", ["cross_logit", "deep_logit", "lin_logit"],
                     ["prob"]))
    return m


def _width(cfg):
    return cfg["num_dense_features"] + len(cfg["vocab_sizes"]) * \\
        cfg["embedding_dim"]


def dense_forward_flops(cfg):
    x, d = _width(cfg), cfg["deep_mlp"]
    return 2 * 5 * x + 2 * x + 2 * (x * d[0] + d[0] * d[1]) + 2 * d[1] + 8


def param_spec(cfg):
    x, d = _width(cfg), cfg["deep_mlp"]
    return {"crossed/w0": (x,), "crossed/b0": (x,), "crossed/w1": (x,),
            "crossed/b1": (x,), "cross_logit/w0": (x, 1),
            "cross_logit/b0": (1,), "deep_h/w0": (x, d[0]),
            "deep_h/b0": (d[0],), "deep_h/w1": (d[0], d[1]),
            "deep_h/b1": (d[1],), "deep_logit/w0": (d[1], 1),
            "deep_logit/b0": (1,), "lin_logit/w0": (4, 1),
            "lin_logit/b0": (1,)}


def forward(p, dense, emb, q, dot):
    b = dense.shape[0]
    x0 = q(jnp.concatenate([dense, q(emb).reshape(b, -1)], axis=1))
    x = x0
    for i in range(2):
        xw = q(dot(x, p["crossed"][f"w{i}"]))
        x = q(x0 * xw[:, None] + q(p["crossed"][f"b{i}"]) + x)
    h = x0
    for i in range(2):
        h = q(jnp.maximum(dot(h, p["deep_h"][f"w{i}"])
                          + p["deep_h"][f"b{i}"], 0.0))

    def head(name, v):
        return dot(v, p[name]["w0"])[:, 0] + p[name]["b0"][0]

    return (head("cross_logit", x) + head("deep_logit", h)
            + head("lin_logit", q(dense[:, :4])))
'''


_WDL = '''"""A throwaway architecture with two embedding collections: Wide &
Deep, a ReLU deep tower over ``[dense, embeddings]`` with a 1-unit head,
plus a wide logit, the sum of each table's dim-1 twin (``extra``) and a
linear term over the dense features."""
import jax.numpy as jnp


def declare(cfg, solver=None, reader=None):
    from repro.api import DenseLayer, Input, Model, SparseEmbedding
    m = Model(solver, reader, name=cfg["name"])
    m.add(Input(dense_dim=cfg["num_dense_features"]))
    m.add(SparseEmbedding(vocab_sizes=cfg["vocab_sizes"],
                          dim=cfg["embedding_dim"], top_name="emb",
                          table_names=cfg["table_names"]))
    m.add(SparseEmbedding(vocab_sizes=cfg["vocab_sizes"], dim=1,
                          top_name="wide"))
    m.add(DenseLayer("concat", ["dense", "emb"], ["flat"]))
    m.add(DenseLayer("mlp", ["flat"], ["deep_out"],
                     units=tuple(cfg["deep_mlp"]) + (1,)))
    m.add(DenseLayer("mlp", ["dense", "wide"], ["wide_out"], units=(1,)))
    m.add(DenseLayer("sigmoid", ["wide_out", "deep_out"], ["prob"]))
    return m


def dense_forward_flops(cfg):
    x = cfg["num_dense_features"] + len(cfg["vocab_sizes"]) * \\
        cfg["embedding_dim"]
    units, flops = list(cfg["deep_mlp"]) + [1], 0
    for u in units:
        flops, x = flops + 2 * x * u, u
    return flops + 2 * cfg["num_dense_features"]


def param_spec(cfg):
    x = cfg["num_dense_features"] + len(cfg["vocab_sizes"]) * \\
        cfg["embedding_dim"]
    spec = {}
    for i, u in enumerate(list(cfg["deep_mlp"]) + [1]):
        spec[f"deep/w{i}"], spec[f"deep/b{i}"], x = (x, u), (u,), u
    spec["dense_w"] = (cfg["num_dense_features"],)
    spec["bias"] = ()
    return spec


def forward(p, dense, emb, q, dot, extra):
    b = dense.shape[0]
    h = q(jnp.concatenate([dense, q(emb).reshape(b, -1)], axis=1))
    n = len(p["deep"]) // 2
    for i in range(n):
        h = dot(h, p["deep"][f"w{i}"]) + p["deep"][f"b{i}"]
        h = q(jnp.maximum(h, 0.0) if i < n - 1 else h)
    wide = sum(extra.values())[:, 0] + (dense * p["dense_w"]).sum(1)
    return h[:, 0] + wide + p["bias"]
'''

_TINY = {"vocab_sizes": [1000, 584, 1000, 306],
         "table_names": ["C1", "C2", "C3", "C4"], "embedding_dim": 16,
         "num_dense_features": 13, "deep_mlp": [32, 16],
         "serving": {"cache_capacity": 256, "payload_dtype": "f32",
                     "max_batch": 256}}

#: architecture -> (its configuration, its module, the cells whose
#: traffic and end-to-end metrics its own cells take)
ARCHS = {
    "crossdeep": (dict(_TINY, recipe="repro.configs.crossdeep_criteo",
                       model="graph", limits={"pred_max_abs_dev": 0.02}),
                  _CROSSDEEP, ["dcn-serve-saturate"]),
    "wdl": (dict(_TINY, recipe="repro.configs.wdl_criteo", model="wdl",
                 extra_tables=[{"name": f"C{i + 1}_wide", "vocab": v,
                                "dim": 1, "column": i}
                               for i, v in enumerate(_TINY["vocab_sizes"])],
                 training={"batch": 256, "mesh": [1, 1], "lr": 0.001,
                           "optimizer": "adamw",
                           "sparse_optimizer": "rowwise_adagrad",
                           "beta1": 0.9, "beta2": 0.95, "eps": 1e-08,
                           "weight_decay": 0.0, "grad_clip": 1.0,
                           "mode": "gspmd", "comm": "auto"},
                 limits={"pred_max_abs_dev": 0.02, "grad_gap": 0.1,
                         "change_gap": 0.1}),
            _WDL, ["dcn-serve-saturate", "dcn-train"]),
}


@pytest.mark.parametrize("arch", list(ARCHS))
def test_new_architecture_is_files(arch, tmp_path):
    """A configuration of an architecture the harness has no code for (a
    graph of cross and deep heads; Wide & Deep with a second embedding
    collection) runs, served and trained, from its two files and its
    entries, with every existing file left byte for byte."""
    cfg, module, like = ARCHS[arch]
    name = f"{arch}-tiny"
    root = tmp_path / "checkout"
    root.mkdir()
    bench = _copy_benchmark(str(root))
    chip = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    (chip / "configs" / f"{name}.json").write_text(
        json.dumps(dict(cfg, name=name)))
    (chip / "configs" / f"{name}.py").write_text(module)
    bench["configs"].append({
        "name": name, "source": "throwaway",
        "file": f"benchmarks/chip/configs/{name}.json",
        "reduced": [], "why": "throwaway"})
    cells = []
    for other in like:
        w = next(w for w in bench["workloads"] if w["name"] == other)
        cell = f"{name}-{w['traffic']}"
        cells.append(cell)
        bench["workloads"].append(dict(w, name=cell, config=name))
        for m in bench["end_to_end"]:
            if other in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for cell in cells:
        rc, out, err = rehearse.run_cell(cell, root=str(root),
                                         work_dir=str(tmp_path / "w"))
        assert rc == 0, err[-3000:]
        res = rehearse.last_json(out)
        assert res["correct"] is True, res["checks"]
        assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert {p: p.read_bytes() for p in before} == before
