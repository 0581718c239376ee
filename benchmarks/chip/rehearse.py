"""Running a cell's command on the CPU for the tests: a child process (a
cell may need several virtual devices), optionally with a fault planted in
the program before the run starts."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from typing import Dict, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

#: the child runs the command's own entry point after ``prelude``
_CHILD = """
import runpy, sys
{prelude}
sys.argv = {argv!r}
runpy.run_path({run!r}, run_name="__main__")
"""


def run_cell(workload: str, *, seed: int = 20261017, seconds: int = 1,
             trace: int = 0, work_dir: str, rehearse: bool = True,
             chips: int = 1,
             prelude: str = "", root: str = ROOT,
             env: Optional[Dict[str, str]] = None, timeout: float = 600):
    """Run ``workload`` once; returns ``(returncode, stdout, stderr)``.
    ``chips``: the CPU devices a rehearsal needs (the cell's chips)."""
    run = os.path.join(root, "benchmarks", "chip", "run.py")
    argv = [run, "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace),
            "--work-dir", work_dir] + (["--rehearse"] if rehearse else [])
    child_env = dict(os.environ)
    child_env.pop("XLA_FLAGS", None)
    child_env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")] +
        [p for p in [os.environ.get("PYTHONPATH")] if p])
    if rehearse:            # set before a prelude can import JAX
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={chips}"
    child_env.update(env or {})
    code = _CHILD.format(prelude=prelude, argv=argv, run=run)
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       env=child_env, capture_output=True, text=True,
                       timeout=timeout)
    return p.returncode, p.stdout, p.stderr


#: cells whose files the benchmark keeps for a later PR but whose entries
#: ``BENCHMARK.json`` does not hold yet; the tests run them from a copy of
#: the benchmark (``checkout``) that adds the entries
KEPT = {
    "dcn-serve-zipf": {
        "workload": {
            "name": "dcn-serve-zipf", "config": "dcn-criteo",
            "traffic": "open-zipf", "chips": 1,
            "why": "open loop, 128-2048 rows, Zipf 1.1 ids"},
        "end_to_end": [{
            "name": "serve_p95_ms", "unit": "ms", "better": "lower",
            "bound": 0.25, "source": "host_clock",
            "workloads": ["dcn-serve-zipf"]}],
    },
    "dlrm-train-mp4": {
        "workload": {
            "name": "dlrm-train-mp4", "config": "dlrm-criteo",
            "traffic": "train-zipf", "chips": 4,
            "why": "fit at batch 55296 on a 2x2 mesh"},
        "end_to_end": [{
            "name": "train_samples_per_s", "unit": "samples/s",
            "better": "higher", "bound": 0.25, "source": "host_clock",
            "workloads": ["dlrm-train-mp4"]}],
        "config": {
            "name": "dlrm-criteo", "source": "https://arxiv.org/abs/1906.00091",
            "file": "benchmarks/chip/configs/dlrm-criteo.json",
            "reduced": ["vocab_sizes", "training"],
            "why": "model-parallel tables"},
    },
}


def read_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def checkout(dst: str, workloads: Sequence[Dict] = (),
             end_to_end: Sequence[Dict] = (),
             configs: Sequence[Dict] = ()) -> Dict:
    """``BENCHMARK.json`` and the files under its paths, nothing else,
    copied to ``dst``, with each cell and configuration given that it
    lacks added, and each end-to-end metric given added or, where it has
    one of that name, its cells added to that one's; returns the copy's
    ``BENCHMARK.json``."""
    bench = read_benchmark()
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns("_work",
                                                      "__pycache__"))
    have = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [w for w in workloads if w["name"] not in have]
    have = {c["name"] for c in bench["configs"]}
    bench["configs"] += [c for c in configs if c["name"] not in have]
    have = {m["name"]: m for m in bench["end_to_end"]}
    for m in end_to_end:
        if m["name"] not in have:
            bench["end_to_end"].append(m)
        elif "workloads" in have[m["name"]]:
            mine = have[m["name"]]["workloads"]
            mine += [w for w in m["workloads"] if w not in mine]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return bench


def root_for(cell: str, tmp: str) -> str:
    """The checkout that runs ``cell``: this one, or for a kept cell a
    copy under ``tmp`` that adds its entries."""
    if cell in {w["name"] for w in read_benchmark()["workloads"]}:
        return ROOT
    kept = KEPT[cell]
    root = os.path.join(tmp, "checkout")
    if not os.path.exists(root):
        os.makedirs(root)
        checkout(root, [kept["workload"]], kept["end_to_end"],
                 [kept["config"]] if "config" in kept else [])
    return root


def cells() -> Dict[str, Dict]:
    """Every cell the tests rehearse: ``BENCHMARK.json``'s and the kept
    ones, by name."""
    out = {w["name"]: w for w in read_benchmark()["workloads"]}
    for name, kept in KEPT.items():
        out.setdefault(name, kept["workload"])
    return out


def last_json(stdout: str) -> Dict:
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1])


RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def check_result(out: Dict, *, chips: int, trace: int,
                 metrics: Sequence[str]) -> None:
    """The last line's keys in their documented order, ``checks`` last."""
    keys = list(out)
    assert keys[:5] == list(RESULT_KEYS), keys
    assert keys[-1] == "checks", keys
    dev = out["device"]
    for k in DEVICE_KEYS:
        assert k in dev, dev
    assert dev["platform"] == "cpu" and dev["kind"] == "cpu"
    assert dev["count"] == chips
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    for name in metrics:
        m = out["metrics"][name]
        assert set(m) == {"value", "unit"}, m
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}, c
