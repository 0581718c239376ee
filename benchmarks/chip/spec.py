"""Finding a cell's pieces by name.

``BENCHMARK.json`` names the cells; each cell names a configuration
(``configs/<config>.json`` and its reference ``configs/<config>.py``) and a
traffic mix (``traffic/<mix>.json``), whose ``kind`` names the generator
(``traffic/<kind>.py``). A per-layer metric ``<name>`` is read by
``metrics/<name>.py``, or, where that file does not exist, by
``metrics/<base>.py`` for ``<base>.<variant>`` names. Adding a cell, a mix or
a metric adds files and entries; no existing file changes.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: Dict, over: Dict) -> Dict:
    """``over`` merged into a copy of ``base``, nested dicts key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    kind: object                       # the generator module
    end_to_end: List[Dict]             # metric entries this cell reports
    per_layer: List[Dict]
    readers: Dict[str, object] = field(default_factory=dict)


def reports(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, *, rehearse: bool = False) -> Cell:
    """Resolve cell ``name`` of the checkout this file lies in. With
    ``rehearse`` the configuration's and the mix's ``rehearsal`` blocks are
    merged in (tiny CPU sizes)."""
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = _read(os.path.join(ROOT, confs[w["config"]]["file"]))
    mix = _read(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    if rehearse:
        config = merge(config, config.get("rehearsal", {}))
        mix = merge(mix, mix.get("rehearsal", {}))
    kind = load_module(os.path.join(HERE, "traffic", f"{mix['kind']}.py"),
                       f"traffic_{mix['kind']}")
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"] if reports(m, name, e2e_names)]
    readers = {}
    for m in layer:
        path = os.path.join(HERE, "metrics", f"{m['name']}.py")
        if not os.path.exists(path) and "." in m["name"]:
            base = m["name"].split(".", 1)[0]
            path = os.path.join(HERE, "metrics", f"{base}.py")
        readers[m["name"]] = load_module(
            path, "metric_" + m["name"].replace(".", "_").replace("-", "_"))
    return Cell(name, int(w["chips"]), config, mix, kind, e2e, layer,
                readers)
