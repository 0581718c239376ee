"""Operations and bytes the benchmark's layers need, from config shapes.

These are the yardstick's own counts: model FLOPs of the dense nets (what
the architecture requires per sample, not what an implementation spends;
each configuration's module counts its own) and the bytes a pooled
embedding lookup has to move. Each takes the
configuration file's dict, so it never depends on the program under test.
"""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))

#: bytes of one stored value, per L1 payload dtype
_VALUE_BYTES = {"f32": 4, "f16": 2, "int8": 1}
#: per-row side data: int8 rows carry one f32 dequantization scale
_ROW_EXTRA = {"f32": 0, "f16": 0, "int8": 4}


def dense_forward_flops(cfg: Dict) -> int:
    """Dense-net forward FLOPs per sample, as the configuration's own
    module (``configs/<config>.py``) counts them from its shapes."""
    import refcommon
    return int(refcommon.load_reference(cfg["name"]).dense_forward_flops(
        cfg))


def train_flops_per_sample(cfg: Dict) -> int:
    """Forward and backward of the dense net: 3x the forward (the
    backward computes the input and the weight gradients)."""
    return 3 * dense_forward_flops(cfg)


def gather_bytes_per_row(cfg: Dict, payload_dtype: str) -> int:
    """HBM bytes one served row needs from the pooled L1 lookup: every
    table's gathered payload rows (with their scales), the int32 slot
    indices, and the f32 ``[T, D]`` pooled output. Independent of how the
    gather is implemented."""
    if payload_dtype not in _VALUE_BYTES:
        raise ValueError(f"unknown payload dtype {payload_dtype!r}")
    t = len(cfg["vocab_sizes"])
    d = cfg["embedding_dim"]
    hot = cfg.get("hotness", 1)
    row = d * _VALUE_BYTES[payload_dtype] + _ROW_EXTRA[payload_dtype]
    return t * (hot * (row + 4) + d * 4)


def load_peaks(path: str = os.path.join(HERE, "peaks.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


def peak(device_kind: str, key: str, peaks: Dict = None) -> float:
    """One published peak of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = (peaks or load_peaks())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][key])
