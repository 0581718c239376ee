"""The yardstick's counts against hand counts, and the table of peaks."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import work  # noqa: E402


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_dcn_forward_flops_by_hand():
    # x0 = 13 + 26 * 16 = 429 features
    cross = 6 * 5 * 429                      # x.w (2) + x0*s + b + x (3)
    deep = 2 * (429 * 1024 + 1024 * 1024)
    combine = 2 * (429 + 1024) * 1
    assert work.dense_forward_flops(_config("dcn-criteo")) == \
        cross + deep + combine == 2_991_520
    assert work.train_flops_per_sample(_config("dcn-criteo")) == \
        3 * 2_991_520


def test_dlrm_forward_flops_by_hand():
    bottom = 2 * (13 * 512 + 512 * 256 + 256 * 128)
    dots = 2 * (27 * 26 // 2) * 128         # 351 pairs of 128-wide dots
    top = 2 * (479 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
    assert work.dense_forward_flops(_config("dlrm-criteo")) == \
        bottom + dots + top == 4_820_224


@pytest.mark.parametrize("dtype,row", [("f32", 64), ("f16", 32),
                                       ("int8", 16 + 4)])
def test_gather_bytes_per_row(dtype, row):
    # per table: one payload row (+ scale), one int32 slot, 16 f32 out
    want = 26 * (row + 4 + 16 * 4)
    assert work.gather_bytes_per_row(_config("dcn-criteo"), dtype) == want


def test_gather_bytes_depend_only_on_shapes():
    cfg = _config("dcn-criteo")
    other = dict(cfg, serving={"cache_capacity": 7, "max_batch": 1})
    for dtype in ("f32", "f16", "int8"):
        assert work.gather_bytes_per_row(cfg, dtype) == \
            work.gather_bytes_per_row(other, dtype)
    with pytest.raises(ValueError):
        work.gather_bytes_per_row(cfg, "bf16")


def test_peaks_table():
    peaks = work.load_peaks()
    assert peaks["source"]
    assert work.peak("TPU v5 lite", "bf16_flops_per_s", peaks) == 197e12
    assert work.peak("TPU v5 lite", "hbm_bytes_per_s", peaks) == 819e9
    assert work.peak("TPU v5 lite", "hbm_bytes", peaks) == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        work.peak("TPU v9 imaginary", "bf16_flops_per_s")
