"""A run with the timed path broken underneath reads ``correct`` false:
one fault of each kind a cell can have, planted in the program before the
run, at the rehearsal sizes (the harness's look for a chip is skipped)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import rehearse  # noqa: E402

CASES = [
    ("dcn-serve-zipf", 1, "answer_altered", "pred_max_abs_dev"),
    ("dcn-serve-saturate", 1, "answer_altered", "pred_max_abs_dev"),
    ("dcn-train", 1, "state_unchanged", "change_gap"),
    ("dcn-train", 1, "half_batch", None),
    ("dlrm-train-mp4", 4, "exchange_left_out", None),
]


@pytest.mark.parametrize("cell,chips,fault,number", CASES,
                         ids=[f"{c}-{f}" for c, _, f, _ in CASES])
def test_fault_reads_incorrect(cell, chips, fault, number, tmp_path):
    rc, out, err = rehearse.run_cell(cell, chips=chips,
                                     root=rehearse.root_for(cell,
                                                            str(tmp_path)),
                                     work_dir=str(tmp_path / "w"),
                                     prelude=faults.prelude(fault))
    assert rc == 0, err[-3000:]
    res = rehearse.last_json(out)
    assert res["correct"] is False, res["checks"]
    over = [k for k, c in res["checks"].items()
            if c["value"] > c["limit"]]
    assert over, res["checks"]
    if number is not None:
        assert number in over, res["checks"]
