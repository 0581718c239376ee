"""The control, the reference computed one precision below the
configuration's (float8 where it computes in bfloat16) in the program's
place, reads ``correct`` false by the run's own comparison, in every cell,
at the rehearsal sizes."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402
import rehearse  # noqa: E402

WORKLOADS = rehearse.cells()
CELLS = list(WORKLOADS)


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_incorrect(cell, tmp_path):
    rc, out, err = rehearse.run_cell(cell, chips=WORKLOADS[cell]["chips"],
                                     root=rehearse.root_for(cell,
                                                            str(tmp_path)),
                                     work_dir=str(tmp_path / "w"),
                                     prelude=faults.prelude("control"))
    assert rc == 0, err[-3000:]
    res = rehearse.last_json(out)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
