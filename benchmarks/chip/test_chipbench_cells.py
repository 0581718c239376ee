"""Every cell's command, rehearsed on the CPU at its tiny sizes: the last
line's keys, the device it names, and the cell's metrics."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import rehearse  # noqa: E402

CELLS = rehearse.cells()


def _expected(bench, cell, trace):
    if not trace:
        return [m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    # the CPU has no peaks: rooflines and utilizations stay unreported
    return [m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])
            and "roofline" not in m["name"] and "mfu" not in m["name"]
            and not m["name"].startswith("mp_collective")]


@pytest.mark.parametrize("cell", list(CELLS))
def test_cell_rehearsal(cell, tmp_path):
    root = rehearse.root_for(cell, str(tmp_path))
    rc, out, err = rehearse.run_cell(cell, root=root,
                                     work_dir=str(tmp_path / "w"),
                                     chips=CELLS[cell]["chips"])
    assert rc == 0, err[-3000:]
    res = rehearse.last_json(out)
    names = _expected(rehearse.read_benchmark(root), cell, 0)
    rehearse.check_result(res, chips=CELLS[cell]["chips"], trace=0,
                          metrics=names)
    assert set(res["metrics"]) == set(names)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # the compared numbers close standard error, each with its limit
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(ln.startswith("check ") and "(limit " in ln for ln in tail)


def test_traced_rehearsal_reports_layers(tmp_path):
    cell = "dcn-serve-saturate"
    rc, out, err = rehearse.run_cell(cell, trace=1, seconds=2,
                                     work_dir=str(tmp_path))
    assert rc == 0, err[-3000:]
    res = rehearse.last_json(out)
    names = _expected(rehearse.read_benchmark(), cell, 1)
    rehearse.check_result(res, chips=1, trace=1, metrics=names)
    assert set(names) <= set(res["metrics"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
