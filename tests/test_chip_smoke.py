"""chip_smoke.py: the CPU rehearsal passes and never claims a TPU; without
a TPU (or without the rest of the repo) the script fails and prints no
result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(args, *, cwd=ROOT, script=SCRIPT, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _result_line(stdout: str):
    lines = stdout.strip().splitlines()
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        return None


@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal(chips):
    """``--smoke`` runs train -> deploy -> serve (or the 4-device
    model-parallel + striped-L1 phase) at reduced size and reports the
    CPU it ran on."""
    proc = _run(["--smoke", "--chips", str(chips)])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    res = _result_line(proc.stdout)
    assert res is not None and res["ok"] is True
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == chips
    assert '"platform": "tpu"' not in proc.stdout
    if chips == 1:
        assert "f32 pooled lookup bit-exact against the table rows: True" \
            in proc.stdout
        for dtype in ("int8", "f16"):
            assert f"[check] {dtype} vs f32 rebuild" in proc.stdout
    else:
        assert "bit-exact against shards=1 (predictions and pooled " \
               "lookup): True" in proc.stdout


def test_refuses_without_tpu():
    proc = _run([], timeout=300)
    assert proc.returncode != 0
    assert _result_line(proc.stdout) is None
    assert "needs 1 TPU chip(s)" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run(["--smoke"], cwd=str(tmp_path), script=str(lone),
                timeout=300)
    assert proc.returncode != 0
    assert _result_line(proc.stdout) is None
