"""The program-span reduction (``program_spans.py``) on a small recorded
trace, checked by hand, and its command on both cells' rehearsals.

The trace is ``test_chipbench_trace``'s (device 0 idle 400..600 and
700..900 in the window 100..1100, ``bench/hps.probe`` 350..650 on the
main thread) plus two threads of program spans and compiles (ns):

    main    compile 20..60 (before the window), compile 1050..1080,
            bench/server.dense_forward 390..610
    serve   repro/server.coalesce 50..150, repro/server.dense_forward
            380..620 holding PjitFunction(<lambda>) 405..495, which holds
            compile 410..490; repro/hps.pooled_stack 785..870,
            repro/server.materialize 1000..1200
    worker  repro/hps.probe 650..1000 holding repro/hps.miss_fetch
            660..700 and repro/hps.l1_scatter 780..900, which holds
            PjitFunction(scatter) 785..865 around compile 790..860

The pooled stack on the serve thread is shorter than the scatter and
covers the worker's compile: only nesting on the compile's own thread
charges it to the scatter.
"""
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import program_spans as ps_mod  # noqa: E402
import rehearse  # noqa: E402
import spec  # noqa: E402
import work  # noqa: E402
from program_spans import ProgramSpans, idle_gaps, readings  # noqa: E402
from test_chipbench_trace import _DEV0, _DEV1, _HOST, _plane  # noqa: E402
from trace import Trace  # noqa: E402

C = "backend_compile_and_load"
_MAIN = [("main", _HOST[0][1] + [(C, 20, 40), (C, 1050, 30),
                                 ("bench/server.dense_forward", 390, 220)])]
_SERVE = ("serve", [("repro/server.coalesce", 50, 100),
                    ("repro/server.dense_forward", 380, 240),
                    ("PjitFunction(<lambda>)", 405, 90),
                    (C, 410, 80),
                    ("repro/hps.pooled_stack", 785, 85),
                    ("repro/server.materialize", 1000, 200)])
_WORKER = ("worker", [("repro/hps.probe", 650, 350),
                      ("repro/hps.miss_fetch", 660, 40),
                      ("repro/hps.l1_scatter", 780, 120),
                      ("PjitFunction(scatter)", 785, 80),
                      (C, 790, 70)])


def _profile(host_lines):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("\n".join([
        _plane(1, "/device:TPU:0", _DEV0), _plane(2, "/device:TPU:1", _DEV1),
        _plane(3, "/host:CPU", host_lines)]))


@pytest.fixture(scope="module")
def traced():
    pd = _profile(_MAIN + [_SERVE, _WORKER])
    tr = Trace.from_profile(pd)
    return tr, ProgramSpans.from_profile(pd, tr.window)


def test_time_is_clipped_to_the_window_and_counts_starts(traced):
    _, ps = traced
    assert ps.span_time("repro/server.coalesce") == pytest.approx(50e-9)
    assert ps.span_count("repro/server.coalesce") == 0
    assert ps.span_time("repro/server.materialize") == pytest.approx(100e-9)
    assert ps.span_count("repro/server.materialize") == 1
    assert ps.span_time("repro/hps.probe") == pytest.approx(350e-9)
    assert ps.self_time("repro/hps.probe", "repro/hps.miss_fetch") == \
        pytest.approx(310e-9)


def test_compiles_go_to_the_innermost_span_on_their_thread(traced):
    _, ps = traced
    by = ps.compiles_by_span()
    assert by.keys() == {"repro/server.dense_forward",
                         "repro/hps.l1_scatter", ps_mod.NO_SPAN}
    assert by["repro/hps.l1_scatter"] == (1, pytest.approx(70e-9))
    assert ps.compiles_under("repro/server.dense_forward") == 1
    assert ps.compiles_under("repro/hps.pooled_stack") == 0
    assert ps.starts("repro/hps.pooled_stack") == pytest.approx([685e-9])
    assert ps.compiled_functions() == {
        "repro/server.dense_forward <- <lambda>": 1,
        "repro/hps.l1_scatter <- scatter": 1, f"{ps_mod.NO_SPAN} <- ?": 1}


def test_gaps_named_by_the_innermost_span_or_the_compile(traced):
    """A program span names a gap before the benchmark's shorter
    wrapper over the same call."""
    tr, ps = traced
    gaps = idle_gaps(tr, ps)
    assert [g[0] for g in gaps] == [
        "repro/server.dense_forward", f"{C} in repro/hps.l1_scatter"]
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 200e-9])


def test_without_program_spans_the_names_are_trace_py_s():
    pd = _profile(_HOST)
    tr = Trace.from_profile(pd)
    assert idle_gaps(tr, ProgramSpans.from_profile(pd, tr.window)) == \
        tr.idle_gaps()


def test_a_gap_no_span_covers_names_a_long_host_event():
    ms = 1_000_000
    host = [("main", [("bench/window", 0, 10 * ms),
                      ("TransferToDevice", 3 * ms, 4 * ms),
                      ("short", 4 * ms, ms // 2)])]
    dev = [("XLA Modules", [("jit_f(1)", 0, ms)]),
           ("XLA Ops", [("fusion.1", 0, ms)])]
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto("\n".join([
        _plane(1, "/device:TPU:0", dev), _plane(2, "/host:CPU", host)]))
    tr = Trace.from_profile(pd)
    gaps = idle_gaps(tr, ProgramSpans.from_profile(pd, tr.window))
    assert gaps[0][0] == f"{ps_mod.NO_SPAN}: TransferToDevice"


def test_the_harness_metrics_read_the_same_with_program_spans(traced):
    """Program spans in the profile leave every per-layer metric of the
    benchmark as it reads without them."""
    cfg = spec._read(os.path.join(HERE, "configs", "dcn-criteo.json"))

    class Run:
        config = cfg
        layer = {"rows_delivered": 4096, "window_s": 1e-6, "l1_hits": 90,
                 "l1_misses": 10, "compiles_in_window": 3,
                 "dense_flops_per_row": 1000, "gather_bytes_per_row": 3432,
                 "steps": 2, "batch": 16, "chips": 1}

    old = Trace.from_profile(_profile(_HOST))
    new, _ = traced
    peaks = work.load_peaks()
    paths = sorted(glob.glob(os.path.join(HERE, "metrics", "*.py")))
    assert len(paths) == 13
    for path in paths:
        reader = spec.load_module(path, "reader_" + os.path.basename(
            path)[:-3])
        a = reader.read(Run, old, peaks, "TPU v5 lite")
        b = reader.read(Run, new, peaks, "TPU v5 lite")
        assert a == b, path


def test_readings_from_spans_and_counters(traced):
    _, ps = traced
    c0 = {"queue_wait_s": 1.0, "requests_drained": 10}
    c1 = {"queue_wait_s": 4.0, "requests_drained": 16}
    got = readings(ps, (c0, c1))
    assert got["queue_wait_ms_per_request.capacity"] == pytest.approx(500.0)
    assert got["hps_probe_ms_per_dispatch.capacity"] == pytest.approx(310e-6)
    assert got["miss_fetch_ms_per_dispatch.capacity"] == pytest.approx(40e-6)
    assert (got["scatter_compiles.capacity"], got["gather_compiles.capacity"],
            got["dense_compiles.capacity"]) == (1, 0, 1)
    assert "train_input_ms_per_step" not in got
    assert readings(ps) == {}


@pytest.mark.parametrize("cell, names", [
    ("dcn-serve-saturate", {
        "queue_wait_ms_per_request.capacity",
        "hps_probe_ms_per_dispatch.capacity",
        "miss_fetch_ms_per_dispatch.capacity", "scatter_compiles.capacity",
        "gather_compiles.capacity", "dense_compiles.capacity"}),
    ("dcn-train", {"train_input_ms_per_step", "train_sync_ms_per_step"}),
])
def test_command_reads_every_number_of_a_cell(cell, names, tmp_path):
    """The command, rehearsed on the CPU: one JSON line with a number for
    every reading of the cell."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        rehearse.ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "program_spans.py"),
         "--workload", cell, "--seed", "3000000001", "--seconds", "2",
         "--rehearse", "--work-dir", str(tmp_path)],
        cwd=rehearse.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out["readings"]) == names
    assert all(isinstance(v, (int, float)) for v in out["readings"].values())
    assert out["device"] == "cpu"
    assert out["idle_gaps"]
