"""The trace reduction on a small recorded trace, checked by hand.

Device 0 (times in ns, window 100..1100 from the ``bench/window`` span):

    program jit__pooled_stack(1)  100..400   ops fusion.1 100..250,
                                                 gather.2 200..400
    program jit_scatter(7)        600..700   op  scatter.3 600..700
    program jit__pooled_stack(1)  900..1200  op  fusion.1 900..1200

Busy union in the window: 100..400, 600..700, 900..1100 = 300+100+200 =
600 ns; idle gaps 400..600 (under host span bench/hps.probe 350..650) and
700..900 (no host span). Device 1 runs one op 100..300 (200 ns busy).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from trace import Trace, union_ns  # noqa: E402

_DEV0 = [("XLA Modules", [("jit__pooled_stack(1)", 100, 300),
                          ("jit_scatter(7)", 600, 100),
                          ("jit__pooled_stack(1)", 900, 300)]),
         ("XLA Ops", [("fusion.1", 100, 150), ("gather.2", 200, 200),
                      ("scatter.3", 600, 100), ("fusion.1", 900, 300)])]
_DEV1 = [("XLA Modules", [("jit__pooled_stack(1)", 100, 200)]),
         ("XLA Ops", [("all-to-all.4", 100, 200)])]
_HOST = [("main", [("bench/window", 100, 1000),
                   ("bench/hps.probe", 350, 300)])]


def _plane(pid, name, lines):
    names = sorted({e[0] for _, evs in lines for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, evs) in enumerate(lines, 1):
        out.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for ename, start, dur in evs:
            out.append(f"    events {{ metadata_id: {meta[ename]} "
                       f"offset_ps: {start * 1000} "
                       f"duration_ps: {dur * 1000} }}")
        out.append("  }")
    for n, i in meta.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    text = "\n".join([_plane(1, "/device:TPU:0", _DEV0),
                      _plane(2, "/device:TPU:1", _DEV1),
                      _plane(3, "/host:CPU", _HOST)])
    return Trace.from_profile(ProfileData.from_text_proto(text))


def test_union_merges_overlaps_and_drops_empty():
    assert union_ns([(5, 9), (0, 3), (2, 4), (7, 8), (10, 10)]) == \
        [(0, 4), (5, 9)]


def test_window_and_busy(trace):
    assert trace.window == (100, 1100)
    assert trace.window_s == pytest.approx(1000e-9)
    assert trace.busy_s_per_device() == pytest.approx([600e-9, 200e-9])
    assert trace.busy_s() == pytest.approx(400e-9)


def test_programs_by_declared_names(trace):
    assert trace.module_count([r"_pooled_stack"]) == [2, 1]
    # the second execution is clipped at the window's end (900..1100)
    assert trace.module_time([r"_pooled_stack"]) == \
        pytest.approx([500e-9, 200e-9])
    assert trace.module_time([r"scatter"]) == pytest.approx([100e-9, 0.0])
    assert trace.op_time([r"all-to-all"]) == pytest.approx([0.0, 200e-9])


def test_only_the_devices_asked_for():
    from jax.profiler import ProfileData
    text = "\n".join([_plane(1, "/device:TPU:0", _DEV0),
                      _plane(2, "/device:TPU:1", _DEV1),
                      _plane(3, "/host:CPU", _HOST)])
    one = Trace.from_profile(ProfileData.from_text_proto(text),
                             device_names=["/device:TPU:0"])
    assert [d.name for d in one.devices] == ["/device:TPU:0"]


def test_idle_gaps_named_by_host_span(trace):
    gaps = trace.idle_gaps()
    assert [g[0] for g in gaps] == ["bench/hps.probe", "no host span"]
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 200e-9])


def test_top_ops_by_program(trace):
    top = dict((k, v) for k, v in trace.top_ops())
    # fusion.1: 150 + 200 (clipped) ns on device 0, averaged over 2 devices
    assert top["jit__pooled_stack(1):fusion.1"] == pytest.approx(175e-9)
    assert top["jit__pooled_stack(1):all-to-all.4"] == pytest.approx(100e-9)
    assert top["jit_scatter(7):scatter.3"] == pytest.approx(50e-9)
