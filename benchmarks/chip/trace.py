"""Reduction of one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The trace holds device planes (``/device:TPU:<n>``, with an ``XLA Modules``
line of program executions and an ``XLA Ops`` line of the operations inside
them) and the host plane, on one clock. The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events whose names start with ``bench/``;
the one named :data:`WINDOW_SPAN` bounds the measured window. Everything
here is clipped to that window.

On the CPU (rehearsal only) XLA runs on host threads: events that carry an
``hlo_op`` stat are taken as the ops of one device, grouped into programs by
their ``hlo_module`` stat.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
#: JAX's own host events that name what the host was doing: compiling
HOST_EVENTS = ("backend_compile_and_load",)

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int) -> Tuple[int, int]:
    return max(s, lo), min(e, hi)


@dataclass
class Device:
    name: str
    modules: List[Interval] = field(default_factory=list)
    ops: List[Interval] = field(default_factory=list)


@dataclass
class Trace:
    window: Tuple[int, int]
    devices: List[Device]
    spans: List[Interval]                 # the benchmark's host spans

    # -- construction -----------------------------------------------------

    @classmethod
    def from_dir(cls, logdir: str, **kw) -> "Trace":
        paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {logdir}")
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(paths[-1]), **kw)

    @classmethod
    def from_profile(cls, pd, *, device_names: Optional[Sequence[str]] = None
                     ) -> "Trace":
        """``device_names`` keeps only those device planes (the chips the
        run used); None keeps every device plane."""
        devices: List[Device] = []
        cpu = Device("cpu")
        spans: List[Interval] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                if device_names is not None and \
                        plane.name not in device_names:
                    continue
                dev = Device(plane.name)
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        dev.modules = [(e.name, int(e.start_ns),
                                        int(e.end_ns)) for e in line.events]
                    elif line.name == "XLA Ops":
                        dev.ops = [(e.name, int(e.start_ns), int(e.end_ns))
                                   for e in line.events]
                devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX) or \
                                e.name in HOST_EVENTS:
                            spans.append((e.name, int(e.start_ns),
                                          int(e.end_ns)))
                            continue
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            iv = (e.name, int(e.start_ns), int(e.end_ns))
                            cpu.ops.append(iv)
                            cpu.modules.append(
                                (str(stats.get("hlo_module", "?")),) + iv[1:])
        if not devices and cpu.ops:
            devices = [cpu]
        windows = [s for s in spans if s[0] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
        _, ws, we = windows[-1]
        for d in devices:
            d.modules.sort(key=lambda iv: iv[1])
            d.ops.sort(key=lambda iv: iv[1])
        return cls((ws, we), devices,
                   [s for s in spans if s[0] != WINDOW_SPAN])

    # -- window and busy time ---------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, dev: Device) -> List[Tuple[int, int]]:
        lo, hi = self.window
        return union_ns(_clip(s, e, lo, hi) for _, s, e in dev.ops)

    def busy_s_per_device(self) -> List[float]:
        return [sum(e - s for s, e in self._busy(d)) * 1e-9
                for d in self.devices]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per = self.busy_s_per_device()
        return sum(per) / len(per) if per else 0.0

    # -- programs and ops by declared names -------------------------------

    @staticmethod
    def _match(patterns: Sequence[str]):
        rx = [re.compile(p) for p in patterns]
        return lambda name: any(r.search(name) for r in rx)

    def _time(self, ivs: List[Interval], patterns) -> float:
        lo, hi = self.window
        ok = self._match(patterns)
        total = 0
        for name, s, e in ivs:
            if ok(name):
                s, e = _clip(s, e, lo, hi)
                total += max(0, e - s)
        return total * 1e-9

    def _count(self, ivs: List[Interval], patterns) -> int:
        lo, hi = self.window
        ok = self._match(patterns)
        return sum(1 for name, s, _ in ivs if ok(name) and lo <= s < hi)

    def module_time(self, patterns: Sequence[str]) -> List[float]:
        """Seconds of programs whose name matches, per device."""
        return [self._time(d.modules, patterns) for d in self.devices]

    def module_count(self, patterns: Sequence[str]) -> List[int]:
        """Executions of matching programs starting in the window."""
        return [self._count(d.modules, patterns) for d in self.devices]

    def op_time(self, patterns: Sequence[str]) -> List[float]:
        """Seconds of ops whose name matches, per device."""
        return [self._time(d.ops, patterns) for d in self.devices]

    # -- breakdown ---------------------------------------------------------

    def _module_of(self, dev: Device):
        starts = [s for _, s, _ in dev.modules]

        def find(t: int) -> str:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and dev.modules[i][2] >= t:
                return dev.modules[i][0]
            return "?"
        return find

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` ops (``program:op``) that took the most device time,
        in seconds averaged over the devices."""
        lo, hi = self.window
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            mod = self._module_of(d)
            for name, s, e in d.ops:
                s2, e2 = _clip(s, e, lo, hi)
                if e2 > s2:
                    acc[f"{mod(s)}:{name}"] += (e2 - s2) * 1e-9
        k = max(1, len(self.devices))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / k] for name, secs in top]

    def _label(self, t: int) -> str:
        """The innermost benchmark host span that covers time ``t``."""
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "no host span"

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first device, each named by
        what the host was doing in its middle."""
        if not self.devices:
            return []
        lo, hi = self.window
        busy = self._busy(self.devices[0])
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._label((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]
