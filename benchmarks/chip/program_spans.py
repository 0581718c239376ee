#!/usr/bin/env python3
"""The program's own spans and serving counters (``repro.tracing``) in one
traced run of a cell, reduced to per-layer numbers.

``trace.Trace`` keeps the benchmark's ``bench/`` spans and JAX's compile
events, but not the host thread each ran on. ``ProgramSpans`` reads the
same profile and keeps, per host thread, the program's ``repro/`` spans
besides them: a compile is charged to the innermost program span around it
on its own thread, and a span's self time leaves out its children there.

    python3 benchmarks/chip/program_spans.py --workload <cell> --seed <n>

runs the cell once as ``run.py --trace 1`` does (the same profiled window
of at most ``run.TRACE_WINDOW_S`` seconds, the same ``spans.py``
wrappers) and prints one JSON line: ``readings`` (see :func:`readings`),
the harness's own per-layer metrics, the window's rate (``e2e``), the
compiles and their seconds under each innermost program span, what each
compile compiled (``compiled_functions``), every
span's seconds and count, when each pooled gather started, and the ten
longest idle gaps of the first device, each named by :meth:`label`.
``--rehearse`` runs it on the CPU at the rehearsal sizes.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from trace import HOST_EVENTS, SPAN_PREFIX, WINDOW_SPAN, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

PROGRAM_PREFIX = "repro/"
COMPILE = HOST_EVENTS[0]
#: JAX's host event around each call of a jitted function or eager op,
#: named ``PjitFunction(<function>)``; a compile nests in the call's
JIT_PREFIX = "PjitFunction("
NO_SPAN = "no host span"
#: other host events (JAX's and the runtime's own) kept to name a gap that
#: no span covers, if they last at least this long
OTHER_MIN_NS = 1_000_000

Event = Tuple[str, int, int, int]       # (name, start_ns, end_ns, thread)


def _is_span(name: str) -> bool:
    return name.startswith((PROGRAM_PREFIX, SPAN_PREFIX)) and \
        name != WINDOW_SPAN


@dataclass
class ProgramSpans:
    window: Tuple[int, int]
    #: program and benchmark spans, compiles, jitted calls, and long
    #: other host events
    events: List[Event]

    @classmethod
    def from_profile(cls, pd, window: Tuple[int, int]) -> "ProgramSpans":
        """Every host line of the profile is one thread."""
        events: List[Event] = []
        thread = 0
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    s, t = int(e.start_ns), int(e.end_ns)
                    if _is_span(e.name) or e.name == COMPILE or \
                            e.name.startswith(JIT_PREFIX) or \
                            (e.name != WINDOW_SPAN and
                             t - s >= OTHER_MIN_NS):
                        events.append((e.name, s, t, thread))
                thread += 1
        return cls(window, events)

    def _named(self, name: str) -> List[Event]:
        return [e for e in self.events if e[0] == name]

    def _clip(self, s: int, e: int) -> Tuple[int, int]:
        lo, hi = self.window
        return max(s, lo), min(e, hi)

    def span_time(self, name: str) -> float:
        """Seconds of spans ``name`` inside the window, summed over
        threads."""
        total = 0
        for _, s, e, _ in self._named(name):
            s, e = self._clip(s, e)
            total += max(0, e - s)
        return total * 1e-9

    def span_count(self, name: str) -> int:
        """Spans ``name`` that start inside the window."""
        lo, hi = self.window
        return sum(1 for _, s, _, _ in self._named(name) if lo <= s < hi)

    def self_time(self, name: str, child: str) -> float:
        """``span_time(name)`` less the part of each span that spans
        ``child`` on its own thread cover."""
        total = 0
        for _, s, e, th in self._named(name):
            s, e = self._clip(s, e)
            if e <= s:
                continue
            inner = sorted((max(cs, s), min(ce, e))
                           for _, cs, ce, ct in self._named(child)
                           if ct == th and cs < e and ce > s)
            covered, cur = 0, s
            for cs, ce in inner:
                cs = max(cs, cur)
                if ce > cs:
                    covered += ce - cs
                    cur = ce
            total += e - s - covered
        return total * 1e-9

    def _innermost(self, t: int, keep, thread: Optional[int] = None
                   ) -> Optional[Event]:
        best = None
        for ev in self.events:
            name, s, e, th = ev
            if keep(name) and s <= t <= e and \
                    (thread is None or th == thread) and \
                    (best is None or e - s < best[2] - best[1]):
                best = ev
        return best

    def _program_span_of(self, ev: Event) -> str:
        """The innermost program span around ``ev`` on its thread."""
        outer = self._innermost(
            ev[1], lambda n: n.startswith(PROGRAM_PREFIX), ev[3])
        return outer[0] if outer else NO_SPAN

    def _compiles(self) -> List[Event]:
        lo, hi = self.window
        return [ev for ev in self._named(COMPILE) if lo <= ev[1] < hi]

    def compiles_by_span(self) -> Dict[str, Tuple[int, float]]:
        """``{innermost program span: (compiles, seconds)}`` over the
        compiles that start inside the window."""
        out: Dict[str, List] = defaultdict(lambda: [0, 0.0])
        for ev in self._compiles():
            acc = out[self._program_span_of(ev)]
            acc[0] += 1
            acc[1] += (ev[2] - ev[1]) * 1e-9
        return {k: (n, s) for k, (n, s) in out.items()}

    def compiled_functions(self) -> Dict[str, int]:
        """``{"<program span> <- <function>": compiles}``: what each
        compile in the window compiled (the ``PjitFunction`` call around
        it on its thread) and under which program span."""
        out: Dict[str, int] = defaultdict(int)
        for ev in self._compiles():
            fn = self._innermost(ev[1], lambda n: n.startswith(JIT_PREFIX),
                                 ev[3])
            name = fn[0][len(JIT_PREFIX):-1] if fn else "?"
            out[f"{self._program_span_of(ev)} <- {name}"] += 1
        return dict(out)

    def compiles_under(self, name: str) -> int:
        return self.compiles_by_span().get(name, (0, 0.0))[0]

    def label(self, t: int) -> str:
        """What the host was doing at time ``t``: a compile running on any
        thread, named with the program span around it; else the innermost
        ``repro/`` span over ``t``, else the innermost ``bench/`` one (the
        benchmark's wrappers sit around and inside the program's spans);
        else ``NO_SPAN`` and the innermost other long host event."""
        comp = self._innermost(t, lambda n: n == COMPILE)
        if comp is not None:
            return f"{COMPILE} in {self._program_span_of(comp)}"
        for keep in (lambda n: n.startswith(PROGRAM_PREFIX), _is_span):
            ev = self._innermost(t, keep)
            if ev is not None:
                return ev[0]
        other = self._innermost(
            t, lambda n: not _is_span(n) and n != COMPILE)
        return f"{NO_SPAN}: {other[0]}" if other else NO_SPAN

    def starts(self, name: str) -> List[float]:
        """Seconds from the window's start to each span ``name``."""
        lo, hi = self.window
        return sorted((s - lo) * 1e-9 for _, s, _, _ in self._named(name)
                      if lo <= s < hi)


@dataclass
class _Labelled(Trace):
    program: Optional[ProgramSpans] = None

    def _label(self, t: int) -> str:
        return self.program.label(t)


def idle_gaps(tr: Trace, ps: ProgramSpans, n: int = 10) -> List[List]:
    """``Trace.idle_gaps`` with each gap named by ``ps.label``."""
    return _Labelled(tr.window, tr.devices, tr.spans, ps).idle_gaps(n)


def readings(ps: ProgramSpans, counters: Optional[Tuple[Dict, Dict]] = None
             ) -> Dict[str, float]:
    """The per-layer numbers the program's spans and counters give, by the
    names a serving (``.capacity``) or training cell would report them
    under. ``counters`` are the server's ``counters()`` at the window's
    start and end (serving only). Numbers with nothing to read are left
    out."""
    P = PROGRAM_PREFIX
    out: Dict[str, float] = {}
    if counters is not None:
        c0, c1 = counters
        drained = c1["requests_drained"] - c0["requests_drained"]
        if drained:
            out["queue_wait_ms_per_request.capacity"] = \
                1e3 * (c1["queue_wait_s"] - c0["queue_wait_s"]) / drained
        n = ps.span_count(P + "hps.pooled_stack")
        if n:
            out["hps_probe_ms_per_dispatch.capacity"] = 1e3 * ps.self_time(
                P + "hps.probe", P + "hps.miss_fetch") / n
            out["miss_fetch_ms_per_dispatch.capacity"] = \
                1e3 * ps.span_time(P + "hps.miss_fetch") / n
        for metric, span in (("scatter_compiles", "hps.l1_scatter"),
                             ("gather_compiles", "hps.pooled_stack"),
                             ("dense_compiles", "server.dense_forward")):
            out[f"{metric}.capacity"] = ps.compiles_under(P + span)
    steps = ps.span_count(P + "train.step")
    if steps:
        out["train_input_ms_per_step"] = 1e3 * (
            ps.span_time(P + "train.data")
            + ps.span_time(P + "train.put_batch")) / steps
        out["train_sync_ms_per_step"] = \
            1e3 * ps.span_time(P + "train.sync") / steps
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--work-dir", default=os.path.join(HERE, "_work"))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import run as harness
    import serving
    import spans
    import spec
    import work
    cell = spec.load_cell(args.workload, rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips}")
    import jax
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"{args.workload} needs a TPU; JAX sees "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    spans.install()

    # the server's counters at the window's edges, where the serving
    # cells read the L1 counters; and the program spans of the profile
    # the window's Trace is read from
    counters: List[Dict] = []
    program: List[ProgramSpans] = []
    l1_counts = serving.Serving.l1_counts
    from_dir = Trace.from_dir.__func__

    def counted(self):
        counters.append(self.server.counters())
        return l1_counts(self)

    def read_both(cls, logdir, **kw):
        tr = from_dir(cls, logdir, **kw)
        from jax.profiler import ProfileData
        path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        program.append(ProgramSpans.from_profile(
            ProfileData.from_file(path), tr.window))
        return tr

    serving.Serving.l1_counts = counted
    Trace.from_dir = classmethod(read_both)
    opts = argparse.Namespace(seed=args.seed, rehearse=args.rehearse,
                              trace=1, seconds=args.seconds,
                              work_dir=args.work_dir)
    r = harness.Run(opts, cell, devices)
    cell.kind.run(r)
    r.layer["compiles_in_window"] = r.compiles.window_count
    r.layer["chips"] = cell.chips
    tr, ps = r.trace, program[-1]
    kind = "cpu" if args.rehearse else devices[0].device_kind
    peaks = None if args.rehearse else work.load_peaks()
    harness_metrics = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]].read(r, tr, peaks, kind)
        if v is not None:
            harness_metrics[m["name"]] = v
    names = sorted({e[0] for e in ps.events if _is_span(e[0])})
    out = {
        "workload": args.workload, "seed": args.seed, "device": kind,
        "readings": readings(ps, (counters[0], counters[-1])
                             if counters else None),
        "harness": harness_metrics,
        "e2e": r.e2e,
        "compiles_in_window": r.compiles.window_count,
        "compiles_by_span": ps.compiles_by_span(),
        "compiled_functions": ps.compiled_functions(),
        "spans": {n: [ps.span_time(n), ps.span_count(n)] for n in names},
        "pooled_stack_starts_s": ps.starts(PROGRAM_PREFIX +
                                           "hps.pooled_stack"),
        "busy_s": tr.busy_s(), "window_s": tr.window_s,
        "idle_gaps": idle_gaps(tr, ps, 10),
        "checks": r.checks,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
