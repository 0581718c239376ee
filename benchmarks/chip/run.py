#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names its configuration and traffic mix; the
mix names its generator kind (see ``spec.py``). Set-up (weights, bundle,
compiles, warm-up) ends where the window starts: ``setup_s`` is the time
from process start to there. ``--trace 1`` profiles the window (at most
``TRACE_WINDOW_S`` seconds of it) and reports the cell's per-layer metrics,
``--trace 0`` its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``: each compared number beside its limit,
also printed as the last lines of standard error.

Without a TPU holding the cell's chips the run fails with no result.
``--rehearse`` runs the cell on the CPU at the configuration's and mix's
tiny ``rehearsal`` sizes and names the device ``cpu``; its numbers are
never device metrics.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

#: longest window a traced run profiles
TRACE_WINDOW_S = 10
#: the compile event JAX reports for every XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny rehearsal sizes")
    ap.add_argument("--work-dir", default=os.path.join(HERE, "_work"),
                    help="where deployed bundles are kept between runs")
    return ap.parse_args(argv)


class Compiles:
    """XLA backend compiles, counted by the benchmark's own listener."""

    def __init__(self):
        self.in_window = False
        self.window_count = 0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT and self.in_window:
            self.window_count += 1


class Window:
    """The measured window: a host span, the compile count and, traced,
    the profiler."""

    def __init__(self, run):
        self.run = run
        self.t0 = self.t1 = None
        self._tmp = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self):
        import jax
        from trace import WINDOW_SPAN
        if self.run.trace_on:
            self._tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.run.compiles.in_window = True
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax
        self.t1 = time.perf_counter()
        self.run.compiles.in_window = False
        self._span.__exit__(*exc)
        if self._tmp is not None:
            jax.profiler.stop_trace()
            if exc[0] is None:
                from trace import Trace
                names = [f"/device:TPU:{d.id}" for d in self.run.devices]
                self.run.trace = Trace.from_dir(
                    self._tmp.name,
                    device_names=None if self.run.rehearse else names)
            self._tmp.cleanup()
        return False


class Run:
    """One run of one cell: what the generator kinds fill in."""

    def __init__(self, args, cell, devices):
        self.cell = cell
        self.config = cell.config
        self.mix = cell.mix
        self.seed = args.seed
        self.rehearse = args.rehearse
        self.trace_on = bool(args.trace)
        self.seconds = min(args.seconds, TRACE_WINDOW_S) if args.trace \
            else args.seconds
        self.work_dir = args.work_dir
        self.devices = devices
        self.compiles = Compiles()
        self.trace = None
        self.setup_s = None
        self.attempted = self.failed = 0
        self.e2e, self.layer, self.checks = {}, {}, {}
        self.memory_peak = 0

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    def window(self) -> Window:
        return Window(self)

    def read_memory(self) -> None:
        """Peak device bytes of the fullest chip, read before the
        reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak = int(max(peaks))


def _fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import spec
    cell = spec.load_cell(args.workload, rehearse=args.rehearse)
    if args.rehearse:                # never touch an accelerator
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips}")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu" or len(devices) < cell.chips:
            return _fail(f"rehearsal needs {cell.chips} CPU devices, JAX "
                         f"sees {len(devices)} {platform}")
    elif platform != "tpu" or len(devices) < cell.chips:
        return _fail(f"{args.workload} needs {cell.chips} TPU chip(s); JAX "
                     f"sees {len(devices)} {platform} device(s)")
    devices = devices[:cell.chips]
    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    if args.trace:
        import spans
        spans.install()
    import work
    run = Run(args, cell, devices)
    cell.kind.run(run)
    run.layer["compiles_in_window"] = run.compiles.window_count
    run.layer["chips"] = cell.chips

    kind = "cpu" if args.rehearse else devices[0].device_kind
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak}
    out = {"correct": None, "attempted": run.attempted,
           "failed": run.failed, "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        tr = run.trace
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        peaks = None if args.rehearse else work.load_peaks()
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(run, tr, peaks, kind)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v,
                                             "unit": units[m["name"]]}
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    else:
        vals = dict(run.e2e, setup_s=run.setup_s)
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = {"value": vals[m["name"]],
                                         "unit": units[m["name"]]}
    checks = {k: c for k, c in run.checks.items() if c["limit"] is not None}
    for name, c in run.checks.items():
        if c["limit"] is None:
            print(f"reading {name}: {c['value']} (not compared)",
                  file=sys.stderr)
    out["correct"] = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
