"""Benchmark harness — one module per paper table/claim.

Usage:
  PYTHONPATH=src python -m benchmarks.run             # all benches
  PYTHONPATH=src python -m benchmarks.run kernel hps  # a subset

Prints ``name,us_per_call,derived`` CSV (also written to
``artifacts/bench_results.csv``)."""
from __future__ import annotations

import os
import sys

from benchmarks.common import Report
from repro.launch.compile_cache import enable_compile_cache

BENCHES = ("kernel", "train", "hps", "etc", "online", "strategies",
           "roofline")


def main() -> None:
    enable_compile_cache()
    which = [a for a in sys.argv[1:] if not a.startswith("-")] or BENCHES
    report = Report()
    if "kernel" in which:
        from benchmarks import kernel_bench
        kernel_bench.run(report)
    if "train" in which:
        from benchmarks import train_throughput
        train_throughput.run(report)
    if "hps" in which:
        from benchmarks import hps_speedup
        hps_speedup.run(report)
    if "etc" in which:
        from benchmarks import etc_staging
        etc_staging.run(report)
    if "online" in which:
        from benchmarks import online_freshness
        online_freshness.run(report)
    if "strategies" in which:
        from benchmarks import embedding_strategies
        embedding_strategies.run(report)
    if "roofline" in which:
        from benchmarks import roofline_report
        roofline_report.run(report)
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/bench_results.csv", "w") as f:
        f.write(report.dump() + "\n")
    print(f"\n{len(report.rows)} rows -> artifacts/bench_results.csv")


if __name__ == "__main__":
    main()
