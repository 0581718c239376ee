#!/usr/bin/env python3
"""Readings that set the limits ``correct`` compares against; the
benchmark's own runs never run this.

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 1,2,3 \
        [--what program|control|<fault>] [--seconds 30]

Runs the cell once per seed, in one process, with the fault of
``faults.py`` named by ``--what`` planted (``control``: the reference one
precision below the configuration's in the program's place; ``program``:
nothing planted). Each run prints its result line, whose ``checks`` are the
readings. ``--rehearse``: on the CPU, at the tiny sizes.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program")
    ap.add_argument("--seconds", type=int, default=30)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run
    if args.what != "program":
        import faults
        faults.plant(args.what)
    rc = 0
    for seed in args.seeds.split(","):
        print(f"readings: {args.workload} seed {seed} {args.what}",
              flush=True)
        rc |= run.main(["--workload", args.workload, "--seed", seed,
                        "--seconds", str(args.seconds), "--trace", "0"]
                       + (["--rehearse"] if args.rehearse else []))
    return rc


if __name__ == "__main__":
    sys.exit(main())
