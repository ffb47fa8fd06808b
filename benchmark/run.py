#!/usr/bin/env python3
"""The benchmark's command: one run of one cell, in a process of its own.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks for the chip the cell asks for and fails (exit 3, nothing printed to
standard output) where JAX finds none: it never falls back to a CPU. The
last line of standard output is the result as the contract fixes it.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness

    harness.setup_environment(ROOT)
    try:
        harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except harness.NoChip as e:
        print(f"benchmark: {e}: nothing measured", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
