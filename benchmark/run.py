#!/usr/bin/env python3
"""The benchmark's command: argument parsing only.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips alone. Everything else is in
``benchmark/harness.py``, which finds the cell's files by the names in
``BENCHMARK.json``.
"""

import time

_PROCESS_START = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import harness
    return harness.run(ROOT, args.workload, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       process_start=_PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
