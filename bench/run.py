#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

  python3 bench/run.py --workload atm_topo.compress --seed 7 --seconds 10 \\
      --trace 0

Makes the cell's fields on the chip from ``--seed``, warms up the cell's
own programs (set-up), calls the program's batch entry point in a closed
loop for ``--seconds``, checks the last call's output against the plain
reference in ``bench/reference.py``, and prints one JSON object as the
last line of standard output.  ``--trace 1`` records a profiler trace of
the window and reports the per-layer metrics instead of the end-to-end
ones.  Without a TPU, or with kernels that do not resolve to compiled
Pallas, it exits with code 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, spec
    cell = spec.find_cell(args.workload, ROOT)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        harness.log(f"refusing to run: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
