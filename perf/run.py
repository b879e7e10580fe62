#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is set up from the seed (weights, data, compile or cache load,
the checked first rounds), then measured for ``--seconds``. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a shorter window runs under the profiler and the result
carries the per-layer metrics. Either way the program's first rounds are
compared with the plain reference after the window, and the numbers
compared are printed, each beside its limit, as the last lines of standard
error and under ``compared`` at the end of the result. Without as many TPU
chips as the cell asks for it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args()

    from perf import harness

    result = harness.execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
