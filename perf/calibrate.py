#!/usr/bin/env python3
"""Readings that the limits of a cell's compared numbers are set from.

    python3 perf/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 3] [--faults half_batch,no_mix]

For each seed, in one process: the cell is set up as a run sets it up (the
program drives its checked rounds), and its numbers are read against the
float32 reference. On the first ``--control-seeds`` seeds the control (the
reference in bfloat16, put in the program's place) and each fault planted
in the reference are read too. One JSON line per seed. The benchmark's own
runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    args = ap.parse_args()

    from perf import compare, harness

    p = harness.parts(args.workload)
    harness.check_devices(p.workload["chips"])
    harness.enable_cache()
    faults = [f for f in args.faults.split(",") if f]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = p.cell_module.build(p.config, p.traffic, harness.seed32(seed),
                                   harness.Spans())
        cell.setup()
        cell.release()
        t1 = time.perf_counter()
        want = cell.reference()
        t2 = time.perf_counter()
        kept = compare.moving_leaves(want["grads0"].ravel())
        line = {"seed": seed, "program": cell.gaps(cell.got, want),
                "leaves_left_out": kept.count(False),
                "setup_s": t1 - t0, "reference_s": t2 - t1,
                "losses": cell.got["losses"], "ref_losses": want["losses"]}
        if i < args.control_seeds:
            line["control"] = cell.gaps(cell.reference("bfloat16"), want)
            for f in faults:
                line[f] = cell.gaps(cell.reference(fault=f), want)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
