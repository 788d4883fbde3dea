#!/usr/bin/env python3
"""The readings a cell's limits are set from, taken on the chip at the cell's
own size (steps 3 and 4 of "How `correct` is decided"): the program against
the plain reference over a dozen seeds, and the control and the planted
faults on three.  The benchmark's own runs never run this.

    python3 chipbench/control.py --workload <name> --seeds 12 --control-seeds 3 [--first-seed N]

One JSON row a reading on standard output, appended to --out if given.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    import importlib

    from chipbench import harness

    p = argparse.ArgumentParser(prog="chipbench/control.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_200_000_001)
    p.add_argument("--seconds", type=float, default=0.0)
    p.add_argument("--out")
    args = p.parse_args(argv)

    cell = harness.Cell(ROOT, harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    _, info = harness.require_chip(cell, "chipbench/control.py")
    harness.enable_compile_cache(ROOT)
    t0 = time.perf_counter()

    def log(msg):
        print(f"[control {time.perf_counter() - t0:7.1f}s] {msg}", flush=True)

    def make_ctx(seed):
        return harness.Context(cell, seed, args.seconds, False,
                               cell.peaks(info["kind"]),
                               os.path.join(ROOT, "chipbench", ".work"), log)

    def emit(row):
        line = json.dumps({"workload": cell.name, **row})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")

    driver = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    driver.calibrate(make_ctx, seeds, args.control_seeds, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
