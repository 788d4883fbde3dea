#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Device check first (no TPU, or fewer chips than the cell asks for: say why on
standard error, exit non-zero, print no result), then set-up, the measured
window, the check of outputs, and as the last line of standard output one
JSON object: correct, attempted, failed, metrics, device (and breakdown in a
traced run), then `compared`.  See chipbench/README.md.
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    from chipbench import harness

    return harness.main(argv, t0=T0, root=ROOT)


if __name__ == "__main__":
    sys.exit(main())
