#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See bench/lib/harness.py for what a run does and prints. Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for, and where the program under test is not beside the benchmark.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], T_START))
