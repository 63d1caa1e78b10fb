#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ... \
        [--seconds 8] [--control]

Warms the cell up once, then for each seed drives a short window at the
cell's own load (the mix's requests, and a served model's weights, drawn
from that seed) and runs the
configuration's check on what it produced, printing one JSON line per
seed with every compared number. With `--control` the configuration's
lower-precision control is switched on for all of it: its numbers are
the upper readings, the program's sound runs give the lower ones (see
PERF.md). Needs the chip like `run.py`; the benchmark's own runs never
run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell: dict, seeds, seconds: float, control: bool):
    """Yields (seed, requests, checks) for each seed."""
    from bench.lib import drivers, harness, traffic
    config = traffic.load_json("configs", cell["config"])
    mix = traffic.load_json("traffic", cell["traffic"])
    checker = harness.load_module("configs", cell["config"])
    ctl = checker.control(config) if control else None
    if ctl is not None:
        config = ctl.config
        ctl.__enter__()
    try:
        driver = drivers.Driver(mix, config, seeds[0])
        driver.warmup()
        for seed in seeds:
            driver.reseed(seed)
            records = driver.run(seconds)
            checks = checker.readings(records, config, seed)
            checks["requests_failed"] = {
                "value": sum(not r.ok for r in records), "limit": 0}
            yield seed, len(records), checks
    finally:
        if ctl is not None:
            ctl.__exit__(None, None, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    from bench.lib import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 3
    harness.enable_cache()
    for seed, n, checks in readings(cell, args.seeds, args.seconds,
                                    args.control):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "requests": n,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
