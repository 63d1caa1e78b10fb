"""Chaos benchmark: the compile fleet under injected faults.

    PYTHONPATH=src python benchmarks/bench_fleet.py [--smoke]

Writes results/benchmarks/bench_fleet.json. A mixed multi-tenant
workload (overlapping sweeps, matches and co-designs that share most of
their lattice evaluations, plus a few tenant-unique lattices) runs
three ways:

  1. **baseline** — one in-process fault-free `CompileService`: the
     reference responses. Like the workers, it computes on the CPU.
  2. **chaos fleet** — N worker subprocesses over a fresh shared store
     with the deterministic fault harness armed (`repro.testing.faults`):
     one worker hard-killed mid-wave after its second artifact publish,
     the rest tearing writes, corrupting reads and failing evaluations,
     plus one poison request that fails on every attempt everywhere.
  3. **clean fleet** (full mode only) — the same fleet with no faults,
     as the control.

The checks gate CI on the fleet's whole contract: every real request's
response is BIT-IDENTICAL to the baseline despite the chaos, the poison
request is quarantined with a structured error after exactly
`max_attempts`, the chaos actually happened (a worker died, retries
fired), and the shared lease log proves ZERO duplicate lattice
evaluations — every node key was fresh-evaluated at most once across
all workers, with steals and heals reported separately as the
sanctioned recovery paths.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

SHAPE = "decode_32k"


def _workload(smoke: bool):
    """Request dicts for the JSON front door. Tenant sweeps overlap
    (prefixes of a shared num_words ladder) so leases have real
    cross-worker contention; the `unique` sweeps give each shard some
    work nobody else can publish for it."""
    nw = (16, 32, 64) if smoke else (16, 32, 64, 128)
    archs = ["qwen2-0.5b", "llama3.2-1b"] if smoke else \
        ["qwen2-0.5b", "llama3.2-1b", "llama3.2-3b", "minicpm-2b"]
    shared = {"cells": ["gc2t_nn", "gc2t_osos"], "word_sizes": [16, 32],
              "num_words": list(nw)}
    reqs = []
    n_tenants = 4 if smoke else 9
    for i in range(n_tenants):
        t = f"t{i}"
        reqs.append({"id": f"{t}-sweep", "tenant": t, "query": {
            "type": "sweep", "cells": shared["cells"],
            "word_sizes": shared["word_sizes"],
            "num_words": list(nw[:2 + i % max(1, len(nw) - 2)])}})
        reqs.append({"id": f"{t}-match", "tenant": t, "query": {
            "type": "match",
            "demands": [
                {"name": f"{t}-act", "level": "L1",
                 "read_freq_hz": 2.0e8 * (1 + i), "lifetime_s": 2.0e-6},
                {"name": f"{t}-kv", "level": "L2",
                 "read_freq_hz": 4.0e8 * (1 + i), "lifetime_s": 1.0e-3,
                 "capacity_bits": 1 << 20}],
            "sweep": shared}})
        reqs.append({"id": f"{t}-codesign", "tenant": t, "query": {
            "type": "codesign",
            "profiles": [{"arch": archs[i % len(archs)], "shape": SHAPE}],
            "vdd_scales": [0.85, 1.0], "sweep": shared}})
        # a lattice only this tenant asks for, at varying shard
        # positions — exercises publish-before-wait with no other
        # worker able to produce the artifact
        reqs.append({"id": f"{t}-unique", "tenant": t, "query": {
            "type": "sweep", "cells": ["gc2t_nn"], "word_sizes": [8],
            "num_words": [nw[i % len(nw)]], "write_vts": [None],
            "wwlls": [i % 2 == 1]}})
    reqs.append({"id": "POISON-req", "tenant": "chaos", "query": {
        "type": "sweep", "cells": ["gc2t_nn"], "word_sizes": [8],
        "num_words": [16]}})
    return reqs


def _normalize(resp: dict) -> str:
    """The bit-identity canon: id + ok + result, with transport
    bookkeeping (wave, attempts, worker timings) stripped."""
    return json.dumps({"id": resp.get("id"), "ok": resp.get("ok"),
                       "result": resp.get("result")},
                      sort_keys=True, default=str)


def _run_fleet(reqs, n_workers, max_attempts, fault_specs, smoke):
    from repro.api.leases import LeaseManager
    from repro.launch.fleet import Fleet

    spool = tempfile.mkdtemp(prefix="gcram-fleet-spool-")
    store = tempfile.mkdtemp(prefix="gcram-fleet-store-")
    t0 = time.time()
    with Fleet(spool, store, n_workers=n_workers,
               wave_size=max(8, len(reqs) // n_workers + 1),
               deadline_s=120.0 if smoke else 240.0,
               max_attempts=max_attempts, backoff_s=0.2,
               lease_ttl_s=2.0, fault_specs=fault_specs) as fleet:
        responses = fleet.run(reqs, timeout_s=300 if smoke else 900)
        stats = fleet.stats()
    wall = time.time() - t0
    log = LeaseManager.read_eval_log(store)
    fresh = {k: c.get("fresh", 0) for k, c in log.items()}
    return {"responses": responses, "stats": stats, "wall_s": wall,
            "fresh_counts": fresh,
            "duplicates": LeaseManager.duplicate_evals(store)}


def collect(smoke: bool = False) -> dict:
    from repro.launch.compile_service import CompileService

    reqs = _workload(smoke)
    real = [r for r in reqs if "POISON" not in r["id"]]
    n_workers = 2 if smoke else 3
    max_attempts = n_workers + 3

    # 1. baseline: fault-free in-process service, fresh session
    t0 = time.time()
    svc = CompileService(wave_size=len(real))
    lines = svc.serve_lines(json.dumps(r) for r in real)
    baseline = {r["id"]: r for r in map(json.loads, lines)}
    baseline_wall = time.time() - t0

    # 2. chaos fleet: one worker suicides mid-wave after its 2nd
    # publish; the rest tear writes, corrupt reads, fail and stall
    # evaluations; poison fails everywhere, every attempt
    chaos_faults = {"w0": "seed=7,salt=w0,die_after_puts=2,poison=POISON",
                    "inline": "poison=POISON"}
    for i in range(1, n_workers):
        chaos_faults[f"w{i}"] = (
            f"seed=7,salt=w{i},tear_rate=0.4,corrupt_rate=0.3,"
            f"eval_fail_rate=0.3,eval_slow_rate=0.3,slow_s=0.05,"
            f"poison=POISON")
    chaos = _run_fleet(reqs, n_workers, max_attempts, chaos_faults, smoke)

    by_id = {r["id"]: r for r in chaos["responses"]}
    poison = by_id["POISON-req"]
    real_identical = all(
        _normalize(by_id[r["id"]]) == _normalize(baseline[r["id"]])
        for r in real)

    checks = {
        "fleet_all_real_ok": all(by_id[r["id"]]["ok"] for r in real),
        "chaos_bit_identical_to_baseline": real_identical,
        "zero_duplicate_evals": chaos["duplicates"] == {},
        "poison_quarantined": (not poison["ok"]
                               and bool(poison.get("quarantined"))
                               and poison.get("attempts") == max_attempts),
        "worker_died_mid_wave":
            chaos["stats"].get("worker_deaths", 0) >= 1,
        "retries_fired": chaos["stats"].get("retries", 0) > 0,
    }
    out = {
        "n_requests": len(reqs), "n_workers": n_workers,
        "max_attempts": max_attempts,
        "baseline_wall_s": round(baseline_wall, 2),
        "chaos_wall_s": round(chaos["wall_s"], 2),
        "chaos_stats": {k: v for k, v in chaos["stats"].items()
                        if k != "workers"},
        "chaos_fresh_evals": sum(chaos["fresh_counts"].values()),
        "chaos_unique_keys": len(chaos["fresh_counts"]),
        "duplicates": chaos["duplicates"],
    }

    if not smoke:
        # 3. control: same fleet, no faults — every key evaluated fresh
        # exactly once, nothing stolen, nothing retried
        clean = _run_fleet(reqs, n_workers, max_attempts,
                           {"w0": "poison=POISON", "inline":
                            "poison=POISON"}, smoke)
        clean_by_id = {r["id"]: r for r in clean["responses"]}
        checks["clean_bit_identical_to_baseline"] = all(
            _normalize(clean_by_id[r["id"]]) == _normalize(
                baseline[r["id"]]) for r in real)
        checks["clean_single_fresh_eval_per_key"] = (
            clean["duplicates"] == {} and
            all(n <= 1 for n in clean["fresh_counts"].values()))
        checks["clean_no_steals"] = \
            clean["stats"]["evals"]["by_reason"].get("steal", 0) == 0
        out["clean_wall_s"] = round(clean["wall_s"], 2)
        out["clean_stats"] = {k: v for k, v in clean["stats"].items()
                              if k != "workers"}

    out["checks"] = checks
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for CI")
    ap.add_argument("--out", default="results/benchmarks")
    args = ap.parse_args()
    # the fleet's workers compute on the CPU (Fleet's default), so the
    # baseline they must match bit for bit does too — and a parent that
    # never touches the chip cannot hold it from a worker
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    res = collect(args.smoke)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "bench_fleet.json"), "w") as f:
        json.dump(res, f, indent=1)
    s = res["chaos_stats"]
    print(f"bench_fleet: {res['n_requests']} requests  "
          f"{res['n_workers']} workers  baseline {res['baseline_wall_s']}s  "
          f"chaos {res['chaos_wall_s']}s  deaths {s.get('worker_deaths', 0)}  "
          f"retries {s.get('retries', 0)}  quarantined "
          f"{s.get('quarantined', 0)}  fresh evals "
          f"{res['chaos_fresh_evals']}/{res['chaos_unique_keys']} keys  "
          f"duplicates {res['duplicates']}")
    print("checks:", json.dumps(res["checks"]))
    return 0 if all(res["checks"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
