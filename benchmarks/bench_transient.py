"""Benchmark: fused sparse-Newton transient engines vs the PR 2 dense
batched baseline, plus the scalar-parity and Newton-parity contracts.

    PYTHONPATH=src python benchmarks/bench_transient.py [--repeats 1]
    PYTHONPATH=src python benchmarks/bench_transient.py --smoke   # CI

Three sections:

  engine   — one topology (gc2t_nn 32x32), B lanes with jittered ladder
             R/C and stop times, identical inputs into
             `Transient.run_lattice` per (solver, precision) mode:
             "jnp"/f64 (the PR 2 dense batched baseline), "pallas"/f64,
             "pallas"/mixed, "sparse"/f64. Reports warm wall time,
             speedup over the dense baseline, max trace deviation and
             t_cell relative deviation vs the dense reference.
  scalar   — whole-lattice `characterize` (default solver) vs the
             per-point `timing.simulate_read` loop; per-point t_cell
             must agree within 1% (the parity contract).
  newton   — analytic-stamp Newton trace vs the jacfwd Newton trace.

Checks recorded (the PR's acceptance bar):
  * engine_speedup_ge_5x — fused "pallas"/f64 >= 5x over the dense
                           batched baseline at B >= 64 (full mode only;
                           smoke batches are too small to time)
  * engine_parity_1pct   — every fused mode's t_cell within 1% of the
                           dense engine on the jittered batch
  * parity_within_1pct   — batched t_cell within 1% of scalar
                           simulate_read
  * newton_parity_1e-6   — analytic vs jacfwd trace gap <= 1e-6 (f64)

Writes results/bench_transient.json (machine-readable: speedups, parity,
solver modes — uploaded by CI) and mirrors it to
results/benchmarks/BENCH_transient.json for the benchmark index.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

ENGINE_MODES = (("jnp", "f64"), ("pallas", "f64"), ("pallas", "mixed"),
                ("sparse", "f64"))


def _lattice(smoke: bool):
    from repro.core.dse import lattice_configs
    if smoke:
        return lattice_configs(cells=("gc2t_nn", "gc2t_np"),
                               word_sizes=(16, 32), num_words=(16, 32),
                               wwlls=(False,))
    return lattice_configs(cells=("gc2t_nn", "gc2t_np", "gc2t_osos"),
                           word_sizes=(16, 32, 64),
                           num_words=(16, 32, 64, 128),
                           wwlls=(False, True))


def _best_of(fn, repeats: int):
    cold = None
    walls = []
    res = None
    for _ in range(repeats + 1):
        t0 = time.time()
        res = fn()
        walls.append(time.time() - t0)
        cold = cold if cold is not None else walls[0]
    return res, min(walls[1:]) if len(walls) > 1 else walls[0], cold


def _engine_inputs(B: int):
    """One topology's run_lattice inputs with per-lane jitter: the same
    assembly path as char_batch._characterize_group, but B independent
    lanes from a single netlist template (jittered ladder R/C and stop
    times stand in for a real parameter lattice)."""
    from repro.core import timing
    from repro.core.bank import BankConfig, build_bank
    bank = build_bank(BankConfig(32, 32, "gc2t_nn"))
    ckt, meta = timing.read_netlist(bank)
    res_stamps, cap_stamps, src_G = ckt.build_stamps()
    system = ckt.build()

    rng = np.random.default_rng(0)
    g_vals = np.asarray([g for _, _, g in ckt.res])
    c_vals = np.asarray([c for _, _, c in ckt.caps])
    g_b = g_vals[None] * (1.0 + 0.1 * rng.uniform(-1, 1, (B, len(g_vals))))
    c_b = c_vals[None] * (1.0 + 0.1 * rng.uniform(-1, 1, (B, len(c_vals))))
    G_b = src_G[None] + np.einsum("br,rij->bij", g_b, res_stamps)
    C_b = np.einsum("bc,cij->bij", c_b, cap_stamps)

    t_an, _ = timing.cell_read_time(bank)
    t_end1 = max(timing.T_END_OVER_ANALYTIC * t_an, timing.T_END_MIN_S)
    t_end = t_end1 * (1.0 + 0.1 * rng.uniform(-1, 1, B))
    t0 = timing.T0_FRACTION * t_end

    wt = wv = None
    v_pre = 0.0
    for p in range(B):
        waves_p, v_pre = timing.read_stimulus(bank.cell, bank.cfg.tech,
                                              meta["v_sn"], t0[p])
        if wt is None:
            k = max(len(t) for t, _ in waves_p)
            wt = np.zeros((B, len(waves_p), k))
            wv = np.zeros((B, len(waves_p), k))
        for w, (t, v) in enumerate(waves_p):
            wt[p, w] = t + [t[-1]] * (k - len(t))
            wv[p, w] = v + [v[-1]] * (k - len(v))
    return system, bank, dict(wt=wt, wv=wv, t_end=t_end, G_b=G_b, C_b=C_b,
                              v_pre=v_pre, t0=t0)


def _bench_engines(B: int, n_steps: int, repeats: int) -> dict:
    """Identical lattice inputs through every (solver, precision) engine."""
    import jax.numpy as jnp
    from jax import enable_x64
    from repro.core.spice.transient import Transient, crossing_time

    with enable_x64():
        system, bank, inp = _engine_inputs(B)
        v0 = jnp.full((system.n,), inp["v_pre"])

        def run(tr):
            res = tr.run_lattice(inp["wt"], inp["wv"], inp["t_end"],
                                 n_steps,
                                 over_batches={"G": inp["G_b"],
                                               "C": inp["C_b"]}, v0=v0)
            return {k: np.asarray(v) for k, v in res.items()}

        out = {}
        ref = None
        for solver, precision in ENGINE_MODES:
            tr = Transient(system, solver=solver, precision=precision)
            res, warm, cold = _best_of(lambda: run(tr), repeats)
            swing = bank.cfg.tech.v_sense_se
            target = inp["v_pre"] + (swing if bank.cell.predischarge
                                     else -swing)
            tc, valid = crossing_time(res["t"], res["rbl_near"], target,
                                      rising=bank.cell.predischarge)
            t_cell = np.where(np.asarray(valid),
                              np.asarray(tc) - inp["t0"], np.inf)
            entry = {"solver": solver, "precision": precision,
                     "warm_s": round(warm, 4), "cold_s": round(cold, 3)}
            if ref is None:
                ref = {"all": res["all"], "t_cell": t_cell, "warm": warm}
            else:
                trace_dev = float(np.max(np.abs(
                    res["all"].astype(np.float64) - ref["all"])))
                both = np.isfinite(t_cell) & np.isfinite(ref["t_cell"])
                tc_dev = float(np.max(
                    np.abs(t_cell[both] - ref["t_cell"][both])
                    / ref["t_cell"][both])) if both.any() else float("inf")
                if not np.array_equal(np.isfinite(t_cell),
                                      np.isfinite(ref["t_cell"])):
                    tc_dev = float("inf")
                entry.update(
                    speedup=round(ref["warm"] / max(warm, 1e-9), 1),
                    trace_dev=float(f"{trace_dev:.3g}"),
                    t_cell_rel_dev=float(f"{tc_dev:.3g}"))
            out[f"{solver}/{precision}"] = entry
    return out


def _newton_parity() -> float:
    """Max |trace| gap between analytic-stamp Newton and jacfwd Newton."""
    import jax.numpy as jnp
    from jax import enable_x64
    from repro.core import timing
    from repro.core.bank import BankConfig, build_bank
    from repro.core.spice.transient import Transient
    with enable_x64():
        bank = build_bank(BankConfig(32, 32, "gc2t_nn"))
        ckt, meta = timing.read_netlist(bank)
        sys = ckt.build()
        t_an, _ = timing.cell_read_time(bank)
        t_end = max(timing.T_END_OVER_ANALYTIC * t_an, timing.T_END_MIN_S)
        waves, v_pre = timing.read_stimulus(
            bank.cell, bank.cfg.tech, meta["v_sn"],
            timing.T0_FRACTION * t_end)
        v0 = jnp.full((sys.n,), v_pre)
        ref = Transient(sys, newton="jacfwd").run(waves, t_end,
                                                  n_steps=300, v0=v0)
        got = Transient(sys, newton="full", tol=1e-9).run(waves, t_end,
                                                          n_steps=300, v0=v0)
        return float(jnp.max(jnp.abs(ref["all"] - got["all"])))


def collect(repeats: int = 1, smoke: bool = False, n_steps: int = 300
            ) -> dict:
    from repro.core import timing
    from repro.core.bank import build_bank
    from repro.core.spice.char_batch import characterize

    # -- engine section: fused modes vs the PR 2 dense batched baseline
    B = 16 if smoke else 64
    engines = _bench_engines(B, n_steps, repeats)
    pallas = engines["pallas/f64"]
    engine_speedup = pallas.get("speedup", 0.0)
    engine_parity = max(e.get("t_cell_rel_dev", 0.0)
                        for e in engines.values())

    # -- scalar-parity section: batched characterize vs simulate_read
    cfgs = _lattice(smoke)
    batch, batch_s, batch_cold = _best_of(
        lambda: characterize(cfgs, n_steps=n_steps), repeats)
    ref, loop_s, loop_cold = _best_of(
        lambda: [timing.simulate_read(build_bank(c), n_steps=n_steps)[0]
                 for c in cfgs], repeats)

    worst = 0.0
    for ch, t_ref in zip(batch, ref):
        if np.isinf(t_ref) or np.isinf(ch.t_cell_s):
            if t_ref != ch.t_cell_s:
                worst = float("inf")
            continue
        worst = max(worst, abs(ch.t_cell_s - t_ref) / t_ref)

    newton_dev = _newton_parity()
    speedup = loop_s / max(batch_s, 1e-9)
    n_topologies = len({(c.cell, c.write_vt, c.wwlls) for c in cfgs})
    return {
        "engine_batch": B,
        "engines": engines,
        "engine_speedup": engine_speedup,
        "n_points": len(cfgs),
        "n_topologies": n_topologies,
        "n_steps": n_steps,
        "loop_wall_s": round(loop_s, 3),
        "batched_wall_s": round(batch_s, 3),
        "loop_cold_s": round(loop_cold, 3),
        "batched_cold_s": round(batch_cold, 3),
        "speedup": round(speedup, 1),
        "max_rel_dev_t_cell": float(f"{worst:.3g}"),
        "newton_trace_dev": float(f"{newton_dev:.3g}"),
        "checks": {
            "engine_speedup_ge_5x": engine_speedup >= 5.0,
            "engine_parity_1pct": engine_parity <= 0.01,
            "parity_within_1pct": worst <= 0.01,
            "newton_parity_1e-6": newton_dev <= 1e-6,
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="small lattice for CI (skips the 5x bars)")
    ap.add_argument("--n-steps", type=int, default=300)
    ap.add_argument("--out", default="results")
    args = ap.parse_args()
    res = collect(args.repeats, smoke=args.smoke, n_steps=args.n_steps)
    os.makedirs(os.path.join(args.out, "benchmarks"), exist_ok=True)
    for path in (os.path.join(args.out, "bench_transient.json"),
                 os.path.join(args.out, "benchmarks",
                              "BENCH_transient.json")):
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
    eng = "  ".join(
        f"{k} {v['warm_s']}s" + (f" ({v['speedup']}x)" if "speedup" in v
                                 else "")
        for k, v in res["engines"].items())
    print(f"bench_transient: engines[B={res['engine_batch']}] {eng}")
    print(f"  lattice {res['n_points']} pts ({res['n_topologies']} topo)  "
          f"loop {res['loop_wall_s']}s  batched {res['batched_wall_s']}s  "
          f"({res['speedup']}x)  t_cell dev {res['max_rel_dev_t_cell']}  "
          f"newton dev {res['newton_trace_dev']}")
    checks = dict(res["checks"])
    if args.smoke:
        # tiny batches: wall-clock ratios are compile/dispatch noise
        checks.pop("engine_speedup_ge_5x")
        checks.pop("speedup_ge_5x", None)
    return 0 if all(checks.values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
