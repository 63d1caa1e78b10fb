#!/usr/bin/env python3
"""On-chip smoke test: the DSE campaign and the serving loop on one TPU.

    python3 chip_smoke.py [--seed N]

Runs in one process, through the entry points a user calls, and prints
one line per phase (engine, sizes, wall time with compile time apart,
correctness numbers):

  device     fails unless JAX's first device is a TPU — no CPU fallback;
  analytic   `SweepQuery` over the paper's configuration space (every
             gain-cell topology x word sizes 8-256 x words 16-1024 x every
             write-Vt flavor the write device admits x both WWL-boost
             settings), `CoDesignQuery` over an 8-rung voltage ladder and
             one `CompileQuery`; checked on a sample covering every
             topology against scalar `dse.evaluate` run on the CPU device
             (1e-6 relative, identical verdicts, banks and chosen designs);
  transient  the same lattice at transient fidelity (default 300 steps,
             fused engine, f64), `MatchQuery`, `OptimizeQuery` and a
             simulated `CompileQuery`; checked against the dense f64
             reference on the CPU device (t_cell within 1%, identical
             swing verdicts); then the lattice at f32, which must run the
             Pallas kernel (`tpu_custom_call` in the compiled program);
  serving    `ServeEngine` on qwen2-0.5b at full width (bf16, random
             weights from the seed), 4 slots, a 1024-token window and 8
             mixed-length requests, whose greedy streams must be identical
             in device and host mode; its telemetry window then feeds
             `Session.codesign_measured`.

Any failed check or exception exits non-zero before the last line, which
is exactly one JSON object naming the device. JAX's persistent compile
cache goes where `JAX_COMPILATION_CACHE_DIR` says, else to
`<checkout>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GC_CELLS = ("gc2t_nn", "gc2t_np", "gc2t_osos", "gc3t", "gc2t_hyb")
WORD_SIZES = (8, 16, 32, 64, 128, 256)
NUM_WORDS = (16, 32, 64, 128, 256, 512, 1024)
# None is the cell's own write flavor; lattice_configs drops the flavors
# of the other device family (Si VTs on OS cells and vice versa)
WRITE_VTS = (None, "nmos_lvt", "nmos_hvt", "os_n_hvt")
VDD_LADDER = (0.7, 0.775, 0.85, 0.925, 1.0, 1.075, 1.15, 1.225)
PROFILES = (("qwen2-0.5b", "decode_32k"), ("llama3.2-1b", "decode_32k"))
METRICS = ("area_um2", "f_max_hz", "eff_bw_bps", "leakage_w", "refresh_w",
           "retention_s", "t_read_s", "t_write_s")
ANALYTIC_RTOL = 1e-6
TRANSIENT_RTOL = 0.01
SERVE_ARCH = "qwen2-0.5b"
# prompts of two lengths (prefill compiles once per length and batch
# bucket) and eight different output lengths
SERVE_PROMPTS = (24, 200, 24, 200, 200, 24, 200, 24)
SERVE_NEW = (24, 8, 40, 16, 32, 12, 20, 6)


class CheckFailed(Exception):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class Clock:
    """Wall time of a phase and the part of it XLA spent compiling (or
    fetching a compiled program from the persistent cache)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def start(self):
        self.t0, self.c0, self.h0 = (time.perf_counter(), self.compile_s,
                                     self.cache_hits)

    def line(self, phase: str, **fields) -> None:
        out = {"wall_s": time.perf_counter() - self.t0,
               "compile_s": self.compile_s - self.c0,
               "cache_hits": self.cache_hits - self.h0, **fields}
        print(f"phase={phase} " + json.dumps(out, default=str), flush=True)


def rel_err(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(a), abs(b))


def cfg_key(cfg) -> tuple:
    return (cfg.cell, cfg.word_size, cfg.num_words, cfg.write_vt, cfg.wwlls)


def pick_sample(cfgs, rng):
    """Per topology, two points from its default group (own write
    flavor, no WWL boost) and two from one other random group."""
    out = []
    for cell in GC_CELLS:
        groups = {}
        for c in cfgs:
            if c.cell == cell:
                groups.setdefault((c.write_vt, c.wwlls), []).append(c)
        keys = sorted(groups, key=str)
        other = [k for k in keys if k != (None, False)]
        for k in ((None, False), other[rng.integers(len(other))]):
            g = groups[k]
            for i in rng.choice(len(g), 2, replace=False):
                out.append(g[int(i)])
    return out


def phase_device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform!r})",
              file=sys.stderr)
        raise SystemExit(2)
    return dev


def phase_analytic(clock, s, lattice, sample, cpu):
    import jax
    import numpy as np
    from repro.api import CoDesignQuery, CompileQuery, Session
    from repro.core import dse, dse_batch, multibank
    from repro.workloads.profiler import profile_arch

    clock.start()
    table = s.run(lattice)
    profiles = tuple(profile_arch(a, sh) for a, sh in PROFILES)
    cq = CoDesignQuery(profiles=profiles, sweep=lattice,
                       vdd_scales=VDD_LADDER)
    report = s.run(cq)
    comp = s.run(CompileQuery(sample[0]))
    lat = report.lattice
    demands = [d for p in profiles for d in p.demands()]
    rf = np.array([d.read_freq_hz for d in demands])
    lt = np.array([d.lifetime_s for d in demands])
    feas = dse_batch.feasible_grid(lat.f_max_hz, lat.retention_s,
                                   lat.swing_ok, lat.num_words, rf, lt)
    banks = dse_batch.banks_needed_grid(lat.f_max_hz, lat.retention_s,
                                        lat.swing_ok, lat.bits,
                                        lat.num_words, rf, lt)
    wall = time.perf_counter() - clock.t0

    # -- references on the CPU device
    pts = {cfg_key(p.cfg): p for p in table.points}
    col = {cfg_key(c): i for i, c in enumerate(lat.cfgs)}
    rungs = (0, VDD_LADDER.index(1.0), len(VDD_LADDER) - 1)
    worst, n_cmp, n_verdicts = (0.0, None), 0, 0

    def compare(worst, got, ref, where):
        for m in METRICS:
            worst = max(worst, (rel_err(getattr(got, m), getattr(ref, m)),
                                f"{m} {where}"), key=lambda w: w[0])
        return worst

    with jax.default_device(cpu):
        for cfg in sample:
            ref = dse.evaluate(cfg)
            got = pts[cfg_key(cfg)]
            worst = compare(worst, got, ref, cfg_key(cfg))
            require(got.swing_ok == ref.swing_ok, f"swing_ok {cfg_key(cfg)}")
            n_cmp += 1
            for v in rungs:
                ref = dse.evaluate(cfg, VDD_LADDER[v])
                got = lat.point(v, col[cfg_key(cfg)])
                worst = compare(worst, got, ref,
                                cfg_key(cfg) + (VDD_LADDER[v],))
                require(got.swing_ok == ref.swing_ok,
                        f"swing_ok {cfg_key(cfg)} @ {VDD_LADDER[v]}")
                for j, d in enumerate(demands):
                    i = col[cfg_key(cfg)]
                    require(bool(feas[v, i, j]) == dse.feasible(ref, d),
                            f"feasible {cfg_key(cfg)} {d.level}")
                    require(int(banks[v, i, j]) ==
                            multibank.banks_needed(ref, d),
                            f"banks_needed {cfg_key(cfg)} {d.level}")
                    n_verdicts += 1
                n_cmp += 1
        cpu_report = Session().run(cq)
    require(worst[0] <= ANALYTIC_RTOL,
            f"analytic max rel err {worst[0]} > {ANALYTIC_RTOL} ({worst[1]})")

    def chosen(rep):
        return [(p["workload"], lvl, e.get("bank", {}).get("cell"),
                 e.get("bank", {}).get("word_size"),
                 e.get("bank", {}).get("num_words"),
                 e.get("bank", {}).get("write_vt"),
                 e.get("bank", {}).get("wwlls"), e.get("vdd_v"),
                 e.get("banks_needed"))
                for p in rep.plans for lvl, e in sorted(p["levels"].items())]

    require(chosen(report) == chosen(cpu_report),
            "co-design chose different designs on TPU and CPU")
    clock.line("analytic", engine="dse_batch vmap (XLA, f64)",
               points=len(table.points), vdd_rungs=len(VDD_LADDER),
               demands=len(demands), cube=list(feas.shape),
               device_wall_s=wall, compile_f_max_hz=comp.timing.f_max_hz,
               sample=len(sample), compared=n_cmp, verdicts=n_verdicts,
               max_rel_err=worst[0], worst_at=worst[1],
               plans=[p["workload"] for p in report.plans],
               all_feasible=report.all_feasible)
    return demands


def fused_engine_name(cfg, precision, platform):
    from repro.core import timing
    from repro.core.bank import build_bank
    from repro.kernels.batched_solve.newton import build_fused_spec
    from repro.kernels.batched_solve.ops import fused_engine
    ckt, _ = timing.read_netlist(build_bank(cfg))
    return fused_engine(build_fused_spec(ckt.build(), precision), platform)


def phase_transient(clock, s, lattice, sample, demands, cpu, platform):
    import jax
    import warnings
    from repro.api import CompileQuery, MatchQuery, OptimizeQuery
    from repro.core.dse_batch import pow2_bucket
    from repro.core.spice import char_batch

    tq = dataclasses.replace(lattice, fidelity="transient")
    clock.start()
    table = s.run(tq)
    match = s.run(MatchQuery(demands=tuple(demands), sweep=tq))
    opt = s.run(OptimizeQuery())
    comp = s.run(CompileQuery(sample[0], simulate=True))
    wall = time.perf_counter() - clock.t0

    chars = {cfg_key(p.cfg): c for p, c in zip(table.points, table.transient)}
    with jax.default_device(cpu):
        refs = char_batch.characterize(sample, solver="jnp")
    worst, swing_ok = 0.0, 0
    for cfg, ref in zip(sample, refs):
        got = chars[cfg_key(cfg)]
        require(got.swing_ok == ref.swing_ok, f"swing_ok {cfg_key(cfg)}")
        swing_ok += ref.swing_ok
        worst = max(worst, rel_err(got.t_cell_s, ref.t_cell_s))
    comp_err = rel_err(comp.t_cell_sim_s, refs[0].t_cell_s)
    require(worst <= TRANSIENT_RTOL,
            f"transient t_cell max rel err {worst} > {TRANSIENT_RTOL}")
    require(comp_err <= TRANSIENT_RTOL,
            f"CompileQuery(simulate=True) rel err {comp_err}")
    n_sim = sum(c is not None for c in table.transient)
    clock.line("transient", engine=fused_engine_name(sample[0], "f64",
                                                      platform),
               precision="f64", steps=tq.sim_steps, points=n_sim,
               device_wall_s=wall, sample=len(sample), swing_ok=swing_ok,
               max_rel_err_vs_cpu_dense_f64=worst,
               compile_simulate_rel_err=comp_err,
               match_pass_rate=match.pass_rate,
               optimize={"met": opt.met, "improved": opt["improved"],
                         "objective_value": opt["objective_value"]})

    # -- f32: the Pallas kernel (screening-grade precision)
    fq = dataclasses.replace(tq, precision="f32")
    clock.start()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ftable = s.run(fq)
    wall = time.perf_counter() - clock.t0
    B = pow2_bucket(len(WORD_SIZES) * len(NUM_WORDS))
    kernels = {}
    for cell in ("gc2t_nn", "gc2t_np"):       # precharge, predischarge
        cfg = next(c for c in sample if c.cell == cell)
        text = char_batch.lattice_program(cfg, B, precision="f32") \
            .compile().as_text()
        kernels[cell] = "tpu_custom_call" in text
        require(kernels[cell], f"f32 lattice program for {cell} has no "
                "tpu_custom_call")
    f64 = {cfg_key(p.cfg): c for p, c in zip(table.points, table.transient)
           if c is not None}
    dev32 = max(rel_err(c.t_cell_s, f64[cfg_key(p.cfg)].t_cell_s)
                for p, c in zip(ftable.points, ftable.transient)
                if c is not None and math.isfinite(c.t_cell_s)
                and math.isfinite(f64[cfg_key(p.cfg)].t_cell_s))
    clock.line("transient_f32",
               engine=fused_engine_name(sample[0], "f32", platform),
               precision="f32", grade="screening", steps=fq.sim_steps,
               points=sum(c is not None for c in ftable.transient),
               device_wall_s=wall, tpu_custom_call=kernels,
               max_rel_dev_vs_f64=dev32)


def phase_serving(clock, s, lattice, seed):
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.runtime import TelemetryCollector
    from repro.serving import ServeEngine
    from repro.serving.engine import Request

    cfg = get_config(SERVE_ARCH)
    clock.start()
    params = Model(cfg).init(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in SERVE_PROMPTS]
    streams, stats = {}, {}
    col = TelemetryCollector()
    for mode in ("device", "host"):
        eng = ServeEngine(cfg, params, n_slots=4, window=1024, mode=mode,
                          decode_chunk=8,
                          telemetry=col if mode == "device" else None)
        for i, (p, m) in enumerate(zip(prompts, SERVE_NEW)):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=m,
                               temperature=0.0))
        t0 = time.perf_counter()
        done, steps = eng.run()
        streams[mode] = {r.rid: list(r.out_tokens) for r in done}
        stats[mode] = {"steps": steps, "host_syncs": eng.host_syncs,
                       "wall_s": time.perf_counter() - t0}
    require(len(streams["device"]) == len(SERVE_PROMPTS),
            "device mode did not answer every request")
    require(all(len(streams["device"][i]) == m
                for i, m in enumerate(SERVE_NEW)),
            "device mode emitted the wrong token counts")
    require(streams["device"] == streams["host"],
            "greedy streams differ between device and host mode")
    win = col.snapshot()
    rep = s.codesign_measured([win], cfg, sweep=lattice,
                              vdd_scales=VDD_LADDER)
    require(len(rep.plans) == 1, "no co-design plan from telemetry")
    plan = rep.plans[0]
    clock.line("serving", engine="ServeEngine device+host", arch=cfg.name,
               dtype=cfg.dtype, slots=4, window=1024,
               requests=len(SERVE_PROMPTS),
               tokens=sum(map(len, streams["device"].values())),
               modes=stats, streams_identical=True,
               telemetry={"decode_steps": win.decode_steps,
                          "decode_tokens": win.decode_tokens,
                          "prefill_tokens": win.prefill_tokens},
               plan={"workload": plan["workload"],
                     "feasible": plan["feasible"],
                     "L1": plan["levels"]["L1"].get("bank", {}).get("cell"),
                     "L2": plan["levels"]["L2"].get("bank", {}).get("cell")})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    import numpy as np
    clock = Clock()
    clock.start()
    dev = phase_device()
    clock.line("device", platform=dev.platform, kind=dev.device_kind,
               count=len(jax.devices()), compile_cache=cache_dir)

    from repro.api import Session, SweepQuery
    cpu = jax.devices("cpu")[0]
    s = Session()
    lattice = SweepQuery(cells=GC_CELLS, word_sizes=WORD_SIZES,
                         num_words=NUM_WORDS, write_vts=WRITE_VTS,
                         wwlls=(False, True))
    sample = pick_sample(lattice.configs(s.tech),
                         np.random.default_rng(args.seed))
    demands = phase_analytic(clock, s, lattice, sample, cpu)
    phase_transient(clock, s, lattice, sample, demands, cpu, dev.platform)
    phase_serving(clock, s, lattice, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        raise SystemExit(1)
