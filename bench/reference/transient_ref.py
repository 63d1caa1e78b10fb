"""Reference transient read characterization of a list of design points.

For every gain-cell point: build its own read-column netlist and
stimulus (the scalar recipe: stop time 6x the analytic sense time, at
least 0.5 ns, release at 5% of it), integrate `n_steps` backward-Euler
steps with the dense Newton stepper in float64, and take the
interpolated crossing of the sense swing on the near end of the read
bitline. Points whose netlists share a structure run as one vmapped
program with every element and device value as an operand, so a sample
of any mix of topologies compiles at most one program per structure and
chunk size. Call on the CPU device (`bench.reference.on_cpu`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from bench.reference import timing as timing_mod
from bench.reference.bank import build_bank
from bench.reference.transient import crossing_time, make_stepper

CHUNK = 8                 # points per program call (edge-padded)
_DEV_KEYS = ("pol", "vt0", "n", "kp", "lam", "w", "l", "ig")
_PROGRAMS = {}


def _structure(system) -> tuple:
    return (system.n,
            tuple(tuple(np.asarray(system.didx[t]).tolist()) for t in "gab"),
            tuple(np.asarray(system.src_node).tolist()),
            tuple(np.asarray(system.src_wave).tolist()),
            tuple(sorted(system.probes.items())))


def _program(system, n_steps: int):
    key = (_structure(system), n_steps)
    fn = _PROGRAMS.get(key)
    if fn is None:
        step = make_stepper(system)
        node = system.probes["rbl_near"] - 1

        def one(t_end, wt, wv, v0, over):
            h = t_end / n_steps

            def body(v, i):
                v = step(v, (i + 1.0) * h, h, wt, wv, over)
                return v, v[node]

            _, trace = jax.lax.scan(body, v0, jnp.arange(n_steps))
            return trace

        fn = _PROGRAMS[key] = jax.jit(jax.vmap(one))
    return fn


def _prepare(cfg, n_seg: int):
    bank = build_bank(cfg)
    if not bank.is_gc:
        return None
    cell, tech = bank.cell, cfg.tech
    ckt, meta = timing_mod.read_netlist(bank, n_seg=n_seg)
    system = ckt.build()
    t_an = timing_mod.cell_read_time(bank)[0]
    t_end = max(timing_mod.T_END_OVER_ANALYTIC * t_an,
                timing_mod.T_END_MIN_S)
    t0 = timing_mod.T0_FRACTION * t_end
    waves, v_pre = timing_mod.read_stimulus(cell, tech, meta["v_sn"], t0)
    swing = tech.v_sense_se
    target = v_pre + (swing if cell.predischarge else -swing)
    return dict(system=system, t_end=t_end, t0=t0, waves=waves, v_pre=v_pre,
                target=target, rising=bool(cell.predischarge), t_an=t_an)


def characterize(cfgs, *, n_steps: int = 300, n_seg: int = 8):
    """Per config: (t_cell_s, swing_ok, t_end_s), with t_cell_s = inf
    where the swing is never reached and t_end_s the simulated interval;
    None for a config with no single-ended read column."""
    with enable_x64():
        preps = [_prepare(c, n_seg) for c in cfgs]
        out = [None] * len(cfgs)
        by_struct = {}
        for i, p in enumerate(preps):
            if p is not None:
                by_struct.setdefault(_structure(p["system"]), []).append(i)
        for idx in by_struct.values():
            for s in range(0, len(idx), CHUNK):
                part = idx[s:s + CHUNK]
                for i, res in zip(part, _run_chunk([preps[i] for i in part],
                                                   n_steps)):
                    out[i] = res
    return out


def _run_chunk(preps, n_steps: int):
    k = max(len(t) for p in preps for t, _ in p["waves"])
    rows = preps + [preps[-1]] * (CHUNK - len(preps))

    def padded(vals):
        return list(vals) + [vals[-1]] * (k - len(vals))

    wt = np.array([[padded(t) for t, _ in p["waves"]] for p in rows])
    wv = np.array([[padded(v) for _, v in p["waves"]] for p in rows])
    t_end = np.array([p["t_end"] for p in rows])
    n = rows[0]["system"].n
    v0 = np.array([np.full(n, p["v_pre"]) for p in rows])
    over = {"G": np.stack([np.asarray(p["system"].G) for p in rows]),
            "C": np.stack([np.asarray(p["system"].C) for p in rows])}
    for key in _DEV_KEYS:
        over[key] = np.stack([np.asarray(p["system"].dev[key]) for p in rows])
    trace = _program(rows[0]["system"], n_steps)(
        jnp.asarray(t_end), jnp.asarray(wt), jnp.asarray(wv),
        jnp.asarray(v0), {key: jnp.asarray(a) for key, a in over.items()})
    trace = np.asarray(trace)
    out = []
    for i, p in enumerate(preps):
        t = (np.arange(n_steps) + 1) * (p["t_end"] / n_steps)
        tc, valid = crossing_time(t, trace[i], p["target"], p["rising"])
        valid = bool(valid)
        t_cell = float(tc) - p["t0"] if valid else math.inf
        out.append((t_cell, valid, p["t_end"]))
    return out
