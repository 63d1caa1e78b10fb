"""Topology-grouped batched transient characterization of the read path.

`timing.simulate_read` is the scalar (HSPICE-class) reference: per design
point it rebuilds the RBL-column netlist, re-jits a fresh Newton
integrator and extracts the sense-swing crossing on host — O(lattice)
compilations and O(lattice * n_steps * newton) small dense solves issued
one program at a time. This module characterizes a whole design lattice
in a handful of compiled programs:

  1. group configs by cell topology (`dse_batch.topology_key`): within a
     group the critical-path netlist STRUCTURE (nodes, devices, sources)
     is identical — only the wire parasitics, stop time and wave timings
     differ with the array geometry;
  2. build ONE parametric netlist per group and lift the per-point
     structural quantities into parameter arrays:
       * the linear elements assemble via unit-value incidence stamps
         (`Circuit.build_stamps`): G_b = src_G + g_b @ R_stamps and
         C_b = c_b @ C_stamps, where g_b/c_b (B, n_elem) hold each
         point's bitline-ladder segment conductances, wire/SA/junction
         capacitances — an einsum instead of B python assemblies;
       * per-point stop times t_end (from the analytic swing estimate)
         and the precharge/wordline wave timings enter as (B, ...) arrays;
  3. integrate the whole group in a single `Transient.run_lattice`
     program. solver="pallas" (default) routes to the fused sparse-
     Newton engine (kernels.batched_solve.newton): the constant part of
     the Jacobian G + C/h + gmin is inverted ONCE per run (h is fixed
     per point) and each Newton iteration applies a rank-3*n_dev
     Woodbury correction from the analytic device stamps — a Pallas
     kernel on TPU, a bit-identical XLA while_loop on CPU. "sparse"
     replays a symbolic LU over the fixed nonzero pattern instead;
     "jnp" keeps the dense `jax.vmap` + `jnp.linalg.solve` reference
     path of PR 2;
  4. extract the sense-swing threshold crossing vectorized on-device
     (`transient.crossing_time`), interpolated between bracketing steps
     exactly like the scalar reference.

Compiled programs are memoized per (netlist structure, n_seg, n_steps,
solver, precision) — the fused engines take every element and device
value as an operand, so all topology groups of one read-column
structure share a program — and repeated characterizations of
overlapping lattices (Session sweeps, benchmarks) pay tracing once.

Newton Jacobian stamp math (the per-iteration hot path): the MNA Newton
system is J dv = F(v) with J = C/h + G + dI/dv + gmin. dI/dv is built
from per-device 3x3 analytic stamps — `channel_current_grads` gives
(di/dvg, di/dva, di/dvb) of the EKV channel current in closed form, one
vectorized pass over the device parameter arrays, and
`MNASystem.device_jacobian` scatter-adds the nine KCL entries per device
into the dense matrix. See those docstrings for the row/column algebra.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from repro.core import timing as timing_mod
from repro.core import trace
from repro.core.bank import BankConfig, build_bank
from repro.core.host import on_host
from repro.core.dse_batch import (group_by_topology, pad_bucket,
                                  pow2_bucket, topology_key)
from repro.core.spice.transient import Transient, crossing_time
from repro.kernels.batched_solve.sparse import PARAM_FIELDS

_PIPE_CACHE_MAX = 32     # compiled-pipeline entries kept (FIFO eviction)
_DEV_KEYS = PARAM_FIELDS + ("ig",)


@dataclass
class TransientChar:
    """Transient read characterization of one design point."""
    cfg: BankConfig
    t_cell_s: float            # simulated sense-swing time (inf: no cross)
    t_cell_analytic_s: float   # analytic estimate (timing.cell_read_time)
    rel_dev: float             # |analytic - sim| / sim (the GEMTOO gap)
    swing_ok: bool             # trace reached the sense target
    t_end_s: float
    n_steps: int

    def as_dict(self) -> dict:
        return {"cell": self.cfg.cell, "word_size": self.cfg.word_size,
                "num_words": self.cfg.num_words, "wwlls": self.cfg.wwlls,
                "write_vt": self.cfg.write_vt,
                "t_cell_sim_s": self.t_cell_s,
                "t_cell_analytic_s": self.t_cell_analytic_s,
                "rel_dev": self.rel_dev, "swing_ok": self.swing_ok,
                "t_end_s": self.t_end_s, "n_steps": self.n_steps}


# (topology_key, n_seg, n_steps, solver) -> (system, Transient, stamps)
_PIPE_CACHE: Dict[tuple, tuple] = {}
# (netlist structure, solver, precision) -> Transient shared by every
# topology group of that structure (fused engines only)
_SHARED_TR: Dict[tuple, Transient] = {}


def _structure_key(system) -> tuple:
    """Everything a fused lattice program bakes in about its netlist:
    node count, device terminals, sources, probes and the matrix
    sparsity. Element and device VALUES are lattice operands, so the
    topology groups of one read-column structure (every write-Vt flavor
    and WWL boost setting of a cell, and cells that share a read scheme)
    run the same compiled program."""
    return (system.n,
            tuple(tuple(np.asarray(system.didx[t]).tolist()) for t in "gab"),
            tuple(np.asarray(system.src_node).tolist()),
            tuple(np.asarray(system.src_wave).tolist()),
            tuple(sorted(system.probes.items())),
            (np.asarray(system.G) != 0).tobytes(),
            (np.asarray(system.C) != 0).tobytes())


def _device_batches(system, B: int, **over) -> dict:
    """Every device parameter of `system` as a (B, n_dev) lattice
    operand (`over` replaces entries), so the shared program takes the
    group's own device values."""
    n_dev = int(np.shape(system.dev["pol"])[-1])
    out = {k: np.broadcast_to(np.asarray(system.dev[k]), (B, n_dev))
           for k in _DEV_KEYS}
    out.update(over)
    return out


def _pipeline(bank0, key: tuple):
    """Template netlist + jitted Transient + incidence stamps for one
    topology group (memoized: repeat characterizations re-trace nothing).
    The fused engines share one Transient per netlist structure
    (`_structure_key`); the dense "jnp" reference bakes the group's
    device values and keeps its own.

    The key embeds id(tech) (via topology_key), so each entry also PINS
    the TechFile object: without the strong reference, a collected tech's
    id could be reused by a different TechFile and silently hit the stale
    template."""
    hit = _PIPE_CACHE.get(key)
    if hit is not None:
        return hit[:-1]
    n_seg, n_steps, solver, precision = key[-4:]
    with on_host():
        ckt, meta = timing_mod.read_netlist(bank0, n_seg=n_seg)
        res_stamps, cap_stamps, src_G = ckt.build_stamps()
        system = ckt.build()
    if solver == "jnp":
        tr = Transient(system, solver=solver, precision=precision)
    else:
        skey = (_structure_key(system), solver, precision)
        tr = _SHARED_TR.get(skey)
        if tr is None:
            tr = _SHARED_TR[skey] = Transient(system, solver=solver,
                                              precision=precision)
    out = (system, tr, res_stamps, cap_stamps, src_G, meta)
    while len(_PIPE_CACHE) >= _PIPE_CACHE_MAX:   # bound pinned programs
        del _PIPE_CACHE[next(iter(_PIPE_CACHE))]
    _PIPE_CACHE[key] = out + (bank0.cfg.tech,)
    return out


def _characterize_group(cfgs: List[BankConfig], *, n_seg: int,
                        n_steps: int, solver: str,
                        precision: str = "f64",
                        parasitics: str = "modeled"
                        ) -> Optional[List[TransientChar]]:
    """One topology group, None where its cell has no single-ended read
    column. Spans: host prep, dispatch of the device work, the host
    blocked on its first result, result assembly."""
    with trace.span("char_batch.prep"):
        banks = [build_bank(c) for c in cfgs]
        if not banks[0].is_gc:
            return None
        bank0 = banks[0]
        tech = cfgs[0].tech
        cell = bank0.cell
        key = topology_key(cfgs[0]) + (n_seg, n_steps, solver, precision)
        system, tr, res_stamps, cap_stamps, src_G, meta = _pipeline(bank0, key)

        # parasitics="extracted" (the layout tier): ONE batched extraction
        # over the group replaces the hand-modeled bitline ladder totals.
        # Via R/C folds uniformly into the n_seg segments, so the element
        # structure — and with it the compiled pipeline — is unchanged.
        ext = None
        if parasitics == "extracted":
            from repro.geom import extract as geom_extract
            ext = geom_extract.extract_lattice(banks)

        # -- lift structural values into per-point parameter arrays. The
        # per-point netlist builder is the single source of truth for element
        # VALUES (ladder R/C, device caps, SA load); structure is asserted
        # identical to the template.
        g_vals = np.zeros((len(banks), len(res_stamps)))
        c_vals = np.zeros((len(banks), len(cap_stamps)))
        t_an = np.zeros((len(banks),))
        for p, bank in enumerate(banks):
            rc_p = (float(ext["bl_r_ohm"][p]), float(ext["bl_c_f"][p])) \
                if ext is not None else None
            with on_host():
                ckt_p, _ = timing_mod.read_netlist(bank, n_seg=n_seg, rc=rc_p)
                t_an[p] = timing_mod.cell_read_time(bank, rc=rc_p)[0]
            assert len(ckt_p.names) == len(system.names) and \
                len(ckt_p.res) == len(res_stamps) and \
                len(ckt_p.caps) == len(cap_stamps), "topology group mismatch"
            g_vals[p] = [g for _, _, g in ckt_p.res]
            c_vals[p] = [c for _, _, c in ckt_p.caps]

        # float64 assembly, float64 all the way down (the group runs under
        # enable_x64 — see characterize; no f32 cast happens or should)
        G_b = src_G[None] + np.einsum("br,rij->bij", g_vals, res_stamps)
        C_b = np.einsum("bc,cij->bij", c_vals, cap_stamps)

        # -- per-point stop time + waves, from the SAME stimulus recipe as
        # the scalar simulate_read (timing.read_stimulus), edge-padded to the
        # longest waveform exactly like Transient.pack_waves
        t_end = np.maximum(timing_mod.T_END_OVER_ANALYTIC * t_an,
                           timing_mod.T_END_MIN_S)
        t0 = timing_mod.T0_FRACTION * t_end
        B = len(banks)
        wt = wv = None
        v_pre = 0.0
        for p in range(B):
            with on_host():
                waves_p, v_pre = timing_mod.read_stimulus(cell, tech,
                                                          meta["v_sn"], t0[p])
            if wt is None:   # buffer dims derived from the stimulus itself
                k = max(len(t) for t, _ in waves_p)
                wt = np.zeros((B, len(waves_p), k))
                wv = np.zeros((B, len(waves_p), k))
            for w, (t, v) in enumerate(waves_p):
                wt[p, w] = t + [t[-1]] * (k - len(t))
                wv[p, w] = v + [v[-1]] * (k - len(v))

        # pad the batch to a power-of-two bucket (edge-repeat) so the jitted
        # lattice program is reused across characterizations of different
        # sizes — vmap shapes are static, and session sweeps routinely hand
        # this pipeline varying-size "missing" subsets
        Bp = pow2_bucket(B)
        if Bp > B:
            pad = lambda a: pad_bucket(a, Bp)
            G_b, C_b, wt, wv = map(pad, (G_b, C_b, wt, np.asarray(wv)))
            t_end_p = pad(t_end)
        else:
            t_end_p = t_end

        over = {"G": G_b, "C": C_b}
        if solver != "jnp":
            over.update(_device_batches(system, Bp))

    trace.count("char_batch.points", B)
    trace.count("char_batch.lanes", Bp)

    with trace.span("char_batch.dispatch"):
        res = tr.run_lattice(wt, wv, t_end_p, n_steps, over_batches=over,
                             v0=jnp.full((system.n,), v_pre))
        swing = tech.v_sense_se
        target = v_pre + (swing if cell.predischarge else -swing)
        tc, valid = crossing_time(res["t"], res["rbl_near"], target,
                                  rising=cell.predischarge)
    with trace.span("char_batch.wait"):
        tc = np.asarray(tc)[:B]
        valid = np.asarray(valid)[:B]
    with trace.span("char_batch.finish"):
        t_cell = np.where(valid, tc - t0, np.inf)
        out = []
        for p, cfg in enumerate(cfgs):
            sim = float(t_cell[p])
            dev = abs(t_an[p] - sim) / sim if np.isfinite(sim) and sim > 0 \
                else float("inf")
            out.append(TransientChar(cfg, sim, float(t_an[p]), float(dev),
                                     bool(valid[p]), float(t_end[p]),
                                     n_steps))
    return out


def t_cell_grad_fn(cfg: BankConfig, *, n_seg: int = 8, n_steps: int = 300,
                   solver: str = "pallas", precision: str = "f64"):
    """Differentiable transient read characterization of ONE topology.

    Returns `fn(knobs) -> (t_cell_s (B,), valid (B,))` where `knobs` maps
    any subset of the continuous design knobs to (B,) arrays:

      vdd_scale     array operating voltage multiplier (techfile
                    `with_vdd_scale` semantics: rails, written SN level
                    and stimulus levels scale; sense swing does not)
      w_read_scale  read-device width multiplier (device current + its
                    gate/junction caps + the bitline junction load)
      bl_wire_scale bitline wire WIDTH multiplier (ladder conductance
                    scales up, wire capacitance scales up)

    The returned fn is traced end-to-end: every knob flows through the
    MNA assembly, the stimulus waves and the implicit-function VJP of the
    fused Newton solve (kernels.batched_solve), so `jax.grad` of any
    reduction of t_cell_s is ONE extra adjoint solve per timestep — not a
    differentiated unroll. Discretization constants (t0, t_end, step
    count) are pinned at the NOMINAL design point: they are solver
    settings, not physics, and freezing them keeps the objective smooth.

    Call under `jax.enable_x64` (gradients of interpolated
    crossings through a cond(J)~1e6 system need f64). Gain cells only;
    solver must be "pallas" or "sparse" (the dense "jnp" path takes no
    device-parameter overrides).
    """
    if solver not in ("pallas", "sparse"):
        raise ValueError(f"solver {solver!r} not differentiable here "
                         "(use 'pallas' or 'sparse')")
    bank0 = build_bank(cfg)
    if not bank0.is_gc:
        raise ValueError(f"cell {cfg.cell!r} has no single-ended read "
                         "column to characterize")
    tech = cfg.tech
    cell = bank0.cell
    key = topology_key(cfg) + (n_seg, n_steps, solver, precision)
    system, tr, res_stamps, cap_stamps, src_G, meta = _pipeline(bank0, key)

    # -- nominal element values + cap-class decomposition. read_netlist
    # appends, in order: 4 precharge-device caps (fixed w=1.2), n_seg
    # ladder caps (c_bl/n_seg each), the SA input cap, 4 read-device caps
    # (each proportional to w_read). Assert that layout before relying
    # on it.
    with on_host():
        ckt0, _ = timing_mod.read_netlist(bank0, n_seg=n_seg)
        t_an0 = timing_mod.cell_read_time(bank0)[0]
    g0 = np.array([g for _, _, g in ckt0.res])          # conductances
    c0 = np.array([c for _, _, c in ckt0.caps])
    assert len(g0) == n_seg and len(c0) == n_seg + 9, \
        "read_netlist element layout changed; update t_cell_grad_fn"
    from repro.core import bank as bank_mod
    r_bl0, c_bl0 = bank_mod.bitline_rc(bank0)
    rf = cell.rf(tech)
    c_junc0 = bank0.rows * rf.cj_f_per_um * cell.w_read  # scales w_read
    c_wire0 = c_bl0 - c_junc0                            # scales bl width
    np.testing.assert_allclose(g0, n_seg / r_bl0, rtol=1e-9)
    np.testing.assert_allclose(c0[4:4 + n_seg], c_bl0 / n_seg, rtol=1e-9)

    d_rd = next(i for i, d in enumerate(ckt0.devs) if d["name"] == "read_dev")
    w0 = np.array([d["w"] for d in ckt0.devs])
    n_dev = len(w0)

    # -- static discretization (from the nominal analytic estimate)
    t_end = max(timing_mod.T_END_OVER_ANALYTIC * t_an0,
                timing_mod.T_END_MIN_S)
    t0 = timing_mod.T0_FRACTION * t_end
    # wave TIME grids are static (the stimulus recipe of read_stimulus,
    # edge-padded to 3 knots); LEVELS are rebuilt traced per point below
    wt1 = np.array([[0.0, t0, t0 * 1.2],
                    [0.0, t0 * 0.8, t0],
                    [0.0, 1.0, 1.0],
                    [0.0, 1.0, 1.0]])
    bit = 0 if cell.read_on_sn_low else 1
    swing = tech.v_sense_se
    n = system.n

    from repro.core import cells as cells_mod

    def fn(knobs):
        some = next(iter(knobs.values()))
        B = some.shape[0]
        one = jnp.ones((B,), dtype=some.dtype)
        s_v = jnp.asarray(knobs.get("vdd_scale", one))
        s_w = jnp.asarray(knobs.get("w_read_scale", one))
        s_bl = jnp.asarray(knobs.get("bl_wire_scale", one))

        # linear elements: ladder conductance ~ wire width; ladder cap =
        # wire part ~ width + junction part ~ w_read; device caps of the
        # read transistor ~ w_read; precharge-device + SA caps fixed
        g_vals = g0[None, :] * s_bl[:, None]
        c_lad = (c_wire0 * s_bl + c_junc0 * s_w)[:, None] / n_seg
        c_vals = jnp.concatenate([
            jnp.broadcast_to(c0[:4], (B, 4)),
            jnp.broadcast_to(c_lad, (B, n_seg)),
            jnp.broadcast_to(c0[4 + n_seg], (B, 1)),
            c0[None, 4 + n_seg + 1:] * s_w[:, None],
        ], axis=1)
        G_b = jnp.asarray(src_G)[None] + jnp.einsum(
            "br,rij->bij", g_vals, jnp.asarray(res_stamps))
        C_b = jnp.einsum("bc,cij->bij", c_vals, jnp.asarray(cap_stamps))

        # stimulus levels, traced (same recipe as timing.read_stimulus)
        vdd = tech.vdd * s_v
        zero = jnp.zeros_like(vdd)
        v_sn = cells_mod.v_sn_written_t(cell, tech, bit, vdd,
                                        wwlls=cfg.wwlls,
                                        wwl_boost=cfg.wwl_boost)
        rwl_idle = zero if cell.rwl_active_high else vdd
        rwl_act = vdd if cell.rwl_active_high else zero
        v_pre = zero if cell.predischarge else vdd
        en_idle = vdd if cell.predischarge else zero
        en_off = zero if cell.predischarge else vdd
        wv = jnp.stack([
            jnp.stack([rwl_idle, rwl_idle, rwl_act], axis=1),
            jnp.stack([en_idle, en_idle, en_off], axis=1),
            jnp.stack([v_sn, v_sn, v_sn], axis=1),
            jnp.stack([vdd, vdd, vdd], axis=1),
        ], axis=1)
        wt = jnp.broadcast_to(wt1[None], (B, 4, 3))

        w_b = jnp.broadcast_to(w0, (B, n_dev)).at[:, d_rd].set(
            w0[d_rd] * s_w)
        v0 = jnp.broadcast_to(v_pre[:, None], (B, n))
        res = tr.run_lattice(wt, wv, jnp.full((B,), t_end), n_steps,
                             over_batches={"G": G_b, "C": C_b,
                                           **_device_batches(system, B,
                                                             w=w_b)},
                             v0=v0)
        # per-point sense target via trace shift (crossing_time takes a
        # scalar target)
        target = v_pre + (swing if cell.predischarge else -swing)
        tc, valid = crossing_time(res["t"], res["rbl_near"] - target[:, None],
                                  0.0, rising=cell.predischarge)
        return tc - t0, valid

    return fn


def lattice_program(cfg: BankConfig, B: int, *, n_seg: int = 8,
                    n_steps: int = 300, solver: str = "pallas",
                    precision: str = "f64", sharding=None):
    """The lowered program `characterize` runs for `cfg`'s topology group
    at a lattice bucket of B points, built from shapes alone — on
    `sharding`'s device when given (a described chip need not be
    attached), else on the default device. `.compile().as_text()` shows
    which engine the compiler got: `tpu_custom_call` marks the Pallas
    kernel. Fused solvers only."""
    if solver == "jnp":
        raise ValueError("the dense 'jnp' reference has no fused lattice "
                         "program")
    import jax
    with enable_x64():
        bank0 = build_bank(cfg)
        key = topology_key(cfg) + (n_seg, n_steps, solver, precision)
        system, tr, *_, meta = _pipeline(bank0, key)
        waves, _ = timing_mod.read_stimulus(bank0.cell, cfg.tech,
                                            meta["v_sn"], 0.0)
        n_w, k = len(waves), max(len(t) for t, _ in waves)
        n, n_dev = system.n, int(np.shape(system.dev["pol"])[-1])

        def S(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float64,
                                        sharding=sharding)

        dev_keys = tuple(sorted(_DEV_KEYS))
        return tr._fused_fn(n_steps, dev_keys).lower(
            S(B), S(B, n_w, k), S(B, n_w, k), S(n), S(B, n, n), S(B, n, n),
            tuple(S(B, n_dev) for _ in dev_keys))


def characterize(cfgs: Sequence[BankConfig], *, n_steps: int = 300,
                 solver: str = "pallas", n_seg: int = 8,
                 precision: str = "f64", parasitics: str = "modeled"
                 ) -> List[Optional[TransientChar]]:
    """Batched transient read characterization of a config lattice.

    Returns one TransientChar per config, in input order; non-gain-cell
    configs (no single-ended read column to simulate) get None. Matches
    the scalar `timing.simulate_read` per point — same netlist builder,
    same integrator, same interpolated crossing extraction — but runs one
    compiled program per cell topology instead of one per point.

    parasitics="extracted" (fidelity="layout") swaps the hand-modeled
    read-bitline ladder for the batched layout extraction
    (`repro.geom.extract.extract_lattice`) — one struct-of-arrays
    extraction per topology group, same compiled transient pipeline.
    """
    if parasitics not in ("modeled", "extracted"):
        raise ValueError(f"parasitics must be 'modeled' or 'extracted', "
                         f"got {parasitics!r}")
    cfgs = list(cfgs)
    out: List[Optional[TransientChar]] = [None] * len(cfgs)
    # float64 throughout (see timing.simulate_read: cond(J) ~ 1e6 makes
    # f32 Newton noise dominate the traces). solver="pallas" (default) is
    # the fused sparse-Newton engine — f64 or mixed-precision per the
    # `precision` knob; "jnp" stays the dense accuracy anchor and
    # precision="f32" is screening-only.
    with enable_x64():
        for idx in group_by_topology(cfgs).values():
            group = [cfgs[i] for i in idx]
            with trace.span("char_batch.group"):
                chars = _characterize_group(group, n_seg=n_seg,
                                            n_steps=n_steps, solver=solver,
                                            precision=precision,
                                            parasitics=parasitics)
            for i, ch in zip(idx, chars or ()):
                out[i] = ch
    return out
