"""Retention (paper Fig 8): SN decay through write-device subthreshold +
read-gate leakage, until the read margin is lost.

Two paths, cross-validated in tests:
  * closed-form-ish ODE integration in jnp (fast, differentiable — feeds
    the DSE gradient co-optimizer);
  * the transient engine on the retention netlist (the "HSPICE" path).

Retention is defined as t(V_SN crosses V_margin) for the worst-case
state — the decaying '1' for NMOS-read cells (paper: "primarily
constrained by the decay of state 1"), the rising '0' for PMOS-read.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cells import Bitcell
from repro.core.spice.mna import channel_current_raw
from repro.core.techfile import TechFile, with_vdd_scale


@dataclass
class Retention:
    """Retention analysis result. Units: `t_ret_s` seconds, voltages in
    volts, `i_leak0_a` (the SN leak at the freshly-written level) in
    amperes."""
    t_ret_s: float
    v_sn0: float
    v_margin: float
    i_leak0_a: float

    def as_dict(self):
        return self.__dict__.copy()


def _margin_voltage(cell: Bitcell, tech: TechFile) -> float:
    """SN level at which the '1' state is lost (paper: retention is
    "primarily constrained by the decay of state 1"):
      NMOS read — below VT_read + 0.15 V the cell can no longer meet the
      sense swing;
      PMOS read — below VDD - |VT_read| - 0.15 V the read device starts
      conducting and a stored '1' mis-reads as '0'."""
    rf = cell.rf(tech)
    if cell.read_on_sn_low:
        return tech.vdd - rf.vt0 - 0.15
    return rf.vt0 + 0.15


def leak_fn(cell: Bitcell, tech: TechFile):
    """Returns i_leak(v_sn) (A, discharging positive) as a jnp function of
    the raw write-device params — differentiable for DSE."""
    wf, rf = cell.wf(tech), cell.rf(tech)

    def fn(v_sn, vt0=wf.vt0, w=cell.w_write):
        return _sn_leak(jnp.float32(wf.polarity), vt0, wf.n_slope,
                        wf.k_prime, wf.lambda_, w, cell.l_write,
                        jnp.float32(_v_off(wf, tech)),
                        rf.i_gate_a_per_um * cell.w_read, v_sn)

    return fn


def _v_off(wf, tech: TechFile) -> float:
    """Gate voltage that holds the write device off: 0 for n-type, vdd
    for p-type."""
    return 0.0 if wf.polarity > 0 else tech.vdd


def _sn_leak(pol, vt0, n, kp, lam, w, l, v_off, g_read, v_sn):
    """SN leak (A, discharging positive): the off write device (gate at
    `v_off`, WBL at 0) plus the read gate, `g_read` amperes per volt at
    1.1 V. Elementwise over broadcastable operands."""
    i_w = channel_current_raw(pol, vt0, n, kp, lam, w, l, v_off, v_sn,
                              jnp.float32(0.0))
    return jnp.abs(i_w) + g_read * v_sn / 1.1


def analyze(cell: Bitcell, tech: TechFile, *, wwlls=False, wwl_boost=0.55,
            n_steps=4000, vdd_scale: float = 1.0) -> Retention:
    """Log-time ODE integration of dV/dt = -I(V)/C_SN (decaying '1').

    `vdd_scale` evaluates the cell at a scaled operating voltage (the
    paper's on-the-fly retention knob): the written SN level, the margin
    and the write-device leak all follow the scaled rail."""
    tech = with_vdd_scale(tech, vdd_scale)
    c_sn = cell.sn_cap(tech)
    v0 = cell.v_sn_written(tech, 1, wwlls=wwlls, wwl_boost=wwl_boost)
    v_m = _margin_voltage(cell, tech)
    fn = leak_fn(cell, tech)
    t = _cross_time(fn, c_sn, v0, v_m, n_steps)
    return Retention(float(t), v0, v_m, float(fn(jnp.float32(v0))))


def _cross_time(i_of_v, c_sn, v0, v_margin, n_steps):
    """t = C * integral_{v_m}^{v0} dV / I(V)  (exact for dV/dt=-I/C)."""
    if v0 <= v_margin:
        return 0.0
    vs = jnp.linspace(v_margin, v0, n_steps)
    inv_i = 1.0 / jnp.maximum(jax.vmap(i_of_v)(vs), 1e-30)
    return float(c_sn * jnp.trapezoid(inv_i, vs))


def integral_row(cell: Bitcell, tech: TechFile, *, wwlls=False,
                 wwl_boost=0.55, vdd_scale: float = 1.0) -> tuple:
    """One row of `t_ret_rows`: the operands `analyze` integrates for the
    same arguments, as Python floats (write-device polarity, vt0, n_slope,
    k_prime, lambda, width, length; its off-gate voltage; the read-gate
    leak coefficient `i_gate_a_per_um * w_read`; c_sn, v0, v_margin)."""
    tech = with_vdd_scale(tech, vdd_scale)
    wf, rf = cell.wf(tech), cell.rf(tech)
    return (float(wf.polarity), wf.vt0, wf.n_slope, wf.k_prime, wf.lambda_,
            cell.w_write, cell.l_write, _v_off(wf, tech),
            rf.i_gate_a_per_um * cell.w_read, cell.sn_cap(tech),
            cell.v_sn_written(tech, 1, wwlls=wwlls, wwl_boost=wwl_boost),
            _margin_voltage(cell, tech))


def t_ret_rows(rows, n_steps=4000) -> np.ndarray:
    """`analyze(...).t_ret_s` of every row of `rows` ((R, 12) float64,
    rows of `integral_row`) in one pass of the same eager f32 ops over an
    (R, n_steps) grid, bit for bit. Each row's grid is its own eager
    `jnp.linspace`, as in `analyze` (a broadcast one rounds differently);
    the float64 columns round to f32 once, as `analyze`'s Python floats
    do. Do not jit this: XLA's fusion rounds the integrand differently.
    Rows with v0 <= v_margin are computed like the others and read 0.0,
    so the shape is R whatever the rows hold."""
    rows = np.asarray(rows, np.float64)
    (pol, vt0, n, kp, lam, w, l, v_off, g_read, c_sn, v0,
     v_m) = (rows[:, k:k + 1] for k in range(12))
    vs = jnp.stack([jnp.linspace(a, b, n_steps)
                    for a, b in zip(v_m[:, 0].tolist(), v0[:, 0].tolist())])
    inv_i = 1.0 / jnp.maximum(
        _sn_leak(pol, vt0, n, kp, lam, w, l, v_off, g_read, vs), 1e-30)
    t = np.asarray(c_sn[:, 0] * jnp.trapezoid(inv_i, vs), np.float64)
    return np.where(v0[:, 0] > v_m[:, 0], t, 0.0)


def retention_vs_vt(cell: Bitcell, tech: TechFile, vt_values, *,
                    wwlls=False) -> np.ndarray:
    """Fig 8(c): differentiable retention as a function of write-VT."""
    c_sn = cell.sn_cap(tech)
    v_m = _margin_voltage(cell, tech)
    wf = cell.wf(tech)

    def one(vt0):
        v0 = jnp.minimum(
            tech.vdd,
            tech.vdd + (0.55 if wwlls else 0.0) - vt0 + 0.12) \
            - cell.wwl_couple_ratio * tech.vdd
        fn = leak_fn(cell, tech)
        vs = jnp.linspace(v_m, jnp.maximum(v0, v_m + 1e-3), 2000)
        inv_i = 1.0 / jnp.maximum(jax.vmap(lambda v: fn(v, vt0=vt0))(vs), 1e-30)
        return c_sn * jnp.trapezoid(inv_i, vs)

    return np.asarray(jax.vmap(one)(jnp.asarray(vt_values, jnp.float32)))


def sn_decay_trace(cell: Bitcell, tech: TechFile, t_end, n=400, *,
                   wwlls=False):
    """Fig 8(b)/(e): V_SN(t) by direct integration (log-spaced)."""
    c_sn = cell.sn_cap(tech)
    v0 = cell.v_sn_written(tech, 1, wwlls=wwlls)
    fn = leak_fn(cell, tech)
    ts = jnp.concatenate([jnp.zeros((1,)),
                          jnp.logspace(math.log10(t_end) - 6,
                                       math.log10(t_end), n - 1)])

    def body(v, dt):
        v = jnp.maximum(v - fn(v) / c_sn * dt, 0.0)
        return v, v

    dts = jnp.diff(ts)
    _, vs = jax.lax.scan(body, jnp.float32(v0), dts)
    return np.asarray(ts[1:]), np.asarray(vs)
