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

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench.reference.cells import Bitcell
from bench.reference.mna import channel_current_raw
from bench.reference.techfile import TechFile, with_vdd_scale


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
        # write device off: gate at 0 (NMOS) with WBL at 0 -> discharges SN
        i_w = channel_current_raw(
            jnp.float32(wf.polarity), vt0, wf.n_slope, wf.k_prime,
            wf.lambda_, w, cell.l_write,
            jnp.float32(0.0 if wf.polarity > 0 else tech.vdd),
            v_sn, jnp.float32(0.0))
        i_g = rf.i_gate_a_per_um * cell.w_read * v_sn / 1.1
        return jnp.abs(i_w) + i_g

    return fn


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
