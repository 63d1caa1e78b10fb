"""EKV-style compact transistor model (JAX-differentiable).

    i = I_S * [ L2((Vgs_on - VT)/(2 n phi_t)) - L2((Vgs_on - VT - n Vds)/(2 n phi_t)) ]
        * (1 + lambda * Vds),       L2(x) = ln^2(1 + e^x)
    I_S = 2 n k' (W/L) phi_t^2

One smooth expression covers subthreshold (slope == the deck's SS:
n phi_t ln10) through strong inversion (square law /2n) and saturation —
exactly what the retention problem needs (the write transistor sits deep
in subthreshold while the SN discharges). Both polarities share the same
magnitude function: conventional current always flows high->low terminal;
NMOS gates on with vg above the LOW terminal, PMOS with vg below the HIGH
terminal. All functions are elementwise jnp, so circuits vmap over
design-point batches (the "HSPICE -> batched JAX" adaptation, DESIGN §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.techfile import PHI_T, DeviceFlavor


def _l2(x):
    return jax.nn.softplus(x) ** 2  # ln^2(1+e^x)


def _i_mag_per_um(fl: DeviceFlavor, vg, v_hi, v_lo, l_um):
    """|I| per um width for current flowing v_hi -> v_lo (>= 0)."""
    vds = v_hi - v_lo
    if fl.polarity > 0:
        vgs_on = vg - v_lo          # NMOS: source = low terminal
    else:
        vgs_on = v_hi - vg          # PMOS: source = high terminal
    n = fl.n_slope
    i_s = 2.0 * n * fl.k_prime * (1.0 / max(l_um, 1e-3)) * PHI_T ** 2
    a = (vgs_on - fl.vt0) / (2.0 * n * PHI_T)
    b = (vgs_on - fl.vt0 - n * vds) / (2.0 * n * PHI_T)
    return i_s * (_l2(a) - _l2(b)) * (1.0 + fl.lambda_ * vds)


def channel_current(fl: DeviceFlavor, w_um, l_um, vg, va, vb):
    """Signed conventional current a -> b through the channel (A)."""
    fwd = _i_mag_per_um(fl, vg, va, vb, l_um)
    rev = _i_mag_per_um(fl, vg, vb, va, l_um)
    return w_um * jnp.where(va >= vb, fwd, -rev)


def i_gate(fl: DeviceFlavor, w_um, vg, vch):
    """Gate leakage (A), linear-in-bias toy model (sign: gate -> channel)."""
    return fl.i_gate_a_per_um * w_um * (vg - vch) / 1.1


def i_off(fl: DeviceFlavor, w_um, l_um, vdd):
    """Off-state leakage magnitude at Vgs_on=0, |Vds|=vdd (A)."""
    if fl.polarity > 0:
        return float(w_um * _i_mag_per_um(fl, 0.0, vdd, 0.0, l_um))
    return float(w_um * _i_mag_per_um(fl, vdd, vdd, 0.0, l_um))
