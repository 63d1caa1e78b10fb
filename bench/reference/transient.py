"""Dense backward-Euler + Newton transient: the plain reference engine.

Frozen copy of the program's dense `jnp` stepper (analytic Jacobian
stamps, `jnp.linalg.solve`, tolerance early exit) and its interpolated
threshold-crossing extraction. The benchmark integrates every sampled
design point with it in float64 on the CPU device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.mna import MNASystem

NEWTON_ITERS = 6
NEWTON_TOL = 1e-6       # volts; max|dv| under this ends the Newton loop


def wave_value(times, values, t):
    """Piecewise-linear waveform lookup. times/values: (k,)."""
    return jnp.interp(t, times, values)


def crossing_time(t, v, target, rising: bool):
    """First threshold crossing of a trace, linearly interpolated between
    the bracketing time steps. t, v: (..., T). Returns (t_cross, valid):
    t_cross is +inf where the trace never reaches the target (the final
    sample must be past the target and the crossing not at step 0)."""
    t = jnp.asarray(t)
    v = jnp.asarray(v)
    mask = (v >= target) if rising else (v <= target)
    ok = mask[..., -1]
    hit = jnp.argmax(mask, axis=-1)
    pos = jnp.maximum(hit, 1)[..., None]
    v1 = jnp.take_along_axis(v, pos, axis=-1)[..., 0]
    v0 = jnp.take_along_axis(v, pos - 1, axis=-1)[..., 0]
    t1 = jnp.take_along_axis(jnp.broadcast_to(t, v.shape), pos,
                             axis=-1)[..., 0]
    t0 = jnp.take_along_axis(jnp.broadcast_to(t, v.shape), pos - 1,
                             axis=-1)[..., 0]
    dv = v1 - v0
    frac = jnp.clip((target - v0) / jnp.where(dv == 0.0, 1.0, dv), 0.0, 1.0)
    valid = ok & (hit > 0)
    return jnp.where(valid, t0 + frac * (t1 - t0), jnp.inf), valid


def make_stepper(system: MNASystem, iters: int = NEWTON_ITERS,
                 tol: float = NEWTON_TOL):
    """step(v, t, h, wave_t, wave_v, over) -> v_next, where `over` holds
    per-point "G"/"C" matrices and device parameter arrays: analytic-
    Jacobian Newton, re-stamped and solved densely every iteration, until
    max|dv| < tol or `iters` iterations."""

    def step(v, t, h, wave_times, wave_values, over):
        sys = system.with_params(**over)
        wv = jax.vmap(lambda tt, vv: wave_value(tt, vv, t))(wave_times,
                                                            wave_values)

        def res(vv):
            return sys.residual(vv, v, h, wv)

        def cond(state):
            _, done, i = state
            return (i < iters) & jnp.logical_not(done)

        def body(state):
            vv, _, i = state
            dv = jnp.linalg.solve(sys.jacobian(vv, h), res(vv))
            done = jnp.max(jnp.abs(dv)) < tol
            return vv - dv, done, i + 1

        v2, _, _ = jax.lax.while_loop(
            cond, body, (v, jnp.asarray(False), jnp.asarray(0)))
        return v2

    return step
