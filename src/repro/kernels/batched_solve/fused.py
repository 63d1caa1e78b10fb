"""Pallas kernel: the WHOLE Newton solve of one backward-Euler timestep
fused into a single kernel over the lattice batch axis.

The lattice lies on the vreg: lane arrays are (rows, 128) tiles, so one
program handles up to `block_rows` x 128 lattice points and every
operation of the iteration is one elementwise vector op over all of
them. The program runs the complete fixed-length Newton loop in
registers/VMEM: pick the device terminal voltages, evaluate the channel
model once for current AND 3x3 stamp partials
(`channel_current_and_grads`), assemble the rank-2-per-device Woodbury
capacitance matrix, solve the (3 n_dev)^2 system in closed form, apply
the masked update — no HBM round-trip between Newton iterations, no
(B, n, n) operand anywhere (the constant part of the Jacobian enters
only through its prefactored inverse, see `newton.py`).

The kernel body calls the SAME traced lane-form iteration
(`make_fused_iter`) as the XLA while_loop; per-lane freeze makes
fixed-length fori_loop (here) and early-exit while_loop bit-identical,
so the CPU interpret-mode parity tests pin the kernel to the XLA path
exactly.

Dtype note: Mosaic, the TPU kernel compiler, has no 64-bit types, so
the kernel serves only specs whose compute dtype is f32
(precision="f32"). The "f64" and "mixed" specs both compute in f64 and
run the XLA while_loop on TPU as well (`ops._fused_solve` holds that
rule). On CPU (interpret mode) f64 runs fine, which is what the parity
suite exercises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.batched_solve.newton import (FusedSpec, make_fused_iter,
                                                pack_coefs)

LANES = 128


def _newton_kernel(coef_ref, krhs_ref, v_ref, vout_ref, *, spec: FusedSpec,
                   iters: int, tol: float):
    it = make_fused_iter(spec, tol)
    v0 = tuple(v_ref[i] for i in range(spec.n))

    # the freeze mask rides the loop as int32: Mosaic cannot carry a
    # boolean vector through scf.for
    def body(_, state):
        v, done = state
        v, done = it(coef_ref, krhs_ref, v, done != 0)
        return v, done.astype(jnp.int32)

    # int32 bounds: under enable_x64 python ints would make an i64 loop
    # counter, which Mosaic cannot lay out
    v, _ = jax.lax.fori_loop(jnp.int32(0), jnp.int32(iters), body,
                             (v0, jnp.zeros(v0[0].shape, jnp.int32)))
    for i in range(spec.n):
        vout_ref[i] = v[i]


@functools.partial(jax.jit, static_argnames=("spec", "iters", "tol",
                                             "block_rows", "interpret"))
def fused_newton(spec: FusedSpec, pre, Krhs, params, v0, *,
                 iters: int, tol: float, block_rows: int = 8,
                 interpret: bool = False):
    """One timestep's Newton solve through the Pallas kernel.

    pre: dict from `newton.precompute` (only KU/Sb/KPa/KPg enter the
    kernel; K/KCoh are per-step hoists handled by the caller).
    Krhs (B, n), params (B, N_PARAMS, n_dev), v0 (B, n) -> v (B, n).
    Lanes pad to whole (rows, 128) tiles, at most `block_rows` rows per
    grid step (edge lanes repeat lane 0, which is always a valid
    system)."""
    B, n = v0.shape
    _, cdt = spec.dtypes
    rows = -(-B // LANES)
    blk = min(rows, block_rows)
    rows = -(-rows // blk) * blk
    Bp = rows * LANES

    def tiles(x):
        """(r, B) coefficient-major rows -> (r, rows, 128) lane tiles."""
        if Bp > B:
            x = jnp.concatenate(
                [x, jnp.broadcast_to(x[:, :1], (x.shape[0], Bp - B))],
                axis=1)
        return x.reshape(x.shape[0], rows, LANES)

    coef = tiles(pack_coefs(spec, pre, params))
    operands = (coef, tiles(Krhs.astype(cdt).T), tiles(v0.T))

    def spec_of(x):
        # block indices derived from the int32 grid index: python zeros
        # would be i64 under enable_x64, and an index map may not capture
        # constants
        return pl.BlockSpec((x.shape[0], blk, LANES),
                            lambda i: (i * 0, i, i * 0))

    out = pl.pallas_call(
        functools.partial(_newton_kernel, spec=spec, iters=iters, tol=tol),
        grid=(rows // blk,),
        in_specs=[spec_of(x) for x in operands],
        out_specs=spec_of(operands[2]),
        out_shape=jax.ShapeDtypeStruct((n, rows, LANES), v0.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(n, Bp)[:, :B].T
