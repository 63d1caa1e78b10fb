"""Jit'd public wrappers for the batched MNA solvers.

Dense Gauss-Jordan (`solve`/`batched_solve`): the PR 2 kernel, f32
per-iteration dense solves for screening sweeps. On CPU the Pallas
kernel runs in interpret mode; on TPU it compiles natively. `solve1`
adapts it to the single-system signature the Newton stepper uses —
under vmap the batch dimension folds back into the kernel's grid via
jax's batching rule for pallas_call.

Fused Newton (`fused_newton_step`): the sparse-Newton engine's
whole-timestep solve (newton.py / fused.py). Engine choice follows the
platform the program is LOWERED for (`jax.lax.platform_dependent`), not
the process's default backend: an f32 spec runs the native Pallas
kernel on TPU; f64 and mixed specs run the XLA while_loop there
(Mosaic has no 64-bit types); every other platform runs the
identical-result XLA while_loop (interpret-mode Pallas is an emulation
— orders of magnitude slower than compiled XLA, so it is reserved for
the parity tests). `fused_engine` names the choice.

The dispatching solve is wrapped in a `jax.custom_vjp`: neither the
while_loop fallback nor the Pallas kernel is reverse-differentiable,
but the converged root is an implicit function of the data inputs, so
the backward pass is ONE extra Woodbury solve with the transposed
capacitance matrix (`newton.fixed_point_adjoint`) instead of a
differentiated unroll. This is what lets energy/delay gradients flow
through whole transient characterizations (core/dse_grad.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.batched_solve import newton as _newton
from repro.kernels.batched_solve.fused import fused_newton as _fused_kernel
from repro.kernels.batched_solve.kernel import batched_solve as _kernel
from repro.kernels.batched_solve.ref import batched_solve_ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_engine(spec, platform: str) -> str:
    """The engine `fused_newton_step` runs for `spec` on `platform`:
    "pallas" (the Mosaic kernel — TPU, f32 compute) or "xla_while"."""
    kernel_ok = spec.dtypes[1] == jnp.float32 and spec.n_dev > 0
    return "pallas" if platform == "tpu" and kernel_ok else "xla_while"


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_solve(spec, iters, tol, pre, Krhs, params, v0):
    """Differentiable fused Newton solve (platform-dispatching primal).
    spec/iters/tol are static (FusedSpec hashes by identity)."""
    def xla(pre, Krhs, params, v0):
        return _newton.newton_solve(spec, pre, Krhs, params, v0,
                                    iters, tol)[0]

    if fused_engine(spec, "tpu") != "pallas":
        return xla(pre, Krhs, params, v0)

    def tpu(pre, Krhs, params, v0):
        return _fused_kernel(spec, pre, Krhs, params, v0,
                             iters=iters, tol=tol)

    return jax.lax.platform_dependent(pre, Krhs, params, v0,
                                      tpu=tpu, default=xla)


def _fused_solve_fwd(spec, iters, tol, pre, Krhs, params, v0):
    v = _fused_solve(spec, iters, tol, pre, Krhs, params, v0)
    return v, (pre, Krhs, params, v)


def _fused_solve_bwd(spec, iters, tol, res, v_bar):
    pre, Krhs, params, v_star = res
    pre_bar, krhs_bar, params_bar = _newton.fixed_point_adjoint(
        spec, pre, Krhs, params, v_star, v_bar)
    return pre_bar, krhs_bar, params_bar, jnp.zeros_like(v_star)


_fused_solve.defvjp(_fused_solve_fwd, _fused_solve_bwd)


def fused_newton_step(spec, pre, Krhs, params, v0, *, iters, tol):
    """One timestep's fused Newton solve -> v (B, n), through the engine
    `fused_engine` names for the platform the program is lowered for.
    The result carries the implicit-function VJP, so whole
    characterizations built on this step are reverse-differentiable."""
    return _fused_solve(spec, iters, tol, pre, Krhs, params, v0)


def batched_solve(J, r, block_b: int = 8):
    return _kernel(J, r, block_b=block_b, interpret=_interpret())


def solve1(J, r):
    """Single system (N, N) @ x = (N,)."""
    return batched_solve(J[None], r[None], block_b=1)[0]


def solve(J, r, block_b: int = 8):
    """Shape-dispatching entry: (N, N) or (B, N, N) systems. NOTE the
    kernel computes in float32 regardless of input dtype — fine for DSE
    screening sweeps, but the float64 characterization anchor
    (repro.core.spice.char_batch) should use the "jnp" solver."""
    if J.ndim == 2:
        return solve1(J, r)
    return batched_solve(J, r, block_b=block_b)
