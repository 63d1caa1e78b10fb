"""Fused low-rank Newton engine: the per-iteration body behind
`solver="pallas"`.

The sparse-LU engine (`sparse.py`) already beats B serial dense solves
by replaying a symbolic factorization over the batch axis — but it still
refactors the full pattern every Newton iteration. This module goes one
step further using two structural facts of the batched transient runs:

  1. the timestep h = t_end / n_steps is CONSTANT per lattice point, so
     the linear part of the Jacobian J0 = G + C/h + gmin is constant
     across the whole run and can be factored ONCE per characterization
     (we keep K = J0^-1 explicitly — one (B, n, n) inverse per run, never
     per step);
  2. the only entries that change between iterations are the per-device
     3x3 conductance stamps, i.e. J = J0 + Um @ D @ Vm with Um/Vm
     CONSTANT 0/1 incidence matrices of the device terminals and D the
     block-diagonal (3 n_dev x 3 n_dev) matrix of channel partials — a
     rank 3*n_dev update.

The Newton step then collapses via the Woodbury identity

    dv = J^-1 F = t - KU @ (I + D S)^-1 D (Vm @ t),
    t  = K F = v - K rhs + (K Pa) i_ab + (K Pg) i_g

where S = Vm K Um, KU = K Um, K Pa / K Pg (terminal incidence columns of
K) are all hoisted out of the iteration, and K rhs is hoisted out to
once per TIMESTEP (K C/h and the K @ source-injection sequence are
per-run precomputes). Note K J0 = I kills the residual matvec entirely:
the iteration touches no (B, n, n) operand at all — just the channel
model on (B, n_dev) and a (3 n_dev)^2 solve. This is an inexact-Newton
scheme in the round-off sense only: the fixed point satisfies F(v) = 0
exactly regardless of the error in K, so parity with the dense reference
holds to integration tolerance (asserted at 1e-6 on whole traces).

D itself is rank-2 per device: D_d = s_a (x) d3 + s_g (x) gg*e_g with
s_a = (1,-1,0), s_g = (-1/2,-1/2,1) over KCL rows (a,b,g), d3 the
channel partials and e_g = (1,-1/2,-1/2) the gate-leak row — so (I+DS)
assembles from two outer products per device, no 3x3 stamps are ever
materialized.

The iteration is written in LANE form: every per-point quantity is one
array over the lattice lanes, and the small matrix algebra (the (n, k)
Woodbury factors, the (k, k) capacitance matrix and its closed-form
solve) is unrolled over static indices into elementwise multiply-adds.
Nothing contracts a batched dimension, so the same body lowers through
XLA on any backend and through Mosaic (which refuses batched
contractions such as `bdj,bdjk->bdk`) inside the TPU kernel, where each
lane array is one (8, 128) vreg tile of 1024 lattice points. The
step-constant operands travel coefficient-major (`pack_coefs`): row c
holds one scalar coefficient for every lane.

The same traced body runs three ways: under `jax.lax.while_loop` with a
whole-batch early exit (the XLA path: CPU, and the f64/mixed specs on
TPU), under a fixed-length `fori_loop` inside the Pallas kernel
(`fused.py`, f32 specs on TPU), and in interpret mode for the CPU parity
tests. Per-lane freeze (`done` mask) makes all three bit-identical: a
converged lane stops changing, so an early-exited while_loop and a
run-to-the-cap fori_loop agree exactly.

Precision policy (docs/fidelity-tiers.md): `store_dtype` is the dtype of
the carried state/traces, `compute_dtype` the dtype of the model
evaluation and the Woodbury solve. "mixed" = f32 storage, f64 compute —
safe because Newton re-evaluates the residual from the stored state each
iteration; "f32" is screening-only (cond(J0) ~ 1e6 amplifies solve
round-off into the traces).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spice.mna import (G_MIN, channel_current_and_grads,
                                  channel_current_raw)
from repro.kernels.batched_solve.sparse import (PARAM_FIELDS, PRECISIONS,
                                                pack_params)

__all__ = ["FusedSpec", "build_fused_spec", "precompute", "pack_coefs",
           "make_fused_iter", "newton_solve", "newton_solve_fixed",
           "pack_params", "residual", "fixed_point_adjoint"]

#: KCL row signs of the channel current (rows a, b, g)
S_A = np.array([1.0, -1.0, 0.0])
#: KCL row signs of the gate-leak current
S_G = np.array([-0.5, -0.5, 1.0])
#: gate-leak voltage row: i_g = gg * (vg - (va+vb)/2), columns (g, a, b)
E_G = np.array([1.0, -0.5, -0.5])


@dataclass(frozen=True, eq=False)
class FusedSpec:
    """Static structure of one topology group for the fused engine:
    terminal incidence matrices and gather maps (host numpy — they bake
    into the jitted programs / Pallas kernel as constants). eq=False:
    identity hashing, so the spec can be a jit static argument (specs
    are built once per topology group and cached)."""
    n: int
    n_dev: int
    um: np.ndarray          # (n, k) KCL row incidence, cols per device (a,b,g)
    vm: np.ndarray          # (k, n) terminal voltage rows, per device (g,a,b)
    pa: np.ndarray          # (n, n_dev) channel-current KCL incidence
    pg: np.ndarray          # (n, n_dev) gate-leak KCL incidence
    g_safe: np.ndarray      # terminal gather indices, ground -> n (pad row)
    a_safe: np.ndarray
    b_safe: np.ndarray
    precision: str = "f64"

    @property
    def k(self) -> int:
        return 3 * self.n_dev

    @property
    def dtypes(self) -> tuple:
        return PRECISIONS[self.precision]


def build_fused_spec(system, precision: str = "f64") -> FusedSpec:
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r} "
                         f"({' | '.join(PRECISIONS)})")
    n = system.n
    didx_g = np.asarray(system.didx["g"])
    didx_a = np.asarray(system.didx["a"])
    didx_b = np.asarray(system.didx["b"])
    n_dev = len(didx_g)
    k = 3 * n_dev
    um = np.zeros((n, k))
    vm = np.zeros((k, n))
    pa = np.zeros((n, n_dev))
    pg = np.zeros((n, n_dev))
    for d in range(n_dev):
        a, b, g = int(didx_a[d]), int(didx_b[d]), int(didx_g[d])
        if a >= 0:
            pa[a, d] += 1.0
            pg[a, d] -= 0.5
        if b >= 0:
            pa[b, d] -= 1.0
            pg[b, d] -= 0.5
        if g >= 0:
            pg[g, d] += 1.0
        for j, node in enumerate((a, b, g)):    # Um columns: rows of D
            if node >= 0:
                um[node, 3 * d + j] = 1.0
        for j, node in enumerate((g, a, b)):    # Vm rows: cols of D
            if node >= 0:
                vm[3 * d + j, node] = 1.0
    return FusedSpec(
        n=n, n_dev=n_dev, um=um, vm=vm, pa=pa, pg=pg,
        g_safe=np.where(didx_g >= 0, didx_g, n),
        a_safe=np.where(didx_a >= 0, didx_a, n),
        b_safe=np.where(didx_b >= 0, didx_b, n),
        precision=precision)


def precompute(spec: FusedSpec, G_b, C_b, h):
    """Per-run constants of the Woodbury iteration. G_b/C_b (B, n, n)
    dense linear stamps (built once per lattice), h (B,) per-point step.

    Returns a dict pytree: K (B,n,n) inverse of the constant Jacobian
    part, KU (B,n,k), Sb (B,n_dev,3,k) = Vm K Um in device blocks,
    KPa/KPg (B,n,n_dev) = K @ terminal incidence, KCoh (B,n,n) = K C / h
    (for the per-step rhs hoist K rhs = KCoh @ v_prev + K src)."""
    _, cdt = spec.dtypes
    n = spec.n
    G_b = jnp.asarray(G_b, cdt)
    C_b = jnp.asarray(C_b, cdt)
    h = jnp.asarray(h, cdt)
    J0 = G_b + C_b / h[:, None, None] + G_MIN * jnp.eye(n, dtype=cdt)
    K = _inv_dominant(J0)
    KU = jnp.einsum("bij,jk->bik", K, jnp.asarray(spec.um, cdt))
    Sb = jnp.einsum("ki,bij->bkj", jnp.asarray(spec.vm, cdt), KU)
    if spec.n_dev:
        Sb = Sb.reshape(-1, spec.n_dev, 3, spec.k)
    return {
        "K": K,
        "KU": KU,
        "Sb": Sb,
        "KPa": jnp.einsum("bij,jd->bid", K, jnp.asarray(spec.pa, cdt)),
        "KPg": jnp.einsum("bij,jd->bid", K, jnp.asarray(spec.pg, cdt)),
        "KCoh": jnp.einsum("bij,bjk->bik", K, C_b) / h[:, None, None],
    }


def _inv_dominant(J):
    """Inverse of a (B, n, n) batch of strictly diagonally dominant
    matrices by unpivoted Gauss-Jordan, unrolled over the static n.
    J0 = G + C/h + gmin is dominant (nodal Laplacians plus positive
    diagonal stamps), so no pivoting is needed — and none of the
    elementwise steps needs an LU custom call, which XLA:TPU offers in
    f32 only."""
    n = J.shape[-1]
    A = J
    X = jnp.broadcast_to(jnp.eye(n, dtype=J.dtype), J.shape)
    for p in range(n):
        inv_p = 1.0 / A[:, p, p:p + 1]
        row_a = A[:, p, :] * inv_p
        row_x = X[:, p, :] * inv_p
        f = A[:, :, p].at[:, p].set(0.0)[:, :, None]
        A = (A - f * row_a[:, None, :]).at[:, p, :].set(row_a)
        X = (X - f * row_x[:, None, :]).at[:, p, :].set(row_x)
    return X


def pack_coefs(spec: FusedSpec, pre, params):
    """Step-constant operands of the fused iteration, coefficient-major:
    (C, B) in the compute dtype, row c one scalar coefficient of every
    lane. Row order (see `_CoefIndex`): KU (n, k), Sb (n_dev, 3, k),
    KPa (n, n_dev), KPg (n, n_dev), params (N_PARAMS, n_dev)."""
    _, cdt = spec.dtypes
    B = params.shape[0]
    if not spec.n_dev:
        return jnp.zeros((0, B), cdt)
    blocks = (pre["KU"], pre["Sb"], pre["KPa"], pre["KPg"], params)
    return jnp.concatenate(
        [x.astype(cdt).reshape(B, -1).T for x in blocks], axis=0)


class _CoefIndex:
    """Row offsets into the `pack_coefs` layout."""

    def __init__(self, spec: FusedSpec):
        n, nd, k = spec.n, spec.n_dev, spec.k
        self.nd, self.k = nd, k
        self.s = n * k
        self.pa = self.s + nd * 3 * k
        self.pg = self.pa + n * nd
        self.p = self.pg + n * nd

    def ku(self, i, c):
        return i * self.k + c

    def sb(self, d, j, c):
        return self.s + (3 * d + j) * self.k + c

    def kpa(self, i, d):
        return self.pa + i * self.nd + d

    def kpg(self, i, d):
        return self.pg + i * self.nd + d

    def param(self, f, d):
        return self.p + f * self.nd + d


def _dot(xs, ys):
    """sum_j xs[j] * ys[j] over lane arrays, accumulated in index order."""
    acc = xs[0] * ys[0]
    for x, y in zip(xs[1:], ys[1:]):
        acc = acc + x * y
    return acc


def _matvec(M, x):
    return [_dot(row, x) for row in M]


def _matmul(X, Y):
    cols = list(zip(*Y))
    return [[_dot(row, col) for col in cols] for row in X]


def _inv3(M):
    """Closed-form 3x3 inverse (adjugate via cross products) of a nested
    list of lane arrays — branch-free, no per-pivot unrolling."""
    def cross(u, v):
        return [u[1] * v[2] - u[2] * v[1], u[2] * v[0] - u[0] * v[2],
                u[0] * v[1] - u[1] * v[0]]

    r = (cross(M[1], M[2]), cross(M[2], M[0]), cross(M[0], M[1]))
    det = _dot(M[0], r[0])
    return [[r[j][i] / det for j in range(3)] for i in range(3)]


def _solve_small(A, b, n_dev: int):
    """w = A^-1 b for the k x k Woodbury capacitance matrix A = I + D S,
    given as a nested list of lane arrays. k = 3 n_dev is tiny;
    specialize the common shapes (closed-form 3x3 blocks, block-Schur
    for two devices) and fall back to unrolled unpivoted elimination for
    larger device counts (A is a small perturbation of the identity in
    the circuits this engine targets)."""
    if n_dev == 1:
        return _matvec(_inv3(A), b)
    if n_dev == 2:
        P = [row[:3] for row in A[:3]]
        Q = [row[3:] for row in A[:3]]
        R = [row[:3] for row in A[3:]]
        T = [row[3:] for row in A[3:]]
        Pi = _inv3(P)
        X = _matmul(Pi, Q)
        y1 = _matvec(Pi, b[:3])
        RX = _matmul(R, X)
        Ry1 = _matvec(R, y1)
        x2 = _matvec(
            _inv3([[T[i][j] - RX[i][j] for j in range(3)] for i in range(3)]),
            [b[3 + i] - Ry1[i] for i in range(3)])
        Xx2 = _matvec(X, x2)
        return [y1[i] - Xx2[i] for i in range(3)] + x2
    k = 3 * n_dev
    A = [list(row) for row in A]
    b = list(b)
    for i in range(k):
        for r in range(i + 1, k):
            f = A[r][i] / A[i][i]
            for c in range(i + 1, k):
                A[r][c] = A[r][c] - f * A[i][c]
            b[r] = b[r] - f * b[i]
    x = [None] * k
    for i in range(k - 1, -1, -1):
        s_ = b[i]
        for c in range(i + 1, k):
            s_ = s_ - A[i][c] * x[c]
        x[i] = s_ / A[i][i]
    return x


def make_fused_iter(spec: FusedSpec, tol: float):
    """Returns iter_fn(coef, krhs, v, done) -> (v, done): one fused
    Woodbury-Newton step in lane form. `coef` is anything whose [c]
    yields coefficient row c over the lanes — the (C, B) array from
    `pack_coefs`, or its (C, rows, 128) block ref inside the Pallas
    kernel; `krhs` likewise holds the per-timestep hoist K @ rhs
    row-wise (n rows, compute dtype). `v` is a tuple of n store-dtype
    lane arrays, `done` a boolean lane array (per-lane freeze).

    The body is CONSTANT-FREE: Pallas rejects kernels that capture array
    literals, so terminal gathers resolve to static node indices (ground
    reads a zero lane array), the S_A/S_G/E_G sign vectors enter as
    python scalar coefficients, and the Woodbury identity is a python
    `1.0 +` on the diagonal."""
    sdt, cdt = spec.dtypes
    n, n_dev, k = spec.n, spec.n_dev, spec.k
    ix = _CoefIndex(spec)
    # host-side static node indices per device terminal (-1 = ground)
    g_idx = [int(i) if i < n else -1 for i in spec.g_safe]
    a_idx = [int(i) if i < n else -1 for i in spec.a_safe]
    b_idx = [int(i) if i < n else -1 for i in spec.b_safe]
    nf = len(PARAM_FIELDS)

    def iter_fn(coef, krhs, v, done):
        vc = [x.astype(cdt) for x in v]

        @functools.lru_cache(maxsize=None)
        def cf(c):                      # each coefficient row loads once
            return coef[c]

        def freeze(dv):
            return tuple(jnp.where(done, v[i], (vc[i] - dv[i]).astype(sdt))
                         for i in range(n))

        if n_dev == 0:      # linear circuit: one exact solve
            dv = [vc[i] - krhs[i] for i in range(n)]
            return freeze(dv), done | jnp.ones_like(done)
        zero = jnp.zeros_like(vc[0])

        def pick(xs, idx):
            return [xs[i] if i >= 0 else zero for i in idx]

        vg, va, vb = pick(vc, g_idx), pick(vc, a_idx), pick(vc, b_idx)
        i_ab, i_g, gg, d3 = [], [], [], []
        for d in range(n_dev):
            i_d, dg, da, db = channel_current_and_grads(
                *(cf(ix.param(f, d)) for f in range(nf)),
                vg[d], va[d], vb[d])
            gg.append(cf(ix.param(nf, d)))
            i_ab.append(i_d)
            i_g.append(gg[d] * (vg[d] - 0.5 * (va[d] + vb[d])))
            d3.append((dg, da, db))
        t = [vc[i] - krhs[i]
             + _dot([cf(ix.kpa(i, d)) for d in range(n_dev)], i_ab)
             + _dot([cf(ix.kpg(i, d)) for d in range(n_dev)], i_g)
             for i in range(n)]
        # Vm @ t rows are one-hot terminal picks (g, a, b) per device
        tg, ta, tb = pick(t, g_idx), pick(t, a_idx), pick(t, b_idx)
        # A = I + D S with D = s_a (x) d3 + s_g (x) gg*e_g per device
        # block (rank 2); e_g = (1, -1/2, -1/2) over the terminal axis
        # (g, a, b), KCL rows (a, b, g): s_a = (1, -1, 0),
        # s_g = (-1/2, -1/2, 1)
        A, b_k = [], []
        for d in range(n_dev):
            rows = ([], [], [])
            for c in range(k):
                sb = [cf(ix.sb(d, j, c)) for j in range(3)]
                d3S = _dot(list(d3[d]), sb)
                egS = (sb[0] - 0.5 * sb[1] - 0.5 * sb[2]) * gg[d]
                rows[0].append(d3S - 0.5 * egS)
                rows[1].append(-d3S - 0.5 * egS)
                rows[2].append(egS)
            A.extend(rows)
            d3g = _dot(list(d3[d]), [tg[d], ta[d], tb[d]])
            egg = (tg[d] - 0.5 * ta[d] - 0.5 * tb[d]) * gg[d]
            b_k.extend([d3g - 0.5 * egg, -d3g - 0.5 * egg, egg])
        for r in range(k):
            A[r][r] = 1.0 + A[r][r]
        w = _solve_small(A, b_k, n_dev)
        dv = [t[i] - _dot([cf(ix.ku(i, c)) for c in range(k)], w)
              for i in range(n)]
        err = functools.reduce(jnp.maximum, [jnp.abs(x) for x in dv])
        return freeze(dv), done | (err < tol)

    return iter_fn


def _lanes_in(spec: FusedSpec, pre, Krhs, params, v0):
    """Dense (B, ...) operands -> the lane form `make_fused_iter` takes."""
    _, cdt = spec.dtypes
    return (pack_coefs(spec, pre, params), Krhs.astype(cdt).T,
            tuple(v0[:, i] for i in range(spec.n)))


def newton_solve(spec: FusedSpec, pre, Krhs, params, v0,
                 iters: int, tol: float):
    """XLA fallback: fused iteration under a while_loop with whole-batch
    early exit. Per-lane freeze makes the result bit-identical to the
    fixed-length variant the Pallas kernel runs."""
    it = make_fused_iter(spec, tol)
    coef, krhs, v = _lanes_in(spec, pre, Krhs, params, v0)

    def cond(state):
        _, done, i = state
        return (i < iters) & jnp.logical_not(jnp.all(done))

    def body(state):
        v, done, i = state
        v, done = it(coef, krhs, v, done)
        return v, done, i + 1

    B = v0.shape[0]
    v, _, n_it = jax.lax.while_loop(
        cond, body, (v, jnp.zeros((B,), bool), jnp.asarray(0)))
    return jnp.stack(v, axis=1), n_it


def newton_solve_fixed(spec: FusedSpec, pre, Krhs, params, v0,
                       iters: int, tol: float):
    """Fixed-iteration variant (fori_loop, no early exit) — the exact
    control flow the Pallas kernel uses; parity tests run this against
    the kernel in interpret mode."""
    it = make_fused_iter(spec, tol)
    coef, krhs, v = _lanes_in(spec, pre, Krhs, params, v0)
    B = v0.shape[0]

    def body(_, state):
        v, done = state
        return it(coef, krhs, v, done)

    v, _ = jax.lax.fori_loop(0, iters, body,
                             (v, jnp.zeros((B,), bool)))
    return jnp.stack(v, axis=1)


def _gather_safe(x, idx):
    """(B, n) -> (B, n_dev) terminal values via padded gather; ground
    terminals (index n) read the zero pad column. Index-array twin of
    `make_fused_iter`'s statically-unrolled gather — the adjoint path
    never runs inside Pallas, so dynamic gathers are fine here."""
    xp = jnp.concatenate([x, jnp.zeros_like(x[:, :1])], axis=1)
    return xp[:, idx]


def residual(spec: FusedSpec, pre, Krhs, params, v):
    """Preconditioned BE residual F(v) = v - K rhs + (K Pa) i_ab(v)
    + (K Pg) i_g(v), whose root is the converged Newton state (the
    iteration's update is dv = M^-1 F, so dv = 0 iff F = 0). Pure
    elementwise jnp with no freeze masks or loops: the implicit-function
    adjoint differentiates THIS function w.r.t. the data inputs, never
    the while_loop that located the root. Casts to compute dtype happen
    inside so `jax.vjp` hands back cotangents matching the caller's
    input dtypes (params stays in store dtype on the mixed path)."""
    _, cdt = spec.dtypes
    out = v.astype(cdt) - Krhs.astype(cdt)
    if spec.n_dev == 0:
        return out
    vc = v.astype(cdt)
    vg = _gather_safe(vc, spec.g_safe)
    va = _gather_safe(vc, spec.a_safe)
    vb = _gather_safe(vc, spec.b_safe)
    p = params.astype(cdt)
    i_ab = channel_current_raw(
        *(p[:, i] for i in range(len(PARAM_FIELDS))), vg, va, vb)
    gg = p[:, len(PARAM_FIELDS)]
    i_g = gg * (vg - 0.5 * (va + vb))
    return (out
            + jnp.einsum("bid,bd->bi", pre["KPa"].astype(cdt), i_ab)
            + jnp.einsum("bid,bd->bi", pre["KPg"].astype(cdt), i_g))


def fixed_point_adjoint(spec: FusedSpec, pre, Krhs, params, v_star, v_bar):
    """Implicit-function VJP through the converged Newton solve.

    At the fixed point F(v*, theta) = 0 (theta = the data inputs pre /
    Krhs / params), the implicit function theorem gives
    dv*/dtheta = -M^-1 dF/dtheta with M = dF/dv = I + KU D Vm — the
    SAME rank-k structure the forward iteration inverts. The adjoint
    lam = M^-T vbar therefore costs ONE extra Woodbury solve against the
    transposed capacitance matrix,

        M^-T = I - Vm^T D^T A^-T KU^T,        A = I + D S,

    where A is the (k, k) matrix `make_fused_iter` builds lane-wise
    (assembled here at v*), and theta_bar = -(dF/dtheta)^T lam is one
    VJP of `residual` at the root. Returns (pre_bar, Krhs_bar,
    params_bar). The v0 cotangent is zero — the root does not depend on
    the initial guess, which is what makes the VJP independent of the
    iteration count past convergence (pinned by a regression test)."""
    _, cdt = spec.dtypes
    n_dev, k = spec.n_dev, spec.k
    vb_c = v_bar.astype(cdt)
    if n_dev == 0:
        lam = vb_c
    else:
        B = v_star.shape[0]
        vc = v_star.astype(cdt)
        vg = _gather_safe(vc, spec.g_safe)
        va = _gather_safe(vc, spec.a_safe)
        vb = _gather_safe(vc, spec.b_safe)
        p = params.astype(cdt)
        _, di_dvg, di_dva, di_dvb = channel_current_and_grads(
            *(p[:, i] for i in range(len(PARAM_FIELDS))), vg, va, vb)
        gg = p[:, len(PARAM_FIELDS)]
        d3 = jnp.stack([di_dvg, di_dva, di_dvb], axis=2)  # (B, n_dev, 3)
        Sb = pre["Sb"].astype(cdt)
        d3S = jnp.einsum("bdj,bdjk->bdk", d3, Sb)
        egS = (Sb[:, :, 0] - 0.5 * Sb[:, :, 1] - 0.5 * Sb[:, :, 2]) \
            * gg[:, :, None]
        DS = jnp.stack([d3S - 0.5 * egS,
                        -d3S - 0.5 * egS,
                        egS], axis=2).reshape(B, k, k)
        A = jnp.eye(k, dtype=cdt)[None] + DS
        # lam = vbar - Vm^T D^T (A^T)^-1 KU^T vbar
        y = jnp.einsum("bnk,bn->bk", pre["KU"].astype(cdt), vb_c)
        u = jnp.stack(_solve_small(
            [[A[:, j, i] for j in range(k)] for i in range(k)],
            [y[:, i] for i in range(k)], n_dev), axis=1)
        u3 = u.reshape(B, n_dev, 3)           # rows (a, b, g) of Um cols
        sau = u3[:, :, 0] - u3[:, :, 1]                        # s_a . u
        sgu = u3[:, :, 2] - 0.5 * (u3[:, :, 0] + u3[:, :, 1])  # s_g . u
        # D^T u over D's column order (g, a, b):
        #   d3 * (s_a . u) + gg * e_g * (s_g . u)
        ggs = gg * sgu
        dtu = d3 * sau[:, :, None] \
            + jnp.stack([ggs, -0.5 * ggs, -0.5 * ggs], axis=2)
        corr = jnp.zeros((B, spec.n + 1), cdt)
        corr = corr.at[:, spec.g_safe].add(dtu[:, :, 0])
        corr = corr.at[:, spec.a_safe].add(dtu[:, :, 1])
        corr = corr.at[:, spec.b_safe].add(dtu[:, :, 2])
        lam = vb_c - corr[:, : spec.n]
    _, vjp_fn = jax.vjp(
        lambda pre_, krhs_, params_:
            residual(spec, pre_, krhs_, params_, v_star),
        pre, Krhs, params)
    return vjp_fn(-lam)
