"""Finite-difference verification of the differentiable DSE path.

Three layers, matching how the gradients are built:

  1. `dse_grad.evaluate_grad_fn` — the pure-jnp analytic algebra. Every
     differentiable output is checked against central differences for
     every continuous knob, plus a bit-exact parity check of the
     `quantized=True` mode against the scalar `dse.evaluate` reference
     and a second-order `check_grads` spot check.
  2. `char_batch.t_cell_grad_fn` — the transient path, where gradients
     flow through the implicit-function VJP of the fused Newton solve.
  3. The VJP itself — the adjoint of a converged fixed point must not
     depend on how many Newton iterations the forward pass ran.

Central differences use RELATIVE steps of ~1e-4: much smaller steps sit
in the catastrophic-cancellation regime even in f64 (at eps=1e-7 the
apparent "error" is ~1%), much larger ones truncate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64
from jax.test_util import check_grads

from repro.core import dse
from repro.core.bank import BankConfig
from repro.core.dse_grad import KNOBS, OUTPUTS, evaluate_grad_fn
from repro.core.spice.char_batch import characterize, t_cell_grad_fn

EPS_REL = 1e-4      # central-difference relative step
TOL_REL = 1e-4      # acceptance threshold (ISSUE contract)

# off-nominal base point: keeps every knob away from kinks/specials
BASE = {"vdd_scale": 0.95, "w_read_scale": 1.10,
        "w_write_scale": 0.90, "bl_wire_scale": 1.05}


def _rel_err(ad, fd, out_mag, x_mag):
    """|ad - fd| relative to the gradient scale; the floor ties the
    scale to the output magnitude so exact-zero gradients compare
    clean."""
    # central differences carry ~machine_eps*|f|/(2h) ~ 1e-12*|f| of
    # cancellation noise: gradients below 1e-7*|f|/|x| are numerically
    # zero at this step size and compare against the floor instead
    floor = 1e-7 * (abs(out_mag) / max(x_mag, 1e-30) + 1e-300)
    return abs(ad - fd) / max(abs(ad), abs(fd), floor)


# ---------------------------------------------------------------------------
# 1. analytic algebra: every output x every knob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell,wwlls", [("gc2t_nn", False),
                                        ("gc2t_np", True),
                                        ("gc2t_osos", False)])
def test_analytic_grads_match_central_differences(cell, wwlls):
    with enable_x64():
        cfg = BankConfig(32, 64, cell=cell, wwlls=wwlls)
        fn = evaluate_grad_fn(cfg)

        def vec_fn(x):           # (4,) knob vector -> (n_out,) outputs
            kn = {k: x[i][None] for i, k in enumerate(KNOBS)}
            out = fn(kn)
            return jnp.stack([out[o][0] for o in OUTPUTS])

        x0 = jnp.asarray([BASE[k] for k in KNOBS], dtype=jnp.float64)
        jac = jax.jacfwd(vec_fn)(x0)             # (n_out, 4)
        jac_rev = jax.jacrev(vec_fn)(x0)
        # atol tied to the Jacobian scale: fwd/rev may disagree on
        # whether a dead path is exactly 0.0 or denormal-level noise
        np.testing.assert_allclose(jac, jac_rev, rtol=1e-12,
                                   atol=1e-16 * float(np.abs(jac).max()))

        y0 = vec_fn(x0)
        for j, knob in enumerate(KNOBS):
            h = EPS_REL * float(x0[j])
            yp = vec_fn(x0.at[j].add(+h))
            ym = vec_fn(x0.at[j].add(-h))
            fd = (yp - ym) / (2 * h)
            for i, out in enumerate(OUTPUTS):
                err = _rel_err(float(jac[i, j]), float(fd[i]),
                               float(y0[i]), float(x0[j]))
                assert err < TOL_REL, \
                    f"d({out})/d({knob}): ad={jac[i, j]:.6e} " \
                    f"fd={fd[i]:.6e} rel={err:.3e}"


def test_quantized_mode_matches_scalar_reference_bitwise():
    """quantized=True replicates the scalar staircase algebra exactly;
    both sides run under x64 (the scalar path is f32 otherwise)."""
    with enable_x64():
        for cell, wwlls in [("gc2t_nn", False), ("gc2t_np", False),
                            ("gc2t_osos", True)]:
            cfg = BankConfig(32, 64, cell=cell, wwlls=wwlls)
            fn = evaluate_grad_fn(cfg, quantized=True)
            for vs in (0.8, 1.0, 1.15):
                out = fn({"vdd_scale": jnp.asarray([vs],
                                                   dtype=jnp.float64)})
                ref = dse.evaluate(cfg, vdd_scale=vs)
                for f in ("t_read_s", "t_write_s", "f_max_hz",
                          "retention_s", "leakage_w", "refresh_w",
                          "read_bw_bps", "eff_bw_bps"):
                    a, b = float(out[f][0]), float(getattr(ref, f))
                    assert a == pytest.approx(b, rel=1e-12, abs=0), \
                        f"{cell} vs={vs} {f}: traced={a!r} scalar={b!r}"
                sw = float(out["standby_w"][0])
                assert sw == pytest.approx(ref.standby_w, rel=1e-12)


def test_analytic_second_order_spot_check():
    """check_grads-style: the VJP of the VJP is also correct (order=2)
    for the headline objective along the headline knob."""
    with enable_x64():
        fn = evaluate_grad_fn(BankConfig(32, 64, cell="gc2t_np"))

        def f(vs):
            return fn({"vdd_scale": vs[None]})["standby_w"][0]

        check_grads(f, (jnp.asarray(0.93, dtype=jnp.float64),),
                    order=2, modes=("rev",), eps=1e-4,
                    atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# 2. transient path: implicit-function VJP through the Newton solve
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("solver", ["pallas", "sparse"])
def test_t_cell_transient_grads_match_fd(solver):
    """t_cell gradients w.r.t. device width / vdd / bitline geometry via
    the custom_vjp fixed-point adjoint vs central differences, plus
    nominal parity against the non-differentiable characterize() path.
    One batched forward evaluates the nominal point and all +/-eps
    perturbations in a single compiled program."""
    knob_names = ("vdd_scale", "w_read_scale", "bl_wire_scale")
    base = np.asarray([0.97, 1.05, 0.92])
    with enable_x64():
        cfg = BankConfig(16, 16, cell="gc2t_np")
        fn = t_cell_grad_fn(cfg, solver=solver)

        # batch rows: 0 = nominal-1.0 (parity), 1 = base point,
        # 2..7 = base +/- eps per knob
        h = EPS_REL * base
        rows = [np.ones(3), base]
        for j in range(3):
            for s in (+1, -1):
                p = base.copy()
                p[j] += s * h[j]
                rows.append(p)
        X = np.stack(rows)                      # (8, 3)
        kn = {k: jnp.asarray(X[:, j]) for j, k in enumerate(knob_names)}
        t, valid = fn(kn)
        assert bool(jnp.all(valid))

        ref = characterize([cfg], solver=solver)[0]
        assert float(t[0]) == pytest.approx(ref.t_cell_s, rel=1e-9), \
            "nominal traced t_cell != characterize()"

        def scalar(x):
            k1 = {k: x[j][None] for j, k in enumerate(knob_names)}
            return fn(k1)[0][0]

        grad = jax.grad(scalar)(jnp.asarray(base))
        t0 = float(t[1])
        for j, name in enumerate(knob_names):
            fd = float(t[2 + 2 * j] - t[3 + 2 * j]) / (2 * h[j])
            err = _rel_err(float(grad[j]), fd, t0, base[j])
            assert err < TOL_REL, \
                f"{solver} d(t_cell)/d({name}): ad={float(grad[j]):.6e} " \
                f"fd={fd:.6e} rel={err:.3e}"


# ---------------------------------------------------------------------------
# 3. fixed-point adjoint is iteration-count independent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("solver", ["pallas", "sparse"])
def test_fixed_point_vjp_independent_of_newton_iters(solver):
    """Past convergence, the implicit-function adjoint depends only on
    the fixed point, never on the forward iteration count — doubling
    the Newton budget must reproduce the gradient bitwise. (An unrolled
    backprop would differ: each extra iteration adds terms.)"""
    from repro.core.spice.transient import Transient
    from tests.test_fused_newton import _lattice_inputs

    with enable_x64():
        system, inp = _lattice_inputs(B=2, cell="gc2t_nn")
        v0 = jnp.full((system.n,), inp["v_pre"])

        def loss(scale, iters):
            tr = Transient(system, solver=solver, iters=iters)
            res = tr.run_lattice(
                inp["wt"], inp["wv"], inp["t_end"], 40,
                over_batches={"G": jnp.asarray(inp["G_b"]) * scale,
                              "C": jnp.asarray(inp["C_b"])},
                v0=v0)
            return jnp.sum(res["all"][:, -1, :] ** 2)

        x = jnp.asarray(1.0, dtype=jnp.float64)
        g30 = jax.grad(lambda s: loss(s, 30))(x)
        g60 = jax.grad(lambda s: loss(s, 60))(x)
        assert float(g30) == float(g60), (float(g30), float(g60))
        assert jnp.isfinite(g30)
