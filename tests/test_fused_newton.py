"""Fused sparse-Newton engine: Woodbury/sparse-LU lattice parity vs the
dense reference, Pallas-kernel interpret-vs-XLA parity, the mixed
precision contract, crossing_time edge cases, and the small satellites
(_pad_to round-trip, LU-based modified Newton)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.core import timing
from repro.core.bank import BankConfig, build_bank
from repro.core.spice.mna import G_BIG, MNASparsity
from repro.core.spice.transient import Transient, crossing_time
from repro.kernels.batched_solve import newton as nwt
from repro.kernels.batched_solve import ops as solve_ops
from repro.kernels.batched_solve import sparse as sps
from repro.kernels.batched_solve.fused import fused_newton
from repro.kernels.batched_solve.kernel import _pad_to


def _lattice_inputs(B=3, cell="gc2t_nn", ws=16, nw=16):
    """One topology's run_lattice inputs with per-lane R/C jitter —
    the char_batch assembly path in miniature."""
    bank = build_bank(BankConfig(ws, nw, cell))
    ckt, meta = timing.read_netlist(bank)
    res_stamps, cap_stamps, src_G = ckt.build_stamps()
    system = ckt.build()
    rng = np.random.default_rng(42)
    g = np.asarray([g for _, _, g in ckt.res])
    c = np.asarray([c for _, _, c in ckt.caps])
    g_b = g[None] * (1 + 0.1 * rng.uniform(-1, 1, (B, len(g))))
    c_b = c[None] * (1 + 0.1 * rng.uniform(-1, 1, (B, len(c))))
    G_b = src_G[None] + np.einsum("br,rij->bij", g_b, res_stamps)
    C_b = np.einsum("bc,cij->bij", c_b, cap_stamps)
    t_an, _ = timing.cell_read_time(bank)
    t_end1 = max(timing.T_END_OVER_ANALYTIC * t_an, timing.T_END_MIN_S)
    t_end = t_end1 * (1 + 0.1 * rng.uniform(-1, 1, B))
    waves, v_pre = timing.read_stimulus(bank.cell, bank.cfg.tech,
                                        meta["v_sn"],
                                        timing.T0_FRACTION * t_end1)
    k = max(len(t) for t, _ in waves)
    wt = np.zeros((B, len(waves), k))
    wv = np.zeros((B, len(waves), k))
    for w, (t, v) in enumerate(waves):
        wt[:, w] = t + [t[-1]] * (k - len(t))
        wv[:, w] = v + [v[-1]] * (k - len(v))
    return system, dict(wt=wt, wv=wv, t_end=t_end, G_b=G_b, C_b=C_b,
                        v_pre=v_pre, bank=bank)


def _run(system, inp, solver, precision="f64", n_steps=60):
    tr = Transient(system, solver=solver, precision=precision)
    v0 = jnp.full((system.n,), inp["v_pre"])
    return tr.run_lattice(inp["wt"], inp["wv"], inp["t_end"], n_steps,
                          over_batches={"G": inp["G_b"], "C": inp["C_b"]},
                          v0=v0)


# ---------------------------------------------------------------------------
# fused engines == dense reference on whole lattice traces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("solver", ["pallas", "sparse"])
@pytest.mark.parametrize("cell", ["gc2t_nn", "gc2t_np"])
def test_fused_lattice_matches_dense(solver, cell):
    with enable_x64():
        system, inp = _lattice_inputs(cell=cell)
        ref = _run(system, inp, "jnp")
        got = _run(system, inp, solver)
        dev = float(jnp.max(jnp.abs(ref["all"] - got["all"])))
        assert dev <= 1e-6, dev


def test_mixed_precision_holds_parity_contract():
    """mixed = f32 carried traces, f64 model + solve: t_cell within the
    1% contract; pure f32 is NOT asserted (screening only)."""
    with enable_x64():
        system, inp = _lattice_inputs()
        ref = _run(system, inp, "jnp")
        got = _run(system, inp, "pallas", precision="mixed")
        assert got["all"].dtype == jnp.float32
        bank = inp["bank"]
        swing = bank.cfg.tech.v_sense_se
        target = inp["v_pre"] + (swing if bank.cell.predischarge
                                 else -swing)
        for res in (ref, got):
            tc, valid = crossing_time(res["t"], res["rbl_near"], target,
                                      rising=bank.cell.predischarge)
            res["tc"] = np.asarray(tc, np.float64)
            res["valid"] = np.asarray(valid)
        assert ref["valid"].all() and got["valid"].all()
        rel = np.abs(got["tc"] - ref["tc"]) / ref["tc"]
        assert float(np.max(rel)) <= 0.01


def test_fused_rejects_unknown_override_batches():
    """Device-parameter overrides ("w", "vt0", ... — the differentiable
    DSE path) are accepted alongside G/C; anything else still fails
    loudly instead of being silently dropped."""
    with enable_x64():
        system, inp = _lattice_inputs()
        tr = Transient(system, solver="pallas")
        with pytest.raises(ValueError, match="overrides"):
            tr.run_lattice(inp["wt"], inp["wv"], inp["t_end"], 10,
                           over_batches={"G": inp["G_b"],
                                         "bogus": np.ones((3, 4))})


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode) == XLA fallback, per precision
# ---------------------------------------------------------------------------

def _step_operands(precision, B=3):
    """Physically consistent single-timestep operands for the fused
    solve: first backward-Euler step of the read transient."""
    system, inp = _lattice_inputs(B=B)
    spec = nwt.build_fused_spec(system, precision)
    sdt, cdt = spec.dtypes
    B = inp["t_end"].shape[0]
    h = jnp.asarray(inp["t_end"] / 60, cdt)
    pre = nwt.precompute(spec, inp["G_b"], inp["C_b"], h)
    src = jnp.zeros((B, system.n), cdt).at[:, np.asarray(system.src_node)] \
        .set(G_BIG * jnp.asarray(inp["wv"], cdt)[
            :, np.asarray(system.src_wave), 0])
    v0 = jnp.full((B, system.n), inp["v_pre"], sdt)
    Krhs = jnp.einsum("bij,bj->bi", pre["KCoh"], v0.astype(cdt)) \
        + jnp.einsum("bij,bj->bi", pre["K"], src)
    params = sps.pack_params(system.dev, B, sdt)
    return spec, pre, Krhs, params, v0


@pytest.mark.parametrize("precision,platform,engine", [
    ("f32", "tpu", "pallas"), ("f64", "tpu", "xla_while"),
    ("mixed", "tpu", "xla_while"), ("f32", "cpu", "xla_while")])
def test_fused_engine_follows_platform_and_compute_dtype(precision, platform,
                                                         engine):
    """Mosaic has no 64-bit types: only f32-compute specs take the kernel,
    and only on TPU."""
    with enable_x64():
        system, _ = _lattice_inputs()
    spec = nwt.build_fused_spec(system, precision)
    assert solve_ops.fused_engine(spec, platform) == engine


@pytest.mark.parametrize("B", [3, 260])
@pytest.mark.parametrize("precision", ["f64", "f32"])
def test_fused_kernel_interpret_matches_xla(precision, B):
    """B=260 spans three 128-lane tile rows, one per grid step, with the
    last one edge-padded."""
    with enable_x64():
        spec, pre, Krhs, params, v0 = _step_operands(precision, B)
        v_xla, _ = nwt.newton_solve(spec, pre, Krhs, params, v0, 6, 1e-9)
        v_fix = nwt.newton_solve_fixed(spec, pre, Krhs, params, v0,
                                       6, 1e-9)
        v_ker = fused_newton(spec, pre, Krhs, params, v0, iters=6,
                             tol=1e-9, block_rows=1, interpret=True)
        # per-lane freeze: early-exit while_loop == fixed fori_loop
        np.testing.assert_array_equal(np.asarray(v_xla), np.asarray(v_fix))
        np.testing.assert_array_equal(np.asarray(v_ker), np.asarray(v_fix))
        assert v_ker.dtype == v0.dtype


def test_fused_kernel_dispatcher_routes_and_pads():
    """On CPU ops.fused_newton_step routes to the XLA while_loop, which
    matches the interpret-mode kernel at its default tiling (lane
    padding: B=3 pads to one 128-lane row)."""
    with enable_x64():
        spec, pre, Krhs, params, v0 = _step_operands("f64")
        v_fb = solve_ops.fused_newton_step(spec, pre, Krhs, params, v0,
                                           iters=6, tol=1e-9)
        v_ker = fused_newton(spec, pre, Krhs, params, v0, iters=6,
                             tol=1e-9, interpret=True)
        np.testing.assert_array_equal(np.asarray(v_ker), np.asarray(v_fb))


# ---------------------------------------------------------------------------
# satellites: _pad_to, LU-based modified Newton, crossing_time edges
# ---------------------------------------------------------------------------

def test_pad_to_round_trip():
    x = jnp.arange(15.0).reshape(3, 5)
    for axis, n in ((0, 8), (1, 7)):
        y = _pad_to(x, n, axis)
        assert y.shape[axis] == n
        pad = [slice(None)] * 2
        pad[axis] = slice(x.shape[axis], None)
        assert float(jnp.abs(y[tuple(pad)]).max()) == 0.0
        sl = [slice(None)] * 2
        sl[axis] = slice(0, x.shape[axis])
        np.testing.assert_array_equal(np.asarray(y[tuple(sl)]),
                                      np.asarray(x))
    assert _pad_to(x, 5, 1) is x          # no-op when already sized
    assert _pad_to(x, 3, 1).shape == x.shape


def test_modified_newton_lu_matches_explicit_inverse():
    """The chord iteration now factors once (LU) and applies triangular
    solves; same math as the old explicit-inverse path."""
    with enable_x64():
        bank = build_bank(BankConfig(16, 16, "gc2t_nn"))
        ckt, meta = timing.read_netlist(bank)
        sys = ckt.build()
        rng = np.random.default_rng(5)
        v = jnp.asarray(rng.uniform(0.0, 1.1, (sys.n,)))
        h = jnp.asarray(1e-11)
        J = sys.jacobian(v, h)
        r = jnp.asarray(rng.standard_normal((sys.n,)) * 1e-3)
        lu_piv = jax.scipy.linalg.lu_factor(J)
        x_lu = jax.scipy.linalg.lu_solve(lu_piv, r)
        x_inv = jnp.linalg.inv(J) @ r
        np.testing.assert_allclose(np.asarray(x_lu), np.asarray(x_inv),
                                   rtol=1e-9, atol=1e-18)

        # and the full trace still agrees with fresh-Jacobian Newton.
        # The chord iteration converges only linearly, so it needs fine
        # steps (contractive h) and a deeper iteration budget.
        t_an, _ = timing.cell_read_time(bank)
        t_end = max(timing.T_END_OVER_ANALYTIC * t_an, timing.T_END_MIN_S)
        waves, v_pre = timing.read_stimulus(bank.cell, bank.cfg.tech,
                                            meta["v_sn"],
                                            timing.T0_FRACTION * t_end)
        v0 = jnp.full((sys.n,), v_pre)
        full = Transient(sys, newton="full", tol=1e-9).run(
            waves, t_end, n_steps=300, v0=v0)
        mod = Transient(sys, newton="modified", iters=25).run(
            waves, t_end, n_steps=300, v0=v0)
        dev = float(jnp.max(jnp.abs(full["all"] - mod["all"])))
        assert dev <= 1e-6, dev


def test_crossing_time_step0_flat_and_never():
    """Edge lanes: already past target at step 0 (invalid, +inf), flat
    trace parked ON the target (invalid, no NaN from dv == 0), flat
    below target, and a normal crossing lane in the same batch."""
    t = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    v = jnp.asarray([
        [0.5, 0.9, 1.0, 1.0],   # step-0 exact hit: target reached at t[0]
        [0.5, 0.5, 0.5, 0.5],   # flat ON target: dv == 0 bracket
        [0.1, 0.2, 0.3, 0.4],   # never reaches
        [0.0, 0.4, 0.8, 0.8],   # normal: crosses 0.5 at t=2.25
    ])
    tc, ok = crossing_time(t, v, 0.5, rising=True)
    tc = np.asarray(tc)
    assert np.asarray(ok).tolist() == [False, False, False, True]
    assert not np.isnan(tc).any()
    assert np.isinf(tc[:3]).all()
    assert tc[3] == pytest.approx(2.25)
    # falling direction, same edges
    tcf, okf = crossing_time(t, 1.0 - v, 0.5, rising=False)
    assert np.asarray(okf).tolist() == [False, False, False, True]
    assert float(tcf[3]) == pytest.approx(2.25)


def test_precision_knob_validation():
    from repro.api import SweepQuery
    with pytest.raises(ValueError, match="precision"):
        SweepQuery(precision="f16")
    with pytest.raises(ValueError, match="solver"):
        SweepQuery(solver="scipy")
    with pytest.raises(ValueError, match="precision"):
        nwt.build_fused_spec(object(), "f16")   # checked before system use
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        SweepQuery(fidelity="transient", precision="f32")
    assert any("screening" in str(x.message) for x in w)
