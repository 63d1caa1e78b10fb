"""Batched transient characterization: analytic Jacobian stamps vs
jacfwd, Newton early-exit, whole-lattice parity vs the scalar
simulate_read reference, and the transient-fidelity SweepQuery."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.api import CalibratedTable, DesignTable, Session, SweepQuery
from repro.core import timing
from repro.core.bank import BankConfig, build_bank
from repro.core.spice.char_batch import characterize
from repro.core.spice.mna import Circuit, channel_current_grads
from repro.core.spice.transient import (Transient, crossing_time,
                                        make_stepper)
from repro.core.techfile import SYN40

TOPOLOGIES = ("gc2t_nn", "gc2t_np", "gc2t_osos")


def _read_system(cell, ws=32, nw=32):
    bank = build_bank(BankConfig(ws, nw, cell))
    ckt, meta = timing.read_netlist(bank)
    return bank, ckt.build(), meta


# ---------------------------------------------------------------------------
# analytic Jacobian stamps == jacfwd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", TOPOLOGIES)
def test_device_grads_match_autodiff(cell):
    _, sys, _ = _read_system(cell)
    rng = np.random.default_rng(3)
    for _ in range(3):
        v = jnp.asarray(rng.uniform(0.0, 1.1, (sys.n,)), jnp.float32)
        vg = sys._v_of(v, sys.didx["g"])
        va = sys._v_of(v, sys.didx["a"])
        vb = sys._v_of(v, sys.didx["b"])
        from repro.core.spice.mna import channel_current_raw
        args = (sys.dev["pol"], sys.dev["vt0"], sys.dev["n"], sys.dev["kp"],
                sys.dev["lam"], sys.dev["w"], sys.dev["l"])

        def cur(x, which):
            vs = [vg, va, vb]
            vs[which] = x
            return channel_current_raw(*args, *vs)

        g_an = channel_current_grads(*args, vg, va, vb)
        for which, an in enumerate(g_an):
            ad = jnp.diagonal(jax.jacfwd(lambda x: cur(x, which))(
                [vg, va, vb][which]))
            np.testing.assert_allclose(np.asarray(an), np.asarray(ad),
                                       rtol=1e-5, atol=1e-12)


@pytest.mark.parametrize("cell", TOPOLOGIES)
def test_analytic_jacobian_matches_jacfwd(cell):
    _, sys, _ = _read_system(cell)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.uniform(0.0, 1.1, (sys.n,)), jnp.float32)
    vp = jnp.asarray(rng.uniform(0.0, 1.1, (sys.n,)), jnp.float32)
    wv = jnp.asarray(rng.uniform(0.0, 1.1, (4,)), jnp.float32)
    h = jnp.float32(1e-11)
    J_ad = jax.jacfwd(lambda vv: sys.residual(vv, vp, h, wv))(v)
    J_an = sys.jacobian(v, h)
    scale = float(jnp.max(jnp.abs(J_ad)))
    assert float(jnp.max(jnp.abs(J_ad - J_an))) <= 1e-6 * scale


def test_analytic_newton_trace_matches_jacfwd_newton():
    """Full integration parity (the acceptance bar): analytic-Jacobian
    Newton vs jacfwd Newton traces agree to 1e-6 in float64 (f32 solve
    noise through the cond~1e6 MNA Jacobian swamps either method)."""
    with enable_x64():
        bank, sys, meta = _read_system("gc2t_nn")
        t_an, _ = timing.cell_read_time(bank)
        t_end = max(timing.T_END_OVER_ANALYTIC * t_an, timing.T_END_MIN_S)
        waves, v_pre = timing.read_stimulus(bank.cell, SYN40, meta["v_sn"],
                                            timing.T0_FRACTION * t_end)
        v0 = jnp.full((sys.n,), v_pre)
        ref = Transient(sys, newton="jacfwd").run(waves, t_end,
                                                  n_steps=200, v0=v0)
        got = Transient(sys, newton="full", tol=1e-9).run(waves, t_end,
                                                          n_steps=200, v0=v0)
        diff = float(jnp.max(jnp.abs(ref["all"] - got["all"])))
        assert diff <= 1e-6, diff


def test_newton_early_exit_converges_and_saves_iterations():
    with enable_x64():
        bank, sys, meta = _read_system("gc2t_nn")
        t_an, _ = timing.cell_read_time(bank)
        t_end = max(6.0 * t_an, 0.5e-9)
        h = jnp.asarray(t_end / 300)
        vdd = SYN40.vdd
        wt = jnp.asarray([[0.0, 1.0]] * 4)
        wv = jnp.asarray([[vdd, vdd], [vdd, vdd],
                          [meta["v_sn"]] * 2, [vdd] * 2])
        v = jnp.full((sys.n,), vdd)
        step_aux = make_stepper(sys, iters=10, tol=1e-8, with_aux=True)
        v_fast, n_it = step_aux(v, h, h, wt, wv, {})
        step_full = make_stepper(sys, iters=10, tol=0.0)
        v_ref = step_full(v, h, h, wt, wv, {})
        # early exit triggered well under the cap, same solution
        assert int(n_it) < 10
        assert float(jnp.max(jnp.abs(v_fast - v_ref))) <= 1e-6


# ---------------------------------------------------------------------------
# batched characterization == scalar simulate_read
# ---------------------------------------------------------------------------

def test_batched_characterization_matches_scalar_3_topologies():
    cfgs = [BankConfig(ws, nw, cell) for cell in TOPOLOGIES
            for (ws, nw) in ((16, 16), (32, 32))]
    chars = characterize(cfgs, n_steps=200)
    assert len(chars) == len(cfgs)
    for cfg, ch in zip(cfgs, chars):
        t_ref, _ = timing.simulate_read(build_bank(cfg), n_steps=200)
        assert ch is not None and ch.cfg is cfg
        if np.isinf(t_ref):
            assert np.isinf(ch.t_cell_s)
        else:
            assert ch.t_cell_s == pytest.approx(t_ref, rel=0.01), cfg
        assert ch.t_cell_analytic_s > 0 and ch.n_steps == 200


def test_groups_of_one_structure_share_a_program_with_own_values():
    """Write-Vt flavor, WWL boost and the cell's read device change element
    and device VALUES, not the read column's structure: those topology
    groups run one shared fused program, and each still gets its own
    values (the OS-read gc2t_osos would drift from its dense reference if
    it inherited gc2t_nn's devices)."""
    from repro.core.dse_batch import topology_key
    from repro.core.spice import char_batch
    cfgs = [BankConfig(16, 16, "gc2t_nn", write_vt=vt, wwlls=ls)
            for vt in (None, "nmos_hvt") for ls in (False, True)]
    cfgs.append(BankConfig(16, 16, "gc2t_osos"))
    chars = characterize(cfgs, n_steps=100)
    refs = characterize(cfgs, n_steps=100, solver="jnp")
    for cfg, ch, ref in zip(cfgs, chars, refs):
        assert ch.swing_ok == ref.swing_ok
        assert ch.t_cell_s == pytest.approx(ref.t_cell_s, rel=0.01), cfg
    with enable_x64():
        trs = {id(char_batch._pipeline(
            build_bank(c), topology_key(c) + (8, 100, "pallas", "f64"))[1])
            for c in cfgs}
    assert len(trs) == 1


def test_compile_simulate_is_a_one_lane_lattice():
    """CompileQuery(simulate=True) characterizes through the lattice
    engine (one lane), not the dense scalar stepper it is checked
    against."""
    cfg = BankConfig(16, 16, "gc2t_nn")
    rep = Session().compile(cfg, simulate=True)
    (ch,) = characterize([cfg])
    assert rep.t_cell_sim_s == ch.t_cell_s
    t_ref, _ = timing.simulate_read(build_bank(cfg))
    assert rep.t_cell_sim_s == pytest.approx(t_ref, rel=0.01)


def test_characterize_skips_non_gain_cells():
    chars = characterize([BankConfig(16, 16, "sram6t"),
                          BankConfig(16, 16, "gc2t_nn")], n_steps=100)
    assert chars[0] is None and chars[1] is not None


def test_crossing_time_interpolates_and_flags():
    t = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    v = jnp.asarray([[0.0, 0.5, 1.0, 1.0],     # crosses 0.75 at t=2.5
                     [0.0, 0.1, 0.2, 0.3],     # never crosses
                     [1.0, 1.0, 1.0, 1.0]])    # past target at step 0
    tc, ok = crossing_time(t, v, 0.75, rising=True)
    assert np.asarray(ok).tolist() == [True, False, False]
    assert float(tc[0]) == pytest.approx(2.5)
    assert np.isinf(float(tc[1])) and np.isinf(float(tc[2]))
    tc2, ok2 = crossing_time(t, -v, -0.75, rising=False)
    assert bool(ok2[0]) and float(tc2[0]) == pytest.approx(2.5)


def test_circuit_node_interning_dict_backed():
    ckt = Circuit()
    idx = [ckt.node(f"n{i}") for i in range(50)]
    assert idx == list(range(1, 51))
    assert ckt.node("n7") == 8 and ckt.node("0") == 0
    # stamps of a tiny divider: G(g) reproduces build()
    ckt.r("n0", "n1", 100.0)
    ckt.r("n1", "0", 50.0)
    ckt.c("n1", "0", 1e-15)
    ckt.vsrc("n0", 0)
    rst, cst, src_G = ckt.build_stamps()
    sys = ckt.build()
    g = np.array([x[2] for x in ckt.res])
    c = np.array([x[2] for x in ckt.caps])
    np.testing.assert_allclose(
        src_G + np.einsum("r,rij->ij", g, rst),
        np.asarray(sys.G, np.float64), rtol=1e-7)
    np.testing.assert_allclose(np.einsum("c,cij->ij", c, cst),
                               np.asarray(sys.C, np.float64), rtol=1e-7)


# ---------------------------------------------------------------------------
# SweepQuery(fidelity="transient") through the Session
# ---------------------------------------------------------------------------

def test_transient_sweep_query_returns_calibrated_table():
    s = Session()
    q = SweepQuery(cells=("gc2t_nn", "sram6t"), word_sizes=(16,),
                   num_words=(16, 32), wwlls=(False,),
                   fidelity="transient", sim_steps=150)
    table = s.run(q)
    assert isinstance(table, CalibratedTable)
    assert isinstance(table, DesignTable) and len(table) == 4
    assert table is s.run(q)                      # memoized whole-table
    cal = table.calibration()
    assert cal["n_simulated"] == 2                # gc points only
    assert cal["max_rel_dev"] is not None
    # analytic points identical to an analytic sweep of the same lattice
    ta = s.run(dataclasses.replace(q, fidelity="analytic"))
    assert type(ta) is DesignTable
    assert all(a is b for a, b in zip(ta.points, table.points))
    # per-config transient chars are shared with overlapping sweeps
    q2 = SweepQuery(cells=("gc2t_nn",), word_sizes=(16,), num_words=(16,),
                    wwlls=(False,), fidelity="transient", sim_steps=150)
    t2 = s.run(q2)
    assert t2.transient[0] is table.transient[0]
    rows = table.as_dict()["rows"]
    assert sum("transient" in r for r in rows) == 2


def test_transient_sweep_rejects_unknown_fidelity():
    with pytest.raises(ValueError):
        Session().run(SweepQuery(fidelity="spice"))
