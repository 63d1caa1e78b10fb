"""Voltage-aware co-design path: vdd_scale axis parity vs the scalar
reference, vectorized feasibility/banks grids bit-for-bit, profiler
Profile.demands() unit sanity, feasible/banks_needed edge cases, and the
CoDesignQuery end-to-end flow + memoization."""
import dataclasses

import numpy as np
import pytest

from repro.api import CoDesignQuery, CoDesignReport, Session, SweepQuery
from repro.core import dse, trace
from repro.core.bank import BankConfig
from repro.core.dse import Demand, lattice_configs
from repro.core.dse_batch import (banks_needed_grid, evaluate_vdd_lattice,
                                  feasible_grid, shmoo_batch)
from repro.core.multibank import banks_needed
from repro.core.techfile import SYN40, with_vdd_scale
from repro.workloads.profiler import Profile, profile_arch

SCALES = (0.75, 1.0, 1.2)
CFGS = lattice_configs(cells=("gc2t_nn", "gc2t_osos", "sram6t"),
                       word_sizes=(16, 32), num_words=(16, 32),
                       wwlls=(False, True))


@pytest.fixture(scope="module")
def lat():
    return evaluate_vdd_lattice(CFGS, SCALES)


@pytest.fixture(scope="module")
def scalar_points():
    return {(vi, pi): dse.evaluate(c, vdd_scale=v)
            for vi, v in enumerate(SCALES) for pi, c in enumerate(CFGS)}


# ---------------------------------------------------------------------------
# the vdd axis itself
# ---------------------------------------------------------------------------

def test_with_vdd_scale_is_memoized_and_scales_only_vdd():
    t1 = with_vdd_scale(SYN40, 0.8)
    assert t1 is with_vdd_scale(SYN40, 0.8)
    assert t1.vdd == pytest.approx(SYN40.vdd * 0.8)
    assert t1.v_sense_se == SYN40.v_sense_se          # periphery untouched
    assert t1.devices is SYN40.devices or t1.devices == SYN40.devices
    assert with_vdd_scale(SYN40, 1.0) is SYN40
    with pytest.raises(ValueError):
        with_vdd_scale(SYN40, 0.0)


def test_scalar_evaluate_vdd_scale_moves_retention_and_speed():
    cfg = BankConfig(16, 16, "gc2t_nn")
    lo = dse.evaluate(cfg, vdd_scale=0.8)
    hi = dse.evaluate(cfg, vdd_scale=1.2)
    nom = dse.evaluate(cfg)
    assert nom.vdd_scale == 1.0 and lo.vdd_scale == 0.8
    # higher rail -> higher written level -> longer retention (gc2t_nn)
    assert hi.retention_s > nom.retention_s > lo.retention_s
    # geometry is voltage-independent
    assert lo.area_um2 == nom.area_um2 == hi.area_um2
    assert "vdd_scale" in nom.as_dict()


def test_lattice_batches_retention_once():
    """One lattice call: one batched retention integral over every
    (gain-cell group, rung), and one constants call per (group, rung)."""
    cfgs = lattice_configs(cells=("gc2t_nn", "gc2t_np", "gc2t_osos"),
                           word_sizes=(16,), num_words=(16,),
                           wwlls=(False, True))
    scales = tuple(np.linspace(0.7123, 1.2123, 8).tolist())
    with trace.recording() as rec:
        lat = evaluate_vdd_lattice(cfgs, scales)
    assert len(rec.named("dse_batch.retention")) == 1
    assert len(rec.named("dse_batch.group_constants")) == 6 * 8
    assert (rec.counters["dse_batch.retention_rows"],
            rec.counters["dse_batch.retention_lanes"]) == (48, 64)
    for vi, v in enumerate(scales):
        for pi, cfg in enumerate(cfgs):
            assert lat.point(vi, pi).retention_s == \
                dse.evaluate(cfg, vdd_scale=v).retention_s


def test_vdd_lattice_matches_scalar_reference(lat, scalar_points):
    """(V, P) batched table vs dse.evaluate at each (voltage, config):
    the feasibility-deciding fields must be BIT-FOR-BIT."""
    for (vi, pi), ref in scalar_points.items():
        p = lat.point(vi, pi)
        assert p.swing_ok == ref.swing_ok, (vi, pi)
        assert p.f_max_hz == ref.f_max_hz, (vi, pi)
        if np.isinf(ref.retention_s):
            assert np.isinf(p.retention_s)
        else:
            assert p.retention_s == ref.retention_s, (vi, pi)
        for f in ("leakage_w", "refresh_w", "t_read_s", "t_write_s"):
            assert getattr(p, f) == pytest.approx(getattr(ref, f),
                                                  rel=1e-12), (f, vi, pi)
        assert p.vdd_scale == SCALES[vi] and p.area_um2 == ref.area_um2


# ---------------------------------------------------------------------------
# vectorized shmoo / banks grids == scalar loops, bit-for-bit
# ---------------------------------------------------------------------------

DEMANDS = (Demand("slow", "L1", 1.0e8, 1.0e-6),
           Demand("fast", "L2", 2.5e9, 1.0e-5),
           Demand("hold", "L2", 2.0e8, 10.0),
           Demand("cap", "L2", 5.0e8, 1.0e-9, capacity_bits=1 << 20))


def test_feasible_grid_bit_for_bit(lat, scalar_points):
    mask = feasible_grid(lat.f_max_hz, lat.retention_s, lat.swing_ok,
                         lat.num_words,
                         [d.read_freq_hz for d in DEMANDS],
                         [d.lifetime_s for d in DEMANDS])
    assert mask.shape == (len(SCALES), len(CFGS), len(DEMANDS))
    for (vi, pi), ref in scalar_points.items():
        for di, d in enumerate(DEMANDS):
            assert bool(mask[vi, pi, di]) == dse.feasible(ref, d), \
                (vi, pi, d.name)


def test_feasible_grid_no_refresh_bit_for_bit(lat, scalar_points):
    mask = feasible_grid(lat.f_max_hz, lat.retention_s, lat.swing_ok,
                         lat.num_words,
                         [d.read_freq_hz for d in DEMANDS],
                         [d.lifetime_s for d in DEMANDS],
                         allow_refresh=False)
    for (vi, pi), ref in scalar_points.items():
        for di, d in enumerate(DEMANDS):
            assert bool(mask[vi, pi, di]) == \
                dse.feasible(ref, d, allow_refresh=False), (vi, pi, d.name)


def test_banks_needed_grid_bit_for_bit(lat, scalar_points):
    banks = banks_needed_grid(lat.f_max_hz, lat.retention_s, lat.swing_ok,
                              lat.bits, lat.num_words,
                              [d.read_freq_hz for d in DEMANDS],
                              [d.lifetime_s for d in DEMANDS],
                              [d.capacity_bits for d in DEMANDS],
                              max_banks=64)
    for (vi, pi), ref in scalar_points.items():
        for di, d in enumerate(DEMANDS):
            assert int(banks[vi, pi, di]) == banks_needed(
                ref, d, capacity_bits=d.capacity_bits, max_banks=64), \
                (vi, pi, d.name)


def test_shmoo_batch_equals_scalar_shmoo(lat):
    points = [lat.point(1, pi) for pi in range(len(CFGS))]
    assert shmoo_batch(points, list(DEMANDS)) == \
        dse.shmoo(points, list(DEMANDS))
    assert shmoo_batch(points, list(DEMANDS), allow_refresh=False) == \
        dse.shmoo(points, list(DEMANDS), allow_refresh=False)


# ---------------------------------------------------------------------------
# feasible / banks_needed edges (satellite)
# ---------------------------------------------------------------------------

def test_feasible_zero_retention_never_passes():
    dp = dse.evaluate(BankConfig(16, 16, "gc2t_nn"))
    dead = dataclasses.replace(dp, retention_s=0.0)
    d = Demand("d", "L1", dp.f_max_hz * 0.5, 1e-9)
    assert not dse.feasible(dead, d)                      # even w/ refresh
    assert not dse.feasible(dead, d, allow_refresh=False)
    neg = dataclasses.replace(dp, retention_s=-1.0)
    assert not dse.feasible(neg, d)
    # grid agrees
    m = feasible_grid([dead.f_max_hz], [0.0], [True], [dead.cfg.num_words],
                      [d.read_freq_hz], [d.lifetime_s])
    assert not m[0, 0]


def test_feasible_allow_refresh_false_requires_native_retention():
    dp = dse.evaluate(BankConfig(16, 16, "gc2t_nn"))
    d = Demand("d", "L2", dp.f_max_hz * 0.5, dp.retention_s * 10)
    assert dse.feasible(dp, d)                            # refresh saves it
    assert not dse.feasible(dp, d, allow_refresh=False)


def test_banks_needed_max_banks_clamping():
    dp = dse.evaluate(BankConfig(16, 16, "gc2t_nn"))
    d = Demand("big", "L2", dp.f_max_hz * 0.5, 1e-9,
               capacity_bits=100 * dp.cfg.bits)
    assert banks_needed(dp, d, capacity_bits=d.capacity_bits,
                        max_banks=1024) == 100
    # sentinel is max_banks + 1 whatever the clamp
    bad = dataclasses.replace(dp, swing_ok=False)
    for mb in (8, 64):
        assert banks_needed(bad, d, capacity_bits=d.capacity_bits,
                            max_banks=mb) == mb + 1
        g = banks_needed_grid([dp.f_max_hz], [dp.retention_s], [False],
                              [dp.cfg.bits], [dp.cfg.num_words],
                              [d.read_freq_hz], [d.lifetime_s],
                              [d.capacity_bits], max_banks=mb)
        assert int(g[0, 0]) == mb + 1


# ---------------------------------------------------------------------------
# profiler Profile.demands() unit sanity (satellite)
# ---------------------------------------------------------------------------

def test_profile_demands_units():
    prof = profile_arch("qwen2-0.5b", "decode_32k")
    ds = prof.demands()
    assert [d.level for d in ds] == ["L1", "L2"]
    for d in ds:
        # per-bank read rates: positive, finite, and nowhere near the
        # AGGREGATE chip feed (which is > 1e14 req/s) — i.e. actually
        # split over banks
        assert 0 < d.read_freq_hz < 1e11
        assert 0 < d.lifetime_s < 1e6
        assert d.name == f"{prof.arch}:{prof.shape}"
    # L2 is the shared level: per-bank rate exceeds L1's (Fig 9)
    assert ds[1].read_freq_hz > ds[0].read_freq_hz
    # L2 lifetime covers the kv session, L1 only a layer
    assert ds[1].lifetime_s >= ds[0].lifetime_s
    # frozen + hashable (keys session memoization)
    assert hash(prof) == hash(profile_arch("qwen2-0.5b", "decode_32k"))
    with pytest.raises(dataclasses.FrozenInstanceError):
        prof.l1_read_hz = 0.0


# ---------------------------------------------------------------------------
# CoDesignQuery end-to-end
# ---------------------------------------------------------------------------

SMALL = SweepQuery(cells=("gc2t_nn", "gc2t_osos"),
                   word_sizes=(16, 32), num_words=(16, 32))


def test_codesign_query_end_to_end_and_memoized():
    profs = (profile_arch("qwen2-0.5b", "decode_32k"),)
    s = Session()
    q = CoDesignQuery(profiles=profs, sweep=SMALL, vdd_scales=SCALES)
    rep = s.run(q)
    assert isinstance(rep, CoDesignReport)
    assert s.run(CoDesignQuery(profiles=profs, sweep=SMALL,
                               vdd_scales=SCALES)) is rep
    plan = rep[f"{profs[0].arch}:{profs[0].shape}"]
    assert set(plan["levels"]) == {"L1", "L2"}
    for d, (lvl, e) in zip(profs[0].demands(), plan["levels"].items()):
        assert e["read_freq_hz"] == d.read_freq_hz
        if not e["feasible"]:
            continue
        # the chosen (config, voltage) is macro-feasible per the SCALAR
        # reference at that operating point
        dp = dse.evaluate(BankConfig(
            e["bank"]["word_size"], e["bank"]["num_words"],
            cell=e["bank"]["cell"], wwlls=e["bank"]["wwlls"],
            write_vt=e["bank"]["write_vt"]), vdd_scale=e["vdd_scale"])
        n = banks_needed(dp, d, capacity_bits=d.capacity_bits)
        assert e["banks_needed"] == n <= 1024
        assert e["macro_capacity_bits"] == n * dp.cfg.bits
        assert e["energy_per_inference_j"] > 0
        assert e["vdd_v"] == pytest.approx(SYN40.vdd * e["vdd_scale"])
    d = rep.as_dict()
    assert d["n_workloads"] == 1 and d["vdd_scales"] == list(SCALES)


def test_codesign_objective_and_validation():
    profs = (profile_arch("qwen2-0.5b", "decode_32k"),)
    s = Session()
    e_rep = s.run(CoDesignQuery(profiles=profs, sweep=SMALL,
                                vdd_scales=SCALES, objective="energy"))
    a_rep = s.run(CoDesignQuery(profiles=profs, sweep=SMALL,
                                vdd_scales=SCALES, objective="area"))
    for rep in (e_rep, a_rep):
        for p in rep:
            for e in p["levels"].values():
                assert e["feasible"] == ("bank" in e)
    # area objective can't pick a larger macro than the energy objective
    ep = e_rep.plans[0]
    apn = a_rep.plans[0]
    if ep["feasible"] and apn["feasible"]:
        assert apn["total_area_um2"] <= ep["total_area_um2"] + 1e-9
    with pytest.raises(ValueError):
        s.run(CoDesignQuery(profiles=profs, sweep=SMALL,
                            objective="speed"))
    with pytest.raises(ValueError):
        s.run(CoDesignQuery(profiles=(), sweep=SMALL))
    # co-design is analytic-tier only: transient sweeps are rejected,
    # not silently downgraded
    with pytest.raises(ValueError):
        s.run(CoDesignQuery(profiles=profs, sweep=dataclasses.replace(
            SMALL, fidelity="transient")))
    # sweeps differing only in evaluation knobs share one cached lattice
    assert s.vdd_lattice(SMALL, SCALES) is s.vdd_lattice(
        dataclasses.replace(SMALL, batched=False, sim_steps=77), SCALES)


def test_codesign_infeasible_demand_reported():
    """A profile with an impossible L2 demand still yields a plan row,
    flagged infeasible."""
    base = profile_arch("qwen2-0.5b", "decode_32k")
    hard = dataclasses.replace(base, l2_read_hz=1e15, kv_lifetime_s=1e6,
                               act_lifetime_s=1e6)
    rep = Session().run(CoDesignQuery(profiles=(hard,), sweep=SMALL,
                                      vdd_scales=SCALES, max_banks=4))
    plan = rep.plans[0]
    assert not plan["feasible"] and not rep.all_feasible
    assert not plan["levels"]["L2"]["feasible"]
    assert "bank" not in plan["levels"]["L2"]
