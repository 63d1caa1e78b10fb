"""Property tests of the gradient-based optimizer contract
(`repro.optim.dse_opt`) and of the physics monotonicities the penalty
formulation leans on, via the hypothesis shim in tests/_hyp.py.

The contract under test (dse_opt.optimize):
  * if `met`, the returned point satisfies the EXACT `dse.feasible`
    rule — independently re-derived here through the scalar reference,
    not read back from the result;
  * the exact objective value never regresses vs the grid-seed rung
    (never-regress fallback);
  * reported knob values stay inside the projection bounds.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from repro.api.queries import OptimizeQuery
from repro.core import dse
from repro.core.bank import BankConfig
from repro.core.dse_grad import evaluate_grad_fn
from repro.core.multibank import banks_needed
from repro.optim import dse_opt

from tests._hyp import given, settings, strategies as st

CFG = BankConfig(32, 64, cell="gc2t_np")


def _exact_feasible(cfg, outputs, target_freq_hz, target_ret_s,
                    allow_refresh=True):
    """The dse.feasible rule, re-derived from quantized outputs."""
    if outputs["swing_margin_a"] <= 0 or \
            outputs["f_max_hz"] < target_freq_hz:
        return False
    if outputs["retention_s"] >= target_ret_s:
        return True
    if not allow_refresh or outputs["retention_s"] <= 0:
        return False
    return cfg.num_words / outputs["retention_s"] < \
        0.1 * outputs["f_max_hz"]


# ---------------------------------------------------------------------------
# optimizer contract
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.floats(min_value=5e7, max_value=6e8),
       st.floats(min_value=1e-6, max_value=2e-4))
def test_optimizer_contract_feasible_and_never_regresses(freq, ret):
    r = dse_opt.optimize(CFG, target_freq_hz=freq, target_ret_s=ret,
                         steps=8, seed_vdd_scales=(0.7, 1.0))
    # knob values respect the projection bounds
    for k, v in r.knobs.items():
        lo, hi = dse_opt.DEFAULT_BOUNDS[k]
        assert lo - 1e-12 <= v <= hi + 1e-12
    # never-regress: exact objective <= the grid seed's (when both met,
    # or both unmet); a met result never replaces a met seed with worse
    if r.met == r.seed_met:
        assert r.objective_value <= r.seed_objective_value * (1 + 1e-12)
    if r.seed_met:
        assert r.met
    # independent feasibility re-check through the quantized evaluator
    with enable_x64():
        fn = evaluate_grad_fn(CFG, quantized=True)
        kn = {k: jnp.asarray([v], dtype=jnp.float64)
              for k, v in r.knobs.items()}
        out = {k: float(v[0]) for k, v in fn(kn).items()}
    assert _exact_feasible(CFG, out, freq, ret) == r.met
    if r.met:
        assert out[r.objective] == pytest.approx(r.objective_value,
                                                 rel=1e-9)


@pytest.mark.slow
def test_multi_knob_beats_single_knob_run():
    """Width/wire knobs strictly enlarge the search space; at matched
    settings the multi-knob optimum must be at least as good."""
    kw = dict(target_freq_hz=5e8, target_ret_s=5e-5, steps=40)
    r1 = dse_opt.optimize(CFG, knobs=("vdd_scale",), **kw)
    r4 = dse_opt.optimize(CFG, knobs=("vdd_scale", "w_read_scale",
                                      "w_write_scale", "bl_wire_scale"),
                          **kw)
    assert r1.met and r4.met
    assert r4.objective_value <= r1.objective_value * (1 + 1e-9)


def test_impossible_demand_reports_unmet_gracefully():
    r = dse_opt.optimize(CFG, target_freq_hz=1e14, target_ret_s=1e3,
                         steps=4, seed_vdd_scales=(0.85, 1.0))
    assert not r.met and not r.seed_met
    assert np.isfinite(r.objective_value)


# ---------------------------------------------------------------------------
# physics monotonicities the penalty relies on
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.floats(min_value=0.62, max_value=1.2),
       st.floats(min_value=0.02, max_value=0.25))
def test_retention_lengthens_as_vdd_drops_gc2t_np(vdd, step):
    """PMOS-write gc2t: lower rails lower the written level toward the
    subthreshold leak floor -> retention is monotone non-increasing in
    vdd over the operating window."""
    lo = dse.evaluate(CFG, vdd_scale=vdd)
    hi = dse.evaluate(CFG, vdd_scale=min(vdd + step, 1.25))
    assert lo.retention_s >= hi.retention_s * (1 - 1e-9)


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=40),
       st.integers(min_value=1, max_value=30))
def test_banks_needed_non_increasing_in_bank_capacity(kbits, extra):
    """A macro built from bigger banks never needs MORE of them for the
    same demand."""
    small = dse.evaluate(BankConfig(32, 64, cell="gc2t_nn"))
    big = dse.evaluate(BankConfig(32, 128, cell="gc2t_nn"))
    d = dse.Demand("t", "L1", small.f_max_hz * 1.7, 1e-9)
    cap = kbits * 1024 + extra
    n_small = banks_needed(small, d, capacity_bits=cap)
    n_big = banks_needed(big, d, capacity_bits=cap)
    assert n_big <= n_small


# ---------------------------------------------------------------------------
# OptimizeQuery construction-time validation
# ---------------------------------------------------------------------------

def test_optimize_query_validates_at_construction():
    OptimizeQuery()                                    # defaults are valid
    with pytest.raises(ValueError, match="unknown cell"):
        OptimizeQuery(cell="nope")
    with pytest.raises(ValueError, match="gain cells"):
        OptimizeQuery(cell="sram6t")
    with pytest.raises(ValueError, match="unknown knobs"):
        OptimizeQuery(knobs=("vdd_scale", "not_a_knob"))
    with pytest.raises(ValueError, match=">= 1 knob"):
        OptimizeQuery(knobs=())
    with pytest.raises(ValueError, match="unknown objective"):
        OptimizeQuery(objective="area_um2_but_wrong")
    with pytest.raises(ValueError, match="steps/lr"):
        OptimizeQuery(steps=0)
    with pytest.raises(ValueError, match="targets must be positive"):
        OptimizeQuery(target_ret_s=-1.0)
    with pytest.raises(ValueError, match="seed_vdd_scales"):
        OptimizeQuery(seed_vdd_scales=())
    with pytest.raises(ValueError, match="wrong device"):
        OptimizeQuery(cell="gc2t_nn", write_vt="oshvt")
    # lists normalize to tuples so the query stays hashable
    q = OptimizeQuery(knobs=["vdd_scale"], seed_vdd_scales=[0.8, 1.0])
    assert isinstance(q.knobs, tuple)
    assert hash(q) == hash(OptimizeQuery(knobs=("vdd_scale",),
                                         seed_vdd_scales=(0.8, 1.0)))
