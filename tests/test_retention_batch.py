"""The batched retention integral (`retention.t_ret_rows`, padded and
counted by `dse_batch._retention`) equals the scalar reference
`retention.analyze(...).t_ret_s` bit for bit: every write-cell variant of
the paper's space, both WWL settings, seeded rungs across the operating
range, at the row buckets a co-design cube (256) and a transient
campaign's points node (8) use."""
import numpy as np
import pytest

from repro.core import dse_batch, retention, trace
from repro.core.bank import build_bank
from repro.core.cells import CELLS, with_write_vt
from repro.core.dse import lattice_configs
from repro.core.techfile import SYN40

# the paper's gain-cell topologies with every write-VT flavor each admits
VARIANTS = [(c, vt) for c in ("gc2t_nn", "gc2t_np", "gc3t")
            for vt in (None, "nmos_lvt", "nmos_hvt")] + \
           [(c, vt) for c in ("gc2t_osos", "gc2t_hyb")
            for vt in (None, "os_n_hvt")]
RUNGS = sorted(np.random.default_rng(2718281829).uniform(
    0.7, 1.225, 16).tolist()) + [1.0]
WWL_BOOST = 0.55


def _cell(name, vt):
    return CELLS[name] if vt is None else with_write_vt(CELLS[name], vt)


def _cases(variants):
    return [(_cell(*v), ls, s) for v in variants for ls in (False, True)
            for s in RUNGS]


def _row(cell, wwlls, scale):
    return retention.integral_row(cell, SYN40, wwlls=wwlls,
                                  wwl_boost=WWL_BOOST, vdd_scale=scale)


def _analyze(cell, wwlls, scale):
    return retention.analyze(cell, SYN40, wwlls=wwlls, wwl_boost=WWL_BOOST,
                             vdd_scale=scale).t_ret_s


def _batched(rows):
    with trace.recording() as rec:
        out = dse_batch._retention(rows)
    return out, rec.counters


@pytest.fixture(scope="module")
def big():
    """All 442 (variant, wwlls, rung) cases in two 256-lane batches (221
    rows each), beside the scalar reference of each."""
    cases = _cases(VARIANTS)
    ref = [_analyze(*c) for c in cases]
    rows = [_row(*c) for c in cases]
    half = len(rows) // 2
    out = []
    for part in (rows[:half], rows[half:]):
        t, counts = _batched(part)
        assert counts["dse_batch.retention_lanes"] == 256
        assert counts["dse_batch.retention_rows"] == len(part)
        out += t
    return {c: (r, t) for c, r, t in zip(cases, ref, out)}


@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: f"{v[0]}:{v[1]}")
def test_bucket_256_is_analyze_bitwise(big, variant):
    for case in _cases([variant]):
        ref, got = big[case]
        assert got == ref, case


@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: f"{v[0]}:{v[1]}")
def test_bucket_8_is_analyze_bitwise(big, variant):
    # 5 rows pad to 8 by repeating the last: a transient campaign's shape
    cases = _cases([variant])[::7]
    got, counts = _batched([_row(*c) for c in cases])
    assert (counts["dse_batch.retention_rows"],
            counts["dse_batch.retention_lanes"]) == (len(cases), 8)
    assert got == [big[c][0] for c in cases]


def test_rows_past_the_margin_read_zero_in_place(big):
    zero = [c for c, (ref, _) in big.items() if ref == 0.0]
    live = [c for c, (ref, _) in big.items() if ref > 0.0]
    assert zero and live
    for c in zero:
        v0, v_m = _row(*c)[-2:]
        assert v0 <= v_m and big[c][1] == 0.0
    # a zero row between live ones leaves its neighbours as they were
    cases = [live[0], zero[0], live[-1]]
    got, _ = _batched([_row(*c) for c in cases])
    assert got == [big[c][0] for c in cases]


def test_one_row_fallback_is_analyze_bitwise():
    cfg = lattice_configs(cells=("gc2t_osos",), word_sizes=(8,),
                          num_words=(16,), wwlls=(True,))[0]
    bank = build_bank(cfg)
    with trace.recording() as rec:
        c = dse_batch._group_constants(cfg, bank, 0.8765)
    assert c["t_ret"] == _analyze(bank.cell, True, 0.8765)
    assert (rec.counters["dse_batch.retention_rows"],
            rec.counters["dse_batch.retention_lanes"]) == (1, 8)


def test_sram_groups_make_no_rows():
    scales = (0.7771, 0.8882)
    sram = lattice_configs(cells=("sram6t",), word_sizes=(8, 16),
                           num_words=(16,), wwlls=(False,))
    with trace.recording() as rec:
        lat = dse_batch.evaluate_vdd_lattice(sram, scales)
    assert not rec.named("dse_batch.retention")
    assert "dse_batch.retention_rows" not in rec.counters
    assert len(rec.named("dse_batch.group_constants")) == len(scales)
    assert np.isinf(lat.retention_s).all()

    mixed = sram + lattice_configs(cells=("gc2t_nn",), word_sizes=(8,),
                                   num_words=(16,), wwlls=(False, True))
    scales = (0.7772, 0.8883)
    with trace.recording() as rec:
        lat = dse_batch.evaluate_vdd_lattice(mixed, scales)
    assert (rec.counters["dse_batch.retention_rows"],
            rec.counters["dse_batch.retention_lanes"]) == (4, 8)
    assert len(rec.named("dse_batch.retention")) == 1
    assert np.isinf(lat.retention_s[:, :len(sram)]).all()
    for vi, s in enumerate(scales):
        for pi, cfg in enumerate(mixed[len(sram):], start=len(sram)):
            assert lat.retention_s[vi, pi] == retention.analyze(
                build_bank(cfg).cell, cfg.tech, wwlls=cfg.wwlls,
                wwl_boost=cfg.wwl_boost, vdd_scale=s).t_ret_s
