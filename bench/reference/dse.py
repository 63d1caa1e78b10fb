"""Scalar design-point evaluation and the workload-matching rule.

Frozen copy of the program's scalar reference (`dse.evaluate`,
`dse.feasible`, `Demand`). The benchmark holds the program's batched
lattice evaluators to it; the callers run it on the CPU device
(`bench.reference.on_cpu`).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List

from bench.reference import power as power_mod
from bench.reference import retention as ret_mod
from bench.reference import timing as timing_mod
from bench.reference.bank import BankConfig, build_bank
from bench.reference.cells import CELLS
from bench.reference.techfile import SYN40


@dataclass
class DesignPoint:
    """One evaluated bank at one operating point (units as the program's
    DesignPoint: um^2, Hz, bits/s, W, s)."""
    cfg: BankConfig
    area_um2: float
    f_max_hz: float
    read_bw_bps: float
    write_bw_bps: float
    eff_bw_bps: float
    leakage_w: float
    refresh_w: float
    retention_s: float
    swing_ok: bool
    t_read_s: float = 0.0
    t_write_s: float = 0.0
    vdd_scale: float = 1.0
    e_read_j: float = 0.0          # dynamic joules per word access
    e_write_j: float = 0.0

    @property
    def standby_w(self) -> float:
        return self.leakage_w + self.refresh_w


# retention depends on the cell topology, the deck and the voltage only:
# memoized per (cell, write flavor, deck, WWL boost, voltage); each value
# keeps its deck alive so the id() in the key cannot be reused
_RETENTION = {}


def evaluate(cfg: BankConfig, vdd_scale: float = 1.0) -> DesignPoint:
    """Scalar evaluation of one config at one operating voltage."""
    bank = build_bank(cfg)
    t = timing_mod.analyze(bank, vdd_scale=vdd_scale)
    if bank.is_gc:
        key = (cfg.cell, cfg.write_vt, id(cfg.tech), cfg.wwlls,
               cfg.wwl_boost, float(vdd_scale))
        if key not in _RETENTION:
            _RETENTION[key] = (ret_mod.analyze(
                bank.cell, cfg.tech, wwlls=cfg.wwlls,
                wwl_boost=cfg.wwl_boost, vdd_scale=vdd_scale).t_ret_s,
                cfg.tech)
        ret = _RETENTION[key][0]
    else:
        ret = float("inf")
    p = power_mod.analyze(bank, t.f_max_hz, t_ret_s=ret if bank.is_gc else None,
                          vdd_scale=vdd_scale)
    ws = cfg.word_size
    if bank.is_gc:
        # dual port: concurrent read + write at f_max
        rbw = t.f_max_hz * ws
        wbw = t.f_max_hz * ws
        ebw = rbw + wbw
    else:
        # shared port: effective bandwidth halves
        rbw = t.f_max_hz * ws / 2
        wbw = t.f_max_hz * ws / 2
        ebw = rbw + wbw
    return DesignPoint(cfg, bank.area_um2, t.f_max_hz, rbw, wbw, ebw,
                       p.leakage_w, p.refresh_w, ret, t.read_swing_ok,
                       t.t_read_s, t.t_write_s, vdd_scale, p.e_read_j,
                       p.e_write_j)


def lattice_configs(cells, word_sizes, num_words, write_vts, wwlls,
                    tech=SYN40) -> List[BankConfig]:
    """Expand a config lattice, skipping write-VT flavors that don't match
    the cell's device family (Si VT overrides on OS cells and vice versa)."""
    out = []
    for c, ws, nw, vt, ls in itertools.product(cells, word_sizes, num_words,
                                               write_vts, wwlls):
        wf = getattr(CELLS[c], "write_flavor", None)
        if vt is not None and (wf is None
                               or wf.startswith("os") != vt.startswith("os")):
            continue
        out.append(BankConfig(ws, nw, cell=c, write_vt=vt, wwlls=ls,
                              tech=tech))
    return out


@dataclass(frozen=True)
class Demand:
    """One workload's cache demand: per-instance read Hz, lifetime s,
    capacity bits (0 = don't size for capacity)."""
    name: str
    level: str
    read_freq_hz: float
    lifetime_s: float
    capacity_bits: int = 0


def feasible(dp: DesignPoint, d: Demand, *, allow_refresh=True) -> bool:
    """Meets the read frequency and either retains for the lifetime or
    refreshes at < 10% of f_max (retention <= 0 never passes)."""
    if not dp.swing_ok or dp.f_max_hz < d.read_freq_hz:
        return False
    if dp.retention_s >= d.lifetime_s:
        return True
    if not allow_refresh or dp.retention_s <= 0:
        return False
    refresh_rate = dp.cfg.num_words / dp.retention_s
    return refresh_rate < 0.1 * dp.f_max_hz
