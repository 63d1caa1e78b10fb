"""Interleaved multibank macro sizing: frozen copy of the program's scalar
`multibank.banks_needed` and `compose_multibank`."""
from __future__ import annotations

import math
from dataclasses import dataclass

from bench.reference import dse

XBAR_OVERHEAD = 0.06     # crossbar/arbiter area per bank (fraction)
XBAR_DELAY_S = 35e-12    # one crossbar hop on the read path


@dataclass
class MultiBankPoint:
    n_banks: int
    bank: dse.DesignPoint
    area_um2: float
    f_max_hz: float
    eff_bw_bps: float
    capacity_bits: int
    leakage_w: float
    refresh_w: float
    retention_s: float


def compose_multibank(dp: dse.DesignPoint, n_banks: int) -> MultiBankPoint:
    t_read = dp.t_read_s + XBAR_DELAY_S
    f = 1.0 / max(t_read, dp.t_write_s)
    area = n_banks * dp.area_um2 * (1.0 + XBAR_OVERHEAD)
    return MultiBankPoint(
        n_banks=n_banks, bank=dp, area_um2=area, f_max_hz=f,
        eff_bw_bps=n_banks * dp.eff_bw_bps * (f / dp.f_max_hz),
        capacity_bits=n_banks * dp.cfg.bits,
        leakage_w=n_banks * dp.leakage_w,
        refresh_w=n_banks * dp.refresh_w,
        retention_s=dp.retention_s)


def banks_needed(dp: dse.DesignPoint, demand: dse.Demand,
                 capacity_bits: int = 0, max_banks: int = 1024, *,
                 allow_refresh: bool = True) -> int:
    """Smallest interleaved bank count covering the aggregate read rate and
    the capacity; `max_banks + 1` when the per-bank retention/refresh rule,
    the swing or f_max fails."""
    if not dp.swing_ok or dp.f_max_hz <= 0:
        return max_banks + 1
    n_freq = math.ceil(demand.read_freq_hz / dp.f_max_hz)
    n_cap = math.ceil(capacity_bits / dp.cfg.bits) if capacity_bits else 1
    n = max(1, n_freq, n_cap)
    if not dse.feasible(dp, dse.Demand(demand.name, demand.level,
                                       min(demand.read_freq_hz, dp.f_max_hz),
                                       demand.lifetime_s),
                        allow_refresh=allow_refresh):
        return max_banks + 1
    return n
