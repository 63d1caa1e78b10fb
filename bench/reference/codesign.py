"""The co-design check's reference: a `CoDesignReport` recomputed whole
by the plain scalar reference (`bench.reference`: `dse.evaluate` and
`multibank.banks_needed`, on the CPU device, with the demand profiles
given in the configuration dict it is handed), every (rung, point) entry
of the cube, every metric, and every level's chosen design (feasible,
bank count, energy per inference), which no point of the whole lattice
at any rung may beat; an unplannable level must have no plannable point.
Shared by the co-design and the serving cells' checks.
"""
from __future__ import annotations

import math

METRICS = ("f_max_hz", "t_read_s", "t_write_s", "retention_s", "leakage_w",
           "refresh_w")
CUBE_METRICS = METRICS + ("e_read_j", "e_write_j")


def rel_err(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(a), abs(b))


class Reference:
    """Scalar reference points, memoized per (config, deck voltage)."""

    def __init__(self, config: dict):
        from bench.reference import dse, multibank, techfile
        from bench.reference.bank import BankConfig
        self.dse, self.mb, self.BankConfig = dse, multibank, BankConfig
        self.tech = techfile.SYN40
        self.config = config
        self._pts = {}

    def point(self, cell, ws, nw, vt, ls, vdd_scale=1.0):
        key = (cell, int(ws), int(nw), vt, bool(ls), float(vdd_scale))
        if key not in self._pts:
            cfg = self.BankConfig(int(ws), int(nw), cell=cell, write_vt=vt,
                                  wwlls=bool(ls), tech=self.tech)
            self._pts[key] = self.dse.evaluate(cfg, float(vdd_scale))
        return self._pts[key]

    def demands(self, profile_names):
        out = []
        for name in profile_names:
            p = self.config["profiles"][name]
            out.append((self.dse.Demand(name, "L1", p["l1_read_hz"],
                                        p["act_lifetime_s"]),
                        p["step_time_s"]))
            out.append((self.dse.Demand(
                name, "L2", p["l2_read_hz"],
                max(p["kv_lifetime_s"], p["act_lifetime_s"])),
                p["step_time_s"]))
        return out

    def banks(self, dp, d):
        return self.mb.banks_needed(
            dp, d, capacity_bits=d.capacity_bits,
            max_banks=self.config["max_banks"],
            allow_refresh=self.config["allow_refresh"])

    def energy(self, dp, d, step, n):
        return d.read_freq_hz * step * dp.e_read_j + n * dp.standby_w * step


def _cfg_tuple(c):
    if isinstance(c, dict):
        return (c["cell"], c["word_size"], c["num_words"], c["write_vt"],
                c["wwlls"])
    return (c.cell, c.word_size, c.num_words, c.write_vt, c.wwlls)


def worst(gaps) -> float:
    return max(gaps, default=0.0)


def _values(got: dict, ref, names) -> float:
    g = [rel_err(float(got[m]), float(getattr(ref, m))) for m in names]
    if bool(got["swing_ok"]) != bool(ref.swing_ok):
        g.append(math.inf)
    return worst(g)


def check_plans(ref: Reference, plans, profile_names, points) -> float:
    """Chosen designs of a co-design answer: each level's bank, bank
    count and energy per inference, and that no reference point of the
    whole lattice (`points`, every rung) plans for less energy; an
    unplannable level must have no plannable point."""
    gaps = []
    demands = ref.demands(profile_names)
    levels = [(plan, lvl) for plan in plans for lvl in ("L1", "L2")]
    if len(levels) != len(demands):
        return math.inf
    for (plan, lvl), (d, step) in zip(levels, demands):
        e = plan["levels"][lvl]
        if (e["read_freq_hz"], e["lifetime_s"]) != (d.read_freq_hz,
                                                     d.lifetime_s):
            gaps.append(math.inf)
            continue
        plannable = [(dp, ref.banks(dp, d)) for dp in points]
        plannable = [(dp, n) for dp, n in plannable
                     if n <= ref.config["max_banks"]]
        if not e["feasible"]:
            gaps.append(math.inf if plannable else 0.0)
            continue
        dp = ref.point(*_cfg_tuple(e["bank"]), e["vdd_scale"])
        n = ref.banks(dp, d)
        if n != e["banks_needed"]:
            gaps.append(math.inf)
            continue
        energy = ref.energy(dp, d, step, n)
        gaps.append(rel_err(e["energy_per_inference_j"], energy))
        gaps.append(_values(e["bank"], dp, METRICS))
        best = min((ref.energy(s, d, step, k) for s, k in plannable),
                   default=math.inf)
        if best < energy * (1.0 - 1e-9):
            gaps.append(math.inf)
    return worst(gaps)


def report_gaps(ref: Reference, report, req) -> list:
    """A `CoDesignReport`'s gaps: its lattice must be the request's;
    every (rung, point) entry of its cube, every metric, against the
    reference; and its chosen designs (`check_plans`) over that whole
    lattice."""
    from bench.lib.traffic import lattice
    sw = req["sweep"]
    cfgs = lattice(ref.config["space"], sw["cells"], sw["word_sizes"],
                   sw["num_words"])
    rungs = [float(v) for v in req["vdd_scales"]]
    lat = report.lattice
    if [_cfg_tuple(c) for c in lat.cfgs] != cfgs \
            or len(lat.vdd_scales) != len(rungs):
        return [math.inf]
    gaps = [rel_err(float(a), b) for a, b in zip(lat.vdd_scales, rungs)]
    points = []
    for v, rung in enumerate(rungs):
        for p, c in enumerate(cfgs):
            dp = ref.point(*c, rung)
            got = {m: getattr(lat, m)[v, p] for m in CUBE_METRICS}
            got["swing_ok"] = lat.swing_ok[v, p]
            gaps.append(_values(got, dp, CUBE_METRICS))
            points.append(dp)
    names = [f"{p['arch']}:{p['shape']}" for p in req["profiles"]]
    gaps.append(check_plans(ref, report.plans, names, points))
    return gaps
