"""Batched (struct-of-arrays) lattice evaluator for design-space sweeps,
now with an OPERATING-VOLTAGE axis.

`dse.evaluate` is the scalar reference: per config it rebuilds the bank,
re-integrates retention, and issues a dozen single-element jnp dispatches
— fine for one point, slow for a lattice. This module evaluates a whole
lattice at once:

  1. group configs by cell topology (cell, write-VT override, WWLLS,
     WWL boost, tech) so array shapes stay static per group;
  2. compute the group-constant electricals ONCE per (group, vdd_scale)
     with the SAME scalar calls `dse.evaluate` makes (read/leak currents
     at the written SN level, the retention integral, the write SN
     settle);
  3. `jax.vmap` the per-point analytic timing + power algebra across the
     group's struct-of-arrays (rows, wire RC, word size, ...) in float64
     (jax.enable_x64), reusing the formula kernels from
     `repro.core.timing` — and vmap AGAIN over the vdd axis, whose
     per-scale constants ride in as mapped operands (geometry and wire
     RC are voltage-independent, so the structural arrays are shared
     across the whole voltage ladder).

Because the group constants come from the identical scalar calls and the
per-point algebra is the identical float64 expression tree, batched
results match `dse.evaluate` bit-for-bit — asserted in
tests/test_api.py, tests/test_codesign.py and benchmarks.

On top of the (vdd x lattice) tables this module vectorizes the
workload-matching layer that `dse.feasible` / `multibank.banks_needed`
define scalarly: `feasible_grid`, `banks_needed_grid` and
`codesign_metrics` evaluate (vdd x lattice x demand) grids in one device
program each — the engine behind `repro.api.CoDesignQuery`.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from repro.core import bank as bank_mod
from repro.core import retention as ret_mod
from repro.core import timing as timing_mod
from repro.core.bank import BankConfig, build_bank
from repro.core.dse import DesignPoint
from repro.core.host import on_host
from repro.core.power import PERIPH_LEAK_W_PER_UM2
from repro.core.spice import devices as dv
from repro.core.techfile import with_vdd_scale


def pow2_bucket(n: int, floor: int = 4) -> int:
    """Smallest power-of-two >= n, floored at `floor` — the shared
    batch-bucketing rule: jitted programs specialize on array shapes,
    so batches of varying size land in a handful of buckets and reuse
    the compiled program. Shared with `core.spice.char_batch`."""
    return max(floor, 1 << max(0, n - 1).bit_length())


def pad_bucket(a: np.ndarray, bucket: int) -> np.ndarray:
    """Edge-repeat `a` along axis 0 up to `bucket` rows (no-op when
    already there). Padded rows are dropped by the caller's slice-back,
    so bucketing is value-transparent."""
    n = a.shape[0]
    if bucket <= n:
        return a
    return np.concatenate([a, np.repeat(a[-1:], bucket - n, axis=0)],
                          axis=0)


def topology_key(cfg: BankConfig) -> tuple:
    """Cell-topology grouping key: configs sharing it have identical cell
    electricals and (for the transient pipeline) identical critical-path
    netlist STRUCTURE — only wire/structural values differ. Shared with
    `repro.core.spice.char_batch`."""
    return (cfg.cell, cfg.write_vt, cfg.wwlls, cfg.wwl_boost, id(cfg.tech))


def group_by_topology(cfgs: Sequence[BankConfig]) -> Dict[tuple, List[int]]:
    """Indices of `cfgs` grouped by topology_key, preserving order."""
    groups: Dict[tuple, List[int]] = {}
    for i, cfg in enumerate(cfgs):
        groups.setdefault(topology_key(cfg), []).append(i)
    return groups


def evaluate_batch(cfgs: Sequence[BankConfig],
                   vdd_scale: float = 1.0) -> List[DesignPoint]:
    """Evaluate every config (at one operating voltage); returns
    DesignPoints in input order. Thin wrapper over the one-row
    (vdd x lattice) table so there is a single materialization path."""
    lat = evaluate_vdd_lattice(cfgs, (float(vdd_scale),))
    return [lat.point(0, i) for i in range(len(lat.cfgs))]


def _group_constants(cfg0: BankConfig, bank0, vdd_scale: float = 1.0) -> dict:
    """Electricals that depend only on (cell topology, operating voltage)
    — computed with the same scalar calls the reference `dse.evaluate`
    path makes at that vdd_scale, on the host CPU device like it."""
    with on_host():
        return _group_constants_host(cfg0, bank0, vdd_scale)


def _group_constants_host(cfg0: BankConfig, bank0, vdd_scale: float) -> dict:
    tech = with_vdd_scale(cfg0.tech, vdd_scale)
    cell = bank0.cell
    if bank0.is_gc:
        bit = 0 if cell.read_on_sn_low else 1
        v_sn = cell.v_sn_written(tech, bit, wwlls=cfg0.wwlls,
                                 wwl_boost=cfg0.wwl_boost)
        v_rbl0 = 0.0 if cell.predischarge else tech.vdd
        swing = tech.v_sense_se
        v_rbl_mid = v_rbl0 + (0.5 * swing if cell.predischarge
                              else -0.5 * swing)
        i_cell = cell.i_read(tech, v_sn, v_rbl_mid)
        off_sn = cell.v_sn_written(tech, 1 if cell.read_on_sn_low else 0)
        i_leak1 = cell.i_leak_rbl(tech, off_sn)
        t_ret = ret_mod.analyze(cell, tech, wwlls=cfg0.wwlls,
                                wwl_boost=cfg0.wwl_boost).t_ret_s
        wf = cell.wf(tech)
        v_gate = tech.vdd + (cfg0.wwl_boost if cfg0.wwlls else 0.0)
        i_on = abs(float(dv.channel_current(
            wf, cell.w_write, cell.l_write, v_gate, tech.vdd,
            tech.vdd * 0.45)))
        return dict(i_cell=i_cell, i_leak1=i_leak1, dv_sense=swing,
                    t_ret=t_ret, vdd=tech.vdd,
                    t_sn=cell.sn_cap(tech) * 0.9 * tech.vdd
                    / max(i_on, 1e-12),
                    cell_leak_per_bit=0.0)
    return dict(i_cell=cell.i_read(tech), i_leak1=0.0,
                dv_sense=tech.v_sense_diff, t_ret=float("inf"), t_sn=0.0,
                vdd=tech.vdd, cell_leak_per_bit=cell.cell_leakage(tech))


# deterministic pure functions of (cell topology, deck, operating
# voltage): safe to memoize process-wide. Values keep the deck alive so
# the id() in the topology key cannot be recycled. This is what makes a
# warm co-design cube cheap — repeated queries over the same cell
# library re-derive NO retention integrals. Scope caveat: keying by
# deck IDENTITY means equal-but-distinct TechFile objects don't share
# entries (and pin their deck for the process lifetime) — reuse one
# TechFile per deck, as Session does, rather than constructing fresh
# ones per query.
_CONSTS_CACHE: Dict[tuple, tuple] = {}


def _group_constants_cached(cfg0: BankConfig, bank0,
                            vdd_scale: float) -> dict:
    key = topology_key(cfg0) + (float(vdd_scale),)
    hit = _CONSTS_CACHE.get(key)
    if hit is None:
        _CONSTS_CACHE[key] = hit = (
            _group_constants(cfg0, bank0, vdd_scale), cfg0.tech)
    return hit[0]


@lru_cache(maxsize=None)
def _group_kernel(is_gc: bool, wwlls: bool, dv_sense: float, sa_s: float,
                  dff_s: float, unit0: float):
    """Jitted nested-vmap timing/power kernel for one (topology-shape,
    periphery-constant) family: outer vmap over the voltage axis (the
    per-voltage electrical constants ride as mapped operands), inner
    vmap over the lattice's structural arrays. Compiled once per
    (family, array shape); must be TRACED under enable_x64 (callers hold
    the context), so python-float constants promote to float64."""
    fo4 = timing_mod.FO4_S
    margin, cap = timing_mod.CHAIN_MARGIN, float(timing_mod.CHAIN_MAX_STAGES)
    growth = timing_mod.CHAIN_UNIT_GROWTH

    def point(vdd, i_cell_v, i_leak1_v, t_ret_v, t_sn_v, clpb_v,
              rows_i, r_wl, c_wl, r_bl, c_bl, t_dec_i, ws_i, bits_i,
              periph_i, mux_i):
        # -- read path (timing.analyze, vectorized)
        t_wl = timing_mod.elmore_delay(timing_mod.WL_DRIVER_R_OHM, r_wl, c_wl)
        c_bl_read = c_bl + timing_mod.SA_INPUT_C_F
        leak = (rows_i - 1.0) * i_leak1_v
        i_net = jnp.maximum(i_cell_v - leak, 1e-12)
        t_cell = timing_mod.cell_swing_time(dv_sense, c_bl_read, i_net, r_bl)
        analog = t_wl + t_cell + jnp.where(mux_i, 2 * fo4, 0.0) + sa_s
        if is_gc:
            analog = analog + timing_mod.REF_SETTLE_S
        # delay-chain unit coarsening: unit0 * growth**k, smallest k with
        # analog*margin/unit <= cap (exact while-loop semantics; the log
        # estimate is corrected one step either way for float edges)
        a_m = analog * margin
        k = jnp.maximum(jnp.ceil(jnp.log(a_m / (unit0 * cap))
                                 / jnp.log(growth)), 0.0)
        k = jnp.where(a_m / (unit0 * growth ** k) > cap, k + 1.0, k)
        k = jnp.where((k > 0.0) & (a_m / (unit0 * growth ** (k - 1.0))
                                   <= cap), k - 1.0, k)
        unit = unit0 * growth ** k
        t_chain = jnp.ceil(a_m / unit) * unit
        t_read = dff_s + t_dec_i + t_chain + dff_s
        # -- write path (timing.write_time, vectorized)
        t_bl = timing_mod.elmore_delay(timing_mod.WBL_DRIVER_R_OHM, r_bl,
                                       c_bl)
        t_wr_core = t_wl + t_bl + (t_sn_v if is_gc else 2 * fo4)
        t_write = dff_s + t_dec_i + jnp.maximum(t_wr_core, t_chain * 0.6)
        f = 1.0 / jnp.maximum(t_read, t_write)
        # -- standby power (power.analyze leakage + refresh, vectorized)
        leakage = bits_i * clpb_v + periph_i * PERIPH_LEAK_W_PER_UM2
        bl_swing = dv_sense * 3 if is_gc else vdd * 0.5
        e_read = (c_wl * vdd ** 2 + ws_i * c_bl * vdd * bl_swing
                  + ws_i * 8e-15 * vdd ** 2)
        e_write = (c_wl * vdd ** 2 + ws_i * c_bl * vdd ** 2
                   + ws_i * 6e-15 * vdd ** 2)
        if wwlls:
            e_write = e_write * 1.25
        if is_gc:
            safe_ret = jnp.where(t_ret_v > 0.0, t_ret_v, 1.0)
            refresh = jnp.where(
                t_ret_v > 0.0,
                bits_i * (e_write / jnp.maximum(ws_i, 1.0)) / safe_ret, 0.0)
        else:
            refresh = jnp.zeros_like(e_write)
        return t_read, t_write, f, leakage, refresh, e_read, e_write

    inner = jax.vmap(point, in_axes=(None,) * 6 + (0,) * 10)  # over points
    outer = jax.vmap(inner, in_axes=(0,) * 6 + (None,) * 10)  # over vdd
    return jax.jit(outer)


def _eval_group_arrays(cfgs: List[BankConfig], banks,
                       vdd_scales: Sequence[float]) -> dict:
    """Core batched algebra for one topology group: (V, P) metric arrays
    from (V,) per-voltage constants x (P,) structural arrays, nested
    jax.vmap, float64."""
    tech = cfgs[0].tech
    is_gc = banks[0].is_gc
    wwlls = cfgs[0].wwlls
    consts = [_group_constants_cached(cfgs[0], banks[0], v)
              for v in vdd_scales]
    dv_sense = consts[0]["dv_sense"]

    # struct-of-arrays: structural + wire quantities per point
    # (voltage-independent, shared across the whole vdd ladder)
    rows = np.array([b.rows for b in banks], np.float64)
    wl = np.array([bank_mod.wordline_rc(b) for b in banks], np.float64)
    bl = np.array([bank_mod.bitline_rc(b) for b in banks], np.float64)
    t_dec = np.array([timing_mod.decoder_delay(b.rows) for b in banks],
                     np.float64)
    ws = np.array([c.word_size for c in cfgs], np.float64)
    bits = np.array([c.bits for c in cfgs], np.float64)
    periph = np.array([sum(b.modules.values()) for b in banks], np.float64)
    has_mux = np.array([b.has_colmux for b in banks])

    # per-voltage scalar constants, mapped over the outer vmap axis
    i_cell = np.array([c["i_cell"] for c in consts], np.float64)
    i_leak1 = np.array([c["i_leak1"] for c in consts], np.float64)
    t_ret = np.array([c["t_ret"] for c in consts], np.float64)
    t_sn = np.array([c["t_sn"] for c in consts], np.float64)
    clpb = np.array([c["cell_leak_per_bit"] for c in consts], np.float64)
    vdd_v = np.array([c["vdd"] for c in consts], np.float64)

    swing_ok = (i_cell[:, None] > 3.0 * ((rows - 1.0) * i_leak1[:, None])) \
        if is_gc else np.broadcast_to(i_cell[:, None] > 0.0,
                                      (len(consts), len(banks))).copy()

    # pad the lattice axis to a power-of-two bucket (edge-repeat) so the
    # jitted kernel is reused across group sizes: vmap shapes are
    # static, and both session sweeps and the coalescing executor
    # (repro.api.executor) hand this path varying-size unions of
    # "missing" configs. Same bucketing pattern as char_batch/engine;
    # the algebra is elementwise per point, so padding (and batch
    # composition generally) cannot perturb any point's value.
    P = len(banks)
    Pp = pow2_bucket(P)
    pad = lambda a: pad_bucket(a, Pp)
    with enable_x64():
        kernel = _group_kernel(is_gc, wwlls, float(dv_sense),
                               tech.sa_delay_s, tech.dff_delay_s,
                               tech.stage_delay_s)
        parrs = [jnp.asarray(pad(a), jnp.float64) for a in
                 (rows, wl[:, 0], wl[:, 1], bl[:, 0], bl[:, 1], t_dec, ws,
                  bits, periph)]
        mux = jnp.asarray(pad(has_mux))
        varrs = [jnp.asarray(a, jnp.float64) for a in
                 (vdd_v, i_cell, i_leak1, t_ret, t_sn, clpb)]
        t_read, t_write, f, leakage, refresh, e_read, e_write = \
            kernel(*varrs, *parrs, mux)
    out = {k: np.asarray(a)[:, :P] for k, a in
           (("t_read", t_read), ("t_write", t_write), ("f", f),
            ("leakage", leakage), ("refresh", refresh),
            ("e_read", e_read), ("e_write", e_write))}
    out.update(swing_ok=swing_ok, t_ret=t_ret,
               area=np.array([b.area_um2 for b in banks], np.float64),
               bits=bits, ws=ws,
               num_words=np.array([c.num_words for c in cfgs], np.float64))
    return out


# ---------------------------------------------------------------------------
# the (vdd x lattice) table — third lattice dimension for co-design
# ---------------------------------------------------------------------------

@dataclass
class VddLattice:
    """Struct-of-arrays metrics over (operating voltage x design lattice).

    All 2-D arrays are shaped (V, P) = (len(vdd_scales), len(cfgs)) and
    row v holds the lattice evaluated at `tech.vdd * vdd_scales[v]`,
    matching `dse.evaluate(cfg, vdd_scale)` bit-for-bit. Units follow
    DesignPoint: Hz, seconds, watts, um^2, bits; `e_read_j`/`e_write_j`
    are dynamic joules PER ACCESS of one word (the CV^2 terms of
    `power.analyze` without the frequency factor)."""
    cfgs: List[BankConfig]
    vdd_scales: Tuple[float, ...]
    f_max_hz: np.ndarray          # (V, P)
    t_read_s: np.ndarray
    t_write_s: np.ndarray
    retention_s: np.ndarray
    swing_ok: np.ndarray          # (V, P) bool
    leakage_w: np.ndarray
    refresh_w: np.ndarray
    e_read_j: np.ndarray
    e_write_j: np.ndarray
    area_um2: np.ndarray          # (P,)
    bits: np.ndarray              # (P,)
    num_words: np.ndarray         # (P,)
    is_gc: np.ndarray             # (P,) bool

    @property
    def shape(self) -> Tuple[int, int]:
        return self.f_max_hz.shape

    @property
    def standby_w(self) -> np.ndarray:
        return self.leakage_w + self.refresh_w

    def point(self, vi: int, pi: int) -> DesignPoint:
        """Materialize one (voltage, config) entry as a DesignPoint."""
        cfg = self.cfgs[pi]
        f, wsz = float(self.f_max_hz[vi, pi]), cfg.word_size
        rbw = wbw = f * wsz if self.is_gc[pi] else f * wsz / 2
        return DesignPoint(
            cfg, float(self.area_um2[pi]), f, rbw, wbw, rbw + wbw,
            float(self.leakage_w[vi, pi]), float(self.refresh_w[vi, pi]),
            float(self.retention_s[vi, pi]), bool(self.swing_ok[vi, pi]),
            float(self.t_read_s[vi, pi]), float(self.t_write_s[vi, pi]),
            float(self.vdd_scales[vi]))


def evaluate_vdd_lattice(cfgs: Sequence[BankConfig],
                         vdd_scales: Sequence[float]) -> VddLattice:
    """Evaluate the whole (vdd_scales x cfgs) grid, one nested-vmap
    program per cell topology; structural arrays are built once and
    shared across the voltage ladder."""
    cfgs = list(cfgs)
    vdd_scales = tuple(float(v) for v in vdd_scales)
    if not vdd_scales:
        raise ValueError("evaluate_vdd_lattice needs >= 1 vdd_scale")
    V, P = len(vdd_scales), len(cfgs)
    z = lambda: np.zeros((V, P), np.float64)
    out = dict(f_max_hz=z(), t_read_s=z(), t_write_s=z(), retention_s=z(),
               swing_ok=np.zeros((V, P), bool), leakage_w=z(),
               refresh_w=z(), e_read_j=z(), e_write_j=z())
    area = np.zeros(P); bits = np.zeros(P); nw = np.zeros(P)
    is_gc = np.zeros(P, bool)
    for idx in group_by_topology(cfgs).values():
        sub = [cfgs[i] for i in idx]
        banks = [build_bank(c) for c in sub]
        a = _eval_group_arrays(sub, banks, vdd_scales)
        cols = np.asarray(idx)
        for dst, src in (("f_max_hz", "f"), ("t_read_s", "t_read"),
                         ("t_write_s", "t_write"), ("leakage_w", "leakage"),
                         ("refresh_w", "refresh"), ("e_read_j", "e_read"),
                         ("e_write_j", "e_write"), ("swing_ok", "swing_ok")):
            out[dst][:, cols] = a[src]
        out["retention_s"][:, cols] = a["t_ret"][:, None]
        area[cols], bits[cols], nw[cols] = a["area"], a["bits"], \
            a["num_words"]
        is_gc[cols] = banks[0].is_gc
    return VddLattice(cfgs, vdd_scales, out["f_max_hz"], out["t_read_s"],
                      out["t_write_s"], out["retention_s"], out["swing_ok"],
                      out["leakage_w"], out["refresh_w"], out["e_read_j"],
                      out["e_write_j"], area, bits, nw, is_gc)


# ---------------------------------------------------------------------------
# vectorized workload matching: (vdd x lattice x demand) in one program
# ---------------------------------------------------------------------------

def feasible_grid(f_max_hz, retention_s, swing_ok, num_words,
                  read_freq_hz, lifetime_s, *,
                  allow_refresh: bool = True) -> np.ndarray:
    """Vectorized `dse.feasible`: lattice metric arrays of any common
    broadcastable shape S (e.g. (P,) or (V, P)) against demand vectors of
    shape (D,) -> boolean mask of shape S + (D,). Same rule, same float64
    comparisons, bit-for-bit with the scalar reference."""
    with enable_x64():
        f = jnp.asarray(f_max_hz, jnp.float64)[..., None]
        ret = jnp.asarray(retention_s, jnp.float64)[..., None]
        ok = jnp.asarray(swing_ok, bool)[..., None]
        nw = jnp.asarray(num_words, jnp.float64)[..., None]
        rf = jnp.asarray(read_freq_hz, jnp.float64)
        lt = jnp.asarray(lifetime_s, jnp.float64)
        meets_f = ok & (f >= rf)
        native = ret >= lt
        if allow_refresh:
            safe = jnp.where(ret > 0.0, ret, 1.0)
            refr = (ret > 0.0) & (nw / safe < 0.1 * f)
            mask = meets_f & (native | refr)
        else:
            mask = meets_f & native
        return np.asarray(mask)


def banks_needed_grid(f_max_hz, retention_s, swing_ok, bits, num_words,
                      read_freq_hz, lifetime_s, capacity_bits=None, *,
                      allow_refresh: bool = True,
                      max_banks: int = 1024) -> np.ndarray:
    """Vectorized `multibank.banks_needed`: smallest interleaved-macro
    bank count per (lattice-entry, demand) covering both the aggregate
    read frequency and the capacity, with `max_banks + 1` as the
    infeasibility sentinel — identical to the scalar reference."""
    with enable_x64():
        f = jnp.asarray(f_max_hz, jnp.float64)[..., None]
        ret = jnp.asarray(retention_s, jnp.float64)[..., None]
        ok = jnp.asarray(swing_ok, bool)[..., None]
        nw = jnp.asarray(num_words, jnp.float64)[..., None]
        bits_ = jnp.asarray(bits, jnp.float64)[..., None]
        rf = jnp.asarray(read_freq_hz, jnp.float64)
        lt = jnp.asarray(lifetime_s, jnp.float64)
        cap = jnp.zeros_like(rf) if capacity_bits is None \
            else jnp.asarray(capacity_bits, jnp.float64)
        alive = ok & (f > 0.0)
        safe_f = jnp.where(f > 0.0, f, 1.0)
        n_freq = jnp.ceil(rf / safe_f)
        n_cap = jnp.where(cap > 0.0, jnp.ceil(cap / bits_), 1.0)
        n = jnp.maximum(1.0, jnp.maximum(n_freq, n_cap))
        # per-bank retention feasibility at the interleaved (clamped)
        # rate: the frequency test passes by construction, so only the
        # native-retention / refresh rule remains
        native = ret >= lt
        if allow_refresh:
            safe_r = jnp.where(ret > 0.0, ret, 1.0)
            perbank = native | ((ret > 0.0) & (nw / safe_r < 0.1 * f))
        else:
            perbank = native
        n = jnp.where(alive & perbank, n, float(max_banks + 1))
        return np.asarray(n).astype(np.int64)


def shmoo_batch(points, demands, *, allow_refresh: bool = True) -> dict:
    """Drop-in replacement for `dse.shmoo` that evaluates the whole
    (points x demands) grid in one device program; same dict layout (and
    same duplicate-key overwrite semantics), python bools."""
    from repro.core.dse import shmoo_key
    mask = feasible_grid(
        np.array([p.f_max_hz for p in points], np.float64),
        np.array([p.retention_s for p in points], np.float64),
        np.array([p.swing_ok for p in points], bool),
        np.array([p.cfg.num_words for p in points], np.float64),
        np.array([d.read_freq_hz for d in demands], np.float64),
        np.array([d.lifetime_s for d in demands], np.float64),
        allow_refresh=allow_refresh)
    grid = {}
    for j, d in enumerate(demands):
        row = {}
        for i, dp in enumerate(points):
            row[shmoo_key(dp.cfg)] = bool(mask[i, j])
        grid[f"{d.level}:{d.name}"] = row
    return grid


def codesign_metrics(lat: VddLattice, demands, step_time_s, *,
                     allow_refresh: bool = True, max_banks: int = 1024):
    """The co-design cube: for every (vdd, config, demand) return

      feas    (V, P, D) bool   — single-bank feasibility (dse.feasible)
      banks   (V, P, D) int    — interleaved-macro size (banks_needed)
      energy  (V, P, D) float  — joules per inference step: dynamic read
              energy for the demanded accesses (read_freq * step_time
              accesses x e_read_j) + the macro's standby (leakage +
              refresh) integrated over the step
      macro_ok (V, P, D) bool  — banks within max_banks AND the per-bank
              retention rule holds

    `demands` is a Demand sequence, `step_time_s` the per-demand
    inference step time (seconds, same length)."""
    rf = np.array([d.read_freq_hz for d in demands], np.float64)
    lt = np.array([d.lifetime_s for d in demands], np.float64)
    cap = np.array([d.capacity_bits for d in demands], np.float64)
    step = np.asarray(step_time_s, np.float64)
    if step.shape != rf.shape:
        raise ValueError(f"step_time_s {step.shape} != demands {rf.shape}")
    feas = feasible_grid(lat.f_max_hz, lat.retention_s, lat.swing_ok,
                         lat.num_words, rf, lt, allow_refresh=allow_refresh)
    banks = banks_needed_grid(lat.f_max_hz, lat.retention_s, lat.swing_ok,
                              lat.bits, lat.num_words, rf, lt, cap,
                              allow_refresh=allow_refresh,
                              max_banks=max_banks)
    macro_ok = banks <= max_banks
    with enable_x64():
        accesses = jnp.asarray(rf * step)                     # (D,)
        e_dyn = accesses * jnp.asarray(lat.e_read_j)[..., None]
        standby = jnp.asarray(lat.standby_w)[..., None]
        energy = e_dyn + jnp.asarray(banks, jnp.float64) * standby \
            * jnp.asarray(step)
        energy = np.asarray(energy)
    return feas, banks, energy, macro_ok
