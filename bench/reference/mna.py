"""Modified nodal analysis: circuit build (python) -> dense arrays (jnp).

Circuits here are the CRITICAL-PATH netlists of a memory bank (wordline
RC ladder + write transistor + SN; RBL column with one active cell and
R-1 leakers; retention cell) — tens of nodes after rail segmentation, so
dense (N, N) MNA is exact and maps onto the batched Pallas solver.

Nonlinear devices are stored as per-instance PARAMETER ARRAYS (vt0, n,
k', lambda, W, L, polarity), not flavor objects, so a whole design-space
batch — and gradients through VT / sizing for the DSE co-optimizer — are
just vmap/grad over those arrays.

Voltage sources are Norton equivalents (G_BIG to a piecewise-linear
waveform), keeping the system pure nodal.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.techfile import PHI_T, DeviceFlavor

G_BIG = 1e2     # Norton conductance for sources (S)
G_MIN = 1e-10   # diagonal gmin


def channel_current_raw(pol, vt0, n, kp, lam, w, l, vg, va, vb):
    """Vectorized signed current a->b; raw-parameter version of
    devices.channel_current (kept in lockstep; tested against it)."""
    def mag(v_hi, v_lo):
        vds = v_hi - v_lo
        vgs_on = jnp.where(pol > 0, vg - v_lo, v_hi - vg)
        i_s = 2.0 * n * kp * (1.0 / jnp.maximum(l, 1e-3)) * PHI_T ** 2
        a_ = (vgs_on - vt0) / (2.0 * n * PHI_T)
        b_ = (vgs_on - vt0 - n * vds) / (2.0 * n * PHI_T)
        l2 = lambda x: jax.nn.softplus(x) ** 2
        return i_s * (l2(a_) - l2(b_)) * (1.0 + lam * vds)

    return w * jnp.where(va >= vb, mag(va, vb), -mag(vb, va))


def _vgs_partials(is_n, vg):
    """d(vgs_on)/d{vg, v_hi, v_lo}: (1, 0, -1) for n-type, (-1, 1, 0) for
    p-type, in vg's dtype (python-scalar branches would be f64 under
    enable_x64, which the TPU kernel compiler cannot lay out)."""
    one = jnp.ones_like(vg)
    zero = jnp.zeros_like(vg)
    return (jnp.where(is_n, one, -one), jnp.where(is_n, zero, one),
            jnp.where(is_n, -one, zero))


def channel_current_grads(pol, vt0, n, kp, lam, w, l, vg, va, vb):
    """Closed-form (di/dvg, di/dva, di/dvb) of `channel_current_raw`,
    vectorized over device arrays — one pass computes every device's 3x3
    conductance stamp, replacing n forward-mode Jacobian passes per
    Newton iteration.

    With L2(x) = softplus(x)^2 and L2'(x) = 2 softplus(x) sigmoid(x):

        m(v_hi, v_lo) = I_S [L2(a) - L2(b)] (1 + lam vds)
        a = (vgs_on - vt0) / (2 n phi_t)
        b = (vgs_on - vt0 - n vds) / (2 n phi_t)

    so each partial is the chain rule through (a, b, vds) with the branch
    (va >= vb picks which terminal is the source) selected exactly like
    the forward evaluation — matching jacfwd of channel_current_raw to
    float roundoff."""
    den = 2.0 * n * PHI_T
    i_s = 2.0 * n * kp * (1.0 / jnp.maximum(l, 1e-3)) * PHI_T ** 2
    is_n = pol > 0

    def mag_grads(v_hi, v_lo):
        vds = v_hi - v_lo
        vgs_on = jnp.where(is_n, vg - v_lo, v_hi - vg)
        a_ = (vgs_on - vt0) / den
        b_ = (vgs_on - vt0 - n * vds) / den
        sp_a, sp_b = jax.nn.softplus(a_), jax.nn.softplus(b_)
        dl2a = 2.0 * sp_a * jax.nn.sigmoid(a_)
        dl2b = 2.0 * sp_b * jax.nn.sigmoid(b_)
        core = sp_a ** 2 - sp_b ** 2
        lam_f = 1.0 + lam * vds
        # d(vgs_on)/d{vg, v_hi, v_lo}
        dvgs_dvg, dvgs_dhi, dvgs_dlo = _vgs_partials(is_n, vg)
        dm_dvg = i_s * (dl2a - dl2b) * dvgs_dvg / den * lam_f
        dm_dhi = i_s * ((dl2a * dvgs_dhi - dl2b * (dvgs_dhi - n)) / den
                        * lam_f + core * lam)
        dm_dlo = i_s * ((dl2a * dvgs_dlo - dl2b * (dvgs_dlo + n)) / den
                        * lam_f - core * lam)
        return dm_dvg, dm_dhi, dm_dlo

    f_dvg, f_dhi, f_dlo = mag_grads(va, vb)     # forward: hi=va, lo=vb
    r_dvg, r_dhi, r_dlo = mag_grads(vb, va)     # reverse: hi=vb, lo=va
    fwd = va >= vb
    di_dvg = w * jnp.where(fwd, f_dvg, -r_dvg)
    di_dva = w * jnp.where(fwd, f_dhi, -r_dlo)
    di_dvb = w * jnp.where(fwd, f_dlo, -r_dhi)
    return di_dvg, di_dva, di_dvb


@dataclass
class Circuit:
    """Builder. Node 0 is ground."""
    names: List[str] = field(default_factory=lambda: ["0"])
    res: List[tuple] = field(default_factory=list)    # (a, b, G)
    caps: List[tuple] = field(default_factory=list)   # (a, b, C)
    devs: List[dict] = field(default_factory=list)
    vsrcs: List[tuple] = field(default_factory=list)  # (node, wave_idx)
    probes: Dict[str, int] = field(default_factory=dict)

    def __post_init__(self):
        self._index = {n: i for i, n in enumerate(self.names)}

    def node(self, name: str) -> int:
        i = self._index.get(name)
        if i is None:
            i = len(self.names)
            self.names.append(name)
            self._index[name] = i
        return i

    def r(self, a, b, ohms):
        self.res.append((self.node(a), self.node(b), 1.0 / ohms))

    def c(self, a, b, farads):
        self.caps.append((self.node(a), self.node(b), farads))

    def dev(self, flavor: DeviceFlavor, w_um, l_um, g, a, b, name=""):
        self.devs.append({
            "pol": float(flavor.polarity), "vt0": flavor.vt0,
            "n": flavor.n_slope, "kp": flavor.k_prime,
            "lam": flavor.lambda_, "w": w_um, "l": l_um,
            "ig": flavor.i_gate_a_per_um,
            "g": self.node(g), "a": self.node(a), "b": self.node(b),
            "name": name,
        })
        # gate + junction caps as fixed linear caps
        cg = flavor.cg_f_per_um * w_um
        cj = flavor.cj_f_per_um * w_um
        self.caps.append((self.node(g), self.node(a), cg / 2))
        self.caps.append((self.node(g), self.node(b), cg / 2))
        self.caps.append((self.node(a), 0, cj))
        self.caps.append((self.node(b), 0, cj))

    def vsrc(self, node, wave_idx):
        self.vsrcs.append((self.node(node), wave_idx))

    def probe(self, label, node):
        self.probes[label] = self.node(node)

    # ---- assembly ----
    def build(self) -> "MNASystem":
        n = len(self.names) - 1  # exclude ground

        def idx(i):
            return i - 1  # ground dropped

        G = np.zeros((n, n))
        C = np.zeros((n, n))
        for a, b, g in self.res:
            for (i, j) in ((a, a), (b, b)):
                if i > 0:
                    G[idx(i), idx(j)] += g
            if a > 0 and b > 0:
                G[idx(a), idx(b)] -= g
                G[idx(b), idx(a)] -= g
        for a, b, c in self.caps:
            if a > 0:
                C[idx(a), idx(a)] += c
            if b > 0:
                C[idx(b), idx(b)] += c
            if a > 0 and b > 0:
                C[idx(a), idx(b)] -= c
                C[idx(b), idx(a)] -= c
        src_node = np.array([idx(nd) for nd, _ in self.vsrcs], np.int32)
        src_wave = np.array([w for _, w in self.vsrcs], np.int32)
        for nd in src_node:
            G[nd, nd] += G_BIG

        d = self.devs
        dev_arr = {k: jnp.array([x[k] for x in d]) if d else jnp.zeros((0,))
                   for k in ("pol", "vt0", "n", "kp", "lam", "w", "l", "ig")}
        dev_idx = {k: np.array([idx(x[k]) for x in d], np.int32) if d
                   else np.zeros((0,), np.int32) for k in ("g", "a", "b")}
        return MNASystem(jnp.array(G), jnp.array(C), dev_arr, dev_idx,
                         src_node, src_wave, n, dict(self.probes),
                         list(self.names))

@dataclass
class MNASystem:
    G: jnp.ndarray            # (n, n)
    C: jnp.ndarray            # (n, n)
    dev: dict                 # per-instance param arrays
    didx: dict                # g/a/b node indices (ground = -1)
    src_node: np.ndarray
    src_wave: np.ndarray
    n: int
    probes: dict
    names: list

    def with_params(self, **over):
        """Functional override of device parameter arrays (vt0, w, ...) —
        the hook for DSE batching/gradients. The special keys "G" and "C"
        override the LINEAR matrices, which is how the batched
        characterization pipeline threads per-design-point wire parasitics
        (bitline ladder RC, SA load, ...) through one compiled program."""
        over = dict(over)
        G = jnp.asarray(over.pop("G")) if "G" in over else self.G
        C = jnp.asarray(over.pop("C")) if "C" in over else self.C
        dev = dict(self.dev)
        dev.update({k: jnp.asarray(v) for k, v in over.items()})
        return MNASystem(G, C, dev, self.didx, self.src_node,
                         self.src_wave, self.n, self.probes, self.names)

    def _v_of(self, v, node_idx):
        # ground (-1) reads as 0.0
        vg = jnp.concatenate([v, jnp.zeros((1,), v.dtype)])
        return vg[node_idx]

    def device_currents(self, v):
        """KCL residual contribution of all devices: (n,) currents
        LEAVING each node."""
        if self.dev["pol"].shape[0] == 0:
            return jnp.zeros((self.n,))
        vg = self._v_of(v, self.didx["g"])
        va = self._v_of(v, self.didx["a"])
        vb = self._v_of(v, self.didx["b"])
        i_ab = channel_current_raw(self.dev["pol"], self.dev["vt0"],
                                   self.dev["n"], self.dev["kp"],
                                   self.dev["lam"], self.dev["w"],
                                   self.dev["l"], vg, va, vb)
        # gate leakage: gate -> (a+b)/2
        i_g = self.dev["ig"] * self.dev["w"] * (vg - 0.5 * (va + vb)) / 1.1
        out = jnp.zeros((self.n,))
        def acc(out, idxs, cur):
            ok = idxs >= 0
            return out.at[jnp.where(ok, idxs, 0)].add(jnp.where(ok, cur, 0.0))
        out = acc(out, self.didx["a"], i_ab - 0.5 * i_g)
        out = acc(out, self.didx["b"], -i_ab - 0.5 * i_g)
        out = acc(out, self.didx["g"], i_g)
        return out

    def source_currents(self, wave_v):
        """Norton injections for sources; wave_v: (n_waves,) values now."""
        out = jnp.zeros((self.n,))
        if len(self.src_node) == 0:
            return out
        return out.at[self.src_node].add(G_BIG * wave_v[self.src_wave])

    def device_jacobian(self, v):
        """d(device_currents)/dv as a dense (n, n) matrix, assembled from
        per-device 3x3 analytic stamps in ONE vectorized pass.

        For each device, with channel partials (di/dvg, di/dva, di/dvb)
        from `channel_current_grads` and gate-leak conductance
        gg = ig*w/1.1 (i_g = gg*(vg - (va+vb)/2)), the KCL rows stamp as

            row a (+i_ab - i_g/2):  [di_dvg - gg/2, di_dva + gg/4, di_dvb + gg/4]
            row b (-i_ab - i_g/2):  [-di_dvg - gg/2, -di_dva + gg/4, -di_dvb + gg/4]
            row g (+i_g):           [gg, -gg/2, -gg/2]

        (columns ordered g, a, b), scatter-added with ground (-1) rows and
        columns dropped."""
        if self.dev["pol"].shape[0] == 0:
            return jnp.zeros((self.n, self.n))
        vg = self._v_of(v, self.didx["g"])
        va = self._v_of(v, self.didx["a"])
        vb = self._v_of(v, self.didx["b"])
        di_dvg, di_dva, di_dvb = channel_current_grads(
            self.dev["pol"], self.dev["vt0"], self.dev["n"], self.dev["kp"],
            self.dev["lam"], self.dev["w"], self.dev["l"], vg, va, vb)
        gg = self.dev["ig"] * self.dev["w"] / 1.1
        na, nb, ng = self.didx["a"], self.didx["b"], self.didx["g"]
        entries = (
            (na, ng, di_dvg - 0.5 * gg),
            (na, na, di_dva + 0.25 * gg),
            (na, nb, di_dvb + 0.25 * gg),
            (nb, ng, -di_dvg - 0.5 * gg),
            (nb, na, -di_dva + 0.25 * gg),
            (nb, nb, -di_dvb + 0.25 * gg),
            (ng, ng, gg + jnp.zeros_like(di_dvg)),
            (ng, na, -0.5 * gg + jnp.zeros_like(di_dvg)),
            (ng, nb, -0.5 * gg + jnp.zeros_like(di_dvg)),
        )
        rows = jnp.concatenate([jnp.asarray(r) for r, _, _ in entries])
        cols = jnp.concatenate([jnp.asarray(c) for _, c, _ in entries])
        vals = jnp.concatenate([x for _, _, x in entries])
        ok = (rows >= 0) & (cols >= 0)
        flat = jnp.where(ok, rows * self.n + cols, 0)
        J = jnp.zeros((self.n * self.n,)).at[flat].add(
            jnp.where(ok, vals, 0.0))
        return J.reshape(self.n, self.n)

    def jacobian(self, v, h):
        """Analytic MNA Newton Jacobian J = C/h + G + dI/dv + gmin."""
        return (self.C / h + self.G + self.device_jacobian(v)
                + G_MIN * jnp.eye(self.n))

    def residual(self, v, v_prev, h, wave_v):
        """Backward-Euler KCL residual (n,)."""
        return (self.C @ ((v - v_prev) / h) + self.G @ v
                + self.device_currents(v) - self.source_currents(wave_v)
                + G_MIN * v)
