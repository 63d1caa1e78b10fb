"""Reference check and control of `gc_paper_transient`.

`check`: once the window has closed, a sample of the transient points
that the window's campaigns characterized, drawn from the seed and
always holding the point with the longest simulated interval, is
characterized again by the plain reference (`bench.reference.
transient_ref`: each point's own netlist, the dense float64 Newton
engine on the CPU device, at the campaign's deck voltage, with the
configuration's step and segment counts). Two numbers are compared,
where a point whose swing verdict differs counts as an infinite gap:

  * the widest gap of the sensed time `t_cell` over the sample, as a
    share of the point's simulated interval (the reference's stop
    time). Taken over the interval, not over `t_cell`: where the read
    bitline crosses its sense level within a step of the read's start,
    `t_cell` is a small difference of large times and its relative
    gap says nothing of the trace;
  * the median of the relative `t_cell` gaps, which a small error on
    every point moves and a few such points do not.

`control`: the program's own float32 engine (`precision="f32"`, the
Pallas kernel on TPU), the precision below the float64 the
configuration states.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

# each limit lies above what sound float64 runs read on the chip and
# below what the float32 control reads; the readings are in PERF.md
T_CELL_LIMIT = 5e-2
T_CELL_REL_MEDIAN_LIMIT = 1e-5


def gap(t_prog: float, t_ref: float, scale: float) -> float:
    if t_prog == t_ref:
        return 0.0
    if not (math.isfinite(t_prog) and math.isfinite(t_ref)) or scale == 0:
        return math.inf
    return abs(t_prog - t_ref) / scale


def sample(records, n: int, seed: int):
    """(deck voltage, program TransientChar) pairs: the point with the
    longest stop time, then a seeded draw of the rest."""
    cand = [(r.request.get("deck_vdd_scale", 1.0), c)
            for r in records if r.ok for c in r.result.transient
            if c is not None]
    if not cand:
        return []
    rng = np.random.default_rng([int(seed), 7])
    first = max(range(len(cand)), key=lambda i: cand[i][1].t_end_s)
    rest = [i for i in range(len(cand)) if i != first]
    pick = [first] + [rest[int(i)] for i in rng.choice(
        len(rest), min(n - 1, len(rest)), replace=False)]
    return [cand[i] for i in pick]


def gaps(pairs, config):
    """(gap over the simulated interval, relative gap) of `t_cell` for
    each (voltage, char) pair against the reference."""
    from bench.reference import on_cpu, transient_ref
    from bench.reference.bank import BankConfig
    from bench.reference.techfile import SYN40, with_vdd_scale
    cfgs = [BankConfig(c.cfg.word_size, c.cfg.num_words, cell=c.cfg.cell,
                       write_vt=c.cfg.write_vt, wwlls=c.cfg.wwlls,
                       tech=with_vdd_scale(SYN40, v)) for v, c in pairs]
    with on_cpu():
        ref = transient_ref.characterize(cfgs, n_steps=config["sim_steps"],
                                         n_seg=config["n_seg"])
    return [(gap(c.t_cell_s, t, t_end), gap(c.t_cell_s, t, abs(t)))
            if c.swing_ok == ok else (math.inf, math.inf)
            for (_, c), (t, ok, t_end) in zip(pairs, ref)]


def _sample_gaps(records, config, seed):
    pairs = sample(records, config["check"]["points_per_run"], seed)
    return gaps(pairs, config) if pairs else [(math.inf, math.inf)]


def _checks(g) -> dict:
    return {"t_cell_gap": {"value": max(x for x, _ in g),
                           "limit": T_CELL_LIMIT},
            "t_cell_rel_err_median": {
                "value": float(np.median([r for _, r in g])),
                "limit": T_CELL_REL_MEDIAN_LIMIT}}


def check(records, config, seed) -> dict:
    return _checks(_sample_gaps(records, config, seed))


def readings(records, config, seed) -> dict:
    """`check`'s numbers, and beside them how the sampled gaps split: the
    points whose swing verdict differs, the least and widest gap over
    the interval of the others, and the median gap over the interval
    (for setting the limits; see `bench/readings.py`)."""
    g = _sample_gaps(records, config, seed)
    finite = [x for x, _ in g if math.isfinite(x)]
    return {**_checks(g), "verdicts_differ": len(g) - len(finite),
            "finite_gap_min": min(finite, default=math.nan),
            "finite_gap_max": max(finite, default=math.nan),
            "gap_median": float(np.median([x for x, _ in g]))}


class Control(contextlib.AbstractContextManager):
    """Runs the configuration at precision f32; switches nothing else."""

    def __init__(self, config):
        self.config = dict(config, precision="f32")

    def __enter__(self):
        import warnings
        self._warn = warnings.catch_warnings()
        self._warn.__enter__()
        warnings.filterwarnings("ignore", message=".*precision='f32'.*")
        return self

    def __exit__(self, *exc):
        self._warn.__exit__(*exc)
        return None


def control(config) -> Control:
    return Control(config)
