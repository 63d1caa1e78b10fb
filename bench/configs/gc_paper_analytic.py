"""Reference check and control of `gc_paper_analytic`.

`check`: once the window has closed, co-design reports drawn from the
seed among those the window completed are recomputed whole by the plain
reference (`bench.reference`: scalar `evaluate` and `banks_needed`, on
the CPU device, with the demand profiles frozen in the configuration
file): every (rung, point) entry of the cube, every metric, and every
level's chosen design (feasible, bank count, energy per inference),
which no point of the whole lattice at any rung may beat; an
unplannable level must have no plannable point.

The number compared is the widest relative gap of any value, where a
differing verdict, count or choice counts as an infinite gap.

`control`: the batched evaluator `core/dse_batch` traced with 64-bit
types off, i.e. in float32, the precision below the float64 the
configuration states. The program has no switch for it; the control
rebinds the module's `enable_x64` context for the run.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

from bench.reference.codesign import Reference, report_gaps, worst

# widest relative gap the program may show: between the lower reading
# (sound float64 runs) and the upper one (the float32 control); see PERF.md
GAP_LIMIT = 1e-9


def _gaps(records, config, seed) -> list:
    """Every gap of the reports drawn from the seed ([inf] for none)."""
    from bench.reference import on_cpu
    rng = np.random.default_rng([int(seed), 11])
    done = [r for r in records if r.ok]
    gaps = []
    with on_cpu():
        ref = Reference(config)
        for i in rng.permutation(len(done))[:config["check"]
                                             ["reports_per_run"]]:
            r = done[int(i)]
            gaps += report_gaps(ref, r.result, r.request)
    return gaps or [math.inf]


def check(records, config, seed) -> dict:
    return {"value_rel_err": {"value": worst(_gaps(records, config, seed)),
                              "limit": GAP_LIMIT}}


def readings(records, config, seed) -> dict:
    """`check`'s number, and beside it how the gaps split: the values
    whose verdict, count or choice differs, and the widest gap of the
    others (for setting the limit; see `bench/readings.py`)."""
    g = _gaps(records, config, seed)
    finite = [x for x in g if math.isfinite(x)]
    return {"value_rel_err": {"value": worst(g), "limit": GAP_LIMIT},
            "infinite": len(g) - len(finite),
            "finite_gap_max": max(finite, default=math.nan)}


class Control(contextlib.AbstractContextManager):
    """Rebinds `dse_batch.enable_x64` to a context that turns 64-bit
    types off while the run lasts."""

    def __init__(self, config):
        self.config = config

    def __enter__(self):
        import jax
        from repro.core import dse_batch
        self._mod, self._orig = dse_batch, dse_batch.enable_x64
        dse_batch.enable_x64 = lambda: jax.enable_x64(False)
        return self

    def __exit__(self, *exc):
        self._mod.enable_x64 = self._orig
        return None


def control(config) -> Control:
    return Control(config)
