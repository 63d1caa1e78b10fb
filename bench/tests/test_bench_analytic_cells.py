"""The co-design cell's runs, checks and controls on CPU at a tiny size.

Each test drives a whole run through `harness.run_cell` (everything but
the look for a chip): warm-up of every shape the mix can draw, a short
window, the reference check. The sound program must come out correct;
its float32 control, and each fault planted in the timed path, must
not. (The analytic tier keeps no state from step to step, so the fault
of a step returning its state unchanged has no counterpart here.)
"""
import json
import os

import pytest

from bench.lib import harness, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 77
CELLS = ("codesign.paper",)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run(bench, name, **kw):
    c = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = traffic.load_json("configs", c["config"])
    cfg["space"] = {"cells": ["gc2t_nn", "gc2t_osos"],
                    "word_sizes": [8, 16, 32, 64],
                    "num_words": [16, 32, 64, 128],
                    "write_vts": [None], "wwlls": [False]}
    return harness.run_cell(bench, c, seed=SEED, seconds=1.0, trace=False,
                            t_start=0.0, config=cfg, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(bench, name):
    out = run(bench, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(bench, name):
    out = run(bench, name, control=True)
    assert not out["correct"]
    c = out["checks"]["value_rel_err"]
    assert c["value"] > c["limit"]


def _planted(monkeypatch, edit):
    from repro.core import dse_batch
    orig = dse_batch._eval_group_arrays

    def faulty(cfgs, banks, vdd_scales):
        out = orig(cfgs, banks, vdd_scales)
        edit(out)
        return out

    monkeypatch.setattr(dse_batch, "_eval_group_arrays", faulty)


def _half(out):
    # the group's second half of the lattice repeats its first point
    for k in ("t_read", "t_write", "f", "leakage", "refresh", "e_read",
              "e_write"):
        a = out[k] = out[k].copy()
        a[:, a.shape[1] // 2:] = a[:, :1]


def _altered(out):
    out["f"] = out["f"] * (1.0 + 1e-6)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("edit", [_half, _altered],
                         ids=["half_batch_left_out", "answer_altered"])
def test_fault_is_not_correct(bench, name, edit, monkeypatch):
    _planted(monkeypatch, edit)
    assert not run(bench, name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_second_best_choice_is_not_correct(bench, name, monkeypatch):
    # every level picks the plannable entry with the second least energy:
    # its values are all right, only the choice is not
    import numpy as np
    from repro.api import plan
    orig = plan.compose_codesign

    def second_best(session, q, lat, cube):
        feas, banks, energy, ok = cube
        ok = np.array(ok)
        for j in range(ok.shape[-1]):
            e = np.where(ok[:, :, j], energy[:, :, j], np.inf)
            if np.isfinite(e).sum() > 1:
                ok[(*np.unravel_index(np.argmin(e), e.shape), j)] = False
        return orig(session, q, lat, (feas, banks, energy, ok))

    monkeypatch.setattr(plan, "compose_codesign", second_best)
    out = run(bench, name)
    assert not out["correct"]
    assert out["checks"]["value_rel_err"]["value"] > 1e300 / 2
