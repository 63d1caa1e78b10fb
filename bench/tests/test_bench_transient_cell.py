"""The transient cell's run, check and control on CPU at a tiny size.

Each test drives a whole run through `harness.run_cell` (everything but
the look for a chip): warm-up, a short window of the `campaign` mix,
the reference check. The sound program must come out correct; its
float32 control, and each fault planted in the timed path, must not.
"""
import json
import os

import numpy as np
import pytest

from bench.lib import harness, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    c = next(w for w in bench["workloads"] if w["name"] == "transient.paper")
    cfg = traffic.load_json("configs", c["config"])
    cfg["space"] = {"cells": ["gc2t_nn", "gc2t_np"], "word_sizes": [8, 16],
                    "num_words": [16, 32], "write_vts": [None],
                    "wwlls": [False]}
    cfg["check"] = {"points_per_run": 1000}      # every point
    return bench, c, cfg


def run(cell, seconds=1.0, **kw):
    bench, c, cfg = cell
    return harness.run_cell(bench, c, seed=SEED, seconds=seconds,
                            trace=False, t_start=0.0, config=cfg, **kw)


def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"transient_points_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_control_is_not_correct(cell):
    out = run(cell, control=True)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for k, c in out["checks"].items()
               if k.startswith("t_cell"))


def _fresh_programs(monkeypatch):
    from repro.core.spice import char_batch
    monkeypatch.setattr(char_batch, "_SHARED_TR", {})
    monkeypatch.setattr(char_batch, "_PIPE_CACHE", {})


def test_fault_state_unchanged(cell, monkeypatch):
    # every Newton step hands back the state it was given
    from repro.kernels.batched_solve import ops
    _fresh_programs(monkeypatch)
    monkeypatch.setattr(ops, "fused_newton_step",
                        lambda spec, pre, krhs, params, v, **kw: v)
    assert not run(cell)["correct"]


def test_fault_half_batch_left_out(cell, monkeypatch):
    # the lattice program computes every other lane; each lane left out
    # repeats the one before it
    from repro.core.spice.transient import Transient
    orig = Transient.run_lattice

    def half(self, *a, **kw):
        out = orig(self, *a, **kw)

        def thin(v):
            v = np.array(v)
            v[1::2] = v[0:v.shape[0] - 1:2]
            return v
        return {k: thin(v) if np.ndim(v) else v for k, v in out.items()}

    monkeypatch.setattr(Transient, "run_lattice", half)
    # only some campaigns show the fault (two alone read correct), so the
    # window is long enough to hold several on a loaded host too
    assert not run(cell, seconds=4.0)["correct"]


def test_fault_answer_altered(cell, monkeypatch):
    # the sensed crossing comes out 0.1% late where it is extracted
    from repro.core.spice import char_batch
    orig = char_batch.crossing_time

    def late(*a, **kw):
        tc, valid = orig(*a, **kw)
        return tc * 1.001, valid

    monkeypatch.setattr(char_batch, "crossing_time", late)
    assert not run(cell)["correct"]
