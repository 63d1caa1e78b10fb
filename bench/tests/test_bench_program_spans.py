"""The readers of the program's own spans and counters: traced runs of
both cells on CPU at a tiny size read a number for each, a run that
raises leaves no recording in the next run's way, and on a program
without `repro.core.trace` every one of them reads None."""
import json
import os
import sys

import pytest

from bench.lib import harness, program, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 1234
SPACE = {"transient.paper": {"cells": ["gc2t_nn", "gc2t_np"],
                             "word_sizes": [8, 16, 32], "num_words": [16, 32],
                             "write_vts": [None], "wwlls": [False]},
         "codesign.paper": {"cells": ["gc2t_nn", "gc2t_osos"],
                            "word_sizes": [8, 16], "num_words": [16, 32],
                            "write_vts": [None], "wwlls": [False]}}
NEW = {"transient.paper": ("char_prep_host_ms_per_point.transient",
                           "analytic_consts_ms_per_campaign.transient"),
       "codesign.paper": ("consts_retention_ms_per_group.codesign",)}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def traced_run(bench, name):
    c = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = traffic.load_json("configs", c["config"])
    cfg["space"] = SPACE[name]
    return harness.run_cell(bench, c, seed=SEED, seconds=1.0, trace=True,
                            t_start=0.0, config=cfg)


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_run_reads_each_program_metric(bench, name):
    out = traced_run(bench, name)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    for k in NEW[name]:
        assert m[k]["value"] > 0, k
    if name == "transient.paper":
        # each campaign is one topology group of 6 points on 8 lanes;
        # the outside reader of the same layer reads the same order
        assert m["pad_share.transient"]["value"] == pytest.approx(25.0)
        assert m["char_prep_host_ms_per_point.transient"]["value"] <= \
            m["char_prep_ms_per_point.transient"]["value"]
    assert program._open is None            # closed once read


def test_failed_run_leaves_no_recording_in_the_way(bench, monkeypatch):
    from repro.core import trace

    def fail(records, config, seed):
        raise RuntimeError("the check failed")

    def load(kind, name):                   # the check raises after the
        mod = load_module(kind, name)       # window, before any read
        if kind == "configs":
            mod.check = fail
        return mod
    load_module = harness.load_module
    with monkeypatch.context() as m:
        m.setattr(harness, "load_module", load)
        with pytest.raises(RuntimeError, match="the check failed"):
            traced_run(bench, "codesign.paper")
    stale = program._last
    assert trace._ACTIVE is stale and stale.spans   # still open
    out = traced_run(bench, "codesign.paper")      # opens its own
    assert program._last is not stale and trace._ACTIVE is None
    assert out["metrics"]["consts_retention_ms_per_group.codesign"][
        "value"] > 0
    program.record()                                # a run that fails
    program.close()                                 # closed at exit
    assert trace._ACTIVE is None


def test_readers_read_none_without_program_spans(bench, monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    readers = [harness.load_module("metrics", k)
               for ks in NEW.values() for k in ks]
    assert program._open is None and program._last is None
    run = harness.RunData(config={}, records=[], window_s=1.0, setup_s=1.0,
                          spans=None, traced=[object()])
    assert [r.read(run) for r in readers] == [None] * len(readers)
