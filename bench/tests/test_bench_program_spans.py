"""The readers of the program's own spans and counters, which the
harness records while traced and hands them as `RunData.recording`:
traced runs of both cells on CPU at a tiny size read a number for each,
a run that raises leaves no recording open, and on a program without
`repro.core.trace` every one of them reads None."""
import json
import os
import sys

import pytest

from bench.lib import harness, traffic

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
    from repro.core import trace
    assert trace._ACTIVE is None            # closed with the trace


def test_failed_run_leaves_no_recording_open(bench, monkeypatch):
    from repro.core import trace

    def fail(*args, **kwargs):
        raise RuntimeError("the window failed")

    with monkeypatch.context() as m:        # raises inside the window
        m.setattr(harness.drivers_mod.Driver, "run", fail)
        with pytest.raises(RuntimeError, match="the window failed"):
            traced_run(bench, "codesign.paper")
    assert trace._ACTIVE is None                   # closed on the way out
    out = traced_run(bench, "codesign.paper")      # opens its own
    assert trace._ACTIVE is None
    assert out["metrics"]["consts_retention_ms_per_group.codesign"][
        "value"] > 0


def test_readers_read_none_without_program_spans(bench, monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    readers = [harness.load_module("metrics", k)
               for ks in NEW.values() for k in ks]
    traced = harness.Traced("unused", None, 1.0, on=False)
    assert traced.recording is None and traced.inside([object()]) is None
    run = harness.RunData(config={}, records=[], window_s=1.0, setup_s=1.0,
                          spans=None, traced=[object()], recording=None)
    assert [r.read(run) for r in readers] == [None] * len(readers)
