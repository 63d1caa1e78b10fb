"""The program's own spans and counters (`repro.core.trace`): the off path
costs nothing, and on a tiny transient campaign and a tiny co-design
query the spans form the tree the layers promise, on the CPU."""
import threading
import time
import tracemalloc
from types import SimpleNamespace as NS

import jax
import pytest

from repro.api import CoDesignQuery, Session, SweepQuery
from repro.core import trace
from repro.core.techfile import SYN40, with_vdd_scale
from repro.workloads.profiler import profile_arch

# 6 points per topology group, so each group pads to a bucket of 8
TRANSIENT = SweepQuery(cells=("gc2t_nn", "gc2t_np"), word_sizes=(8, 16, 32),
                       num_words=(16, 32), write_vts=(None,),
                       wwlls=(False,), fidelity="transient", sim_steps=40)
PHASES = ("char_batch.prep", "char_batch.dispatch", "char_batch.wait",
          "char_batch.finish")


class _Counting:
    def __init__(self, monkeypatch):
        self.annotations = self.clock = 0
        real_ann, real_clock = jax.profiler.TraceAnnotation, time.perf_counter

        def ann(*a, **kw):
            self.annotations += 1
            return real_ann(*a, **kw)

        def clock():
            self.clock += 1
            return real_clock()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
        monkeypatch.setattr(time, "perf_counter", clock)


def test_off_makes_no_record_annotation_clock_or_allocation(monkeypatch):
    n = _Counting(monkeypatch)
    assert trace.span("a") is trace.span("b") is trace.request("c")
    with trace.span("api.execute") as sp:
        assert sp is None               # callers build no attributes
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            with trace.span("api.plan"):
                trace.count("char_batch.points", 3)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [d for d in after.compare_to(before, "filename")
             if d.size_diff > 0 and d.traceback[0].filename == trace.__file__]
    assert grown == []
    assert n.annotations == 0 and n.clock == 0
    with trace.recording() as rec:
        pass
    assert rec.spans == [] and not rec.counters


def test_on_annotates_each_span_once(monkeypatch):
    n = _Counting(monkeypatch)
    with trace.recording() as rec:
        with trace.request("api.run"):
            with trace.span("api.plan") as sp:
                sp.attrs["k"] = 1
                trace.count("x", 2)
            trace.count("x")
    assert n.annotations == 2
    assert [s.name for s in rec.spans] == ["api.plan", "api.run"]
    assert rec.spans[0].attrs == {"k": 1}
    assert rec.counters == {"x": 3}
    with pytest.raises(RuntimeError):
        with trace.recording(), trace.recording():
            pass


@pytest.fixture(scope="module")
def transient():
    """One tiny transient campaign, recorded by the program and by the
    benchmark's outside spans at once."""
    from bench.lib import layers
    from bench.lib.spans import Spans
    outside = Spans()
    outside.install([layers.CHARACTERIZE, layers.RUN_LATTICE])
    try:
        Session(tech=with_vdd_scale(SYN40, 0.9)).run(TRANSIENT)   # warm
        outside.active = True
        with trace.recording() as rec:
            Session(tech=with_vdd_scale(SYN40, 0.8731)).run(TRANSIENT)
        outside.active = False
    finally:
        outside.uninstall()
    return rec, outside


def _ancestors(rec, s):
    by_id = {x.id: x for x in rec.spans}
    while s.parent is not None:
        s = by_id[s.parent]
        yield s


def test_transient_span_tree(transient):
    rec, _ = transient
    (root,) = rec.named("api.run")
    assert root.request is not None and root.parent is None
    groups = rec.named("char_batch.group")
    assert len(groups) == 2
    by_id = {s.id: s for s in rec.spans}
    for s in rec.spans:
        assert s.request == root.request
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.start <= s.start <= s.end <= p.end
            assert p.thread == s.thread
    for g in groups:
        assert [a.name for a in _ancestors(rec, g)] == ["api.execute",
                                                       "api.run"]
        kids = sorted((s for s in rec.spans if s.parent == g.id),
                      key=lambda s: s.start)
        assert tuple(k.name for k in kids) == PHASES
    assert "transient" in rec.named("api.execute")[0].attrs["kinds"]
    names = {s.name for s in rec.spans}
    assert {"api.plan", "api.compose", "dse_batch.lattice",
            "dse_batch.group_constants"} <= names


def test_counters_match_pad_share_reader(transient):
    from bench.lib import harness
    rec, outside = transient
    c = rec.counters
    assert (c["char_batch.points"], c["char_batch.lanes"]) == (12, 16)
    pad = harness.load_module("metrics", "pad_share.transient")
    assert pad.read(NS(spans=outside)) == pytest.approx(
        100.0 * (1.0 - c["char_batch.points"] / c["char_batch.lanes"]))


def test_codesign_constants_hold_retention_and_currents():
    q = CoDesignQuery(profiles=(profile_arch("qwen2-0.5b", "decode_32k"),),
                      sweep=SweepQuery(cells=("gc2t_nn", "gc2t_osos"),
                                       word_sizes=(8, 16),
                                       num_words=(16, 32)),
                      vdd_scales=(0.8123, 0.9377))
    with trace.recording() as rec:
        Session().run(q)
    consts = rec.named("dse_batch.group_constants")
    assert len(consts) >= 4                 # 2+ groups x 2 fresh rungs
    for g in consts:
        kids = [s.name for s in rec.spans if s.parent == g.id]
        assert "dse_batch.currents" in kids
        assert "dse_batch.retention" not in kids
        assert "dse_batch.lattice" in [a.name for a in _ancestors(rec, g)]
    # the lattice's retention is one batched span beside its constants
    (ret,) = rec.named("dse_batch.retention")
    assert [a.name for a in _ancestors(rec, ret)][0] == "dse_batch.lattice"
    assert ret.end <= min(g.start for g in consts)
    assert rec.self_time("dse_batch.group_constants",
                         ["dse_batch.currents"]) >= 0.0


def test_nested_and_threaded_spans_keep_their_parents():
    seen = {}

    def worker(tag):
        with trace.request("api.run") as a:
            a.attrs["tag"] = tag
            with trace.span("api.execute") as b:
                b.attrs["tag"] = tag
                time.sleep(0.01)
                with trace.span("char_batch.group") as c:
                    c.attrs["tag"] = tag
                    time.sleep(0.01)

    with trace.recording() as rec:
        with trace.span("outer"):
            threads = [threading.Thread(target=worker, args=(t,))
                       for t in ("a", "b")]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    by_id = {s.id: s for s in rec.spans}
    (outer,) = rec.named("outer")
    for s in rec.spans:
        if s.name == "outer":
            continue
        seen.setdefault(s.attrs["tag"], []).append(s)
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.attrs["tag"] == s.attrs["tag"] and p.thread == s.thread
    assert set(seen) == {"a", "b"}
    roots = rec.named("api.run")
    assert all(r.parent is None for r in roots)        # own thread's stack
    assert len({r.request for r in roots}) == 2
    for tag, spans in seen.items():
        assert len({s.request for s in spans}) == 1
        assert len({s.thread for s in spans}) == 1
    assert outer.request is None


def test_self_time_matches_the_benchmarks():
    from bench.lib import spans as bench_spans
    iv = [("p", 0.0, 10.0, 1), ("c", 1.0, 3.0, 1), ("c", 2.0, 4.0, 1),
          ("c", 5.0, 6.0, 2), ("p", 20.0, 21.0, 2), ("c", 20.5, 30.0, 2),
          ("d", 7.0, 8.0, 1)]
    outside = bench_spans.Spans()
    outside.spans = [bench_spans.Span(n, s, e, t) for n, s, e, t in iv]
    rec = trace.Recording(spans=[
        trace.SpanRecord(n, s, e, i, None, None, t, {})
        for i, (n, s, e, t) in enumerate(iv)])
    for kids in (["c"], ["c", "d"], []):
        assert rec.self_time("p", kids) == pytest.approx(
            bench_spans.self_time(outside, "p", kids))
    assert rec.self_time("p", ["c"]) == pytest.approx(7.5)


def test_serving_engine_spans_and_counters():
    """The engine's admission and decode-chunk spans and its slot and
    held-expert counters, read from the arrays its chunk sync already
    brings back: recorded under a recording, absent without one, and the
    same number of host syncs either way. Every expert is held here, so
    each live token makes top_k assignments in each MoE layer."""
    import dataclasses

    import numpy as np

    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serving import ServeEngine
    from repro.serving.engine import Request
    cfg = dataclasses.replace(get_config("deepseek-v2-lite").reduced(),
                              dtype="float32")
    params = Model(cfg).init(jax.random.key(0))
    lens = [(8, 5), (8, 7), (6, 3)]

    def serve():
        eng = ServeEngine(cfg, params, n_slots=2, window=24, decode_chunk=4)
        rng = np.random.default_rng(1)
        for i, (p, o) in enumerate(lens):
            eng.submit(Request(i, rng.integers(0, cfg.vocab_size, p).astype(
                np.int32), max_new_tokens=o))
        eng.run()
        return eng

    with trace.recording() as idle:
        pass
    off = serve()
    with trace.recording() as rec:
        on = serve()
    assert idle.spans == [] and not idle.counters
    assert on.host_syncs == off.host_syncs
    admits, chunks = rec.named("serve.admit"), rec.named("serve.chunk")
    assert [(s.attrs["rows"], s.attrs["padded_rows"]) for s in admits] == \
        [(16, 16), (6, 6)]
    c = rec.counters
    assert c["serve.slot_steps"] == len(chunks) * 4 * 2
    assert c["serve.slot_steps_live"] == sum(o - 1 for _, o in lens)
    assert c["moe.held_assignments"] == cfg.top_k * (
        cfg.n_layers - cfg.first_k_dense) * c["serve.slot_steps_live"]
