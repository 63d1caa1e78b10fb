"""Peaks, work counts, spans and the traffic generator: everything of the
benchmark that needs no device and no program run."""
import json
import os

import pytest

from bench.lib import drivers, peaks, spans, traffic, work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_transient_point_bytes_hand_count():
    # a read column of 13 nodes, 4 stimulus waves of 3 knots, 2 devices
    # (precharge, read device) of 8 parameters each, 300 steps
    inputs = 2 * 13 * 13 + 2 * 4 * 3 + 1 + 8 * 2      # 379 values
    outputs = 300 * 13                                  # 3900 values
    assert work.transient_point_bytes(13, 4, 3, 2, 8, 300) == \
        8 * (inputs + outputs) == 34232


def test_union_and_self_time():
    assert spans.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert spans.union_length([]) == 0
    s = spans.Spans()
    s.spans = [spans.Span("p", 0.0, 10.0, 1), spans.Span("c", 1.0, 3.0, 1),
               spans.Span("c", 2.0, 4.0, 1), spans.Span("c", 5.0, 6.0, 2),
               spans.Span("p", 20.0, 21.0, 2), spans.Span("c", 20.5, 30, 2)]
    # p1: 10 - [1,4) = 7 (the thread-2 child does not count); p2: 0.5
    assert spans.self_time(s, "p", ["c"]) == pytest.approx(7.5)


def test_span_wrapper_records_only_while_active():
    import math
    s = spans.Spans()
    s.install([{"module": "math", "attr": "hypot", "span": "m.hypot",
                "shapes": True}])
    try:
        math.hypot(3.0, 4.0)
        s.active = True
        assert math.hypot(3.0, 4.0) == 5.0
        s.active = False
    finally:
        s.uninstall()
    assert [x.name for x in s.spans] == ["m.hypot"]
    assert s.spans[0].info["args"] == [3.0, 4.0]
    assert math.hypot.__name__ == "hypot" and not hasattr(math.hypot,
                                                           "__wrapped__")


CELLS = [w for w in bench_json()["workloads"]]


def _mix_config(cell):
    return (traffic.load_json("traffic", cell["traffic"]),
            traffic.load_json("configs", cell["config"]))


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_stream_deterministic_per_seed(cell):
    mix, config = _mix_config(cell)
    seed = 2 ** 31 + 12345
    a = traffic.Stream(mix, config, seed)
    b = traffic.Stream(mix, config, seed)
    ra = [a.next() for _ in range(40)]
    assert ra == [b.next() for _ in range(40)]
    c = traffic.Stream(mix, config, seed + 1)
    assert [c.next() for _ in range(40)] != ra


def _in_menu(req, mix, config):
    space = config["space"]
    t = mix["request"]
    sw = req if req["type"] == "sweep" else req["sweep"]
    ok = req["type"] == t["type"]
    ok &= sw["cells"] == list(space["cells"]) if t["cells"] == "all" \
        else len(sw["cells"]) == 1 and sw["cells"][0] in space["cells"]
    for axis in ("word_sizes", "num_words", "write_vts", "wwlls"):
        ok &= sw[axis] == space[axis]
    if "deck_vdd_scale" in t:
        lo, hi = t["deck_vdd_scale"]["uniform"]
        ok &= lo <= req["deck_vdd_scale"] <= hi
    if req["type"] == "codesign":
        v = t["vdd_scales"]
        lo, hi = v["uniform"]
        ok &= len(req["vdd_scales"]) == v["count"] and \
            all(lo <= x <= hi for x in req["vdd_scales"])
        ok &= req["vdd_scales"] == sorted(req["vdd_scales"])
        names = [f"{p['arch']}:{p['shape']}" for p in req["profiles"]]
        ok &= names == t["profiles"] and set(names) <= set(config["profiles"])
    return ok


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_stream_draws_only_from_menu(cell):
    mix, config = _mix_config(cell)
    s = traffic.Stream(mix, config, 7)
    reqs = [s.next() for _ in range(300)]
    assert all(_in_menu(r, mix, config) for r in reqs)
    for r in reqs + list(traffic.representatives(mix, config)):
        assert sum(drivers.units(r, config).values()) > 0


def test_campaign_cycles_every_topology():
    mix, config = _mix_config({"traffic": "campaign",
                               "config": "gc_paper_transient"})
    s = traffic.Stream(mix, config, 3)
    reqs = [s.next() for _ in range(10)]
    for k in (0, 5):
        assert sorted(r["cells"][0] for r in reqs[k:k + 5]) == \
            sorted(config["space"]["cells"])
    pts = [drivers.units(r, config)["transient_points"] for r in reqs[:5]]
    assert sorted(pts) == [168, 168, 252, 252, 252]
    assert sum(pts) == config["points"] == 1092
    # every `strata` campaigns take one deck voltage in each slice
    v = mix["request"]["deck_vdd_scale"]
    (lo, hi), n = v["uniform"], v["strata"]
    s = traffic.Stream(mix, config, 2 ** 31 + 3)
    for _ in range(3):
        volts = [s.next()["deck_vdd_scale"] for _ in range(n)]
        assert sorted(int((x - lo) / (hi - lo) * n) for x in volts) == \
            list(range(n))


def test_cube_size():
    mix, config = _mix_config({"traffic": "cube",
                               "config": "gc_paper_analytic"})
    r = traffic.Stream(mix, config, 5).next()
    assert drivers.units(r, config)["cube_entries"] == 8 * 1092 * 4
    # one rung in each eighth of the range
    v = mix["request"]["vdd_scales"]
    (lo, hi), n = v["uniform"], v["count"]
    assert [int((x - lo) / (hi - lo) * n) for x in r["vdd_scales"]] == \
        list(range(n))


def test_benchmark_json_shape():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert w["config"] in names and w["chips"] == 1
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(ROOT, c["file"][:-5] + ".py"))
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    for m in b["per_layer"]:
        assert m["moves"] in e2e


def test_run_refuses_without_tpu(monkeypatch, capsys):
    from bench.lib import harness
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", harness.CACHE_DIR)
    rc = harness.main(["--workload", "codesign.paper", "--seed",
                       str(2 ** 33 + 1), "--seconds", "1"], 0.0)
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs 1 TPU chip" in out.err
