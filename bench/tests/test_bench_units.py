"""Peaks, work counts, spans and the traffic generator: everything of the
benchmark that needs no device and no program run."""
import json
import os

import pytest

from bench.lib import drivers, peaks, schedule, spans, traffic, work

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
# the replay mix's median decode chunks on its engine (16 slots, 8 steps)
MEDIAN_CHUNKS = 167


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
    t = mix["request"]
    if t["type"] == "serve":
        return _serve_in_menu(req, t, config)
    space = config["space"]
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


def _serve_in_menu(req, t, config):
    prompts = req["prompts"]
    v = t["vdd_scales"]
    lo, hi = v["uniform"]
    eng = config["engine"]
    answers = [o for _, _, o in prompts]
    return (req["type"] == "serve" and len(prompts) == t["requests"]
            and sorted(p for _, p, _ in prompts)
            == traffic.dealt(t["prompt_len"], t["requests"])
            and sorted(answers)
            == traffic.dealt(t["output_len"], t["requests"])
            and schedule.fifo(answers, eng["n_slots"],
                              eng["decode_chunk"]).chunks == MEDIAN_CHUNKS
            and req["vdd_scales"] == sorted(req["vdd_scales"])
            and len(req["vdd_scales"]) == v["count"]
            and all(lo <= x <= hi for x in req["vdd_scales"])
            and req["objective"] == t["objective"] and req["codesign"])


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


def test_dealt_lengths_and_buckets():
    spec = {"values": [1024, 2048, 4096, 8192],
            "weights": [0.4, 0.3, 0.2, 0.1]}
    d = traffic.dealt(spec, 96)              # 38.4, 28.8, 19.2, 9.6
    assert [d.count(v) for v in spec["values"]] == [38, 29, 19, 10]
    assert traffic.dealt(spec, 10) == [1024] * 4 + [2048] * 3 + \
        [4096] * 2 + [8192]
    assert traffic.buckets(1) == [1]
    assert traffic.buckets(10) == [1, 2, 4, 8, 16]
    assert traffic.buckets(16) == [1, 2, 4, 8, 16]
    assert traffic.buckets(17) == [1, 2, 4, 8, 16, 32]


def test_replay_orders_are_seeded_with_one_schedule():
    """Each seed queues its replay in an order of its own, sent as drawn
    and not sorted, whose first-come-first-served schedule takes the
    median decode chunks and about the median padded prefill rows of
    orders from a fixed stream."""
    mix, config = _mix_config({"traffic": "replay",
                               "config": "qwen2_0_5b_serve"})
    eng = config["engine"]
    orders, rows = [], []
    for seed in (1, 2 ** 31 + 5, 2 ** 33 + 7):
        s = traffic.Stream(mix, config, seed)
        chunks, median_rows = s._median
        assert chunks == MEDIAN_CHUNKS
        r = s.next()
        answers = [o for _, _, o in r["prompts"]]
        plan = schedule.fifo(answers, eng["n_slots"], eng["decode_chunk"])
        assert plan.chunks == chunks
        assert answers != sorted(answers, reverse=True)
        rows.append(sum(p * b for p, b in schedule.prefills(
            plan.waves, [p for _, p, _ in r["prompts"]])))
        assert abs(rows[-1] - median_rows) <= 0.01 * median_rows
        orders.append(answers)
    assert orders[0] != orders[1] != orders[2]


def test_fifo_schedule_hand_count():
    # 2 slots, chunks of 4: answers 9, 5, 2 (8, 4, 1 decode steps).
    # Chunk 1 runs the first two; read back after chunk 2 is out, the
    # second's slot takes the third in chunk 3; the first ends in chunk 2
    # and the third in chunk 3.
    plan = schedule.fifo([9, 5, 2], 2, 4)
    assert plan.chunks == 3 and plan.waves == [[0, 1], [2]]
    assert schedule.prefills(plan.waves, [64, 64, 32]) == [(64, 2), (32, 1)]
    assert schedule.prefills([[0, 1, 2]], [8, 16, 8]) == [(8, 2), (16, 1)]
    # an answer of one token is done at its prefill and holds no slot
    assert schedule.fifo([1, 1, 3], 1, 4).waves == [[0], [1], [2]]


def test_replay_warmup_covers_every_admission_shape():
    mix, config = _mix_config({"traffic": "replay",
                               "config": "qwen2_0_5b_serve"})
    reps = list(traffic.representatives(mix, config))
    shapes = {(r["prompts"][0][1], len(r["prompts"])) for r in reps
              if not r["codesign"]}
    slots = config["engine"]["n_slots"]
    t = mix["request"]
    lens = traffic.dealt(t["prompt_len"], t["requests"])
    for L in set(lens):
        top = 1 << (min(slots, lens.count(L)) - 1).bit_length()
        assert {b for p, b in shapes if p == L} == set(
            traffic.buckets(min(slots, lens.count(L))))
        assert max(b for p, b in shapes if p == L) == top
    last = reps[-1]                         # then decode and co-design
    assert last["codesign"] and max(o for _, _, o in last["prompts"]) > 1
    assert len(last["vdd_scales"]) == t["vdd_scales"]["count"]
    # the window's replays fit the engine's window
    r = traffic.Stream(mix, config, 2 ** 31 + 9).next()
    assert max(p + o for _, p, o in r["prompts"]) <= \
        config["engine"]["window"]


def test_decoder_work_hand_count():
    # 1 layer, d 4, 2 heads of 2 (1 kv head), d_ff 8, vocab 10
    model = {"family": "dense", "d_model": 4, "n_heads": 2,
             "n_kv_heads": 1, "head_dim": 2, "d_ff": 8, "n_layers": 1,
             "vocab_size": 10, "dtype": "bfloat16",
             "kv_dtype": "bfloat16"}
    w = work.DecoderWork(model)
    # a token passes 48 attention weights and 96 feed-forward ones
    # a prompt of 3: 3 tokens x 2 x 144, keys 1 + 2 + 3 x 4 x 2 x 2,
    # unembedding 2 x 4 x 10 once
    assert w.prefill_flops(3) == 3 * 2 * 144 + 6 * 16 + 80
    # an answer of 3 after a prompt of 3: decode steps at 4 and 5 keys
    assert w.decode_flops(3, 3) == 2 * 2 * 144 + 9 * 16 + 2 * 80
    assert w.row_bytes() == 2 * 1 * 2 * 2
    assert w.decode_kv_bytes(3, 3) == (4 + 5) * 8
    assert w.decode_weight_bytes(2) == 2 * (144 + 40)
    moe = dict(model, family="moe", n_experts=4, top_k=1)
    m = work.DecoderWork(moe)
    # one token reaches 1 expert, two reach 4 * (1 - (3/4)^2) = 1.75
    assert m.decode_weight_bytes(2) == 2 * (48 + 1.75 * 96 + 16 + 40)
    swa = work.DecoderWork(dict(model, sliding_window=2))
    assert swa.prefill_flops(3) == 3 * 2 * 144 + (1 + 2 + 2) * 16 + 80
    with pytest.raises(ValueError):
        work.DecoderWork(dict(model, family="ssm"))


def test_weights_follow_rules_and_seed():
    import numpy as np
    from bench.lib import weights
    config = {"model": {"name": "t", "family": "dense", "n_layers": 2,
                        "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                        "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                        "qkv_bias": True, "tie_embeddings": True},
              "weights": {"scale": {"mean": 1.0, "std": 0.1},
                          "wq": {"gain": 4.0}, "bk": {"spike": 600.0}}}
    p = weights.make(config, 7)
    f = lambda a: np.asarray(a, np.float32)
    assert f(p["blocks"]["n1"]["scale"]).mean() == pytest.approx(1, abs=.05)
    wq, wk = f(p["blocks"]["attn"]["wq"]), f(p["blocks"]["attn"]["wk"])
    # the program's fan-in convention: every axis but the last, no layers
    assert wq.std() == pytest.approx(4 / np.sqrt(64 * 4), rel=0.1)
    assert wk.std() == pytest.approx(1 / np.sqrt(64 * 2), rel=0.1)
    assert f(p["embed"]).std() == pytest.approx(1 / np.sqrt(64), rel=0.1)
    bk = np.abs(f(p["blocks"]["attn"]["bk"]))          # (2, 2, 16)
    assert (bk > 100).sum(axis=-1).tolist() == [[1, 1], [1, 1]]
    assert p["blocks"]["attn"]["wq"].dtype == "bfloat16"
    again, other = weights.make(config, 7), weights.make(config, 8)
    assert np.array_equal(f(again["embed"]), f(p["embed"]))
    assert not np.array_equal(f(other["embed"]), f(p["embed"]))
