"""The serving cell's run, check and control on CPU at a tiny size.

Each run goes through `harness.run_cell` (everything but the look for a
chip) with a qwen2-shaped model at a tiny size (two layers, vocabulary
512, but the published head width of 64, on which the int8 cache's
per-head scales act, the query spread of the full model: 4 x the fan-in
spread over 14 heads is 1.5 x over 2, and a key spike of 300 spreads, at
which two layers in bfloat16 stay under the limit and the int8 cache
does not, as 600 does at 24) and a mix of short prompts: warm-up of every admission shape, a short window
of replays, the reference check. The sound program must come out
correct; its int8-cache control, and each fault planted in the timed
path (a token altered where it is produced, a decode step that leaves
the cache unchanged, half of the slots left out, a budget cut short, a
co-design value nudged, a profile that counts a cache row's bytes
twice, a window that counts half of the resident rows), must not. One chip: no exchange to leave out. A model of another family, given only as a
configuration dict, is served by the same driver.
"""
import copy
import json
import os

import numpy as np
import pytest

from bench.lib import drivers, harness, serving, traffic, weights
from bench.reference import dense_lm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 4321
TINY = {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 64, "d_ff": 256, "vocab_size": 512}
SPACE = {"cells": ["gc2t_nn", "gc2t_osos"], "word_sizes": [8, 16],
         "num_words": [16, 32], "write_vts": [None], "wwlls": [False]}


REPLAY = traffic.load_json("traffic", "replay")


def tiny_mix():
    mix = copy.deepcopy(REPLAY)
    mix["request"].update(
        requests=6,
        prompt_len={"values": [32, 64], "weights": [0.5, 0.5]},
        output_len={"values": [24, 40], "weights": [0.5, 0.5]})
    return mix


def tiny_config(**model):
    cfg = traffic.load_json("configs", "qwen2_0_5b_serve")
    cfg["model"].update(TINY, **model)
    cfg["weights"].update(wq={"gain": 1.5}, bk={"spike": 300.0})
    cfg["engine"] = {"n_slots": 4, "window": 112, "decode_chunk": 4}
    # the tiny model's bfloat16 gaps run wider than the full model's (two
    # layers, a 512-token vocabulary): over 16 requests its widest reads
    # 0.34 at SEED, near the limit, so the tiny runs sample 4, at which it
    # reads 0.18 and the int8 control 0.85
    cfg["check"]["requests_per_run"] = 4
    return cfg


def small_lattice(monkeypatch):
    """The co-design configuration the serve one names, on a small
    lattice; the mix at a tiny size."""
    load = traffic.load_json

    def tiny(kind, name):
        if kind == "traffic":
            return tiny_mix()
        out = load(kind, name)
        if name == "gc_paper_analytic":
            out["space"] = SPACE
        return out
    monkeypatch.setattr(traffic, "load_json", tiny)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run(bench, monkeypatch, config=None, **kw):
    c = next(w for w in bench["workloads"]
             if w["name"] == "serve.qwen2-0.5b")
    small_lattice(monkeypatch)
    return harness.run_cell(bench, c, seed=SEED, seconds=1.0,
                            trace=kw.pop("trace", False), t_start=0.0,
                            config=config or tiny_config(), **kw)


def test_sound_run_is_correct(bench, monkeypatch):
    out = run(bench, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"decode_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"greedy_margin", "profile_rel_err",
                                  "value_rel_err", "requests_failed"}
    assert out["checks"]["profile_rel_err"]["value"] == 0.0
    assert list(out)[-1] == "checks"


def test_traced_run_reads_its_host_metrics(bench, monkeypatch):
    # the CPU has no device plane: the device-trace readers read nothing
    out = run(bench, monkeypatch, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"measured_codesign_ms_per_replay.serve"}
    assert out["metrics"]["measured_codesign_ms_per_replay.serve"][
        "value"] > 0


def test_control_is_not_correct(bench, monkeypatch):
    out = run(bench, monkeypatch, control=True)
    assert not out["correct"]
    c = out["checks"]["greedy_margin"]
    assert c["value"] > c["limit"]


def _swap_first_token(monkeypatch):
    # the host records another first token than the prefill sampled
    from repro.serving import engine
    orig = engine.ServeEngine._record_first_tokens

    def swapped(self, items, first):
        return orig(self, items, (np.asarray(first) + 1) % self.cfg.vocab_size)
    monkeypatch.setattr(engine.ServeEngine, "_record_first_tokens", swapped)


def _cache_unchanged(monkeypatch):
    # a decode step returns the cache it was given: no new key or value
    from repro.models.model import Model
    orig = Model.decode_step

    def stale(self, p, cache, token, pos):
        logits, _ = orig(self, p, cache, token, pos)
        return logits, cache
    monkeypatch.setattr(Model, "decode_step", stale)


def _half_batch(monkeypatch):
    # a decode step's second half of the slots takes the first half's
    # logits: half of the batch is left out
    from repro.models.model import Model
    orig = Model.decode_step

    def half(self, p, cache, token, pos):
        logits, cache = orig(self, p, cache, token, pos)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h]), cache
    monkeypatch.setattr(Model, "decode_step", half)


def _budget_short(monkeypatch):
    # the engine serves one request a token less than it was asked for
    from repro.serving import engine
    orig = engine.ServeEngine.submit

    def short(self, req):
        if req.rid % 6 == 0:
            req.max_new_tokens -= 1
        return orig(self, req)
    monkeypatch.setattr(engine.ServeEngine, "submit", short)


def _report_nudged(monkeypatch):
    from repro.core import dse_batch
    orig = dse_batch._eval_group_arrays

    def nudged(cfgs, banks, vdd_scales):
        out = orig(cfgs, banks, vdd_scales)
        out["f"] = out["f"] * (1.0 + 1e-6)
        return out
    monkeypatch.setattr(dse_batch, "_eval_group_arrays", nudged)


def _row_bytes_twice(monkeypatch):
    # the measured profile counts a cache row's bytes twice
    from repro.runtime import profile
    orig = profile.kv_row_bytes
    monkeypatch.setattr(profile, "kv_row_bytes", lambda cfg: 2 * orig(cfg))


def _rows_halved(monkeypatch):
    # the telemetry window counts half of the resident cache rows
    from repro.runtime.telemetry import TelemetryCollector
    orig = TelemetryCollector.on_chunk

    def half(self, n_steps, emitted_tokens, kv_rows, queue_depth):
        return orig(self, n_steps, emitted_tokens,
                    [r // 2 for r in kv_rows], queue_depth)
    monkeypatch.setattr(TelemetryCollector, "on_chunk", half)


@pytest.mark.parametrize("plant", [_swap_first_token, _cache_unchanged,
                                   _half_batch, _budget_short,
                                   _report_nudged, _row_bytes_twice,
                                   _rows_halved],
                         ids=["token_swapped", "state_unchanged",
                              "half_batch_left_out", "budget_short_by_one",
                              "report_value_nudged", "profile_row_bytes",
                              "window_rows_halved"])
def test_fault_is_not_correct(bench, monkeypatch, plant):
    plant(monkeypatch)
    out = run(bench, monkeypatch)
    assert not out["correct"], out["checks"]


def test_reference_greedy_equals_engine_in_float32():
    cfg = tiny_config(dtype="float32", kv_dtype="float32")
    server = serving.Server(cfg, SEED)
    prompts = [[11, 64, 24], [12, 32, 40], [13, 64, 5]]
    out = server.replay({"type": "serve", "prompts": prompts})
    params = weights.make(cfg, SEED)
    for (s, p, o), served in zip(prompts, out["tokens"]):
        prompt = serving.prompt_tokens(s, p, cfg["model"]["vocab_size"])
        z = dense_lm.logits(params, cfg["model"],
                            np.concatenate([prompt, served[:-1]]),
                            np.arange(p - 1, p - 1 + o))
        assert z.argmax(axis=1).tolist() == served


def test_another_family_is_served_without_code_change(monkeypatch):
    """mixtral-8x7b at reduced sizes (experts with top-2 routing, a
    sliding-window ring cache), given only as a configuration dict."""
    from repro.configs import get_config
    import dataclasses
    model = dataclasses.asdict(get_config("mixtral-8x7b").reduced())
    config = {"name": "mixtral_tiny_serve", "model": model,
              "engine": {"n_slots": 4, "window": 64, "decode_chunk": 4},
              "weights": {"scale": {"mean": 1.0, "std": 0.1}},
              "codesign": "gc_paper_analytic"}
    small_lattice(monkeypatch)
    driver = drivers.Driver(tiny_mix(), config, SEED)
    assert driver.warmup() >= 3
    records = driver.run(0.5)
    assert records and all(r.ok for r in records)
    assert model["family"] == "moe" and model["sliding_window"] > 0
    r = records[0]
    assert [len(t) for t in r.result["tokens"]] == \
        [o for _, _, o in r.request["prompts"]]
    assert r.result["report"].plans and r.result["decode_steps"] > 0
    assert sum(drivers.units(r.request, config).values()) == \
        sum(o for _, _, o in r.request["prompts"])


def test_reseed_draws_new_weights_for_the_same_engine():
    cfg = tiny_config()
    server = serving.Server(cfg, 1)
    engine, before = server.engine, server.engine.params
    server.reseed(2)
    assert server.engine is engine
    a, b = before["embed"], server.engine.params["embed"]
    assert a.shape == b.shape and a.dtype == b.dtype
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    again = weights.make(copy.deepcopy(cfg), 2)
    assert np.array_equal(np.asarray(again["embed"]), np.asarray(b))
