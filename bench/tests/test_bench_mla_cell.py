"""The latent-attention serving cell's run, check and control on the CPU
at a tiny size.

Each run goes through `harness.run_cell` with a DeepSeek-V2-shaped model
at a tiny size (one dense and three MoE layers, 2 of 16 routed experts
held, top-6 raw gates, 2 shared experts, latent rows of 64 + 16) and a
mix of short prompts: warm-up of every admission shape, a short window
of replays, the reference check. The sound program must come out
correct; its int8 latent-cache control, and each fault planted in the
timed path (top-k gates renormalized, the shared experts left out, a
profile that counts a latent row's bytes twice), must not. The tiny
model computes in float32 beside its bfloat16 latent cache (see
`tiny_config`). The work counts match a hand count at a tiny width.
"""
import copy
import json
import os
import time

import numpy as np
import pytest

from bench.lib import harness, traffic
from bench.lib.work_mla import MLAWork

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 5321
SECONDS = 1.0
TINY = {"n_layers": 4, "d_model": 128, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 48, "d_ff": 256, "vocab_size": 512, "n_experts": 16,
        "experts_held": 2, "moe_d_ff": 64, "kv_lora_rank": 64,
        "qk_nope_dim": 32, "qk_rope_dim": 16, "v_head_dim": 32}
SPACE = {"cells": ["gc2t_nn", "gc2t_osos"], "word_sizes": [8, 16],
         "num_words": [16, 32], "write_vts": [None], "wwlls": [False]}
CELL = "serve.deepseek-v2-lite"
REPLAY = traffic.load_json("traffic", "replay48")


def tiny_mix():
    mix = copy.deepcopy(REPLAY)
    mix["request"].update(
        requests=6,
        prompt_len={"values": [32, 64], "weights": [0.5, 0.5]},
        output_len={"values": [24, 40], "weights": [0.5, 0.5]})
    return mix


def tiny_config():
    cfg = traffic.load_json("configs", "deepseek_v2_lite_serve")
    cfg["model"].update(TINY)
    cfg["engine"] = {"n_slots": 4, "window": 112, "decode_chunk": 4}
    # At four layers of 128 the bfloat16 model's own rounding reads up to
    # 0.49 at a few positions, near the limit, so the tiny model computes
    # in float32 and only its latent cache is bfloat16, as stated. With a
    # flatter router (a quarter of the spread: top-k gates far from
    # summing to 1) and an outlier of 150 spreads, the first replay of the
    # window reads 0.013 sound, 3.23 under the int8 control, 0.75 with
    # renormalized gates, 3.51 without the shared experts. The check takes
    # every request of the window, and each window holds one replay (see
    # `run`): over many replays the flat router's near-ties flip some
    # sound requests' routing against the float32 reference, and 0-3% of
    # them read above the limit (51-s windows, 5 seeds, sandbox CPU), so a
    # window's count of replays, the host's speed, must not set the
    # readings.
    cfg["model"]["dtype"] = "float32"
    cfg["weights"].update(router={"gain": 0.25}, kv_norm={
        "mean": 1.0, "std": 0.1, "spike": 150.0})
    cfg["check"]["requests_per_run"] = 1000
    return cfg


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run(bench, monkeypatch, **kw):
    load = traffic.load_json

    def tiny(kind, name):
        if kind == "traffic":
            return tiny_mix()
        out = load(kind, name)
        if name == "gc_paper_analytic":
            out["space"] = SPACE
        return out
    monkeypatch.setattr(traffic, "load_json", tiny)
    draw = traffic.Stream.next

    def one_replay(stream):
        # a second draw waits out the window: the run ends after one replay
        if getattr(stream, "drawn", False):
            time.sleep(SECONDS)
        stream.drawn = True
        return draw(stream)
    monkeypatch.setattr(traffic.Stream, "next", one_replay)
    c = next(w for w in bench["workloads"] if w["name"] == CELL)
    return harness.run_cell(bench, c, seed=SEED, seconds=SECONDS,
                            trace=kw.pop("trace", False), t_start=0.0,
                            config=tiny_config(), **kw)


def test_sound_run_is_correct(bench, monkeypatch):
    out = run(bench, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"decode_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"greedy_margin", "profile_rel_err",
                                  "value_rel_err", "requests_failed"}
    assert out["checks"]["profile_rel_err"]["value"] == 0.0


def test_traced_run_reads_the_engine_counters(bench, monkeypatch):
    # the CPU has no device plane: only the program's counters and the
    # benchmark's host spans are read
    out = run(bench, monkeypatch, trace=True)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"decode_live_share.mla",
                      "held_expert_tokens_per_step.mla",
                      "measured_codesign_ms_per_replay.mla"}
    assert m["measured_codesign_ms_per_replay.mla"] > 0
    assert 0 < m["decode_live_share.mla"] <= 100
    # at most 4 live slots, each with top-6 of 16: 4 x 6 / 16 = 1.5 at most
    # in expectation a step, 4 x 2 / 2 = 4 at the very most
    assert 0 < m["held_expert_tokens_per_step.mla"] <= 4


def test_control_is_not_correct(bench, monkeypatch):
    out = run(bench, monkeypatch, control=True)
    assert not out["correct"]
    c = out["checks"]["greedy_margin"]
    assert c["value"] > c["limit"]


def _gates_renormalized(monkeypatch):
    from repro.models import moe
    orig = moe._route
    monkeypatch.setattr(moe, "_route",
                        lambda x, w, k, renorm=True: orig(x, w, k, True))


def _shared_left_out(monkeypatch):
    from repro.models import transformer
    orig = transformer.mlp_apply

    def no_shared(p, x, cfg):
        y = orig(p, x, cfg)
        return y * 0 if p["w1"].shape[-1] == 2 * cfg.expert_ff() else y
    monkeypatch.setattr(transformer, "mlp_apply", no_shared)


def _row_bytes_twice(monkeypatch):
    from repro.runtime import profile
    orig = profile.kv_row_bytes
    monkeypatch.setattr(profile, "kv_row_bytes", lambda cfg: 2 * orig(cfg))


@pytest.mark.parametrize("plant", [_gates_renormalized, _shared_left_out,
                                   _row_bytes_twice],
                         ids=["gates_renormalized", "shared_left_out",
                              "profile_row_bytes"])
def test_fault_is_not_correct(bench, monkeypatch, plant):
    plant(monkeypatch)
    out = run(bench, monkeypatch)
    assert not out["correct"], out["checks"]


def test_work_counts_by_hand():
    """One dense and one MoE layer at tiny widths, counted by hand."""
    model = {"attn_kind": "mla", "n_layers": 2, "first_k_dense": 1,
             "d_model": 4, "n_heads": 2, "vocab_size": 10,
             "kv_lora_rank": 3, "qk_nope_dim": 2, "qk_rope_dim": 2,
             "v_head_dim": 1, "d_ff": 5, "moe_d_ff": 2, "n_experts": 8,
             "experts_held": 2, "top_k": 4, "n_shared_experts": 1,
             "dtype": "bfloat16", "kv_dtype": "bfloat16"}
    w = MLAWork(model)
    # wq 4*2*4 + wkv_a 4*5 + wk_b 3*2*2 + wv_b 3*2*1 + wo 2*1*4
    attn = 32 + 20 + 12 + 6 + 8
    assert w._attn_weights() == attn == 78
    expert = 3 * 4 * 2                                   # 24
    # per token: dense layer attn + MLP 60; MoE attn + router 32 + shared
    # 24 + held experts reached 4 x 2 / 8 = 1 of them
    assert w._token_weights() == (78 + 60) + (78 + 32 + 24 + 24)
    # embed + unembed 80, final norm 4, layer norms 2 x 4 and the latent's
    # 3 in each layer, router 32, shared 24, the held experts at 1 / 2
    assert w.params() == 80 + 4 + (78 + 11 + 60) + (78 + 11 + 32 + 24) \
        + 2 * expert * 4 // 8
    # prefill of 3 tokens: 2 x 296 a token, keys 1 + 2 + 3 per layer at
    # 2 x 2 x (2 + 2 + 1), unembedding 2 x 4 x 10
    assert w.prefill_flops(3) == 2 * 296 * 3 + 20 * 2 * 6 + 80
    # decode steps of an answer of 3 after a prompt of 2: contexts 3, 4,
    # absorbed at 2 x 2 x (2 x 3 + 2) a key
    assert w.decode_flops(2, 3) == 2 * 296 * 2 + 32 * 2 * 7 + 80 * 2
    assert w.row_bytes() == (3 + 2) * 2
    assert w.decode_kv_bytes(2, 3) == 2 * 7 * 10
    # one token reaches 2 x (1 - (1 - 4/8)) = 1 held expert
    assert w.decode_weight_bytes(1) == 2 * (
        (78 + 60) + (78 + 32 + 24 + 1 * expert) + 40)
    assert np.isclose(w.decode_weight_bytes(50), 2 * (
        (78 + 60) + (78 + 32 + 24 + 2 * expert) + 40))
    int8 = MLAWork(dict(model, kv_dtype="int8"))
    assert int8.row_bytes() == 3 + 2 + 4
