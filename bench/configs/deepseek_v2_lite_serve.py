"""Reference check and control of `deepseek_v2_lite_serve`.

`check`: `greedy_margin` (`bench.lib.serve_check.margins`), the widest
gap by which a served greedy token's logit lies below the plain
reference's best (`bench.reference.mla_moe_lm`, float32 at the highest
matmul precision, latent attention unabsorbed, the same held share of
experts), over that position's spread of logits, in the requests drawn
from the seed with the longest among them; `profile_rel_err`, one drawn
replay's measured profile against the one worked out from its lengths,
the model's widths (`bench.lib.work_mla`: held-share parameters, latent
row bytes) and the window's measured steps; `value_rel_err`, that
replay's co-design report against the co-design cell's scalar reference
on the profile worked out. The routing near-ties of the sampled
sequences (the reference's top_k-th router probability less the next,
under 1e-4 and 1e-3) are printed, not compared: a near tie is where
bfloat16 may route a token to another expert than float32 does.

`control`: the same run with the program's int8 latent cache
(`kv_dtype: "int8"`, per-row scales), below the bfloat16 cache the
configuration states.
"""
from __future__ import annotations

import contextlib
import copy
import math
import sys

import numpy as np

from bench.lib import serve_check, serving
from bench.lib.work_mla import MLAWork
from bench.reference import mla_moe_lm

# limits set between the lower reading (sound runs) and the upper one
# (the int8-cache control); see PERF.md
MARGIN_LIMIT = 0.4
PROFILE_LIMIT = 1e-9
GAP_LIMIT = 1e-9


def expected_profile(result: dict, request: dict, config: dict) -> dict:
    """`serve_check.expected_profile` with the latent model's widths
    (and the program's 2 bytes a weight, whatever type it computes in)."""
    model, win = config["model"], result["window"]
    w = MLAWork(model)
    steps = win["decode_steps"]
    step = (win["t_end_s"] - win["t_start_s"]) / steps
    n_active = w.params()
    toks = sum(o - 1 for _, _, o in request["prompts"]) / steps
    wb = 2.0 * n_active
    kvb = w.L * (win["kv_row_steps"] / steps) * float(w.row_bytes())
    life = win["kv_lifetimes_s"]
    act = 2.0 * toks * model["d_model"] * serve_check.ACT_TENSORS
    l1_bw = 2.0 * n_active * toks / step * 2 * 2 / serve_check.REUSE_DEPTH
    stream = (wb + kvb + act) / step
    n_l1 = serve_check.N_CORES * serve_check.BANKS_PER_CORE
    return {"step_time_s": step, "weights_bytes": wb, "kv_bytes": kvb,
            "act_bytes_per_layer": act / w.L,
            "weight_reuse_s": serve_check.WEIGHT_REUSE_S,
            "kv_lifetime_s": sum(life) / len(life),
            "act_lifetime_s": step / w.L,
            "l1_read_hz": l1_bw / n_l1 / serve_check.WORD_BYTES,
            "l2_read_hz": (serve_check.L1_MISS * l1_bw + stream)
            / serve_check.L2_BANKS / serve_check.WORD_BYTES}


def _drawn_sound(records, config, seed):
    r = serve_check._drawn(records, seed)
    if r is None or serve_check.window_off(r.result, r.request, config):
        return None
    return r


def profile_gap(records, config: dict, seed: int) -> float:
    from bench.reference.codesign import rel_err
    r = _drawn_sound(records, config, seed)
    if r is None or r.result["profile"]["kind"] != "decode":
        return math.inf
    got = r.result["profile"]
    want = expected_profile(r.result, r.request, config)
    return max(rel_err(float(got[k]), v) for k, v in want.items())


def codesign_gap(records, config: dict, seed: int) -> float:
    from bench.reference import on_cpu
    from bench.reference.codesign import Reference, report_gaps
    r = _drawn_sound(records, config, seed)
    if r is None:
        return math.inf
    got = r.result["profile"]
    name = f"{got['arch']}:{got['shape']}"
    codesign = serving.codesign_config(config)
    space = codesign["space"]
    req = {"sweep": {k: space[k] for k in ("cells", "word_sizes",
                                           "num_words")},
           "vdd_scales": r.request["vdd_scales"],
           "profiles": [{"arch": got["arch"], "shape": got["shape"]}]}
    want = expected_profile(r.result, r.request, config)
    with on_cpu():
        ref = Reference(dict(codesign, profiles={name: want}))
        gaps = report_gaps(ref, r.result["report"], req)
    return max(gaps, default=0.0)


def readings(records, config, seed) -> dict:
    """`check`'s numbers, each sampled request's own margin, and the
    routing margins of the sampled sequences."""
    route = []

    def logits(params, model, tokens, at):
        return mla_moe_lm.logits(params, model, tokens, at, margins=route)
    m = serve_check.margins(records, config, seed, logits)
    route = np.concatenate(route) if route else np.zeros(0)
    return {"greedy_margin": {"value": max(m, default=float("inf")),
                              "limit": MARGIN_LIMIT},
            "margins": m,
            "route_near_ties": [int(np.sum(route < t)) for t in (1e-4,
                                                                 1e-3)]
            + [int(route.size)],
            "profile_rel_err": {"value": profile_gap(records, config, seed),
                                "limit": PROFILE_LIMIT},
            "value_rel_err": {"value": codesign_gap(records, config, seed),
                              "limit": GAP_LIMIT}}


def check(records, config, seed) -> dict:
    out = readings(records, config, seed)
    n4, n3, n = out.pop("route_near_ties")
    print(f"bench: routing near-ties of the sampled sequences: {n4} under "
          f"1e-4 and {n3} under 1e-3 of {n} MoE routing decisions; "
          f"margins {out.pop('margins')}", file=sys.stderr, flush=True)
    return out


class Control(contextlib.AbstractContextManager):
    def __init__(self, config):
        self.config = copy.deepcopy(config)
        self.config["model"]["kv_dtype"] = "int8"

    def __exit__(self, *exc):
        return None


def control(config) -> Control:
    return Control(config)
