"""The reference check of a `serve` cell, for a configuration's `.py`.

Once the window has closed and the program's state is freed:

`greedy_margin`: engine requests drawn from the seed among those the
window's replays completed, the longest (prompt plus answer) always
among them. Each prompt with its served tokens goes once through the
plain reference (`logits_fn(params, model, tokens, at)`, on weights made
again from the seed), teacher-forced; at every served position,
including the first token, which comes from prefill, the reference's
best logit less its logit for the served token, over the spread
(standard deviation) of that position's logits. The number is the
widest such gap; greedy tokens that agree with the reference read 0.

`profile_gap`: one replay drawn from the seed. Its measured profile is
worked out again here (`expected_profile`) from what the benchmark
knows: the replay's prompt and answer lengths, the model's widths
(`bench.lib.work.DecoderWork`: parameters, cache row bytes) and, as
measurements like its time, the window's decode steps, its cache
row-steps integral and its requests' residencies. The byte model and the
hierarchy split are the program's, copied below. The number is the
widest relative gap of any field of the recorded profile; a window
counter that differs from what the lengths fix (tokens emitted after
prefill, prompt tokens, requests submitted, admitted and retired), or a
step count or row-steps integral outside what any schedule of the
replay's lengths can give (`_bounds`), is infinite.

`codesign_gap`: that replay's co-design report, recomputed whole by the
co-design reference (`bench.reference.codesign`) over the lattice of the
configuration the serving one names, on the profile worked out here:
every entry of the cube, every metric, every chosen design.
"""
from __future__ import annotations

import math

import numpy as np

from bench.lib import serving, weights
from bench.lib.work import DecoderWork

# the program's byte model of a measured decode profile and its split
# over the profiled memory hierarchy (runtime/profile.py,
# workloads/profiler.py), copied
ACT_TENSORS = 12                  # materialized tensors a layer, 2 B each
WEIGHT_REUSE_S = 3600.0 * 24
N_CORES, BANKS_PER_CORE, L2_BANKS = 128, 8, 128
L1_MISS, REUSE_DEPTH, WORD_BYTES = 0.25, 64, 4.0


def sample(records, seed: int, n: int) -> list:
    """(replay, request) indices of `n` served requests drawn from the
    seed among the completed replays, the longest first."""
    done = [(i, r) for i, r in enumerate(records) if r.ok]
    pool = [(i, j) for i, r in done
            for j in range(len(r.request["prompts"]))]
    if not pool:
        return []
    rng = np.random.default_rng([int(seed), 13])
    order = rng.permutation(len(pool))

    def size(k):
        i, j = pool[k]
        _, p, o = records[i].request["prompts"][j]
        return p + o
    first = max(order, key=size)                 # ties: the seed's pick
    rest = [k for k in order if k != first][:n - 1]
    return [pool[k] for k in [first] + rest]


def margins(records, config: dict, seed: int, logits_fn) -> list:
    """The widest normalized gap of each sampled request."""
    picks = sample(records, seed, int(config["check"]["requests_per_run"]))
    if not picks:
        return []
    model = config["model"]
    params = weights.make(config, seed)
    out = []
    for i, j in picks:
        s, p_len, _ = records[i].request["prompts"][j]
        served = np.asarray(records[i].result["tokens"][j], np.int64)
        prompt = serving.prompt_tokens(s, p_len, model["vocab_size"])
        seq = np.concatenate([prompt, served[:-1]])
        z = logits_fn(params, model, seq,
                      np.arange(p_len - 1, p_len - 1 + len(served)))
        gap = z.max(axis=1) - z[np.arange(len(served)), served]
        out.append(float(np.max(gap / z.std(axis=1))))
    return out


def _drawn(records, seed: int):
    """The replay whose profile and report are checked."""
    done = [r for r in records if r.ok]
    if not done:
        return None
    return done[int(np.random.default_rng([int(seed), 11]).integers(
        len(done)))]


def _bounds(po, n_slots: int, chunk: int) -> dict:
    """(least, most) decode steps and cache row-steps of any schedule of
    (prompt, answer) lengths `po` on `n_slots` slots in chunks of
    `chunk` steps. A request decodes o - 1 steps, the j-th over p + j
    resident rows; it is counted in at most ceil((o - 1) / chunk) + 1
    chunks, at most p + o rows a step, in the last of them at most a
    longest prompt's (a slot sampled once it holds the next request)."""
    dec = [o - 1 for _, o in po]
    chunks = [-(-d // chunk) for d in dec]
    longest = max(p for p, _ in po)
    steps = (max(max(dec), -(-sum(dec) // n_slots)),
             chunk * sum(c + 1 for c in chunks))
    rows = (sum(d * p + d * (d + 1) // 2 for (p, _), d in zip(po, dec)),
            chunk * sum(c * (p + o) + max(p + o, longest)
                        for (p, o), c in zip(po, chunks)))
    return {"decode_steps": steps, "kv_row_steps": rows}


def window_off(result: dict, request: dict, config: dict) -> list:
    """The window counters of a replay that its lengths rule out."""
    win = result["window"]
    po = [(p, o) for _, p, o in request["prompts"]]
    n = len(po)
    fixed = {"decode_tokens": sum(o - 1 for _, o in po),
             "prefill_tokens": sum(p for p, _ in po),
             "n_submitted": n, "n_admitted": n, "n_retired": n}
    off = [k for k, v in fixed.items() if win[k] != v]
    eng = config["engine"]
    for k, (lo, hi) in _bounds(po, eng["n_slots"],
                               eng["decode_chunk"]).items():
        if not lo <= win[k] <= hi:
            off.append(k)
    return off


def expected_profile(result: dict, request: dict, config: dict) -> dict:
    """The replay's decode profile, in the field order and operation
    order of the program's, from the lengths, the widths and the
    window's measured steps, row-steps and residencies."""
    model, win = config["model"], result["window"]
    w = DecoderWork(model)
    steps = win["decode_steps"]
    step = (win["t_end_s"] - win["t_start_s"]) / steps
    n_active = w.params()
    toks = sum(o - 1 for _, _, o in request["prompts"]) / steps
    wb = float(w.itemsize * n_active)
    kvb = w.L * (win["kv_row_steps"] / steps) * float(w.row_bytes())
    life = win["kv_lifetimes_s"]
    act = 2.0 * toks * model["d_model"] * ACT_TENSORS
    l1_bw = 2.0 * n_active * toks / step * 2 * 2 / REUSE_DEPTH
    stream = (wb + kvb + act) / step
    return {"step_time_s": step, "weights_bytes": wb, "kv_bytes": kvb,
            "act_bytes_per_layer": act / w.L,
            "weight_reuse_s": WEIGHT_REUSE_S,
            "kv_lifetime_s": sum(life) / len(life),
            "act_lifetime_s": step / w.L,
            "l1_read_hz": l1_bw / (N_CORES * BANKS_PER_CORE) / WORD_BYTES,
            "l2_read_hz": (L1_MISS * l1_bw + stream) / L2_BANKS
            / WORD_BYTES}


def profile_gap(records, config: dict, seed: int) -> float:
    """Widest relative gap of the drawn replay's recorded profile."""
    from bench.reference.codesign import rel_err
    r = _drawn(records, seed)
    if r is None or window_off(r.result, r.request, config):
        return math.inf
    got = r.result["profile"]
    if got["kind"] != "decode":
        return math.inf
    want = expected_profile(r.result, r.request, config)
    return max(rel_err(float(got[k]), v) for k, v in want.items())


def codesign_gap(records, config: dict, seed: int) -> float:
    """Widest relative gap of the drawn replay's co-design report."""
    from bench.reference import on_cpu
    from bench.reference.codesign import Reference, report_gaps
    r = _drawn(records, seed)
    if r is None or window_off(r.result, r.request, config):
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
