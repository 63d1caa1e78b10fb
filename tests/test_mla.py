"""DeepSeek-V2 at a tiny size on the CPU: latent attention (MLA) with its
absorbed decode over a latent cache, routed experts with a held share and
shared experts, the leading dense layer, checked against the plain
float32 reference (`bench/reference/mla_moe_lm.py`, loaded by path: it
imports nothing of the program) on seeded random weights."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention, moe
from repro.models.model import Model
from repro.models.transformer import mlp_apply
from repro.runtime.profile import kv_row_bytes
from repro.serving import ServeEngine
from repro.serving.engine import Request

_spec = importlib.util.spec_from_file_location(
    "mla_moe_lm", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "reference", "mla_moe_lm.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# one dense layer, three MoE layers; 2 of 16 experts held, top-6, raw gates
BASE = dataclasses.replace(get_config("deepseek-v2-lite"),
                           experts_held=8).reduced()


def _params(cfg, seed, spike=None):
    """Program init, with one latent channel's norm gain at `spike`: the
    outlier channel that makes an int8 row's step coarse."""
    p = Model(cfg).init(jax.random.key(seed))
    if spike is not None:
        for name in ("dense_blocks", "blocks"):
            a = p[name]["attn"]
            a["kv_norm"] = a["kv_norm"].at[:, 3].set(spike)
    return p


def _teacher_forced(cfg, p, toks, P):
    """Logits of positions P-1 .. len-2: prefill of the first P tokens,
    then one cached decode step per further token."""
    m = Model(cfg)
    z0, cache, pos = jax.jit(lambda p, b: m.prefill(p, b, W=len(toks)))(
        p, {"tokens": jnp.asarray(toks[None, :P])})
    dec = jax.jit(m.decode_step)
    zs = [np.asarray(z0[0], np.float32)]
    for t in range(P, len(toks) - 1):
        z, cache = dec(p, cache, jnp.asarray(toks[None, t:t + 1]), pos)
        pos = pos + 1
        zs.append(np.asarray(z[0], np.float32))
    return np.stack(zs)


# Widest logit gap to the reference over the logits' spread. In a float32
# model only the cache's type rounds: bfloat16 rows (2^-9 relative) read
# 0.0075-0.016 over seeds 0-2 at this size, int8 rows (steps of a row's
# largest value / 127, coarse beside the outlier channel) 0.077-0.106.
CACHE_LIMIT = 0.04


@pytest.mark.parametrize("kv_dtype,passes", [("bfloat16", True),
                                             ("int8", False)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_cached_decode_matches_reference(kv_dtype, passes,
                                                      seed):
    cfg = dataclasses.replace(BASE, dtype="float32", kv_dtype=kv_dtype)
    p = _params(cfg, seed, spike=30.0)
    toks = np.asarray(jax.random.randint(jax.random.key(seed + 1), (32,), 0,
                                         cfg.vocab_size))
    z = _teacher_forced(cfg, p, toks, 20)
    zr = ref.logits(p, dataclasses.asdict(cfg), toks, np.arange(19, 31))
    err = float(np.max(np.abs(z - zr).max(1) / zr.std(1)))
    assert (err < CACHE_LIMIT) == passes, err


def test_absorbed_decode_matches_unabsorbed_attention():
    """One decode step over cached latent rows equals the last row of
    full-sequence (per-head keys and values) attention; float32 through,
    so the two orders of the same products agree to rounding."""
    cfg = dataclasses.replace(BASE, dtype="float32", kv_dtype="float32")
    p = Model(cfg).init(jax.random.key(3))["blocks"]
    a = jax.tree.map(lambda x: x[0], p["attn"])
    S, W = 11, 16
    x = jax.random.normal(jax.random.key(4), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    full, (c, kpe) = attention.mla_attend_train(a, x, pos, cfg)
    cache = {"ckv": jnp.zeros((3, 2, W, cfg.kv_lora_rank)),
             "kpe": jnp.zeros((3, 2, W, cfg.qk_rope_dim))}
    cache["ckv"] = cache["ckv"].at[1, :, :S - 1].set(c[:, :S - 1])
    cache["kpe"] = cache["kpe"].at[1, :, :S - 1].set(kpe[:, :S - 1])
    out, new = attention.mla_decode(a, x[:, S - 1:], cache, 1,
                                    jnp.full((2,), S - 1), cfg)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(new["ckv"][1, :, S - 1]),
                               np.asarray(c[:, -1]), rtol=1e-6, atol=1e-6)
    assert not np.any(np.asarray(new["ckv"][0]))     # other layers untouched


def test_expert_shares_sum_to_the_whole_layer():
    """The share test: each of the 8 shares of a layer's 16 routed experts
    (2 each, held from expert 0, so share j rolls the router by 2j), with
    the shared experts counted once, adds up to the uncut reference layer
    over all 16. Float32; sums in another order, hence 1e-5."""
    cfg = dataclasses.replace(BASE, dtype="float32")
    uncut = dataclasses.replace(cfg, experts_held=0)
    lp = jax.tree.map(lambda x: x[0],
                      Model(uncut).init(jax.random.key(5))["blocks"])
    x = jax.random.normal(jax.random.key(6), (1, 24, cfg.d_model))
    total = mlp_apply(lp["shared"], x, cfg)
    E, held = uncut.n_experts, cfg.experts_held
    assignments = 0
    for j in range(E // held):
        share = {"router": jnp.roll(lp["moe"]["router"], -j * held, axis=1),
                 **{k: lp["moe"][k][j * held:(j + 1) * held]
                    for k in ("w1", "w3", "w2")}}
        y, n = moe.apply(share, x, cfg, dropless=True)
        total, assignments = total + y, assignments + n
    want, _ = ref.expert_ffn(x[0], lp, cfg.top_k, E, cfg.norm_topk)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # each assignment lands on the held experts of exactly one share
    assert np.all(np.asarray(assignments) == cfg.top_k)


def test_dropless_routing_keeps_every_token():
    """Every token's first choice is expert 0; serving keeps them all
    (a training capacity of ceil(T k 1.25 / E) = 12 of 32 would not)."""
    cfg = dataclasses.replace(BASE, dtype="float32", experts_held=0)
    p = jax.tree.map(lambda x: x[0],
                     Model(cfg).init(jax.random.key(7))["blocks"])["moe"]
    x = jnp.abs(jax.random.normal(jax.random.key(8), (1, 32, cfg.d_model)))
    p["router"] = p["router"].at[:, 0].set(1.0)
    _, idx, _ = moe._route(x[0], p["router"], cfg.top_k, cfg.norm_topk)
    assert np.all(np.asarray(idx[:, 0]) == 0)
    y, _ = moe.apply(p, x, cfg, dropless=True)
    want, _ = ref.expert_ffn(x[0], {"moe": p, "shared": {
        "w1": jnp.zeros((cfg.d_model, 1)), "w3": jnp.zeros((cfg.d_model, 1)),
        "w2": jnp.zeros((1, cfg.d_model))}}, cfg.top_k, cfg.n_experts,
        cfg.norm_topk)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dropped, _ = moe.apply(p, x, cfg)
    assert not np.allclose(np.asarray(dropped[0]), np.asarray(want),
                           atol=1e-3)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "qwen2-0.5b"])
def test_prefill_ffn_chunks_match_whole(arch, monkeypatch):
    """Prefill's feed-forward in chunks of PREFILL_FFN_TOKENS tokens (here
    8 of a batch of 2 x 16) gives the whole batch's logits and cache rows:
    the feed-forward is token-wise. Float32; the matmuls' shapes differ,
    hence 1e-5."""
    from repro.models import transformer
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                              kv_dtype="float32")
    m = Model(cfg)
    p = m.init(jax.random.key(11))
    toks = jax.random.randint(jax.random.key(12), (2, 16), 0, cfg.vocab_size)

    def prefill():
        return jax.jit(lambda p, t: m.prefill(p, {"tokens": t}, W=16))(
            p, toks)
    whole = prefill()
    monkeypatch.setattr(transformer, "PREFILL_FFN_TOKENS", 8)
    chunked = prefill()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def test_engine_greedy_matches_reference():
    """Served through ServeEngine (batched prefill with bucket padding,
    the prompt's latent rows written into slots, chunked decode, slots
    reused), in float32 the greedy tokens are the reference's."""
    cfg = dataclasses.replace(BASE, dtype="float32", kv_dtype="float32")
    p = _params(cfg, 9)
    eng = ServeEngine(cfg, p, n_slots=4, window=40, decode_chunk=3)
    rng = np.random.default_rng(0)
    lens = [(12, 9), (12, 5), (12, 7), (8, 6), (8, 11)]
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, P).astype(np.int32),
                    max_new_tokens=O) for i, (P, O) in enumerate(lens)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    model = dataclasses.asdict(cfg)
    for r, (P, O) in zip(reqs, lens):
        seq = np.concatenate([r.prompt, r.out_tokens[:-1]])
        z = ref.logits(p, model, seq, np.arange(P - 1, P - 1 + O))
        assert z.argmax(axis=1).tolist() == r.out_tokens


def test_held_share_sizes_the_profile():
    """Active parameters count the held experts at top_k / n_experts of
    each (as a token reaches them), and a latent cache row is c_kv plus
    the rope key."""
    cfg = BASE
    d, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    attn = d * H * (dn + dr) + d * (R + dr) + R + R * H * (dn + dv) \
        + H * dv * d
    f = cfg.expert_ff()
    n_moe = cfg.n_layers - cfg.first_k_dense
    moe_layer = 2 * d + attn + d * cfg.n_experts + 3 * d * f * \
        cfg.n_shared_experts
    reached = n_moe * cfg.experts_held * 3 * d * f * cfg.top_k
    assert reached % cfg.n_experts == 0
    dense_layer = 2 * d + attn + 3 * d * cfg.d_ff
    want = 2 * cfg.vocab_size * d + d + dense_layer + n_moe * moe_layer \
        + reached // cfg.n_experts
    assert Model(cfg).param_count(active_only=True) == want
    assert kv_row_bytes(cfg) == 2.0 * (R + dr)
    assert kv_row_bytes(dataclasses.replace(cfg, kv_dtype="int8")) == R + dr
