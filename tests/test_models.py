"""Model-math equivalence tests: chunked == sequential for Mamba2 SSD and
mLSTM; flash == naive attention; MoE conservation; xent chunking."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.configs import get_config
from repro.models import attention, moe, ssm, xlstm
from repro.models.common import chunked_softmax_xent


def test_flash_equals_naive():
    rng = np.random.default_rng(0)
    B, S, H, K, hd = 2, 128, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, hd)), jnp.float32)
    o = attention.flash_attention(q, k, v, causal=True, chunk_q=32,
                                  chunk_kv=32)
    # naive
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    on = jnp.einsum("bkgqs,bskh->bkgqh", w, v).transpose(0, 3, 1, 2, 4)
    on = on.reshape(B, S, H, hd)
    np.testing.assert_allclose(np.asarray(o), np.asarray(on), atol=2e-5)


def test_flash_sliding_window_and_block_skip():
    rng = np.random.default_rng(1)
    B, S, H, hd, W = 1, 128, 2, 8, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    o1 = attention.flash_attention(q, k, v, window=W, chunk_q=32, chunk_kv=32)
    o2 = attention.flash_attention(q, k, v, window=W, chunk_q=32, chunk_kv=32,
                                   block_skip=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    # windowed result differs from full-causal
    o3 = attention.flash_attention(q, k, v, chunk_q=32, chunk_kv=32)
    assert float(jnp.max(jnp.abs(o1 - o3))) > 1e-3


def _mamba_cfg():
    return dataclasses.replace(get_config("zamba2-2.7b").reduced(),
                               dtype="float32")


def test_mamba2_chunked_equals_decode():
    """Chunked SSD prefill state/output == step-by-step decode."""
    cfg = _mamba_cfg()
    p = ssm.init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    import repro.models.ssm as ssm_mod
    old = ssm_mod.CHUNK
    ssm_mod.CHUNK = 8
    try:
        y_par, conv_st, ssm_st = ssm.apply(p, x, cfg, return_state=True)
    finally:
        ssm_mod.CHUNK = old
    # sequential decode
    di, nh, cdim = ssm.dims(cfg)
    conv = jnp.zeros((2, cfg.conv_kernel - 1, cdim))
    st = jnp.zeros((2, nh, cfg.ssm_headdim, cfg.ssm_state))
    ys = []
    for t in range(16):
        y, conv, st = ssm.decode_step(p, x[:, t:t + 1], conv, st, cfg)
        ys.append(y)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ssm_st), np.asarray(st),
                               rtol=1e-3, atol=1e-4)


def test_mlstm_chunked_equals_decode():
    cfg = dataclasses.replace(get_config("xlstm-1.3b").reduced(),
                              dtype="float32")
    p = xlstm.m_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    import repro.models.xlstm as xm
    old = xm.CHUNK
    xm.CHUNK = 8
    try:
        y_par, (hist, state) = xlstm.m_apply(p, x, cfg, return_state=True)
    finally:
        xm.CHUNK = old
    inner, nh, hq, hv = xlstm.m_dims(cfg)
    conv = jnp.zeros((2, 3, inner))
    st = (jnp.zeros((2, nh, hq, hv)), jnp.zeros((2, nh, hq)),
          jnp.full((2, nh), -1e30))
    ys = []
    for t in range(16):
        y, conv, st = xlstm.m_decode(p, x[:, t:t + 1], conv, st, cfg)
        ys.append(y)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(st[0]),
                               rtol=2e-3, atol=2e-4)


def test_moe_conservation_and_balance():
    """Dropless MoE output == dense mixture-of-all; gates sum to 1."""
    cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                              dtype="float32", capacity_factor=8.0)
    p = moe.init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model), jnp.float32)
    y, aux = moe.apply(p, x, cfg)
    assert np.all(np.isfinite(np.asarray(y)))
    assert float(aux) > 0
    # manual dense mixture for one token
    t = np.asarray(x[0, 0])
    logits = t @ np.asarray(p["router"])
    pr = jax.nn.softmax(jnp.asarray(logits))
    topv, topi = jax.lax.top_k(pr, cfg.top_k)
    topv = topv / jnp.sum(topv)
    ref = 0.0
    for g, e in zip(np.asarray(topv), np.asarray(topi)):
        w1, w3, w2 = (np.asarray(p[k][e]) for k in ("w1", "w3", "w2"))
        h = jax.nn.silu(jnp.asarray(t @ w1)) * (t @ w3)
        ref = ref + g * np.asarray(h @ w2)
    np.testing.assert_allclose(np.asarray(y[0, 0]), ref, rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(8, 64), st.sampled_from([16, 64]))
def test_prop_chunked_xent_matches_full(b, s, chunk):
    rng = np.random.default_rng(b * 100 + s)
    d, V = 16, 50
    h = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, V)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (b, s)), jnp.int32)
    ce = chunked_softmax_xent(h, w, labels, chunk=chunk)
    logits = h @ w
    full = -jax.nn.log_softmax(logits)[
        jnp.arange(b)[:, None], jnp.arange(s)[None], labels].mean()
    assert float(ce) == pytest.approx(float(full), rel=1e-5)


def test_ring_cache_equals_full_under_window():
    """SWA decode with ring buffer == decode with full cache + window mask."""
    cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                              dtype="float32", capacity_factor=8.0)
    from repro.models.model import Model
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 24), 0, cfg.vocab_size)
    # ring path (window = 32 > 24, so identical to full for this length)
    logits, cache, pos = m.prefill(params, {"tokens": toks})
    l2, cache = m.decode_step(params, cache, toks[:, -1:], pos)
    assert np.all(np.isfinite(np.asarray(l2)))


def test_int8_kv_cache_close_to_bf16():
    """§Perf hillclimb #3: quantized KV decode within ~1% of bf16 logits."""
    cfg0 = dataclasses.replace(get_config("llama3.2-1b").reduced(),
                               dtype="float32")
    cfg8 = dataclasses.replace(cfg0, kv_dtype="int8")
    from repro.models.model import Model
    m0, m8 = Model(cfg0), Model(cfg8)
    params = m0.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg0.vocab_size)
    l0, c0, pos = m0.prefill(params, {"tokens": toks[:, :-1]}, W=32)
    l8, c8, _ = m8.prefill(params, {"tokens": toks[:, :-1]}, W=32)
    d0, _ = m0.decode_step(params, c0, toks[:, -1:], pos)
    d8, c8b = m8.decode_step(params, c8, toks[:, -1:], pos)
    assert c8b["k"].dtype == jnp.int8
    rel = float(jnp.max(jnp.abs(d0 - d8)) / jnp.max(jnp.abs(d0)))
    assert rel < 0.05, rel


def test_moe_small_t_path_matches_local():
    """§Perf hillclimb #2: the 2D weight-stationary decode MoE equals the
    single-device computation (dropless both sides)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a mesh")


def _plain_decode(p, x, cache, layer, pos, cfg, *, use_rope=True,
                  ring=False):
    """Reference for `attention.decode`: plain softmax attention of each
    head over its kv head's rows of one layer, the rows viewed as
    (B, W, K, hd) and dequantized (int8) before the dots, in float32."""
    from repro.models.common import rope
    B, W = x.shape[0], cache["k"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    H, K, hd = q.shape[1], k.shape[1], k.shape[2]
    slot = pos % W if ring else jnp.minimum(pos, W - 1)
    b = jnp.arange(B)
    new = dict(cache)
    for name, row in (("k", k), ("v", v)):
        if name + "sc" in cache:
            sc = jnp.maximum(jnp.max(jnp.abs(row.astype(jnp.float32)), -1)
                             / 127.0, 1e-8)
            row = jnp.clip(jnp.round(row.astype(jnp.float32) / sc[..., None]),
                           -127, 127)
            new[name + "sc"] = cache[name + "sc"].at[layer, b, slot].set(
                sc.astype(jnp.bfloat16))
        new[name] = cache[name].at[layer, b, slot].set(
            row.reshape(B, K * hd).astype(cache[name].dtype))
    kv = {}
    for name in ("k", "v"):
        rows = new[name][layer].reshape(B, W, K, hd).astype(jnp.float32)
        if name + "sc" in new:
            rows = rows * new[name + "sc"][layer].astype(jnp.float32)[..., None]
        kv[name] = rows
    valid = jnp.arange(W)[None] <= slot[:, None]
    if ring:
        valid = valid | (pos[:, None] >= W)
    heads = []
    for h in range(H):
        g = h // (H // K)
        s = jnp.einsum("bd,bwd->bw", q[:, h].astype(jnp.float32),
                       kv["k"][:, :, g]) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bw,bwd->bd", w, kv["v"][:, :, g]))
    o = jnp.stack(heads, axis=1).astype(x.dtype)
    return jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None], new


# every family whose decode attends over a K/V-head cache, at 2 kv heads
# so that each kv head serves a group of 2 query heads
STACKED_KV_CASES = {
    "dense": ("qwen2-0.5b", {}, 12),
    "dense_bf16": ("qwen2-0.5b", {"dtype": "bfloat16"}, 12),
    "int8": ("qwen2-0.5b", {"kv_dtype": "int8"}, 12),
    "ring": ("qwen2-0.5b", {"sliding_window": 8}, 8),
    "moe": ("mixtral-8x7b", {"sliding_window": 0, "capacity_factor": 8.0},
            12),
    "vlm": ("internvl2-1b", {}, 16),
    "hybrid": ("zamba2-2.7b", {}, 12),
    "audio": ("whisper-large-v3", {}, 12),
}


@pytest.mark.parametrize("n_steps", [1, 4], ids=["step", "chunk"])
@pytest.mark.parametrize("case", list(STACKED_KV_CASES))
def test_stacked_kv_decode_matches_plain_attention(case, n_steps,
                                                   monkeypatch):
    """The in-place decode over the stacked (L, B, W, K*hd) cache gives
    the logits and the cache that plain per-layer softmax attention over
    the same rows gives: one step's logits, then the whole cache after
    `n_steps` decode steps of one decode_loop (each step's rows of every
    layer but the last depend on the attention below them). Slots sit at
    different positions; the ring case crosses its wrap."""
    from repro.models.model import Model
    arch, over, W = STACKED_KV_CASES[case]
    cfg = dataclasses.replace(get_config(arch).reduced(), n_kv_heads=2,
                              **{"dtype": "float32", **over})
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 6
    batch = {"tokens": jax.random.randint(jax.random.key(1), (B, S), 0,
                                          cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.key(2), (B, cfg.n_patches, cfg.d_model)).astype(
                cfg.dtype)
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.key(2), (B, cfg.enc_frames, cfg.d_model)).astype(
                cfg.dtype)
    _, cache, pos = m.prefill(params, batch, W=W)
    pos = pos - jnp.asarray([0, 2], jnp.int32)
    assert cache["k"].shape == (cache["k"].shape[0], B, W, 2 * cfg.hd())
    toks = jax.random.randint(jax.random.key(3), (n_steps, B), 0,
                              cfg.vocab_size)

    def run():
        logits, _ = m.decode_step(params, cache, toks[0][:, None], pos)
        out = m.decode_loop(params, cache, toks[0][:, None], pos,
                            jnp.zeros((B,), jnp.int32),
                            jnp.full((B,), n_steps + 1, jnp.int32),
                            jnp.zeros((B,), bool), jnp.full((B,), -1),
                            lambda lg, t: t, toks, n_tokens=n_steps)
        return logits, out[0]

    logits, after = run()
    monkeypatch.setattr(attention, "decode", _plain_decode)
    ref_logits, ref_after = run()
    tol = 2e-2 if cfg.dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=tol, atol=tol)
    assert set(after) == set(ref_after)
    for name in after:
        np.testing.assert_allclose(np.asarray(after[name], np.float32),
                                   np.asarray(ref_after[name], np.float32),
                                   rtol=tol, atol=tol, err_msg=name)
