"""GQA attention: blocked-flash train/prefill path + KV-cache decode path.

The train/prefill path is a pure-JAX flash attention (online softmax over
KV chunks inside a lax.scan, q chunks via lax.map) so that 32k-token
prefill never materializes an (S, S) score matrix and the HLO stays small
(one while body per loop — see launch/hlo_analysis.py for trip-count-aware
costing).

Decode reads and writes one stacked cache of every layer's rows, (L, B, W,
K*hd) with kv heads and head width merged in the minor dim, in place
(`decode` below).

`block_skip=True` enables causal block skipping (lax.cond around fully
masked KV blocks) — a §Perf hillclimb knob; baseline computes all blocks
with masking.

Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434) is the
second kind (`cfg.attn_kind == "mla"`, `mla_*` below): prefill expands
the latent `c_kv` to per-head keys and values and runs the same flash
path; decode is absorbed and reads and writes latent cache rows only.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import (dense_init, dtype_of, rms_norm, rope,
                                 shard_act)

NEG_INF = -1e30


def init(key, cfg, d_model=None, n_heads=None, n_kv_heads=None, cross=False):
    d = d_model or cfg.d_model
    H = n_heads or cfg.n_heads
    K = n_kv_heads or cfg.n_kv_heads
    hd = cfg.hd()
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H, hd), dt),
        "wk": dense_init(ks[1], (d, K, hd), dt),
        "wv": dense_init(ks[2], (d, K, hd), dt),
        "wo": dense_init(ks[3], (H, hd, d), dt, scale=1.0 / np.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((K, hd), dt)
        p["bv"] = jnp.zeros((K, hd), dt)
    return p


def specs(cfg):
    p = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("heads", "head_dim")
        p["bk"] = ("kv_heads", "head_dim")
        p["bv"] = ("kv_heads", "head_dim")
    return p


def _qkv(p, x, cfg, positions=None, use_rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = shard_act(q, "batch", "seq", "heads", "head_dim")
    k = shard_act(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard_act(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_len=None, chunk_q=512, chunk_kv=1024, block_skip=False,
                    scale=None):
    """q, k: (B, S, H or K, hd); v: (B, Skv, K, hv); H = K * G. Returns
    (B, Sq, H, hv). `scale` defaults to hd ** -0.5.

    Online-softmax over KV chunks; fp32 accumulation; GQA via head groups.
    """
    B, Sq, H, hd = q.shape
    hv = v.shape[-1]
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    cq = min(chunk_q, Sq)
    ckv = min(chunk_kv, Skv)
    kv_len = Skv if kv_len is None else kv_len
    # pad non-divisible sequence lengths (e.g. whisper's 1500 frames);
    # padded KV is masked via kv_len, padded q rows are sliced off.
    Sq0 = Sq
    if Sq % cq or Skv % ckv:
        Sqp = -(-Sq // cq) * cq
        Skvp = -(-Skv // ckv) * ckv
        q = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, Skvp - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skvp - Skv), (0, 0), (0, 0)))
        Sq, Skv = Sqp, Skvp
    nq, nkv = Sq // cq, Skv // ckv
    if scale is None:
        scale = 1.0 / np.sqrt(hd)

    qg = q.reshape(B, nq, cq, K, G, hd)
    kc = k.reshape(B, nkv, ckv, K, hd)
    vc = v.reshape(B, nkv, ckv, K, hv)

    def q_chunk_body(qi):
        qq = qg[:, qi]  # (B, cq, K, G, hd)
        qpos = q_offset + qi * cq + jnp.arange(cq)

        def kv_body(carry, kj):
            m, l, acc = carry

            # each (q-chunk, kv-chunk) tile is its own remat unit: the
            # backward recomputes s/p per tile (true flash backward) instead
            # of stacking (nq, nkv, B, K, G, cq, ckv) score residuals —
            # measured 14 GiB/device for qwen2 train_4k without this.
            @partial(jax.checkpoint,
                     policy=jax.checkpoint_policies.nothing_saveable)
            def compute(args):
                m, l, acc = args
                kk = jax.lax.dynamic_index_in_dim(kc, kj, 1, keepdims=False)
                vv = jax.lax.dynamic_index_in_dim(vc, kj, 1, keepdims=False)
                kpos = kj * ckv + jnp.arange(ckv)
                s = jnp.einsum("bqkgh,bskh->bkgqs", qq, kk,
                               preferred_element_type=jnp.float32) * scale
                mask = kpos[None, :] <= qpos[:, None] if causal else jnp.ones(
                    (cq, ckv), bool)
                if window:
                    mask &= (qpos[:, None] - kpos[None, :]) < window
                mask &= (kpos < kv_len)[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                # l in fp32 (sum of exps), but the materialized probability
                # BLOCK is bf16: halves the dominant HBM-traffic term
                # (§Perf hillclimb #1 iter 2); max-normalized exps lose
                # <1e-2 relative which is below bf16 matmul noise anyway.
                p32 = jnp.exp(s - m_new[..., None])
                l_new = l * jnp.exp(m - m_new) + jnp.sum(p32, axis=-1)
                p = p32.astype(vv.dtype)
                corr = jnp.exp(m - m_new)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bkgqs,bskh->bkgqh", p, vv,
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            if block_skip:
                needed = kj * ckv <= qpos[-1]
                if window:
                    needed &= (kj + 1) * ckv - 1 > qpos[0] - window
                carry = jax.lax.cond(needed, compute, lambda a: a, (m, l, acc))
            else:
                carry = compute((m, l, acc))
            return carry, None

        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, hv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, hv)  # (B,cq,H,hv)

    if nq == 1:
        out = q_chunk_body(jnp.int32(0))[:, None]
    else:
        out = jax.lax.map(q_chunk_body, jnp.arange(nq))  # (nq, B, cq, H, hv)
        out = out.transpose(1, 0, 2, 3, 4)
    return out.reshape(B, Sq, H, hv)[:, :Sq0].astype(q.dtype)


def _seqpar_flash(q, k, v, mesh, *, causal, window, block_skip):
    """Context-parallel flash: q's SEQUENCE dim sharded over 'model', k/v
    replicated over 'model' (they already are when the head count doesn't
    divide the axis). Each model rank computes its q slice against the
    full KV — zero collectives inside attention; the (9x-measured) win is
    that per-device score-block HBM traffic drops by the axis size."""
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    m = mesh.shape["model"]
    B, S, H, hd = q.shape
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    bspec = data_axes if (data_axes and B % max(
        1, int(np.prod([mesh.shape[a] for a in data_axes]))) == 0) else None

    def fn(ql, kl, vl):
        off = jax.lax.axis_index("model") * (S // m)
        return flash_attention(ql, kl, vl, causal=causal, window=window,
                               q_offset=off, block_skip=block_skip)

    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, "model", None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, "model", None, None),
        check_rep=False,
    )(q, k, v)


def _want_seqpar(cfg, q, k):
    from repro.models.common import current_mesh
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape or not cfg.attn_seqpar:
        return None
    m = mesh.shape["model"]
    H, S = q.shape[2], q.shape[1]
    if H % m == 0:          # heads shard fine; TP attention is better
        return None
    if S % m != 0 or S // m < 128:
        return None
    return mesh


def attend_train(p, x, positions, cfg, *, use_rope=True, causal=True,
                 block_skip=False):
    """Full training/prefill attention. Returns (out(B,S,d), k, v)."""
    q, k, v = _qkv(p, x, cfg, positions, use_rope)
    mesh = _want_seqpar(cfg, q, k)
    if mesh is not None:
        o = _seqpar_flash(q, k, v, mesh, causal=causal,
                          window=cfg.sliding_window, block_skip=block_skip)
    else:
        o = flash_attention(q, k, v, causal=causal, window=cfg.sliding_window,
                            block_skip=block_skip)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return shard_act(o, "batch", "seq", "embed"), k, v


def cross_attend_train(p, x, enc_kv, cfg):
    """Decoder cross-attention against precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = enc_kv
    o = flash_attention(q, k, v, causal=False)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return shard_act(o, "batch", "seq", "embed")


def cross_kv(p, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return k, v


# ---------------------------------------------------------------------------
# Decode path (one new token against a KV cache; ring buffer under SWA)
# ---------------------------------------------------------------------------

def quantize_kv(k, axis=-1):
    """Symmetric int8 per-token-per-head quantization.
    k: (..., hd) -> (int8 like k, scale (...,) bf16)."""
    s = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=axis) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.bfloat16)


def decode(p, x, cache, layer, pos, cfg, *, use_rope=True, ring=False):
    """Decode of one token against a stacked K/V-head cache, in place.

    x: (B, 1, d); cache: {"k", "v": (L, B, W, K*hd)}, each row's kv heads
    and head width merged in the minor dim as the dots read them (qwen2's
    2 x 64 fill the 128 lanes), and for an int8 cache per-row-per-head
    scales {"ksc", "vsc": (L, B, W, K)} (§Perf hillclimb #3: halves the
    decode-dominant cache-read traffic; dequant is folded into the
    scores and weights so no bf16 copy materializes); layer: this
    layer's index into it; pos: (B,) position. Writes the position's row
    at [layer, b, slot] (slot = pos % W if `ring`, a sliding-window ring
    buffer), then attends over the layer's rows as stored: each head's
    query sits in its kv group's hd lanes of a block-diagonal K*hd query
    (zeros elsewhere), so the scores are one dot against the rows and
    each head keeps its group's lanes of the weighted sum of rows. The
    dots read the rows where they lie: no transposed or reshaped copy,
    and no copy of the cache, which rides in the layer scan's carry.
    Returns (out (B, 1, d), cache).
    """
    B = x.shape[0]
    W = cache["k"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    H, K, hd = q.shape[2], k.shape[2], k.shape[3]
    G = H // K

    slot = jnp.mod(pos, W) if ring else jnp.minimum(pos, W - 1)
    bidx = jnp.arange(B)
    int8 = "ksc" in cache
    new = dict(cache)
    for name, row in (("k", k[:, 0]), ("v", v[:, 0])):
        if int8:
            row, sc = quantize_kv(row)
            new[name + "sc"] = cache[name + "sc"].at[layer, bidx,
                                                     slot].set(sc)
        new[name] = cache[name].at[layer, bidx, slot].set(
            row.reshape(B, K * hd).astype(cache[name].dtype))
    rows = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for n, a in new.items()}

    def per_group(s, sc):
        # s (B, H, W) times each head's kv-group scale, sc (B, W, K)
        s = s.reshape(B, K, G, W) * sc.astype(jnp.float32).transpose(
            0, 2, 1)[:, :, None]
        return s.reshape(B, H, W)

    dt = q.dtype
    own = (np.arange(H) // G)[:, None] == np.arange(K)     # (H, K)
    q_bd = jnp.where(own[:, :, None], q[:, 0, :, None], 0).reshape(
        B, H, K * hd)
    s = jnp.einsum("bhc,bwc->bhw", q_bd, rows["k"].astype(dt),
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if int8:
        s = per_group(s, rows["ksc"])
    valid = jnp.arange(W)[None] <= slot[:, None]
    if ring:
        valid |= pos[:, None] >= W
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    if int8:
        w = per_group(w, rows["vsc"])
    o = jnp.einsum("bhw,bwc->bhc", w.astype(dt), rows["v"].astype(dt))
    o = jnp.sum(jnp.where(own[:, :, None], o.reshape(B, H, K, hd), 0),
                axis=2)
    return jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None], new


def cross_decode(p, x, cross_k, cross_v, kv_len=None):
    """Cross-attention during decode (static encoder cache)."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    H, hd = q.shape[2], q.shape[3]
    K = cross_k.shape[2]
    qg = q.reshape(B, 1, K, H // K, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, cross_k,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", w.astype(cross_v.dtype), cross_v)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, hd)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def seed_ring_cache(k, v, window):
    """Convert full prefill rows (B, S, ...) into a ring cache of W rows
    positioned such that slot = pos % W, ready for decode at pos = S."""
    B, S = k.shape[:2]
    W = window
    ring = lambda a: jnp.zeros((B, W) + a.shape[2:], a.dtype)
    if S <= W:
        return ring(k).at[:, :S].set(k), ring(v).at[:, :S].set(v)
    idx = np.mod(np.arange(S - W, S), W)
    return (ring(k).at[:, idx].set(k[:, S - W:]),
            ring(v).at[:, idx].set(v[:, S - W:]))


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA). Per token: q = x Wq gives each head a
# no-rope part (qk_nope_dim) and a rope part (qk_rope_dim); x Wkv_a gives
# the latent c_kv (kv_lora_rank), RMS-normed, and ONE rope key k_pe shared
# by every head. Per-head keys are [c_kv Wk_b, k_pe] and values c_kv Wv_b
# (HF's kv_b_proj, split into its key and value halves). Rope pairs are
# interleaved as in the HF code: pair i is (x[2i], x[2i+1]), and the
# rotated vector comes out de-interleaved ([rotated evens, rotated odds]);
# q and k take the same layout, so their product is the paper's.
# ---------------------------------------------------------------------------

def yarn(cfg):
    """(inverse frequencies of the rope pairs (f32, qk_rope_dim/2),
    cos/sin scale, softmax scale) under the config's YaRN scaling
    (`rope_scaling`; plain rope and qk_head_dim ** -0.5 without it)."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    rs = dict(cfg.rope_scaling)
    if not rs:
        return inv, 1.0, scale
    f = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    def mscale(m):
        return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0
    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo)
                   / (hi - lo if hi > lo else 0.001), 0, 1)
    keep = (1.0 - ramp).astype(np.float32)   # 1: extrapolate, 0: interpolate
    inv = (inv / np.float32(f)) * (1 - keep) + inv * keep
    return (inv.astype(np.float32),
            mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]),
            scale * mscale(rs["mscale_all_dim"]) ** 2)


def rope_pairs(x, positions, inv_freq, mscale=1.0):
    """x: (..., S, H, dr), positions broadcastable to (..., S); pairs
    (x[2i], x[2i+1]) rotate at inv_freq[i], out de-interleaved."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * mscale)[..., None, :]
    sin = (jnp.sin(ang) * mscale)[..., None, :]
    xe = x[..., 0::2].astype(jnp.float32)
    xo = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin],
                           axis=-1).astype(x.dtype)


def mla_init(key, cfg):
    d, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, H, dn + dr), dt),
        "wkv_a": dense_init(ks[1], (d, R + dr), dt),
        "kv_norm": jnp.ones((R,), dt),
        "wk_b": dense_init(ks[2], (R, H, dn), dt),
        "wv_b": dense_init(ks[3], (R, H, dv), dt),
        "wo": dense_init(ks[4], (H, dv, d), dt, scale=1.0 / np.sqrt(H * dv)),
    }


def mla_specs(cfg):
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wkv_a": ("embed", "kv_lora"),
        "kv_norm": ("kv_lora",),
        "wk_b": ("kv_lora", "heads", "head_dim"),
        "wv_b": ("kv_lora", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def _mla_project(p, x, positions, cfg):
    """x: (B, S, d) -> q_nope (B,S,H,dn), q_pe (B,S,H,dr), c_kv (B,S,R),
    k_pe (B,S,dr), softmax scale; rope applied."""
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_dim
    inv, msc, scale = yarn(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c = rms_norm(kv[..., :R], p["kv_norm"], cfg.norm_eps)
    k_pe = rope_pairs(kv[..., None, R:], positions, inv, msc)[..., 0, :]
    q_pe = rope_pairs(q[..., dn:], positions, inv, msc)
    return q[..., :dn], q_pe, c, k_pe, scale


def mla_attend_train(p, x, positions, cfg, *, block_skip=False):
    """Full training/prefill MLA, unabsorbed: c_kv expanded to per-head
    keys and values. Returns (out (B,S,d), (c_kv, k_pe)), the latter
    the layer's cache rows."""
    q_nope, q_pe, c, k_pe, scale = _mla_project(p, x, positions, cfg)
    B, S, H, _ = q_nope.shape
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c, p["wv_b"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H,
                                                    k_pe.shape[-1]))], -1)
    o = flash_attention(q, k, v, causal=True, block_skip=block_skip,
                        scale=scale)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return shard_act(o, "batch", "seq", "embed"), (c, k_pe)


def mla_decode(p, x, cache, layer, pos, cfg):
    """Absorbed decode of one token against the whole latent cache.

    x: (B, 1, d); cache: {"ckv": (L, B, W, R), "kpe": (L, B, W, dr)} and,
    for an int8 cache, per-row scales "ckv_sc", "kpe_sc" (L, B, W);
    layer: this layer's index into it; pos: (B,) position. Writes the
    position's row into the layer's rows in place, then attends over
    them: Wk_b is folded into the query (q_lat = Wk_b q_nope, scores
    q_lat . c_kv + q_pe . k_pe) and Wv_b applied after the weighted sum
    of latent rows. No per-head key or value is formed. Returns
    (out (B, 1, d), cache)."""
    q_nope, q_pe, c, k_pe, scale = _mla_project(p, x, pos[:, None], cfg)
    B, W = x.shape[0], cache["ckv"].shape[2]
    slot = jnp.minimum(pos, W - 1)
    bidx = jnp.arange(B)
    int8 = "ckv_sc" in cache
    new = dict(cache)
    for name, row in (("ckv", c[:, 0]), ("kpe", k_pe[:, 0])):
        if int8:
            row, sc = quantize_kv(row)
            new[name + "_sc"] = cache[name + "_sc"].at[layer, bidx,
                                                       slot].set(sc)
        new[name] = cache[name].at[layer, bidx, slot].set(
            row.astype(cache[name].dtype))
    rows = {k: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for k, a in new.items()}
    dt = q_nope.dtype
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["wk_b"])
    s_c = jnp.einsum("bhr,bwr->bhw", q_lat, rows["ckv"].astype(dt),
                     preferred_element_type=jnp.float32)
    s_r = jnp.einsum("bhk,bwk->bhw", q_pe[:, 0], rows["kpe"].astype(dt),
                     preferred_element_type=jnp.float32)
    if int8:
        s_c = s_c * rows["ckv_sc"].astype(jnp.float32)[:, None]
        s_r = s_r * rows["kpe_sc"].astype(jnp.float32)[:, None]
    s = (s_c + s_r) * scale
    valid = jnp.arange(W)[None] <= slot[:, None]
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    if int8:
        w = w * rows["ckv_sc"].astype(jnp.float32)[:, None]
    o_lat = jnp.einsum("bhw,bwr->bhr", w.astype(dt), rows["ckv"].astype(dt))
    o = jnp.einsum("bhr,rhk->bhk", o_lat, p["wv_b"])
    return jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None], new
