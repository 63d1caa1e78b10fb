"""GQA attention: blocked-flash train/prefill path + KV-cache decode path.

The train/prefill path is a pure-JAX flash attention (online softmax over
KV chunks inside a lax.scan, q chunks via lax.map) so that 32k-token
prefill never materializes an (S, S) score matrix and the HLO stays small
(one while body per loop — see launch/hlo_analysis.py for trip-count-aware
costing).

`block_skip=True` enables causal block skipping (lax.cond around fully
masked KV blocks) — a §Perf hillclimb knob; baseline computes all blocks
with masking.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import dense_init, dtype_of, rope, shard_act

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
                    kv_len=None, chunk_q=512, chunk_kv=1024, block_skip=False):
    """q: (B, Sq, H, hd); k, v: (B, Skv, K, hd); H = K * G. Returns (B, Sq, H, hd).

    Online-softmax over KV chunks; fp32 accumulation; GQA via head groups.
    """
    B, Sq, H, hd = q.shape
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
    scale = 1.0 / np.sqrt(hd)

    qg = q.reshape(B, nq, cq, K, G, hd)
    kc = k.reshape(B, nkv, ckv, K, hd)
    vc = v.reshape(B, nkv, ckv, K, hd)

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
        a0 = jnp.zeros((B, K, G, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, hd)  # (B,cq,H,hd)

    if nq == 1:
        out = q_chunk_body(jnp.int32(0))[:, None]
    else:
        out = jax.lax.map(q_chunk_body, jnp.arange(nq))  # (nq, B, cq, H, hd)
        out = out.transpose(1, 0, 2, 3, 4)
    return out.reshape(B, Sq, H, hd)[:, :Sq0].astype(q.dtype)


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


def decode(p, x, cache_k, cache_v, pos, cfg, *, use_rope=True, ring=False,
           scales=None):
    """x: (B, 1, d); cache_k/v: (B, W, K, hd); pos: (B,) int32 current index.

    Returns (out (B,1,d), new_k_cache, new_v_cache[, new_scales]). If
    `ring`, the cache is a sliding-window ring buffer indexed by pos % W.
    `scales`: (ks, vs) each (B, W, K) for int8 caches (§Perf hillclimb #3:
    halves the decode-dominant cache-read traffic; dequant is folded into
    the score/value einsums so no bf16 cache copy materializes).
    """
    B, _, d = x.shape
    W = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)

    slot = jnp.mod(pos, W) if ring else jnp.minimum(pos, W - 1)
    bidx = jnp.arange(B)
    if scales is not None:
        ks, vs = scales
        kq, ksc = quantize_kv(k[:, 0])
        vq, vsc = quantize_kv(v[:, 0])
        cache_k = cache_k.at[bidx, slot].set(kq)
        cache_v = cache_v.at[bidx, slot].set(vq)
        ks = ks.at[bidx, slot].set(ksc)
        vs = vs.at[bidx, slot].set(vsc)
    else:
        cache_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))

    H, hd = q.shape[2], q.shape[3]
    K = cache_k.shape[2]
    G = H // K
    qg = q.reshape(B, 1, K, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg,
                   cache_k.astype(qg.dtype),
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if scales is not None:
        s = s * ks.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    slots = jnp.arange(W)
    if ring:
        valid = (slots[None] <= slot[:, None]) | (pos[:, None] >= W)
    else:
        valid = slots[None] <= slot[:, None]
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    if scales is not None:
        w = w * vs.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    o = jnp.einsum("bkgqs,bskh->bkgqh", w.astype(qg.dtype),
                   cache_v.astype(qg.dtype))
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, hd)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if scales is not None:
        return o, cache_k, cache_v, (ks, vs)
    return o, cache_k, cache_v


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
    """Convert full prefill K/V (B, S, K, hd) into a ring cache of size W
    positioned such that slot = pos % W, ready for decode at pos = S."""
    B, S, K, hd = k.shape
    W = window
    if S <= W:
        ck = jnp.zeros((B, W, K, hd), k.dtype).at[:, :S].set(k)
        cv = jnp.zeros((B, W, K, hd), v.dtype).at[:, :S].set(v)
        return ck, cv
    idx = np.mod(np.arange(S - W, S), W)
    ck = jnp.zeros((B, W, K, hd), k.dtype).at[:, idx].set(k[:, S - W:])
    cv = jnp.zeros((B, W, K, hd), v.dtype).at[:, idx].set(v[:, S - W:])
    return ck, cv
