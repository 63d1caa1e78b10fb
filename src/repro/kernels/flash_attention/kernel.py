"""Pallas TPU kernel: flash attention forward (causal, GQA).

Why this exists: the pure-JAX flash path materializes every (cq, ckv)
score/probability block in HBM — the dominant roofline term for every
attention-heavy cell. In this kernel the whole online-softmax tile
pipeline (scores -> max -> exp -> accumulate)
lives in VMEM; HBM traffic collapses to Q + K + V + O.

Grid: (B, K_heads, nq) — one program per (batch, kv-head, q-block),
looping over kv blocks with lax.fori_loop. Per-program VMEM footprint:
  q block   (G, bq, hd)            e.g. 4 x 256 x 128 x 4 B = 0.5 MiB
  k/v SEQ   2 x (Skv, hd) bf16     e.g. 2 x 32768 x 128 x 2 B = 16 MiB*
  scores    (G, bq, bkv) f32       e.g. 4 x 256 x 512 x 4 B = 2 MiB
(*) for Skv > ~8k at hd=128 the full-KV block exceeds v5e VMEM; callers
split KV externally (seq-parallel shard_map does this for free: each
model rank holds Skv/16). MXU alignment: bq, bkv, hd multiples of 128
preferred; smaller shapes run (padded lanes) but underfill the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, qoff_ref, o_ref, *, bkv, causal):
    # q: (1, bq, 1, G, hd) ; k/v: (1, Skv, 1, hd) ; o like q
    q = q_ref[0, :, 0].astype(jnp.float32)           # (bq, G, hd)
    bq, G, hd = q.shape
    Skv = k_ref.shape[1]
    nkv = Skv // bkv
    qi = pl.program_id(2)
    qpos = qoff_ref[0] + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq,), 0)
    scale = 1.0 / np.sqrt(hd)

    def body(j, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k_ref[0, :, 0], j * bkv, bkv, 0)
        vb = jax.lax.dynamic_slice_in_dim(v_ref[0, :, 0], j * bkv, bkv, 0)
        s = jax.lax.dot_general(
            q.reshape(bq * G, hd), kb.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(bq, G, bkv) * scale
        if causal:
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bkv,), 0)
            mask = kpos[None, :] <= qpos[:, None]
            s = jnp.where(mask[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.reshape(bq * G, bkv), vb.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(bq, G, hd)
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, G), jnp.float32)
    a0 = jnp.zeros((bq, G, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkv, body, (m0, l0, a0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    o_ref[0, :, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "causal",
                                             "interpret"))
def flash_attention_fwd(q, k, v, q_offset=0, *, bq=256, bkv=512,
                        causal=True, interpret=False):
    """q: (B, Sq, H, hd); k/v: (B, Skv, K, hd); H = K*G. Returns like q.
    Sq % bq == 0 and Skv % bkv == 0 required (callers pad)."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)
    qg = q.reshape(B, Sq, K, G, hd)
    qoff = jnp.asarray([q_offset], jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, bkv=bkv, causal=causal),
        grid=(B, K, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, 1, G, hd), lambda b, h, i: (b, i, h, 0, 0)),
            pl.BlockSpec((1, Skv, 1, hd), lambda b, h, i: (b, 0, h, 0)),
            pl.BlockSpec((1, Skv, 1, hd), lambda b, h, i: (b, 0, h, 0)),
            pl.BlockSpec((1,), lambda b, h, i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, G, hd), lambda b, h, i: (b, i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, K, G, hd), q.dtype),
        interpret=interpret,
    )(qg, k, v, qoff)
    return out.reshape(B, Sq, H, hd)
