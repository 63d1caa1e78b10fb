"""Transformer blocks: dense (attn + gated MLP), MoE, encoder and
decoder-with-cross-attention variants. Residual wiring + norms live here;
attention math in attention.py, MoE math in moe.py.

Every block exposes init / specs / apply(+decode) with params as plain
dicts so model.py can stack them over layers and lax.scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention, moe
from repro.models.common import (act_fn, dense_init, dtype_of, norm,
                                 norm_init, norm_specs, shard_act)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    return {
        "w1": dense_init(ks[0], (d, f), dt),
        "w3": dense_init(ks[1], (d, f), dt),
        "w2": dense_init(ks[2], (f, d), dt, scale=1.0 / np.sqrt(f)),
    }


def mlp_specs(cfg):
    return {"w1": ("embed", "mlp"), "w3": ("embed", "mlp"), "w2": ("mlp", "embed")}


# prefill's feed-forward runs this many tokens at a time (see ffn_chunked)
PREFILL_FFN_TOKENS = 8192


def ffn_chunked(fn, h):
    """A token-wise feed-forward `fn` over h (B, S, d), in chunks of
    PREFILL_FFN_TOKENS tokens (one lax.map step each) when the batch holds
    more and they divide it, so that a long prefill's widest activations
    (a dense MLP's hidden width, a dropless MoE's top_k gathered rows a
    token) are a chunk's; whole otherwise."""
    n, (B, S, d) = PREFILL_FFN_TOKENS, h.shape
    if B * S <= n or (B * S) % n:
        return fn(h)
    return jax.lax.map(fn, h.reshape(B * S // n, 1, n, d)).reshape(B, S, d)


def mlp_apply(p, x, cfg):
    act = act_fn(cfg.act)
    h = act(jnp.einsum("bsd,df->bsf", x, p["w1"])) * jnp.einsum(
        "bsd,df->bsf", x, p["w3"])
    h = shard_act(h, "batch", "seq", "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    return shard_act(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Self-attention of a decoder block: GQA, or latent attention (MLA) whose
# cache rows are (c_kv, k_pe) in place of (k, v)
# ---------------------------------------------------------------------------

def attn_init(key, cfg):
    if cfg.attn_kind == "mla":
        return attention.mla_init(key, cfg)
    return attention.init(key, cfg)


def attn_specs(cfg):
    if cfg.attn_kind == "mla":
        return attention.mla_specs(cfg)
    return attention.specs(cfg)


def attend(p, x, positions, cfg, block_skip=False):
    """Full-sequence self-attention: (out, cache rows of the layer)."""
    if cfg.attn_kind == "mla":
        return attention.mla_attend_train(p, x, positions, cfg,
                                          block_skip=block_skip)
    a, k, v = attention.attend_train(p, x, positions, cfg,
                                     block_skip=block_skip)
    return a, (k, v)


# ---------------------------------------------------------------------------
# Dense decoder block (llama/qwen/minicpm/internvl2 backbone; the leading
# dense layers of deepseek-v2)
# ---------------------------------------------------------------------------

def dense_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {
        "n1": norm_init(cfg),
        "attn": attn_init(ks[0], cfg),
        "n2": norm_init(cfg),
        "mlp": mlp_init(ks[1], cfg),
    }


def dense_block_specs(cfg):
    return {
        "n1": norm_specs(cfg),
        "attn": attn_specs(cfg),
        "n2": norm_specs(cfg),
        "mlp": mlp_specs(cfg),
    }


def dense_block_apply(p, x, positions, cfg, block_skip=False):
    a, _ = attend(p["attn"], norm(x, p["n1"], cfg), positions, cfg,
                  block_skip=block_skip)
    x = x + a
    return x + mlp_apply(p["mlp"], norm(x, p["n2"], cfg), cfg)


def dense_block_prefill(p, x, positions, cfg):
    a, rows = attend(p["attn"], norm(x, p["n1"], cfg), positions, cfg)
    x = x + a
    y = ffn_chunked(lambda h: mlp_apply(p["mlp"], h, cfg),
                    norm(x, p["n2"], cfg))
    return x + y, rows


# ---------------------------------------------------------------------------
# MoE decoder block (mixtral / arctic / deepseek-v2). arctic adds a parallel
# dense residual MLP alongside the MoE FFN (dense-MoE hybrid); deepseek-v2
# adds shared experts, one SwiGLU of n_shared x the expert width that every
# token passes through.
# ---------------------------------------------------------------------------

def moe_block_init(key, cfg):
    ks = jax.random.split(key, 4)
    p = {
        "n1": norm_init(cfg),
        "attn": attn_init(ks[0], cfg),
        "n2": norm_init(cfg),
        "moe": moe.init(ks[1], cfg),
    }
    if cfg.moe_dense_ff:
        p["dense_mlp"] = mlp_init(ks[2], cfg, d_ff=cfg.moe_dense_ff)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[3], cfg,
                               d_ff=cfg.n_shared_experts * cfg.expert_ff())
    return p


def moe_block_specs(cfg):
    p = {
        "n1": norm_specs(cfg),
        "attn": attn_specs(cfg),
        "n2": norm_specs(cfg),
        "moe": moe.specs(cfg),
    }
    if cfg.moe_dense_ff:
        p["dense_mlp"] = mlp_specs(cfg)
    if cfg.n_shared_experts:
        p["shared"] = mlp_specs(cfg)
    return p


def _moe_ffn(p, h, cfg, mesh, dropless=False):
    """The routed experts plus any dense residual or shared experts; the
    second output is moe.apply's (held assignments when dropless)."""
    y, aux = moe.apply(p["moe"], h, cfg, mesh=mesh, dropless=dropless)
    if cfg.moe_dense_ff:
        y = y + mlp_apply(p["dense_mlp"], h, cfg)
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], h, cfg)
    return y, aux


def moe_block_apply(p, x, positions, cfg, mesh=None, block_skip=False):
    a, _ = attend(p["attn"], norm(x, p["n1"], cfg), positions, cfg,
                  block_skip=block_skip)
    x = x + a
    y, aux = _moe_ffn(p, norm(x, p["n2"], cfg), cfg, mesh)
    return x + y, aux


def moe_block_prefill(p, x, positions, cfg, mesh=None):
    """Prefill routes dropless; the aux loss is a training term (0)."""
    a, rows = attend(p["attn"], norm(x, p["n1"], cfg), positions, cfg)
    x = x + a
    y = ffn_chunked(lambda h: _moe_ffn(p, h, cfg, mesh, dropless=True)[0],
                    norm(x, p["n2"], cfg))
    return x + y, rows, jnp.float32(0.0)


def kv_block_decode(p, x, cache, layer, pos, cfg, mesh=None, ring=False):
    """One GQA block's decode step (dense if it has an "mlp", else MoE)
    against the stacked K/V-head cache, which it updates in place at
    `layer`. Returns (y, cache, held): each slot's assignments to the
    experts held here (B,), 0 for a dense block."""
    a, cache = attention.decode(p["attn"], norm(x, p["n1"], cfg), cache,
                                layer, pos, cfg, ring=ring)
    x = x + a
    h = norm(x, p["n2"], cfg)
    if "mlp" in p:
        return (x + mlp_apply(p["mlp"], h, cfg), cache,
                jnp.zeros((x.shape[0],), jnp.int32))
    y, held = _moe_ffn(p, h, cfg, mesh, dropless=True)
    return x + y, cache, held[:, 0]


def latent_block_decode(p, x, cache, layer, pos, cfg, mesh=None):
    """One MLA block's decode step (dense if it has an "mlp", else MoE)
    against the whole latent cache, which it updates in place at
    `layer`. Returns (y, cache, held): held as in kv_block_decode, 0
    for a dense block."""
    a, cache = attention.mla_decode(p["attn"], norm(x, p["n1"], cfg), cache,
                                    layer, pos, cfg)
    x = x + a
    h = norm(x, p["n2"], cfg)
    if "mlp" in p:
        return (x + mlp_apply(p["mlp"], h, cfg), cache,
                jnp.zeros((x.shape[0],), jnp.int32))
    y, held = _moe_ffn(p, h, cfg, mesh, dropless=True)
    return x + y, cache, held[:, 0]


# ---------------------------------------------------------------------------
# Encoder block (whisper encoder: bidirectional, layernorm+gelu)
# ---------------------------------------------------------------------------

def enc_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {
        "n1": norm_init(cfg),
        "attn": attention.init(ks[0], cfg),
        "n2": norm_init(cfg),
        "mlp": mlp_init(ks[1], cfg),
    }


enc_block_specs = dense_block_specs


def enc_block_apply(p, x, cfg):
    a, _, _ = attention.attend_train(p["attn"], norm(x, p["n1"], cfg), None, cfg,
                                     use_rope=False, causal=False)
    x = x + a
    return x + mlp_apply(p["mlp"], norm(x, p["n2"], cfg), cfg)


# ---------------------------------------------------------------------------
# Decoder block with cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def xdec_block_init(key, cfg):
    ks = jax.random.split(key, 3)
    return {
        "n1": norm_init(cfg),
        "attn": attention.init(ks[0], cfg),
        "n2": norm_init(cfg),
        "xattn": attention.init(ks[1], cfg),
        "n3": norm_init(cfg),
        "mlp": mlp_init(ks[2], cfg),
    }


def xdec_block_specs(cfg):
    return {
        "n1": norm_specs(cfg),
        "attn": attention.specs(cfg),
        "n2": norm_specs(cfg),
        "xattn": attention.specs(cfg),
        "n3": norm_specs(cfg),
        "mlp": mlp_specs(cfg),
    }


def xdec_block_apply(p, x, enc_out, positions, cfg):
    a, k, v = attention.attend_train(p["attn"], norm(x, p["n1"], cfg), positions,
                                     cfg, use_rope=False)
    x = x + a
    xk, xv = attention.cross_kv(p["xattn"], enc_out)
    x = x + attention.cross_attend_train(p["xattn"], norm(x, p["n2"], cfg),
                                         (xk, xv), cfg)
    return x + mlp_apply(p["mlp"], norm(x, p["n3"], cfg), cfg), (k, v), (xk, xv)


def xdec_block_decode(p, x, cache, layer, xk, xv, pos, cfg):
    """Self-attention against the stacked cache (updated in place at
    `layer`), cross-attention against this layer's encoder K/V."""
    a, cache = attention.decode(p["attn"], norm(x, p["n1"], cfg), cache,
                                layer, pos, cfg, use_rope=False)
    x = x + a
    x = x + attention.cross_decode(p["xattn"], norm(x, p["n2"], cfg), xk, xv)
    return x + mlp_apply(p["mlp"], norm(x, p["n3"], cfg), cfg), cache
