"""Model facade: one class driving all 10 assigned architectures.

Families:
  dense / vlm   - stacked dense decoder blocks (vlm prepends patch embeds)
  moe           - stacked MoE decoder blocks (arctic adds dense residual;
                  deepseek-v2: shared experts, `first_k_dense` leading
                  dense blocks before the stack, latent attention with a
                  latent cache (c_kv, k_pe) updated in place in decode)
  hybrid        - zamba2: 54 Mamba2 layers + ONE shared attn+MLP block
                  applied after every `attn_every` Mamba layers
  ssm           - xlstm: groups of (slstm_every-1) mLSTM + 1 sLSTM
  audio         - whisper: encoder (frames stub) + cross-attn decoder

All layer stacks are lax.scan over STACKED params (compile-time constant
HLO size regardless of depth). remat policy per cfg.remat.

API:
  init(key)                                -> params
  param_specs()                            -> logical-axis tree
  loss(params, batch)                      -> (scalar, metrics)
  prefill(params, batch, W[, into])        -> (logits_last, cache, pos)
  decode_step(params, cache, token, pos)   -> (logits, cache)
                                              (+ held (B,), routed=True)
  decode_loop(params, cache, token, pos, emitted, max_new, done, eos,
              sample_fn, keys, n_tokens=K) -> fused K-token decode scan
  init_cache(B, W)                         -> zeroed cache tree (K/V rows
                                              (L, B, W, K*hd), decoded in
                                              place)
  cache_specs(W)                           -> logical-axis tree for cache
  param_count(active_only=False)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention, moe, ssm, transformer as tfm, xlstm
from repro.models.common import (chunked_softmax_xent, dense_init, dtype_of,
                                 norm, norm_init, norm_specs, shard_act,
                                 sinusoid_at, sinusoidal_positions)

Params = Dict[str, Any]


def _stack_init(fn, key, n, *args):
    return jax.vmap(lambda k: fn(k, *args))(jax.random.split(key, n))


def _add_layer_axis(tree):
    return jax.tree.map(lambda s: ("layers",) + tuple(s),
                        tree, is_leaf=lambda s: isinstance(s, tuple))


def _remat(fn, mode):
    if mode == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


class Model:
    def __init__(self, cfg, mesh=None, block_skip=False):
        self.cfg = cfg
        self.mesh = mesh
        self.block_skip = block_skip

    # ------------------------------------------------------------------
    # init / specs
    # ------------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        dt = dtype_of(cfg)
        k_emb, k_blocks, k_head, k_extra = jax.random.split(key, 4)
        p: Params = {
            "embed": dense_init(k_emb, (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
            "final_norm": norm_init(cfg),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)

        fam = cfg.family
        if fam in ("dense", "vlm"):
            p["blocks"] = _stack_init(tfm.dense_block_init, k_blocks, cfg.n_layers, cfg)
        elif fam == "moe":
            k0 = cfg.first_k_dense
            if k0:
                p["dense_blocks"] = _stack_init(tfm.dense_block_init,
                                                k_extra, k0, cfg)
            p["blocks"] = _stack_init(tfm.moe_block_init, k_blocks,
                                      cfg.n_layers - k0, cfg)
        elif fam == "hybrid":
            p["mamba"] = _stack_init(ssm.init, k_blocks, cfg.n_layers, cfg)
            p["shared_attn"] = tfm.dense_block_init(k_extra, cfg)
        elif fam == "ssm":
            n_s = cfg.n_layers // cfg.slstm_every
            n_m = cfg.n_layers - n_s
            p["mlstm"] = _stack_init(xlstm.m_init, k_blocks, n_m, cfg)
            p["slstm"] = _stack_init(xlstm.s_init, k_extra, n_s, cfg)
        elif fam == "audio":
            p["enc"] = _stack_init(tfm.enc_block_init, k_extra, cfg.n_enc_layers, cfg)
            p["enc_norm"] = norm_init(cfg)
            p["dec"] = _stack_init(tfm.xdec_block_init, k_blocks, cfg.n_layers, cfg)
        else:
            raise ValueError(fam)
        return p

    def param_specs(self):
        cfg = self.cfg
        p = {"embed": ("vocab", "embed_fsdp"), "final_norm": norm_specs(cfg)}
        if not cfg.tie_embeddings:
            p["unembed"] = ("embed_fsdp", "vocab")
        fam = cfg.family
        if fam in ("dense", "vlm"):
            p["blocks"] = _add_layer_axis(tfm.dense_block_specs(cfg))
        elif fam == "moe":
            if cfg.first_k_dense:
                p["dense_blocks"] = _add_layer_axis(tfm.dense_block_specs(cfg))
            p["blocks"] = _add_layer_axis(tfm.moe_block_specs(cfg))
        elif fam == "hybrid":
            p["mamba"] = _add_layer_axis(ssm.specs(cfg))
            p["shared_attn"] = tfm.dense_block_specs(cfg)
        elif fam == "ssm":
            p["mlstm"] = _add_layer_axis(xlstm.m_specs(cfg))
            p["slstm"] = _add_layer_axis(xlstm.s_specs(cfg))
        elif fam == "audio":
            p["enc"] = _add_layer_axis(tfm.enc_block_specs(cfg))
            p["enc_norm"] = norm_specs(cfg)
            p["dec"] = _add_layer_axis(tfm.xdec_block_specs(cfg))
        return p

    # ------------------------------------------------------------------
    # embedding helpers
    # ------------------------------------------------------------------
    def _embed(self, p, tokens):
        h = jnp.take(p["embed"], tokens, axis=0)
        return shard_act(h, "batch", "seq", None)

    def _unembed_w(self, p):
        return p["embed"].T if self.cfg.tie_embeddings else p["unembed"]

    def _logits_last(self, p, h_last):
        """h_last: (B, d) -> (B, V) fp32."""
        return jnp.einsum("bd,dv->bv", h_last, self._unembed_w(p),
                          preferred_element_type=jnp.float32)

    # ------------------------------------------------------------------
    # backbone: train forward (no caches)
    # ------------------------------------------------------------------
    def _backbone_train(self, p, h, positions):
        cfg, mesh = self.cfg, self.mesh
        fam = cfg.family
        aux = jnp.float32(0.0)

        if fam in ("dense", "vlm"):
            def body(x, bp):
                return tfm.dense_block_apply(bp, x, positions, cfg,
                                             block_skip=self.block_skip), None
            h, _ = jax.lax.scan(_remat(body, cfg.remat), h, p["blocks"])

        elif fam == "moe":
            if "dense_blocks" in p:
                def dbody(x, bp):
                    return tfm.dense_block_apply(
                        bp, x, positions, cfg,
                        block_skip=self.block_skip), None
                h, _ = jax.lax.scan(_remat(dbody, cfg.remat), h,
                                    p["dense_blocks"])

            def body(carry, bp):
                x, a = carry
                x, al = tfm.moe_block_apply(bp, x, positions, cfg, mesh=mesh,
                                            block_skip=self.block_skip)
                return (x, a + al), None
            (h, aux), _ = jax.lax.scan(_remat(body, cfg.remat), (h, aux), p["blocks"])

        elif fam == "hybrid":
            per = cfg.attn_every
            ng = cfg.n_layers // per
            mamba = jax.tree.map(
                lambda a: a.reshape(ng, per, *a.shape[1:]), p["mamba"])

            def inner(x, mp):
                return ssm.apply(mp, x, cfg) + x, None

            def group(x, gp):
                x, _ = jax.lax.scan(_remat(inner, cfg.remat), x, gp)
                x = tfm.dense_block_apply(p["shared_attn"], x, positions, cfg,
                                          block_skip=self.block_skip)
                return x, None
            h, _ = jax.lax.scan(group, h, mamba)

        elif fam == "ssm":
            per = cfg.slstm_every
            ng = cfg.n_layers // per
            ml = jax.tree.map(
                lambda a: a.reshape(ng, per - 1, *a.shape[1:]), p["mlstm"])

            def inner(x, mp):
                return xlstm.m_apply(mp, x, cfg) + x, None

            def group(x, gps):
                gm, gs = gps
                x, _ = jax.lax.scan(_remat(inner, cfg.remat), x, gm)
                x = x + xlstm.s_apply(gs, x, cfg)
                return x, None
            h, _ = jax.lax.scan(group, h, (ml, p["slstm"]))

        elif fam == "audio":
            raise RuntimeError("audio handled by _audio_train")
        return h, aux

    # ------------------------------------------------------------------
    # loss (train step forward)
    # ------------------------------------------------------------------
    def loss(self, p, batch):
        cfg = self.cfg
        fam = cfg.family
        tokens, labels = batch["tokens"], batch["labels"]
        B = tokens.shape[0]

        if fam == "audio":
            frames = batch["frames"].astype(dtype_of(cfg))
            F = frames.shape[1]
            pe = sinusoidal_positions(F, cfg.d_model).astype(frames.dtype)
            e = frames + pe[None]
            e = shard_act(e, "batch", "seq", None)

            def ebody(x, bp):
                return tfm.enc_block_apply(bp, x, cfg), None
            e, _ = jax.lax.scan(_remat(ebody, cfg.remat), e, p["enc"])
            enc_out = norm(e, p["enc_norm"], cfg)

            S = tokens.shape[1]
            h = self._embed(p, tokens)
            h = h + sinusoidal_positions(S, cfg.d_model).astype(h.dtype)[None]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))

            def dbody(x, bp):
                y, _, _ = tfm.xdec_block_apply(bp, x, enc_out, positions, cfg)
                return y, None
            h, _ = jax.lax.scan(_remat(dbody, cfg.remat), h, p["dec"])
            aux = jnp.float32(0.0)
        else:
            if fam == "vlm":
                patches = batch["patches"].astype(dtype_of(cfg))
                ht = self._embed(p, tokens)
                h = jnp.concatenate([patches, ht], axis=1)
                # loss only on text positions: pad labels with ignore_index
                pad = jnp.full((B, patches.shape[1]), -100, labels.dtype)
                labels = jnp.concatenate([pad, labels], axis=1)
            else:
                h = self._embed(p, tokens)
            S = h.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            h, aux = self._backbone_train(p, h, positions)

        h = norm(h, p["final_norm"], cfg)
        ce = chunked_softmax_xent(h, self._unembed_w(p), labels)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def kv_window(self, seq_len):
        cfg = self.cfg
        # ring caches are ALWAYS exactly sliding_window long: the ring
        # index math (slot = pos % W) and the prefill seeding both assume
        # W == cfg.sliding_window.
        return cfg.sliding_window if cfg.sliding_window else seq_len

    def init_cache(self, B, W):
        """Zeroed cache of B slots of W rows. A K/V-head cache holds one
        stacked array per K and V, (L, B, W, K*hd): each row's kv heads
        and head width merged in the minor dim as decode's dots read
        them, (L, B, W, K) for the int8 scales; decode carries it
        through the layer scan and writes each new row in place."""
        cfg = self.cfg
        dt = dtype_of(cfg)
        K, hd, L = cfg.n_kv_heads, cfg.hd(), cfg.n_layers
        C = K * hd
        fam = cfg.family
        if fam in ("dense", "vlm", "moe") and cfg.attn_kind == "mla":
            # a latent cache is kept in kv_dtype (int8 with per-row scales)
            rows = (L, B, W)
            cdt = jnp.dtype(cfg.kv_dtype)
            cache = {"ckv": jnp.zeros(rows + (cfg.kv_lora_rank,), cdt),
                     "kpe": jnp.zeros(rows + (cfg.qk_rope_dim,), cdt)}
            if self._int8_kv():
                cache.update(ckv_sc=jnp.zeros(rows, jnp.bfloat16),
                             kpe_sc=jnp.zeros(rows, jnp.bfloat16))
            return cache
        if fam in ("dense", "vlm", "moe"):
            W = self.kv_window(W)
            if self._int8_kv():
                return {"k": jnp.zeros((L, B, W, C), jnp.int8),
                        "v": jnp.zeros((L, B, W, C), jnp.int8),
                        "ksc": jnp.zeros((L, B, W, K), jnp.bfloat16),
                        "vsc": jnp.zeros((L, B, W, K), jnp.bfloat16)}
            return {"k": jnp.zeros((L, B, W, C), dt),
                    "v": jnp.zeros((L, B, W, C), dt)}
        if fam == "audio":
            # the cross cache (encoder K/V, written once by prefill) is
            # read per layer and keeps its (K, hd) minor dims
            F = cfg.enc_frames
            return {"k": jnp.zeros((L, B, W, C), dt),
                    "v": jnp.zeros((L, B, W, C), dt),
                    "xk": jnp.zeros((L, B, F, K, hd), dt),
                    "xv": jnp.zeros((L, B, F, K, hd), dt)}
        if fam == "hybrid":
            di, nh, cdim = ssm.dims(cfg)
            napp = cfg.n_layers // cfg.attn_every
            return {
                "conv": jnp.zeros((L, B, cfg.conv_kernel - 1, cdim), dt),
                "ssm": jnp.zeros((L, B, nh, cfg.ssm_headdim, cfg.ssm_state),
                                 jnp.float32),
                "k": jnp.zeros((napp, B, W, C), dt),
                "v": jnp.zeros((napp, B, W, C), dt),
            }
        if fam == "ssm":
            inner, nh, hq, hv = xlstm.m_dims(cfg)
            n_s = L // cfg.slstm_every
            n_m = L - n_s
            d = cfg.d_model
            return {
                "mconv": jnp.zeros((n_m, B, 3, inner), dt),
                "mC": jnp.zeros((n_m, B, nh, hq, hv), jnp.float32),
                "mN": jnp.zeros((n_m, B, nh, hq), jnp.float32),
                "mM": jnp.full((n_m, B, nh), -1e30, jnp.float32),
                "sh": jnp.zeros((n_s, B, d), jnp.float32),
                "sc": jnp.zeros((n_s, B, d), jnp.float32),
                "sn": jnp.zeros((n_s, B, d), jnp.float32),
                "sm": jnp.full((n_s, B, d), -1e30, jnp.float32),
            }
        raise ValueError(fam)

    def _int8_kv(self):
        # int8 KV: decode-path quantized cache (§Perf hillclimb #3).
        # ring buffers (SWA) keep bf16 (seeding rotates quantized rows).
        return (self.cfg.kv_dtype == "int8"
                and self.cfg.sliding_window == 0
                and self.cfg.family in ("dense", "vlm", "moe"))

    def cache_specs(self):
        fam = self.cfg.family
        # a K/V-head row's merged kv heads x head width (and an int8
        # row's per-head scales) map as kv heads
        kv = ("layers", "batch", "kv_seq", "kv_heads")
        if fam in ("dense", "vlm", "moe") and self.cfg.attn_kind == "mla":
            lat = {"ckv": ("layers", "batch", "kv_seq", "kv_lora"),
                   "kpe": ("layers", "batch", "kv_seq", None)}
            if self._int8_kv():
                lat.update(ckv_sc=kv[:3], kpe_sc=kv[:3])
            return lat
        if fam in ("dense", "vlm", "moe"):
            if self._int8_kv():
                return {"k": kv, "v": kv, "ksc": kv, "vsc": kv}
            return {"k": kv, "v": kv}
        if fam == "audio":
            return {"k": kv, "v": kv, "xk": kv + ("head_dim",),
                    "xv": kv + ("head_dim",)}
        if fam == "hybrid":
            return {"conv": ("layers", "batch", None, "conv_dim"),
                    "ssm": ("layers", "batch", "ssm_heads", None, None),
                    "k": kv, "v": kv}
        if fam == "ssm":
            return {"mconv": ("layers", "batch", None, "inner"),
                    "mC": ("layers", "batch", "heads", None, None),
                    "mN": ("layers", "batch", "heads", None),
                    "mM": ("layers", "batch", "heads"),
                    "sh": ("layers", "batch", "embed"),
                    "sc": ("layers", "batch", "embed"),
                    "sn": ("layers", "batch", "embed"),
                    "sm": ("layers", "batch", "embed")}
        raise ValueError(fam)

    # ------------------------------------------------------------------
    # prefill: full forward that also builds the cache; returns logits of
    # the last position. W (cache window) == padded cache length. With
    # `into=(cache, slots)` (a latent cache) each layer writes its rows
    # straight into those slots of the serving engine's cache instead, in
    # place, and that cache is returned.
    # ------------------------------------------------------------------
    def prefill(self, p, batch, W=None, into=None):
        cfg, mesh = self.cfg, self.mesh
        fam = cfg.family
        tokens = batch["tokens"]
        B, S = tokens.shape[0], None
        ring = cfg.sliding_window > 0

        def pad_kv(k):
            # k: (L, B, S, K, hd) -> rows (L, B, W_eff, K*hd); int8
            # scales (L, B, S, K) keep their K
            k = k.reshape(*k.shape[:3], -1)
            Sk = k.shape[2]
            W_eff = self.kv_window(W or Sk)
            if W_eff == Sk:
                return k
            return jnp.pad(k, ((0, 0), (0, 0), (0, W_eff - Sk), (0, 0)))

        if fam in ("dense", "vlm", "moe") and cfg.attn_kind == "mla":
            h = self._embed(p, tokens)
            S = h.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            h, cache = self._prefill_latent(p, h, positions, W or S, into)

        elif fam in ("dense", "vlm", "moe"):
            if fam == "vlm":
                patches = batch["patches"].astype(dtype_of(cfg))
                h = jnp.concatenate([patches, self._embed(p, tokens)], axis=1)
            else:
                h = self._embed(p, tokens)
            S = h.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))

            if fam == "moe":
                def body(x, bp):
                    y, (k, v), _ = tfm.moe_block_prefill(bp, x, positions, cfg,
                                                         mesh=mesh)
                    return y, (k, v)
            else:
                def body(x, bp):
                    y, (k, v) = tfm.dense_block_prefill(bp, x, positions, cfg)
                    return y, (k, v)
            h, (ks, vs) = jax.lax.scan(_remat(body, cfg.remat), h, p["blocks"])
            if ring:
                Wr = cfg.sliding_window
                merge = lambda a: a.reshape(*a.shape[:3], -1)
                seeded = jax.vmap(
                    lambda a, b: attention.seed_ring_cache(a, b, Wr))(
                        merge(ks), merge(vs))
                cache = {"k": seeded[0], "v": seeded[1]}
            elif self._int8_kv():
                # per-row-per-head scales, rows merged after quantizing
                kq, ksc = attention.quantize_kv(ks)
                vq, vsc = attention.quantize_kv(vs)
                cache = {"k": pad_kv(kq), "v": pad_kv(vq),
                         "ksc": pad_kv(ksc), "vsc": pad_kv(vsc)}
            else:
                cache = {"k": pad_kv(ks), "v": pad_kv(vs)}

        elif fam == "audio":
            frames = batch["frames"].astype(dtype_of(cfg))
            F = frames.shape[1]
            e = frames + sinusoidal_positions(F, cfg.d_model).astype(frames.dtype)[None]

            def ebody(x, bp):
                return tfm.enc_block_apply(bp, x, cfg), None
            e, _ = jax.lax.scan(_remat(ebody, cfg.remat), e, p["enc"])
            enc_out = norm(e, p["enc_norm"], cfg)

            S = tokens.shape[1]
            h = self._embed(p, tokens)
            h = h + sinusoidal_positions(S, cfg.d_model).astype(h.dtype)[None]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))

            def dbody(x, bp):
                y, (k, v), (xk, xv) = tfm.xdec_block_apply(bp, x, enc_out,
                                                           positions, cfg)
                return y, (k, v, xk, xv)
            h, (ks, vs, xks, xvs) = jax.lax.scan(_remat(dbody, cfg.remat), h,
                                                 p["dec"])
            cache = {"k": pad_kv(ks), "v": pad_kv(vs), "xk": xks, "xv": xvs}

        elif fam == "hybrid":
            h = self._embed(p, tokens)
            S = h.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            per = cfg.attn_every
            ng = cfg.n_layers // per
            mamba = jax.tree.map(lambda a: a.reshape(ng, per, *a.shape[1:]),
                                 p["mamba"])

            def inner(x, mp):
                y, cs, st = ssm.apply(mp, x, cfg, return_state=True)
                return x + y, (cs, st)

            def group(x, gp):
                x, (cs, st) = jax.lax.scan(_remat(inner, cfg.remat), x, gp)
                x, (k, v) = tfm.dense_block_prefill(p["shared_attn"], x,
                                                    positions, cfg)
                return x, (cs, st, k, v)
            h, (css, sts, ks, vs) = jax.lax.scan(group, h, mamba)
            cache = {
                "conv": css.reshape(cfg.n_layers, *css.shape[2:]),
                "ssm": sts.reshape(cfg.n_layers, *sts.shape[2:]),
                "k": pad_kv(ks), "v": pad_kv(vs),
            }

        elif fam == "ssm":
            h = self._embed(p, tokens)
            S = h.shape[1]
            per = cfg.slstm_every
            ng = cfg.n_layers // per
            ml = jax.tree.map(lambda a: a.reshape(ng, per - 1, *a.shape[1:]),
                              p["mlstm"])

            def inner(x, mp):
                y, (cs, st) = xlstm.m_apply(mp, x, cfg, return_state=True)
                return x + y, (cs, st)

            def group(x, gps):
                gm, gs = gps
                x, (cs, st) = jax.lax.scan(_remat(inner, cfg.remat), x, gm)
                y, sstate = xlstm.s_apply(gs, x, cfg, return_state=True)
                return x + y, (cs, st, sstate)
            h, (css, sts, sstates) = jax.lax.scan(group, h, (ml, p["slstm"]))
            n_m = cfg.n_layers - ng
            cache = {
                "mconv": css.reshape(n_m, *css.shape[2:]),
                "mC": sts[0].reshape(n_m, *sts[0].shape[2:]),
                "mN": sts[1].reshape(n_m, *sts[1].shape[2:]),
                "mM": sts[2].reshape(n_m, *sts[2].shape[2:]),
                "sh": sstates[0], "sc": sstates[1],
                "sn": sstates[2], "sm": sstates[3],
            }
        else:
            raise ValueError(fam)

        h = norm(h, p["final_norm"], cfg)
        logits = self._logits_last(p, h[:, -1])
        pos = jnp.full((B,), S, jnp.int32)
        return logits, cache, pos

    # ------------------------------------------------------------------
    # decode: one token against the cache
    # ------------------------------------------------------------------
    def decode_step(self, p, cache, token, pos, routed=False):
        """token: (B, 1) int32; pos: (B,) int32. Returns (logits, cache);
        with `routed` (MoE) also each slot's assignments to the experts
        held here, summed over layers (B,) int32. A K/V-head or latent
        cache rides in the layer scan's carry: each layer writes its new
        row at [layer, b, slot] (slot = pos, or pos % W in a ring) and
        attends over its rows in place, so the step makes no cache-sized
        copy."""
        cfg, mesh = self.cfg, self.mesh
        fam = cfg.family
        x = self._embed(p, token)
        ring = cfg.sliding_window > 0
        held = None

        if fam in ("dense", "vlm", "moe") and cfg.attn_kind == "mla":
            x, cache, held = self._decode_latent(p, cache, x, pos)

        elif fam in ("dense", "vlm", "moe"):
            # the stacked cache rides in the carry, each layer writing its
            # row in place; a MoE block also gives its held assignments
            def body(carry, xs):
                x, cache, held = carry
                bp, layer = xs
                y, cache, h = tfm.kv_block_decode(bp, x, cache, layer, pos,
                                                  cfg, mesh=mesh, ring=ring)
                return (y, cache, held + h), None
            (x, cache, held), _ = jax.lax.scan(
                body, (x, dict(cache), jnp.zeros((x.shape[0],), jnp.int32)),
                (p["blocks"], jnp.arange(cfg.n_layers)))

        elif fam == "audio":
            x = x + sinusoid_at(pos, cfg.d_model).astype(x.dtype)

            def body(carry, xs):
                x, kv = carry
                bp, xk, xv, layer = xs
                return tfm.xdec_block_decode(bp, x, kv, layer, xk, xv, pos,
                                             cfg), None
            (x, kv), _ = jax.lax.scan(
                body, (x, {"k": cache["k"], "v": cache["v"]}),
                (p["dec"], cache["xk"], cache["xv"],
                 jnp.arange(cfg.n_layers)))
            cache = dict(cache, **kv)

        elif fam == "hybrid":
            per = cfg.attn_every
            ng = cfg.n_layers // per
            r = lambda a: a.reshape(ng, per, *a.shape[1:])
            mamba = jax.tree.map(r, p["mamba"])

            def inner(x, xs):
                mp, cs, st = xs
                y, cs, st = ssm.decode_step(mp, x, cs, st, cfg)
                return x + y, (cs, st)

            # the shared block's application g attends over layer g of
            # the stacked cache, carried as in the dense families
            def group(carry, xs):
                x, kv = carry
                gp, gcs, gst, g = xs
                x, (cs, st) = jax.lax.scan(inner, x, (gp, gcs, gst))
                x, kv, _ = tfm.kv_block_decode(p["shared_attn"], x, kv, g,
                                               pos, cfg)
                return (x, kv), (cs, st)
            (x, kv), (css, sts) = jax.lax.scan(
                group, (x, {"k": cache["k"], "v": cache["v"]}),
                (mamba, r(cache["conv"]), r(cache["ssm"]), jnp.arange(ng)))
            cache = {"conv": css.reshape(cfg.n_layers, *css.shape[2:]),
                     "ssm": sts.reshape(cfg.n_layers, *sts.shape[2:]), **kv}

        elif fam == "ssm":
            per = cfg.slstm_every
            ng = cfg.n_layers // per
            rm = lambda a: a.reshape(ng, per - 1, *a.shape[1:])
            ml = jax.tree.map(rm, p["mlstm"])

            def inner(x, xs):
                mp, hist, C, n, m = xs
                y, hist, (C, n, m) = xlstm.m_decode(mp, x, hist, (C, n, m), cfg)
                return x + y, (hist, C, n, m)

            def group(x, xs):
                gm, hist, C, n, m, gs, sh, sc, sn, sm = xs
                x, (hist, C, n, m) = jax.lax.scan(inner, x,
                                                  (gm, hist, C, n, m))
                y, sstate = xlstm.s_decode(gs, x, (sh, sc, sn, sm), cfg)
                return x + y, (hist, C, n, m) + sstate
            x, outs = jax.lax.scan(
                group, x,
                (ml, rm(cache["mconv"]), rm(cache["mC"]), rm(cache["mN"]),
                 rm(cache["mM"]), p["slstm"], cache["sh"], cache["sc"],
                 cache["sn"], cache["sm"]))
            hist, C, n, m, sh, sc, sn, sm = outs
            n_m = cfg.n_layers - ng
            flat = lambda a: a.reshape(n_m, *a.shape[2:])
            cache = {"mconv": flat(hist), "mC": flat(C), "mN": flat(n),
                     "mM": flat(m), "sh": sh, "sc": sc, "sn": sn, "sm": sm}
        else:
            raise ValueError(fam)

        h = norm(x, p["final_norm"], cfg)
        logits = self._logits_last(p, h[:, -1])
        if routed:
            return logits, cache, held
        return logits, cache

    def _latent_rows(self, ckv, kpe):
        """Latent cache entries of rows (..., R) and (..., dr), in the
        cache's type (int8 with per-row scales)."""
        out = {}
        for name, rows in (("ckv", ckv), ("kpe", kpe)):
            if self._int8_kv():
                out[name], out[name + "_sc"] = attention.quantize_kv(rows)
            else:
                out[name] = rows.astype(self.cfg.kv_dtype)
        return out

    def _prefill_latent(self, p, h, positions, W, into=None):
        """Prefill's layers (the leading dense ones, then the stack), each
        writing its latent rows of the batch straight into the cache, the
        cache riding in the scan's carry so that no stack of every layer's
        rows is formed. `into=(cache, slots)`: the serving engine's cache
        and the batch's slots in it ((B,), out-of-range entries dropped),
        whose rows past the prompt keep what they held (decode writes each
        before it is attended); else a new cache of W rows. Returns
        (h, cache)."""
        cfg, (B, S) = self.cfg, h.shape[:2]
        cache, slots = into if into is not None else (
            self.init_cache(B, W), jnp.arange(B))
        first = 0
        for name in ("dense_blocks", "blocks"):
            if name not in p:
                continue
            n = jax.tree.leaves(p[name])[0].shape[0]

            def body(carry, xs, dense=name == "dense_blocks"):
                x, cache = carry
                bp, layer = xs
                if dense:
                    y, (c, k_pe) = tfm.dense_block_prefill(bp, x, positions,
                                                           cfg)
                else:
                    y, (c, k_pe), _ = tfm.moe_block_prefill(
                        bp, x, positions, cfg, mesh=self.mesh)
                for key, rows in self._latent_rows(c, k_pe).items():
                    cache[key] = cache[key].at[layer, slots, :S].set(rows)
                return (y, cache), None
            (h, cache), _ = jax.lax.scan(_remat(body, cfg.remat),
                                         (h, dict(cache)),
                                         (p[name], first + jnp.arange(n)))
            first += n
        return h, cache

    def _decode_latent(self, p, cache, x, pos):
        """Decode through the leading dense blocks, then the main stack,
        each layer updating its rows of the latent cache in place (the
        cache rides in the scan's carry, so no cache-sized copy is
        made). Returns (x, cache, held (B,))."""
        held = jnp.zeros((x.shape[0],), jnp.int32)
        first = 0
        for name in ("dense_blocks", "blocks"):
            if name not in p:
                continue
            n = jax.tree.leaves(p[name])[0].shape[0]

            def body(carry, xs):
                x, cache, held = carry
                bp, layer = xs
                y, cache, h = tfm.latent_block_decode(bp, x, cache, layer,
                                                      pos, self.cfg,
                                                      mesh=self.mesh)
                return (y, cache, held + h), None
            (x, cache, held), _ = jax.lax.scan(
                body, (x, cache, held), (p[name], first + jnp.arange(n)))
            first += n
        return x, cache, held

    # ------------------------------------------------------------------
    # decode loop: K fused decode+sample steps per host dispatch
    # ------------------------------------------------------------------
    def decode_loop(self, p, cache, token, pos, emitted, max_new, done, eos,
                    sample_fn, keys, *, n_tokens):
        """`n_tokens` decode steps fused into one lax.scan.

        token: (B, 1) int32 feedback tokens; pos / emitted / max_new /
        eos: (B,) int32 (eos < 0 means "no stop token"); done: (B,) bool;
        keys: (n_tokens,) PRNG keys; sample_fn(logits, key) -> (B,) int32
        (the engine closes it over per-slot temperature / top-k).

        Per-slot stop state is carried through the scan: finished slots
        freeze — their pos/emitted stop advancing and their feedback
        token is re-fed, so the repeated cache write at the frozen
        position is idempotent for KV families and only perturbs state
        the host will overwrite on re-admission for recurrent families.

        Returns (cache, token, pos, emitted, done, toks, live, held) with
        toks and live shaped (n_tokens, B): token k belongs to slot b's
        output stream iff live[k, b] (slots freeze monotonically, so the
        live column is a prefix mask). held (n_tokens, B): each step's
        assignments to the experts held here, for a MoE model (frozen
        slots included; mask with live); None otherwise.

        The carry signature is donation-safe: every carried array is
        returned with identical shape/dtype, so callers can jit with
        donate_argnums over (cache, token, pos, emitted, done) and the
        KV cache updates in place instead of round-tripping.
        """
        routed = self.cfg.family == "moe"

        def step(carry, key):
            cache, token, pos, emitted, done = carry
            if routed:
                logits, cache, held = self.decode_step(p, cache, token, pos,
                                                       routed=True)
            else:
                logits, cache = self.decode_step(p, cache, token, pos)
                held = None
            tok = sample_fn(logits, key)
            live = ~done
            tok = jnp.where(live, tok, token[:, 0]).astype(jnp.int32)
            inc = live.astype(jnp.int32)
            emitted = emitted + inc
            pos = pos + inc
            done = done | (emitted >= max_new) | (live & (eos >= 0) &
                                                  (tok == eos))
            return (cache, tok[:, None], pos, emitted, done), (tok, live,
                                                               held)

        (cache, token, pos, emitted, done), (toks, live, held) = \
            jax.lax.scan(step, (cache, token, pos, emitted, done), keys,
                         length=n_tokens)
        return cache, token, pos, emitted, done, toks, live, held

    # ------------------------------------------------------------------
    # counting
    # ------------------------------------------------------------------
    def param_count(self, active_only=False) -> int:
        shapes = jax.eval_shape(self.init, jax.random.key(0))
        total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        if active_only and self.cfg.n_experts:
            # routed experts held here, as many as a token reaches of them
            # in expectation (top_k / n_experts of each)
            cfg = self.cfg
            expert = (cfg.n_layers - cfg.first_k_dense) * cfg.n_held() * 3 \
                * cfg.d_model * cfg.expert_ff()
            total = total - expert + expert * cfg.top_k // cfg.n_experts
        return total
