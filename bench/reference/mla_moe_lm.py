"""Plain reference of a DeepSeek-V2 decoder (arXiv:2405.04434): latent
attention (MLA) and routed plus shared experts.

The forward pass of one sequence in straightforward `jax.numpy`, in
float32 under `jax.default_matmul_precision("highest")`, one layer at a
time, attention in blocks of query positions so that some thousands of
tokens fit. No cache, no batching, no kernel; nothing of the program is
imported.

Per layer: h += MLA(RMSNorm(h)); h += FFN(RMSNorm(h)).

  MLA, written as the paper writes it (no absorption): q = x Wq per head
  [q_nope | q_pe]; x Wkv_a = [c | k_pe'], c = RMSNorm(c) (its own gain
  `kv_norm`), k_pe = rope(k_pe'), one rope key shared by the heads; per
  head k = [c Wk_b | k_pe], v = c Wv_b; causal softmax of q.k times
  qk_head_dim ** -0.5 x mscale(mscale_all_dim) ** 2; output heads Wo.
  Rope: YaRN (HF `DeepseekV2YarnRotaryEmbedding`: inverse frequencies
  blended between extrapolation and interpolation by `factor` over the
  ramp from beta_fast to beta_slow rotations at the original context,
  cos and sin scaled by mscale / mscale_all_dim); pairs interleaved as in
  the HF code, (x[2i], x[2i+1]) at frequency i, output de-interleaved.
  FFN of the first `first_k_dense` layers: SwiGLU down(silu(x W1) * x W3).
  FFN of the others: the router's softmax over all `n_experts` logits
  (float32), its top_k experts (greedy), gates the raw probabilities
  (renormalized only if `norm_topk`; routed_scaling_factor 1); the
  routed experts held here (`experts_held`, from expert 0; all when 0)
  each add gate x SwiGLU_e(x) for the tokens routed to them, computed
  expert by expert over every token with a zero gate elsewhere; plus the
  shared experts, one SwiGLU.

Then a final RMSNorm and the untied unembedding. Its one contact with
the program is the layout of the parameter tree it is handed: `embed`,
`unembed`, `final_norm`, `dense_blocks` (leading dense layers) and
`blocks`, each stacked over layers, with `n1`, `attn` {wq, wkv_a,
kv_norm, wk_b, wv_b, wo}, `n2` and `mlp` {w1 gate, w3 up, w2 down}, or
`moe` {router, w1, w3, w2 over the held experts} and `shared`.

Departures from the published model: the weights are random, not the
released checkpoint; HF's kv_b_proj is split into its key half (Wk_b)
and value half (Wv_b); only the held experts' part of the routed output
is added (the part one chip of the stated expert-parallel deployment
computes), as in the program.
"""
from __future__ import annotations

import functools
import math

import numpy as np

BLOCK = 1024


def yarn(model: dict):
    """(inverse frequencies (f32), cos/sin scale, softmax scale)."""
    dim, base = model["qk_rope_dim"], float(model["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    scale = (model["qk_nope_dim"] + dim) ** -0.5
    rs = dict(model.get("rope_scaling") or ())
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
    keep = (1.0 - ramp).astype(np.float32)
    inv = (inv / np.float32(f)) * (1 - keep) + inv * keep
    return (inv.astype(np.float32),
            mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]),
            scale * mscale(rs["mscale_all_dim"]) ** 2)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, inv, msc):
    """x: (S, H, dr); interleaved pairs, de-interleaved out."""
    import jax.numpy as jnp
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
    cos, sin = (jnp.cos(ang) * msc)[:, None], (jnp.sin(ang) * msc)[:, None]
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin], -1)


def _swiglu(x, m):
    import jax
    return (jax.nn.silu(x @ m["w1"]) * (x @ m["w3"])) @ m["w2"]


def expert_ffn(x, lp, top_k: int, held: int, renorm: bool):
    """A MoE layer's feed-forward of tokens x (T, d): the held routed
    experts' part plus the shared experts; and each token's routing
    margin (its top_k-th less its next router probability)."""
    import jax
    import jax.numpy as jnp
    m = lp["moe"]
    probs = jax.nn.softmax(x @ m["router"], -1)              # all experts
    top, idx = jax.lax.top_k(probs, top_k)
    if renorm:
        top = top / jnp.sum(top, -1, keepdims=True)
    y = _swiglu(x, lp["shared"])
    for e in range(held):
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
        w = {k: m[k][e] for k in ("w1", "w3", "w2")}
        y = y + gate[:, None] * _swiglu(x, w)
    srt = jnp.sort(probs, -1)[:, ::-1]
    return y, srt[:, top_k - 1] - srt[:, top_k]


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: tuple, dense: bool):
    import jax
    import jax.numpy as jnp
    (R, dn, eps, top_k, held, renorm, inv, msc, scale) = dims
    inv = np.asarray(inv, np.float32)

    def attention(x, a):
        S = x.shape[0]
        pos = jnp.arange(S)
        q = jnp.einsum("sd,dhk->shk", x, a["wq"])
        kv = x @ a["wkv_a"]
        c = _rms(kv[:, :R], a["kv_norm"], eps)
        k_pe = _rope(kv[:, None, R:], pos, inv, msc)          # (S, 1, dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, inv, msc)
        k_nope = jnp.einsum("sr,rhk->shk", c, a["wk_b"])
        v = jnp.einsum("sr,rhk->shk", c, a["wv_b"])

        def block(i):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * BLOCK, BLOCK)
            qp = jax.lax.dynamic_slice_in_dim(q_pe, i * BLOCK, BLOCK)
            s = (jnp.einsum("qhk,shk->hqs", qn, k_nope)
                 + jnp.einsum("qhk,sk->hqs", qp, k_pe[:, 0])) * scale
            qpos = i * BLOCK + jnp.arange(BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        o = jax.lax.map(block, jnp.arange(S // BLOCK))
        o = o.reshape(S, *o.shape[2:])
        return jnp.einsum("shk,hkd->sd", o, a["wo"])

    def layer(h, lp):
        h = h + attention(_rms(h, lp["n1"]["scale"], eps), lp["attn"])
        x = _rms(h, lp["n2"]["scale"], eps)
        if dense:
            return h + _swiglu(x, lp["mlp"]), jnp.full(h.shape[:1], jnp.inf)
        y, margin = expert_ffn(x, lp, top_k, held, renorm)
        return h + y, margin
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax

    def head(h, g, w_out):
        return _rms(h, g, eps) @ w_out
    return jax.jit(head)


def logits(params, model: dict, tokens, at, margins=None) -> np.ndarray:
    """Float32 logits of the positions `at` of the sequence `tokens`
    ((len(at), vocab)), each from the tokens up to it. `margins`, a list,
    gets each MoE layer's routing margins (the top_k-th less the next
    router probability) of the sequence's positions."""
    import jax
    import jax.numpy as jnp
    if model.get("attn_kind") != "mla" or model["family"] != "moe":
        raise ValueError("mla_moe_lm covers MoE decoders with latent "
                         "attention")
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    S = -(-n // BLOCK) * BLOCK            # later rows are causally
    padded = np.zeros(S, np.int32)        # invisible padding
    padded[:n] = tokens
    eps = float(model["norm_eps"])
    inv, msc, scale = yarn(model)
    dims = (model["kv_lora_rank"], model["qk_nope_dim"], eps,
            model["top_k"], model.get("experts_held") or model["n_experts"],
            bool(model.get("norm_topk", True)), tuple(inv.tolist()),
            float(msc), float(scale))
    with jax.default_matmul_precision("highest"):
        h = f32(params["embed"])[jnp.asarray(padded)]
        for name, dense in (("dense_blocks", True), ("blocks", False)):
            stack = params.get(name)
            if stack is None:
                continue
            layer = _layer_fn(dims, dense)
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                h, margin = layer(h, f32(jax.tree.map(lambda a: a[i],
                                                      stack)))
                if margins is not None and not dense:
                    margins.append(np.asarray(margin)[:n])
        out = _head_fn(eps)(h[jnp.asarray(np.asarray(at))],
                            f32(params["final_norm"]["scale"]),
                            f32(params["unembed"]))
        return np.asarray(out)
