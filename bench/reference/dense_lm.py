"""Plain reference of a dense decoder language model (the Qwen2 block).

The forward pass of one sequence in straightforward `jax.numpy`, in
float32 under `jax.default_matmul_precision("highest")`: token
embedding; per layer, RMSNorm, grouped-query attention with biases on
the query, key and value projections and rotary positions (rotate-half,
inverse frequencies theta^(-2i/head_dim)), causal softmax, output
projection and residual; RMSNorm, SwiGLU feed-forward (down(silu(gate x)
* up x)) and residual; a final RMSNorm and the unembedding, tied to the
embedding table where the configuration says so. No cache, no batching,
no kernel: the whole sequence goes through one layer at a time, the
attention in blocks of query positions, so that a sequence of some
thousands of tokens fits.

Its one contact with the program is the layout of the parameter tree it
is handed (the keys `embed`, `final_norm`, `blocks` with `n1`, `attn`
{wq, wk, wv, wo, bq, bk, bv}, `n2`, `mlp` {w1 gate, w3 up, w2 down},
each stacked over layers): the values are the benchmark's
(`bench.lib.weights`), and the dimensions come from the configuration's
`model` dict. Departures from the published Qwen2 description: the
weights are random, not the released checkpoint; the layout's
`head_dim` is taken as given (Qwen2-0.5B: 64 = 896 / 14).
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK = 1024


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(n_heads: int, n_kv: int, eps: float, theta: float):
    import jax
    import jax.numpy as jnp

    def layer(h, lp):
        S = h.shape[0]
        a = lp["attn"]
        x = _rms(h, lp["n1"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", x, a["wq"])
        k = jnp.einsum("sd,dhk->shk", x, a["wk"])
        v = jnp.einsum("sd,dhk->shk", x, a["wv"])
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        pos = jnp.arange(S)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        group = jnp.arange(n_heads) // (n_heads // n_kv)
        k, v = k[:, group], v[:, group]              # each head's kv head
        hd = q.shape[-1]

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK)
            s = jnp.einsum("qhk,shk->hqs", qb, k) / np.sqrt(hd)
            qpos = i * BLOCK + jnp.arange(BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        o = jax.lax.map(block, jnp.arange(S // BLOCK)).reshape(q.shape)
        h = h + jnp.einsum("shk,hkd->sd", o, a["wo"])
        x = _rms(h, lp["n2"]["scale"], eps)
        m = lp["mlp"]
        return h + (jax.nn.silu(x @ m["w1"]) * (x @ m["w3"])) @ m["w2"]
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax

    def head(h, g, w_out):
        return _rms(h, g, eps) @ w_out
    return jax.jit(head)


def logits(params, model: dict, tokens, at) -> np.ndarray:
    """Float32 logits of the positions `at` of the sequence `tokens`
    ((len(at), vocab)), each from the tokens up to it."""
    import jax
    import jax.numpy as jnp
    if model["family"] != "dense" or model.get("sliding_window", 0):
        raise ValueError("dense_lm covers dense decoders with full "
                         "attention")
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)
    tokens = np.asarray(tokens, np.int32)
    S = -(-len(tokens) // BLOCK) * BLOCK          # later rows are causal-
    padded = np.zeros(S, np.int32)                # ly invisible padding
    padded[:len(tokens)] = tokens
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    layer = _layer_fn(model["n_heads"], model["n_kv_heads"], eps, theta)
    with jax.default_matmul_precision("highest"):
        embed = f32(params["embed"])
        h = embed[jnp.asarray(padded)]
        blocks = params["blocks"]
        for i in range(model["n_layers"]):
            h = layer(h, f32(jax.tree.map(lambda a: a[i], blocks)))
        w_out = embed.T if model["tie_embeddings"] else \
            f32(params["unembed"])
        out = _head_fn(eps)(h[jnp.asarray(np.asarray(at))],
                            f32(params["final_norm"]["scale"]), w_out)
        return np.asarray(out)
