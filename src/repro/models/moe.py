"""Mixture-of-Experts FFN (GShard-style top-k with capacity, scatter dispatch).

Design (DESIGN.md §5): activations are replicated across the 'model' mesh
axis between blocks (TP layout), so expert parallelism needs NO token
all-to-all: every model-rank sees all local-data-shard tokens, keeps only
assignments routed to ITS experts, computes, and the per-rank partial
outputs are summed by the same all-reduce a dense TP FFN would need.

Two weight layouts, one code path:
  * EP  (E % model_axis == 0, e.g. arctic 128e/16): experts sharded over
    'model'; each rank owns E_loc experts at offset rank*E_loc.
  * TP  (E < model_axis, e.g. mixtral 8e/16): all experts on every rank
    with d_ff sharded over 'model'; partial-ff outputs psum'd.

The (T, E, C) one-hot einsum of the original GShard paper is replaced by a
scatter-add into an (E_loc, C, d) buffer — O(T·k·d) instead of O(T·E·C).

Capacity is a training setting: prefill and decode route dropless. On one
device that is `_dropless_local`: the assignments sorted by expert and
each projection one grouped matmul (`jax.lax.ragged_dot`) over the rows
of the held experts only; under a mesh the dispatch sizes every buffer
C = T.
A config may hold only a share of the routed experts (`experts_held`, from
expert 0): the router keeps all `n_experts` outputs and the layer returns
the held experts' part of the result, the part one chip of an
expert-parallel deployment computes; nothing stands in for the rest.
Gates are the top-k softmax probabilities, renormalized unless
`norm_topk` is false.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import act_fn, dense_init, dtype_of, shard_act

_MODEL_AXIS = "model"


def init(key, cfg):
    d, f, E = cfg.d_model, cfg.expert_ff(), cfg.n_experts
    E_loc = cfg.n_held()
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w1": dense_init(ks[1], (E_loc, d, f), dt),
        "w3": dense_init(ks[2], (E_loc, d, f), dt),
        "w2": dense_init(ks[3], (E_loc, f, d), dt, scale=1.0 / np.sqrt(f)),
    }


def specs(cfg):
    return {
        "router": ("embed", None),
        "w1": ("experts", "embed", "expert_mlp"),
        "w3": ("experts", "embed", "expert_mlp"),
        "w2": ("experts", "expert_mlp", "embed"),
    }


def _route(x32, router_w, k, renorm=True):
    """x32: (T, d) fp32. Returns gates (T, k), expert ids (T, k), aux loss."""
    logits = x32 @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    if renorm:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True),
                                    1e-9)
    # Switch-style load-balancing aux loss.
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _moe_local(x, router_w, w1, w3, w2, cfg, e_offset, axis_name=None,
               mean_axes=None, capacity=None):
    """x: (T, d) tokens local to this device (replicated over model axis).

    e_offset: first global expert id owned by this rank (EP) or 0 (TP).
    w*: local expert weights (E_loc, d, f_loc).
    """
    T, d = x.shape
    E = cfg.n_experts
    E_loc = w1.shape[0]
    k = cfg.top_k
    act = act_fn(cfg.act)

    gates, idx, aux = _route(x.astype(jnp.float32), router_w, k,
                             cfg.norm_topk)

    flat_e = idx.reshape(-1)                      # (T*k,) global expert ids
    flat_g = gates.reshape(-1).astype(jnp.float32)
    flat_t = jnp.repeat(jnp.arange(T), k)

    # Slot within each expert: rank of this assignment among same-expert
    # assignments, in token order (consistent across ranks: full router view).
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)       # (T*k, E)
    slot = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(T * k), flat_e]
    C = capacity or max(1, int(math.ceil(T * k * cfg.capacity_factor / E)))

    local = (flat_e >= e_offset) & (flat_e < e_offset + E_loc)
    keep = (slot < C) & local
    le = jnp.clip(flat_e - e_offset, 0, E_loc - 1)
    slot_c = jnp.clip(slot, 0, C - 1)

    # Dispatch: scatter tokens into (E_loc, C, d).
    upd = x[flat_t] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E_loc, C, d), x.dtype).at[le, slot_c].add(
        upd, mode="drop")

    h = act(jnp.einsum("ecd,edf->ecf", buf, w1)) * jnp.einsum(
        "ecd,edf->ecf", buf, w3)
    out_e = jnp.einsum("ecf,efd->ecd", h, w2)     # (E_loc, C, d)

    # Combine: gather expert outputs back to tokens, weighted by gates.
    contrib = out_e[le, slot_c] * (flat_g * keep).astype(out_e.dtype)[:, None]
    y = jnp.zeros((T, d), out_e.dtype).at[flat_t].add(contrib, mode="drop")

    if axis_name is not None:
        y = jax.lax.psum(y, axis_name)
        # aux must come out replicated over the WHOLE mesh (out_spec P()).
        aux = jax.lax.pmean(aux, mean_axes or axis_name)
    return y, aux


import os

# decode-scale token counts take the 2D weight-stationary path; settable
# to 0 (env REPRO_MOE_SMALL_T=0) to reproduce the paper-faithful baseline
SMALL_T = int(os.environ.get("REPRO_MOE_SMALL_T", "4096"))


def _apply_small_t(p, xt, cfg, mesh):
    """Decode path (§Perf hillclimb #2): tokens are tiny (a few thousand),
    expert weights are huge. Replicate the TOKENS over the whole mesh and
    keep the WEIGHTS fully stationary in their 2D (experts@model,
    d_ff@data) shards: each rank computes its expert/f-slice partials for
    all tokens and one psum of (T, d) activations replaces the 58 GB/step
    expert all-gather. Dropless (C = T)."""
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    T = xt.shape[0]
    data_axes = tuple(a for a in mesh.axis_names if a != _MODEL_AXIS)
    all_axes = tuple(mesh.axis_names)
    w13 = P(_MODEL_AXIS, None, data_axes)
    w2s = P(_MODEL_AXIS, data_axes, None)

    def fn(xt, router_w, w1, w3, w2):
        e_off = jax.lax.axis_index(_MODEL_AXIS) * w1.shape[0]
        return _moe_local(xt, router_w, w1, w3, w2, cfg, e_off,
                          axis_name=all_axes, mean_axes=all_axes,
                          capacity=T)

    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None), P(None, None), w13, w13, w2s),
        out_specs=(P(None, None), P()),
        check_rep=False,
    )(xt, p["router"], p["w1"], p["w3"], p["w2"])


def _dropless_local(x, router_w, w1, w3, w2, cfg):
    """x: (T, d) -> (T, d) and (T,) int32: the local experts' gated sum
    over the tokens routed to them, and each token's assignments that land
    on them. The T * top_k assignments are sorted by expert, those to
    experts held elsewhere last; the held experts' rows go through grouped
    matmuls and the rest are neither computed nor added (their rows of a
    grouped product are undefined and masked). Each token gathers its k
    rows back (no scatter-add) and sums them, gated, in float32."""
    T, d = x.shape
    E_loc, k = w1.shape[0], cfg.top_k
    gates, idx, _ = _route(x.astype(jnp.float32), router_w, k,
                           cfg.norm_topk)
    group = jnp.minimum(idx.reshape(-1), E_loc)      # E_loc: held elsewhere
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=E_loc + 1)[:E_loc].astype(jnp.int32)
    xs = x[order // k]
    act = act_fn(cfg.act)
    h = act(jax.lax.ragged_dot(xs, w1, sizes)) * jax.lax.ragged_dot(
        xs, w3, sizes)
    out = jax.lax.ragged_dot(h, w2, sizes)
    out = out[jnp.argsort(order)].reshape(T, k, d)   # back to (token, j)
    g = jnp.where(idx < E_loc, gates, 0.0)[..., None]
    y = jnp.sum(jnp.where(g > 0, out.astype(jnp.float32), 0.0) * g, axis=1)
    return y.astype(x.dtype), jnp.sum(idx < E_loc, axis=-1).astype(jnp.int32)


def apply(p, x, cfg, mesh=None, dropless=False):
    """x: (B, S, d) -> (B, S, d), aux loss. Uses shard_map when a mesh with a
    'model' axis is active, plain local computation otherwise. `dropless`
    (prefill and decode) keeps every token's assignments; inference has
    no aux loss, so the second output is then each token's assignments
    that land on the experts held here, (B, S) int32."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)

    if mesh is None or _MODEL_AXIS not in mesh.shape:
        if dropless:
            y, held = _dropless_local(xt, p["router"], p["w1"], p["w3"],
                                      p["w2"], cfg)
            return y.reshape(B, S, d), held.reshape(B, S)
        y, aux = _moe_local(xt, p["router"], p["w1"], p["w3"], p["w2"], cfg, 0)
        return y.reshape(B, S, d).astype(x.dtype), aux
    if cfg.experts_held:
        raise NotImplementedError("a held expert share runs on one device")

    small_t = int(os.environ.get("REPRO_MOE_SMALL_T", SMALL_T))
    m_sz = mesh.shape[_MODEL_AXIS]
    n_dat = int(np.prod([s for a, s in mesh.shape.items()
                         if a != _MODEL_AXIS]))
    if (B * S <= small_t and cfg.n_experts % m_sz == 0
            and cfg.n_experts >= m_sz
            and cfg.expert_ff() % max(n_dat, 1) == 0):
        y, aux = _apply_small_t(p, xt, cfg, mesh)
        return y.reshape(B, S, d).astype(x.dtype), aux

    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    m = mesh.shape[_MODEL_AXIS]
    ep = cfg.n_experts % m == 0 and cfg.n_experts >= m
    # data axes: everything except 'model' shards the token dim (replicate
    # tokens when too few to split, e.g. batch-1 long-context decode).
    data_axes = tuple(a for a in mesh.axis_names if a != _MODEL_AXIS)
    n_data = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    if (B * S) % max(n_data, 1) != 0:
        data_axes = ()
    xs = P(data_axes, None) if data_axes else P(None, None)
    if ep:
        wspec = P(_MODEL_AXIS, None, None)
    else:
        wspec = P(None, None, _MODEL_AXIS)

    def fn(xt, router_w, w1, w3, w2):
        e_off = jax.lax.axis_index(_MODEL_AXIS) * w1.shape[0] if ep else 0
        return _moe_local(xt, router_w, w1, w3, w2, cfg, e_off,
                          axis_name=_MODEL_AXIS,
                          mean_axes=tuple(mesh.axis_names),
                          capacity=xt.shape[0] if dropless else None)

    y, aux = shard_map(
        fn, mesh=mesh,
        in_specs=(xs, P(None, None), wspec, wspec,
                  P(None, _MODEL_AXIS, None) if not ep else wspec),
        out_specs=(xs, P()),
        check_rep=False,
    )(xt, p["router"], p["w1"], p["w3"], p["w2"])
    if dropless:               # the mesh holds every expert
        aux = jnp.full((B, S), cfg.top_k, jnp.int32)
    return y.reshape(B, S, d).astype(x.dtype), aux
