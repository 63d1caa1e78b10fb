"""Seeded weights for a served model, made by the benchmark.

The program gives the layout only: the tree of its parameters' shapes
and types (`Model.init` traced abstractly, nothing run) and their
logical axes (`Model.param_specs`). Every value is drawn here, on the
device, in one jitted call from the seed, in the type it is served in,
so that the plain reference takes weights the program did not make.

Each leaf is normal with a mean and a spread set by its name (the last
key of its path) in the configuration's `weights` block:
`{"mean": m, "std": s}` sets both, `{"gain": g}` scales the default
spread, and `{"spike": a}` adds `a` spreads, of a seeded sign, to one
seeded element of each vector along the last axis: one large channel in
each head, as trained models' keys have. The default spread is
1/sqrt(fan-in), with the program's fan-in convention (the product of
every axis but the last, leaving out the stacked `layers` and `experts`
axes). An embedding table (first axis `vocab`)
takes its fan-in from its last axis, as the unembedding it is tied to
reads it.
"""
from __future__ import annotations

import math

import numpy as np

STACKED = ("layers", "experts")


def model_config(config: dict):
    """The program's `ModelConfig` from the configuration's `model`."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**config["model"])


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _law(name: str, shape, axes, rules: dict) -> tuple:
    """(mean, spread, spike) of a leaf."""
    rule = rules.get(name, {})
    mean, spike = float(rule.get("mean", 0.0)), float(rule.get("spike", 0.0))
    if "std" in rule:
        return mean, float(rule["std"]), spike
    kept = [n for n, a in zip(shape, axes) if a not in STACKED]
    fan_in = kept[-1] if axes and axes[0] == "vocab" else \
        int(np.prod(kept[:-1])) if len(kept) > 1 else kept[0]
    return mean, float(rule.get("gain", 1.0)) / math.sqrt(fan_in), spike


def seed_key(seed: int, stream: int):
    """A JAX key from the whole of `seed` (JAX keeps 32 bits of an int)."""
    import jax
    return jax.random.key(int(np.random.default_rng(
        [int(seed), stream]).integers(2 ** 31)))


def make(config: dict, seed: int):
    """The parameter tree of `config["model"]`, drawn from `seed`."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import Model
    model = Model(model_config(config))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    specs = model.param_specs()
    rules = config.get("weights", {})
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    axes = tree.flatten_up_to(specs)
    plan = [(s.shape, s.dtype) + _law(_name(p), s.shape, a, rules)
            for (p, s), a in zip(leaves, axes)]

    def one(key, shape, dtype, mean, std, spike):
        k_val, k_at, k_sign = jax.random.split(key, 3)
        z = jax.random.normal(k_val, shape, jnp.float32)
        if spike:
            at = jax.random.randint(k_at, shape[:-1], 0, shape[-1])
            sign = jnp.where(jax.random.bernoulli(k_sign, 0.5, shape[:-1]),
                             1.0, -1.0)
            z = z + spike * sign[..., None] * jax.nn.one_hot(
                at, shape[-1], dtype=jnp.float32)
        return (mean + std * z).astype(dtype)

    def draw(key):
        keys = jax.random.split(key, len(plan))
        return tree.unflatten([one(k, *law) for k, law in zip(keys, plan)])
    return jax.jit(draw)(seed_key(seed, 5))
