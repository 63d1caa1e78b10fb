"""Persistent XLA compilation cache for the command-line entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
`enable_compile_cache` changes nothing. Otherwise it points the cache at
`<checkout>/.jax_cache`, a fixed path inside the checkout: a cache whose
directory moved from run to run would never be found again, so the path
never depends on a temp name, a pid or the time. Only entry points call
it (`chip_smoke.py`, `launch/serve.py`, `launch/compile_service.py`, the
fleet worker) — never a library module as it is imported.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
