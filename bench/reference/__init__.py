"""Frozen plain reference of the gain-cell model: the scalar analytic
evaluator, the workload-matching rules and the dense f64 transient
engine, copied from the program so that what decides `correct` cannot
move with the code under test. Run it on the CPU device (`on_cpu`)."""
from __future__ import annotations


def on_cpu():
    """Context manager: eager jnp ops and jitted calls inside run on the
    CPU device, whatever the default backend."""
    import jax
    return jax.default_device(jax.devices("cpu")[0])
