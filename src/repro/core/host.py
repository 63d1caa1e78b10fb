"""Host placement of the scalar electrical model.

The scalar model (`cells`, `retention`, `timing.analyze`, `dse.evaluate`
and the per-topology constants of the batched evaluators) computes with
eager jnp ops on scalars. On an accelerator each such op would be its
own device dispatch, and the accelerator's own rounding of transcendentals
would part a batched table from its scalar reference (retention
integrals moved by ~5e-4 relative on a TPU). `on_host()` runs them on the
CPU device, whatever the default backend; the batched device programs
stay where they are.
"""
from __future__ import annotations

import jax


def on_host():
    """Context manager: eager jnp ops inside run on the CPU device."""
    return jax.default_device(jax.devices("cpu")[0])
