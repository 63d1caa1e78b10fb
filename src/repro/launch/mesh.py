"""Production mesh definitions (functions, never module-level constants, so
importing this module never touches jax device state).

Single pod:  (16, 16)      ("data", "model")   = 256 chips (TPU v5e pod)
Multi pod:   (2, 16, 16)   ("pod", "data", "model") = 512 chips

The dry-run (launch/dryrun.py) sets XLA_FLAGS host-device-count=512 before
any jax import; tests use make_test_mesh() over however many devices exist.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto-typed: the model code relies
    on sharding propagation (jnp.take under `with mesh:`), which Explicit
    axes — what `make_mesh` builds by default on JAX 0.9 — reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over available (or forced) host devices for tests."""
    n = len(jax.devices())
    need = data * model * pod
    if n < need:
        raise RuntimeError(f"need {need} devices, have {n}")
    if pod > 1:
        return auto_mesh((pod, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))


def data_axis_names(mesh) -> tuple:
    """Mesh axes that shard the batch (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_chips(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
