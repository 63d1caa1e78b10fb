"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed with jax, compiles
for a v5e that is described and not attached, and raises what the chip's
compiler would raise (Mosaic refusing a kernel, XLA:TPU refusing an f64
LU). The programs compile under `enable_x64`, as production runs them.
The topology is described in a module fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax import enable_x64
from jax.sharding import SingleDeviceSharding

from repro.core import dse_batch
from repro.core import timing
from repro.core.bank import BankConfig, build_bank
from repro.core.spice import char_batch
from repro.kernels.batched_solve import newton as nwt
from repro.kernels.batched_solve import ops as solve_ops
from repro.kernels.batched_solve.sparse import N_PARAMS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, dtype, *shape):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("cell", ["gc2t_nn", "gc2t_np"])
def test_f32_fused_step_compiles_to_pallas_kernel(one_chip, cell):
    """The dispatcher lowered for TPU picks the Mosaic kernel for an f32
    spec, and Mosaic accepts it at a 1024-point lattice (8 full vreg
    tiles) for both read-column structures (precharge, predischarge)."""
    B = 1024
    with enable_x64():
        ckt, _ = timing.read_netlist(build_bank(BankConfig(32, 64, cell)))
        spec = nwt.build_fused_spec(ckt.build(), "f32")
        assert solve_ops.fused_engine(spec, "tpu") == "pallas"
        n, nd, k = spec.n, spec.n_dev, spec.k
        S = lambda *shape: _shape(one_chip, jnp.float32, *shape)
        pre = {"KU": S(B, n, k), "Sb": S(B, nd, 3, k), "KPa": S(B, n, nd),
               "KPg": S(B, n, nd)}

        def step(pre, krhs, params, v0):
            return solve_ops.fused_newton_step(spec, pre, krhs, params, v0,
                                               iters=8, tol=1e-6)

        text = jax.jit(step).lower(pre, S(B, n), S(B, N_PARAMS, nd),
                                   S(B, n)).compile().as_text()
    assert "tpu_custom_call" in text


def test_f64_lattice_program_compiles_without_kernel(one_chip):
    """The default-precision lattice program (f64, 300 steps) compiles
    for the chip: the Jacobian inverse needs no f64 LU, and the f64 spec
    runs the XLA while_loop — Mosaic has no 64-bit types."""
    cfg = BankConfig(32, 64, "gc2t_nn")
    text = char_batch.lattice_program(cfg, 64, sharding=one_chip) \
        .compile().as_text()
    assert "tpu_custom_call" not in text


def test_analytic_vdd_lattice_kernel_compiles(one_chip):
    """The analytic (vdd x lattice) kernel of dse_batch at a co-design
    cube's shape: 8 voltage rungs x a 64-point topology group."""
    V, P = 8, 64
    cfg = BankConfig(32, 64, "gc2t_nn")
    tech = cfg.tech
    with enable_x64():
        kernel = dse_batch._group_kernel(True, False, float(tech.v_sense_se),
                                         tech.sa_delay_s, tech.dff_delay_s,
                                         tech.stage_delay_s)
        f64 = lambda n: _shape(one_chip, jnp.float64, n)
        compiled = kernel.lower(*[f64(V)] * 6, *[f64(P)] * 9,
                                _shape(one_chip, jnp.bool_, P)).compile()
    assert compiled.as_text()
