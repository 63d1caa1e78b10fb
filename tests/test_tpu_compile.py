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


def _decode_chunk_memory(one_chip, monkeypatch, cfg, slots):
    """The serving engine's decode chunk (8 steps, windows of 9216 rows)
    compiled for one v5e on shapes alone: (memory_analysis(), bytes of
    the cache)."""
    from repro.models.model import Model
    from repro.serving import ServeEngine
    init_cache = Model.init_cache
    monkeypatch.setattr(Model, "init_cache", lambda self, B, W: jax.eval_shape(
        lambda: init_cache(self, B, W)))
    eng = ServeEngine(cfg, None, n_slots=slots, window=9216, decode_chunk=8)
    on = lambda t: jax.tree.map(
        lambda a: _shape(one_chip, a.dtype, *a.shape), t)
    B = slots
    i32 = lambda *s: _shape(one_chip, jnp.int32, *s)
    compiled = eng._decode_chunk.lower(
        on(jax.eval_shape(eng.model.init, jax.random.key(0))), on(eng.cache),
        i32(B, 1), i32(B), i32(B), _shape(one_chip, jnp.bool_, B),
        _shape(one_chip, jnp.float32, B), i32(B), i32(B), i32(B),
        _shape(one_chip, jax.random.key(0).dtype)).compile()
    cache = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(eng.cache))
    return compiled.memory_analysis(), cache


def test_latent_decode_chunk_updates_its_cache_in_place(one_chip,
                                                        monkeypatch):
    """DeepSeek-V2-Lite's decode chunk at its served size (27 layers, 8 of
    64 routed experts held, 16 slots of 9216 latent rows) compiles for one
    v5e holding no cache-sized temporary: the 4.6 GB latent cache is
    donated and updated in place (0.24 GB of temporaries when written)."""
    import dataclasses

    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), experts_held=8)
    mem, cache = _decode_chunk_memory(one_chip, monkeypatch, cfg, 16)
    assert cache > 4.5e9
    assert mem.alias_size_in_bytes >= cache
    assert mem.temp_size_in_bytes < cache / 8


@pytest.mark.parametrize("kv_dtype,slots", [("bfloat16", 16), ("int8", 16),
                                            ("bfloat16", 32)])
def test_kv_decode_chunk_updates_its_cache_in_place(one_chip, monkeypatch,
                                                    kv_dtype, slots):
    """qwen2-0.5b's decode chunk at its served size (24 layers, 16 slots of
    9216 rows; the int8 cache is its cell's control) compiles for one v5e
    with the stacked K/V cache donated and updated in place and no
    cache-sized temporary (about 1.1 MB of temporaries against the 1.81 GB
    bf16 cache when written; 10.87 GB when each layer's rows were scan
    inputs and outputs); 32 slots fit the chip's 16 GB."""
    import dataclasses

    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("qwen2-0.5b"), kv_dtype=kv_dtype)
    mem, cache = _decode_chunk_memory(one_chip, monkeypatch, cfg, slots)
    assert cache > 0.9e9 * slots / 16
    assert mem.alias_size_in_bytes >= cache
    assert mem.temp_size_in_bytes < cache / 8
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
