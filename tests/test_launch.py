"""Launch-layer integration: build->lower->compile->analyze on a small
mesh, HLO analyzer invariants, sharding rule table."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, ShapeConfig
from repro.launch import hlo_analysis, roofline
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_test_mesh, data_axis_names, n_chips
from repro.launch.sharding import make_rules

MINI = {
    "train": ShapeConfig("mini_train", 64, 8, "train"),
    "prefill": ShapeConfig("mini_prefill", 64, 8, "prefill"),
    "decode": ShapeConfig("mini_decode", 64, 8, "decode"),
}


def _mesh():
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >= 2 host devices (run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return make_test_mesh(data=2, model=n // 2 if n < 8 else 4)


def test_compile_cache_dir_from_env_else_fixed_in_checkout(tmp_path,
                                                         monkeypatch):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; without it the cache
    goes to one fixed directory inside the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_small_mesh_dryrun_pipeline(kind):
    mesh = _mesh()
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              name="qwen-mini")
    bundle = steps_mod.build(cfg, mesh, MINI[kind])
    with mesh:
        compiled = bundle.lower().compile()
    an = hlo_analysis.analyze(compiled.as_text(), n_chips(mesh))
    assert an["flops"] > 0
    assert an["mem_bytes"] > 0
    assert an["unknown_trip_counts"] == 0          # all loops resolved
    assert an["collective_count"] > 0              # SPMD really sharded
    rl = roofline.derive(an, n_chips=n_chips(mesh),
                         model_flops=roofline.model_flops_for(cfg, MINI[kind]))
    assert rl.step_time_s > 0 and rl.bottleneck in ("compute", "memory",
                                                    "collective")


def test_rules_divisibility_fallback():
    mesh = _mesh()
    rules = make_rules(mesh, batch_size=8)
    from repro.models.common import logical_to_pspec
    # a dim that doesn't divide the axis must fall back to replication
    m = mesh.shape["model"]
    spec = logical_to_pspec(("heads",), rules, shape=(m + 1,), mesh=mesh)
    assert spec == jax.sharding.PartitionSpec(None) or spec == \
        jax.sharding.PartitionSpec()
    spec2 = logical_to_pspec(("heads",), rules, shape=(m * 4,), mesh=mesh)
    assert spec2[0] == "model"


def test_decode_rules_differ_from_train():
    mesh = _mesh()
    rt = make_rules(mesh, kind="train")
    rd = make_rules(mesh, kind="decode")
    assert rt["expert_mlp"] is None
    assert rd["expert_mlp"] == data_axis_names(mesh)


def test_hlo_analyzer_trip_counts_and_dots():
    """scan-of-matmul: analyzer must multiply by the trip count (XLA's own
    cost_analysis does not)."""
    mesh = _mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P
    L, d = 4, 64
    def step(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y * y)
    f = jax.jit(step, in_shardings=(
        NamedSharding(mesh, P(None, "data", "model")),
        NamedSharding(mesh, P("data", None))))
    lo = f.lower(jax.ShapeDtypeStruct((L, d, d), jnp.float32),
                 jax.ShapeDtypeStruct((8, d), jnp.float32))
    an = hlo_analysis.analyze(lo.compile().as_text(), n_chips(mesh))
    nd = mesh.shape["data"]
    nm = mesh.shape["model"]
    expect = L * 2 * (8 // nd) * d * (d // nm)
    assert an["flops"] == pytest.approx(expect, rel=0.05)
    assert an["dot_count"] == L
