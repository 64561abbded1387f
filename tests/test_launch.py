"""Launcher plumbing: the mesh from the devices present, the compile-cache
setting, and the placement of the optimizer state."""

import jax
from jax.sharding import AxisType, NamedSharding

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.train import build_training
from repro.parallel.sharding import ShardingRules


def test_host_mesh_from_devices_present():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("pod", "data")
    assert mesh.devices.size == len(jax.devices())
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_optimizer_state_sharded_like_params():
    mesh = make_host_mesh()
    _, _, params, opt_state, _ = build_training(
        get_config("qwen2.5-3b", smoke=True), mesh, ShardingRules())
    for moment in ("mu", "nu"):
        for p, m in zip(jax.tree.leaves(params),
                        jax.tree.leaves(opt_state[moment])):
            assert isinstance(m.sharding, NamedSharding)
            assert m.sharding.spec == p.sharding.spec
