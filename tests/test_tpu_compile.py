"""Compile-only tests: the Pallas kernels at real widths, compiled for a
described TPU v5e chip (no chip attached).

Nothing runs, so these check what interpret mode cannot: that the chip's
compiler accepts each kernel (block tiling, VMEM) and that the compiled
program holds the kernel (``tpu_custom_call``).  The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU compiler's library, and xdist workers import every test file.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_reorder import datatype_pack, datatype_unpack
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_bwd import (flash_attention_fwd,
                                               flash_attention_trainable)
from repro.kernels.moe_gmm import grouped_matmul

# qwen2.5-3b attention widths at S=2048; phi3.5-moe expert widths
B, HQ, HKV, S, HD = 1, 16, 2, 2048, 128
E, C, D_MODEL, D_FF = 16, 256, 4096, 6400


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2`` host, with the persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _attn_shapes(with_dout=False):
    q = ((B, HQ, S, HD), jnp.bfloat16)
    kv = ((B, HKV, S, HD), jnp.bfloat16)
    return [q, kv, kv] + ([q] if with_dout else [])


def test_flash_attention_forward(one_chip):
    text = _compile_text(lambda q, k, v: flash_attention(q, k, v),
                         _attn_shapes(), one_chip)
    assert "tpu_custom_call" in text


def test_flash_attention_trainable_forward(one_chip):
    text = _compile_text(lambda q, k, v: flash_attention_fwd(q, k, v),
                         _attn_shapes(), one_chip)
    assert "tpu_custom_call" in text


def test_flash_attention_trainable_grad(one_chip):
    def grads(q, k, v, dout):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention_trainable(q, k, v).astype(jnp.float32)
                * dout),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile_text(grads, _attn_shapes(with_dout=True), one_chip)
    # forward-with-lse, dq and dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("k_dim,n_dim", [(D_MODEL, D_FF), (D_FF, D_MODEL)],
                         ids=["up", "down"])
def test_grouped_matmul(one_chip, k_dim, n_dim):
    text = _compile_text(lambda a, b: grouped_matmul(a, b),
                         [((E, C, k_dim), jnp.bfloat16),
                          ((E, k_dim, n_dim), jnp.bfloat16)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fn", [datatype_pack, datatype_unpack],
                         ids=["pack", "unpack"])
@pytest.mark.parametrize("dims,k", [((2, 2), 0), ((2, 2), 1),
                                    ((2, 3, 4), 0), ((2, 3, 4), 1),
                                    ((2, 3, 4), 2)])
def test_datatype_pack_unpack(one_chip, fn, dims, k):
    text = _compile_text(lambda x: fn(x, dims=dims, k=k),
                         [((math.prod(dims), 4096), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text
