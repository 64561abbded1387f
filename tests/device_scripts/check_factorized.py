"""Multi-device validation of the factorized all-to-all (12 CPU devices).

Checks, for a sweep of factorizations/variants/round orders:
  * factorized == direct collective == all-to-all semantics (out[r,i] = x[i,r])
  * the paper-literal and natural variants agree
  * pipelined (a deprecated alias of factorized) agrees
  * tiled semantics == lax tiled collective
  * dtype coverage: f32, bf16, i32, f16
Exits nonzero on any mismatch.
"""

import itertools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.cache import cart_create
from repro.core.comm import torus_comm


def run_case(dims, names, variant, block=(3,), round_order=None, pipelined=0,
             dtype=jnp.float32):
    p = math.prod(dims)
    mesh = cart_create(p, dims, names)
    comm = torus_comm(mesh, names, variant=variant)
    spec = P(tuple(reversed(names)))
    x = (jnp.arange(p)[:, None] * 1000 + jnp.arange(p)[None, :])
    x = (x[..., None] * jnp.ones(block)).astype(dtype)

    if pipelined:
        plan = comm.all_to_all(block, dtype, backend="pipelined",
                               n_chunks=pipelined)
    else:
        plan = comm.all_to_all(block, dtype, backend="factorized",
                               round_order=round_order)
    plan_dir = comm.all_to_all(block, dtype, backend="direct")

    def loc(xl):
        return plan.forward(xl[0])[None]

    def loc_direct(xl):
        return plan_dir.forward(xl[0])[None]

    f = jax.jit(jax.shard_map(loc, mesh=mesh, in_specs=spec, out_specs=spec))
    g = jax.jit(jax.shard_map(loc_direct, mesh=mesh, in_specs=spec,
                              out_specs=spec))
    got, ref = np.array(f(x)), np.array(g(x))
    expected = np.array(x).transpose(1, 0, *range(2, x.ndim))
    np.testing.assert_array_equal(ref, expected)
    np.testing.assert_array_equal(got, expected)


def run_tiled(dims, names, shape, split, concat):
    p = math.prod(dims)
    mesh = cart_create(p, dims, names)
    spec = P(tuple(reversed(names)), *([None] * (len(shape) - 1)))
    x = jax.random.normal(jax.random.PRNGKey(0), (p,) + shape)

    comm = torus_comm(mesh, names)
    plan = comm.all_to_all(backend="factorized")
    plan_dir = comm.all_to_all(backend="direct")

    def loc(xl):
        return plan.tiled(xl[0], split, concat)[None]

    def locd(xl):
        return plan_dir.tiled(xl[0], split, concat)[None]

    f = jax.jit(jax.shard_map(loc, mesh=mesh, in_specs=spec, out_specs=spec))
    g = jax.jit(jax.shard_map(locd, mesh=mesh, in_specs=spec, out_specs=spec))
    np.testing.assert_array_equal(np.array(f(x)), np.array(g(x)))


def main():
    assert jax.device_count() >= 12, f"need 12 devices, got {jax.device_count()}"
    cases = [
        ((3, 4), ("i", "j")),
        ((4, 3), ("i", "j")),
        ((2, 6), ("i", "j")),
        ((2, 3, 2), ("i", "j", "k")),
        ((2, 2, 3), ("i", "j", "k")),
        ((12,), ("i",)),
        ((3, 2, 2), ("i", "j", "k")),
    ]
    for dims, names in cases:
        for variant in ("natural", "paper"):
            run_case(dims, names, variant)
    print(f"OK factorized==direct for {len(cases)} meshes x 2 variants")

    for order in itertools.permutations(range(3)):
        run_case((2, 3, 2), ("i", "j", "k"), "natural", round_order=order)
        run_case((2, 3, 2), ("i", "j", "k"), "paper", round_order=order)
    print("OK all round orders")

    for dt in (jnp.bfloat16, jnp.int32, jnp.float16):
        run_case((3, 4), ("i", "j"), "natural", dtype=dt)
        run_case((2, 3, 2), ("i", "j", "k"), "paper", dtype=dt)
    print("OK dtypes")

    run_case((2, 3, 2), ("i", "j", "k"), "natural", block=(4,), pipelined=2)
    run_case((3, 4), ("i", "j"), "natural", block=(8,), pipelined=4)
    run_case((3, 4), ("i", "j"), "natural", block=(7,), pipelined=3)  # ragged
    print("OK pipelined")

    run_tiled((3, 4), ("i", "j"), (24, 5), 0, 0)
    run_tiled((3, 4), ("i", "j"), (24, 5), 0, 1)
    run_tiled((3, 4), ("i", "j"), (5, 24), 1, 0)
    run_tiled((2, 3, 2), ("i", "j", "k"), (4, 24, 3), 1, 2)
    run_tiled((2, 3, 2), ("i", "j", "k"), (24, 2, 3), 0, 2)
    run_tiled((2, 3, 2), ("i", "j", "k"), (2, 3, 24), 2, 0)
    print("OK tiled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
