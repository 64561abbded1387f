"""Exchange name scopes (4 CPU devices, a 2x2 torus).

Every dense backend's exchange runs under ``a2a[<backend>]``, which names
its ops (collectives and local packing alike) in the compiled HLO's
``op_name`` metadata; with the scope taken away the compiled program is
the same.  Exits nonzero on any failure.
"""

import contextlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.cache import cart_create
from repro.core.hlo_inspect import program_structure
from repro.core.plan import free_plans, plan_all_to_all

DIMS, NAMES = (2, 2), ("i", "j")


def compiled(backend):
    free_plans()
    mesh = cart_create(4, DIMS, NAMES)
    x = jnp.arange(4 * 4 * 6, dtype=jnp.float32).reshape(4, 4, 6)
    plan = plan_all_to_all(mesh, NAMES, x.shape[2:], x.dtype,
                           backend=backend, n_chunks=2)
    spec = P(tuple(reversed(NAMES)))
    f = jax.jit(jax.shard_map(lambda xl: plan.reverse(plan.forward(
        xl[0]))[None], mesh=mesh, in_specs=spec, out_specs=spec))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))
    return f.lower(x).compile().as_text()


def main():
    assert jax.device_count() >= 4
    for backend in ("direct", "factorized", "overlap"):
        scoped = compiled(backend)
        paths = set(re.findall(r'op_name="([^"]*)"', scoped))
        scope = f"a2a[{backend}]"
        under = [p for p in paths if f"/{scope}/" in p]
        assert under, (backend, sorted(paths))
        assert any("all_to_all" in p for p in under), (backend, under)
        real = jax.named_scope
        jax.named_scope = lambda name: contextlib.nullcontext()
        try:
            bare = compiled(backend)
        finally:
            jax.named_scope = real
        assert scope not in bare
        assert program_structure(scoped) == program_structure(bare), backend
        print(f"OK {scope}: {len(under)} op paths, program unchanged")
    print("OK check_scopes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
