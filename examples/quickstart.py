"""Quickstart: the paper's factorized zero-copy all-to-all in 60 seconds.

A CPU demo: it forces 12 virtual host devices, so it does not run on a
TPU host (``chip_smoke.py --chips 4`` is the chip's torus all-to-all).
Builds a 2x3x2 torus (Cartesian communicator), runs the d=3 round
schedule, checks it against the direct collective, and shows the tuning
model's algorithm choice — the three viewpoints of the paper in one
script.

  PYTHONPATH=src python examples/quickstart.py
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=12")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.core import (ICI, DCN, cart_create, choose_algorithm,   # noqa: E402
                        dims_create, example_index_table,
                        get_factorization, torus_comm)

# 1. MPI_Dims_create analogue: balanced factorizations (paper Table 1)
p = 12
for d in (1, 2, 3):
    print(f"dims_create({p}, {d}) = {dims_create(p, d)}")
print(f"dims_create(1152, 2) = {dims_create(1152, 2)}  "
      f"(the paper's 36x32; OpenMPI wrongly returns 48x24)")

# 2. The round-k derived datatype (paper §3 worked example, 2x3x4)
print("\nRound-0 composite blocks for the 2x3x4 example (paper table):")
for j, idx in enumerate(example_index_table((2, 3, 4), 0)):
    print(f"  R'[{j}] = {idx}")

# 3. Cartesian communicator + cached factorization (Listings 1-2)
mesh = cart_create(12, (2, 3, 2), ("x", "y", "z"))
desc = get_factorization(mesh, ("x", "y", "z"))
print(f"\ncached factorization: dims={desc.dims} sigma={desc.sigma} "
      f"blocks/device (Thm 1) = {desc.blocks_sent_per_device()} "
      f"vs direct {desc.p - 1}")

# 4. The collective itself (Listing 3, zero-copy), through the
#    communicator — the API root every collective hangs off:
comm = torus_comm(mesh, ("x", "y", "z"))
x = jnp.arange(12 * 12 * 4, dtype=jnp.float32).reshape(12, 12, 4)
fact = comm.all_to_all((4,), jnp.float32, backend="factorized").host_fn()
direct = comm.all_to_all((4,), jnp.float32, backend="direct").host_fn()
np.testing.assert_array_equal(np.asarray(fact(x)), np.asarray(direct(x)))
print("factorized(d=3) == direct all-to-all ✓  (12 devices)")

# 4b. The dimension-wise family on the same communicator: a sub-comm
#     over two of the axes, and the d-stage all-gather
sub = comm.sub(("x", "y"))
g = jnp.arange(12 * 3, dtype=jnp.int32).reshape(12, 3)
gathered = comm.all_gather((3,), jnp.int32, backend="factorized").host_fn()
np.testing.assert_array_equal(np.asarray(gathered(g))[0], np.asarray(g))
print(f"sub-comm over {sub.axis_names} dims={sub.dims}; "
      f"d-stage all_gather ✓")

# 5. Tuning: the paper's small-block/large-block crossover
for nbytes in (4, 400, 4_000_000):
    s = choose_algorithm((16, 16), (ICI, ICI), nbytes)
    print(f"block {nbytes:>9} B -> {s.kind:10s} dims={s.dims} "
          f"predicted {s.predicted_seconds * 1e6:.1f} us")
s = choose_algorithm((16, 2), (ICI, DCN), 4096)
print(f"cross-pod 4 KiB blocks -> {s.kind} dims={s.dims} "
      f"(hierarchical: ICI round + DCN round)")
