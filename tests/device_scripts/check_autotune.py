"""Empirical-autotuner acceptance suite (12 CPU devices).

Proves, on a (3, 4) torus with a throwaway tuning DB selected via the
``REPRO_TUNING_DB`` override:

(a) the autotuned plan (measured winner from ``core.autotune``) is
    bit-exact with the analytic ``backend="tuned"`` plan — measured
    selection changes the schedule, never the bytes;
(b) a second ``plan_all_to_all(..., backend="autotune")`` with a warm DB
    performs ZERO timing executions (``autotune_stats`` counter) — the
    search cost is paid once, ever, and the record round-trips through
    JSON to an identical plan;
(c) deleting the DB file falls back to the analytic ``choose_algorithm``
    choice without error (``tuned_from: "model"``).

Exits nonzero on any failure.
"""

import os
import sys
import tempfile
from pathlib import Path

_TMP = tempfile.mkdtemp(prefix="repro-autotune-")
os.environ["REPRO_TUNING_DB"] = str(Path(_TMP) / "tuning.json")

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.core.autotune import (autotune, autotune_stats,     # noqa: E402
                                 default_db_path,
                                 reset_autotune_stats)
from repro.core.cache import cart_create, free_all             # noqa: E402
from repro.core.plan import free_plans, plan_all_to_all        # noqa: E402

DIMS, NAMES = (3, 4), ("i", "j")
BLOCK, DTYPE = (48,), jnp.float32


def main():
    assert jax.device_count() >= 12, \
        f"need 12 devices, got {jax.device_count()}"
    assert str(default_db_path()).startswith(_TMP), \
        "REPRO_TUNING_DB override not honored"
    p = 12
    mesh = cart_create(p, DIMS, NAMES)
    x = (jnp.arange(p * p * BLOCK[0]) % 251) \
        .reshape((p, p) + BLOCK).astype(DTYPE)
    expected = np.array(x).transpose(1, 0, 2)

    # ---- (a) measured search; winner bit-exact with the analytic plan ----
    plan = autotune(mesh, NAMES, BLOCK, DTYPE, warmup=1, repeats=3,
                    budget_seconds=60.0)
    assert plan.tuned_from == "measured", plan.tuned_from
    assert autotune_stats()["timing_executions"] > 0
    analytic = plan_all_to_all(mesh, NAMES, BLOCK, DTYPE, backend="tuned")
    assert analytic.tuned_from == "model"
    got = np.array(plan.host_fn(mesh)(x))
    ref = np.array(analytic.host_fn(mesh)(x))
    np.testing.assert_array_equal(got, expected)
    np.testing.assert_array_equal(ref, expected)
    table = plan.describe()["measured"]["table"]
    # a compute-free overlap plan runs the factorized rounds unchunked,
    # so the search times no overlap candidate
    assert {r["backend"] for r in table} == {"direct", "factorized"}, table
    assert any(not r["eligible"] for r in table), \
        "factorization sweep rows missing"
    print(f"OK autotuned == analytic bit-exact "
          f"(winner={plan.backend}[n={plan.n_chunks}], "
          f"{len(table)} candidates measured)")

    # ---- (b) warm DB: reconstruction measures nothing ----
    free_plans()
    free_all()
    reset_autotune_stats()
    p2 = plan_all_to_all(mesh, NAMES, BLOCK, DTYPE, backend="autotune")
    stats = autotune_stats()
    assert stats["timing_executions"] == 0, stats
    assert stats["db_hits"] == 1 and stats["db_misses"] == 0, stats
    assert p2.tuned_from == "measured"
    assert (p2.backend, p2.order, p2.n_chunks) == \
        (plan.backend, plan.order, plan.n_chunks)
    np.testing.assert_array_equal(np.array(p2.host_fn(mesh)(x)), expected)
    print(f"OK warm-DB hit rebuilds the winner with zero measurements "
          f"({stats})")

    # ---- (c) DB deleted: analytic fallback, no error, no measurement ----
    default_db_path().unlink()
    free_plans()
    reset_autotune_stats()
    p3 = plan_all_to_all(mesh, NAMES, BLOCK, DTYPE, backend="autotune")
    stats = autotune_stats()
    assert stats["timing_executions"] == 0, stats
    assert stats["db_misses"] == 1, stats
    assert p3.tuned_from == "model"
    assert p3.backend == analytic.backend and p3.n_chunks == \
        analytic.n_chunks
    np.testing.assert_array_equal(np.array(p3.host_fn(mesh)(x)), expected)
    print(f"OK deleted DB falls back to the analytic choice "
          f"(backend={p3.backend}, tuned_from=model)")

    # ---- subset axes: tuned axes spanning only part of the mesh (the
    # MoE EP shape — e.g. EP axes next to an untuned "model" axis); the
    # factorization sweep must rebuild its aux meshes over one subgroup's
    # devices, not the whole mesh ----
    sub_mesh = cart_create(12, (2, 3, 2), ("a", "b", "c"))
    sub_p = 6
    plan_s = autotune(sub_mesh, ("a", "b"), BLOCK, DTYPE, warmup=1,
                      repeats=2, budget_seconds=60.0)
    assert plan_s.tuned_from == "measured" and plan_s.p == sub_p
    xs = (jnp.arange(sub_p * sub_p * BLOCK[0]) % 251) \
        .reshape((sub_p, sub_p) + BLOCK).astype(DTYPE)
    got = np.array(plan_s.host_fn(sub_mesh)(xs))
    np.testing.assert_array_equal(got, np.array(xs).transpose(1, 0, 2))
    assert any(not r["eligible"]
               for r in plan_s.describe()["measured"]["table"]), \
        "subset-axes factorization sweep missing"
    print(f"OK subset-axes autotune (p={sub_p} of 12 devices, "
          f"winner={plan_s.backend})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
