"""Device-free unit tests for the A2APlan API (core.plan): resolution,
describe() golden dict, the LRU plan registry, the bounded factorization
cache, and the deprecation shims.

Multi-device bit-exactness of plan execution against the legacy free
functions runs in ``tests/device_scripts/check_plan.py`` (see
test_multidevice.py).
"""

import math
import warnings

import pytest

from repro.core import cache as core_cache
from repro.core import plan as core_plan
from repro.core.cache import (
    LRUCache,
    cache_stats,
    cart_create,
    free_all,
    get_factorization,
    set_cache_capacity,
)
from repro.core.plan import (
    A2APlan,
    free_plans,
    plan_all_to_all,
    plan_cache_stats,
    set_plan_cache_capacity,
)
from repro.core.tuning import DCN, ICI, choose_algorithm, predict_factorized


@pytest.fixture(autouse=True)
def _fresh_registries():
    """Each test sees empty registries at default capacity and leaves the
    module state the way it found it."""
    free_plans()
    free_all()
    core_plan._PLANS.stats.update(hits=0, misses=0, evictions=0)
    core_cache._REGISTRY.stats.update(hits=0, misses=0, evictions=0)
    old_plan_cap = core_plan._PLANS.capacity
    old_fact_cap = core_cache._REGISTRY.capacity
    yield
    set_plan_cache_capacity(old_plan_cap)
    set_cache_capacity(old_fact_cap)
    free_plans()
    free_all()


class TestLRUCache:
    def test_eviction_order_and_stats(self):
        c = LRUCache(capacity=2)
        c.put("a", 1)
        c.put("b", 2)
        assert c.get("a") == 1          # refreshes "a"
        c.put("c", 3)                   # evicts LRU "b"
        assert c.get("b") is None
        assert c.get("a") == 1 and c.get("c") == 3
        assert c.stats == {"hits": 3, "misses": 1, "evictions": 1}

    def test_set_capacity_shrinks(self):
        c = LRUCache(capacity=8)
        for i in range(8):
            c.put(i, i)
        c.set_capacity(3)
        assert len(c) == 3
        assert c.stats["evictions"] == 5

    def test_evict_callback(self):
        seen = []
        c = LRUCache(capacity=1, on_evict=seen.append)
        c.put("a", "va")
        c.put("b", "vb")
        assert seen == ["va"]


class TestPlanResolution:
    def test_explicit_backends(self):
        for backend in ("direct", "factorized", "pipelined", "overlap"):
            p = plan_all_to_all((2, 3), ("i", "j"), (8,), "float32",
                                backend=backend)
            # pipelined: deprecated alias of factorized, no chunks
            assert p.backend == ("factorized" if backend == "pipelined"
                                 else backend)
            assert p.requested_backend == backend
        assert plan_all_to_all((2, 3), ("i", "j"), backend="pipelined",
                               n_chunks=4).n_chunks == 1
        assert plan_all_to_all((2, 3), ("i", "j"), backend="overlap",
                               n_chunks=0).n_chunks == 2
        assert plan_all_to_all((2, 3), ("i", "j"), backend="factorized",
                               ).n_chunks == 1

    def test_tuned_matches_choose_algorithm(self):
        dims, links = (16, 4), (ICI, DCN)
        for bytes_ in (4.0, float(1 << 16), float(1 << 24)):
            sched = choose_algorithm(dims, links, bytes_, max_chunks=8)
            p = plan_all_to_all(dims, ("i", "j"), (int(bytes_),), "int8",
                                backend="tuned", max_chunks=8, links=links)
            assert p.backend == sched.kind
            assert p.n_chunks == max(1, sched.n_chunks)
            assert p.schedule.predicted_seconds == \
                pytest.approx(sched.predicted_seconds)

    def test_tuned_needs_cost_inputs(self):
        with pytest.raises(ValueError, match="tuned"):
            plan_all_to_all((2, 2), ("i", "j"), backend="tuned")

    def test_round_order_validated_at_plan_time(self):
        with pytest.raises(ValueError, match="permutation"):
            plan_all_to_all((2, 3), ("i", "j"), backend="factorized",
                            round_order=(0, 0))
        # trivial (size-1) dims are skipped before validation
        p = plan_all_to_all((2, 1, 3), ("i", "j", "k"),
                            backend="factorized", round_order=(1, 0))
        assert p.order == (1, 0) and p.rev_order == (0, 1)

    def test_unknown_backend_and_variant(self):
        with pytest.raises(ValueError, match="backend"):
            plan_all_to_all((2, 2), ("i", "j"), backend="quantum")
        with pytest.raises(ValueError, match="variant"):
            plan_all_to_all((2, 2), ("i", "j"), backend="direct",
                            variant="sideways")

    def test_default_links_flag_pod_as_dcn(self):
        p = plan_all_to_all((4, 2), ("data", "pod"), backend="factorized")
        assert p.links == (ICI, DCN)


class TestDescribeGolden:
    def test_golden_dict(self):
        p = plan_all_to_all((4, 2), ("i", "j"), (16, 8), "bfloat16",
                            backend="overlap", variant="paper",
                            round_order=(1, 0), n_chunks=3,
                            links=(ICI, DCN))
        d = p.describe()
        pred = d.pop("predicted_seconds")
        assert pred > 0
        assert d == {
            "kind": "dense",
            "axis_names": ["i", "j"],
            "dims": [4, 2],
            "p": 8,
            "d": 2,
            "backend": "overlap",
            "requested_backend": "overlap",
            "variant": "paper",
            "round_order": [1, 0],
            "reverse_round_order": [0, 1],
            "n_chunks": 3,
            "forward_chunks": 1,
            "block_shape": [16, 8],
            "dtype": "bfloat16",
            "block_bytes": 256,
            "blocks_sent_per_device": 2 * 8 - (2 + 4),   # Theorem 1
            "links": [{"alpha": ICI.alpha, "bandwidth": ICI.bandwidth},
                      {"alpha": DCN.alpha, "bandwidth": DCN.bandwidth}],
            "tuned_from": None,     # explicit backend: no tuning provenance
            "measured": None,
            "cache": "miss",
            "drift_ratio": None,    # no traced executions observed yet
        }

    @pytest.mark.parametrize("backend", ["tuned", "overlap"])
    def test_forward_path_is_unchunked(self, backend):
        # the exchange cell's geometry: phi3.5-moe's dispatch block over
        # the 2x2 host's ICI links.  "tuned" still picks overlap with 2
        # chunks there, from predict_overlapped's price for chunked
        # compute-free rounds that no longer run: a known-stale choice
        # until the cost model is re-priced.  forward / reverse / tiled
        # have no compute stage and use one chunk, and describe() and
        # drift price them as the factorized rounds they run.
        p = plan_all_to_all((2, 2), ("data", "pod"), (4, 640, 4096),
                            "bfloat16", backend=backend, max_chunks=4,
                            links=(ICI, ICI))
        d = p.describe()
        assert d["backend"] == "overlap" and d["n_chunks"] == 2
        assert d["forward_chunks"] == 1
        assert d["predicted_seconds"] == pytest.approx(predict_factorized(
            (2, 2), (ICI, ICI), float(p.block_bytes), 4))
        assert d["predicted_seconds"] > p.schedule.predicted_seconds

    def test_describe_is_json_serializable(self):
        import json
        p = plan_all_to_all((2, 2), ("i", "j"), (4,), "float32",
                            backend="tuned")
        json.dumps(p.describe())

    def test_no_cost_inputs_yields_none_fields(self):
        d = plan_all_to_all((2, 2), ("i", "j"),
                            backend="factorized").describe()
        assert d["block_shape"] is None and d["dtype"] is None
        assert d["block_bytes"] is None and d["predicted_seconds"] is None


class TestRaggedPlan:
    """Device-free resolution/registry tests for RaggedA2APlan; bucketed
    execution vs the oracle runs in check_ragged.py (12 devices)."""

    def test_describe_golden(self):
        from repro.core.plan import plan_ragged_all_to_all

        p = plan_ragged_all_to_all((4, 2), ("i", "j"), (16,), "bfloat16",
                                   max_count=12, avg_count=6.0,
                                   backend="factorized", variant="paper",
                                   round_order=(1, 0), links=(ICI, DCN))
        d = p.describe()
        pred = d.pop("predicted_seconds")
        assert pred > 0
        assert d == {
            "kind": "ragged",
            "axis_names": ["i", "j"],
            "dims": [4, 2],
            "p": 8,
            "d": 2,
            "backend": "factorized",
            "requested_backend": "factorized",
            "variant": "paper",
            "round_order": [1, 0],
            "reverse_round_order": [0, 1],
            "n_chunks": 1,
            "row_shape": [16],
            "dtype": "bfloat16",
            "row_bytes": 32,
            "max_count": 12,
            "avg_count": 6.0,
            "bucket": 16,                       # next pow2 of 12
            "bucket_block_bytes": 16 * 32,
            "expected_occupancy": 6.0 / 16,
            "counts_backend": "factorized",     # tiny int32 block: tuned
            "counts_block_bytes": 8 * 4,        # one full count row
            "blocks_sent_per_device": 2 * 8 - (2 + 4),
            "links": [{"alpha": ICI.alpha, "bandwidth": ICI.bandwidth},
                      {"alpha": DCN.alpha, "bandwidth": DCN.bandwidth}],
            "tuned_from": None,
            "measured": None,
            "cache": "miss",
            "drift_ratio": None,
        }
        import json
        json.dumps(p.describe())

    def test_registry_identity_and_sharing(self):
        from repro.core.plan import plan_ragged_all_to_all

        a = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=5)
        b = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=5)
        assert a is b and b.describe()["cache"] == "hit"
        # distinct max_count -> distinct bucket -> distinct plan
        c = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=9)
        assert c is not a and c.bucket == 16
        # the underlying dense data/counts plans live in the same registry
        # (two ragged plans over the same torus share the counts plan)
        assert a.counts_plan is c.counts_plan

    def test_validation(self):
        from repro.core.plan import plan_ragged_all_to_all

        with pytest.raises(ValueError, match="bucket bound"):
            plan_ragged_all_to_all((2, 2), ("i", "j"), (4,), "float32",
                                   max_count=0)
        with pytest.raises(ValueError, match="avg_count"):
            plan_ragged_all_to_all((2, 2), ("i", "j"), (4,), "float32",
                                   max_count=4, avg_count=9.0)
        with pytest.raises(ValueError, match="backend"):
            plan_ragged_all_to_all((2, 2), ("i", "j"), (4,), "float32",
                                   max_count=4, backend="quantum")

    def test_predicted_includes_counts_phase(self):
        from repro.core.plan import plan_ragged_all_to_all
        from repro.core.tuning import predict_ragged

        dims, links = (4, 2), (ICI, DCN)
        p = plan_ragged_all_to_all(dims, ("i", "j"), (16,), "float32",
                                   max_count=8, backend="factorized",
                                   links=links)
        want = predict_ragged(dims, links, 16 * 4, p.bucket, p.p)
        assert p.predicted_seconds == pytest.approx(want)

    def test_tuned_matches_choose_ragged_algorithm(self):
        from repro.core.plan import plan_ragged_all_to_all
        from repro.core.tuning import choose_ragged_algorithm

        dims, links = (16, 4), (ICI, DCN)
        for row_bytes, max_count in ((4, 2), (1 << 12, 64)):
            sched = choose_ragged_algorithm(
                dims, links, float(row_bytes),
                plan_ragged_all_to_all(dims, ("i", "j"), (row_bytes,),
                                       "int8", max_count=max_count,
                                       links=links).bucket,
                max_chunks=8)
            plan = plan_ragged_all_to_all(dims, ("i", "j"), (row_bytes,),
                                          "int8", max_count=max_count,
                                          backend="tuned", max_chunks=8,
                                          links=links)
            assert plan.backend == sched.kind
            assert plan.predicted_seconds == \
                pytest.approx(sched.predicted_seconds)


class TestPlanRegistry:
    def test_same_key_hits(self):
        a = plan_all_to_all((2, 2), ("i", "j"), (8,), "float32",
                            backend="tuned")
        b = plan_all_to_all((2, 2), ("i", "j"), (8,), "float32",
                            backend="tuned")
        assert a is b
        assert a.describe()["cache"] == "hit"
        stats = plan_cache_stats()
        assert stats["hits"] == 1 and stats["misses"] == 1

    def test_distinct_keys_miss(self):
        a = plan_all_to_all((2, 2), ("i", "j"), (8,), "float32",
                            backend="direct")
        b = plan_all_to_all((2, 2), ("i", "j"), (16,), "float32",
                            backend="direct")
        c = plan_all_to_all((2, 2), ("i", "j"), (8,), "int32",
                            backend="direct")
        assert a is not b and a is not c
        assert plan_cache_stats()["size"] == 3

    def test_registry_is_bounded(self):
        set_plan_cache_capacity(4)
        for k in range(20):
            plan_all_to_all((2, 2), ("i", "j"), (k + 1,), "float32",
                            backend="direct")
        stats = plan_cache_stats()
        assert stats["size"] <= 4
        assert stats["evictions"] == 16
        free_plans()
        assert plan_cache_stats()["size"] == 0


class TestPlanTeardownSymmetry:
    """The registry delete-callback fix: evicting (or dropping) a
    composite plan tears down its nested dense entries and releases the
    factorization refs it pinned, keeping plan and factorization cache
    stats balanced."""

    def test_evicting_ragged_plan_drops_nested_entries(self):
        from repro.core.plan import plan_ragged_all_to_all

        r = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=5)
        assert plan_cache_stats()["size"] == 3   # ragged + data + counts
        # refresh the nested plans' recency so the composite is the LRU
        # victim, then squeeze: evicting it must drop both nested entries
        core_plan._PLANS.get(r.data._registry_key)
        core_plan._PLANS.get(r.counts_plan._registry_key)
        set_plan_cache_capacity(3)
        plan_all_to_all((5,), ("z",), (4,), "float32", backend="direct")
        assert r._registry_key not in core_plan._PLANS
        assert r.data._registry_key not in core_plan._PLANS
        assert r.counts_plan._registry_key not in core_plan._PLANS
        assert plan_cache_stats()["size"] == 1   # only the flooding plan

    def test_shared_counts_plan_survives_sibling_eviction(self):
        from repro.core.plan import plan_ragged_all_to_all

        a = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=5)
        b = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=9)
        assert a.counts_plan is b.counts_plan
        core_plan._drop_plan(a._registry_key)
        # a's private data plan went with it; the shared counts plan is
        # still owned by the live sibling and must stay
        assert a.data._registry_key not in core_plan._PLANS
        assert b.counts_plan._registry_key in core_plan._PLANS
        assert b._registry_key in core_plan._PLANS
        assert b.data._registry_key in core_plan._PLANS

    def test_eviction_releases_factorization_refs(self):
        mesh = cart_create(1, (1,), ("x",))
        base = cache_stats()["size"]
        plan = plan_all_to_all(mesh, ("x",), (4,), "float32",
                               backend="direct")
        assert cache_stats()["size"] == base + 1
        core_plan._drop_plan(plan._registry_key)
        # last plan over the descriptor: the registry entry is released
        assert cache_stats()["size"] == base

    def test_free_plans_leaves_stats_balanced(self):
        from repro.core.plan import plan_ragged_all_to_all

        mesh = cart_create(1, (1,), ("x",))
        base = cache_stats()["size"]
        plan_ragged_all_to_all(mesh, ("x",), (4,), "float32", max_count=3)
        plan_all_to_all(mesh, ("x",), (8,), "float32", backend="direct")
        assert plan_cache_stats()["size"] == 4
        assert cache_stats()["size"] == base + 1
        free_plans()
        assert plan_cache_stats()["size"] == 0
        assert cache_stats()["size"] == base


class TestFactorizationCacheBounded:
    def test_mesh_rebuilds_do_not_grow_cache(self):
        # The satellite regression: a serving loop that rebuilds its Mesh
        # every step must not grow the registry — the (device.id,
        # platform) fingerprint keys all rebuilds to one entry.
        import jax
        n = min(1, len(jax.devices()))
        assert n == 1
        before = cache_stats()["size"]
        for _ in range(10):
            mesh = cart_create(1, (1,), ("t0",))
            get_factorization(mesh, ("t0",))
        stats = cache_stats()
        assert stats["size"] == before + 1
        assert stats["hits"] >= 9

    def test_capacity_bounds_distinct_entries(self):
        set_cache_capacity(3)
        mesh = cart_create(1, (1,), ("x",))
        for v in range(8):
            get_factorization(mesh, ("x",), variant=f"natural{v}")
        stats = cache_stats()
        assert stats["size"] <= 3
        assert stats["evictions"] >= 5


class TestShims:
    """The legacy free functions delegate through plans and warn."""

    def _single_device_mesh(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:1]), ("x",))

    def test_factorized_shim_warns_and_matches_plan(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.factorized import factorized_all_to_all

        mesh = self._single_device_mesh()
        x = jnp.arange(8.0).reshape(1, 8)
        plan = plan_all_to_all(mesh, ("x",), (8,), x.dtype,
                               backend="factorized")

        def loc_plan(xl):
            return plan.forward(xl)

        def loc_shim(xl):
            return factorized_all_to_all(xl, ("x",))

        run = lambda loc: jax.jit(jax.shard_map(
            loc, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
        got_plan = np.array(run(loc_plan))
        with pytest.warns(DeprecationWarning, match="plan_all_to_all"):
            got_shim = np.array(run(loc_shim))
        np.testing.assert_array_equal(got_plan, got_shim)
        np.testing.assert_array_equal(got_plan, np.array(x))

    def test_host_alltoall_shim_builds_plan(self):
        import jax.numpy as jnp
        import numpy as np
        from repro.core.factorized import host_alltoall

        mesh = self._single_device_mesh()
        with pytest.warns(DeprecationWarning, match="host_fn"):
            fn = host_alltoall(mesh, ("x",), backend="factorized")
        x = jnp.arange(4.0).reshape(1, 1, 4)
        np.testing.assert_array_equal(np.array(fn(x)), np.array(x))
        assert plan_cache_stats()["misses"] >= 1

    def test_every_shim_warns(self):
        import jax.numpy as jnp
        from repro.core import factorized as f
        from repro.core import overlap as o

        x = jnp.zeros((1, 4))
        mesh = self._single_device_mesh()
        import jax
        from jax.sharding import PartitionSpec as P

        shim_calls = [
            lambda xl: f.direct_all_to_all(xl, ("x",)),
            lambda xl: f.factorized_all_to_all(xl, ("x",)),
            lambda xl: f.factorized_all_to_all_tiled(xl, ("x",), 0, 0),
            lambda xl: f.direct_all_to_all_tiled(xl, ("x",), 0, 0),
            lambda xl: o.overlapped_all_to_all(xl, ("x",)),
            lambda xl: o.overlapped_all_to_all_tiled(xl, ("x",), 0, 0),
            lambda xl: o.pipelined_all_to_all(xl, ("x",)),
        ]
        for call in shim_calls:
            with pytest.warns(DeprecationWarning):
                jax.jit(jax.shard_map(call, mesh=mesh, in_specs=P("x"),
                                      out_specs=P("x")))(x)


class TestPlanTrivialTorus:
    def test_p1_forward_is_identity_and_overlap_computes(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
        plan = plan_all_to_all(mesh, ("x",), (8,), "float32",
                               backend="overlap", n_chunks=2)
        x = jnp.arange(8.0).reshape(1, 8)

        def loc(xl):
            return plan.overlap(xl, lambda chunk, c: chunk * (c + 1.0),
                                reverse=False)

        y = jax.jit(jax.shard_map(loc, mesh=mesh, in_specs=P("x"),
                                  out_specs=P("x")))(x)
        want = np.concatenate([np.arange(4.0), np.arange(4.0, 8.0) * 2.0])
        np.testing.assert_allclose(np.array(y), want.reshape(1, 8))

        def fwd(xl):
            return plan.forward(xl)

        z = jax.jit(jax.shard_map(fwd, mesh=mesh, in_specs=P("x"),
                                  out_specs=P("x")))(x)
        np.testing.assert_array_equal(np.array(z), np.array(x))


class TestMoEPlanConstruction:
    def test_config_parameterizes_plan(self):
        from repro.models.config import ModelConfig
        from repro.models.moe import moe_a2a_plan

        mesh = cart_create(1, (1, 1), ("pod", "data"))
        cfg = ModelConfig(name="t", family="moe", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab=64,
                          n_experts=2, top_k=1, a2a_backend="factorized")
        plan = moe_a2a_plan(cfg, mesh, ("data", "pod"), E_loc=2, C=8)
        assert isinstance(plan, A2APlan)
        assert plan.backend == "factorized"
        assert plan.block_shape == (2, 8, 32)
        assert moe_a2a_plan(cfg, mesh, (), 2, 8) is None
        # same geometry again: fetched from the registry, not rebuilt
        again = moe_a2a_plan(cfg, mesh, ("data", "pod"), E_loc=2, C=8)
        assert again is plan


class TestKVMigrationPlan:
    """Device-free resolution/registry/datatype tests for
    KVMigrationPlan; bit-exact disaggregated serving over the plan runs
    in check_serving.py (12 devices)."""

    def test_describe_golden(self):
        from repro.core.plan import plan_kv_migration

        p = plan_kv_migration((4, 2), ("i", "j"), (16,), "float32",
                              max_count=12, avg_count=6.0, n_prefill=3,
                              migrations_per_tick=2.0, backend="ragged",
                              variant="paper", round_order=(1, 0),
                              links=(ICI, DCN))
        d = p.describe()
        pred = d.pop("predicted_seconds")
        assert pred > 0
        assert d == {
            "kind": "kv_migrate",
            "inner_kind": "ragged",
            "axis_names": ["i", "j"],
            "dims": [4, 2],
            "p": 8,
            "d": 2,
            "backend": "factorized",    # inner data phase: cost model
            "requested_backend": "ragged",
            "variant": "paper",
            "row_shape": [16],
            "dtype": "float32",
            "row_bytes": 64,
            "max_count": 12,
            "avg_count": 6.0,
            "bucket": 16,               # next pow2 of 12
            "expected_occupancy": 6.0 / 16,
            "n_prefill": 3,
            "n_decode": 5,
            "migrations_per_tick": 2.0,
            # 2 migrating pairs in an 8x8 count matrix
            "expected_density": 2.0 / 64,
            "tuned_from": "model",
            "cache": "miss",
            "drift_ratio": None,
        }
        import json
        json.dumps(p.describe())

    def test_registry_identity_and_inner_sharing(self):
        from repro.core.plan import (RaggedA2APlan, SparseA2APlan,
                                     plan_kv_migration,
                                     plan_ragged_all_to_all)

        a = plan_kv_migration((2, 3), ("i", "j"), (4,), "float32",
                              max_count=5, n_prefill=2, backend="ragged")
        b = plan_kv_migration((2, 3), ("i", "j"), (4,), "float32",
                              max_count=5, n_prefill=2, backend="ragged")
        assert a is b and b.describe()["cache"] == "hit"
        assert isinstance(a.inner, RaggedA2APlan)
        # distinct n_prefill -> distinct plan, shared inner exchange
        c = plan_kv_migration((2, 3), ("i", "j"), (4,), "float32",
                              max_count=5, n_prefill=4, backend="ragged")
        assert c is not a and c.inner is a.inner
        # the inner ragged plan lives in the same registry
        r = plan_ragged_all_to_all((2, 3), ("i", "j"), (4,), "float32",
                                   max_count=5, backend="tuned")
        assert r is a.inner
        # an explicit sparse inner
        s = plan_kv_migration((2, 3), ("i", "j"), (4,), "float32",
                              max_count=5, n_prefill=2, backend="sparse")
        assert s.inner_kind == "sparse"
        assert isinstance(s.inner, SparseA2APlan)

    def test_tuned_matches_predict_kv_migration(self):
        from repro.core.plan import plan_kv_migration
        from repro.core.tuning import predict_kv_migration

        dims, links = (4, 2), (ICI, DCN)
        p = plan_kv_migration(dims, ("i", "j"), (16,), "float32",
                              max_count=8, n_prefill=3,
                              migrations_per_tick=2.0, links=links)
        sched = predict_kv_migration(dims, links, 16 * 4, p.bucket,
                                     n_prefill=3, migrations_per_tick=2.0)
        assert p.tuned_from == "model"
        assert p.inner_kind == \
            ("sparse" if sched.kind == "sparse" else "ragged")
        assert p.predicted_seconds == pytest.approx(sched.predicted_seconds)

    def test_validation(self):
        from repro.core.plan import plan_kv_migration

        with pytest.raises(ValueError, match="n_prefill"):
            plan_kv_migration((2, 2), ("i", "j"), (4,), "float32",
                              max_count=4, n_prefill=0)
        with pytest.raises(ValueError, match="n_prefill"):
            plan_kv_migration((2, 2), ("i", "j"), (4,), "float32",
                              max_count=4, n_prefill=4)
        with pytest.raises(ValueError, match="migrations_per_tick"):
            plan_kv_migration((2, 2), ("i", "j"), (4,), "float32",
                              max_count=4, n_prefill=2,
                              migrations_per_tick=0.0)

    def test_pair_counts_enforces_block_structure(self):
        from repro.core.plan import plan_kv_migration

        p = plan_kv_migration((2, 3), ("i", "j"), (4,), "float32",
                              max_count=5, n_prefill=2)
        counts = p.pair_counts({(0, 3): 2, (1, 5): 5})
        assert counts.shape == (6, 6)
        assert counts[0, 3] == 2 and counts[1, 5] == 5
        assert counts.sum() == 7
        with pytest.raises(ValueError, match="not a prefill"):
            p.pair_counts({(3, 4): 1})      # decode rank as source
        with pytest.raises(ValueError, match="not a decode"):
            p.pair_counts({(0, 1): 1})      # prefill rank as destination
        with pytest.raises(ValueError, match="max_count"):
            p.pair_counts({(0, 3): 6})      # over the bucket bound

    def test_exact_matches_oracle(self):
        import numpy as np

        from repro.core.plan import plan_kv_migration
        from repro.core.simulator import simulate_kv_migration

        dims, n_prefill = (2, 3), 2
        lengths = {(0, 2): 3, (0, 5): 1, (1, 4): 4}
        plan = plan_kv_migration(dims, ("i", "j"), (3,), "float32",
                                 max_count=4, n_prefill=n_prefill,
                                 backend="ragged")
        p = plan.p
        rows = [[np.arange(lengths.get((s, d), 0) * 3, dtype=np.float32)
                 .reshape(-1, 3) + 100 * s + 10 * d
                 for d in range(p)] for s in range(p)]
        recv, counts = plan.exact(rows)
        oracle, _ = simulate_kv_migration(dims, n_prefill, lengths)
        assert counts == [[len(rows[s][d]) for d in range(p)]
                          for s in range(p)]
        for r in range(p):
            for s in range(p):
                np.testing.assert_array_equal(recv[r][s], rows[s][r])
                assert len(oracle[r][s]) == len(recv[r][s])
        # sparse inner normalizes to the same (recv, counts) surface
        sp = plan_kv_migration(dims, ("i", "j"), (3,), "float32",
                               max_count=4, n_prefill=n_prefill,
                               backend="sparse")
        recv_s, counts_s = sp.exact(rows)
        assert counts_s == counts
        for r in range(p):
            for s in range(p):
                np.testing.assert_array_equal(recv_s[r][s], recv[r][s])
