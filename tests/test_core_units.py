"""Unit tests: dims_create, tuning model, guidelines checker, HLO parser,
descriptor cache.  (Property tests: test_core_properties.py, behind
``pytest.importorskip("hypothesis")``.)"""

import math

import pytest

from repro.core.cache import TorusFactorization, cache_stats, free, \
    get_factorization
from repro.core.dims import dims_create, max_dims, prime_factorization
from repro.core.guidelines import Measurement, check_guidelines
from repro.core.hlo_inspect import parse_hlo, shape_bytes
from repro.core.tuning import (DCN, ICI, choose_algorithm,
                               candidate_factorizations,
                               crossover_block_bytes, mesh_links,
                               predict_direct, predict_factorized)


class TestDimsCreate:
    def test_paper_table1(self):
        # Table 1: the spec-conforming factorizations of p = 36*32 = 1152.
        assert dims_create(1152, 2) == (36, 32)
        assert dims_create(1152, 3) == (12, 12, 8)
        assert dims_create(1152, 4) == (8, 6, 6, 4)
        # The paper's d = "ceil(log2 p)" row lists the 9-factor prime
        # factorization 3x3x2^7:
        assert dims_create(1152, 9) == (3, 3, 2, 2, 2, 2, 2, 2, 2)
        assert max_dims(1152) == 11  # ceil(log2 1152); extra dims pad with 1
        assert dims_create(1152, 11) == (3, 3, 2, 2, 2, 2, 2, 2, 2, 1, 1)

    def test_openmpi_violation_not_reproduced(self):
        # The OpenMPI bug: 48x24. Correct per spec: 36x32.
        assert dims_create(1152, 2) != (48, 24)

    def test_powers_of_two(self):
        assert dims_create(512, 2) == (32, 16)
        assert dims_create(512, 3) == (8, 8, 8)
        assert dims_create(256, 2) == (16, 16)
        assert prime_factorization(512) == [2] * 9


class TestTuning:
    def test_small_blocks_prefer_factorized(self):
        # Paper §5: d=2,3 beats direct for <=100 ints on a uniform network.
        s = choose_algorithm((16, 16), (ICI, ICI), block_bytes=4)
        assert s.kind == "factorized"

    def test_large_blocks_prefer_direct(self):
        s = choose_algorithm((16, 16), (ICI, ICI), block_bytes=1 << 20)
        assert s.kind == "direct"

    def test_crossover_is_monotone(self):
        c = crossover_block_bytes((16, 16), (ICI, ICI))
        assert 4 < c < (1 << 22)
        small = choose_algorithm((16, 16), (ICI, ICI), c // 2)
        big = choose_algorithm((16, 16), (ICI, ICI), c * 2)
        assert small.kind == "factorized" and big.kind == "direct"

    def test_dcn_axis_ordering_matters(self):
        # With a slow pod axis, factorized should beat a direct collective
        # bounded by the DCN link for medium messages.
        t_f = predict_factorized((16, 2), (ICI, DCN), 1024, 32)
        t_d = predict_direct(32, 1024, DCN)
        assert t_f < t_d

    @pytest.mark.parametrize("slices,want", [
        ([[0, 0], [0, 0]], (ICI, ICI)),
        ([[0, 0], [1, 1]], (ICI, DCN)),      # pods in different slices
        (None, (ICI, ICI)),                  # one slice: no slice_index
    ])
    def test_mesh_links_observe_slices(self, slices, want):
        import types
        import numpy as np
        devs = np.empty((2, 2), dtype=object)
        for i in range(2):
            for j in range(2):
                devs[i, j] = types.SimpleNamespace(platform="tpu")
                if slices is not None:
                    devs[i, j].slice_index = slices[i][j]
        mesh = types.SimpleNamespace(devices=devs,
                                     axis_names=("pod", "data"))
        assert mesh_links(mesh, ("data", "pod")) == want

    def test_mesh_links_defer_to_names_off_tpu(self):
        import jax
        mesh = jax.make_mesh((1, 1), ("pod", "data"))
        assert mesh_links(mesh, ("data", "pod")) is None

    def test_candidates_cover_paper_sweep(self):
        cands = candidate_factorizations(1152)
        assert (36, 32) in cands and (12, 12, 8) in cands \
            and (8, 6, 6, 4) in cands


class TestGuidelines:
    def test_detects_violation(self):
        ms = [Measurement("direct", 100, 10e-6),
              Measurement("factorized[d=2]", 100, 1e-6),
              Measurement("direct", 10000, 1e-6),
              Measurement("factorized[d=2]", 10000, 5e-6)]
        v = check_guidelines(ms)
        assert len(v) == 1 and v[0].block_elems == 100
        assert v[0].factor == pytest.approx(10.0)

    def test_tolerance(self):
        ms = [Measurement("direct", 1, 1.05e-6),
              Measurement("factorized[d=2]", 1, 1.00e-6)]
        assert check_guidelines(ms, tolerance=1.10) == []


HLO_SAMPLE = """
HloModule test
ENTRY %main (p0: f32[16,128]) -> f32[16,128] {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ata = f32[16,128]{1,0} all-to-all(%p0), replica_groups={{0,1}}
  %t = f32[128,16]{1,0} transpose(%ata), dimensions={1,0}
  %cp = f32[128,16]{1,0} copy(%t)
  %t2 = f32[16,128]{1,0} transpose(%cp), dimensions={1,0}
  ROOT %ar = f32[16,128]{1,0} all-reduce(%t2), to_apply=%add
}
"""


class TestHloInspect:
    def test_shape_bytes(self):
        assert shape_bytes("f32[16,128]{1,0}") == 16 * 128 * 4
        assert shape_bytes("bf16[2,3]") == 12
        assert shape_bytes("(f32[4], u32[2])") == 24
        assert shape_bytes("f32[]") == 4

    def test_parse_and_account(self):
        rep = parse_hlo(HLO_SAMPLE)
        kinds = rep.op_counts
        assert kinds["all-to-all"] == 1 and kinds["all-reduce"] == 1
        assert kinds["transpose"] == 2 and kinds["copy"] == 1
        assert rep.collective_bytes() == 2 * 16 * 128 * 4
        mv = rep.movement_ops_between_collectives()
        assert {o.kind for o in mv} == {"transpose", "copy"}


class TestCache:
    def test_descriptor_and_theorem1(self):
        t = TorusFactorization(("a", "b"), (4, 8))
        assert t.p == 32 and t.d == 2 and t.sigma == (1, 4)
        assert t.blocks_sent_per_device() == 2 * 32 - (8 + 4)

    def test_caching_amortizes(self):
        import jax
        from jax.sharding import Mesh
        import numpy as np
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("x", "y"))
        before = cache_stats()["cart_creates"]
        f1 = get_factorization(mesh, ("y", "x"))
        f2 = get_factorization(mesh, ("y", "x"))
        assert f1 is f2
        assert cache_stats()["cart_creates"] == before + 1
        free(f1)
        f3 = get_factorization(mesh, ("y", "x"))
        assert cache_stats()["cart_creates"] == before + 2
        assert f3 == f1

    def test_cache_survives_mesh_rebuild(self):
        # The fingerprint must be stable device identity (device.id,
        # platform), not object identity: re-looking up through a freshly
        # constructed Mesh over the same devices must hit the cache.
        import jax
        from jax.sharding import Mesh
        import numpy as np
        arr = np.array(jax.devices()[:1]).reshape(1, 1)
        m1 = Mesh(arr.copy(), ("u", "v"))
        before = cache_stats()["cart_creates"]
        f1 = get_factorization(m1, ("v", "u"))
        m2 = Mesh(arr.copy(), ("u", "v"))   # new Mesh, same devices
        f2 = get_factorization(m2, ("v", "u"))
        assert f1 is f2
        assert cache_stats()["cart_creates"] == before + 1
        free(f1)
