"""Multi-device (subprocess) integration tests for the JAX collectives.

The pytest session keeps the default single CPU device; collective checks
run in subprocesses with ``--xla_force_host_platform_device_count``.
"""

import pytest

from _subproc import run_device_script


@pytest.mark.slow
def test_factorized_all_to_all_12dev():
    out = run_device_script("check_factorized.py", devices=12)
    assert "OK tiled" in out


@pytest.mark.slow
def test_zero_copy_hlo():
    out = run_device_script("check_zero_copy.py", devices=12)
    assert "zero-copy verified" in out


@pytest.mark.slow
@pytest.mark.parametrize("operand", ["flat", "blocks"])
@pytest.mark.parametrize("backend", ["overlap", "pipelined"])
def test_zero_copy_compute_free_exchange(backend, operand):
    # overlap / pipelined forward and reverse with no compute stage: d
    # all-to-alls per direction, as many as describe()["forward_chunks"]
    # (1) says, and no chunk slicing or concatenation under the plan's
    # a2a[<backend>] scope, on a 2x2 and a 2x3 torus.
    out = run_device_script("check_zero_copy.py", 6, "--backend", backend,
                            "--operand", operand)
    assert out.count(f"OK compute-free {backend} {operand}") == 2


@pytest.mark.slow
def test_plan_equivalence_12dev():
    # A2APlan.forward/reverse/tiled/overlap bit-exact with the legacy free
    # functions across backends x variants x round orders, shims warn, and
    # the plan registry amortizes construction.
    out = run_device_script("check_plan.py", devices=12)
    assert "OK plan forward/reverse == legacy free functions" in out
    assert "OK plan tiled == legacy tiled" in out
    assert "OK plan fused overlap == legacy overlapped_all_to_all" in out
    assert "OK plan cache amortizes" in out


@pytest.mark.slow
def test_autotune_measured_selection_12dev():
    # Empirical autotuner acceptance: measured winner bit-exact with the
    # analytic plan, warm-DB reconstruction performs zero timing
    # executions, deleted DB falls back to the cost model without error.
    out = run_device_script("check_autotune.py", devices=12)
    assert "OK autotuned == analytic bit-exact" in out
    assert "zero measurements" in out
    assert "OK deleted DB falls back" in out
    assert "OK subset-axes autotune" in out


@pytest.mark.slow
def test_ragged_alltoallv_12dev():
    # Ragged subsystem acceptance: bucketed and exact modes match the
    # simulator Alltoallv oracle bit-exactly, uniform-counts bucketed
    # execution is bit-exact with the dense A2APlan, and dropless MoE
    # (capacity_factor=None) equals the capacity-padded path whenever no
    # token would have been dropped.
    out = run_device_script("check_ragged.py", devices=12)
    assert "OK bucketed ragged == simulator oracle" in out
    assert "OK exact two-phase == simulator oracle" in out
    assert "OK uniform ragged == dense A2APlan bit-exact" in out
    assert out.count("OK dropless MoE == capacity MoE") == 4


@pytest.mark.slow
def test_sparse_alltoallv_12dev():
    # Sparse-neighborhood subsystem acceptance: the bucketed sparse plan
    # matches the simulator sparse oracle bit-exactly, degenerates to the
    # dense ragged path under uniform counts, skips >= 50% of per-round
    # peer exchanges at <= 10% density (the ISSUE bound, via plan stats),
    # and dropless MoE routes through the sparse plan when the tuning DB
    # names it the measured winner.
    out = run_device_script("check_sparse.py", devices=12)
    assert "OK bucketed sparse == simulator oracle" in out
    assert "OK uniform sparse == dense ragged bit-exact" in out
    assert out.count(">= 0.5") == 3
    assert "OK exact sparse == exact ragged == simulator oracle" in out
    assert "OK dropless MoE routes through sparse plan" in out


@pytest.mark.slow
def test_torus_comm_12dev():
    # TorusComm acceptance: sub-comm plans are the shared cached objects
    # and execute bit-exactly; the new all-gather / reduce-scatter family
    # matches the simulator oracles (pinned to the paper's 5x4 / 2x3x4
    # tori) and the direct collectives; the dims_create path builds its
    # own Cartesian mesh; one stats() call unifies the cache state; and
    # free() drops the comm's plan slice.
    out = run_device_script("check_comm.py", devices=12)
    assert "OK simulator oracles on the paper tori" in out
    assert "OK all-gather == simulator oracle" in out
    assert "OK reduce-scatter == simulator oracle" in out
    assert "OK sub-comm plans == top-level plans" in out
    assert "OK sub-comm execution bit-exact" in out
    assert "OK torus_comm(p, d=2)" in out
    assert "OK unified stats + free()" in out


@pytest.mark.slow
def test_overlap_engine_parity():
    out = run_device_script("check_overlap.py", devices=8)
    assert "OK overlap==factorized==direct" in out
    assert "OK fwd/compute/reverse pipeline" in out
    assert "OK tiled overlap" in out
    assert "OK MoE overlap HLO interleaved" in out


@pytest.mark.slow
def test_moe_expert_parallel():
    out = run_device_script("check_moe_ep.py", devices=8)
    assert "replicated" in out and "partitioned" in out


@pytest.mark.slow
def test_ulysses_sequence_parallel():
    out = run_device_script("check_ulysses.py", devices=8)
    assert out.count("OK Ulysses") == 7
    assert out.count("backend=overlap") == 3


@pytest.mark.slow
def test_compressed_psum():
    out = run_device_script("check_compression.py", devices=8)
    assert "OK compressed" in out


@pytest.mark.slow
def test_elastic_restore():
    out = run_device_script("check_elastic.py", devices=8)
    assert "OK elastic" in out


@pytest.mark.slow
def test_elastic_rebuild_12dev():
    # Elastic rebuild acceptance: injected device loss is detected by the
    # watchdog policy, TorusComm.rebuild re-factorizes the survivors into
    # a valid d-factor torus with bit-exact resumed all-to-all (plan-LRU
    # slice invalidated, tuning winners migrated), and the elastic
    # trainer recovers through checkpoint restore onto the survivor mesh
    # with params identical to a direct-restore reference.
    out = run_device_script("check_rebuild.py", devices=12)
    assert "OK rebuild: (3,4) -> (2,4) survivor torus" in out
    assert "1 tuning record migrated" in out
    assert "OK elastic trainer: device loss at step 8" in out
    assert "OK rebuild: detect -> degrade -> rebuild -> resume" in out


@pytest.mark.slow
def test_pipeline_parallel():
    out = run_device_script("check_pipeline.py", devices=4)
    assert "pipeline gradients == sequential" in out


@pytest.mark.slow
def test_ring_attention():
    out = run_device_script("check_ring_attention.py", devices=8)
    assert out.count("OK ring attention") == 4


@pytest.mark.slow
def test_serving_disaggregated_12dev():
    # Serving spine acceptance: a (3,4) device-backed torus partitioned
    # into prefill/decode domains serves bit-exact with the colocated
    # ContinuousBatcher reference — KV handoff through the jitted
    # KVMigrationPlan collective — including an injected 4-rank loss
    # mid-stream (rebuild onto the (2,4) survivor torus, every in-flight
    # request replayed, zero dropped).
    out = run_device_script("check_serving.py", devices=12)
    assert "OK serving disaggregated:" in out
    assert "bit-exact vs colocated" in out
    assert "OK serving rebuild: lost 4 ranks mid-stream" in out
    assert "OK serving: disaggregated prefill/decode bit-exact" in out


@pytest.mark.slow
def test_pencil_fft_12dev():
    # Pencil-FFT workload acceptance: the kind="transpose" plan is a pure
    # re-shard on every dense backend (forward/inverse stages sharing one
    # cached inner dense plan), pencil_fft matches numpy.fft on slab /
    # pencil / real decompositions with an identity round-trip, rebuilding
    # resolves the identical cached TransposePlans, the jitted data path
    # has zero host round-trips, and the distributed spectral conv rides
    # it correctly.
    out = run_device_script("check_fft.py", devices=12)
    assert "OK pencil-transpose oracle on the paper tori" in out
    assert "OK transpose == pure re-shard" in out
    assert "OK 2-D slab (24,60) == numpy.fft" in out
    assert "OK 3-D pencil (6,12,8) == numpy.fft" in out
    assert "OK real 3-D pencil (6,12,14) == numpy.rfftn" in out
    assert "OK plan-cache reuse" in out
    assert "OK zero host round-trips" in out
    assert "OK distributed spectral conv == local FFT conv" in out


@pytest.mark.slow
def test_telemetry_12dev(tmp_path):
    # Telemetry spine acceptance: with tracing on, factorized plans on
    # d=2 (3x4) and d=3 (2x2x3) tori, and an overlap plan on the 3x4
    # torus, execute the stepped per-round path
    # bit-exact with the fused jit, recording one plan.round span per
    # dimension-wise round per execution; unified_stats() returns the
    # merged MetricsRegistry snapshot; an injected FaultSpec slow round
    # drives drift_ratio above threshold into a watchdog "retune"
    # recommendation; and the tracer exports valid Chrome-trace JSON.
    trace = tmp_path / "trace.json"
    out = run_device_script("check_telemetry.py", 12, str(trace))
    assert "OK span coverage d=2 (3x4): 3 executions x 2 rounds" in out
    assert "OK overlap plan steps its rounds" in out
    assert "OK span coverage d=3 (2x2x3): 3 executions x 3 rounds" in out
    assert "OK unified snapshot" in out
    assert "OK drift retune" in out
    assert "OK export" in out
    assert "OK check_telemetry" in out
    assert trace.exists()


def test_exchange_scopes_4dev():
    # Every dense backend's exchange runs under a2a[<backend>] in the
    # compiled HLO's op_name metadata, and the scope leaves the compiled
    # program unchanged.
    out = run_device_script("check_scopes.py", devices=4)
    for backend in ("direct", "factorized", "overlap"):
        assert f"OK a2a[{backend}]" in out
    assert "OK check_scopes" in out
