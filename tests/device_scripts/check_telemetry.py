"""Telemetry spine acceptance (12 CPU devices).

Part 1 — span coverage: with tracing enabled, factorized all-to-all
plans on a d=2 (3x4) and a d=3 (2x2x3) torus execute the *stepped*
per-round path; every plan execution must record exactly one
``plan.execute`` span with one ``plan.round`` child per dimension-wise
round (d children, axes in round order), bit-exact with the fused
untraced path.

Part 2 — unified snapshot: ``unified_stats()["telemetry"]["metrics"]``
must be the same merged MetricsRegistry snapshot
``telemetry.metrics_snapshot()`` returns.

Part 3 — drift under an injected fault: a ``FaultSpec(kind="slow")``
installed on the plan fires *inside* each round span (the
``_round_fault_check`` hook), driving measured/model ``drift_ratio``
above threshold; the watchdog's ``check_drift`` must surface a
"retune" recommendation event.

Part 4 — export: the tracer writes a valid Chrome-trace (Perfetto)
JSON document (path from argv[1] or ``TELEMETRY_TRACE_PATH``, default
``telemetry_trace.json``) that CI uploads as a workflow artifact.

Exits nonzero on any failure.
"""

import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.cache import cart_create, free_all
from repro.core.comm import free_comms, torus_comm, unified_stats
from repro.core.faults import FaultInjector, FaultSpec
from repro.core.plan import free_plans, plan_all_to_all
from repro.runtime.watchdog import StragglerWatchdog

N_EXEC = 3


def _execute(plan, x, n=N_EXEC):
    fn = plan.host_fn()
    out = None
    for _ in range(n):
        out = jax.block_until_ready(fn(x))
    return out


def check_span_coverage(tr, plan, x, axis_names):
    """Every traced execution: one plan.execute span, one plan.round
    child per dimension-wise round, rounds bit-exact with fused."""
    tr.clear()
    telemetry.disable_tracing()
    ref = _execute(plan, x, n=1)
    assert tr.spans() == [], "disabled tracer must record nothing"
    telemetry.enable_tracing()
    out = _execute(plan, x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    spans = tr.spans()
    execs = [s for s in spans
             if s.name == "plan.execute" and s.attrs["kind"] == "dense"]
    assert len(execs) == N_EXEC, \
        f"expected {N_EXEC} plan.execute spans, got {len(execs)}"
    d = len(axis_names)
    for ex in execs:
        rounds = [s for s in spans
                  if s.name == "plan.round" and s.parent_id == ex.span_id]
        assert len(rounds) == d, \
            f"expected {d} plan.round children, got {len(rounds)} " \
            f"(axes {[s.attrs.get('axis') for s in rounds]})"
        expected = [axis_names[k] for k in plan.order]
        assert [s.attrs["axis"] for s in rounds] == expected, \
            f"round axes {[s.attrs['axis'] for s in rounds]} != {expected}"
        for s in rounds:
            assert s.duration > 0.0
            assert s.attrs["dim"] > 1
            assert s.attrs["predicted_seconds"] > 0.0
        assert ex.attrs["measured_seconds"] > 0.0
        assert ex.attrs["drift_key"] == plan._drift_key()
    # per-axis drift series observed for every active axis
    det = telemetry.drift_detector()
    summ = det.summary()
    for name in axis_names:
        k = f"{plan._drift_key()}:axis={name}"
        assert k in summ and summ[k]["samples"] >= N_EXEC, \
            f"missing per-axis drift series {k}"
    print(f"OK span coverage d={d} "
          f"({'x'.join(str(s) for s in plan.dims)}): "
          f"{len(execs)} executions x {d} rounds")


def check_unified_snapshot():
    us = unified_stats()
    snap = telemetry.metrics_snapshot()
    assert us["telemetry"]["metrics"] == snap, \
        "unified_stats telemetry.metrics != metrics_snapshot()"
    assert us["telemetry"]["tracer"]["enabled"]
    assert "drift" in us["telemetry"]
    assert snap["plan.traced_executions"] >= 2 * N_EXEC
    for prefix in ("plan_cache.", "factorization.", "comms.",
                   "autotune."):
        assert any(k.startswith(prefix) for k in snap), \
            f"no {prefix}* keys in the merged snapshot"
    print("OK unified snapshot: metrics merged "
          f"({len(snap)} keys)")


def check_drift_retune(tr, plan, x):
    """Injected slow rounds -> drift above threshold -> watchdog retune."""
    det = telemetry.drift_detector()
    det.clear()
    inj = FaultInjector(specs=(
        FaultSpec(kind="slow", every=1, delay_seconds=0.05,
                  label="a2a.round"),))
    inj.install(plan, label="a2a")
    try:
        for _ in range(max(3, det.min_samples)):
            jax.block_until_ready(plan.host_fn()(x))
    finally:
        inj.uninstall(plan)
    assert inj.fired, "the injected slow-round spec never fired"
    key = plan._drift_key()
    ratio = det.drift_ratio(key)
    assert ratio is not None and ratio > det.threshold, \
        f"injected slow rounds left drift_ratio at {ratio}"
    assert plan.describe()["drift_ratio"] == ratio

    wd = StragglerWatchdog()
    recs = wd.check_drift(step=1)
    keys = [k for k, _ in recs]
    assert key in keys, f"no retune recommendation for {key} (got {keys})"
    assert all(a.kind == "retune" for _, a in recs)
    assert any(ev[0] == "drift" and ev[3] == key for ev in wd.events)
    assert telemetry.metrics().snapshot()["drift.retune_recommendations"] \
        >= 1
    print(f"OK drift retune: ratio {ratio:.1f} > "
          f"threshold {det.threshold} -> {len(recs)} recommendation(s)")


def check_export(tr, out_path):
    doc = tr.export_chrome_trace(out_path)
    loaded = json.loads(Path(out_path).read_text())
    assert loaded == doc
    assert loaded["displayTimeUnit"] == "ms"
    events = loaded["traceEvents"]
    assert events, "empty trace export"
    for ev in events:
        assert set(ev) == {"name", "ph", "ts", "dur", "pid", "tid",
                           "cat", "args"}
        assert ev["ph"] == "X"
    names = {ev["name"] for ev in events}
    assert {"plan.execute", "plan.round"} <= names
    print(f"OK export: {len(events)} trace events -> {out_path}")


def main():
    if jax.device_count() < 12:
        print(f"need 12 devices, have {jax.device_count()}",
              file=sys.stderr)
        return 1
    free_plans()
    free_comms()
    free_all()
    telemetry.reset_telemetry()
    tr = telemetry.enable_tracing(capacity=8192)

    # d=2: a 3x4 torus through the TorusComm surface
    mesh2 = cart_create(12, (3, 4), ("i", "j"))
    comm2 = torus_comm(mesh2, ("i", "j"))
    plan2 = comm2.all_to_all(block_shape=(4,), dtype=jnp.int32,
                             backend="factorized")
    x2 = jnp.arange(12 * 12 * 4, dtype=jnp.int32).reshape(12, 12, 4)
    check_span_coverage(tr, plan2, x2, ("i", "j"))

    # an overlap plan's compute-free exchange runs the same unchunked
    # rounds, so it steps them too, priced as factorized
    plan2o = comm2.all_to_all(block_shape=(4,), dtype=jnp.int32,
                              backend="overlap", n_chunks=2)
    check_span_coverage(tr, plan2o, x2, ("i", "j"))
    assert plan2o.predicted_seconds == plan2.predicted_seconds
    print("OK overlap plan steps its rounds")

    # d=3: a 2x2x3 torus through the plan factory
    mesh3 = cart_create(12, (2, 2, 3), ("x", "y", "z"))
    plan3 = plan_all_to_all(mesh3, ("x", "y", "z"), backend="factorized",
                            block_shape=(4,), dtype=jnp.int32)
    x3 = jnp.arange(12 * 12 * 4, dtype=jnp.int32).reshape(12, 12, 4)
    check_span_coverage(tr, plan3, x3, ("x", "y", "z"))

    check_unified_snapshot()
    check_drift_retune(tr, plan2, x2)

    out = sys.argv[1] if len(sys.argv) > 1 else \
        os.environ.get("TELEMETRY_TRACE_PATH", "telemetry_trace.json")
    check_export(tr, out)

    telemetry.reset_telemetry()
    print("OK check_telemetry")
    return 0


if __name__ == "__main__":
    sys.exit(main())
