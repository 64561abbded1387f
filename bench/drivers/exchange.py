"""Exchange cells: the MoE layer's dispatch and combine all-to-all, on its
own, at the block that the layer sends.

The block is the layer's ``(E_loc, C, d_model)`` per destination chip, with
``C`` the layer's capacity for the mix's tokens per chip.  The window
drives ``models.moe.moe_a2a_plan`` as the layer does: dispatch
(``plan.forward``) then combine (``plan.reverse``), chained inside one
jitted program of ``round_trips_per_call`` round trips, each exchange
waiting on the one before it.  ``exchange_us`` is the window over the
number of exchanges completed in it.

Afterwards the dispatched blocks of some calls (drawn from the seed, and
the last call) and what the combine gave back are compared bit for bit
with a plain numpy transpose of the block grid.
"""

from __future__ import annotations

import math
import time

from bench import harness
from bench.harness import Check, Outcome, span
from bench.traffic.gen import jax_seed, rng


def exchange_program(plan, mesh, spec, p: int, round_trips: int):
    """The timed program: ``x`` (the global ``(p, p, *block)`` send
    buffer) -> ``(z, y)``, where ``y`` is the last dispatch's output and
    ``z`` what the last combine gave back."""
    import jax

    def local(x):
        b = x[0].reshape(p, -1)
        b = jax.lax.fori_loop(0, round_trips - 1,
                              lambda _, b: plan.reverse(plan.forward(b)), b)
        y = plan.forward(b)
        z = plan.reverse(y)
        return z.reshape(x.shape), y.reshape(x.shape)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec,
                                 out_specs=(spec, spec)))


def geometry(cell, mesh):
    """The layer's own plan and block for this mesh and mix."""
    from repro.configs import get_config
    from repro.models import moe
    from repro.parallel.sharding import ep_axes
    prog = get_config(cell.config["registry"]).replace(
        **cell.config.get("program", {}))
    axes = ep_axes(mesh)
    _, p, e_loc, _ = moe._group_geometry(prog, mesh)
    cap = moe._capacity(prog, cell.mix["tokens_per_chip"],
                        max(prog.n_experts, p))
    plan = moe.moe_a2a_plan(prog, mesh, axes, e_loc, cap)
    return prog, axes, p, (e_loc, cap, prog.d_model), plan


def run(cell, *, seed, seconds, trace, peaks, hooks, control=False):
    harness.use_program()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh

    mix = cell.mix
    ref = harness.load_reference(cell.config)
    devices = jax.devices()[:cell.chips]
    mesh = make_host_mesh(devices)
    prog, axes, p, block, plan = geometry(cell, mesh)
    spec = P(tuple(reversed(axes)))
    sharding = NamedSharding(mesh, spec)
    dtype = prog.cdtype
    k = int(mix["round_trips_per_call"])
    block_bytes = math.prod(block) * jnp.dtype(dtype).itemsize
    harness.log(f"plan: backend {plan.backend} (asked "
                f"{plan.requested_backend!r}), torus dims {plan.dims}, "
                f"block {block} {jnp.dtype(dtype).name}, {block_bytes} B per "
                f"destination, {k} round trips per call")

    make_input = jax.jit(
        lambda key: jax.random.normal(key, (p, p) + block,
                                      jnp.float32).astype(dtype),
        out_shardings=sharding)
    key = jax.random.key(jax_seed(seed, "blocks"))
    call = exchange_program(plan, mesh, spec, p, k)

    x = make_input(key)
    z, y = call(x)                        # compiles; not part of the window
    jax.block_until_ready((z, y))
    x = z
    del y
    sample_at = set(int(i) for i in rng(seed, "sample").choice(
        int(mix["sample_from_calls"]), int(mix["sampled_calls"]),
        replace=False))
    tw = harness.TracedWindow(trace)
    trace_from = seconds / 2 - mix["trace_seconds"] / 2
    hooks.setup_done()

    samples = {}
    calls = traced_from = traced_calls = 0
    prev = None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if tw.enabled and not tw.done:
            if not tw.active and now >= trace_from:
                x.block_until_ready()
                tw.start()
                traced_from = calls
            elif tw.active and now >= trace_from + mix["trace_seconds"]:
                x.block_until_ready()
                tw.stop()
                traced_calls = calls - traced_from
        with span("call"):
            z, y = call(x)
        if calls in sample_at:
            samples[calls] = (x, y, z)
        last = (calls, x, y, z)
        if prev is not None:
            with span("wait"):
                prev.block_until_ready()    # at most one call queued
        prev = x = z
        calls += 1
    x.block_until_ready()
    t1 = time.perf_counter()
    if tw.active:
        tw.stop()
        traced_calls = calls - traced_from
    hooks.window_done()
    exchanges = calls * 2 * k
    mem = harness.memory_peak_bytes(devices)
    tw.reduce()
    samples[last[0]] = last[1:]

    # ---- comparison with the plain reference, after the window ----
    x0 = np.asarray(make_input(key))
    dispatch_bad = combine_bad = input_bad = 0
    for i in sorted(samples):
        xi, yi, zi = (np.asarray(a) for a in samples[i])
        input_bad += ref.mismatched_bytes(xi, x0)
        dispatch_bad += ref.mismatched_bytes(yi, ref.expected(xi))
        combine_bad += ref.mismatched_bytes(zi, xi)
    checks = [Check("dispatch_mismatched_bytes", dispatch_bad, 0),
              Check("combine_mismatched_bytes", combine_bad, 0),
              Check("input_mismatched_bytes", input_bad, 0)]
    notes = [f"{calls} calls, {exchanges} exchanges in {t1 - t0:.3f} s; "
             f"compared calls {sorted(samples)}"]
    counters = {"exchanges_traced": traced_calls * 2 * k, "p": p,
                "block_bytes": block_bytes}
    if control:     # the reference in the program's place, in fp8
        counters["control_mismatched_bytes"] = ref.mismatched_bytes(
            ref.lower_precision(x0), ref.expected(x0))
    return Outcome(
        e2e={"exchange_us": (t1 - t0) / exchanges * 1e6},
        checks=checks, attempted=exchanges, failed=0, memory_peak_bytes=mem,
        counters=counters, traced=tw, notes=notes)
