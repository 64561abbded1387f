"""The exchange cell at a size the CPU holds, on four forced host devices,
driven past the harness's look for a chip.  Run by
``test_exchange_small.py`` in a process of its own, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; prints one JSON
line per case: whether the run came out correct, and its counters."""

from __future__ import annotations

import json
import sys

from bench.drivers import exchange
from bench.tests import small

SEED = 2 ** 31 + 4242


def _run(cell, **kw):
    out = exchange.run(cell, seed=SEED, seconds=0.5, trace=False, peaks=None,
                       hooks=small.no_hooks(), **kw)
    return {"correct": small.correct(cell, out),
            "checks": {c.name: c.value for c in out.checks},
            "counters": {k: v for k, v in out.counters.items()
                         if isinstance(v, (int, float))}}


def _broken(make_local):
    """Replace the timed program by one whose per-device body is
    ``make_local(plan, p)``."""
    def build(plan, mesh, spec, p, round_trips):
        import jax
        return jax.jit(jax.shard_map(make_local(plan, p), mesh=mesh,
                                     in_specs=spec, out_specs=(spec, spec)))
    return build


def _left_out(plan, p):
    return lambda x: (x, x)                 # no exchange between chips


def _altered(plan, p):
    def local(x):
        b = x[0].reshape(p, -1)
        y = plan.forward(b)
        y = y.at[0, 0].add(1)               # one value altered
        return plan.reverse(y).reshape(x.shape), y.reshape(x.shape)
    return local


def _control(plan, mesh, spec, p, round_trips):
    """The plain reference in the program's place, carried in fp8."""
    import jax
    import jax.numpy as jnp

    def ref(x):
        y = jnp.swapaxes(x, 0, 1).astype(jnp.float8_e4m3fn).astype(x.dtype)
        return x, y
    return jax.jit(ref)


def main() -> int:
    good = exchange.exchange_program
    for backend in ("direct", "factorized", "overlap"):
        moe = {**small.SMALL_MOE, "program": {
            **small.SMALL_MOE["program"], "a2a_backend": backend}}
        c = small.cell("phi35moe.a2a_dispatch.2x2", moe, small.SMALL_DISPATCH)
        print(json.dumps({"case": f"sound/{backend}", **_run(c)}), flush=True)
    c = small.cell("phi35moe.a2a_dispatch.2x2", small.SMALL_MOE,
                   small.SMALL_DISPATCH)
    print(json.dumps({"case": "control_reading",
                      **_run(c, control=True)}), flush=True)
    for name, build in (("control", _control),
                        ("left_out", _broken(_left_out)),
                        ("altered", _broken(_altered))):
        exchange.exchange_program = build
        try:
            print(json.dumps({"case": name, **_run(c)}), flush=True)
        finally:
            exchange.exchange_program = good
    return 0


if __name__ == "__main__":
    sys.exit(main())
