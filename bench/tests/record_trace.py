"""Record a small trace on a chip, for a test of ``bench/reduce.py``:

  python3 -m bench.tests.record_trace bench/tests/data/small_trace

A few jitted calls, one op under a ``jax.named_scope``, inside the
harness's ``bench.window`` span, with host spans around the calls and a
host-only pause between them, so the trace has device ops, a scope and an
idle gap named by a host span.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import harness
from bench import reduce as red


def main(out: str) -> int:
    harness.require_chips(1)

    @jax.jit
    def f(x):
        with jax.named_scope("a2a_round[data]"):
            y = jnp.sin(x) * 2.0
        return (y @ y.T).sum()

    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tw = harness.TracedWindow(True)
    tw.start()
    for _ in range(3):
        with harness.span("call"):
            f(x).block_until_ready()
        with harness.span("pause"):
            time.sleep(0.01)
    tw.stop()
    src = red.find_xplane(tw._dir)
    dst = Path(out)
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, dst.with_suffix(".xplane.pb"))
    r = red.reduce(red.load(src))
    print(r.window_s, r.busy_s, r.n_devices, r.breakdown(), flush=True)
    tr = red.load(src)
    for dev, ops in tr.devices.items():
        for e in ops[:40]:
            print(dev, e.name, e.end_ns - e.start_ns, e.text[:300])
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(src).planes:
        print("PLANE", plane.name, [ln.name for ln in plane.lines])
    shutil.rmtree(tw._dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
