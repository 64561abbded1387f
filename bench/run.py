"""Run one benchmark cell once on the chips of this machine.

  python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration file,
a traffic mix and a chip count; the mix names the driver that runs it
(``bench/drivers/<driver>.py``).  Set-up builds the program, makes its
weights and inputs from ``--seed`` on the device and compiles every shape
the window uses; then the window runs for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the same window.  Afterwards the run compares what the timed path produced
with the configuration's plain reference.

The last line of standard output is the JSON result; the numbers compared,
each with its limit, are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits nonzero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                  # noqa: E402
import importlib                                                 # noqa: E402
import json                                                      # noqa: E402
import sys                                                       # noqa: E402
from pathlib import Path                                         # noqa: E402

if __package__ in (None, ""):       # run as a file: python3 bench/run.py
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness                                        # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    device = harness.require_chips(cell.chips)
    from bench.peaks import peaks_for
    peaks = peaks_for(device["kind"])
    cache = harness.enable_cache()
    clock = harness.CompileClock()
    harness.log(f"{cell.name}: {device['kind']} x{device['count']}, seed "
                f"{args.seed}, {args.seconds} s, trace {args.trace}, "
                f"compile cache {cache}")

    driver = importlib.import_module(f"bench.drivers.{cell.mix['driver']}")
    marks = {}

    def setup_done():
        marks["setup_s"] = time.perf_counter() - T_START
        marks["compile_s"] = clock.seconds
        marks["compiles"] = clock.compiles
        harness.log(f"set-up {marks['setup_s']:.3f} s, of which getting "
                    f"programs {clock.seconds:.3f} s ({clock.compiles} "
                    f"compiles, {clock.hits} cache hits)")

    def window_done():
        harness.log(f"compiles inside the window: "
                    f"{clock.compiles - marks['compiles']}")

    out = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), peaks=peaks,
                     hooks=harness.Hooks(setup_done, window_done))
    line = harness.result_line(cell, out, device, marks["setup_s"],
                               marks["compile_s"], bool(args.trace), peaks)
    for note in out.notes:
        harness.log(note)
    for c in out.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.NoChip as e:
        print(str(e), file=sys.stderr, flush=True)
        sys.exit(3)
