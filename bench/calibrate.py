"""Readings that set the benchmark's fixed numbers; never part of a
benchmark run.

  python3 -m bench.calibrate sweep --workload danube.chat.1c \\
      --rates 6,8,10 --seconds 20 --seed 1
  python3 -m bench.calibrate control --workload <cell> --seeds 1,2,3 \\
      --seconds 10

``sweep`` serves the cell's mix at each offered rate in turn and prints,
per rate, the tails and whether the backlog grew: the median time to
first token of the last third of the arrivals over that of the first
third.  ``control`` runs the cell on each seed and prints the numbers
compared beside what the control gives: the plain reference computed in
the precision below the configuration's, in the program's place.

One JSON line per reading on standard output; the chip check is the
benchmark's own.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import json
import sys

import numpy as np

from bench import harness
from bench.peaks import peaks_for


def backlog_growth(due, ttft) -> float:
    order = np.argsort(due)
    t = np.asarray(ttft)[order]
    k = max(1, len(t) // 3)
    return float(np.median(t[-k:]) / np.median(t[:k]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    cell = harness.resolve(harness.load_benchmark(), args.workload)
    device = harness.require_chips(cell.chips)
    peaks = peaks_for(device["kind"])
    harness.enable_cache()
    driver = importlib.import_module(f"bench.drivers.{cell.mix['driver']}")
    hooks = harness.Hooks(lambda: None, lambda: None)
    if args.what == "sweep":
        runs = [(dataclasses.replace(cell, mix={**cell.mix,
                                                "rate_per_s": float(r)}),
                 args.seed) for r in args.rates.split(",")]
    else:
        runs = [(cell, int(s)) for s in args.seeds.split(",")]
    for c, seed in runs:
        out = driver.run(c, seed=seed, seconds=args.seconds, trace=False,
                         peaks=peaks, hooks=hooks,
                         control=args.what == "control")
        row = {"workload": c.name, "seed": seed, "e2e": out.e2e,
               "checks": {k.name: k.value for k in out.checks},
               "attempted": out.attempted, "failed": out.failed,
               "memory_peak_bytes": out.memory_peak_bytes,
               "notes": out.notes}
        if "rate_per_s" in c.mix:
            row["rate_per_s"] = c.mix["rate_per_s"]
            row["backlog_growth"] = backlog_growth(out.counters["due_s"],
                                                   out.counters["ttft_s"])
        for k, v in out.counters.items():
            if k.startswith("control"):
                row[k] = v
        print(json.dumps(row), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
