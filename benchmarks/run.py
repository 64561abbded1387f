"""Benchmark orchestrator — one function per paper table/figure.

CPU only: this process runs JAX work itself and starts children that run
more, and a TPU chip belongs to one process at a time, so it holds JAX to
the CPU (``JAX_PLATFORMS=cpu``) for itself and its children.  Its timings
are XLA-CPU timings, not device numbers.  ``chip_smoke.py`` is what runs
on the chip.

Prints ``name,us_per_call,derived`` CSV rows.  Multi-device benches run in
subprocesses on forced host devices (this process keeps 1 CPU device).

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _subproc(module: str, devices: int) -> int:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT}" + (
        os.pathsep + env["PYTHONPATH"] if "PYTHONPATH" in env else "")
    proc = subprocess.run([sys.executable, "-m", module], env=env,
                          cwd=ROOT, text=True, capture_output=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode:
        sys.stderr.write(proc.stderr)
    return proc.returncode


def table1_factorizations():
    """Paper Table 1: dims_create factorizations."""
    from benchmarks import table1
    table1.main()


def figures_1_2_3_alltoall():
    """Paper Figures 1-3: factorized vs direct over message sizes
    (measured, 16 virtual devices, subprocess)."""
    rc = _subproc("benchmarks.alltoall_cmp", devices=16)
    if rc:
        print("alltoall_cmp,failed,,see stderr")


def guideline_check():
    """Paper viewpoint 3: self-consistent performance guidelines."""
    from benchmarks import guidelines
    guidelines.main()


def zero_copy():
    """Paper §4: the explicit-copy cost that zero-copy eliminates."""
    from benchmarks import zero_copy_cost
    zero_copy_cost.main()


def roofline_table():
    """§Roofline: derived terms from the dry-run artifacts."""
    from benchmarks import roofline
    roofline.main()


def model_steps():
    """Measured smoke-config step times per architecture."""
    from benchmarks import model_step
    model_step.main()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slower measured benches")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"     # before any JAX import

    print("# table1 (paper Table 1)")
    table1_factorizations()
    print("\n# alltoall message-size sweep (paper Figs 1-3)")
    if not args.quick:
        figures_1_2_3_alltoall()
    print("\n# guideline check (paper [5,12])")
    guideline_check()
    print("\n# zero-copy saving (paper §4)")
    zero_copy()
    print("\n# roofline (from dry-run artifacts)")
    roofline_table()
    print("\n# per-arch smoke step times")
    if not args.quick:
        model_steps()


if __name__ == "__main__":
    main()
