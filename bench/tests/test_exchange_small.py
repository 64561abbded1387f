"""The exchange cell's run on four forced CPU devices, in a process of its
own (the test session keeps one device): every backend moves the same
bytes and comes out correct; the control (the reference in fp8 in the
program's place) and each fault planted in the timed program come out
not correct."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.peaks import moe_capacity


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.exchange_cases"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    return {r["case"]: r for r in rows}


@pytest.mark.parametrize("backend", ["direct", "factorized", "overlap"])
def test_every_backend_is_correct_on_the_same_bytes(cases, backend):
    row = cases[f"sound/{backend}"]
    assert row["correct"], row
    # (E_loc, C, D) = (1, 40, 64) bf16 per destination, p = 4
    assert row["counters"]["p"] == 4
    assert row["counters"]["block_bytes"] == \
        1 * moe_capacity(1.25, 2, 64, 4) * 64 * 2


def test_control_reading_separates(cases):
    assert cases["control_reading"]["correct"]
    assert cases["control_reading"]["counters"][
        "control_mismatched_bytes"] > 0


@pytest.mark.parametrize("case", ["control", "left_out", "altered"])
def test_control_and_faults_are_not_correct(cases, case):
    assert not cases[case]["correct"], cases[case]
