"""The control separates from the program: the plain reference computed one
precision below what the configuration states (``CONTROL`` of the
configuration module), put in the program's place, reads at least three
times the program's reading (or float32 round-off, if that is larger) on at
least one number, at the tiny size on the CPU, while the program passes
every limit of the cell.  (The limits themselves were set from the
control's readings on the chip at the cell's own size; the tiny size reads
smaller gaps, so the separation is asserted here and not those limits.)"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tpubench import compare, harness

HERE = Path(__file__).resolve().parent
WORKLOADS = [w["name"] for w in harness.load_benchmark()["workloads"]]
ROUNDOFF = 1e-7   # float32 round-off of one of these numbers


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if harness.load_cell(workload).chips > 1:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, str(HERE.parent / "control.py"), "--workload", workload,
         "--seeds", "2147483749", "--window", "0.3", "--tiny"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    row = json.loads(out.stdout.strip().splitlines()[0])
    program_ok, _ = compare.judge(row["program"], harness.load_limits(workload))
    assert program_ok, row["program"]
    separated = [k for k, v in row["control"].items()
                 if v >= 3 * max(row["program"].get(k, 0.0), ROUNDOFF)]
    assert separated, row
