"""The comparison catches a broken timed path: each fault a cell can have,
planted in the program underneath a tiny run, turns ``correct`` false; the
same run unbroken stays correct."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CASES = [
    ("femnist-prefetch", "none", True),
    ("femnist-prefetch", "state_unchanged", False),
    ("femnist-prefetch", "half_batch", False),
    ("femnist-scan", "none", True),
    ("femnist-scan", "state_unchanged", False),
    ("femnist-scan", "half_batch", False),
    ("femnist-shard4", "none", True),
    ("femnist-shard4", "state_unchanged", False),
    ("femnist-shard4", "half_batch", False),
    ("femnist-shard4", "no_exchange", False),
    ("mamba2-scan", "none", True),
    ("mamba2-scan", "state_unchanged", False),
    ("mamba2-scan", "half_batch", False),
]


@pytest.mark.parametrize("workload,fault,correct", CASES)
def test_fault_decides_correct(workload, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if workload == "femnist-shard4":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, str(HERE / "fault_run.py"), workload, fault],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is correct, result["numbers"]
