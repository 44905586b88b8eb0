"""CPU rehearsal of the benchmark: no chip, no metric.

    JAX_PLATFORMS=cpu python3 benchmarks/tpu/rehearse.py [--cells a,b] [--compile]

For each cell, in a process of its own (a four-chip cell gets four virtual
CPU devices): the cell's entry at its configuration's tiny size — set-up,
a one-second window, the comparison with the plain reference — with the
Pallas kernels in interpret mode.  It prints the numbers compared beside
their limits and whether the run would be ``correct``; it prints no metric,
since a CPU run measures nothing of the chip.

``--compile`` also compiles each cell's round step at its real size for a
described TPU ``v5e:2x2`` (nothing runs) and prints what
``memory_analysis`` says the step needs, so that shapes, kernels and
memory are checked before chip time is spent.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def rehearse_cell(name: str, compile_real: bool) -> int:
    from tpubench import compare, harness

    cell = harness.load_cell(name)
    harness.require_program()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    state = cell.entry.setup(cell, 2**31 + 12345, tiny=True)
    win = cell.entry.window(state, 1.0)
    numbers = compare.check(cell.entry, state)
    limits = harness.load_limits(name)
    ok = win["failed"] == 0 and all(v <= limits[k] for k, v in numbers.items())
    for k, v in numbers.items():
        print(f"[rehearse] {name} {k} {v!r} limit {limits[k]!r}")
    print(f"[rehearse] {name}: {win['rounds']} rounds, would be correct: {ok}")
    if compile_real:
        for line in cell.entry.compile_for_tpu(cell):
            print(f"[rehearse] {name} v5e: {line}")
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", default=None, help="comma-separated cells (default: all)")
    ap.add_argument("--compile", action="store_true",
                    help="also compile each round step at real size for a described v5e")
    ap.add_argument("--one", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        return rehearse_cell(args.one, args.compile)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    cells = args.cells.split(",") if args.cells else [w["name"] for w in bench["workloads"]]
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    rc = 0
    for name in cells:
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        if chips[name] > 1:
            flag = f"--xla_force_host_platform_device_count={chips[name]}"
            env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {flag}".strip()
        cmd = [sys.executable, __file__, "--one", name] + (["--compile"] if args.compile else [])
        rc |= subprocess.run(cmd, env=env).returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
