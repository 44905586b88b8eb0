"""Run one cell with the timed path broken underneath, and print whether
the comparison still calls it correct.

    python3 fault_run.py <workload> <fault> [--full] [--seeds N,M,...]

At the configuration's tiny size by default (the CPU tests); ``--full``
drives the cell at its own size, on whatever devices JAX finds.

Faults (the program is patched in this process only):

* ``none`` — nothing broken (the run must come out correct);
* ``state_unchanged`` — every round step returns the params it was given;
* ``half_batch`` — each local step's loss sees the first half of its batch
  rows only, the mean taken over them;
* ``no_exchange`` — the mesh round's aggregate skips its cross-chip psum.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))


def benchmark_with_dormant() -> dict:
    """``BENCHMARK.json`` plus the cells kept ready under ``cells/`` but left
    out of it (a cell file with a ``workload`` entry), so that their entry,
    configuration and reference stay tested."""
    from tpubench import harness

    bench = harness.load_benchmark()
    for path in sorted((harness.BENCH_DIR / "cells").glob("*.json")):
        spec = json.loads(path.read_text())
        if "workload" not in spec or spec["workload"] in bench["workloads"]:
            continue
        bench["workloads"].append(spec["workload"])
        if "config" in spec and spec["config"] not in bench["configs"]:
            bench["configs"].append(spec["config"])
    return bench


def _frozen(step):
    def run(params, opt_state, *a, **k):
        _, _, metrics = step(params, opt_state, *a, **k)
        return params, opt_state, metrics
    return run


def state_unchanged():
    import repro.fl.engine as eng
    import repro.sim.driver as drv

    make_step, make_engine = eng.RoundEngine.make_step, eng.make_engine
    eng.RoundEngine.make_step = lambda self, diag=False: _frozen(make_step(self, diag))
    eng.make_engine = drv.make_engine = lambda *a, **k: _frozen(make_engine(*a, **k))


def half_batch():
    import repro.fl.engine as eng
    import repro.fl.shard_round as shard

    make_local_update = eng.make_local_update

    def halved(loss_fn, fl):
        def loss(p, batch):
            return loss_fn(p, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
        return make_local_update(loss, fl)

    eng.make_local_update = shard.make_local_update = halved


def no_exchange():
    from repro.kernels import ops

    aggregate = ops.shard_masked_aggregate
    ops.shard_masked_aggregate = lambda *a, axis_name=None, **k: aggregate(*a, **k)


FAULTS = {"none": lambda: None, "state_unchanged": state_unchanged,
          "half_batch": half_batch, "no_exchange": no_exchange}


def main(workload: str, fault: str, seed: int = 2**31 + 99, full: bool = False) -> dict:
    from tpubench import compare, harness

    cell = harness.load_cell(workload, benchmark_with_dormant())
    harness.require_program()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    FAULTS[fault]()
    state = cell.entry.setup(cell, seed, tiny=not full)
    win = cell.entry.window(state, 2.0 if full else 0.3)
    numbers = compare.check(cell.entry, state)
    ok, rows = compare.judge(numbers, harness.load_limits(workload))
    return {"workload": workload, "fault": fault, "seed": seed, "full": full,
            "correct": bool(ok and win["failed"] == 0), "numbers": numbers}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("fault", choices=sorted(FAULTS))
    ap.add_argument("--seeds", default=str(2**31 + 99), help="comma-separated seeds")
    ap.add_argument("--full", action="store_true")
    a = ap.parse_args()
    for seed in a.seeds.split(","):
        print(json.dumps(main(a.workload, a.fault, int(seed), a.full)), flush=True)
