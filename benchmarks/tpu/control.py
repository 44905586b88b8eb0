"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/tpu/control.py --workload femnist-prefetch \
        --seeds 1,2,3 [--window 1] [--tiny] [--out FILE]

In one process, for each seed: the program's numbers (the entry's set-up,
a short window at the cell's own load, the comparison with the plain
reference) and the control's numbers (the reference computed one
precision below what the configuration states — the configuration
module's ``CONTROL`` — put in the program's place), each judged by
``compare.judge`` against the cell's committed limits, as a run judges
them: the program has to come out correct and the control not.  A limit
lies above the program's largest reading and below the control's smallest.

On a TPU this reads the cell at its own size; ``--tiny`` reads the
configuration's tiny size on any backend (the CPU test of the control).
Each seed's readings are printed as one JSON line, and written to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tpubench import compare, harness  # noqa: E402


def readings(workload: str, seeds: list, window: float, tiny: bool) -> list:
    cell = harness.load_cell(workload)
    harness.require_program()
    if not tiny:
        harness.require_devices(cell.chips)
        harness.enable_compile_cache()
    limits = harness.load_limits(workload)
    out = []
    for seed in seeds:
        state = cell.entry.setup(cell, seed, tiny=tiny)
        win = cell.entry.window(state, window)
        cell.entry.release(state)
        ref = cell.entry.reference(state, "highest")
        low = cell.entry.reference(state, cell.config_mod.CONTROL)
        # in the program's place: the control yields what the program yields
        low = {k: v for k, v in low.items() if k in state["program"]}
        row = {"seed": seed, "program": compare.numbers(state["program"], ref),
               "control": compare.numbers(low, ref)}
        row["program_correct"] = compare.judge(row["program"], limits)[0] and win["failed"] == 0
        row["control_correct"] = compare.judge(row["control"], limits)[0]
        out.append(row)
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--window", type=float, default=1.0, help="seconds of each short window")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                    args.window, args.tiny)
    names = sorted({k for r in rows for k in r["program"]})
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min(r["control"][k] for r in rows)} for k in names}
    summary["seeds"] = len(rows)
    summary["program_correct"] = sum(r["program_correct"] for r in rows)
    summary["control_correct"] = sum(r["control_correct"] for r in rows)
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows + [summary]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
