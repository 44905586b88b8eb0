"""Run one benchmark cell on the TPU and print its result line.

    python3 benchmarks/tpu/run.py --workload femnist-prefetch --seed 7 \
        --seconds 10 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; the traffic names the entry that drives the program.  A run:
set-up (the entry's data, weights and warm-up: ``setup_s`` counts from the
start of this process), the measured window of ``--seconds`` (with
``--trace 1`` a shorter traced window, reduced to the per-layer metrics),
the peak device memory, then the comparison with the plain reference that
decides ``correct``.  The last line of standard output is the result's
JSON; the numbers compared, each beside its limit, are the last lines of
standard error and the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tpubench import compare, harness  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def traced_window(cell, state, seconds: float, seed: int) -> tuple:
    """The entry's window under the profiler, and the trace's readings."""
    import jax

    from tpubench import trace_reduce

    log_dir = harness.OUT_DIR / f"trace-{cell.name}-{seed}"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            win = cell.entry.window(state, seconds)
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(str(log_dir)))
    return win, trace


def per_layer_metrics(cell, win: dict, trace, peaks: dict) -> tuple:
    """``(metrics, busy_s, window_s, breakdown)`` of the traced window."""
    from tpubench import trace_reduce

    lo, hi = trace.annotation("bench:window")
    ctx = dict(win["context"], rounds=win["rounds"], lo=lo, hi=hi,
               window_s=(hi - lo) / 1e9, chips=cell.chips, peaks=peaks)
    out = {}
    for spec, reader in cell.per_layer:
        value = reader.read(trace, ctx)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    busy = [trace_reduce.covered(trace_reduce.busy_intervals(d, lo, hi))
            for d in trace.devices]
    thread = trace.thread_of("bench:window")
    breakdown = {"device_ops": trace_reduce.top_ops(trace, lo, hi),
                 "idle_gaps": trace_reduce.idle_gaps(trace, lo, hi, thread)}
    return out, sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.require_program()
    import jax

    devices = harness.require_devices(cell.chips)
    t_devices = time.perf_counter() - T_START
    device = harness.device_record(devices)
    peaks = harness.load_peaks(device["kind"])
    log(f"cell {cell.name}: {device['count']} x {device['kind']}, "
        f"compile cache {harness.enable_compile_cache()}")
    counter = harness.CompileCounter()

    with jax.profiler.TraceAnnotation("bench:setup"):
        state = cell.entry.setup(cell, args.seed)
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.3f} s ({t_devices:.3f} s to reach the chips, "
        f"{setup_s - t_devices:.3f} s in the entry)")

    snap = counter.snapshot()
    if args.trace:
        seconds = min(args.seconds, cell.traffic["trace_seconds"])
        win, trace = traced_window(cell, state, seconds, args.seed)
    else:
        win = cell.entry.window(state, args.seconds)
    compiles = counter.since(snap)
    log(f"window: {win['rounds']} rounds in {win['seconds']:.4f} s; programs "
        f"compiled in the window {compiles['compiled']}, loaded from the "
        f"persistent cache {compiles['loaded_from_cache']}")
    mem = harness.peak_memory(devices)
    device["memory_peak_bytes"] = mem

    result = {"correct": False, "attempted": win["attempted"], "failed": win["failed"],
              "metrics": {}, "device": device}
    if args.trace:
        metrics, busy_s, window_s, breakdown = per_layer_metrics(cell, win, trace, peaks)
        device.update(busy_s=busy_s, window_s=window_s)
        result["metrics"] = metrics
        result["breakdown"] = breakdown
    else:
        values = {"rounds_per_s": win["rounds"] / win["seconds"], "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}

    t0 = time.perf_counter()
    ok, rows = compare.judge(compare.check(cell.entry, state),
                             harness.load_limits(cell.name))
    log(f"reference and comparison {time.perf_counter() - t0:.1f} s")
    result["correct"] = ok and win["failed"] == 0
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in rows}
    for name, value, limit in rows:
        print(f"[check] {name} {value!r} limit {limit!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchError as e:
        print(f"[bench] no result: {e.msg}", file=sys.stderr, flush=True)
        sys.exit(e.code)
