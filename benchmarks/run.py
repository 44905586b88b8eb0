"""Benchmark harness: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmark results are also
written as JSON under benchmarks/artifacts/).

  PYTHONPATH=src python -m benchmarks.run            # fast suite
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale rounds
  PYTHONPATH=src python -m benchmarks.run --only femnist,kernels
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_cifar,
        bench_compression,
        bench_femnist,
        bench_kernels,
        bench_roofline,
        bench_round_engine,
        bench_sampler_frontier,
        bench_shakespeare,
        bench_sim,
        bench_stepsize,
        bench_variance,
    )

    suites = {
        # paper Figures 3-5 (FEMNIST datasets 1-3, acc/loss vs rounds & bits)
        "femnist": lambda: bench_femnist.run(rounds=150 if args.full else 50),
        # paper Figures 6-7 (Shakespeare, n in {32,128})
        "shakespeare": lambda: bench_shakespeare.run(rounds=300 if args.full else 80),
        # paper Appendix G (balanced CIFAR100-like)
        "cifar": lambda: bench_cifar.run(rounds=100 if args.full else 30),
        # paper Sec 5.2/5.4 step-size robustness claim
        "stepsize": lambda: bench_stepsize.run(rounds=60 if args.full else 20),
        # Definitions 11/12 (alpha/gamma) + Alg1-vs-Alg2 agreement table
        "variance": lambda: bench_variance.run(),
        # beyond-paper: OCS x unbiased compression (paper Sec. 6 future work)
        "compression": lambda: bench_compression.run(rounds=80 if args.full else 30),
        # kernel hot-spots
        "kernels": lambda: bench_kernels.run(),
        # round-engine matrix: (vmap|scan) x (jnp|pallas) µs/round
        "round_engine": lambda: bench_round_engine.run(reps=10 if args.full else 5),
        # sim-driver modes: host loop vs prefetched pool vs scan-over-rounds
        "sim": lambda: bench_sim.run(rounds=96 if args.full else 48),
        # sampler zoo: loss-vs-cumulative-uplink-bits frontier per sampler
        "sampler_frontier": lambda: (
            bench_sampler_frontier.run(rounds=40)
            if args.full else bench_sampler_frontier.smoke()
        ),
        # deliverable (g): roofline table from dry-run artifacts
        "roofline": lambda: bench_roofline.run(),
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites.items():
        if name not in only:
            continue
        t0 = time.perf_counter()
        try:
            fn()
            print(f"# suite {name} done in {time.perf_counter()-t0:.0f}s", file=sys.stderr)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
