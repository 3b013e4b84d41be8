"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Run:

    PYTHONPATH=src python -m benchmarks.run            # full set
    PYTHONPATH=src python -m benchmarks.run --fast     # smoke subset
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated table names")
    ap.add_argument("--skip", default="", help="comma-separated table names to skip")
    args = ap.parse_args()

    from benchmarks import kernels_bench, serve_bench, tables
    from repro.launch.jax_cache import enable_compile_cache

    enable_compile_cache()

    # classification benches run in the pre-saturation regime (the synthetic
    # proxy task saturates to F1=1.0 for every method given enough steps —
    # method ORDERINGS, the reproduction target, are visible below ~20 steps)
    steps = 12 if args.fast else 16
    suites = {
        "table1": lambda: tables.table1_classification(steps=steps),
        "table2": tables.table2_resources,
        "table3": lambda: tables.table3_ablation(steps=steps),
        "table4": lambda: tables.table4_sensitivity(steps=max(8, steps - 4)),
        "table5": lambda: tables.table5_stability(total_steps=60 if args.fast else 120),
        "anomaly": lambda: tables.anomaly_auc(steps=max(30, steps)),
        "kernels": kernels_bench.kernel_benchmarks,
        "tilesweep": kernels_bench.tile_sweep,
        "serving": kernels_bench.serving_benchmarks,
        "serve_flow": lambda: serve_bench.serve_flow_benchmarks(fast=args.fast),
        "serve_adaptive": lambda: serve_bench.serve_adaptive_benchmarks(
            fast=args.fast
        ),
        "serve_flow_sharded": lambda: serve_bench.serve_flow_sharded_benchmarks(
            fast=args.fast
        ),
        "serve_elastic": lambda: serve_bench.serve_elastic_benchmarks(
            fast=args.fast
        ),
        "serve_redteam": lambda: serve_bench.serve_redteam_benchmarks(
            fast=args.fast
        ),
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}
    if args.skip:
        drop = set(args.skip.split(","))
        suites = {k: v for k, v in suites.items() if k not in drop}

    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            import traceback

            traceback.print_exc(file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr, flush=True)
    if failed:
        print(f"{len(failed)} suite(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
