"""Benchmark harness — one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run             # full sweeps
  PYTHONPATH=src python -m benchmarks.run --quick     # reduced sweeps
  PYTHONPATH=src python -m benchmarks.run --only dse  # one module
  PYTHONPATH=src python -m benchmarks.run --smoke     # CI gate: quick mode,
                                                      # fast module subset

Each module prints its rows as an aligned table plus one
``CSV,name,us_per_call,derived`` line for machine consumption.
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import use_compile_cache

from . import (bench_3dmemory, bench_dse, bench_mappings,
               bench_memory_sweep, bench_roofline, bench_serving,
               bench_solver, bench_specdecode, bench_validation)
from .common import emit, table

MODULES = {
    "solver": bench_solver,
    "validation": bench_validation,
    "mappings": bench_mappings,
    "memory_sweep": bench_memory_sweep,
    "dse": bench_dse,
    "serving": bench_serving,
    "specdecode": bench_specdecode,
    "3dmemory": bench_3dmemory,
    "roofline": bench_roofline,
}


# the CI smoke gate: cheap enough for every PR, still exercises the solver
# DPs and the full DSE engine path (parallel sweep + cache + Pareto)
SMOKE_MODULES = ("solver", "dse")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: --quick grids, fast module subset")
    ap.add_argument("--only", choices=list(MODULES))
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
    use_compile_cache()

    if args.only:
        names = [args.only]
    elif args.smoke:
        names = list(SMOKE_MODULES)
    else:
        names = list(MODULES)
    failures = []
    for name in names:
        mod = MODULES[name]
        print(f"\n=== {name}: {mod.TITLE} ===")
        t0 = time.perf_counter()
        try:
            rows = mod.run(quick=args.quick)
        except Exception:
            traceback.print_exc()
            failures.append(name)
            continue
        dt = time.perf_counter() - t0
        print(table(rows))
        emit(name, dt, f"rows={len(rows)}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
