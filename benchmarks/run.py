"""Benchmark driver — one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV lines (us=0 where the benchmark is
a metric table rather than a timing).  ``--smoke`` (or
``REPRO_BENCH_SMOKE=1``) runs every module in its reduced configuration —
the CI liveness job that keeps new benchmarks from silently rotting.

``--index`` consolidates the repo-root ``BENCH_*.json`` trajectory records
into ``BENCH_index.json`` (name, date, headline wall/cell numbers) and
exits — the cheap "what do we measure and how fast is it" summary CI
regenerates on every bench-smoke run.
"""
from __future__ import annotations

import os
import sys
import traceback

# --smoke must be in the environment before the modules read it.
if "--smoke" in sys.argv[1:]:
    os.environ["REPRO_BENCH_SMOKE"] = "1"

if "--index" in sys.argv[1:]:
    from benchmarks import _bench

    print(_bench.write_index())
    sys.exit(0)

from benchmarks import (
    allocator_scaling,
    chaos_grid,
    fig2_timeseries,
    fleet_scaling,
    robustness,
    roofline,
    scaling_frontier,
    serverless_elasticity,
    serving_engine,
    sweep_grid,
    table2_metrics,
    workflow_topologies,
)
from repro.launch.compile_cache import enable_compile_cache

MODULES = (
    ("table2", table2_metrics),
    ("fig2", fig2_timeseries),
    ("robustness", robustness),
    ("chaos_grid", chaos_grid),
    ("sweep_grid", sweep_grid),
    ("workflow_topologies", workflow_topologies),
    ("serverless_elasticity", serverless_elasticity),
    ("allocator_scaling", allocator_scaling),
    ("fleet_scaling", fleet_scaling),
    ("roofline", roofline),
    ("serving_engine", serving_engine),
    ("scaling_frontier", scaling_frontier),
)


def main() -> None:
    enable_compile_cache()
    # Each module resolves its own artifact dir via _smoke.out_dir(), so
    # smoke runs land in experiments/smoke/ from any entry point.
    failed = False
    print("name,us_per_call,derived")
    for name, mod in MODULES:
        try:
            for line in mod.run():
                print(line)
        except Exception:
            failed = True
            print(f"{name},0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
