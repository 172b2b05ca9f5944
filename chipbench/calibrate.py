"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 chipbench/calibrate.py --workload <cell> --seeds <s1,s2,...> [--seconds S]

For each seed, in this one process: the cell's set-up and a short window
of its timed path, then the numbers its check compares, read twice: for
the program (what the window produced against the plain reference), and
for the control (the plain reference itself in the program's place,
computed one precision below what the configuration states: float8 for
the served bfloat16 models, bfloat16 for the float32 sweep), the control
on the first three seeds only.  One JSON line per seed:
``{"seed", "program": {name: value}, "control": {...}}``.

The limit of each number lies between the largest program reading and
the smallest control reading (PERF.md gives both).  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CONTROL_SEEDS = 3
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The TPU runtime would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(ctx, driver, seconds: float, control: bool = True) -> dict:
    """Set up, run a window, release, and read the program and, with
    ``control``, the control."""
    import ml_dtypes

    state = driver.setup(ctx)
    result = driver.window(ctx, state, seconds)
    driver.release(ctx, state)
    out = {"attempted": result["attempted"]}
    if ctx.traffic["driver"] == "served_waves":
        out["program"] = driver.reference_gaps(ctx, state)
        if control:
            out["control"] = driver.reference_gaps(ctx, state, lower=ml_dtypes.float8_e4m3fn)
    else:
        out["program"] = driver.gaps(ctx, state)
        if control:
            out["control"] = driver.gaps(ctx, state, against=lambda cell: (
                driver.reference_metrics(ctx, state, cell, round_to=ml_dtypes.bfloat16)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window per seed (0: one tick or one grid)")
    args = ap.parse_args(argv)

    from chipbench import harness

    ctx = harness.load_cell(args.workload)
    devices = harness.require_chips(int(ctx.cell["chips"]))
    if devices is None:
        return harness.EXIT_NO_CHIP
    ctx.devices = devices
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = harness.driver_of(ctx)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        t0 = time.perf_counter()
        out = readings(ctx, driver, args.seconds, control=i < CONTROL_SEEDS)
        out["seed"] = seed
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
