"""Record one traced window of a cell as a compressed trace file.

  python3 chipbench/record_trace.py --workload <cell> --seed <n> --out <file.xplane.pb.gz> [--seconds S]

Set-up as in a benchmark run, then one window under the profiler
(``--seconds 0``, the default, runs a single grid of a sweep cell), then
the cell's per-layer metrics read from that window, printed as the last
line of standard output (one JSON object).  The trace reduction's tests
read such a file: ``chipbench/testdata/fleet1024-sweep.xplane.pb.gz`` is
one grid of ``fleet1024-sweep``.  Exits 3 without a TPU.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench import trace as trace_mod
    from repro.launch.compile_cache import enable_compile_cache

    ctx = harness.load_cell(args.workload)
    ctx.seed = args.seed
    devices = harness.require_chips(int(ctx.cell["chips"]))
    if devices is None:
        return harness.EXIT_NO_CHIP
    ctx.devices = devices
    enable_compile_cache()
    driver = harness.driver_of(ctx)
    state = driver.setup(ctx)
    tracer = trace_mod.Tracer(harness.OUT / "record" / ctx.name)
    tracer.start()
    result = driver.window(ctx, state, args.seconds)
    traced = tracer.stop()
    used = devices[: int(ctx.cell["chips"])]
    run = {"cell": ctx.cell, "config": ctx.config, "traffic": ctx.traffic,
           "window_s": result["window_s"], "records": result["records"],
           "trace": traced, "devices": used,
           "peaks": harness.peaks_of(devices[0].device_kind)}
    metrics = {m["name"]: harness.read_metric(m["name"], run)
               for m in harness.metric_entries(ctx, "per_layer")}
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(traced.path, "rb") as src, gzip.open(out, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    driver.release(ctx, state)
    print(json.dumps({"workload": ctx.name, "out": str(out), "bytes": out.stat().st_size,
                      "window_s": result["window_s"], "grids": result["attempted"],
                      "summary": traced.summary([d.id for d in used]),
                      "metrics": metrics}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
