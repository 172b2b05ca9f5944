"""Run one benchmark cell once, on the chip the process finds.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration and traffic mix are data files under
``chipbench/configs`` and ``chipbench/traffic``, and the traffic file
names the driver (``chipbench/drivers/<driver>.py``) that sets the cell
up, runs its measured window and checks what the window produced.

Exits nonzero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``compared`` and, with ``--trace 1``, ``breakdown``.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The TPU runtime would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t0=PROCESS_T0)


if __name__ == "__main__":
    sys.exit(main())
