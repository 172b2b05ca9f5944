"""Microseconds of device time per scan step of the sweep kernel.

Reads the ``XLA Modules`` line of each chip the cell uses: the events of
the grid programs the driver names under ``records["programs"]``
(``jit__stream_grid``, ``jit__stream_grid_sharded``), summed per chip,
averaged over the chips, over the scan steps of the grids the window ran.
"""


def read(run):
    trace, rec = run["trace"], run["records"]
    prefixes = tuple(rec["programs"])
    planes = trace.events.device_planes([d.id for d in run["devices"]])
    lo, hi = trace.window_bounds()
    per_chip = []
    for plane in planes:
        ns = sum(d for name, s, d, _ in trace.events.modules(plane)
                 if name.startswith(prefixes) and lo <= s < hi)
        per_chip.append(ns)
    if not per_chip or not any(per_chip) or not rec["scan_steps"]:
        return None
    return sum(per_chip) / len(per_chip) / 1e3 / rec["scan_steps"]
