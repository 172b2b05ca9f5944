"""Device microseconds per scan step of the grid program's leaf ops whose
scope path holds ``synthesis`` (the in-scan arrival generators,
``workload.workload_step``), per chip, averaged over the cell's chips
(``chipbench/program_spans.py``)."""

from chipbench.program_spans import stage_us_per_step


def read(run):
    return stage_us_per_step(run, "synthesis")
