"""Host-clock microseconds in the program's ``repro:sweep.wait`` spans in
the traced window, over the window's scan steps: the host's view of
``scan_us_per_step.sweep`` (``chipbench/program_spans.py``)."""

from chipbench.program_spans import wait_us_per_step


def read(run):
    return wait_us_per_step(run)
