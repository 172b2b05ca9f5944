"""Share of the traced window, in percent, in which the chip runs no op
while the host is inside the program's ``repro:sweep.prepare`` or
``repro:workload.make_spec`` spans: validation, spec building, stacking,
sharded padding (``chipbench/program_spans.py``)."""

from chipbench.program_spans import idle_share


def read(run):
    return idle_share(run, "prepare")
