"""Share of the traced window, in percent, in which the chip runs no op
while the host waits for it inside the program's ``repro:sweep.wait``
spans.  Near 0 when the device timeline holds all the work the host waits
for (``chipbench/program_spans.py``)."""

from chipbench.program_spans import idle_share


def read(run):
    return idle_share(run, "wait")
