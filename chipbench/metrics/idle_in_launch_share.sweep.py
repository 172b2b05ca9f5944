"""Share of the traced window, in percent, in which the chip runs no op
while the host is inside the program's ``repro:sweep.launch`` spans: the
call of the grid program until it returns, with any trace, lowering or
cache load (``chipbench/program_spans.py``)."""

from chipbench.program_spans import idle_share


def read(run):
    return idle_share(run, "launch")
