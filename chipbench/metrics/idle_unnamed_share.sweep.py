"""Share of the traced window, in percent, in which the chip runs no op
and the host is under none of the program's spans: what the program's
instrumentation cannot name yet.  With the four ``idle_in_*`` shares it
sums to ``device_idle_share.sweep`` (``chipbench/program_spans.py``)."""

from chipbench.program_spans import idle_share


def read(run):
    return idle_share(run, "unnamed")
