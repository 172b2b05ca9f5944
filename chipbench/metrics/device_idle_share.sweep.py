"""Share of the traced window in which no operation ran on the chip, in
percent, averaged over the chips the cell uses: one minus the union of the
``XLA Ops`` intervals over the window (``chipbench/trace.py``)."""


def read(run):
    return run["trace"].idle_share([d.id for d in run["devices"]])
