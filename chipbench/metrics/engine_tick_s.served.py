"""Seconds per engine tick (``FleetEngine.step``): the traced window over
the ticks run in it.  A request's latency is quantized to ticks."""


def read(run):
    ticks = run["records"].get("ticks", 0)
    return run["window_s"] / ticks if ticks else None
