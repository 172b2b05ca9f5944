"""The bytes the served model steps must move, over what the chip's HBM
could move in the window, in percent: each call reads every weight once
and the attention cache or SSM state it needs
(``chipbench/counts/models.py``), over window seconds times the peak
bandwidth (``chipbench/peaks.json``)."""

from chipbench.drivers.served_waves import calls


def read(run):
    model_calls, _ = calls(run["records"])
    if not model_calls:
        return None
    moved = sum(c["bytes"] for c in model_calls)
    return 100.0 * moved / (run["window_s"] * run["peaks"]["hbm_bytes_per_s"])
