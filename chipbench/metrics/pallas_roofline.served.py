"""The Pallas kernels' share of their roofline, in percent, over all the
kernel calls of the window: the least time the chip could take for each
call (the larger of its operations over the bf16 peak and its bytes over
the HBM bandwidth, ``chipbench/counts/models.py``), summed, over the
summed device time of the Pallas custom-call events in the trace.  Only
the aggregate can be attributed: the three kernels carry one name."""

from chipbench.counts.models import roofline_s
from chipbench.drivers.served_waves import calls


def is_pallas(name: str, stats: dict) -> bool:
    text = " ".join([name] + [f"{k}={v}" for k, v in stats.items()]).lower()
    return "tpu_custom_call" in text or "_kernel" in text


def read(run):
    _, kernel_calls = calls(run["records"])
    trace = run["trace"]
    lo, hi = trace.window_bounds()
    ns = 0.0
    for plane in trace.events.device_planes([d.id for d in run["devices"]]):
        ns += sum(d for n, s, d, st in trace.ops(plane) if lo <= s < hi and is_pallas(n, st))
    if not kernel_calls or ns <= 0:
        return None
    least = sum(roofline_s(c, run["peaks"]) for c in kernel_calls)
    return 100.0 * least / (ns / 1e9)
