"""The served model steps' share of the chip's bf16 peak, in percent: the
operations every prefill and decode call of the window needs
(``chipbench/counts/models.py``, at the real context lengths), over the
window's seconds times the peak (``chipbench/peaks.json``)."""

from chipbench.drivers.served_waves import calls


def read(run):
    model_calls, _ = calls(run["records"])
    if not model_calls:
        return None
    flops = sum(c["flops"] for c in model_calls)
    return 100.0 * flops / (run["window_s"] * run["peaks"]["bf16_flops_per_s"])
