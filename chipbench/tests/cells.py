"""Helpers for the benchmark's own tests: a cell run at a size a test run
can hold, on whatever devices JAX has (the chip check is skipped)."""
from __future__ import annotations

import contextlib
import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

SMALL = {
    "table1-served-waves": {
        "traffic": {"batch_slots": 2, "max_len": 48, "budget_tokens": 4000,
                    "roles": {"coordinator": {"prompt": 12, "output": 5},
                              "specialist_nlp": {"prompt": 16, "output": 7},
                              "specialist_vision": {"prompt": 16, "output": 6},
                              "specialist_reasoning": {"prompt": 8, "output": 12}},
                    # The reduced backbones serve 5 to 12 tokens a request,
                    # so their gaps are held to limits of their own, set
                    # from 12 seeds at this size on the CPU (the control on
                    # 3): widest gaps, program at most 0.0134 (coordinator)
                    # and 0.0091 (vision); mean gaps, program at most
                    # 0.00159 (nlp) and 0.00072 (reasoning), the float8
                    # control's nlp at least 0.020.
                    "check": {"limit": {"logit_gap.coordinator": 0.05,
                                        "logit_gap.specialist_vision": 0.05,
                                        "logit_gap_mean.specialist_nlp": 0.006,
                                        "logit_gap_mean.specialist_reasoning": 0.003}}}},
    # The fleet cells keep their horizons, 4,000 and 1,000 steps (the
    # control's rounding grows with the horizon), at 32 agents a fleet;
    # the six-hour cell runs 1,000 of its 21,600.
    "fleet1024-sweep": {"config": {"fleet": {"num_agents": 32}},
                        "traffic": {"fleets_per_grid": 2}},
    "table1-sweep-6h": {"traffic": {"num_steps": 1000}},
    "fleet1024-sweep-4chip": {"config": {"fleet": {"num_agents": 32}},
                              "traffic": {"fleets_per_grid": 4}},
}


def _merge(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


@contextlib.contextmanager
def reduced_backbones():
    """The program's reduced configuration of every backbone in place of
    its full one, for the block."""
    import repro.configs as configs

    full = configs.get_config
    configs.get_config = lambda arch, reduced=False: full(arch, reduced=True)
    try:
        yield
    finally:
        configs.get_config = full


def reduced_models(config: dict) -> dict:
    """The served backbones as the reduced program describes them, at a
    size a test run holds."""
    from chipbench.drivers.served_waves import describe

    with reduced_backbones():
        import repro.configs as configs

        models = {a: describe(configs.get_config(a)) for a in config["served"]["models"]}
    return models


def small_ctx(cell: str, seed: int, **extra):
    import jax

    ctx = harness.load_cell(cell)
    ctx.config = copy.deepcopy(ctx.config)
    ctx.traffic = copy.deepcopy(ctx.traffic)
    if ctx.traffic["driver"] == "served_waves":
        # The reduced sizes replace the published ones, departures applied.
        ctx.config["served"]["models"] = reduced_models(ctx.config)
        ctx.config["served"]["departures"] = {}
    for part, over in SMALL.get(cell, {}).items():
        _merge(getattr(ctx, part), over)
    for part, over in extra.items():
        _merge(getattr(ctx, part), over)
    ctx.seed = seed
    ctx.devices = jax.devices()
    return ctx


def run_small(cell: str, seed: int, seconds: float = 0.0, trace: bool = False, **extra):
    ctx = small_ctx(cell, seed, **extra)
    with reduced_backbones():
        return harness.execute(ctx, seconds, trace, t0=time.perf_counter())
