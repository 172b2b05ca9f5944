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
                    # so their gaps are held to a limit of their own, set
                    # from 12 seeds at this size on the CPU: the program
                    # read at most 0.0211, the float8 control's widest
                    # agent at least 0.107.
                    "check": {"limit": {"coordinator": 0.05, "specialist_nlp": 0.05,
                                        "specialist_vision": 0.05,
                                        "specialist_reasoning": 0.05}}}},
    # The sweep cells keep their horizon of 1,000 steps (the control's
    # rounding grows with it) at 32 agents a fleet.
    "fleet1024-sweep": {"config": {"fleet": {"num_agents": 32}},
                        "traffic": {"fleets_per_grid": 2}},
    "table1-sweep-6h": {"traffic": {"num_steps": 1000}},
    "fleet1024-sweep-4chip": {"config": {"fleet": {"num_agents": 32}},
                              "traffic": {"fleets_per_grid": 4}},
}


# The served cell is not in BENCHMARK.json until its limits are set from
# chip readings; its tests run its traffic and configuration as stated here.
PENDING = [{"name": "table1-served-waves", "config": "table1-onechip", "traffic": "waves",
            "chips": 1}]


def load(cell: str):
    bench = harness.load_json(harness.BENCH)
    known = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [w for w in PENDING if w["name"] not in known]
    return harness.load_cell(cell, bench)


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

    ctx = load(cell)
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
