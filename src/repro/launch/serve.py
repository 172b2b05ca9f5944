"""Serving launcher: the paper's multi-agent fleet on real models.

  PYTHONPATH=src python -m repro.launch.serve --policy adaptive --ticks 20
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.core.agents import AgentSpec, Fleet
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serving.engine import AgentRuntime, FleetEngine

# Paper Table I fleet -> backbone per agent, as
# (agent, arch, base throughput, min GPU share, priority, Poisson rate/tick).
# Run reduced on the CPU: granite-8b (15.4 GiB of bf16 parameters) and
# mixtral-8x7b (87 GiB) do not fit one 16 GB chip at their published widths.
DEFAULT_FLEET = (
    ("coordinator", "qwen2-vl-2b", 100.0, 0.10, 1, 3),
    ("specialist_nlp", "granite-8b", 50.0, 0.30, 2, 2),
    ("specialist_vision", "qwen2-vl-2b", 60.0, 0.25, 2, 2),
    ("specialist_reasoning", "mixtral-8x7b", 30.0, 0.35, 1, 1),
)

# The same four agents on backbones that fit one TPU v5e chip (16 GB HBM)
# at their published widths: two qwen2-vl-2b copies (3.31 GiB each),
# granite-moe-1b-a400m (2.58 GiB) and mamba2-370m (0.78 GiB), about
# 10 GiB of bf16 parameters.  Together they run all three Pallas kernels
# and the MoE layer.
ONE_CHIP_FLEET = (
    ("coordinator", "qwen2-vl-2b", 100.0, 0.10, 1, 3),
    ("specialist_nlp", "granite-moe-1b-a400m", 50.0, 0.30, 2, 2),
    ("specialist_vision", "qwen2-vl-2b", 60.0, 0.25, 2, 2),
    ("specialist_reasoning", "mamba2-370m", 30.0, 0.35, 1, 1),
)


def build_engine(policy: str, *, fleet=DEFAULT_FLEET, reduced: bool = True,
                 budget_tokens: int = 64, max_len: int = 64,
                 batch_slots: int = 4) -> FleetEngine:
    """One ``AgentRuntime`` per row of ``fleet`` (see ``DEFAULT_FLEET``),
    with random weights from a fixed seed, under ``policy``."""
    specs, rts = [], {}
    key = jax.random.key(0)
    for name, arch, tput, min_gpu, pri, _rate in fleet:
        cfg = get_config(arch, reduced=reduced)
        api = build_model(cfg)
        specs.append(AgentSpec(name, cfg.param_count / 1e6, tput, min_gpu, pri))
        rts[name] = AgentRuntime(name, api, api.init(key), max_len=max_len,
                                 batch_slots=batch_slots)
    return FleetEngine(Fleet.from_specs(specs), rts, policy=policy,
                       budget_tokens=budget_tokens)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="adaptive")
    ap.add_argument("--ticks", type=int, default=20)
    ap.add_argument("--budget-tokens", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    eng = build_engine(args.policy, budget_tokens=args.budget_tokens)
    rng = np.random.default_rng(args.seed)
    for t in range(args.ticks):
        for (name, _, _, _, _, rate) in DEFAULT_FLEET:
            for _ in range(rng.poisson(rate)):
                eng.submit(name, rng.integers(0, 1000, args.prompt_len), args.max_new)
        eng.step()
        h = eng.history[-1]
        print(f"tick {t:3d} alloc={[round(x,2) for x in h['allocation']]} "
              f"queues={[int(q) for q in h['queues']]}", flush=True)
    print(json.dumps(eng.metrics(), indent=1))


if __name__ == "__main__":
    main()
