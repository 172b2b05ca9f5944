"""The comparison that decides ``correct``, shown to fail where it must.

At a size a test run holds, on the CPU:

* a sound run of each cell is correct;
* the control (the plain reference in the program's place, one precision
  below what the configuration states) reads above the cell's limit;
* the timed path broken underneath, one planted fault at a time, makes
  ``correct`` come out false.

The sweep cells are held to their committed limits.  The four-chip cell
runs in a child process that has four host devices: on one device the
sweep takes its unsharded path.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from cells import reduced_backbones, run_small, small_ctx

SHARDED = "fleet1024-sweep-4chip"
SWEEPS = ("fleet1024-sweep", "table1-sweep-6h", SHARDED)
SERVED = "table1-served-waves"
SEED = 2**31 + 4099
HERE = Path(__file__).resolve().parent


def on_its_devices(fn, cell: str, *args):
    """``fn(cell, *args)``; for the four-chip cell, in a child process
    that has four host devices."""
    if cell != SHARDED:
        return fn(cell, *args)
    code = (f"import json, sys; sys.path[:0] = {sys.path!r}; import test_controls as t; "
            f"print(json.dumps(t.{fn.__name__}(*{(cell,) + args!r})))")
    flags = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags.strip())
    proc = subprocess.run([sys.executable, "-c", code], cwd=HERE, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(cell: str, fault: str | None = None) -> dict:
    """A run of ``cell`` at test size, with ``fault`` planted."""
    with contextlib.ExitStack() as stack:
        if fault is not None:
            stack.enter_context(patched(*SWEEP_FAULTS[fault]))
        out = run_small(cell, SEED)
    return {"correct": out["correct"], "compared": out["compared"],
            "attempted": out["attempted"], "failed": out["failed"],
            "devices": jax.device_count()}


def control(cell: str) -> dict:
    from chipbench import harness
    from chipbench.calibrate import readings

    ctx = small_ctx(cell, SEED)
    got = readings(ctx, harness.driver_of(ctx), 0.0)
    return {"program": got["program"], "control": got["control"],
            "limit": float(ctx.traffic["check"]["limit"]), "devices": jax.device_count()}


@pytest.mark.parametrize("cell", (SERVED,) + SWEEPS)
def test_sound_run_is_correct(cell):
    out = on_its_devices(run, cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["devices"] == (4 if cell == SHARDED else 1)


@pytest.mark.parametrize("cell", SWEEPS)
def test_sweep_control_fails(cell):
    got = on_its_devices(control, cell)
    assert max(got["program"].values()) <= got["limit"], got["program"]
    assert max(got["control"].values()) > got["limit"], got["control"]


def test_served_control_fails():
    from chipbench.calibrate import readings
    from chipbench import harness

    ctx = small_ctx(SERVED, SEED)
    with reduced_backbones():
        got = readings(ctx, harness.driver_of(ctx), 0.0)
    limits = ctx.traffic["check"]["limit"]
    assert all(got["program"][k] <= v for k, v in limits.items()), got["program"]
    assert any(got["control"][k] > v for k, v in limits.items()), got["control"]


@contextlib.contextmanager
def patched(module: str, name: str, make):
    """Replace ``module.name`` by ``make(original)`` for the block, with
    every compiled program dropped before and after."""
    mod = importlib.import_module(module)
    original = getattr(mod, name)
    jax.clear_caches()
    setattr(mod, name, make(original))
    try:
        yield
    finally:
        setattr(mod, name, original)
        jax.clear_caches()


def _state_unchanged(original):
    def step(queue, lam, g, fleet, config, route_eff, exit_frac):
        served, _, latency, completed, endo, mis = original(
            queue, lam, g, fleet, config, route_eff, exit_frac)
        return served, queue, latency, completed, endo, mis
    return step


def _half_the_agents(original):
    def finalize(acc, num_steps, active=None, workflow=None, *, config):
        n = acc.lat_sum.shape[-1]
        mask = jnp.ones(n) if active is None else active
        mask = mask * (jnp.arange(n) < (n + 1) // 2)
        return original(acc, num_steps, mask, workflow, config=config)
    return finalize


def _answer_altered(original):
    def run_grid(*args, **kwargs):
        out = original(*args, **kwargs)
        return (out[0] * 1.25,) + tuple(out[1:])
    return run_grid


def _exchange_left_out(original):
    """The sharded grid's blocks from the other chips never arrive: the
    second half of the fleet axis (the mesh's data axis) repeats the
    first half's results."""
    def sharded(*args, **kwargs):
        out = original(*args, **kwargs)

        def first_half_only(x):
            h = x.shape[0] // 2
            return x.at[h:].set(x[:x.shape[0] - h])
        return tuple(first_half_only(x) for x in out)
    return sharded


SWEEP_FAULTS = {
    "state_unchanged": ("repro.core.simulator", "_queue_step", _state_unchanged),
    "half_the_agents": ("repro.core.simulator", "finalize_metrics", _half_the_agents),
    "answer_altered": ("repro.core.sweep", "_run_grid", _answer_altered),
    "exchange_left_out": ("repro.core.sweep", "_stream_grid_sharded", _exchange_left_out),
}
# Only the sharded path has an exchange between chips to leave out.
CELL_FAULTS = [(c, f) for c in SWEEPS for f in sorted(SWEEP_FAULTS)
               if f != "exchange_left_out" or c == SHARDED]


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_sweep_fault_is_caught(cell, fault):
    out = on_its_devices(run, cell, fault)
    assert not out["correct"], out["compared"]


def _token_altered(original):
    def decode_once(self, rt):
        made = original(self, rt)
        for r in rt.active:
            if r is not None and len(r.tokens_out) > 1:
                r.tokens_out[-1] = (r.tokens_out[-1] + 1) % rt.api.cfg.vocab_size
                break
        return made
    return decode_once


def _cache_unchanged(original):
    def decode_step(params, caches, token, pos, cfg, max_len):
        logits, _ = original(params, caches, token, pos, cfg, max_len)
        return logits, caches
    return decode_step


def _half_the_slots(original):
    def decode_step(params, caches, token, pos, cfg, max_len):
        b = token.shape[0]
        logits, new = original(params, caches, token, pos, cfg, max_len)
        keep = jnp.arange(b) < (b + 1) // 2
        logits = jnp.where(keep[:, None], logits, logits[:1])
        return logits, new
    return decode_step


SERVED_FAULTS = {
    "token_altered": ("repro.serving.engine", "FleetEngine", None),
    "state_unchanged": ("repro.models.transformer", "decode_step", _cache_unchanged),
    "half_the_slots": ("repro.models.transformer", "decode_step", _half_the_slots),
}


@pytest.mark.parametrize("fault", sorted(SERVED_FAULTS))
def test_served_fault_is_caught(fault):
    if fault == "token_altered":
        engine = importlib.import_module("repro.serving.engine")
        original = engine.FleetEngine._decode_once
        engine.FleetEngine._decode_once = _token_altered(original)
        try:
            out = run_small(SERVED, SEED)
        finally:
            engine.FleetEngine._decode_once = original
    else:
        with patched(*SERVED_FAULTS[fault]):
            out = run_small(SERVED, SEED)
    assert not out["correct"], out["compared"]


def test_control_rounding_is_lower_precision():
    from chipbench.reference import layers

    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096), jnp.float32)
    err = jnp.abs(layers._round(x, ml_dtypes.float8_e4m3fn) - x).max()
    bf16 = jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).max()
    assert float(err) > 4 * float(bf16)
