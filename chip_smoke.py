"""Smoke run of both main paths on TPU, through their normal entry points.

  python chip_smoke.py            # one chip: kernels, served fleet, sweeps
  python chip_smoke.py --chips 4  # four chips: sharded sweeps only

One chip:

1. kernels: each Pallas kernel at the served models' shapes against its
   ``kernels/*/ref.py`` reference, max error printed and held to the
   bf16 tolerances of tests/test_kernels.py;
2. served path: ``launch/serve.py::build_engine`` over
   ``ONE_CHIP_FLEET`` at published widths (random weights from a fixed
   seed), a few requests per agent through ``FleetEngine.submit`` and
   ``step()`` until every one completes; the engine's ``metrics()`` is
   printed, every agent's compiled decode step must give finite logits,
   and the compiled decode step of an attention agent and the compiled
   prefill of the SSD agent must hold a ``tpu_custom_call``;
3. sweep path: the Table II anchors, ``sweep`` over the paper fleet and
   the 8-scenario library at 10,000 steps, and a ``sweep_fleets`` grid of
   N=1024 fleets, with cells compared against the float64 numpy oracle
   (``reference_sim.simulate_numpy``) at tests/test_reference_sim.py's
   tolerances.

Four chips (``--chips 4``): only the sharded sweeps on the
``("data", "grid", "policy")`` mesh, each against a one-device grid in
the same process (see ``check_sharded_sweeps``).

Everything runs in this one process.  Progress goes to earlier lines; the
last line of standard output is one JSON object naming the device.  Exits
nonzero, before any phase, when JAX finds no TPU, and on any failure.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
BF16_ATOL = {"flash_attention": 2e-2, "decode_attention": 2e-2, "ssd": 5e-2}
# Oracle tolerances of tests/test_reference_sim.py.
ORACLE_RTOL, ORACLE_ATOL = 2e-4, 2e-3
# One prompt length per agent, so every slot of an agent decodes at the
# same position (the engine decodes all slots at one position).
PROMPT_LEN = {
    "coordinator": 256,
    "specialist_nlp": 128,
    "specialist_vision": 384,
    "specialist_reasoning": 512,
}
REQUESTS_PER_AGENT = 3
MAX_NEW_TOKENS = 16
MAX_LEN = 640
# Large enough that every agent admits all its prompts in the first tick.
BUDGET_TOKENS = 16384
SWEEP_STEPS = 10_000
FLEET_N, FLEET_STEPS = 1024, 1000
# Table II anchors (.claude/skills/verify/SKILL.md): value, tolerance.
TABLE2 = {
    ("static_equal", "avg_latency"): (110.3, 0.05),
    ("round_robin", "avg_latency"): (756.0, 1.0),
    ("adaptive", "avg_latency"): (111.9, 0.05),
    ("adaptive", "total_throughput"): (58.12, 0.005),
}


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform!r})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: needs {chips} chips, JAX found {len(devices)}")
    return devices


def _bf16(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def _max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))))


def check_kernels() -> dict:
    """Each Pallas kernel at served shapes vs its jnp reference."""
    from repro.configs import get_config
    from repro.kernels.attention import ops as attn_ops
    from repro.kernels.attention import ref as attn_ref
    from repro.kernels.ssd import ops as ssd_ops
    from repro.kernels.ssd import ref as ssd_ref

    qwen, mamba = get_config("qwen2-vl-2b"), get_config("mamba2-370m")
    h, kv, d = qwen.num_heads, qwen.num_kv_heads, qwen.head_dim
    k = jax.random.split(jax.random.key(SEED), 8)
    errs = {}
    s = PROMPT_LEN["coordinator"]
    q, kk, v = _bf16(k[0], (1, s, h, d)), _bf16(k[1], (1, s, kv, d)), _bf16(k[2], (1, s, kv, d))
    got = attn_ops.flash_attention(q, kk, v, causal=True, impl="pallas")
    with jax.default_matmul_precision("float32"):
        want = attn_ref.mha(q, kk, v, causal=True)
    errs["flash_attention"] = _max_err(got, want)

    b = 4
    q = _bf16(k[3], (b, h, d))
    kc, vc = _bf16(k[4], (b, MAX_LEN, kv, d)), _bf16(k[5], (b, MAX_LEN, kv, d))
    lens = jnp.asarray([1, 100, 257, MAX_LEN], jnp.int32)
    got = attn_ops.decode_attention(q, kc, vc, lens, impl="pallas")
    with jax.default_matmul_precision("float32"):
        want = attn_ref.decode_gqa(q, kc, vc, lens)
    errs["decode_attention"] = _max_err(got, want)

    nh, p, n = mamba.ssm_num_heads, mamba.ssm_head_dim, mamba.ssm_state_dim
    s = PROMPT_LEN["specialist_reasoning"]
    kx = jax.random.split(k[6], 5)
    x = (jax.random.normal(kx[0], (1, s, nh, p)) * 0.5).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(kx[1], (1, s, nh)))
    a = -jnp.exp(jax.random.normal(kx[2], (nh,)) * 0.3)
    # B and C scaled by N^-1/2 so that C.B has unit variance: with unit B
    # and C at N=128, |y| reaches ~150 and one bf16 ulp of the output
    # alone exceeds the absolute tolerance, which is sized for |y| ~ 1.
    bm = (jax.random.normal(kx[3], (1, s, n)) * n ** -0.5).astype(jnp.bfloat16)
    cm = (jax.random.normal(kx[4], (1, s, n)) * n ** -0.5).astype(jnp.bfloat16)
    dd = jnp.ones((nh,))
    y, hf = ssd_ops.ssd(x, dt, a, bm, cm, dd, chunk=mamba.ssm_chunk_size, impl="pallas")
    with jax.default_matmul_precision("float32"):
        y_ref, h_ref = ssd_ref.ssd_naive(x, dt, a, bm, cm, dd)
    errs["ssd"] = max(_max_err(y, y_ref), _max_err(hf, h_ref))

    for name, err in errs.items():
        log(f"kernel {name}: max |pallas - ref| = {err!r} (atol {BF16_ATOL[name]})")
        if not err <= BF16_ATOL[name]:
            raise AssertionError(f"{name} error {err} exceeds {BF16_ATOL[name]}")
    return errs


def serve_fleet():
    """Drive ``build_engine`` over ``ONE_CHIP_FLEET`` at published widths
    until every request completes."""
    from repro.configs import get_config
    from repro.launch.serve import ONE_CHIP_FLEET, build_engine

    need = sum(2 * get_config(arch).param_count for _, arch, *_ in ONE_CHIP_FLEET)
    limit = jax.devices()[0].memory_stats().get("bytes_limit", 0)
    log(f"serve: {need / 2**30:.2f} GiB of bf16 parameters, device limit "
        f"{limit / 2**30:.2f} GiB")
    if limit and need > 0.9 * limit:
        raise AssertionError("the fleet's parameters do not fit the device")

    t0 = time.perf_counter()
    eng = build_engine("adaptive", fleet=ONE_CHIP_FLEET, reduced=False,
                       budget_tokens=BUDGET_TOKENS, max_len=MAX_LEN)
    log(f"serve: engine built in {time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(SEED)
    submitted = []
    for rt in eng.runtimes:
        vocab = rt.api.cfg.vocab_size
        for _ in range(REQUESTS_PER_AGENT):
            prompt = rng.integers(0, vocab, PROMPT_LEN[rt.name])
            submitted.append(eng.submit(rt.name, prompt, MAX_NEW_TOKENS))
    t0 = time.perf_counter()
    for _ in range(50):
        if all(r.finish_tick >= 0 for r in submitted):
            break
        eng.step()
    log(f"serve: {eng.tick} ticks in {time.perf_counter() - t0:.1f} s "
        "(compilation included)")
    unfinished = [(r.agent, r.id) for r in submitted if r.finish_tick < 0]
    if unfinished:
        raise AssertionError(f"requests never completed: {unfinished}")
    short = [(r.agent, r.id) for r in submitted if len(r.tokens_out) != MAX_NEW_TOKENS]
    if short:
        raise AssertionError(f"requests finished short of max_new_tokens: {short}")
    m = eng.metrics()
    log("serve: metrics " + json.dumps(m))
    if m["completed"] != len(submitted):
        raise AssertionError(f"completed {m['completed']} of {len(submitted)}")
    return eng


def _check_logits(name: str, logits, rows: int, vocab: int) -> None:
    if logits.shape != (rows, vocab):
        raise AssertionError(f"{name} logits {logits.shape}, want {(rows, vocab)}")
    if not bool(jnp.isfinite(logits.astype(jnp.float32)).all()):
        raise AssertionError(f"{name} logits hold non-finite values")


def check_compiled_steps(eng) -> None:
    """The engine's own compiled steps, after serving.  Every agent's decode
    step gives finite logits of shape (slots, padded vocab).  A silent
    fall-back to the jnp reference would drop the Pallas kernels from the
    compiled programs: the attention decode step and the SSD prefill must
    each hold a ``tpu_custom_call``."""
    for rt in eng.runtimes:
        tokens = jnp.zeros((rt.batch_slots,), jnp.int32)
        pos = jnp.int32(PROMPT_LEN[rt.name])
        logits, _ = rt._decode_jit(rt.params, rt.caches, tokens, pos)
        _check_logits(f"{rt.name} decode", logits, rt.batch_slots, rt.api.cfg.padded_vocab)
        if rt.name == "coordinator":
            txt = rt._decode_jit.lower(rt.params, rt.caches, tokens, pos).compile().as_text()
            if "tpu_custom_call" not in txt:
                raise AssertionError("coordinator decode step holds no tpu_custom_call")
        if rt.name == "specialist_reasoning":
            batch = {"tokens": jnp.zeros((1, PROMPT_LEN[rt.name]), jnp.int32)}
            logits, _ = rt._prefill_jit(rt.params, batch)
            _check_logits(f"{rt.name} prefill", logits, 1, rt.api.cfg.padded_vocab)
            txt = rt._prefill_jit.lower(rt.params, batch).compile().as_text()
            if "tpu_custom_call" not in txt:
                raise AssertionError("SSD agent prefill holds no tpu_custom_call")
    log("serve: finite logits from every agent's decode step; tpu_custom_call "
        "in the attention decode step and the SSD prefill")


def _oracle_metrics(policy, arrivals, fleet, config):
    """A sweep cell's metric vector, recomputed from the numpy oracle's
    float64 trajectories through the one metric definition."""
    from repro.core.reference_sim import simulate_numpy
    from repro.core.simulator import SimTrace, trace_metrics

    ref = simulate_numpy(policy, np.asarray(arrivals, np.float64), fleet,
                         g_total=config.g_total, latency_cap=config.latency_cap,
                         ema_alpha=config.ema_alpha, num_gpus=config.num_gpus)
    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in ref.items()}
    trace = SimTrace(arrivals=jnp.asarray(arrivals, jnp.float32), **f32)
    return np.asarray(trace_metrics(trace, fleet.active, None, config=config)[0])


def _compare_cells(tag, result, cells, arrivals_of, fleet_of) -> None:
    from repro.core.simulator import METRIC_NAMES

    for policy, scen, fl in cells:
        p = result.policy_names.index(policy)
        w = result.scenario_names.index(scen)
        got = result.metrics[p, w] if fl is None else result.metrics[fl, p, w]
        want = _oracle_metrics(policy, arrivals_of(fl, w), fleet_of(fl), result.config)
        err = np.abs(got - want)
        worst = int(np.argmax(err / (ORACLE_ATOL + ORACLE_RTOL * np.abs(want))))
        log(f"{tag}: {policy}/{scen}{'' if fl is None else f'/fleet{fl}'} vs oracle: "
            f"max |diff| {float(err.max())!r} ({METRIC_NAMES[worst]})")
        np.testing.assert_allclose(got, want, rtol=ORACLE_RTOL, atol=ORACLE_ATOL,
                                   err_msg=f"{tag} {policy}/{scen}")


def check_sweeps() -> None:
    from repro.core import workload
    from repro.core.agents import PAPER_ARRIVAL_RATES, paper_fleet, synthetic_fleet
    from repro.core.sweep import Scenario, sweep, sweep_fleets

    fleet = paper_fleet()
    rates = jnp.asarray(PAPER_ARRIVAL_RATES)
    res = sweep(fleet, (Scenario("constant", workload.constant(rates, 100)),))
    for policy in res.policy_names:
        s = res.summary(policy, "constant")
        if abs(s.cost - 0.02) > 5e-4:
            raise AssertionError(f"Table II: {policy} cost {s.cost}")
    for (policy, metric), (value, tol) in TABLE2.items():
        got = getattr(res.summary(policy, "constant"), metric)
        log(f"table2: {policy} {metric} = {got!r} (anchor {value} ± {tol})")
        if abs(got - value) > tol:
            raise AssertionError(f"Table II anchor {policy}/{metric}: {got} vs {value}")

    specs = workload.scenario_specs(PAPER_ARRIVAL_RATES, num_steps=SWEEP_STEPS)
    t0 = time.perf_counter()
    res = sweep(fleet, specs)
    log(f"sweep: paper fleet, {len(res.policy_names)} policies x "
        f"{len(specs)} scenarios x {SWEEP_STEPS} steps in "
        f"{time.perf_counter() - t0:.1f} s (compilation included)")
    if not np.isfinite(res.metrics).all():
        raise AssertionError("sweep metrics hold non-finite values")
    cells = [("adaptive", "constant", None), ("round_robin", "bursty", None),
             ("throughput_greedy", "poisson", None), ("objective_descent", "diurnal", None)]
    _compare_cells("sweep", res, cells,
                   lambda fl, w: workload.materialize(specs[w]), lambda fl: fleet)

    fleets = [synthetic_fleet(FLEET_N, seed=i) for i in range(2)]
    t0 = time.perf_counter()
    res = sweep_fleets(fleets, num_steps=FLEET_STEPS, seed=SEED, synthesize=True)
    log(f"sweep_fleets: 2 fleets of N={FLEET_N}, {len(res.policy_names)} policies x "
        f"{len(res.scenario_names)} scenarios x {FLEET_STEPS} steps in "
        f"{time.perf_counter() - t0:.1f} s (compilation included)")
    if not np.isfinite(res.metrics).all():
        raise AssertionError("sweep_fleets metrics hold non-finite values")
    rate_vectors = [workload.synthetic_rates(FLEET_N, seed=SEED + i) for i in range(2)]
    _, rows = workload.fleet_scenario_specs(rate_vectors, FLEET_N, FLEET_STEPS, SEED)
    cells = [("adaptive", "bursty", 0), ("water_filling", "spike", 1)]
    _compare_cells("sweep_fleets", res, cells,
                   lambda fl, w: workload.materialize(rows[fl][w]), lambda fl: fleets[fl])


def _diff(got, want) -> str:
    d = np.abs(got - want)
    rel = np.max(d / np.maximum(np.abs(want), 1e-30))
    return (f"bit-identical: {np.array_equal(got, want)}, {int(np.count_nonzero(d))} "
            f"of {d.size} differ, max |diff| {float(d.max())!r}, max relative {float(rel)!r}")


def _switch_dispatch_grid(fleet, wspec, batch_axis) -> np.ndarray:
    """The one-device streaming grid with the workload generators dispatched
    through the vmapped ``lax.switch``, as the sharded body dispatches them
    (``shard=False`` groups them statically instead)."""
    from repro.core import allocator as alloc
    from repro.core.simulator import SimConfig
    from repro.core.sweep import _stream_grid_jit

    return np.asarray(_stream_grid_jit(
        None, fleet, None, None, wspec, None, SimConfig(), alloc.policy_names(),
        batch_axis, block_size=1, gen_groups=None,
    )[0])


def check_sharded_sweeps(chips: int) -> None:
    """The sharded sweeps on the chips' mesh, in both workload arms.

    A sharded and an unsharded grid are two compilations, which on a TPU
    round differently (one f32 ulp in a few values), so both arms are held
    to tests/test_sharding.py's tolerance for such pairs, and bit-identity
    is printed.  With materialized arrivals the reference is
    ``shard=False``.  With in-scan synthesis the sharded body dispatches the
    generators through a vmapped ``lax.switch`` while ``shard=False``
    groups them statically; on a TPU that changes the rounding of the
    trajectories, and threshold policies amplify it, so the reference is
    the one-device grid with the same switch dispatch, and the difference
    from ``shard=False`` is printed.
    """
    from repro.core import sharding, workload
    from repro.core.agents import (
        PAPER_ARRIVAL_RATES, paper_fleet, stack_fleets, synthetic_fleet,
    )
    from repro.core.sweep import sweep, sweep_fleets

    if not sharding.should_shard():
        raise AssertionError("the sweeps would not shard on this host")
    specs = workload.scenario_specs(PAPER_ARRIVAL_RATES, num_steps=SWEEP_STEPS)
    fleets = [synthetic_fleet(FLEET_N, seed=i) for i in range(chips)]
    rates = [workload.synthetic_rates(FLEET_N, seed=SEED + i) for i in range(chips)]
    _, rows = workload.fleet_scenario_specs(rates, FLEET_N, FLEET_STEPS, SEED)
    grids = {
        "sweep": (
            lambda shard, synth: sweep(
                paper_fleet(), specs, shard=shard, synthesize=synth).metrics,
            lambda: _switch_dispatch_grid(
                paper_fleet(), workload.stack_specs(specs), None),
        ),
        "sweep_fleets": (
            lambda shard, synth: sweep_fleets(
                fleets, num_steps=FLEET_STEPS, seed=SEED, synthesize=synth,
                shard=shard).metrics,
            lambda: _switch_dispatch_grid(stack_fleets(fleets), workload.stack_specs(
                [workload.stack_specs(r) for r in rows]), "fleet"),
        ),
    }
    # shard="3d" factors 4 chips with a policy axis of 1; the explicit
    # policy width of 2 splits the 9 policies (padded to 10) over two chips.
    layouts = (("2d", True, None), ("3d", "3d", None), ("policy=2", True, "2"))
    mismatched = []
    for synth in (False, True):
        arm = "synthesized" if synth else "materialized"
        for name, (grid, switch_grid) in grids.items():
            t0 = time.perf_counter()
            base = grid(False, synth)
            ref = switch_grid() if synth else base
            log(f"{name} ({arm}): one device in {time.perf_counter() - t0:.1f} s")
            for layout, shard, dp in layouts:
                if dp is None:
                    os.environ.pop(sharding.POLICY_ENV, None)
                else:
                    os.environ[sharding.POLICY_ENV] = dp
                mesh = sharding.grid_mesh(policy_devices=sharding.policy_mesh_devices(shard))
                t0 = time.perf_counter()
                got = grid(shard, synth)
                msg = (f"{name} ({arm}): {layout} on mesh {dict(mesh.shape)} in "
                       f"{time.perf_counter() - t0:.1f} s; vs shard=False: {_diff(got, base)}")
                if synth:
                    msg += f"; vs one-device switch dispatch: {_diff(got, ref)}"
                log(msg)
                if not np.allclose(got, ref, rtol=1e-5, atol=1e-6):
                    mismatched.append(f"{name} {arm} {layout}")
            os.environ.pop(sharding.POLICY_ENV, None)
    if mismatched:
        raise AssertionError(f"sharded grids differ from their reference: {mismatched}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweeps against shard=False")
    args = ap.parse_args()
    devices = require_tpu(args.chips)

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    if args.chips == 4:
        check_sharded_sweeps(args.chips)
    else:
        check_kernels()
        eng = serve_fleet()
        check_compiled_steps(eng)
        del eng
        check_sweeps()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
