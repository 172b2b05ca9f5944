"""Weak/strong scaling of the 2D-sharded streaming sweep to million-cell
grids — the ``BENCH_scaling.json`` writer.

The sweep mesh (``core/sharding.py``) is exercised at 1/2/4/8 **forced host
devices**: the XLA flag is consumed once at backend initialization, so each
device count runs in its own subprocess (``sharding.host_device_env``) and
reports its timings back over stdout.  Four grid families:

* ``strong`` — one fixed (F × P × W) grid at every device count; ideal
  strong scaling halves the wall time per doubling.
* ``weak`` — per-device work held constant (F ∝ devices); ideal weak
  scaling holds the wall time flat.
* ``scenario_major`` — the grid shape the old 1D layout handled worst: a
  tiny fleet axis that never divides the device count, so the whole grid
  fell back to replication (every device computing every cell).  Measured
  both ways at the top device count: the 2D mesh shards the scenario axis
  instead, and the entry pair records the honest speedup.
* ``frontier`` — the N=10⁴-fleet grid and a million-cell (F·P·W > 10⁶)
  grid, streaming + 2D-sharded, with the replicated-1D baseline measured
  alongside at 10⁴ fleets; plus a 10⁵-step scenario-axis horizon grid
  through the plain ``sweep`` entry point (horizon-independent memory is
  what makes it feasible at all).
* ``block_sweep`` — the time-blocked kernel's B-sweep: the full-registry
  grid at S = 10⁵ for B ∈ {1, 8, 32, 128} × synth/materialized, each row
  compiled cold through ``_bench.compile_probe`` so ``compile_s`` records
  the one-time cost the block size buys its throughput with.  Synth rows
  run before materialized rows so their ``max_rss_bytes`` marks stay
  attributable.
* ``horizon_synth`` / ``horizon_mat`` — the in-scan-synthesis payoff pair
  at S = 10⁶ steps: the full scenario registry as ``WorkloadSpec`` columns
  synthesized inside the scan (O(W·N) input memory) versus the same specs
  materialized to a (W, S, N) tensor first (the materialization runs
  inside the timed region — it is exactly the producer cost synthesis
  eliminates).  Each arm is measured at B = 1 and again at the best B its
  own ``block_sweep`` rows measured (resolved worker-side, recorded in the
  row's ``block_size``).  Within each arm the B = 1 row runs *first* so
  the blocked row's ``max_rss_bytes`` is comparable against it.
* ``widefleet`` — the honest memory frontier: a fleet wide enough that the
  materialized S = 10⁶ arrivals tensor exceeds physical host RAM.  The
  materialized arm is **refused** (an entry with ``status`` and
  ``required_bytes`` > ``available_bytes`` — no timing, the allocation
  cannot exist), while the synthesis arm is measured at the same width
  over a shorter *probe* horizon (its memory is O(1) in S, so only wall
  time — ~14 ms/step/80k-lanes on this host — caps the probe).
* ``policy_axis`` — strong scaling over the third mesh axis at the top
  device count: a deliberately narrow scenario axis (W=2) that starves
  the 2D layout, re-run with dp ∈ divisors so the (P, N) policy-stack
  rows split across the ``policy`` axis instead.

Timed regions contain kernel work only (fleet/scenario construction is
hoisted, as in ``fleet_scaling.py``), block on device output via
``_bench.time_device``, and — because the 2D kernel *donates* its arrivals
block — rebuild the donated buffer inside the timed function, exactly the
cost a fresh-arrivals producer pays.  Every entry lands in the stable
``_bench`` schema with its own ``device_count``/``host_cpus``; wall-clock
caveat: on a host with fewer physical cores than forced devices the
device blocks time-slice, so strong/weak curves flatten — the
``scenario_major`` pair stays meaningful there because the replicated
baseline burns ``device_count×`` *total* work, not just wall time.

``--smoke`` (or ``REPRO_BENCH_SMOKE=1``) shrinks to 2 device counts and
liveness-sized grids; the JSON then goes to ``experiments/smoke/`` (CI
uploads it as an artifact).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

SENTINEL = "SCALING_JSON:"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEVICE_COUNTS = (1, 2, 4, 8)
STRONG_FLEETS = 64
WEAK_FLEETS_PER_DEVICE = 16
SCENARIO_MAJOR_FLEETS = 2      # deliberately never divides the device count
FRONTIER_FLEETS = 10_000
MILLION_CELL_FLEETS = 18_000   # 18_000 · 7 policies · 8 scenarios > 10⁶ cells
HORIZON_STEPS = 100_000
HORIZON_FRONTIER_STEPS = 1_000_000
BLOCK_SWEEP_STEPS = 100_000
BLOCK_SWEEP_SIZES = (1, 8, 32, 128)
SMOKE_BLOCK_SIZES = (1, 8)
WIDE_AGENTS = 40_960           # (1, 10⁶, 40960) f32 = 164 GB: exceeds host RAM
WIDE_STEPS = 1_000_000
WIDE_PROBE_STEPS = 20_000      # synth probe horizon: memory is O(1) in S,
                               # only the ~14 ms/step wall caps the probe
POLICY_AXIS_STEPS = 1_000
POLICY_AXIS_SCENARIOS = 2      # narrow scenario axis: starves the 2D layout
NUM_STEPS = 200
FRONTIER_STEPS = 50
AGENTS = 8
FRONTIER_AGENTS = 4
REPS = 3
WORKER_TIMEOUT_S = 7200


def _policy_axis_widths(device_count: int) -> tuple[int, ...]:
    return tuple(k for k in (1, 2, 4, 8) if device_count % k == 0
                 and k <= device_count)


def _tasks(device_count: int, max_devices: int, smoke: bool) -> list[dict]:
    """The grid family list one worker process runs."""
    steps = 20 if smoke else NUM_STEPS
    reps = 1 if smoke else REPS
    strong_f = 8 if smoke else STRONG_FLEETS
    weak_f = (4 if smoke else WEAK_FLEETS_PER_DEVICE) * device_count
    tasks = []
    if device_count == 1:
        # Memory-frontier grids are a per-host story: single device, and
        # first in the worker so each arm's max_rss high-water mark is
        # attributable (synth before materialized, both before anything
        # bigger).
        h_steps = 1_000 if smoke else HORIZON_FRONTIER_STEPS
        b_steps = 500 if smoke else BLOCK_SWEEP_STEPS
        b_sizes = SMOKE_BLOCK_SIZES if smoke else BLOCK_SWEEP_SIZES
        # Per arm: the S=1e5 B-sweep, then the S=1e6 row at B=1, then the
        # S=1e6 row at the best B the sweep measured — synth family first
        # so every one of its max_rss marks precedes the bigger
        # materialized buffers.
        for arm in ("synth", "mat"):
            mode = f"{arm}_horizon"
            for b in b_sizes:
                tasks.append(dict(grid="block_sweep_1e5", mode=mode,
                                  fleets=1, agents=FRONTIER_AGENTS,
                                  num_steps=b_steps, reps=1, block_size=b))
            tasks.append(dict(grid=f"horizon_{arm}_1e6", mode=mode,
                              fleets=1, agents=FRONTIER_AGENTS,
                              num_steps=h_steps, reps=1, block_size=1))
            tasks.append(dict(grid=f"horizon_{arm}_1e6_bestB", mode=mode,
                              fleets=1, agents=FRONTIER_AGENTS,
                              num_steps=h_steps, reps=1, block_size="best"))
        wide_n = 2_048 if smoke else WIDE_AGENTS
        tasks.append(dict(grid="widefleet_synth_probe", mode="synth_wide",
                          fleets=1, agents=wide_n,
                          num_steps=50 if smoke else WIDE_PROBE_STEPS,
                          reps=1))
        tasks.append(dict(grid="widefleet_mat_1e6", mode="refusal_mat",
                          fleets=1, agents=WIDE_AGENTS,
                          num_steps=WIDE_STEPS, reps=0))
    tasks += [
        dict(grid="strong", mode="default", fleets=strong_f, agents=AGENTS,
             num_steps=steps, reps=reps),
        dict(grid="weak", mode="default", fleets=weak_f, agents=AGENTS,
             num_steps=steps, reps=reps),
    ]
    if device_count == max_devices:
        sm_f = SCENARIO_MAJOR_FLEETS
        tasks.append(dict(grid="scenario_major", mode="default", fleets=sm_f,
                          agents=AGENTS, num_steps=steps, reps=reps))
        tasks.append(dict(grid="scenario_major", mode="replicated_1d",
                          fleets=sm_f, agents=AGENTS, num_steps=steps,
                          reps=reps))
        for dp in _policy_axis_widths(device_count):
            tasks.append(dict(grid="policy_axis", mode="policy_axis",
                              fleets=1, agents=AGENTS,
                              num_steps=50 if smoke else POLICY_AXIS_STEPS,
                              reps=reps, policy_devices=dp))
        if not smoke:
            tasks.append(dict(grid="frontier_10k", mode="default",
                              fleets=FRONTIER_FLEETS, agents=FRONTIER_AGENTS,
                              num_steps=FRONTIER_STEPS, reps=1))
            tasks.append(dict(grid="frontier_10k", mode="replicated_1d",
                              fleets=FRONTIER_FLEETS, agents=FRONTIER_AGENTS,
                              num_steps=FRONTIER_STEPS, reps=1))
            tasks.append(dict(grid="million_cell", mode="default",
                              fleets=MILLION_CELL_FLEETS,
                              agents=FRONTIER_AGENTS,
                              num_steps=FRONTIER_STEPS, reps=1))
            tasks.append(dict(grid="horizon_1e5", mode="scenario_axis",
                              fleets=1, agents=FRONTIER_AGENTS,
                              num_steps=HORIZON_STEPS, reps=1))
    return tasks


# -- worker side (runs once per forced device count) -------------------------


def _worker(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks import _bench
    import importlib

    from repro.core import allocator as alloc
    from repro.core import sharding, workload

    # ``from repro.core import sweep`` yields the re-exported *function*
    # (the package __init__ shadows the submodule name); the kernels live
    # on the module itself.
    sweep_mod = importlib.import_module("repro.core.sweep")
    from repro.core.agents import synthetic_fleet
    from repro.core.simulator import SimConfig
    from repro.core.sweep import scenario_library, sweep

    assert jax.device_count() == cfg["device_count"], jax.devices()
    names = alloc.policy_names()
    config = SimConfig()
    entries = []
    for task in cfg["tasks"]:
        f, n = task["fleets"], task["agents"]
        steps, reps = task["num_steps"], task["reps"]
        if task["mode"] == "refusal_mat":
            # The materialized arrivals tensor for this configuration cannot
            # exist on this host: record the refusal with the arithmetic
            # instead of OOM-killing the worker.  Even a single scenario
            # column ((1, S, N) float32 — same W=1 shape the synthesis arm
            # runs as ``widefleet_synth_probe``) exceeds physical RAM.
            required = steps * n * 4
            available = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
            entries.append({
                "grid": task["grid"], "kernel": "streaming_materialized",
                "n": n, "num_steps": steps, "cells": 1,
                "wall_us": None, "us_per_step": None,
                "us_per_step_per_cell": None, "peak_device_bytes": None,
                "status": "refused: materialized arrivals tensor exceeds host RAM",
                "required_bytes": required, "available_bytes": int(available),
                "device_count": cfg["device_count"],
                "host_cpus": os.cpu_count(),
            })
            continue
        fleet = synthetic_fleet(n, seed=0)
        if task["mode"] in ("synth_horizon", "mat_horizon"):
            # The block-sweep rows and the S=10⁶ payoff pair: same full
            # scenario registry, one arm synthesizing rows inside the scan,
            # the other materializing the (W, S, N) tensor inside the timed
            # region — the producer cost synthesis eliminates.  Every row
            # compiles cold through ``compile_probe`` and times the AOT
            # object, so ``compile_s`` is honest and the timed region pays
            # no hidden recompile.
            synth = task["mode"] == "synth_horizon"
            kernel = "streaming_synth" if synth else "streaming_materialized"
            bsz = task.get("block_size", 1)
            if bsz == "best":
                # Resolved worker-side, per arm: the B whose own
                # ``block_sweep`` row ran fastest earlier in this process.
                cands = [e for e in entries
                         if e["grid"] == "block_sweep_1e5"
                         and e["kernel"] == kernel]
                bsz = (min(cands, key=lambda e: e["wall_us"])["block_size"]
                       if cands else 1)
            specs = workload.scenario_specs(
                workload.synthetic_rates(n, seed=0), num_steps=steps, seed=0
            )
            cells = f * len(names) * len(specs)
            if synth:
                stack = workload.stack_specs(specs)
                compile_s, compiled = _bench.compile_probe(
                    sweep_mod._stream_grid_jit,
                    None, fleet, None, None, stack, None, config, names,
                    None, 1, bsz,
                )
                fn = lambda: compiled(None, fleet, None, None, stack, None)
            else:
                arr = jnp.stack([workload.materialize(s) for s in specs])
                compile_s, compiled = _bench.compile_probe(
                    sweep_mod._stream_grid_jit,
                    arr, fleet, None, None, None, None, config, names, None,
                    1, bsz,
                )
                del arr
                fn = lambda: compiled(
                    jnp.stack([workload.materialize(s) for s in specs]),
                    fleet, None, None, None, None,
                )
            wall_us = _bench.time_device(fn, reps)
            entries.append(_bench.timing_entry(
                task["grid"], kernel, n, steps, cells, wall_us,
                block_size=bsz, compile_s=compile_s,
                device_count=cfg["device_count"], host_cpus=os.cpu_count(),
                fleets=f, max_rss_bytes=_bench.max_rss_bytes(),
                arrivals_bytes_if_materialized=len(specs) * steps * n * 4,
            ))
            continue
        if task["mode"] == "synth_wide":
            # Synthesis at the refused width: a single cheap time-varying
            # generator (diurnal — no per-step RNG) over one policy, probe
            # horizon (memory is O(1) in S; see module docstring).
            spec = workload.diurnal_spec(
                workload.synthetic_rates(n, seed=0), num_steps=steps
            )
            stack = workload.stack_specs([spec])
            sub = names[:1]
            cells = f * len(sub)
            fn = lambda: sweep_mod._stream_grid_jit(
                None, fleet, None, None, stack, None, config, sub, None,
            )
            wall_us = _bench.time_device(fn, task["reps"])
            entries.append(_bench.timing_entry(
                task["grid"], "streaming_synth", n, steps, cells, wall_us,
                device_count=cfg["device_count"], host_cpus=os.cpu_count(),
                fleets=f, max_rss_bytes=_bench.max_rss_bytes(),
                probe_of_num_steps=WIDE_STEPS,
                arrivals_bytes_if_materialized=steps * n * 4,
            ))
            continue
        if task["mode"] == "policy_axis":
            # dp-way policy-axis split on a scenario axis too narrow for
            # the 2D layout (W=2): the (P, N) policy rows shard over the
            # mesh's third axis, names padded to divisibility inside
            # ``_run_stream_sharded``.
            dp = task["policy_devices"]
            specs = workload.scenario_specs(
                workload.synthetic_rates(n, seed=0), num_steps=steps, seed=0
            )[:POLICY_AXIS_SCENARIOS]
            stack = workload.stack_specs(specs)
            cells = f * len(names) * len(specs)
            if cfg["device_count"] > 1:
                fn = lambda: sweep_mod._run_stream_sharded(
                    None, fleet, None, None, config, names, None,
                    wspec=stack, policy_devices=dp,
                )
            else:
                fn = lambda: sweep_mod._stream_grid_jit(
                    None, fleet, None, None, stack, None, config, names, None
                )
            wall_us = _bench.time_device(fn, reps)
            entries.append(_bench.timing_entry(
                task["grid"], f"streaming_3d_dp{dp}", n, steps, cells,
                wall_us, device_count=cfg["device_count"],
                host_cpus=os.cpu_count(), fleets=f, policy_devices=dp,
            ))
            continue
        scenarios = scenario_library(
            workload.synthetic_rates(n, seed=0), num_steps=steps, seed=0
        )
        cells = f * len(names) * len(scenarios)
        if task["mode"] == "scenario_axis":
            # The long-horizon grid goes through the public ``sweep`` entry
            # point: scenario axis over the full mesh, fresh arrivals per
            # call (the donation contract), prep outside the timed region.
            fn = lambda: sweep(fleet, scenarios, return_arrays=True)
        else:
            # Fleet-axis grids: one shared scenario block broadcast across
            # F identical fleets, so million-fleet prep is O(1) host work
            # and the timed region is kernel-only.
            block = jnp.stack(
                [jnp.asarray(s.arrivals, jnp.float32) for s in scenarios]
            )  # (W, S, N)
            arrivals = jnp.array(
                jnp.broadcast_to(block, (f,) + block.shape)
            )  # (F, W, S, N), materialized
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.array(jnp.broadcast_to(x, (f,) + x.shape)),
                fleet,
            )
            if task["mode"] == "replicated_1d":
                # The pre-2D fallback for a non-divisible fleet axis:
                # inputs replicated on every device, every device computes
                # the full grid.  Kept only as this baseline measurement.
                layout = sharding.replicated(sharding.grid_mesh())
                arrivals_r = jax.device_put(arrivals, layout)
                stacked_r = jax.device_put(stacked, layout)
                fn = lambda: sweep_mod._stream_grid_jit(
                    arrivals_r, stacked_r, None, None, None, None, config,
                    names, "fleet",
                )
            elif jax.device_count() > 1:
                # The donated arrivals buffer is consumed per call; the
                # rebuild (one memcpy) stays inside the timed region — the
                # real per-call cost of a donating pipeline.
                fn = lambda: sweep_mod._run_stream_sharded(
                    jnp.copy(arrivals), stacked, None, None, config, names,
                    "fleet",
                )
            else:
                fn = lambda: sweep_mod._stream_grid_jit(
                    arrivals, stacked, None, None, None, None, config, names,
                    "fleet",
                )
        wall_us = _bench.time_device(fn, reps)
        kernel = {
            "default": "streaming_2d" if cfg["device_count"] > 1 else "streaming",
            "replicated_1d": "streaming_replicated_1d",
            "scenario_axis": "streaming_2d" if cfg["device_count"] > 1 else "streaming",
        }[task["mode"]]
        entries.append(_bench.timing_entry(
            task["grid"], kernel, n, steps, cells, wall_us,
            device_count=cfg["device_count"],
            host_cpus=os.cpu_count(),
            fleets=f,
            max_rss_bytes=_bench.max_rss_bytes(),
        ))
    return {"device_count": cfg["device_count"], "entries": entries}


# -- parent side -------------------------------------------------------------


def _spawn_worker(device_count: int, tasks: list[dict]) -> dict:
    from repro.core import sharding

    env = sharding.host_device_env(device_count)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO_ROOT, "src"), REPO_ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    cfg = {"device_count": device_count, "tasks": tasks}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.scaling_frontier", "--worker"],
        input=json.dumps(cfg), env=env, cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=WORKER_TIMEOUT_S,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"scaling worker ({device_count} devices) failed:\n"
            f"{proc.stdout}\n{proc.stderr}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith(SENTINEL):
            return json.loads(line[len(SENTINEL):])
    raise RuntimeError(f"no {SENTINEL} line in worker output:\n{proc.stdout}")


def _wall(entries: list[dict], grid: str, kernel_prefix: str = "streaming",
          device_count: int | None = None) -> float | None:
    for e in entries:
        if (e["grid"] == grid and e["kernel"].startswith(kernel_prefix)
                and not e["kernel"].endswith("replicated_1d")
                and (device_count is None or e["device_count"] == device_count)):
            return e["wall_us"]
    return None


def run(out_dir: str | None = None) -> list[str]:
    from benchmarks import _bench, _smoke

    smoke = _smoke.smoke()
    device_counts = (1, 2) if smoke else DEVICE_COUNTS
    max_devices = max(device_counts)
    entries: list[dict] = []
    for d in device_counts:
        payload = _spawn_worker(d, _tasks(d, max_devices, smoke))
        entries.extend(payload["entries"])

    path = _bench.write("scaling", entries, out_dir=out_dir)

    out = [f"scaling_frontier/bench,0,path={os.path.relpath(path, REPO_ROOT)}"]
    strong_1 = _wall(entries, "strong", device_count=device_counts[0])
    for d in device_counts:
        s = _wall(entries, "strong", device_count=d)
        w = _wall(entries, "weak", device_count=d)
        if s:
            out.append(
                f"scaling_frontier/strong_d{d},{s:.1f},"
                f"speedup_vs_d{device_counts[0]}={strong_1 / s:.2f}x"
            )
        if w:
            out.append(f"scaling_frontier/weak_d{d},{w:.1f},fleets_scale_with_devices")
    two_d = _wall(entries, "scenario_major", device_count=max_devices)
    one_d = next((e["wall_us"] for e in entries
                  if e["grid"] == "scenario_major"
                  and e["kernel"] == "streaming_replicated_1d"), None)
    if two_d and one_d:
        out.append(
            f"scaling_frontier/scenario_major_2d,{two_d:.1f},"
            f"speedup_vs_1d_replicated={one_d / two_d:.2f}x"
        )
    for grid in ("frontier_10k", "million_cell", "horizon_1e5"):
        wall = _wall(entries, grid)
        if wall:
            cells = next(e["cells"] for e in entries if e["grid"] == grid)
            out.append(f"scaling_frontier/{grid},{wall:.1f},cells={cells}")
    rep = next((e["wall_us"] for e in entries
                if e["grid"] == "frontier_10k"
                and e["kernel"] == "streaming_replicated_1d"), None)
    if rep and (f10k := _wall(entries, "frontier_10k")):
        out.append(
            f"scaling_frontier/frontier_10k_1d,{rep:.1f},"
            f"slowdown_vs_2d={rep / f10k:.2f}x"
        )
    synth = next((e for e in entries
                  if e["grid"] == "horizon_synth_1e6"), None)
    mat = next((e for e in entries if e["grid"] == "horizon_mat_1e6"), None)
    if synth and mat:
        out.append(
            f"scaling_frontier/horizon_synth,{synth['wall_us']:.1f},"
            f"S={synth['num_steps']};rss={synth.get('max_rss_bytes')}"
        )
        out.append(
            f"scaling_frontier/horizon_mat,{mat['wall_us']:.1f},"
            f"wall_vs_synth={mat['wall_us'] / synth['wall_us']:.2f}x;"
            f"rss={mat.get('max_rss_bytes')}"
        )
    for e in sorted((e for e in entries if e["grid"] == "block_sweep_1e5"),
                    key=lambda e: (e["kernel"], e["block_size"])):
        arm = e["kernel"].removeprefix("streaming_")
        out.append(
            f"scaling_frontier/block_{arm}_B{e['block_size']},"
            f"{e['wall_us']:.1f},compile_s={e['compile_s']:.2f}"
        )
    best_s = next((e for e in entries
                   if e["grid"] == "horizon_synth_1e6_bestB"), None)
    best_m = next((e for e in entries
                   if e["grid"] == "horizon_mat_1e6_bestB"), None)
    if best_s and synth:
        out.append(
            f"scaling_frontier/horizon_synth_bestB,{best_s['wall_us']:.1f},"
            f"B={best_s['block_size']};"
            f"speedup_vs_B1={synth['wall_us'] / best_s['wall_us']:.2f}x;"
            f"rss={best_s.get('max_rss_bytes')}"
        )
    if best_s and best_m:
        out.append(
            f"scaling_frontier/horizon_mat_bestB,{best_m['wall_us']:.1f},"
            f"B={best_m['block_size']};"
            f"mat_vs_synth={best_m['wall_us'] / best_s['wall_us']:.2f}x"
        )
    refusal = next((e for e in entries if e.get("status")), None)
    if refusal:
        out.append(
            f"scaling_frontier/widefleet_mat,0,"
            f"refused_required_gb={refusal['required_bytes'] / 1e9:.0f};"
            f"available_gb={refusal['available_bytes'] / 1e9:.0f}"
        )
    probe = next((e for e in entries
                  if e["grid"] == "widefleet_synth_probe"), None)
    if probe:
        out.append(
            f"scaling_frontier/widefleet_synth_probe,{probe['wall_us']:.1f},"
            f"n={probe['n']};us_per_step={probe['us_per_step']:.1f}"
        )
    pol = sorted((e for e in entries if e["grid"] == "policy_axis"),
                 key=lambda e: e["policy_devices"])
    if pol:
        base = pol[0]["wall_us"]
        for e in pol:
            out.append(
                f"scaling_frontier/policy_axis_dp{e['policy_devices']},"
                f"{e['wall_us']:.1f},speedup_vs_dp1={base / e['wall_us']:.2f}x"
            )
    return out


def main() -> None:
    if "--worker" in sys.argv[1:]:
        cfg = json.loads(sys.stdin.read())
        payload = _worker(cfg)
        print(SENTINEL + json.dumps(payload))
        return
    if "--smoke" in sys.argv[1:]:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    for line in run():
        print(line)


if __name__ == "__main__":
    main()
