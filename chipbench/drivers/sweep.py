"""Sweep cells: back-to-back grids of the streaming sweep kernel.

The traffic file sets the grid: the entry point (``sweep`` over one fleet
or ``sweep_fleets`` over ``fleets_per_grid``), the horizon ``num_steps``,
``shard``, and the scenario library the reference draws arrivals from.
The configuration file sets the fleets and their base rates.  Both are
made here from the run's seed, once, in set-up; each grid of the window
then runs under the next grid seed, so no two grids repeat their draws.

End to end: ``sweep_agent_steps_per_s``, every (fleet, policy, scenario,
agent, step) of every grid completed in the window over the window's
seconds; padded agents do not count.

``correct``: after the window, cells drawn from the seed, one for each
policy and every scenario among them, are run again by the float64
reference (``chipbench/reference``) on arrivals the reference draws
itself, and compared metric by metric.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.harness import log, seed_words
from chipbench.reference import arrivals as ref_arrivals
from chipbench.reference import sweep_oracle
from chipbench.trace import span


def make_fleets(config: dict, count: int, seed: int):
    """``count`` fleets and their base rate vectors, drawn from ``seed``
    as the configuration states."""
    import jax.numpy as jnp
    from repro.core.agents import Fleet

    f = config["fleet"]
    if f["kind"] == "table":
        rows = f["agents"]
        fleet = Fleet(
            names=tuple(a["name"] for a in rows),
            model_size_mb=jnp.asarray([a["model_size_mb"] for a in rows], jnp.float32),
            base_throughput=jnp.asarray([a["base_throughput"] for a in rows], jnp.float32),
            min_gpu=jnp.asarray([a["min_gpu"] for a in rows], jnp.float32),
            priority=jnp.asarray([a["priority"] for a in rows], jnp.float32),
        )
        rates = np.asarray(config["rates"]["per_agent"], np.float32)
        return [fleet] * count, [rates] * count
    rng = np.random.default_rng(seed)
    n = int(f["num_agents"])
    fleets, rate_vectors = [], []
    for i in range(count):
        mins = rng.uniform(*f["min_gpu_weight"], n)
        mins = f["min_gpu_total"] * mins / mins.sum()
        fleets.append(Fleet(
            names=tuple(f"agent_{k:04d}" for k in range(n)),
            model_size_mb=jnp.asarray(rng.uniform(*f["model_size_mb"], n), jnp.float32),
            base_throughput=jnp.asarray(rng.uniform(*f["base_throughput"], n), jnp.float32),
            min_gpu=jnp.asarray(mins, jnp.float32),
            priority=jnp.asarray(rng.integers(f["priority"][0], f["priority"][1] + 1, n),
                                 jnp.float32),
        ))
        w = rng.uniform(*config["rates"]["weight"], n)
        rate_vectors.append((config["rates"]["total"] * w / w.sum()).astype(np.float32))
    return fleets, rate_vectors


def _sim_config(config: dict):
    from repro.core.simulator import SimConfig

    s = config["sim"]
    return SimConfig(g_total=s["g_total"], latency_cap=s["latency_cap"],
                     price_per_hour=s["price_per_hour"], ema_alpha=s["ema_alpha"])


def traffic_specs(traffic: dict, rates, num_steps: int, seed: int) -> tuple:
    """The traffic file's scenarios as the program's workload specs for
    one fleet: each scenario's generator, its rate vector (after the
    scenario's rate transform), its parameters and its key."""
    from repro.core import workload

    keys = ref_arrivals.keys(seed)
    out = []
    for scn in traffic["scenarios"]:
        n = len(rates)
        key = keys[int(scn["key"])] if "key" in scn else None
        out.append(workload.make_spec(
            scn["gen"], ref_arrivals.scenario_rates(scn, rates), num_steps, key=key,
            knobs=ref_arrivals.knobs(scn, n, num_steps), name=scn["name"]))
    return tuple(out)


def check_library(traffic: dict, rates, num_steps: int, seed: int) -> None:
    """``sweep_fleets`` draws its scenario column from the program's own
    library; refuse to run when that library is not the traffic file's."""
    from repro.core import workload

    names, rows = workload.fleet_scenario_specs(rates, len(rates[0]), num_steps, seed)
    for r, row in zip(rates, rows):
        want = traffic_specs(traffic, r, num_steps, seed)
        same = names == tuple(s.name for s in want) and all(
            int(a.gen_id) == int(b.gen_id)
            and np.array_equal(np.asarray(a.knobs), np.asarray(b.knobs))
            and np.array_equal(np.asarray(a.key_data), np.asarray(b.key_data))
            and np.allclose(np.asarray(a.rates), np.asarray(b.rates), rtol=1e-6, atol=0)
            for a, b in zip(row, want))
        if not same:
            raise ValueError("the program's scenario library differs from the traffic "
                             "file's scenarios; sweep_fleets cannot run this mix")


def grid_call(traffic: dict, config: dict, fleets, rates):
    """The program's entry for one grid, as a function of the grid seed."""
    import importlib

    # ``repro.core`` exports the function ``sweep`` under the module's name.
    sweep_mod = importlib.import_module("repro.core.sweep")
    steps, shard = int(traffic["num_steps"]), traffic["shard"]
    sim = _sim_config(config)
    if traffic["entry"] == "sweep_fleets":
        def call(seed):
            return sweep_mod.sweep_fleets(
                fleets, rate_vectors=rates, num_steps=steps, seed=seed,
                config=sim, synthesize=True, shard=shard)
    else:
        def call(seed):
            specs = traffic_specs(traffic, rates[0], steps, seed)
            return sweep_mod.sweep(fleets[0], specs, config=sim, shard=shard,
                                   synthesize=True)
    return call


def setup(ctx):
    tr = ctx.traffic
    w = seed_words(ctx.seed, 2)
    count = int(tr.get("fleets_per_grid", 1))
    fleets, rates = make_fleets(ctx.config, count, w[0])
    call = grid_call(tr, ctx.config, fleets, rates)
    base = w[1] % (1 << 30)
    if tr["entry"] == "sweep_fleets":
        check_library(tr, rates, int(tr["num_steps"]), base)
    t0 = time.perf_counter()
    with span("warm-up grid"):
        res = call(base)
    log(f"sweep: warm-up grid (compiles on a cold cache) {time.perf_counter() - t0!r} s")
    return {"call": call, "fleets": fleets, "rates": rates, "base": base,
            "names": (res.policy_names, res.scenario_names)}


def window(ctx, state, seconds):
    tr = ctx.traffic
    call = state["call"]
    grids, ends = [], []
    t0 = time.perf_counter()
    while True:
        seed = state["base"] + 1 + len(grids)
        with span("grid"):
            res = call(seed)
        grids.append((seed, res.metrics))
        ends.append(time.perf_counter())
        if ends[-1] - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    each = np.diff([t0] + ends)
    slow = [(i, float(s)) for i, s in enumerate(each) if s > 1.25 * np.median(each)]
    log(f"sweep: seconds per grid min {each.min()!r}, quartiles "
        f"{np.percentile(each, [25, 50, 75]).tolist()!r}, max {each.max()!r}; "
        f"grids over 1.25 times the median (index, seconds) {slow}")
    steps = int(tr["num_steps"])
    policies, scenarios = state["names"]
    active = sum(int(np.asarray(f.active).sum()) for f in state["fleets"])
    agent_steps = len(grids) * len(policies) * len(scenarios) * active * steps
    log(f"sweep: {len(grids)} grids of {agent_steps // len(grids)} agent-steps in "
        f"{window_s!r} s")
    state["grids"] = grids
    return {
        "window_s": window_s,
        "attempted": len(grids),
        "failed": sum(int(not np.isfinite(m).all()) for _, m in grids),
        "metrics": {"sweep_agent_steps_per_s": agent_steps / window_s},
        "records": {"grids": len(grids), "scan_steps": len(grids) * steps,
                    "programs": ("jit__stream_grid",)},
    }


def release(ctx, state):
    state.pop("call", None)


def sample_cells(traffic: dict, state: dict, seed: int) -> list:
    """(grid, fleet, policy, scenario) indices drawn from ``seed``: each
    policy the reference knows once, the scenarios in a drawn order, so
    that every scenario is among them."""
    policies, scenarios = state["names"]
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(scenarios))
    known = [p for p in policies if p in sweep_oracle.POLICIES]
    count = max(len(known), int(traffic["check"].get("cells", 0)))
    cells = []
    for i in range(count):
        cells.append((int(rng.integers(len(state["grids"]))),
                      int(rng.integers(len(state["fleets"]))),
                      policies.index(known[i % len(known)]),
                      int(perm[i % len(scenarios)])))
    return cells


def reference_metrics(ctx, state, cell, round_to=None) -> np.ndarray:
    tr, cfg = ctx.traffic, ctx.config
    g, fl, p, w = cell
    seed = state["grids"][g][0]
    policies, scenarios = state["names"]
    scn = {s["name"]: s for s in tr["scenarios"]}[scenarios[w]]
    arr = ref_arrivals.arrivals(scn, state["rates"][fl], int(tr["num_steps"]), seed)
    fleet = state["fleets"][fl]
    sim = cfg["sim"]
    traj = sweep_oracle.simulate(
        policies[p], arr, np.asarray(fleet.base_throughput), np.asarray(fleet.min_gpu),
        np.asarray(fleet.priority), g_total=sim["g_total"],
        latency_cap=sim["latency_cap"], ema_alpha=sim["ema_alpha"], round_to=round_to)
    return sweep_oracle.metrics(traj, g_total=sim["g_total"],
                                price_per_hour=sim["price_per_hour"])


def program_metrics(state, cell) -> np.ndarray:
    g, fl, p, w = cell
    m = state["grids"][g][1]
    return np.asarray(m[fl, p, w] if m.ndim == 4 else m[p, w], np.float64)


def gap(got, want, floor: float) -> tuple[float, int]:
    """The widest metric gap: |got - want| over max(|want|, floor), so
    relative for large metrics and absolute for those near zero."""
    err = np.abs(np.asarray(got, np.float64) - want) / np.maximum(np.abs(want), floor)
    err = np.where(np.isfinite(err), err, np.inf)
    i = int(np.argmax(err))
    return float(err[i]), i


def gaps(ctx, state, against=None) -> dict:
    """Widest gap per sampled cell: the program's grids (or ``against``,
    a function of the cell giving another metric vector) vs the float64
    reference."""
    chk = ctx.traffic["check"]
    policies, scenarios = state["names"]
    cells = sample_cells(ctx.traffic, state, seed_words(ctx.seed, 3)[2])
    out = {}
    for cell in cells:
        want = reference_metrics(ctx, state, cell)
        got = program_metrics(state, cell) if against is None else against(cell)
        value, worst = gap(got, want, chk["floor"])
        key = f"{policies[cell[2]]}/{scenarios[cell[3]]}/grid{cell[0]}/fleet{cell[1]}"
        out[key] = value
        log(f"sweep check {key}: widest gap {value!r} "
            f"({sweep_oracle.METRIC_NAMES[worst]}: {got[worst]!r} vs {want[worst]!r})")
    return out


def check(ctx, state, result):
    per_cell = gaps(ctx, state)
    return [("sweep_metric_gap", max(per_cell.values()),
             float(ctx.traffic["check"]["limit"]))]
