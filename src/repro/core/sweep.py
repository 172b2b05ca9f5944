"""Vmapped (fleet | workflow | capacity × policy × workload) sweep grids —
the evaluation surface.

The paper's claim (Table II / Fig. 2) is comparative: adaptive vs baselines
across workloads.  This module evaluates the *entire* policy registry
against a scenario library in ONE jitted call, and — because ``Fleet``,
``Workflow`` and ``CapacityConfig`` are registered pytrees
(``core/agents.py`` / ``core/routing.py`` / ``core/capacity.py``) — scales
that grid along a batched **fleet axis** of heterogeneous fleet sizes, a
batched **workflow axis** of routing topologies, or a batched **capacity
axis** of warm-pool autoscalers:

    sweep(fleet, scenario_library(rates))          ->  SweepResult (P, W)
    sweep_fleets([fleet_4, ..., fleet_256])        ->  SweepResult (F, P, W)
    sweep_workflows(fleet, scenarios=...)          ->  SweepResult (K, P, W)
    sweep_capacity(fleet, scenarios=...)           ->  SweepResult (C, P, W)

``sweep`` nests ``vmap(policy) ∘ vmap(workload)`` over ``simulate_core``;
``sweep_fleets`` pads every fleet to a common width, stacks them
(``stack_fleets``), builds one matched, padded scenario column per fleet
(``fleet_scenario_library``), and adds ``vmap(fleet)`` outermost.
``sweep_workflows`` stacks routing topologies (``stack_workflows``) and
adds ``vmap(workflow)`` outermost — policies are ranked under *inter-agent
dataflow*, not just arrival processes; ``workflow_scenario_library`` builds
the canonical topology set for a fleet width.  ``sweep_capacity`` stacks
autoscaler configs (``stack_capacities``) and adds ``vmap(capacity)``
outermost, so every allocation policy is ranked under every elasticity
regime — the cost column of the grid is per-cell (warm-instance-seconds
billing) and genuinely differs across cells; ``capacity_scenario_library``
builds the canonical capacity set (always-on, reactive with and without
cold starts, scale-to-zero).  Padded slots contribute zero demand, receive
exactly g = 0 from every registered policy, are excluded from all metric
reductions, and receive/forward no routed traffic (``pad_workflow``), so
each row of a batched grid matches its unbatched original within float
tolerance.

Every streaming grid is **device-sharded over a 2D mesh** when more than
one device is live (``core/sharding.py``): the batched sweep axis (fleet |
workflow | capacity) lays out over the mesh's ``data`` axis and the
scenario axis — the largest axis in every paper-style grid — over its
``grid`` axis, via ``shard_map`` with the per-cell streaming scan unchanged
inside the shard body and the arrivals block donated
(``donate_argnums``) so large grids stop double-buffering their biggest
input.  Non-divisible axes are padded with copies of row 0 and stripped on
the host side (never the old silent whole-axis replication), so sharded
metrics are identical to unsharded ones; on a single device every entry
point routes through the plain jit and stays bit-identical to the
unsharded kernel.  ``REPRO_SWEEP_SHARD=0`` forces that single-device path
everywhere (the documented debugging escape hatch), and the trace-based
oracle kernel keeps a ``NamedSharding`` layout hint on the fleet axis.

Per-cell Table II metrics are reduced inside the jit so the host only
materializes a small (…, P, W, M) grid (plus full traces when
``keep_traces=True``).  Adding a policy to the allocator registry or a
scenario to the library grows the grid with no other edits.

**Streaming grid kernel** (the default whenever ``keep_traces=False``):
the policy axis is evaluated *inside* the scan by
``simulator.simulate_stream_core`` — each registered policy dispatched
exactly once per step on its own state row (``alloc.policy_stack``),
instead of the vmapped ``lax.switch`` whose lowering evaluates all P
branches per policy row (P² allocator work per grid) — and the
METRIC_NAMES reductions accumulate in the scan carry, so peak memory per
cell is O(P · N) regardless of the horizon instead of materializing all
eight (S, N) trace leaves.  Pass ``stream=False`` (or ``keep_traces=True``)
to run the trace-based kernel, which is kept as the parity oracle:
streaming metrics match it within float tolerance on all four grid types
(tests/test_streaming.py).  ``return_arrays=True`` on any entry point
skips the host transfer and returns raw device arrays — the benchmark
timing surface.

**Spans** (``core/spans.py``): every entry point runs as four
back-to-back host spans under ``jax.profiler`` — ``repro:sweep.prepare``
(validation, specs, stacking, policy ids, sharded padding),
``repro:sweep.launch`` (the kernel call and the strip slices),
``repro:sweep.wait`` (the device finishing the grid) and
``repro:sweep.collect`` (host copies, ``SweepResult``) — sharing one
``call`` number and carrying the grid's counts.  ``return_arrays=True``
ends in ``launch``: it returns without a sync.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import allocator as alloc
from repro.core import failures as fail_mod
from repro.core import sharding
from repro.core.sharding import grid_mesh  # re-export: the cached 2D mesh
from repro.core import routing
from repro.core import spans
from repro.core import workload
from repro.core.agents import Fleet, stack_fleets
from repro.core.capacity import (
    CapacityConfig,
    capacity_config,
    check_capacity,
    stack_capacities,
)
from repro.core.routing import Workflow, stack_workflows
from repro.core.simulator import (
    METRIC_NAMES,
    SimConfig,
    SimSummary,
    SimTrace,
    resolve_block_size,
    simulate_core,
    simulate_stream_core,
    trace_metrics,
)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named (S, N) arrival matrix; one workload column of the grid."""

    name: str
    arrivals: jnp.ndarray


def scenario_library(
    rates: Sequence[float] | jnp.ndarray,
    num_steps: int = 100,
    seed: int = 0,
) -> tuple[Scenario, ...]:
    """The standard 8-scenario library over one base rate vector.

    Covers the paper's workloads (constant = Table II, overload / spike /
    dominated = §V-B) plus the beyond-paper diurnal, bursty (per-agent MMPP)
    and correlated (fleet-wide surge) processes.  Stochastic scenarios are
    keyed off ``seed`` and fully reproducible.
    """
    rates = jnp.asarray(rates, jnp.float32)
    n = int(rates.shape[0])
    k_poisson, k_bursty, k_corr = jax.random.split(jax.random.key(seed), 3)
    return (
        Scenario("constant", workload.constant(rates, num_steps)),
        Scenario("poisson", workload.poisson(rates, num_steps, k_poisson)),
        Scenario(
            "spike",
            workload.spike(
                rates, num_steps,
                spike_agent=n - 1,
                spike_start=num_steps // 2,
                spike_len=max(num_steps // 10, 1),
            ),
        ),
        Scenario("overload_3x", workload.scaled(rates, num_steps, 3.0)),
        Scenario("dominated", workload.dominated(rates, num_steps, agent=0, share=0.9)),
        Scenario("diurnal", workload.diurnal(rates, num_steps)),
        Scenario("bursty", workload.bursty(rates, num_steps, k_bursty)),
        Scenario("correlated", workload.correlated(rates, num_steps, k_corr)),
    )


def fleet_scenario_library(
    rate_vectors: Sequence[Sequence[float] | jnp.ndarray],
    n_max: int,
    num_steps: int = 100,
    seed: int = 0,
) -> tuple[tuple[str, ...], jnp.ndarray]:
    """Matched per-fleet scenario columns, padded to a common agent width.

    Each rate vector gets the standard library generated *at its own size*
    (so stochastic draws match what the unbatched ``scenario_library`` would
    produce for that fleet) and is then zero-padded to ``n_max`` agents.
    Returns ``(scenario_names, arrivals)`` with arrivals of shape
    (F, W, S, n_max) — the workload block of one batched fleet sweep.
    """
    names: tuple[str, ...] | None = None
    blocks = []
    for rates in rate_vectors:
        lib = scenario_library(rates, num_steps, seed)
        lib_names = tuple(s.name for s in lib)
        if names is None:
            names = lib_names
        elif names != lib_names:
            raise ValueError("scenario libraries diverged across fleets")
        stacked = np.stack([np.asarray(s.arrivals, np.float32) for s in lib])
        pad = n_max - stacked.shape[-1]
        if pad < 0:
            raise ValueError(
                f"rate vector wider ({stacked.shape[-1]}) than n_max={n_max}"
            )
        blocks.append(np.pad(stacked, ((0, 0), (0, 0), (0, pad))))
    return names, jnp.asarray(np.stack(blocks))


@dataclasses.dataclass(frozen=True)
class SweepSummary:
    """Flat Table-II-style rows, one per (fleet,) policy, scenario cell."""

    columns: tuple[str, ...]
    rows: tuple[tuple, ...]

    def to_csv_lines(self) -> list[str]:
        out = [",".join(self.columns)]
        for row in self.rows:
            out.append(",".join(
                f"{v:.4f}" if isinstance(v, float) else str(v) for v in row
            ))
        return out

    def best(self, metric: str = "avg_latency", minimize: bool = True) -> dict[str, str]:
        """Winning policy per scenario (per fleet/workflow/capacity and
        scenario when the table has a leading batch axis) under one metric.

        Comparisons are strict, so exact ties are stable: the first row in
        table order (= policy-registry order) keeps the win in both the
        minimize and maximize directions.
        """
        mi = self.columns.index(metric)
        si = self.columns.index("scenario")
        pi = self.columns.index("policy")
        fi = next(
            (self.columns.index(c)
             for c in ("fleet", "workflow", "capacity", "failure")
             if c in self.columns),
            None,
        )
        winners: dict[str, tuple[str, float]] = {}
        for row in self.rows:
            key = row[si] if fi is None else f"{row[fi]}/{row[si]}"
            val = row[mi]
            if key not in winners:
                winners[key] = (row[pi], val)
                continue
            better = val < winners[key][1] if minimize else val > winners[key][1]
            if better:
                winners[key] = (row[pi], val)
        return {key: pol for key, (pol, _) in winners.items()}


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Raw grids from one sweep; axes are ([fleet | workflow | capacity,]
    policy, scenario[, agent]).

    ``fleet_names`` / ``workflow_names`` / ``capacity_names`` are None for a
    plain 2-axis ``sweep``; when one is set (the ``sweep_fleets`` /
    ``sweep_workflows`` / ``sweep_capacity`` paths) every grid carries that
    leading batch axis.  Cost is a per-cell metric (``metrics[...,
    METRIC_NAMES.index("cost")]``, warm-instance-seconds billing) — it is
    only constant across cells under an always-on capacity pool.
    """

    policy_names: tuple[str, ...]
    scenario_names: tuple[str, ...]
    metrics: np.ndarray               # ([F|K|C,] P, W, len(METRIC_NAMES)) float32
    per_agent_latency: np.ndarray     # ([F|K|C,] P, W, N)
    per_agent_throughput: np.ndarray  # ([F|K|C,] P, W, N)
    config: SimConfig
    traces: SimTrace | None = None    # leaves ([F|K|C,] P, W, S, N) when kept
    fleet_names: tuple[str, ...] | None = None
    workflow_names: tuple[str, ...] | None = None
    capacity_names: tuple[str, ...] | None = None
    per_agent_queue: np.ndarray | None = None  # ([F|K|C,] P, W, N) per-stage backlog
    failure_names: tuple[str, ...] | None = None

    def _leading_axis(self) -> tuple[str, tuple[str, ...]] | None:
        if self.fleet_names is not None:
            return "fleet", self.fleet_names
        if self.workflow_names is not None:
            return "workflow", self.workflow_names
        if self.capacity_names is not None:
            return "capacity", self.capacity_names
        if self.failure_names is not None:
            return "failure", self.failure_names
        return None

    def metric(self, name: str) -> np.ndarray:
        return self.metrics[..., METRIC_NAMES.index(name)]

    def _cell_index(
        self,
        policy: str,
        scenario: str,
        fleet: str | None,
        workflow: str | None = None,
        capacity: str | None = None,
        failure: str | None = None,
    ):
        p = self.policy_names.index(policy)
        w = self.scenario_names.index(scenario)
        lead = self._leading_axis()
        picked = {"fleet": fleet, "workflow": workflow, "capacity": capacity,
                  "failure": failure}
        if lead is None:
            bad = [k for k, v in picked.items() if v is not None]
            if bad:
                raise ValueError(f"this sweep has no {bad[0]} axis")
            return (p, w)
        axis, names = lead
        if picked[axis] is None:
            raise ValueError(f"{axis} axis present; pick one of {names}")
        for other in picked:
            if other != axis and picked[other] is not None:
                raise ValueError(f"this sweep has no {other} axis")
        return (names.index(picked[axis]), p, w)

    def summary(
        self,
        policy: str,
        scenario: str,
        fleet: str | None = None,
        workflow: str | None = None,
        capacity: str | None = None,
        failure: str | None = None,
    ) -> SimSummary:
        """One cell as a ``SimSummary`` — same fields as ``run_policy``."""
        idx = self._cell_index(
            policy, scenario, fleet, workflow, capacity, failure
        )
        m = dict(zip(METRIC_NAMES, (float(x) for x in self.metrics[idx])))
        per_queue = (
            () if self.per_agent_queue is None else self.per_agent_queue[idx]
        )
        return SimSummary.from_metrics(
            policy, m, self.per_agent_latency[idx],
            self.per_agent_throughput[idx], per_queue,
        )

    def table(self) -> SweepSummary:
        base = ("policy", "scenario") + METRIC_NAMES
        # One loop serves all shapes: an unbatched grid is a single
        # anonymous leading slot whose prefix column is dropped.
        lead = self._leading_axis()
        lead_names = (None,) if lead is None else lead[1]
        rows = []
        for f, fl in enumerate(lead_names):
            grid = self.metrics if lead is None else self.metrics[f]
            for p, pol in enumerate(self.policy_names):
                for w, scen in enumerate(self.scenario_names):
                    prefix = (pol, scen) if lead is None else (fl, pol, scen)
                    rows.append(prefix + tuple(float(x) for x in grid[p, w]))
        columns = base if lead is None else ((lead[0],) + base)
        return SweepSummary(columns=columns, rows=tuple(rows))


@functools.partial(
    jax.jit, static_argnames=("config", "reg_names", "keep_traces", "batch_axis")
)
def _grid_jit(
    pids: jnp.ndarray,
    arrivals: jnp.ndarray,   # (W, S, N), or (F, W, S, N) when batch_axis="fleet"
    fleet: Fleet,            # leaves (N,), or (F, N) when batch_axis="fleet"
    workflow: Workflow | None,  # leaves (K, N, N)/(K, N) when batch_axis="workflow"
    capacity: CapacityConfig | None,  # leaves (C,) when batch_axis="capacity"
    fspec,                   # FailureSpec | None; leaves (B,) when batch_axis="failure"
    config: SimConfig,
    reg_names: tuple,
    keep_traces: bool,
    batch_axis: str | None,
):
    """The trace-based (policy × scenario) grid kernel — the parity oracle.

    Materializes a full ``SimTrace`` per cell (and vmaps the policy axis, so
    the per-step ``lax.switch`` lowers to evaluate-all-branches: P² policy
    evaluations per grid).  ``keep_traces=True`` sweeps and
    ``stream=False`` parity checks run here; the streaming kernel
    (``_stream_grid_jit``) is the default hot path.

    ``batch_axis`` picks the outermost vmapped dimension: None (plain
    ``sweep``), "fleet" (batched fleet leaves + matched per-fleet arrival
    columns), "workflow" (batched routing topologies over one shared
    scenario block), "capacity" (batched warm-pool autoscaler configs), or
    "failure" (stacked chaos scenarios over one shared workload block).
    """

    def cell(fl, wf, cp, fs, pid, arr):
        trace = simulate_core(
            pid, arr, fl, config, reg_names, wf, cp, failures=fs
        )
        vec, per_lat, per_tput, per_q = trace_metrics(
            trace, fl.active, wf, config=config
        )
        if keep_traces:
            return vec, per_lat, per_tput, per_q, trace
        return vec, per_lat, per_tput, per_q

    over_scen = jax.vmap(cell, in_axes=(None, None, None, None, None, 0))
    over_pol = jax.vmap(over_scen, in_axes=(None, None, None, None, 0, None))
    if batch_axis is None:
        return over_pol(fleet, workflow, capacity, fspec, pids, arrivals)
    outer_axes = {
        "fleet": (0, None, None, None, None, 0),
        "workflow": (None, 0, None, None, None, None),
        "capacity": (None, None, 0, None, None, None),
        "failure": (None, None, None, 0, None, None),
    }[batch_axis]
    return jax.vmap(over_pol, in_axes=outer_axes)(
        fleet, workflow, capacity, fspec, pids, arrivals
    )


def _stream_grid(
    arrivals: jnp.ndarray | None,  # (W, S, N), or (F, W, S, N) when batch_axis="fleet"
    fleet: Fleet,            # leaves (N,), or (F, N) when batch_axis="fleet"
    workflow: Workflow | None,  # leaves (K, N, N)/(K, N) when batch_axis="workflow"
    capacity: CapacityConfig | None,  # leaves (C,) when batch_axis="capacity"
    wspec=None,              # stacked WorkloadSpec, leaves (W, ·)/(F, W, ·)
    fspec=None,              # FailureSpec | None; leaves (B,) when batch_axis="failure"
    config: SimConfig = None,
    names: tuple = (),
    batch_axis: str | None = None,
    num_policy_blocks: int = 1,
    block_size: int = 1,
):
    """The streaming (policy × scenario) grid kernel — the default for
    ``keep_traces=False`` sweeps.

    Each cell runs ``simulate_stream_core``: the whole policy axis in ONE
    scan (O(P) dispatch via the unrolled ``alloc.policy_stack`` instead of
    the vmapped ``lax.switch``'s P² evaluate-all-branches lowering) with
    metrics accumulated in the carry (peak memory per cell O(P · N), not
    O(P · S · N)).  Only the scenario axis — and the optional outer
    fleet/workflow/capacity axis — is vmapped.  ``_grid_jit`` remains the
    trace-materializing parity oracle.

    The workload column is EITHER a materialized arrivals tensor OR a
    stacked ``WorkloadSpec`` (``wspec``), never both: with a spec each
    cell's arrival rows are synthesized *inside* the scan
    (``workload_step``), so nothing of shape (S, ·) exists on the input
    side either.  With ``num_policy_blocks`` > 1 the kernel runs under the
    3D mesh and computes only this device's policy block, selected by
    ``lax.axis_index("policy")`` (``allocator.policy_stack_blocks``).

    Every scenario column runs in the same scan: synthesis dispatches the
    generator through ``workload_step``'s ``lax.switch``, which under
    ``vmap`` evaluates every registered generator for every column.  That
    costs about 42 µs per step at N=1024 on a TPU v5e (85 µs of synthesis
    against 44 when each column ran only its own generator).  The
    alternative, one vmap per generator group, put one horizon loop per
    group into the program (six for the 8-scenario library), each running
    the whole policy stack every step at a sixth of the batch width.  On
    one TPU v5e, 9 policies × 8 scenarios, one scan took 203 µs per step
    against 568 for the grouped loops at N=1024 (4 fleets), and 58 against
    223 at N=4.

    This function is deliberately unjitted: ``_stream_grid_jit`` wraps it
    for the single-device path and ``_stream_grid_sharded`` runs the exact
    same body per device block under ``shard_map`` — one kernel, two
    placements, one dispatch, no way for the sharded math to drift.
    """
    block = (
        jax.lax.axis_index(sharding.POLICY_AXIS)
        if num_policy_blocks > 1 else None
    )

    def cell(arr, fl, wf, cp, sp, fs):
        return simulate_stream_core(
            arr, fl, config, names, wf, cp, workload_spec=sp,
            num_policy_blocks=num_policy_blocks, policy_block=block,
            block_size=block_size, failures=fs,
        )

    a_ax = None if arrivals is None else 0
    s_ax = None if wspec is None else 0

    # out_axes=1: the per-cell policy axis stays leading, scenarios second,
    # matching the trace kernel's (…, P, W, ·) layout.
    over_scen = jax.vmap(
        cell, in_axes=(a_ax, None, None, None, s_ax, None), out_axes=1
    )

    if batch_axis is None:
        return over_scen(arrivals, fleet, workflow, capacity, wspec, fspec)
    outer_axes = {
        "fleet": (a_ax, 0, None, None, s_ax, None),
        "workflow": (None, None, 0, None, None, None),
        "capacity": (None, None, None, 0, None, None),
        "failure": (None, None, None, None, None, 0),
    }[batch_axis]
    return jax.vmap(over_scen, in_axes=outer_axes)(
        arrivals, fleet, workflow, capacity, wspec, fspec
    )


_stream_grid_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "config", "names", "batch_axis", "num_policy_blocks", "block_size",
    ),
)(_stream_grid)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "config", "names", "batch_axis", "num_policy_blocks",
        "block_size",
    ),
    donate_argnums=(0,),
)
def _stream_grid_sharded(
    arrivals: jnp.ndarray | None,
    fleet: Fleet,
    workflow: Workflow | None,
    capacity: CapacityConfig | None,
    wspec,
    fspec,
    mesh: jax.sharding.Mesh,
    config: SimConfig,
    names: tuple,
    batch_axis: str | None,
    num_policy_blocks: int = 1,
    block_size: int = 1,
):
    """The sharded streaming grid: ``shard_map`` of ``_stream_grid`` over
    the ``("data", "grid", "policy")`` mesh.

    Each device runs the unchanged per-cell streaming scan on its
    (batch-block × scenario-block) of the grid — cells are independent, so
    no collectives appear anywhere in the body.  ``arrivals`` (the grid's
    dominant input, (F, W, S, N) floats) is **donated**: XLA may reuse its
    buffer for outputs/scratch instead of double-buffering million-cell
    grids.  Callers must therefore pass a freshly built (or freshly
    padded) array and never reuse it afterwards — every sweep entry point
    rebuilds arrivals per call, which is what keeps second calls safe
    (tests/test_sharding.py).  A synthesized grid (``wspec`` instead of
    ``arrivals``) has no slab to donate — its dominant input is O(W · N).

    With ``num_policy_blocks`` > 1 the policy dim of every output shards
    over the mesh's third axis: each device evaluates only its own block
    of policy rows (inputs stay replicated along ``policy`` — every block
    reads the same state).  The default ``dp=1`` path never consults the
    axis, so it lowers to the exact 2D program.

    Axes must already divide the mesh (``_run_grid`` pads them); specs are
    built in ``core/sharding.py::grid_specs``.
    """
    in_specs, out_spec = sharding.grid_specs(
        batch_axis, policy=num_policy_blocks > 1
    )
    body = functools.partial(
        _stream_grid, config=config, names=names, batch_axis=batch_axis,
        num_policy_blocks=num_policy_blocks, block_size=block_size,
    )
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        check_vma=False,
    )(arrivals, fleet, workflow, capacity, wspec, fspec)


def _run_stream_sharded(
    arrivals: jnp.ndarray | None,
    fleet: Fleet,
    workflow: Workflow | None,
    capacity: CapacityConfig | None,
    config: SimConfig,
    names: tuple,
    batch_axis: str | None,
    wspec=None,
    fspec=None,
    policy_devices: int = 1,
    block_size: int = 1,
    phases: spans.Phases | None = None,
):
    """Pad the sharded axes to mesh divisibility, run the sharded streaming
    kernel, strip the padding host-side.

    Padding repeats row 0 (always-valid cells — the ``active``-mask idiom
    of inert-but-well-posed filler) instead of falling back to whole-axis
    replication, so a non-divisible axis costs at most ``mesh_dim - 1``
    wasted rows rather than ``device_count - 1`` redundant copies of the
    entire grid.  The stripped results are identical to the unpadded grid
    because cells never interact.  A stacked ``WorkloadSpec`` pads exactly
    like the arrivals tensor it replaces (same leading axes, O(N) rows).
    With ``policy_devices`` (dp) > 1 the *name list* pads the same way —
    repeating ``names[0]`` up to divisibility, stripped from the output's
    policy dim — and the kernel dispatches per-device policy blocks.
    """
    dp = int(policy_devices)
    mesh = sharding.grid_mesh(policy_devices=dp)
    dd = mesh.shape[sharding.DATA_AXIS]
    dg = mesh.shape[sharding.GRID_AXIS]
    p = len(names)
    if dp > 1:
        names = tuple(names) + (names[0],) * ((-p) % dp)

    def pad(axis_mults):
        nonlocal arrivals, wspec
        for axis, mult in axis_mults:
            if arrivals is not None:
                arrivals = sharding.pad_axis(arrivals, axis, mult)
            else:
                wspec = sharding.pad_tree_axis(wspec, axis, mult)

    if batch_axis is None:
        w = arrivals.shape[0] if wspec is None else wspec.gen_id.shape[0]
        pad([(0, dd * dg)])
    elif batch_axis == "fleet":
        b, w = (
            arrivals.shape[:2] if wspec is None else wspec.gen_id.shape[:2]
        )
        pad([(0, dd), (1, dg)])
        fleet = sharding.pad_tree_axis(fleet, 0, dd)
    elif batch_axis == "workflow":
        b = workflow.route.shape[0]
        w = arrivals.shape[0] if wspec is None else wspec.gen_id.shape[0]
        pad([(0, dg)])
        workflow = sharding.pad_tree_axis(workflow, 0, dd)
    elif batch_axis == "failure":
        b = fspec.revoke_frac.shape[0]
        w = arrivals.shape[0] if wspec is None else wspec.gen_id.shape[0]
        pad([(0, dg)])
        fspec = sharding.pad_tree_axis(fspec, 0, dd)
    else:
        b = capacity.policy_id.shape[0]
        w = arrivals.shape[0] if wspec is None else wspec.gen_id.shape[0]
        pad([(0, dg)])
        capacity = sharding.pad_tree_axis(capacity, 0, dd)
    keep = (() if batch_axis is None else (slice(b),)) + (slice(p), slice(w))
    _launch(phases)
    out = _stream_grid_sharded(
        arrivals, fleet, workflow, capacity, wspec, fspec, mesh, config,
        names, batch_axis, dp, block_size,
    )
    return tuple(x[keep] for x in out)


def _launch(phases: spans.Phases | None) -> None:
    """Mark the enqueue of the grid program: the end of ``prepare``."""
    if phases is not None:
        phases.enter("launch")


def _grid_counts(arrivals, fleet, workflow, capacity, wspec, fspec, names,
                 batch_axis) -> dict:
    """The grid's size, recorded on the call's spans."""
    batch = 1
    if batch_axis is not None:
        stacked = {"fleet": fleet, "workflow": workflow, "capacity": capacity,
                   "failure": fspec}[batch_axis]
        batch = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if wspec is None:
        scenarios, steps = arrivals.shape[-3], arrivals.shape[-2]
    else:
        scenarios, steps = wspec.gen_id.shape[-1], wspec.num_steps
    return {"batch": int(batch), "policies": len(names),
            "scenarios": int(scenarios), "steps": int(steps),
            "agents": int(fleet.active.shape[-1])}


def _run_grid(
    pids: jnp.ndarray,
    arrivals: jnp.ndarray | None,
    fleet: Fleet,
    workflow: Workflow | None,
    capacity: CapacityConfig | None,
    config: SimConfig,
    reg_names: tuple,
    names: tuple,
    keep_traces: bool,
    stream: bool | None,
    batch_axis: str | None,
    shard: bool | None = None,
    wspec=None,
    fspec=None,
    block_size: int | None = None,
    phases: spans.Phases | None = None,
):
    """Pick the kernel and placement for one sweep call: streaming by
    default — sharded over the ``("data", "grid", "policy")`` mesh whenever
    more than one device is live (``sharding.should_shard``; the policy
    axis only splits when requested, ``sharding.policy_mesh_devices``) —
    and the trace-based oracle when traces are requested or
    ``stream=False``.  The workload column arrives EITHER materialized
    (``arrivals``) or as a stacked ``WorkloadSpec`` (``wspec``) for in-scan
    synthesis; the entry points materialize specs host-side before any
    non-streaming call, so the trace oracle only ever sees tensors.

    Returns the kernel's device-array tuple — (metrics, per-lat, per-tput,
    per-queue[, traces]).  ``phases``, the entry point's spans, records
    the grid's counts and moves from ``prepare`` to ``launch`` just before
    the kernel is called.
    """
    streamed = (not keep_traces) if stream is None else bool(stream)
    if streamed and keep_traces:
        raise ValueError(
            "streaming mode accumulates metrics in O(1) memory per step and "
            "never materializes traces; use keep_traces=True with "
            "stream=False (or leave stream unset)"
        )
    if wspec is not None and not streamed:
        raise ValueError(
            "in-scan synthesis runs inside the streaming kernel; "
            "materialize the specs for the trace oracle"
        )
    if phases is not None:
        phases.annotate(**_grid_counts(arrivals, fleet, workflow, capacity,
                                       wspec, fspec, names, batch_axis))
    sharded = sharding.should_shard(shard)
    if streamed:
        # Resolved here — before any jit boundary — so the env default is
        # read exactly once per call and B enters the kernels static.
        bsz = resolve_block_size(block_size)
        if sharded:
            return _run_stream_sharded(
                arrivals, fleet, workflow, capacity, config, names,
                batch_axis, wspec=wspec, fspec=fspec,
                policy_devices=sharding.policy_mesh_devices(shard),
                block_size=bsz, phases=phases,
            )
        _launch(phases)
        return _stream_grid_jit(
            arrivals, fleet, workflow, capacity, wspec, fspec, config,
            names, batch_axis, block_size=bsz,
        )
    if sharded and batch_axis == "fleet":
        # The parity oracle keeps the pre-shard_map layout-hint path: pad
        # the fleet axis to device divisibility (never replicate — the old
        # fallback burned device_count× redundant work), lay it across the
        # flattened mesh, and strip the padded rows from every output
        # (traces included) host-side.
        f = arrivals.shape[0]
        fleet, arrivals = _shard_fleet_axis(fleet, arrivals)
        _launch(phases)
        out = _grid_jit(
            pids, arrivals, fleet, workflow, capacity, fspec, config,
            reg_names, keep_traces, batch_axis,
        )
        return tuple(
            jax.tree_util.tree_map(lambda x: x[:f], o) for o in out
        )
    _launch(phases)
    return _grid_jit(
        pids, arrivals, fleet, workflow, capacity, fspec, config, reg_names,
        keep_traces, batch_axis,
    )


def _shard_fleet_axis(stacked: Fleet, arrivals: jnp.ndarray, mesh=None):
    """Lay the fleet axis of the trace-oracle grid across every device.

    A ``NamedSharding`` layout *hint* (GSPMD propagates it through the
    vmapped kernel) over the flattened 2D mesh.  A fleet count that does
    not divide the device count is **padded** to the next multiple with
    copies of fleet 0 — the old whole-axis replication fallback silently
    forfeited all parallelism (6 fleets on 4 devices ran every cell on
    every device); padded rows cost at most ``device_count - 1`` wasted
    fleets and are stripped by ``_run_grid``, keeping metrics identical.
    """
    mesh = sharding.grid_mesh() if mesh is None else mesh
    total = int(np.prod(list(mesh.shape.values())))
    stacked = sharding.pad_tree_axis(stacked, 0, total)
    arrivals = sharding.pad_axis(arrivals, 0, total)
    spec = PartitionSpec((sharding.DATA_AXIS, sharding.GRID_AXIS))
    layout = NamedSharding(mesh, spec)
    return jax.device_put(stacked, layout), jax.device_put(arrivals, layout)


def _prepare_scenarios(
    scenarios, synthesize: bool | None, streamed: bool
) -> tuple[tuple[str, ...], jnp.ndarray | None, "workload.WorkloadSpec | None"]:
    """Resolve one sweep call's workload column: (names, arrivals, wspec).

    ``scenarios`` is a homogeneous list of either ``Scenario`` tensors (the
    classic path — ``synthesize`` must stay unset/False) or
    ``workload.WorkloadSpec`` rows.  Specs run **in-scan** (``wspec``
    returned, ``arrivals=None``) when synthesis is on — the default for
    specs — AND the call streams AND the ``REPRO_SWEEP_SYNTH`` hatch is not
    "0"; otherwise they are materialized host-side via
    ``workload.materialize``, which scans the very same registered step
    functions, so both arms are bit-for-bit identical by construction
    (the acceptance contract, tests/test_workload_synthesis.py).
    """
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("need at least one scenario")
    spec_like = [isinstance(s, workload.WorkloadSpec) for s in scenarios]
    names = tuple(s.name for s in scenarios)
    if any(spec_like):
        if not all(spec_like):
            raise ValueError(
                "scenarios must be all Scenario or all WorkloadSpec, not a mix"
            )
        synth = True if synthesize is None else bool(synthesize)
        if synth and streamed and workload.synth_env_enabled():
            return names, None, workload.stack_specs(scenarios)
        return names, jnp.stack(
            [workload.materialize(s) for s in scenarios]
        ), None
    if synthesize:
        raise ValueError(
            "synthesize=True needs WorkloadSpec scenarios "
            "(e.g. workload.scenario_specs); got materialized Scenario tensors"
        )
    return names, jnp.stack(
        [jnp.asarray(s.arrivals, jnp.float32) for s in scenarios]
    ), None


def _streamed(keep_traces: bool, stream: bool | None) -> bool:
    return (not keep_traces) if stream is None else bool(stream)


def _resolve_failure_axis(failures, allow_batch: bool):
    """Resolve one sweep call's ``failures=`` argument.

    Returns ``(fspec, failure_names)``: a single validated spec (or None)
    with no axis, or — on the plain ``sweep`` only (``allow_batch``) — a
    stacked spec plus its scenario names, the vmapped chaos axis.  The
    ``REPRO_FAILURES=0`` kill switch applies before anything else.
    """
    if isinstance(failures, fail_mod.FailureSpec) or failures is None:
        failures = fail_mod.resolve_failures(failures)
        if failures is None:
            return None, None
        if failures.batched:
            raise ValueError(
                "pass a sequence of FailureSpec rows (not a pre-stacked "
                "spec) to put failures on the sweep axis"
            )
        fail_mod.check_failures(failures)
        return failures, None
    specs = list(failures)
    if not allow_batch:
        raise ValueError(
            "only the plain sweep() supports a failure axis; "
            "sweep_fleets/sweep_workflows/sweep_capacity already batch "
            "their own axis — pass a single FailureSpec"
        )
    if not specs:
        raise ValueError("need at least one failure scenario")
    for s in specs:
        fail_mod.check_failures(s)
    if not fail_mod.failures_env_enabled():
        return None, None
    return fail_mod.stack_failures(specs), tuple(s.name for s in specs)


def _collect(phases: spans.Phases, out: tuple, keep_traces: bool,
             return_arrays: bool, **fields) -> SweepResult | tuple:
    """The end of every entry point: with ``return_arrays`` the kernel's
    device arrays, unsynced; otherwise ``wait`` for the grid, then
    ``collect`` it to the host as a ``SweepResult`` with ``fields``."""
    if return_arrays:
        return out
    phases.enter("wait")
    jax.block_until_ready(out)
    phases.enter("collect")
    metrics, per_lat, per_tput, per_q = (np.asarray(x) for x in out[:4])
    return SweepResult(
        metrics=metrics,
        per_agent_latency=per_lat,
        per_agent_throughput=per_tput,
        per_agent_queue=per_q,
        traces=out[4] if keep_traces else None,
        **fields,
    )


def sweep(
    fleet: Fleet,
    scenarios: Sequence[Scenario],
    config: SimConfig = SimConfig(),
    policies: Sequence[str] | None = None,
    keep_traces: bool = False,
    capacity: CapacityConfig | None = None,
    stream: bool | None = None,
    return_arrays: bool = False,
    shard: bool | None = None,
    synthesize: bool | None = None,
    block_size: int | None = None,
    failures=None,
) -> SweepResult | tuple:
    """Evaluate ``policies`` (default: the whole registry) × ``scenarios``.

    All scenarios must share one (S, N) shape.  The grid is a single jitted
    call (cached across calls with the same fleet structure/config/
    registry): by default the **streaming kernel** (``_stream_grid_jit`` —
    O(P) policy dispatch, metrics accumulated in the scan carry so peak
    memory per cell never grows with the horizon); ``keep_traces=True`` or
    ``stream=False`` selects the trace-materializing oracle kernel.  An
    optional ``capacity`` autoscaler applies to every cell; cost is a
    per-cell metric either way.  ``return_arrays=True`` skips the host
    transfer and returns the kernel's raw device arrays — the benchmark
    timing surface (``jax.block_until_ready`` them to time device work).
    On a multi-device host the scenario axis of the streaming grid shards
    over the full (data × grid) mesh plane (``core/sharding.py``);
    ``shard=False`` — or ``REPRO_SWEEP_SHARD=0`` in the environment —
    forces the single-device path, and ``shard="3d"`` additionally splits
    the policy axis over the mesh's third dimension.

    ``scenarios`` may be ``workload.WorkloadSpec`` rows instead of
    materialized ``Scenario`` tensors: by default (``synthesize=None`` or
    ``True``) their arrival rows are then synthesized *inside* the scan —
    the input side never materializes an (S, N) slab, making S = 10⁶⁺
    horizons feasible.  ``synthesize=False`` (or ``REPRO_SWEEP_SYNTH=0``,
    or any trace-oracle run) materializes the same specs host-side via the
    same registered step functions — bit-for-bit identical results, the
    synthesis parity oracle.

    ``block_size`` (or ``REPRO_SWEEP_BLOCK``; default 1) sets the
    streaming kernel's time-block B: the scan walks the horizon in
    B-step blocks — one workload dispatch and one capped-unroll inner
    scan per block — identical results at every B, trading one-time
    compile cost for steady-state throughput (see
    ``simulate_stream_core``).  The same knob threads through every
    sweep entry point, sharded or not.

    ``failures`` injects chaos (``core/failures.py``): a single
    ``FailureSpec`` applies to every cell, while a *sequence* of specs
    (e.g. ``failure_scenario_library()``) becomes a vmapped **failure
    axis** — the grid grows a leading chaos dimension exactly like the
    fleet/workflow/capacity axes of the other entry points, and the
    result carries ``failure_names``.  ``failures=None`` is bit-for-bit
    the pre-failure program; ``REPRO_FAILURES=0`` forces that path.
    """
    with spans.Phases("sweep", "prepare", entry="sweep") as ph:
        fleet.validate()
        if capacity is not None:
            check_capacity(capacity, config.g_total, config.num_gpus)
        fspec, failure_names = _resolve_failure_axis(failures, allow_batch=True)
        batch_axis = None if failure_names is None else "failure"
        reg_names = alloc.policy_names()
        names = reg_names if policies is None else tuple(policies)
        pids = jnp.asarray([alloc.policy_id(p) for p in names])
        scen_names, arrivals, wspec = _prepare_scenarios(
            scenarios, synthesize, _streamed(keep_traces, stream)
        )  # (W, S, N) | stacked spec

        out = _run_grid(
            pids, arrivals, fleet, None, capacity, config, reg_names, names,
            keep_traces, stream, batch_axis, shard, wspec=wspec, fspec=fspec,
            block_size=block_size, phases=ph,
        )
        return _collect(
            ph, out, keep_traces, return_arrays,
            policy_names=names,
            scenario_names=scen_names,
            config=config,
            failure_names=failure_names,
        )


def sweep_fleets(
    fleets: Sequence[Fleet],
    rate_vectors: Sequence[Sequence[float] | jnp.ndarray] | None = None,
    num_steps: int = 100,
    seed: int = 0,
    config: SimConfig = SimConfig(),
    policies: Sequence[str] | None = None,
    fleet_names: Sequence[str] | None = None,
    keep_traces: bool = False,
    shard: bool | None = True,
    stream: bool | None = None,
    return_arrays: bool = False,
    synthesize: bool | None = None,
    block_size: int | None = None,
    failures=None,
) -> SweepResult | tuple:
    """One jitted (fleet × policy × scenario) grid over heterogeneous fleets.

    Fleets are padded to the widest member and stacked into a single batched
    ``Fleet`` pytree; each fleet gets a matched scenario column generated at
    its true size from its own rate vector (default:
    ``workload.synthetic_rates`` at the paper's aggregate load, so total
    demand is held constant while the agent count scales).  With
    ``shard=True`` (the default) a multi-device host lays the fleet axis
    over the 2D mesh's ``data`` axis and the scenario axis over its
    ``grid`` axis via ``shard_map`` (trace-oracle runs keep a
    ``NamedSharding`` hint on the fleet axis); non-divisible axes are
    padded, never replicated, and single-device metrics are bit-identical
    to the unsharded kernel.  ``shard=False`` or ``REPRO_SWEEP_SHARD=0``
    forces the single-device path.  The per-fleet rows match the unbatched
    ``sweep`` within float tolerance.  The streaming kernel (default for
    ``keep_traces=False``) is what makes the long-horizon end of this grid
    feasible at all: peak memory per cell is O(N), not O(S · N), so
    N = 1024 fleets over 10⁴-step horizons fit on a single host.

    ``synthesize`` selects the workload column's representation:
    ``None`` (default) keeps the legacy materialized
    ``fleet_scenario_library`` tensors; ``True`` builds the matched
    per-fleet ``workload.fleet_scenario_specs`` and synthesizes arrivals
    *in-scan* (no (F, W, S, N) slab is ever built — the horizon-frontier
    mode); ``False`` materializes those same specs host-side (the
    synthesis parity arm, bit-identical to ``True`` by construction).
    """
    with spans.Phases("sweep", "prepare", entry="sweep_fleets") as ph:
        fleets = list(fleets)
        if not fleets:
            raise ValueError("sweep_fleets needs at least one fleet")
        for f in fleets:
            f.validate()
        if rate_vectors is None:
            rate_vectors = [
                workload.synthetic_rates(f.num_agents, seed=seed + i)
                for i, f in enumerate(fleets)
            ]
        if len(rate_vectors) != len(fleets):
            raise ValueError("need one rate vector per fleet")
        for i, (f, r) in enumerate(zip(fleets, rate_vectors)):
            width = np.asarray(r).shape[-1]
            if width != f.num_agents:
                raise ValueError(
                    f"rate vector {i} has {width} agents but fleet {i} has "
                    f"{f.num_agents}; a mismatch would silently zero real demand"
                )
        if fleet_names is None:
            fleet_names = tuple(
                f"fleet{i}_n{f.num_agents}" for i, f in enumerate(fleets)
            )
        else:
            fleet_names = tuple(fleet_names)

        stacked = stack_fleets(fleets)
        wspec = None
        if synthesize is None:
            scen_names, arrivals = fleet_scenario_library(
                rate_vectors, stacked.num_agents, num_steps, seed
            )  # (F, W, S, N_max)
        else:
            scen_names, spec_rows = workload.fleet_scenario_specs(
                rate_vectors, stacked.num_agents, num_steps, seed
            )
            cols = [
                workload.stack_specs(row, name=f"fleet{i}")
                for i, row in enumerate(spec_rows)
            ]
            if (synthesize and _streamed(keep_traces, stream)
                    and workload.synth_env_enabled()):
                arrivals = None
                wspec = workload.stack_specs(cols, name="fleet_grid")
            else:
                arrivals = jnp.stack([
                    jnp.stack([workload.materialize(s) for s in row])
                    for row in spec_rows
                ])  # the parity arm: same step functions, host-scanned

        fspec, _ = _resolve_failure_axis(failures, allow_batch=False)
        reg_names = alloc.policy_names()
        names = reg_names if policies is None else tuple(policies)
        pids = jnp.asarray([alloc.policy_id(p) for p in names])

        out = _run_grid(
            pids, arrivals, stacked, None, None, config, reg_names, names,
            keep_traces, stream, "fleet", shard, wspec=wspec, fspec=fspec,
            block_size=block_size, phases=ph,
        )
        return _collect(
            ph, out, keep_traces, return_arrays,
            policy_names=names,
            scenario_names=scen_names,
            config=config,
            fleet_names=fleet_names,
        )


def workflow_scenario_library(
    num_agents: int, seed: int = 0, fan_out: float = 1.0
) -> tuple[Workflow, ...]:
    """The canonical workflow-topology set for one fleet width.

    ``independent`` (today's exogenous behavior), ``coordinator_star``,
    ``pipeline_chain``, ``hierarchical`` (when the width allows it) and a
    reproducible random DAG.  The workflow axis of ``sweep_workflows``.
    """
    wfs = [routing.independent(num_agents)]
    if num_agents >= 2:
        wfs.append(routing.coordinator_star(num_agents, fan_out=fan_out))
        wfs.append(routing.pipeline_chain(num_agents))
    if num_agents >= 3:
        wfs.append(routing.hierarchical(num_agents, fan_out=fan_out))
    wfs.append(routing.synthetic_workflow(num_agents, seed=seed))
    return tuple(wfs)


def sweep_workflows(
    fleet: Fleet,
    workflows: Sequence[Workflow] | None = None,
    scenarios: Sequence[Scenario] | None = None,
    num_steps: int = 100,
    seed: int = 0,
    config: SimConfig = SimConfig(),
    policies: Sequence[str] | None = None,
    keep_traces: bool = False,
    stream: bool | None = None,
    return_arrays: bool = False,
    shard: bool | None = None,
    synthesize: bool | None = None,
    block_size: int | None = None,
    failures=None,
) -> SweepResult | tuple:
    """One jitted (workflow × policy × scenario) grid over one fleet.

    Every workflow must already span the fleet's width (``pad_workflow`` a
    narrower topology explicitly); they are stacked into a single batched
    ``Workflow`` pytree (``stack_workflows``).  The same scenario block
    feeds every topology — the simulator gates exogenous arrivals by each
    workflow's source flags, so a coordinator-star column only injects
    traffic at the coordinator.  Defaults: the canonical topology library
    at the fleet's width, and the standard scenario library over
    ``workload.synthetic_rates``.  On a multi-device host the workflow
    axis shards over the mesh's ``data`` axis and the scenario axis over
    ``grid`` (``shard=False`` / ``REPRO_SWEEP_SHARD=0`` force the
    single-device path).
    """
    with spans.Phases("sweep", "prepare", entry="sweep_workflows") as ph:
        fleet.validate()
        n = fleet.num_agents
        if workflows is None:
            workflows = workflow_scenario_library(n, seed=seed)
        workflows = list(workflows)
        if not workflows:
            raise ValueError("sweep_workflows needs at least one workflow")
        for wf in workflows:
            routing.check_workflow(wf, n)
        workflow_names = tuple(w.name for w in workflows)
        if len(set(workflow_names)) != len(workflow_names):
            raise ValueError(f"workflow names must be unique: {workflow_names}")
        stacked_wf = stack_workflows(workflows)  # all widths == n after the check

        if scenarios is None:
            rates = workload.synthetic_rates(n, seed=seed)
            scenarios = (
                workload.scenario_specs(rates, num_steps, seed) if synthesize
                else scenario_library(rates, num_steps, seed)
            )
        scen_names, arrivals, wspec = _prepare_scenarios(
            scenarios, synthesize, _streamed(keep_traces, stream)
        )  # (W, S, N) | stacked spec

        fspec, _ = _resolve_failure_axis(failures, allow_batch=False)
        reg_names = alloc.policy_names()
        names = reg_names if policies is None else tuple(policies)
        pids = jnp.asarray([alloc.policy_id(p) for p in names])

        out = _run_grid(
            pids, arrivals, fleet, stacked_wf, None, config, reg_names, names,
            keep_traces, stream, "workflow", shard, wspec=wspec, fspec=fspec,
            block_size=block_size, phases=ph,
        )
        return _collect(
            ph, out, keep_traces, return_arrays,
            policy_names=names,
            scenario_names=scen_names,
            config=config,
            workflow_names=workflow_names,
        )


def capacity_scenario_library(
    cold_start_s: float = 5.0,
    keep_alive_s: float = 10.0,
    target_rate_per_instance: float = 60.0,
    backlog_per_instance: float = 50.0,
) -> tuple[CapacityConfig, ...]:
    """The canonical capacity-policy set — the capacity axis of
    ``sweep_capacity``.

    ``fixed`` (the pre-capacity always-on pool), ``reactive`` with free
    scale-up, ``reactive_cold`` paying ``cold_start_s`` per new instance,
    and ``scale_to_zero`` with both a cold start and a keep-alive window.
    """
    return (
        capacity_config("fixed"),
        capacity_config(
            "reactive",
            target_rate_per_instance=target_rate_per_instance,
            backlog_per_instance=backlog_per_instance,
            min_instances=1.0,
        ),
        capacity_config(
            "reactive",
            cold_start_s=cold_start_s,
            target_rate_per_instance=target_rate_per_instance,
            backlog_per_instance=backlog_per_instance,
            min_instances=1.0,
            name="reactive_cold",
        ),
        capacity_config(
            "scale_to_zero",
            cold_start_s=cold_start_s,
            keep_alive_s=keep_alive_s,
            target_rate_per_instance=target_rate_per_instance,
            backlog_per_instance=backlog_per_instance,
        ),
    )


def sweep_capacity(
    fleet: Fleet,
    capacities: Sequence[CapacityConfig] | None = None,
    scenarios: Sequence[Scenario] | None = None,
    num_steps: int = 100,
    seed: int = 0,
    config: SimConfig = SimConfig(),
    policies: Sequence[str] | None = None,
    keep_traces: bool = False,
    stream: bool | None = None,
    return_arrays: bool = False,
    shard: bool | None = None,
    synthesize: bool | None = None,
    block_size: int | None = None,
    failures=None,
) -> SweepResult | tuple:
    """One jitted (capacity × policy × scenario) grid over one fleet.

    Capacity configs are stacked into a single batched ``CapacityConfig``
    pytree (``stack_capacities``) and vmapped outermost over the same
    ``_grid_jit`` kernel as every other sweep — allocation policies are
    ranked under *elasticity regimes*, and because billing is
    warm-instance-seconds the grid's cost column differs across allocation
    policies, capacity policies, and scenarios (the paper's cost-efficiency
    comparison, finally non-vacuous).  Defaults: the canonical capacity
    library and the standard scenario library over
    ``workload.synthetic_rates``.  On a multi-device host the capacity
    axis shards over the mesh's ``data`` axis and the scenario axis over
    ``grid`` (``shard=False`` / ``REPRO_SWEEP_SHARD=0`` force the
    single-device path).
    """
    with spans.Phases("sweep", "prepare", entry="sweep_capacity") as ph:
        fleet.validate()
        if capacities is None:
            capacities = capacity_scenario_library()
        capacities = list(capacities)
        if not capacities:
            raise ValueError("sweep_capacity needs at least one capacity config")
        for cp in capacities:
            check_capacity(cp, config.g_total, config.num_gpus)
        capacity_names = tuple(c.name for c in capacities)
        if len(set(capacity_names)) != len(capacity_names):
            raise ValueError(f"capacity names must be unique: {capacity_names}")
        stacked_cap = stack_capacities(capacities)

        if scenarios is None:
            rates = workload.synthetic_rates(fleet.num_agents, seed=seed)
            scenarios = (
                workload.scenario_specs(rates, num_steps, seed) if synthesize
                else scenario_library(rates, num_steps, seed)
            )
        scen_names, arrivals, wspec = _prepare_scenarios(
            scenarios, synthesize, _streamed(keep_traces, stream)
        )  # (W, S, N) | stacked spec

        fspec, _ = _resolve_failure_axis(failures, allow_batch=False)
        reg_names = alloc.policy_names()
        names = reg_names if policies is None else tuple(policies)
        pids = jnp.asarray([alloc.policy_id(p) for p in names])

        out = _run_grid(
            pids, arrivals, fleet, None, stacked_cap, config, reg_names, names,
            keep_traces, stream, "capacity", shard, wspec=wspec, fspec=fspec,
            block_size=block_size, phases=ph,
        )
        return _collect(
            ph, out, keep_traces, return_arrays,
            policy_names=names,
            scenario_names=scen_names,
            config=config,
            capacity_names=capacity_names,
        )
