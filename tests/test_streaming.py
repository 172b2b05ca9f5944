"""Streaming sweep kernel: parity with the trace-based oracle.

The streaming kernel (``simulator.simulate_stream_core`` +
``sweep._stream_grid_jit``) replaces the vmapped ``lax.switch`` (P² policy
evaluations per grid under the evaluate-all-branches lowering) with an
unrolled per-policy stack, and accumulates the METRIC_NAMES reductions in
the scan carry instead of materializing (S, N) traces.  The trace-based
path is kept as the parity oracle; these tests pin the acceptance
criterion: streaming metrics match it within float tolerance for every
registered policy on all four grid types, including under a workflow
topology and an elastic capacity config.

Tolerances are float32 accumulation-order noise: the streaming carry sums
sequentially where the trace path tree-reduces, and ``latency_std``
amplifies the difference through cancellation at the ~1000 s latency cap.
"""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from repro.core import allocator as alloc
from repro.core import routing
from repro.core import sharding
from repro.core import workload
from repro.core.agents import PAPER_ARRIVAL_RATES, paper_fleet, synthetic_fleet
from repro.core.capacity import capacity_config
from repro.core.simulator import (
    METRIC_NAMES,
    SimConfig,
    resolve_block_size,
    simulate,
    simulate_stream_core,
    trace_metrics,
)
from repro.core.sweep import (
    scenario_library,
    sweep,
    sweep_capacity,
    sweep_fleets,
    sweep_workflows,
)

# The package re-exports the ``sweep`` *function* under the submodule's
# name, so reach the module itself through importlib.
sweep_mod = importlib.import_module("repro.core.sweep")

FLEET = paper_fleet()
RTOL, ATOL = 1e-3, 1e-3

ELASTIC = capacity_config(
    "reactive", cold_start_s=3.0, min_instances=1.0, name="reactive_cold"
)


def _assert_grids_match(streamed, traced, label):
    assert streamed.metrics.shape == traced.metrics.shape, label
    np.testing.assert_allclose(
        streamed.metrics, traced.metrics, rtol=RTOL, atol=ATOL, err_msg=label
    )
    np.testing.assert_allclose(
        streamed.per_agent_latency, traced.per_agent_latency,
        rtol=RTOL, atol=ATOL, err_msg=label,
    )
    np.testing.assert_allclose(
        streamed.per_agent_throughput, traced.per_agent_throughput,
        rtol=RTOL, atol=ATOL, err_msg=label,
    )
    np.testing.assert_allclose(
        streamed.per_agent_queue, traced.per_agent_queue,
        rtol=RTOL, atol=ATOL, err_msg=label,
    )


class TestStreamingIsDefault:
    def test_keep_traces_false_routes_to_streaming_kernel(self, monkeypatch):
        calls = []
        real = sweep_mod._stream_grid_jit

        def spy(*args, **kwargs):
            calls.append(1)
            return real(*args, **kwargs)

        monkeypatch.setattr(sweep_mod, "_stream_grid_jit", spy)
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=10, seed=0)
        sweep(FLEET, scen)
        assert calls, "keep_traces=False must default to the streaming kernel"

    def test_keep_traces_true_uses_trace_kernel(self, monkeypatch):
        monkeypatch.setattr(
            sweep_mod, "_stream_grid_jit",
            lambda *a, **k: pytest.fail("trace sweep hit the streaming kernel"),
        )
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=10, seed=0)
        res = sweep(FLEET, scen[:1], keep_traces=True)
        assert res.traces is not None

    def test_stream_with_keep_traces_rejected(self):
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=10, seed=0)
        with pytest.raises(ValueError, match="streaming"):
            sweep(FLEET, scen, keep_traces=True, stream=True)


class TestGridParity:
    """Acceptance: streaming matches the trace oracle on all four grids."""

    def test_sweep(self):
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=40, seed=0)
        _assert_grids_match(
            sweep(FLEET, scen), sweep(FLEET, scen, stream=False), "sweep"
        )

    def test_sweep_with_capacity(self):
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=40, seed=0)
        _assert_grids_match(
            sweep(FLEET, scen, capacity=ELASTIC),
            sweep(FLEET, scen, capacity=ELASTIC, stream=False),
            "sweep+capacity",
        )

    def test_sweep_fleets(self):
        fleets = [synthetic_fleet(n, seed=n) for n in (2, 3, 5)]
        _assert_grids_match(
            sweep_fleets(fleets, num_steps=25, seed=0),
            sweep_fleets(fleets, num_steps=25, seed=0, stream=False),
            "sweep_fleets",
        )

    def test_sweep_workflows(self):
        _assert_grids_match(
            sweep_workflows(FLEET, num_steps=25, seed=0),
            sweep_workflows(FLEET, num_steps=25, seed=0, stream=False),
            "sweep_workflows",
        )

    def test_sweep_capacity(self):
        _assert_grids_match(
            sweep_capacity(FLEET, num_steps=25, seed=0),
            sweep_capacity(FLEET, num_steps=25, seed=0, stream=False),
            "sweep_capacity",
        )

    def test_policy_subset(self):
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=20, seed=0)
        pols = ("water_filling", "round_robin")
        streamed = sweep(FLEET, scen, policies=pols)
        traced = sweep(FLEET, scen, policies=pols, stream=False)
        assert streamed.policy_names == pols
        _assert_grids_match(streamed, traced, "policy subset")


class TestStreamCoreAgainstSingleRuns:
    """Row i of the streaming stack must be policy names[i]'s own run —
    exactly one dispatch per registered policy, against ``simulate`` (the
    single-run ``lax.switch`` path, untouched by this kernel)."""

    ARR = workload.poisson(
        jnp.asarray(PAPER_ARRIVAL_RATES, jnp.float32), 50, jax.random.key(7)
    )

    @pytest.mark.parametrize(
        "workflow,capacity",
        [
            (None, None),
            (routing.coordinator_star(4), None),
            (None, ELASTIC),
            (routing.pipeline_chain(4), ELASTIC),
        ],
        ids=("plain", "workflow", "capacity", "workflow+capacity"),
    )
    def test_every_policy_row_matches_its_simulate(self, workflow, capacity):
        cfg = SimConfig()
        names = alloc.policy_names()
        vec, per_lat, per_tput, per_q = simulate_stream_core(
            self.ARR, FLEET, cfg, names, workflow, capacity
        )
        assert vec.shape == (len(names), len(METRIC_NAMES))
        for i, name in enumerate(names):
            tr = simulate(name, self.ARR, FLEET, cfg, workflow, capacity)
            want, want_lat, want_tput, want_q = trace_metrics(
                tr, FLEET.active, workflow, config=cfg
            )
            np.testing.assert_allclose(
                np.asarray(vec[i]), np.asarray(want),
                rtol=RTOL, atol=ATOL, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(per_lat[i]), np.asarray(want_lat),
                rtol=RTOL, atol=ATOL, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(per_tput[i]), np.asarray(want_tput),
                rtol=RTOL, atol=ATOL, err_msg=name,
            )
            np.testing.assert_allclose(
                np.asarray(per_q[i]), np.asarray(want_q),
                rtol=RTOL, atol=ATOL, err_msg=name,
            )


class TestTimeBlocking:
    """The time-blocked two-level scan is a pure schedule change:
    ``block_size`` must never alter a single bit of any output."""

    # Covers the two new registered policies alongside EMA-coupled ones;
    # a subset keeps per-shape XLA compiles affordable (the full-registry
    # bit-identity bar is held by the B=1 routing — identical scan — plus
    # the property below exercising blocked dispatch itself).
    NAMES = ("adaptive", "water_filling", "sqrt_demand", "ema_water_filling")

    def test_env_var_matches_explicit_block_size(self, monkeypatch):
        scen = scenario_library(PAPER_ARRIVAL_RATES, num_steps=13, seed=0)[:2]
        base = sweep(FLEET, scen, policies=self.NAMES)
        explicit = sweep(FLEET, scen, policies=self.NAMES, block_size=4)
        monkeypatch.setenv("REPRO_SWEEP_BLOCK", "4")
        via_env = sweep(FLEET, scen, policies=self.NAMES)
        np.testing.assert_array_equal(
            np.asarray(explicit.metrics), np.asarray(base.metrics)
        )
        np.testing.assert_array_equal(
            np.asarray(via_env.metrics), np.asarray(base.metrics)
        )

    def test_block_size_below_one_rejected(self):
        with pytest.raises(ValueError, match="block_size"):
            resolve_block_size(0)
        with pytest.raises(ValueError, match="block_size"):
            resolve_block_size(-3)

    @hypothesis.given(
        gen=st.sampled_from(("poisson", "bursty", "correlated", "diurnal")),
        key=st.integers(0, 6),
        # Both horizons are indivisible by 3 and 64, so every blocked run
        # exercises the masked tail; at 65 steps B=3 crosses 21 block
        # boundaries with the MMPP regime state carried across each one,
        # and B=64 covers full-block + tail; at 20 steps B=64 > S covers
        # the tail-only path.
        num_steps=st.sampled_from((20, 65)),
        synth=st.booleans(),
    )
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_blocked_scan_is_bit_identical(self, gen, key, num_steps, synth):
        n = 4
        rates = workload.synthetic_rates(n, seed=1)
        fleet = synthetic_fleet(n, seed=1)
        if gen == "diurnal":
            spec = workload.diurnal_spec(rates, num_steps)
        else:
            spec = getattr(workload, f"{gen}_spec")(
                rates, num_steps, jax.random.key(key)
            )
        cfg = SimConfig()
        arr = None if synth else workload.materialize(spec)
        wspec = spec if synth else None
        base = simulate_stream_core(
            arr, fleet, cfg, self.NAMES, workload_spec=wspec, block_size=1
        )
        for b in (3, 64):
            got = simulate_stream_core(
                arr, fleet, cfg, self.NAMES, workload_spec=wspec, block_size=b
            )
            for part, want in zip(got, base):
                np.testing.assert_array_equal(
                    np.asarray(part), np.asarray(want),
                    err_msg=f"{gen}/key={key}/S={num_steps}/B={b}/synth={synth}",
                )


class TestOneScanGrid:
    """The single-device grid runs every scenario column of the
    8-scenario library, whatever its generator, in one horizon scan — the
    same program the sharded path runs per device."""

    N, STEPS = 4, 23

    def _grid(self, entry, block_size):
        """The entry point's single-device synthesized grid, its fleets,
        and per fleet the scenario columns' specs as the entry builds them."""
        if entry == "sweep":
            fleets = [synthetic_fleet(self.N, seed=0)]
            rows = [workload.scenario_specs(
                workload.synthetic_rates(self.N, seed=0),
                num_steps=self.STEPS, seed=0,
            )]
            res = sweep(fleets[0], rows[0], shard=False, synthesize=True,
                        block_size=block_size)
            parts = [(res.metrics, res.per_agent_latency,
                      res.per_agent_throughput, res.per_agent_queue)]
            return fleets, rows, parts
        fleets = [synthetic_fleet(self.N, seed=i) for i in range(2)]
        _, rows = workload.fleet_scenario_specs(
            [workload.synthetic_rates(self.N, seed=i) for i in range(2)],
            self.N, self.STEPS, 0,
        )
        res = sweep_fleets(fleets, num_steps=self.STEPS, seed=0,
                           synthesize=True, shard=False, block_size=block_size)
        parts = [(res.metrics[f], res.per_agent_latency[f],
                  res.per_agent_throughput[f], res.per_agent_queue[f])
                 for f in range(len(fleets))]
        return fleets, rows, parts

    @pytest.mark.parametrize("entry", ("sweep", "sweep_fleets"))
    @pytest.mark.parametrize("block_size", (1, 4))
    def test_grid_matches_per_column_runs(self, entry, block_size):
        fleets, rows, parts = self._grid(entry, block_size)
        core = jax.jit(simulate_stream_core, static_argnums=(2, 3),
                       static_argnames=("block_size",))
        names = alloc.policy_names()
        for f, (fleet, row, grid) in enumerate(zip(fleets, rows, parts)):
            stack = workload.stack_specs(row)
            for w in range(len(row)):
                col = jax.tree_util.tree_map(lambda x, w=w: x[w], stack)
                want = core(None, fleet, SimConfig(), names,
                            workload_spec=col, block_size=block_size)
                for part, ref in zip(grid, want):
                    np.testing.assert_array_equal(
                        np.asarray(part)[:, w], np.asarray(ref),
                        err_msg=f"{entry} fleet {f} {row[w].name} "
                                f"B={block_size}",
                    )

    @staticmethod
    def _horizon_loops(lowered, steps) -> int:
        """Loops at the compiled program's top level that walk the horizon."""
        text = lowered.compile().as_text()
        entry = text[text.index("\nENTRY"):]
        entry = entry[:entry.index("\n}")]
        return sum(
            f'"known_trip_count":{{"n":"{steps}"}}' in line
            for line in entry.splitlines() if " while(" in line
        )

    @pytest.mark.parametrize("entry", ("sweep", "sweep_fleets"))
    def test_single_device_grid_holds_one_horizon_loop(self, entry, monkeypatch):
        calls = []
        real = sweep_mod._stream_grid_jit

        def spy(*args, **kwargs):
            calls.append((args, kwargs))
            return real(*args, **kwargs)

        monkeypatch.setattr(sweep_mod, "_stream_grid_jit", spy)
        self._grid(entry, block_size=1)
        (args, kwargs), = calls
        grid, (config, names, batch_axis) = args[:6], args[6:9]
        sharded = sweep_mod._stream_grid_sharded.lower(
            *grid, mesh=sharding.grid_mesh(), config=config, names=names,
            batch_axis=batch_axis, block_size=1,
        )
        assert self._horizon_loops(real.lower(*args, **kwargs), self.STEPS) == 1
        assert self._horizon_loops(sharded, self.STEPS) == 1


@hypothesis.given(
    n=st.integers(2, 4),
    seed=st.integers(0, 10),
    # Discrete horizons so examples share compiled scans instead of paying
    # one XLA compile per drawn shape.
    num_steps=st.sampled_from((12, 30)),
    topology=st.sampled_from(("none", "star", "chain", "synthetic")),
    elastic=st.booleans(),
)
@hypothesis.settings(max_examples=8, deadline=None)
def test_streaming_matches_trace_metrics_property(
    n, seed, num_steps, topology, elastic
):
    """Property acceptance bar: streaming-mode metrics equal trace-mode
    ``trace_metrics`` within float tolerance for EVERY registered policy ×
    the full 8-scenario library, under randomized fleet width, seed,
    horizon, workflow topology, and elastic capacity."""
    fleet = synthetic_fleet(n, seed=seed)
    rates = workload.synthetic_rates(n, seed=seed)
    scenarios = scenario_library(rates, num_steps=num_steps, seed=seed)
    workflow = {
        "none": None,
        "star": routing.coordinator_star(n),
        "chain": routing.pipeline_chain(n),
        "synthetic": routing.synthetic_workflow(n, seed=seed),
    }[topology]
    capacity = ELASTIC if elastic else None
    cfg = SimConfig()
    names = alloc.policy_names()
    arrivals = jnp.stack(
        [jnp.asarray(s.arrivals, jnp.float32) for s in scenarios]
    )
    for w, scen in enumerate(scenarios):
        vec, _, _, _ = simulate_stream_core(
            arrivals[w], fleet, cfg, names, workflow, capacity
        )
        for i, name in enumerate(names):
            tr = simulate(name, arrivals[w], fleet, cfg, workflow, capacity)
            want, _, _, _ = trace_metrics(
                tr, fleet.active, workflow, config=cfg
            )
            np.testing.assert_allclose(
                np.asarray(vec[i]), np.asarray(want), rtol=5e-3, atol=5e-3,
                err_msg=f"{name}/{scen.name}/{topology}/elastic={elastic}",
            )
