"""Discrete-time fleet simulator (paper §IV-B), pure JAX ``lax.scan``.

Semantics reconstructed from the paper (DESIGN.md §6):

* one-second timesteps; requests arrive, the allocator distributes the GPU,
  agents serve ``min(g_i·T_i, queue_i + arrivals_i)`` (throughput scales
  proportionally with allocation), leftovers carry over FIFO;
* per-step latency estimate is the Little's-law drain time of the post-step
  queue at the *current* service rate, clipped at ``latency_cap`` seconds —
  a starved agent (g=0, e.g. off-turn under round-robin) reports the cap.
  This clipping is what produces the paper's round-robin figure of
  756.1 s ≈ 0.75·1000 + on-turn drain; we reproduce it faithfully and also
  expose the unclipped long-run latency (``littles_law_latency``);
* cost is billed on **warm-instance-seconds** (``capacity.billing_cost``):
  with the default always-on pool this reduces to the provisioned-device
  cost of Table II (duration · price/hour, identical across policies), but
  under an elastic capacity policy it is genuinely policy-dependent.

**Serverless capacity** (``core/capacity.py``) makes the budget itself part
of the dynamics: with a ``CapacityConfig`` the scan carries a warm-pool
autoscaler state and the allocator's budget becomes the traced trajectory
``g_total(t) = warm(t)`` — discrete instances, cold-start delay lines,
scale-to-zero keep-alive windows, an instance ceiling at
``SimConfig.num_gpus``.  With ``capacity=None`` the budget stays the static
python float ``config.g_total`` — exactly the pre-capacity program — and
``fixed`` capacity with zero cold start reproduces it bit-for-bit
(regression-tested per policy in tests/test_capacity.py).

**Workflow routing** (``core/routing.py``) makes the multi-agent dataflow
itself part of the dynamics: each step's *served* requests at agent i are
routed into downstream queues for step t+1
(``arrivals_endogenous = (served * fan_out) @ route``), exogenous
generators feed only ``workflow.source`` agents, and the row deficit of the
routing matrix exits the workflow as completed end-to-end requests
(``SimTrace.completed``).  Policies observe the *total* intake — exogenous
plus endogenous — so queue-pressure and rate-driven allocators both react
to collaborative cascades.  With no workflow (or ``routing.independent``)
the endogenous term is identically zero and trajectories are bit-for-bit
what they were before routing existed.

The whole run is one ``lax.scan``; policies are selected with ``lax.switch``
built from the allocator's policy registry, and ``Fleet`` / ``Workflow``
are registered pytrees, so a (fleets × policies × workloads) or
(workflows × policies × workloads) sweep is plain nested ``vmap`` — see
``core/sweep.py`` for the grid runners.  Padded fleets are first-class:
arrivals are gated by ``fleet.active`` and every metric reduction is
mask-weighted, so a padded fleet reports the same numbers as its unpadded
original.

**Streaming mode** (``simulate_stream_core``) is the sweep grids' hot
path: the whole policy axis runs inside one scan (each registered policy
dispatched exactly once per step via ``allocator.policy_stack``) and the
METRIC_NAMES reductions accumulate in the carry (``MetricAccum``), so no
(S, N) trajectory is ever materialized — ``trace_metrics`` and the
streaming carry share one finalizer (``finalize_metrics``), keeping
exactly one metric definition.  ``simulate``/``simulate_core`` remain the
single-run, trace-producing API.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import allocator as alloc
from repro.core import capacity as cap_mod
from repro.core import failures as fail_mod
from repro.core.agents import Fleet, T4_PRICE_PER_HOUR
from repro.core.capacity import CapacityConfig, billing_cost
from repro.core.failures import FailureSpec
from repro.core.routing import Workflow, check_workflow

_EPS = 1e-9

# Env default for the streaming kernel's time-block size (see
# ``resolve_block_size`` / ``simulate_stream_core(block_size=)``).
BLOCK_ENV = "REPRO_SWEEP_BLOCK"


def resolve_block_size(block_size: int | None = None) -> int:
    """Resolve the streaming time-block size B to a concrete python int.

    Explicit ``block_size`` wins; ``None`` falls back to the
    ``REPRO_SWEEP_BLOCK`` env var, then to 1 (the classic single-level
    scan).  B is a trace constant — it sizes the inner unrolled scan — so
    it must be resolved *before* jit, never traced.
    """
    if block_size is None:
        raw = os.environ.get(BLOCK_ENV, "").strip()
        block_size = int(raw) if raw else 1
    b = int(block_size)
    if b < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    return b


def __getattr__(attr: str):
    # Back-compat alias; the registry is authoritative (alloc.policy_id).
    if attr == "POLICY_IDS":
        return {name: i for i, name in enumerate(alloc.policy_names())}
    raise AttributeError(f"module {__name__!r} has no attribute {attr!r}")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static simulation knobs (hashable; a jit static argument).

    ``g_total`` is the provisioned budget: the allocator's constant budget
    when no capacity layer runs, and the warm pool's t=0 baseline when one
    does.  ``num_gpus`` is the **warm-pool instance ceiling** — the most
    instances any capacity policy may keep warm or pending (it is *not* a
    second copy of the budget; configs with ``g_total > num_gpus`` are
    rejected, since the static budget could never be provisioned under its
    own ceiling).  ``price_per_hour`` bills warm-instance-seconds via
    ``capacity.billing_cost``.
    """

    num_steps: int = 100
    g_total: float = 1.0
    latency_cap: float = 1000.0
    price_per_hour: float = T4_PRICE_PER_HOUR
    num_gpus: float = 1.0
    ema_alpha: float = 0.3

    def __post_init__(self):
        cap_mod.check_budget_ceiling(self.g_total, self.num_gpus)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SimTrace:
    """Per-step, per-agent trajectories: everything Fig. 2 plots.

    ``arrivals`` records the *exogenous* input (gated by the workflow's
    source flags and the fleet's active mask); ``completed`` the requests
    that exited the workflow at each agent (= served, when no workflow
    routes traffic).  The difference between served and completed is the
    endogenous traffic forwarded downstream.

    ``warm`` is the allocator's per-step budget ``g_total(t)`` — the warm
    instance count under a capacity policy, the constant ``config.g_total``
    without one; ``pending`` counts instances still in their cold start.
    """

    allocation: jnp.ndarray  # (S, N) g_i(t)
    served: jnp.ndarray      # (S, N) requests served in step t
    queue: jnp.ndarray       # (S, N) backlog after step t
    latency: jnp.ndarray     # (S, N) clipped drain-time estimate
    arrivals: jnp.ndarray    # (S, N) exogenous arrivals (source-gated)
    completed: jnp.ndarray = None  # (S, N) requests exiting the workflow
    warm: jnp.ndarray = None       # (S,) warm instances = g_total(t)
    pending: jnp.ndarray = None    # (S,) instances mid cold start
    # Failure/robustness trajectories (zeros when nothing fails).
    misrouted: jnp.ndarray = None  # (S, N) mass routed into inactive slots
    dropped: jnp.ndarray = None    # (S, N) deadline drops (budget exhausted)
    retried: jnp.ndarray = None    # (S, N) deadline-expired mass re-queued
    expired: jnp.ndarray = None    # (S, N) SLO-violating mass (pre-retry)
    recovery: jnp.ndarray = None   # (S,) post-outage backlog-drain indicator

    def __post_init__(self):
        if self.completed is None:
            self.completed = self.served
        if self.warm is None:
            self.warm = jnp.ones(self.served.shape[:-1], jnp.float32)
        if self.pending is None:
            self.pending = jnp.zeros(self.served.shape[:-1], jnp.float32)
        for f in ("misrouted", "dropped", "retried", "expired"):
            if getattr(self, f) is None:
                setattr(self, f, jnp.zeros_like(self.served))
        if self.recovery is None:
            self.recovery = jnp.zeros(self.served.shape[:-1], jnp.float32)

    def tree_flatten(self):
        return (self.allocation, self.served, self.queue, self.latency,
                self.arrivals, self.completed, self.warm, self.pending,
                self.misrouted, self.dropped, self.retried, self.expired,
                self.recovery), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@dataclasses.dataclass(frozen=True)
class SimSummary:
    """Table II row for one policy."""

    policy: str
    avg_latency: float
    latency_std: float          # std across agents' mean latencies (Table II)
    per_agent_latency: tuple
    total_throughput: float     # served requests / second
    per_agent_throughput: tuple
    cost: float                 # warm-instance-seconds billed in $
    gpu_utilization: float      # mean Σ g_i
    littles_law_latency: float  # unclipped long-run estimate
    mean_queue: float
    # Workflow (end-to-end) metrics; equal their per-agent analogues when no
    # workflow routes traffic.
    sink_throughput: float = 0.0        # requests exiting the workflow / s
    critical_path_latency: float = 0.0  # longest source→sink latency chain
    per_agent_queue: tuple = ()         # per-stage mean backlog
    # Serverless capacity metrics; under the default always-on pool
    # utilization == gpu_utilization / g_total and the stall time is 0.
    utilization: float = 0.0            # Σ g / warm-instance-seconds
    cold_start_stall_time: float = 0.0  # backlogged seconds with pending pool
    mean_warm_instances: float = 0.0    # mean warm pool size
    # Failure/robustness metrics; all 0 when nothing fails.
    dropped: float = 0.0                # deadline drops / s (budget exhausted)
    retried: float = 0.0                # deadline-expired mass re-queued / s
    slo_violations: float = 0.0         # deadline-expired mass / s (pre-retry)
    recovery_time: float = 0.0          # steps draining post-outage backlog
    misrouted: float = 0.0              # mass lost to inactive route slots / s

    @classmethod
    def from_metrics(
        cls,
        policy: str,
        m: dict,
        per_agent_latency,
        per_agent_throughput,
        per_agent_queue,
    ) -> "SimSummary":
        """The one METRIC_NAMES-dict → summary mapping, shared by
        ``summarize`` and ``SweepResult.summary`` so a new metric cannot be
        threaded through one path and silently default on the other."""
        return cls(
            policy=policy,
            avg_latency=m["avg_latency"],
            latency_std=m["latency_std"],
            per_agent_latency=tuple(float(x) for x in per_agent_latency),
            total_throughput=m["total_throughput"],
            per_agent_throughput=tuple(float(x) for x in per_agent_throughput),
            cost=m["cost"],
            gpu_utilization=m["gpu_utilization"],
            littles_law_latency=m["littles_law_latency"],
            mean_queue=m["mean_queue"],
            sink_throughput=m["sink_throughput"],
            critical_path_latency=m["critical_path_latency"],
            per_agent_queue=tuple(float(x) for x in per_agent_queue),
            utilization=m["utilization"],
            cold_start_stall_time=m["cold_start_stall_time"],
            mean_warm_instances=m["mean_warm_instances"],
            dropped=m["dropped"],
            retried=m["retried"],
            slo_violations=m["slo_violations"],
            recovery_time=m["recovery_time"],
            misrouted=m["misrouted"],
        )


def _routing_terms(
    workflow: Workflow | None, fleet: Fleet, arrivals: jnp.ndarray | None
):
    """Shared scan prep: gate exogenous arrivals, precompute routing terms.

    With ``workflow=None`` the routing terms are ``None`` — the scan body's
    signal to skip the endogenous path entirely (see ``_queue_step``).

    Returns ``(route_eff, exit_frac, gated_arrivals, gate)``: the 0/1
    ``gate`` mask (active, source-restricted under a workflow) is what the
    streaming scan applies per step when arrivals are *synthesized* in the
    body instead of materialized up front (``arrivals=None``).  Gating by
    the fused mask is bit-identical to the old two-multiply chain: 0/1
    masks multiply exactly in any association order.
    """
    if workflow is None:
        gate = fleet.active
        route_eff = exit_frac = None
    else:
        route_eff = workflow.route * workflow.fan_out[..., :, None]  # forwarded copies
        exit_frac = jnp.maximum(1.0 - workflow.route.sum(axis=-1), 0.0)
        gate = fleet.active * workflow.source
    gated = None if arrivals is None else arrivals * gate
    return route_eff, exit_frac, gated, gate


def _queue_step(
    queue: jnp.ndarray,
    lam: jnp.ndarray,
    g: jnp.ndarray,
    fleet: Fleet,
    config: SimConfig,
    route_eff: jnp.ndarray | None,
    exit_frac: jnp.ndarray | None,
):
    """One step of the serving/queueing physics — THE definition, shared by
    the trace scan (``simulate_core``) and the streaming scan
    (``simulate_stream_core``); the state arrays may carry a leading policy
    axis (broadcasting handles both).

    ``route_eff=None`` is the workflow-free fast path: the routing matrix
    would be the N×N zero matrix and ``exit_frac`` identically 1, so the
    ``served @ route`` contraction burns O(N²) multiplies per step producing
    exact zeros.  Skipping it keeps the output bit-for-bit (``served · 1.0
    == served``, and the endogenous term was exactly zero already) — the
    no-op guarantee regression-tested in tests/test_routing.py.
    """
    capacity_rps = g * fleet.base_throughput
    served = jnp.minimum(capacity_rps, queue + lam)
    new_queue = queue + lam - served
    latency = jnp.minimum(
        new_queue / jnp.maximum(capacity_rps, _EPS), config.latency_cap
    )
    if route_eff is None:
        completed = served
        new_endo = jnp.zeros_like(served)
        mis = jnp.zeros_like(served)
    else:
        completed = served * exit_frac  # row deficit exits the workflow
        # Routed mass arrives downstream next step; the active gate keeps
        # padded slots inert even if a route column points at one.  The
        # misrouted mass is dropped, exactly like gated exogenous traffic —
        # but it is *accounted*, so conservation stays checkable.
        fwd = served @ route_eff
        new_endo = fwd * fleet.active
        mis = fwd * (1.0 - fleet.active)
    return served, new_queue, latency, completed, new_endo, mis


def _failure_queue_step(
    queue: jnp.ndarray,
    lam: jnp.ndarray,
    g: jnp.ndarray,
    fleet: Fleet,
    config: SimConfig,
    route_eff: jnp.ndarray | None,
    exit_frac: jnp.ndarray | None,
    failures: FailureSpec,
    phi: jnp.ndarray,
    up: jnp.ndarray,
    retry_q: jnp.ndarray,
):
    """Failure-aware twin of ``_queue_step`` (only compiled when a
    ``FailureSpec`` is passed — the ``failures=None`` program never sees it).

    * **agent outage** (``up`` → 0): the agent's effective capacity is
      zeroed (no reallocation — its share idles), the queue is preserved
      and arrivals keep accumulating across the outage;
    * **revocation** (``phi`` > 0): a ``phi`` fraction of warm capacity is
      yanked mid-step — its in-service work drains back into the queue
      (``served = served_raw · (1-phi)``, so the clawed-back mass stays in
      ``new_queue`` by mass balance);
    * **deadlines**: :func:`repro.core.failures.deadline_step` expires the
      backlog beyond the deadline's worth of effective service, retrying
      (class promotion, bounded by ``retry_budget``) or dropping it.

    Returns ``(served, new_queue, latency, completed, new_endo, mis,
    new_retry_q, dropped, retried, viol)``.  Mass balance per agent:
    ``new_queue = queue + lam - served - dropped``.
    """
    capacity_rps = g * up * fleet.base_throughput
    served_raw = jnp.minimum(capacity_rps, queue + lam)
    served = served_raw * (1.0 - phi)
    q_post = queue + lam - served
    cap_eff = capacity_rps * (1.0 - phi)
    new_queue, new_retry_q, dropped, retried, viol = fail_mod.deadline_step(
        failures, queue, lam, served, q_post, cap_eff, retry_q, eps=_EPS
    )
    # The drop accounting can leave a roundoff residue where the true
    # post-drop queue is exactly zero.  Snap it to an exact zero (and gate
    # the clipped-latency cliff on the same dead band) so the f32 kernel
    # and the f64 oracle cannot land on opposite sides of a queue>0 branch
    # — discrete allocators (throughput_greedy) chase any positive demand,
    # and the 0-vs-latency_cap latency boundary is a 1000x cliff.  The
    # snapped mass is bounded by the dead band per agent-step.
    new_queue = new_queue * (new_queue > 1e-4)
    latency = jnp.minimum(
        new_queue / jnp.maximum(cap_eff, _EPS), config.latency_cap
    ) * (new_queue > 1e-4)
    if route_eff is None:
        completed = served
        new_endo = jnp.zeros_like(served)
        mis = jnp.zeros_like(served)
    else:
        completed = served * exit_frac
        fwd = served @ route_eff
        new_endo = fwd * fleet.active
        mis = fwd * (1.0 - fleet.active)
    return (served, new_queue, latency, completed, new_endo, mis,
            new_retry_q, dropped, retried, viol)


def simulate_core(
    policy_id: jnp.ndarray,
    arrivals: jnp.ndarray,
    fleet: Fleet,
    config: SimConfig,
    policy_names: Sequence[str] | None = None,
    workflow: Workflow | None = None,
    capacity: CapacityConfig | None = None,
    failures: FailureSpec | None = None,
) -> SimTrace:
    """Pure scan body — jit/vmap-able over ``policy_id``, ``arrivals``, the
    ``fleet`` pytree, the ``workflow`` pytree and the ``capacity`` pytree
    (any of which may carry a batch axis).

    The EMA carry is seeded with the first observation; the update is skipped
    at t=0 so that observation is not applied twice.  Exogenous arrivals are
    gated by ``fleet.active`` (padding slots never accumulate queue) and by
    ``workflow.source`` (only source agents see outside traffic); each
    step's served requests are fanned into downstream queues for the next
    step via the routing matrix.  With ``workflow=None`` the endogenous
    path contributes exact zeros — trajectories are bit-for-bit identical
    to the pre-routing simulator.

    With a ``capacity`` config the scan also carries the warm-pool state:
    the autoscaler runs *before* the allocation policy each step (cohorts
    warm up, the idle clock ticks, desired count is chosen) and the policy's
    budget is the traced ``warm(t)`` instead of the static ``config.g_total``.
    With ``capacity=None`` the budget stays a python float — the literal
    pre-capacity program — which the ``fixed``/zero-cold-start capacity path
    must reproduce bit-for-bit (tests/test_capacity.py).

    ``failures`` injects revocation / agent-outage / deadline dynamics
    (``core/failures.py``): the chain state rides the carry, the physics
    switch to ``_failure_queue_step``, and the trace grows the
    dropped/retried/expired/misrouted/recovery trajectories.  The
    ``failures=None`` branch is resolved in *python*, so the no-failure
    program is structurally the pre-failure program — bit-for-bit, not
    merely numerically close (tests/test_failures.py).
    """
    names = alloc.policy_names() if policy_names is None else tuple(policy_names)
    n = fleet.num_agents
    route_eff, exit_frac, arrivals, _ = _routing_terms(workflow, fleet, arrivals)
    elastic = capacity is not None
    failing = failures is not None

    def step(carry, inp):
        if failing:
            fstate = carry[-1]
            carry = carry[:-1]
        if elastic:
            queue, lam_ema, endo, cstate = carry
        else:
            queue, lam_ema, endo = carry
        t, lam_exo = inp
        lam = lam_exo + endo            # total intake: exogenous + routed
        lam_ema = jnp.where(
            t > 0, alloc.ema_forecast(lam_ema, lam, config.ema_alpha), lam_ema
        )
        if elastic:
            cstate, g_total_t, pending_t = cap_mod.capacity_step(
                cstate, capacity, t, lam.sum(), lam_ema.sum(), queue.sum(),
                config.g_total, config.num_gpus,
            )
        else:
            g_total_t = config.g_total  # static python float: the pre-capacity program
            pending_t = jnp.zeros((), jnp.float32)
        g = alloc.policy_switch(
            policy_id, t, lam, lam_ema, queue, fleet, g_total_t, names
        )
        if failing:
            u_rev, u_down = fail_mod.failure_uniforms(failures, t, n)
            phi, avail, rev_nxt, down_nxt = fail_mod.advance_failures(
                failures, t, fstate.rev_on, fstate.down, u_rev, u_down
            )
            fail_t = jnp.maximum(
                (phi > 0).astype(jnp.float32),
                (((1.0 - avail) * fleet.active) > 0.5).any().astype(jnp.float32),
            )
            pre_q_tot = (queue * fleet.active).sum(-1)
            onset = fail_t * (1.0 - fstate.fail_prev) * (1.0 - fstate.recovering)
            q_mark = jnp.where(onset > 0, pre_q_tot, fstate.q_mark)
            (served, new_queue, latency, completed, new_endo, mis,
             new_retry_q, dropped, retried, viol) = _failure_queue_step(
                queue, lam, g, fleet, config, route_eff, exit_frac,
                failures, phi, avail, fstate.retry_q,
            )
            # Recovery bookkeeping: once the failure clears, count the steps
            # until the backlog drains back under its pre-outage watermark.
            new_q_tot = (new_queue * fleet.active).sum(-1)
            in_rec = (1.0 - fail_t) * jnp.maximum(fstate.fail_prev,
                                                  fstate.recovering)
            recovering = jnp.where(
                fail_t > 0, fstate.recovering,
                in_rec * (new_q_tot > q_mark).astype(jnp.float32),
            )
            fstate = fail_mod.FailureState(
                rev_on=rev_nxt, down=down_nxt, fail_prev=fail_t,
                recovering=recovering, q_mark=q_mark, retry_q=new_retry_q,
            )
            if elastic:
                # Revoked instances leave the warm pool: the autoscaler must
                # re-provision them through the cold-start pipeline.
                cstate = cap_mod.CapacityState(
                    cstate.warm * (1.0 - phi), cstate.pipeline, cstate.idle_s
                )
        else:
            served, new_queue, latency, completed, new_endo, mis = _queue_step(
                queue, lam, g, fleet, config, route_eff, exit_frac
            )
        warm_t = jnp.asarray(g_total_t, jnp.float32)
        if failing:
            # Billing excludes revoked instance-seconds: the yanked share
            # of the pool is not warm capacity for this step.
            warm_t = warm_t * (1.0 - phi)
        new_carry = (
            (new_queue, lam_ema, new_endo, cstate) if elastic
            else (new_queue, lam_ema, new_endo)
        )
        out = (g, served, new_queue, latency, completed, warm_t, pending_t, mis)
        if failing:
            new_carry = new_carry + (fstate,)
            out = out + (dropped, retried, viol, in_rec)
        return new_carry, out

    num_steps = arrivals.shape[0]
    ts = jnp.arange(num_steps)
    init = (
        jnp.zeros(n, jnp.float32),
        arrivals[0],
        jnp.zeros(n, jnp.float32),
    )
    if elastic:
        init = init + (cap_mod.init_capacity_state(config.g_total),)
    if failing:
        init = init + (fail_mod.init_failure_state(n),)
    _, outs = jax.lax.scan(step, init, (ts, arrivals))
    g, served, queue, latency, completed, warm, pending, mis = outs[:8]
    if failing:
        dropped, retried, viol, recovery = outs[8:]
    else:
        dropped = retried = viol = recovery = None
    return SimTrace(g, served, queue, latency, arrivals, completed, warm,
                    pending, mis, dropped, retried, viol, recovery)


# ``Fleet``, ``Workflow`` and ``CapacityConfig`` are registered pytrees
# (names are static aux data), so they pass straight through jit — no
# array/static plumbing.
_simulate_jit = jax.jit(simulate_core, static_argnames=("config", "policy_names"))


def simulate(
    policy: str,
    arrivals: jnp.ndarray,
    fleet: Fleet,
    config: SimConfig = SimConfig(),
    workflow: Workflow | None = None,
    capacity: CapacityConfig | None = None,
    failures: FailureSpec | None = None,
) -> SimTrace:
    """Run one registered policy over an (S, N) arrival matrix, optionally
    routing served requests through a ``Workflow`` topology, scaling the
    warm pool with a ``CapacityConfig`` autoscaler, and/or injecting
    failures from a ``FailureSpec`` chaos scenario."""
    fleet.validate()
    if workflow is not None:
        check_workflow(workflow, fleet.num_agents)
    if capacity is not None:
        cap_mod.check_capacity(capacity, config.g_total, config.num_gpus)
    failures = fail_mod.resolve_failures(failures)
    if failures is not None:
        fail_mod.check_failures(failures)
        if failures.batched:
            raise ValueError(
                "simulate() takes a single FailureSpec; batched (stacked) "
                "specs only flow through sweep(..., failures=[...])"
            )
    return _simulate_jit(
        jnp.asarray(alloc.policy_id(policy)), arrivals, fleet, config,
        alloc.policy_names(), workflow, capacity, failures,
    )


def simulate_stream_core(
    arrivals: jnp.ndarray | None,
    fleet: Fleet,
    config: SimConfig,
    policy_names: Sequence[str] | None = None,
    workflow: Workflow | None = None,
    capacity: CapacityConfig | None = None,
    workload_spec=None,
    num_policy_blocks: int = 1,
    policy_block: jnp.ndarray | None = None,
    block_size: int | None = None,
    failures: FailureSpec | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused streaming scan: every named policy's trajectory AND its metric
    reductions in ONE pass, materializing no per-step traces.

    The sweep grids' hot path (``core/sweep.py``).  Two structural changes
    versus vmapping ``simulate_core`` over a policy axis:

    * **O(P) dispatch** — under ``vmap`` the per-step ``lax.switch`` lowers
      to evaluate-all-branches-and-select, so each of P policy rows computes
      all P policies (P² allocator evaluations per step).  Here the policy
      axis lives *inside* the scan as a (P, N) state stack and
      ``alloc.policy_stack`` dispatches each named policy exactly once per
      step, on its own row.
    * **O(1)-in-time memory** — the carry folds each step's outputs straight
      into a ``MetricAccum`` (running METRIC_NAMES sums); nothing of shape
      (S, ·) is ever materialized, so peak memory per cell is O(P · N)
      however long the horizon.

    **In-scan synthesis** closes the input side too: pass a
    ``workload.WorkloadSpec`` (and ``arrivals=None``) and step t's arrival
    row is computed *inside* the scan body from the O(N) parameter row —
    ``workload_step`` with a ``fold_in(key, t)`` counter-based draw, MMPP
    state riding the carry — so nothing of shape (S, ·) exists on either
    end of the scan.  Synthesized runs are bit-for-bit identical to running
    the same spec through ``workload.materialize`` and passing the tensor:
    the materializer scans the very same registered step functions.

    **Policy-axis sharding** (``num_policy_blocks`` > 1): the named policy
    list is cut into equal contiguous blocks and this invocation computes
    only block ``policy_block`` (a traced index — under ``shard_map`` it is
    ``lax.axis_index("policy")``).  Each block still gets the O(P) unrolled
    dispatch via ``allocator.policy_stack_blocks``; state/metric rows shrink
    to P/blocks per device.

    **Time blocking** (``block_size`` > 1, env ``REPRO_SWEEP_BLOCK``): the
    scan becomes two-level — an outer ``lax.scan`` over ⌈S/B⌉ blocks whose
    body synthesizes a whole (B, N) arrival block in one
    ``workload.step_block`` call (one generator dispatch per block instead
    of per step, and one *batched* RNG draw per block for the expensive
    samplers) and runs B physics/dispatch steps through an inner rolled
    scan.  Unrolling that inner scan was measured a net loss on XLA CPU —
    ~1.7× slower execution and far longer compiles than the rolled loop —
    so blocking's payoff is entirely in the amortized synthesis, not in
    loop unrolling.  A non-divisible horizon is handled by a masked tail
    block: steps with ``t >= S`` keep the previous carry element-wise, so
    they change nothing.  ``block_size=1`` routes to the original
    single-level scan verbatim, and every block size yields bit-identical
    metrics (tests/test_streaming.py) — B trades compile time for step
    throughput, never results.  Peak memory per cell grows to O(B·N);
    both ends of the scan stay horizon-free.

    Synthesis dispatches the spec's generator by its traced ``gen_id``
    (``workload_step``'s ``lax.switch``), so a grid vmapped over scenario
    columns of different generators runs them all in this one scan.  Under
    ``vmap`` the switch evaluates every registered generator for every
    column; that costs less than a horizon loop per generator would (see
    ``sweep._stream_grid``).

    Physics (``_queue_step``), EMA seeding, the autoscaler
    (``capacity_step``, vmapped over the policy rows — each policy's queue
    trajectory drives its own warm pool) and the metric finalizer
    (``finalize_metrics``) are all shared with the trace-based path, which
    remains the parity oracle: streaming metrics match
    ``trace_metrics(simulate_core(...))`` within float tolerance
    (tests/test_streaming.py).

    The step's stages carry ``jax.named_scope`` names, so a device trace
    can attribute each op: ``synthesis``, ``policies`` (one nested scope
    per policy name, ``allocator.policy_stack``), ``capacity``,
    ``failures``, ``queue`` and ``accumulate``.  Scopes are op metadata
    only: the computation, and every bit of its results, is unchanged.

    Returns ``(metrics (P, M), per-agent latency (P, N), per-agent
    throughput (P, N), per-agent queue (P, N))`` with P = len(policy_names)
    in name order (P/blocks rows of the current block when blocked) and
    M = len(METRIC_NAMES).
    """
    from repro.core import workload as workload_mod

    names = alloc.policy_names() if policy_names is None else tuple(policy_names)
    if (arrivals is None) == (workload_spec is None):
        raise ValueError("pass exactly one of arrivals= / workload_spec=")
    synth = workload_spec is not None
    blocks = int(num_policy_blocks)
    if blocks > 1:
        if len(names) % blocks:
            raise ValueError(
                f"{len(names)} policies do not split into {blocks} equal blocks"
            )
        if policy_block is None:
            raise ValueError("num_policy_blocks > 1 requires policy_block")
    p, n = len(names) // blocks, fleet.num_agents
    route_eff, exit_frac, arrivals, gate = _routing_terms(
        workflow, fleet, arrivals
    )
    elastic = capacity is not None
    failing = failures is not None
    if elastic:
        # vmap over the policy rows only; the config itself is shared.  The
        # inner ``lax.switch`` keeps its unbatched index, so no branch blowup.
        cap_step = jax.vmap(
            cap_mod.capacity_step, in_axes=(0, None, None, 0, 0, 0, None, None)
        )

    def dispatch(t, lam, lam_ema, queue, g_total_t):
        if blocks > 1:
            return alloc.policy_stack_blocks(
                t, lam, lam_ema, queue, fleet, g_total_t, names,
                blocks, policy_block,
            )
        return alloc.policy_stack(t, lam, lam_ema, queue, fleet, g_total_t, names)

    def step_body(carry, t, lam_exo):
        # One streaming step on the workload-state-free carry:
        # (queue, lam_ema, endo, acc[, cstate][, fstate]).
        queue, lam_ema, endo, acc = carry[:4]
        rest = carry[4:]
        if failing:
            fstate = rest[-1]
            rest = rest[:-1]
        lam = lam_exo + endo            # (P, N) total intake per policy row
        lam_ema = jnp.where(
            t > 0, alloc.ema_forecast(lam_ema, lam, config.ema_alpha), lam_ema
        )
        if elastic:
            with jax.named_scope("capacity"):
                cstate, g_total_t, pending_t = cap_step(
                    rest[-1], capacity, t, lam.sum(axis=-1),
                    lam_ema.sum(axis=-1), queue.sum(axis=-1), config.g_total,
                    config.num_gpus,
                )
            rest = rest[:-1] + (cstate,)
        else:
            g_total_t = config.g_total  # static python float: the pre-capacity program
            pending_t = jnp.zeros((p,), jnp.float32)
        with jax.named_scope("policies"):
            g = dispatch(t, lam, lam_ema, queue, g_total_t)
        if failing:
            # The chains are exogenous — one draw shared by every policy
            # row; only the per-policy bookkeeping carries a (P,) axis.
            with jax.named_scope("failures"):
                u_rev, u_down = fail_mod.failure_uniforms(failures, t, n)
                phi, avail, rev_nxt, down_nxt = fail_mod.advance_failures(
                    failures, t, fstate.rev_on, fstate.down, u_rev, u_down
                )
                fail_t = jnp.maximum(
                    (phi > 0).astype(jnp.float32),
                    (((1.0 - avail) * fleet.active) > 0.5).any().astype(jnp.float32),
                )
                pre_q_tot = (queue * fleet.active).sum(-1)          # (P,)
                onset = fail_t * (1.0 - fstate.fail_prev) * (1.0 - fstate.recovering)
                q_mark = jnp.where(onset > 0, pre_q_tot, fstate.q_mark)
            with jax.named_scope("queue"):
                (served, new_queue, latency, completed, new_endo, mis,
                 new_retry_q, dropped, retried, viol) = _failure_queue_step(
                    queue, lam, g, fleet, config, route_eff, exit_frac,
                    failures, phi, avail, fstate.retry_q,
                )
            with jax.named_scope("failures"):
                new_q_tot = (new_queue * fleet.active).sum(-1)
                in_rec = (1.0 - fail_t) * jnp.maximum(fstate.fail_prev,
                                                      fstate.recovering)
                recovering = jnp.where(
                    fail_t > 0, fstate.recovering,
                    in_rec * (new_q_tot > q_mark).astype(jnp.float32),
                )
                fstate = fail_mod.FailureState(
                    rev_on=rev_nxt, down=down_nxt, fail_prev=fail_t,
                    recovering=recovering, q_mark=q_mark, retry_q=new_retry_q,
                )
                if elastic:
                    cstate = cap_mod.CapacityState(
                        cstate.warm * (1.0 - phi), cstate.pipeline, cstate.idle_s
                    )
                    rest = rest[:-1] + (cstate,)
        else:
            with jax.named_scope("queue"):
                served, new_queue, latency, completed, new_endo, mis = _queue_step(
                    queue, lam, g, fleet, config, route_eff, exit_frac
                )
            dropped = retried = viol = in_rec = None
        with jax.named_scope("accumulate"):
            warm_t = jnp.broadcast_to(jnp.asarray(g_total_t, jnp.float32), (p,))
            if failing:
                # Billing excludes revoked instance-seconds (as in simulate_core).
                warm_t = warm_t * (1.0 - phi)
            acc = accumulate_metrics(
                acc, fleet.active, g, served, new_queue, latency, completed,
                warm_t, pending_t, misrouted=mis, dropped=dropped,
                retried=retried, viol=viol, recovery=in_rec,
            )
        out = (new_queue, lam_ema, new_endo, acc) + rest
        if failing:
            out = out + (fstate,)
        return out

    def step(carry, inp):
        # Single-level (block_size=1) scan body: per-step synthesis inline.
        if synth:
            t = inp
            with jax.named_scope("synthesis"):
                lam_row, wstate = workload_mod.workload_step(
                    workload_spec, carry[4], t
                )
            out = step_body(carry[:4] + carry[5:], t, lam_row * gate)
            return out[:4] + (wstate,) + out[4:], None
        t, lam_exo = inp
        return step_body(carry, t, lam_exo), None

    if synth:
        num_steps = workload_spec.num_steps
        with jax.named_scope("synthesis"):
            wstate0 = workload_mod.workload_init(workload_spec)
            # EMA seed = the very row the scan body will synthesize at t=0
            # (same step function, same fold — bit-identical to arrivals[0]
            # of the materialized tensor, gated the same way).
            lam0 = (
                workload_mod.workload_step(
                    workload_spec, wstate0, jnp.asarray(0, jnp.int32)
                )[0]
                * gate
            )
    else:
        num_steps = arrivals.shape[0]
        lam0 = arrivals[0]
    ts = jnp.arange(num_steps)
    init = (
        jnp.zeros((p, n), jnp.float32),
        jnp.broadcast_to(lam0, (p, n)),  # EMA seed, as in simulate_core
        jnp.zeros((p, n), jnp.float32),
        init_metric_accum(n, (p,)),
    )
    if synth:
        init = init + (wstate0,)
    if elastic:
        init = init + (jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (p,) + x.shape),
            cap_mod.init_capacity_state(config.g_total),
        ),)
    if failing:
        # fstate rides LAST in the carry: the chains (rev_on/down/fail_prev)
        # are shared across policy rows, the bookkeeping is per-policy.
        init = init + (fail_mod.init_failure_state(n, (p,)),)
    bsz = resolve_block_size(block_size)
    if bsz == 1:
        carry, _ = jax.lax.scan(step, init, ts if synth else (ts, arrivals))
    else:
        # Two-level blocked scan: the outer scan walks the ⌊S/B⌋ *full*
        # blocks with a mask-free inner scan (the hot path); a
        # non-divisible horizon finishes in one masked tail block below.
        # Both inner scans stay ROLLED: unrolling the full physics body was
        # measured a straight loss on XLA CPU (~1.7× slower at B=128, with
        # far longer compiles — and the tail's per-step where-gate builds
        # select chains the simplifier degenerates on when unrolled).  The
        # block's payoff is the batched per-block synthesis in
        # workload.step_block, not loop unrolling.
        full = num_steps // bsz
        rem = num_steps - full * bsz
        unroll = 1

        def inner_step(carry, inp):
            t, lam_exo = inp
            return step_body(carry, t, lam_exo), None

        def tail_step(carry, inp):
            # Masked tail block: steps past the horizon keep the old carry
            # element-wise (where(True, new, old) == new exactly, so valid
            # steps are untouched by the gate).
            t, lam_exo = inp
            new_carry = step_body(carry, t, lam_exo)
            valid = t < num_steps
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(valid, new, old), new_carry, carry
            ), None

        def split_wstate(carry):
            # (queue, ema, endo, acc[, wstate][, cstate]) -> workload-free
            # carry for the inner scans + the wstate to thread at block level.
            if synth:
                return carry[:4] + carry[5:], carry[4]
            return carry, None

        def join_wstate(inner, wstate):
            if synth:
                return inner[:4] + (wstate,) + inner[4:]
            return inner

        def run_block(carry, ts_blk, lam_blk, scan_step, unroll):
            inner, wstate = split_wstate(carry)
            if synth:
                with jax.named_scope("synthesis"):
                    lam_blk, wstate = workload_mod.step_block(
                        workload_spec, wstate, ts_blk
                    )
                lam_blk = lam_blk * gate
            inner, _ = jax.lax.scan(
                scan_step, inner, (ts_blk, lam_blk), unroll=unroll
            )
            return join_wstate(inner, wstate)

        if synth:
            arr_blocks = None
            xs = jnp.arange(full, dtype=ts.dtype) * bsz  # block start t0
        else:
            arr_blocks = arrivals[: full * bsz].reshape(
                (full, bsz) + arrivals.shape[1:]
            )
            xs = (
                jnp.arange(full * bsz, dtype=ts.dtype).reshape(full, bsz),
                arr_blocks,
            )

        def block_step(carry, inp):
            if synth:
                ts_blk = inp + jnp.arange(bsz, dtype=inp.dtype)
                lam_blk = None
            else:
                ts_blk, lam_blk = inp
            return run_block(carry, ts_blk, lam_blk, inner_step, unroll), None

        carry = init
        if full:
            carry, _ = jax.lax.scan(block_step, carry, xs)
        if rem:
            ts_tail = full * bsz + jnp.arange(bsz, dtype=ts.dtype)
            if synth:
                lam_tail = None  # synthesized inside run_block
            else:
                pad = bsz - rem
                lam_tail = jnp.concatenate(
                    [arrivals[full * bsz:],
                     jnp.zeros((pad,) + arrivals.shape[1:], arrivals.dtype)]
                )
            carry = run_block(carry, ts_tail, lam_tail, tail_step, 1)
    acc = carry[3]
    return jax.vmap(
        lambda a: finalize_metrics(
            a, num_steps, fleet.active, workflow, config=config
        )
    )(acc)


# Order of the metric vector returned by trace_metrics (and of the metric
# axis in sweep grids).  Capacity metrics (cost included — it is now
# policy-dependent) live at the end so index-based consumers of the original
# eight keep working.
METRIC_NAMES = (
    "avg_latency",
    "latency_std",
    "total_throughput",
    "gpu_utilization",
    "mean_queue",
    "littles_law_latency",
    "sink_throughput",
    "critical_path_latency",
    "cost",
    "utilization",
    "cold_start_stall_time",
    "mean_warm_instances",
    # Failure/robustness metrics (PR 10) — appended at the end so
    # index-based consumers of the original twelve keep working.
    "dropped",
    "retried",
    "slo_violations",
    "recovery_time",
    "misrouted",
)


def critical_path_latency(
    per_agent_latency: jnp.ndarray,
    workflow: Workflow | None,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """Longest source→sink chain of per-stage latencies through the DAG.

    ``cp_i = lat_i + max over successors cp_j``, iterated N times (a DAG
    over N agents has depth < N), then maximized over source agents.  With
    no workflow every agent is its own one-stage path, so this reduces to
    the max per-agent latency over active agents.
    """
    if workflow is None:
        return (per_agent_latency * mask).max()
    adj = (workflow.route > 0).astype(per_agent_latency.dtype)  # (N, N)
    n = per_agent_latency.shape[-1]

    def body(_, cp):
        return per_agent_latency + (adj * cp[None, :]).max(axis=-1)

    cp = jax.lax.fori_loop(0, n, body, per_agent_latency)
    return (cp * workflow.source * mask).max()


class MetricAccum(NamedTuple):
    """Running METRIC_NAMES reductions — the streaming scan's metric carry.

    Everything ``trace_metrics`` needs, as O(N) running sums instead of
    (S, N) trajectories: peak memory per cell is independent of the horizon.
    Leaves may carry a leading policy axis (the streaming kernel accumulates
    all P policies at once).
    """

    lat_sum: jnp.ndarray        # (..., N) Σ_t latency
    served_sum: jnp.ndarray     # (..., N) Σ_t served
    queue_sum: jnp.ndarray      # (..., N) Σ_t queue
    completed_sum: jnp.ndarray  # (..., N) Σ_t completed
    alloc_sum: jnp.ndarray      # (...,)   Σ_t Σ_i g_i
    warm_sum: jnp.ndarray       # (...,)   Σ_t warm(t) — warm-instance-seconds
    stall_steps: jnp.ndarray    # (...,)   steps with pending > 0 and backlog
    dropped_sum: jnp.ndarray    # (...,)   Σ_t Σ_i deadline drops
    retried_sum: jnp.ndarray    # (...,)   Σ_t Σ_i re-queued expired mass
    viol_sum: jnp.ndarray       # (...,)   Σ_t Σ_i deadline-expired mass
    misrouted_sum: jnp.ndarray  # (...,)   Σ_t Σ_i mass lost to inactive slots
    recovery_steps: jnp.ndarray # (...,)   steps draining post-outage backlog


def init_metric_accum(num_agents: int, batch_shape: tuple = ()) -> MetricAccum:
    """Zero accumulator for ``batch_shape`` cells of ``num_agents`` agents."""
    agent = jnp.zeros(batch_shape + (num_agents,), jnp.float32)
    scalar = jnp.zeros(batch_shape, jnp.float32)
    return MetricAccum(agent, agent, agent, agent, scalar, scalar, scalar,
                       scalar, scalar, scalar, scalar, scalar)


def accumulate_metrics(
    acc: MetricAccum,
    mask: jnp.ndarray,
    g: jnp.ndarray,
    served: jnp.ndarray,
    queue: jnp.ndarray,
    latency: jnp.ndarray,
    completed: jnp.ndarray,
    warm: jnp.ndarray,
    pending: jnp.ndarray,
    misrouted: jnp.ndarray | None = None,
    dropped: jnp.ndarray | None = None,
    retried: jnp.ndarray | None = None,
    viol: jnp.ndarray | None = None,
    recovery: jnp.ndarray | None = None,
) -> MetricAccum:
    """Fold one step's outputs into the running sums (O(N) work/memory).

    The failure-side inputs default to ``None`` — contributing nothing —
    so the no-failure program folds exactly the same sums as before."""
    backlogged = (queue * mask).sum(axis=-1) > 0
    msum = lambda x: 0.0 if x is None else (x * mask).sum(axis=-1)
    return MetricAccum(
        lat_sum=acc.lat_sum + latency,
        served_sum=acc.served_sum + served,
        queue_sum=acc.queue_sum + queue,
        completed_sum=acc.completed_sum + completed,
        alloc_sum=acc.alloc_sum + g.sum(axis=-1),
        warm_sum=acc.warm_sum + warm,
        stall_steps=acc.stall_steps
        + ((pending > 0) & backlogged).astype(jnp.float32),
        dropped_sum=acc.dropped_sum + msum(dropped),
        retried_sum=acc.retried_sum + msum(retried),
        viol_sum=acc.viol_sum + msum(viol),
        misrouted_sum=acc.misrouted_sum + msum(misrouted),
        recovery_steps=acc.recovery_steps
        + (0.0 if recovery is None else recovery),
    )


def finalize_metrics(
    acc: MetricAccum,
    num_steps: int,
    active: jnp.ndarray | None = None,
    workflow: Workflow | None = None,
    *,
    config: SimConfig,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """METRIC_NAMES reductions from the running sums — THE metric
    definition (unbatched; ``vmap`` it over a policy axis).

    ``trace_metrics`` feeds it sums over a materialized trace, the
    streaming scan feeds it the accumulated carry — either way there is
    exactly one formula per metric, so the two modes cannot drift.

    Returns (metric vector in METRIC_NAMES order, per-agent mean latency,
    per-agent mean throughput, per-agent mean queue).
    """
    m = jnp.ones(acc.lat_sum.shape[-1]) if active is None else active
    n_active = jnp.maximum(m.sum(), 1.0)
    mmean = lambda x: (x * m).sum() / n_active  # masked mean over agents
    per_lat = acc.lat_sum / num_steps
    per_tput = acc.served_sum / num_steps
    per_queue = acc.queue_sum / num_steps
    # Unclipped long-run latency: mean backlog over long-run service rate.
    longrun_rate = jnp.maximum(per_tput, _EPS)
    littles = mmean(per_queue / longrun_rate)
    lat_mean = mmean(per_lat)
    lat_std = jnp.sqrt(mmean((per_lat - lat_mean) ** 2))
    vec = jnp.stack([
        lat_mean,
        lat_std,
        per_tput.sum(),
        acc.alloc_sum / num_steps,
        mmean(per_queue),
        littles,
        (acc.completed_sum / num_steps * m).sum(),
        critical_path_latency(per_lat, workflow, m),
        billing_cost(acc.warm_sum, config.price_per_hour),
        acc.alloc_sum / jnp.maximum(acc.warm_sum, _EPS),
        acc.stall_steps,
        acc.warm_sum / num_steps,
        acc.dropped_sum / num_steps,
        acc.retried_sum / num_steps,
        acc.viol_sum / num_steps,
        acc.recovery_steps,
        acc.misrouted_sum / num_steps,
    ])
    return vec, per_lat, per_tput, per_queue


def trace_metrics(
    trace: SimTrace,
    active: jnp.ndarray | None = None,
    workflow: Workflow | None = None,
    *,
    config: SimConfig,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Table II + workflow + capacity reductions for one trace, jit/vmap-safe.

    Reduces the trace to a ``MetricAccum`` and finalizes — a thin wrapper
    over ``finalize_metrics``, the same finalizer the streaming scan uses,
    so trace mode and streaming mode share one metric definition.  Returns
    (metric vector in METRIC_NAMES order, per-agent mean latency, per-agent
    mean throughput, per-agent mean queue — the per-stage backlog of a
    workflow pipeline).  The single definition behind both ``summarize``
    and the sweep grids.

    ``active`` is the fleet's validity mask: per-agent means/stds weight by
    it, so padded slots (latency 0, throughput 0) never dilute the metrics.
    With the default all-ones mask this is exactly the unweighted reduction.
    ``workflow`` feeds the end-to-end metrics: ``sink_throughput`` counts
    requests *exiting* the workflow (served = sink throughput when nothing
    is routed) and ``critical_path_latency`` chains per-stage latencies
    along the routing DAG.  ``config`` prices the capacity metrics and is
    deliberately required — it must be the config the trace was produced
    under, or the cost column is silently priced wrong: ``cost`` bills the
    trace's warm-instance-seconds, ``utilization`` is the allocated
    fraction of the warm pool, and ``cold_start_stall_time`` counts the
    seconds the fleet sat backlogged while instances were still cold — the
    serverless tax no provisioned-cost model can see.
    """
    m = jnp.ones(trace.latency.shape[-1]) if active is None else active
    backlogged = (trace.queue * m).sum(axis=-1) > 0
    msum = lambda x: (x * m).sum(axis=-1).sum(axis=-1)
    acc = MetricAccum(
        lat_sum=trace.latency.sum(axis=0),
        served_sum=trace.served.sum(axis=0),
        queue_sum=trace.queue.sum(axis=0),
        completed_sum=trace.completed.sum(axis=0),
        alloc_sum=trace.allocation.sum(axis=-1).sum(axis=-1),
        warm_sum=trace.warm.sum(axis=0),  # 1 s steps: Σ_t warm(t) · 1 s
        stall_steps=((trace.pending > 0) & backlogged).sum().astype(jnp.float32),
        dropped_sum=msum(trace.dropped),
        retried_sum=msum(trace.retried),
        viol_sum=msum(trace.expired),
        misrouted_sum=msum(trace.misrouted),
        recovery_steps=trace.recovery.sum(axis=0),
    )
    return finalize_metrics(
        acc, trace.latency.shape[0], active, workflow, config=config
    )


def summarize(
    policy: str,
    trace: SimTrace,
    config: SimConfig = SimConfig(),
    active: jnp.ndarray | None = None,
    workflow: Workflow | None = None,
) -> SimSummary:
    """Table II metrics from a trace (``active`` masks padded agents)."""
    vec, per_agent_lat, per_agent_tput, per_agent_queue = trace_metrics(
        trace, active, workflow, config=config
    )
    m = dict(zip(METRIC_NAMES, (float(x) for x in vec)))
    return SimSummary.from_metrics(
        policy, m, per_agent_lat, per_agent_tput, per_agent_queue
    )


def run_policy(
    policy: str,
    arrivals: jnp.ndarray,
    fleet: Fleet,
    config: SimConfig = SimConfig(),
    workflow: Workflow | None = None,
    capacity: CapacityConfig | None = None,
    failures: FailureSpec | None = None,
) -> SimSummary:
    return summarize(
        policy,
        simulate(policy, arrivals, fleet, config, workflow, capacity, failures),
        config,
        fleet.active,
        workflow,
    )
