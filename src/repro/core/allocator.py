"""GPU-fraction allocation policies.

``adaptive_allocation`` is the paper's contribution (Algorithm 1), kept
faithful line-for-line.  ``static_equal`` and ``round_robin`` are the paper's
baselines.  The remaining policies are *beyond-paper* extensions recorded
separately in EXPERIMENTS.md §Perf:

* ``water_filling``        — equalizes Little's-law latency q/(g·T) across
                             agents (minimizes the max-latency agent).
* ``predictive_adaptive``  — Algorithm 1 driven by an EMA forecast of the
                             arrival rate instead of the instantaneous rate.
* ``throughput_greedy``    — maximizes Σ served subject to minimum
                             guarantees (upper bound on raw throughput).

All policies are pure jnp, O(N), and jittable; each returns g with
Σ g <= g_total and g >= 0.

Every policy is also registered in the **policy registry** (bottom of this
module) under a uniform signature

    (t, lam_obs, lam_ema, queue, fleet, g_total) -> g

``g_total`` may be a static python float (the provisioned budget) **or a
traced scalar**: under the serverless capacity layer (``core/capacity.py``)
the budget is the warm-pool trajectory ``g_total(t) = warm(t)``, including
exact zeros when the pool scales to zero — every registry entry must (and
does) emit Σ g <= g_total(t) and g >= 0 for any time-varying traced budget
(property-tested in tests/test_policy_invariants.py).

Under workflow routing (``core/routing.py``) ``lam_obs`` is the agent's
*total* intake — exogenous arrivals plus requests routed from upstream
agents — and ``queue`` carries any backlog of routed traffic, so
queue-pressure policies (``water_filling``, ``throughput_greedy``,
``objective_descent``) and rate-driven ones (``adaptive``, ``predictive``)
all see endogenous demand without any per-policy changes.

The registry is the single source of truth for dispatch: the simulator's
``lax.switch`` branches, the serving engine's per-tick dispatch, and the
vmapped sweep grid (``core/sweep.py``) are all built from it, so adding a
policy here makes it available everywhere with no other edits.

Registry entries are **mask-aware**: ``fleet.active`` (the agent-validity
mask, see ``core/agents.py``) gates every input, so padded slots contribute
zero demand and receive exactly g = 0, and ``static_equal``/``round_robin``
divide by the *traced* active-agent count rather than a Python int — the
whole registry therefore vmaps over a batched fleet axis of heterogeneous
(padded) fleet sizes.
"""
from __future__ import annotations

from typing import Callable, Sequence, TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from repro.core.agents import Fleet

_EPS = 1e-9


def _normalize_capacity(g: jnp.ndarray, g_total: float) -> jnp.ndarray:
    """Algorithm 1 lines 19-25: proportional scale-down iff over capacity."""
    allocated = g.sum()
    scale = jnp.where(allocated > g_total, g_total / jnp.maximum(allocated, _EPS), 1.0)
    return g * scale


def adaptive_allocation(
    lam: jnp.ndarray,
    min_gpu: jnp.ndarray,
    priority: jnp.ndarray,
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Paper Algorithm 1, faithful.

    demand        d_i = lam_i * R_i / P_i                 (line 5)
    proportional  g_i = d_i / D_total * G_total           (line 15)
    minimum       g_i = max(R_i, g_i)                     (line 16)
    normalize     g *= G_total / G_allocated if over      (lines 21-25)
    All-idle fleets (D_total == 0) release everything     (lines 10-12).
    """
    demand = lam * min_gpu / priority
    d_total = demand.sum()
    prop = demand / jnp.maximum(d_total, _EPS) * g_total
    g = jnp.maximum(min_gpu, prop)
    g = _normalize_capacity(g, g_total)
    return jnp.where(d_total > 0, g, jnp.zeros_like(g))


def static_equal(num_agents: int, g_total: float = 1.0) -> jnp.ndarray:
    """Baseline: G_total/N to every agent, regardless of load."""
    return jnp.full((num_agents,), g_total / num_agents, jnp.float32)


def masked_static_equal(active: jnp.ndarray, g_total: float = 1.0) -> jnp.ndarray:
    """``static_equal`` over the *traced* active-agent count: G_total/N_active
    to each unmasked agent, 0 to padding.  Identical to ``static_equal`` when
    the mask is all-ones; vmappable over a batched fleet axis."""
    n_active = jnp.maximum(active.sum(), 1.0)
    return (active * (g_total / n_active)).astype(jnp.float32)


def round_robin(t: jnp.ndarray, num_agents: int, g_total: float = 1.0) -> jnp.ndarray:
    """Baseline: 100% of the GPU to agent (t mod N) — '100% sequential'."""
    return jax.nn.one_hot(jnp.mod(t, num_agents), num_agents, dtype=jnp.float32) * g_total


def masked_round_robin(
    t: jnp.ndarray, active: jnp.ndarray, g_total: float = 1.0
) -> jnp.ndarray:
    """``round_robin`` over active agents only: the full GPU goes to the
    (t mod N_active)-th *unmasked* agent.  With an all-ones mask the active
    ranks are 0..N-1 and this reduces exactly to ``round_robin``.

    The rotation is integer arithmetic: a float32 mod would lose tick
    precision past 2^24 and skip agents in a long-running engine.
    """
    n_active = jnp.maximum(active.sum().astype(jnp.int32), 1)
    rank = (jnp.cumsum(active) - 1.0).astype(jnp.int32)  # rank among active
    chosen = jnp.mod(jnp.asarray(t).astype(jnp.int32), n_active)
    return (active * jnp.where(rank == chosen, g_total, 0.0)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Beyond-paper policies.
# ---------------------------------------------------------------------------

def water_filling(
    queue: jnp.ndarray,
    lam: jnp.ndarray,
    base_throughput: jnp.ndarray,
    min_gpu: jnp.ndarray,
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Equalize projected latency (q + lam)/(g·T) across busy agents.

    Solving for equal latency gives g_i ∝ (q_i + lam_i)/T_i; minimum
    guarantees and capacity normalization are applied as in Algorithm 1 so
    the policy is a drop-in replacement.
    """
    pressure = (queue + lam) / jnp.maximum(base_throughput, _EPS)
    total = pressure.sum()
    prop = pressure / jnp.maximum(total, _EPS) * g_total
    g = jnp.maximum(jnp.where(pressure > 0, min_gpu, 0.0), prop)
    g = _normalize_capacity(g, g_total)
    return jnp.where(total > 0, g, jnp.zeros_like(g))


def sqrt_demand(
    queue: jnp.ndarray,
    lam: jnp.ndarray,
    base_throughput: jnp.ndarray,
    min_gpu: jnp.ndarray,
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Square-root fair share: g_i ∝ √((q_i + lam_i)/T_i).

    The sublinear weight is the classic square-root rule (cf. √N staffing):
    heavy agents still get more GPU, but the concave weighting shields
    light agents from starvation during skewed bursts — a cheap middle
    ground between ``static_equal`` and ``water_filling``.  Floors and
    capacity normalization follow Algorithm 1, keyed on the *raw* pressure
    (same busy set as water-filling).
    """
    pressure = (queue + lam) / jnp.maximum(base_throughput, _EPS)
    weight = jnp.sqrt(pressure)
    total = weight.sum()
    prop = weight / jnp.maximum(total, _EPS) * g_total
    g = jnp.maximum(jnp.where(pressure > 0, min_gpu, 0.0), prop)
    g = _normalize_capacity(g, g_total)
    return jnp.where(total > 0, g, jnp.zeros_like(g))


def ema_water_filling(
    queue: jnp.ndarray,
    lam_ema: jnp.ndarray,
    base_throughput: jnp.ndarray,
    min_gpu: jnp.ndarray,
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Latency-EMA-weighted water-filling: equalize the *forecast* drain
    time (q_i + ema_i)/(g_i·T_i) instead of the instantaneous one.

    Same fixed point as ``water_filling`` under steady load, but the EMA
    smoothing keeps allocations from thrashing on bursty arrivals — the
    predictive counterpart of water-filling, exactly as ``predictive`` is
    the EMA counterpart of ``adaptive``.
    """
    return water_filling(queue, lam_ema, base_throughput, min_gpu, g_total)


def _committed(x: jnp.ndarray) -> jnp.ndarray:
    """Pin ``x`` to its rounded float32 value against FMA contraction.

    XLA CPU freely contracts ``a·b + c`` into a fused multiply-add (one
    rounding) or not (two roundings) depending on how the surrounding
    program vectorizes — so the *same* expression can differ by 1 ulp
    between two compilations (e.g. the in-scan-synthesis and materialized
    arms of the streaming kernel, which promise bit-identical metrics).
    ``lax.optimization_barrier`` does not help: it is erased before LLVM
    codegen, where the contraction happens.  A select on a data-dependent
    predicate does — ``x == x`` is only false for NaN, which no simplifier
    can prove away, and a select between the multiply and the add breaks
    the contraction pattern while preserving values exactly (NaN stays
    NaN via the on-false branch).
    """
    return jnp.where(x == x, x, jnp.full_like(x, jnp.nan))


def ema_forecast(lam_prev_ema: jnp.ndarray, lam_obs: jnp.ndarray, alpha: float = 0.3) -> jnp.ndarray:
    """One EMA update; the predictive policy's workload model.

    Both products are committed to rounded f32 before the add so the
    update has fixed two-rounding semantics in every compilation — the
    EMA is the one recurrence whose 1-ulp contraction drift was observed
    to break the synthesized-vs-materialized bit-identity contract.
    """
    return _committed(alpha * lam_obs) + _committed((1.0 - alpha) * lam_prev_ema)


def predictive_adaptive(
    lam_ema: jnp.ndarray,
    min_gpu: jnp.ndarray,
    priority: jnp.ndarray,
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Algorithm 1 on the EMA-forecast arrival rate (paper §VI future work)."""
    return adaptive_allocation(lam_ema, min_gpu, priority, g_total)


def throughput_greedy(
    queue: jnp.ndarray,
    lam: jnp.ndarray,
    base_throughput: jnp.ndarray,
    min_gpu: jnp.ndarray,
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Maximize Σ_i min(g_i·T_i, q_i + lam_i) s.t. g >= R on busy agents.

    Greedy water-fill by throughput density: after satisfying minimums,
    residual capacity goes to agents in decreasing T_i order until each
    agent's backlog is covered (g_i·T_i == q_i + lam_i).  O(N log N) for the
    sort; still trivially real-time.
    """
    busy = (queue + lam) > 0
    g = jnp.where(busy, min_gpu, 0.0)
    # Fraction needed to clear the whole backlog this step.
    need = jnp.where(busy, (queue + lam) / jnp.maximum(base_throughput, _EPS), 0.0)
    extra_need = jnp.maximum(need - g, 0.0)
    residual = jnp.maximum(g_total - g.sum(), 0.0)
    # Allocate residual to the highest-throughput agents first.
    order = jnp.argsort(-base_throughput)
    sorted_need = extra_need[order]
    cum_before = jnp.cumsum(sorted_need) - sorted_need
    grant_sorted = jnp.clip(residual - cum_before, 0.0, sorted_need)
    grant = jnp.zeros_like(grant_sorted).at[order].set(grant_sorted)
    g = g + grant
    return _normalize_capacity(g, g_total)


def objective_descent(
    queue: jnp.ndarray,
    lam: jnp.ndarray,
    base_throughput: jnp.ndarray,
    min_gpu: jnp.ndarray,
    priority: jnp.ndarray,
    g_total: float = 1.0,
    *,
    alpha: float = 1.0,
    gamma: float = 10.0,
    steps: int = 12,
    lr: float = 0.05,
    latency_cap: float = 1000.0,
    active: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Directly optimize the paper's Eq. (2) by projected gradient.

    One-step lookahead objective  alpha·L(g) − gamma·H(g)  (cost term is
    constant in g for a provisioned device), differentiated through the
    smooth queue dynamics; projection = clip to [R_i·busy, 1] then
    capacity-normalize.  Still O(N) per iteration, `steps` iterations —
    ~12x Algorithm 1's cost, far under the paper's 1 ms budget.

    ``active`` masks out padded agents: their latency leaves the objective
    mean and projection pins them at g = 0, so a padded fleet descends the
    same trajectory as its unpadded original.
    """
    mask = jnp.ones_like(queue) if active is None else active
    busy = mask * (queue + lam) > 0
    floor = jnp.where(busy, min_gpu, 0.0)
    n_active = jnp.maximum(mask.sum(), 1.0)

    def objective(g):
        capacity = g * base_throughput
        served = jnp.minimum(capacity, queue + lam) * mask
        new_q = (queue + lam) * mask - served
        lat = jnp.minimum(new_q / jnp.maximum(capacity, 1e-6), latency_cap)
        return alpha * (lat * mask).sum() / n_active - gamma * served.sum()

    grad_fn = jax.grad(objective)

    def project(g):
        g = jnp.clip(g, floor, 1.0) * mask
        return _normalize_capacity(g, g_total)

    g0 = adaptive_allocation(lam, min_gpu, priority, g_total)
    g0 = jnp.where(busy.any(), g0, jnp.zeros_like(g0))

    def body(_, g):
        return project(g - lr * grad_fn(g))

    g = jax.lax.fori_loop(0, steps, body, project(g0))
    return jnp.where(busy.any(), g, jnp.zeros_like(g))


# ---------------------------------------------------------------------------
# Policy registry — the single dispatch table for the whole codebase.
#
# Each entry is a thin adapter over the pure functions above with the uniform
# signature ``(t, lam_obs, lam_ema, queue, fleet, g_total) -> g``; the pure
# functions stay faithful to Algorithm 1 and are still importable directly.
# ---------------------------------------------------------------------------

PolicyFn = Callable[..., jnp.ndarray]

_REGISTRY: dict[str, PolicyFn] = {}


def register_policy(name: str) -> Callable[[PolicyFn], PolicyFn]:
    """Register ``fn(t, lam_obs, lam_ema, queue, fleet, g_total) -> g``.

    Registration alone makes the policy reachable from ``simulate()``, the
    serving engine, and the sweep grid; registry order defines the stable
    integer policy id used by ``lax.switch``.
    """

    def deco(fn: PolicyFn) -> PolicyFn:
        if name in _REGISTRY:
            raise ValueError(f"policy {name!r} already registered")
        _REGISTRY[name] = fn
        return fn

    return deco


def policy_names() -> tuple[str, ...]:
    """All registered policies, in registration (= policy-id) order."""
    return tuple(_REGISTRY)


def policy_id(name: str) -> int:
    """Integer id of a registered policy (its index in ``policy_names()``)."""
    get_policy(name)
    return policy_names().index(name)


def get_policy(name: str) -> PolicyFn:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown policy {name!r}; registered policies: {policy_names()}"
        )
    return _REGISTRY[name]


def dispatch(
    name: str,
    t: jnp.ndarray,
    lam_obs: jnp.ndarray,
    lam_ema: jnp.ndarray,
    queue: jnp.ndarray,
    fleet: "Fleet",
    g_total: float = 1.0,
) -> jnp.ndarray:
    """Eager by-name dispatch (the serving-engine path)."""
    return get_policy(name)(t, lam_obs, lam_ema, queue, fleet, g_total)


def policy_switch(
    policy_id: jnp.ndarray,
    t: jnp.ndarray,
    lam_obs: jnp.ndarray,
    lam_ema: jnp.ndarray,
    queue: jnp.ndarray,
    fleet: "Fleet",
    g_total: float = 1.0,
    names: Sequence[str] | None = None,
) -> jnp.ndarray:
    """Traced dispatch over the registry (the simulator / sweep path).

    ``names`` pins the branch order for a jitted caller; it defaults to the
    live registry order.
    """
    names = policy_names() if names is None else tuple(names)
    branches = tuple(
        (lambda fn=_REGISTRY[n]: fn(t, lam_obs, lam_ema, queue, fleet, g_total))
        for n in names
    )
    return jax.lax.switch(policy_id, branches)


def policy_stack(
    t: jnp.ndarray,
    lam_obs: jnp.ndarray,
    lam_ema: jnp.ndarray,
    queue: jnp.ndarray,
    fleet: "Fleet",
    g_total,
    names: Sequence[str] | None = None,
) -> jnp.ndarray:
    """Evaluate each named policy exactly once on its own (P, N) state row.

    The streaming sweep kernel's dispatch (``simulator.simulate_stream_core``):
    the grid's policy axis is the name order, so instead of vmapping a
    ``lax.switch`` over policy ids — which lowers to evaluate-ALL-branches-
    and-select, P² allocator evaluations per grid — the registry is unrolled
    and policy ``names[i]`` sees only row ``i`` of the batched state.  O(P)
    policy evaluations per step, by construction.

    ``lam_obs`` / ``lam_ema`` / ``queue`` carry a leading policy axis (P, N);
    ``g_total`` is either one shared budget (python float or traced scalar)
    or a per-policy (P,) vector of traced warm-pool budgets (each policy row
    drives its own autoscaler trajectory under elastic capacity).
    """
    names = policy_names() if names is None else tuple(names)
    per_row_budget = jnp.ndim(g_total) == 1
    rows = []
    for i, name in enumerate(names):
        fn = get_policy(name)
        budget = g_total[i] if per_row_budget else g_total
        with jax.named_scope(name):
            rows.append(fn(t, lam_obs[i], lam_ema[i], queue[i], fleet, budget))
    return jnp.stack(rows)


def policy_stack_blocks(
    t: jnp.ndarray,
    lam_obs: jnp.ndarray,
    lam_ema: jnp.ndarray,
    queue: jnp.ndarray,
    fleet: "Fleet",
    g_total,
    names: Sequence[str],
    num_blocks: int,
    block_index: jnp.ndarray,
) -> jnp.ndarray:
    """``policy_stack`` for ONE contiguous block of the name list, selected
    by a *traced* index — the policy-axis-sharded dispatch.

    Under ``shard_map`` every device traces the same program, so the static
    name unrolling of ``policy_stack`` cannot differ per device; what can is
    a ``lax.switch`` on ``lax.axis_index("policy")``.  Branch k statically
    unrolls name block k (policies ``names[k*p : (k+1)*p]``), so each device
    still evaluates each of its P/num_blocks policies exactly once per step
    — the O(P) dispatch guarantee survives the mesh split, and total trace
    cost across branches stays O(P).

    The state rows (``lam_obs``/``lam_ema``/``queue``, and ``g_total`` when
    per-row) are the **block-local** (P/num_blocks, N) rows, not the full
    stack — the caller already holds only its shard.
    """
    names = tuple(names)
    if num_blocks <= 0 or len(names) % num_blocks:
        raise ValueError(
            f"{len(names)} policies do not split into {num_blocks} equal blocks"
        )
    size = len(names) // num_blocks
    branches = tuple(
        (lambda group=names[k * size:(k + 1) * size]: policy_stack(
            t, lam_obs, lam_ema, queue, fleet, g_total, group
        ))
        for k in range(num_blocks)
    )
    return jax.lax.switch(block_index, branches)


# Every entry gates its inputs with ``fleet.active`` and hard-masks its
# output, so padded slots contribute zero demand and receive exactly g = 0.

@register_policy("static_equal")
def _static_equal_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    return masked_static_equal(fleet.active, g_total)


@register_policy("round_robin")
def _round_robin_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    return masked_round_robin(t, fleet.active, g_total)


@register_policy("adaptive")
def _adaptive_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return adaptive_allocation(lam_obs * m, fleet.min_gpu * m, fleet.priority, g_total) * m


@register_policy("water_filling")
def _water_filling_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return water_filling(
        queue * m, lam_obs * m, fleet.base_throughput, fleet.min_gpu * m, g_total
    ) * m


@register_policy("predictive")
def _predictive_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return predictive_adaptive(lam_ema * m, fleet.min_gpu * m, fleet.priority, g_total) * m


@register_policy("throughput_greedy")
def _throughput_greedy_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return throughput_greedy(
        queue * m, lam_obs * m, fleet.base_throughput, fleet.min_gpu * m, g_total
    ) * m


@register_policy("objective_descent")
def _objective_descent_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return objective_descent(
        queue * m, lam_obs * m, fleet.base_throughput, fleet.min_gpu * m,
        fleet.priority, g_total, active=m,
    ) * m


@register_policy("sqrt_demand")
def _sqrt_demand_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return sqrt_demand(
        queue * m, lam_obs * m, fleet.base_throughput, fleet.min_gpu * m, g_total
    ) * m


@register_policy("ema_water_filling")
def _ema_water_filling_entry(t, lam_obs, lam_ema, queue, fleet, g_total):
    m = fleet.active
    return ema_water_filling(
        queue * m, lam_ema * m, fleet.base_throughput, fleet.min_gpu * m, g_total
    ) * m


def __getattr__(attr: str):
    # POLICY_NAMES is derived from the registry, never hand-maintained.
    if attr == "POLICY_NAMES":
        return policy_names()
    raise AttributeError(f"module {__name__!r} has no attribute {attr!r}")
