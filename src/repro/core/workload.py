"""Workload (arrival-process) generators for the fleet simulator.

The paper simulates 100 one-second steps with fixed per-agent arrival rates
(80/40/45/25 rps) and a fixed random seed.  Constant arrivals reproduce
Table II exactly; Poisson, spike, diurnal and domination processes support
the robustness study (§V-B) and beyond-paper experiments.  Two further
beyond-paper processes feed the sweep grid (``core/sweep.py``):

* ``bursty``     — two-state Markov-modulated (on/off) arrivals, independent
                   per agent: each agent flips between a burst regime
                   (``on_factor``·rate) and a lull (``off_factor``·rate) with
                   geometric dwell times, the classic MMPP burstiness model.
* ``correlated`` — fleet-wide surges: one shared on/off Markov chain scales
                   *all* agents simultaneously, modelling a collaborative-
                   reasoning cascade where one user request fans out to every
                   agent at once.

Every generator returns an (S, N) float32 array of arrivals per step and is
deterministic given its PRNG key, so sweeps are exactly reproducible.

**In-scan synthesis** (the streaming kernel's input side): every arrival
process also exists as a *per-step* generator in the **workload registry**
(``@register_workload``, mirroring the allocation-policy registry) with the
uniform signature

    (t, rates, knobs, state, key_t) -> (lam (N,), new_state (N,))

dispatched by ``lax.switch`` on a ``WorkloadSpec``'s traced ``gen_id`` —
exactly the ``CapacityConfig.policy_id`` pattern.  Randomness is
counter-based and stateless: ``key_t = jax.random.fold_in(spec.key, t)``,
so step t's draw needs no (S, N) slab and no sequential RNG state — the
streaming scan (``simulator.simulate_stream_core``) computes each step's
arrivals *inside* the ``lax.scan`` body from the O(N) parameter row.
Generators with genuine temporal state (the ``bursty``/``correlated`` MMPP
chains) carry it in the scan carry as an (N,) float vector (``state``);
stateless generators pass it through untouched.  ``materialize`` scans the
very same per-step functions into the classic (S, N) tensor, so the
materialized path is bit-for-bit the synthesized one by construction — it
is the parity oracle, never a second implementation.

``synthetic_rates`` generates the *base rate vector itself* for arbitrary
fleet sizes: random per-agent proportions of a fixed aggregate load
(default: the paper's 190 rps), so agent-count scaling sweeps
(``core/sweep.py::sweep_fleets``) hold total demand constant while N grows.
It draws from the same ``jax.random.key(seed)`` convention as every
stochastic generator here — one documented seed path for rate vectors and
arrival draws alike.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans

# Σ of the paper's §IV-A arrival rates (80+40+45+25 rps).
PAPER_TOTAL_RATE = 190.0

# ``REPRO_SWEEP_SYNTH=0`` forces materialized arrivals everywhere, whatever
# the entry points were asked — the in-scan twin of ``REPRO_SWEEP_SHARD``.
SYNTH_ENV = "REPRO_SWEEP_SYNTH"


def synth_env_enabled() -> bool:
    """False iff ``REPRO_SWEEP_SYNTH=0`` (or ``false``/``off``) is set."""
    return os.environ.get(SYNTH_ENV, "").lower() not in ("0", "false", "off")


def synthetic_rates(
    num_agents: int,
    seed: int = 0,
    total_rate: float = PAPER_TOTAL_RATE,
    key: jax.Array | None = None,
) -> jnp.ndarray:
    """A reproducible per-agent rate vector summing to ``total_rate``.

    Proportions are drawn uniformly in [0.5, 1.5] and normalized, bounding
    any agent's share within 3x of any other's — heterogeneous but never
    degenerate, at any fleet size.

    The draw comes from ``jax.random.key(seed)`` (or an explicit ``key``) —
    the same counter-based convention as every stochastic generator in this
    module, so a sweep whose rate vectors and arrival draws descend from one
    key is exactly reproducible end to end.
    """
    if num_agents < 1:
        raise ValueError(f"num_agents must be >= 1, got {num_agents}")
    if key is None:
        key = jax.random.key(seed)
    w = jax.random.uniform(key, (num_agents,), minval=0.5, maxval=1.5)
    return jnp.asarray(total_rate * w / w.sum(), jnp.float32)


def constant(rates: jnp.ndarray, num_steps: int) -> jnp.ndarray:
    """lam_i(t) = rates_i for all t (reproduces the paper's Table II)."""
    rates = jnp.asarray(rates, jnp.float32)
    return jnp.broadcast_to(rates, (num_steps, rates.shape[0]))


def poisson(rates: jnp.ndarray, num_steps: int, key: jax.Array) -> jnp.ndarray:
    """Poisson(lam_i) arrivals per step, fixed seed for reproducibility."""
    rates = jnp.asarray(rates, jnp.float32)
    draws = jax.random.poisson(key, rates, shape=(num_steps, rates.shape[0]))
    return draws.astype(jnp.float32)


def spike(
    rates: jnp.ndarray,
    num_steps: int,
    spike_agent: int,
    spike_start: int,
    spike_len: int,
    magnitude: float = 10.0,
) -> jnp.ndarray:
    """10x arrival-rate spike on one agent (§V-B adaptation-speed test)."""
    base = constant(rates, num_steps)
    t = jnp.arange(num_steps)[:, None]
    in_spike = (t >= spike_start) & (t < spike_start + spike_len)
    col = jnp.arange(base.shape[1])[None, :] == spike_agent
    return jnp.where(in_spike & col, base * magnitude, base)


def scaled(rates: jnp.ndarray, num_steps: int, factor: float) -> jnp.ndarray:
    """Uniformly scaled demand, e.g. 3x overload (§V-B normalization test)."""
    return constant(jnp.asarray(rates, jnp.float32) * factor, num_steps)


def dominated_rates(rates: jnp.ndarray, agent: int, share: float = 0.9) -> jnp.ndarray:
    """Redistribute a rate vector so one agent carries ``share`` of the total
    (the §V-B monopolization rates; shared by ``dominated`` and
    ``dominated_spec``)."""
    rates = jnp.asarray(rates, jnp.float32)
    total = rates.sum()
    n = rates.shape[0]
    if n < 2:
        raise ValueError(
            "dominated needs >= 2 agents: with a single agent there is "
            f"nobody to redistribute the remaining {1.0 - share:.2f} share to"
        )
    others = jnp.full((n,), total * (1.0 - share) / (n - 1), jnp.float32)
    return others.at[agent].set(total * share)


def dominated(rates: jnp.ndarray, num_steps: int, agent: int, share: float = 0.9) -> jnp.ndarray:
    """One agent carries `share` of total requests (§V-B monopolization test)."""
    return constant(dominated_rates(rates, agent, share), num_steps)


def diurnal(rates: jnp.ndarray, num_steps: int, period: int = 50, depth: float = 0.5) -> jnp.ndarray:
    """Sinusoidal load swing — beyond-paper, exercises the predictive policy."""
    rates = jnp.asarray(rates, jnp.float32)
    t = jnp.arange(num_steps, dtype=jnp.float32)[:, None]
    mod = 1.0 + depth * jnp.sin(2.0 * jnp.pi * t / period)
    return rates[None, :] * mod


def bursty(
    rates: jnp.ndarray,
    num_steps: int,
    key: jax.Array,
    on_factor: float = 4.0,
    off_factor: float = 0.25,
    p_enter: float = 0.08,
    p_exit: float = 0.25,
) -> jnp.ndarray:
    """Markov-modulated on/off bursts, independent per agent.

    Each agent carries a two-state chain: a lull enters a burst with
    probability ``p_enter`` per step, a burst ends with ``p_exit``; the
    arrival rate is ``on_factor``·rate in a burst and ``off_factor``·rate in
    a lull.  Mean dwell times are geometric (1/p), giving heavy temporal
    correlation that constant/Poisson workloads lack.
    """
    rates = jnp.asarray(rates, jnp.float32)
    n = rates.shape[0]
    key_init, key_steps = jax.random.split(key)
    state0 = jax.random.bernoulli(key_init, 0.5, (n,))
    u = jax.random.uniform(key_steps, (num_steps, n))

    def step(state, ut):
        nxt = jnp.where(state, ut >= p_exit, ut < p_enter)
        factor = jnp.where(nxt, on_factor, off_factor)
        return nxt, factor

    _, factors = jax.lax.scan(step, state0, u)
    return rates[None, :] * factors


def correlated(
    rates: jnp.ndarray,
    num_steps: int,
    key: jax.Array,
    surge_factor: float = 4.0,
    p_enter: float = 0.05,
    p_exit: float = 0.2,
) -> jnp.ndarray:
    """Fleet-wide multi-agent surges: all agents spike *together*.

    A single shared on/off Markov chain multiplies every agent's rate by
    ``surge_factor`` during a surge — the arrival pattern of a collaborative
    reasoning burst, where one upstream request cascades to the whole fleet.
    """
    rates = jnp.asarray(rates, jnp.float32)
    u = jax.random.uniform(key, (num_steps,))

    def step(state, ut):
        nxt = jnp.where(state, ut >= p_exit, ut < p_enter)
        factor = jnp.where(nxt, surge_factor, 1.0)
        return nxt, factor

    _, factors = jax.lax.scan(step, jnp.asarray(False), u)
    return rates[None, :] * factors[:, None]


# -- workload registry: per-step generators for in-scan synthesis ------------

# Fixed-width generator parameter row: every spec carries KNOB_SLOTS floats
# whose meaning is per-generator (documented on each ``*_spec`` constructor);
# unused slots are zero.  A fixed width is what lets heterogeneous scenario
# columns stack into one (W, KNOB_SLOTS) leaf and dispatch via one switch.
KNOB_SLOTS = 4

# ``fold_in`` slot reserved for the generator's *initial* state draw; step t
# folds t, so any horizon below this never collides with it.
_INIT_FOLD = 0x7FFFFFFF


class _WorkloadGen(NamedTuple):
    step: Callable  # (t, rates, knobs, state, key_t) -> (lam (N,), state (N,))
    init: Callable  # (rates, knobs, key_init) -> state (N,)
    block: Callable  # (ts, rates, knobs, state, keys, unroll) -> (rows (B,N), state)


_WORKLOADS: dict[str, _WorkloadGen] = {}


def _zeros_init(rates, knobs, key):
    return jnp.zeros_like(rates)


def _scan_block(step: Callable) -> Callable:
    """Generic block synthesis: scan the step function over the block.

    The bit-identity *reference* — B sequential ``step`` calls with the
    per-t keys.  Every specialized block implementation below must match
    this exactly; it remains the default for generators registered without
    one.
    """

    def block(ts, rates, knobs, state, keys, unroll):
        def body(st, xs):
            t, key_t = xs
            lam, st = step(t, rates, knobs, st, key_t)
            return st, lam

        new_state, rows = jax.lax.scan(body, state, (ts, keys), unroll=unroll)
        return rows, new_state

    return block


def _batched_block(step: Callable) -> Callable:
    """Block synthesis for *stateless* generators: one vmapped call.

    A stateless step returns its state untouched, so the whole (B, N) block
    is a single batched evaluation over ``(ts, keys)`` — one RNG kernel per
    block instead of B sequential ones.  ``vmap`` of a deterministic
    function of ``(t, key_t)`` equals stacking the B scalar calls, so the
    rows are bit-identical to the scanned reference.
    """

    def block(ts, rates, knobs, state, keys, unroll):
        rows, _ = jax.vmap(lambda t, k: step(t, rates, knobs, state, k))(
            ts, keys
        )
        return rows, state

    return block


def register_workload(
    name: str,
    init: Callable | None = None,
    block: Callable | None = None,
    stateless: bool = False,
):
    """Register a per-step arrival generator under ``name``.

    ``fn(t, rates, knobs, state, key_t) -> (lam, state)`` computes step t's
    (N,) arrival row from the O(N) parameter row alone: ``key_t`` is already
    ``fold_in(spec.key, t)`` (counter-based — no sequential RNG state), and
    ``state`` is the (N,) float32 carry vector for generators with temporal
    state (MMPP chains); stateless generators return it untouched.  ``init``
    draws the t=0 state (default: zeros) from ``fold_in(spec.key,
    _INIT_FOLD)``.  Registration order defines ``workload_id`` — the
    ``lax.switch`` branch index, exactly like the policy registry.

    ``stateless=True`` marks a generator whose step ignores and passes
    through ``state``: its ``step_block`` branch becomes one vmapped batched
    call (``_batched_block``).  Stateful generators may register an explicit
    ``block`` that presamples their draws in batch and scans only the cheap
    state recurrence; omitting both falls back to the scanned reference
    (``_scan_block``).  Whatever the route, a block must be bit-identical to
    B sequential step calls — the parity property in
    tests/test_workload_synthesis.py enforces it per generator.
    """

    def deco(fn: Callable) -> Callable:
        if name in _WORKLOADS:
            raise ValueError(f"workload generator {name!r} already registered")
        if stateless:
            if block is not None:
                raise ValueError("stateless generators derive their block")
            blk = _batched_block(fn)
        else:
            blk = _scan_block(fn) if block is None else block
        _WORKLOADS[name] = _WorkloadGen(
            fn, _zeros_init if init is None else init, blk
        )
        return fn

    return deco


def workload_names() -> tuple[str, ...]:
    """Registered generator names, in registration (= switch-branch) order."""
    return tuple(_WORKLOADS)


def workload_id(name: str) -> int:
    """The ``lax.switch`` branch index of a registered generator."""
    if name not in _WORKLOADS:
        raise ValueError(
            f"unknown workload generator {name!r}; registered: {workload_names()}"
        )
    return list(_WORKLOADS).index(name)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WorkloadSpec:
    """An arrival process as an O(N) parameter row — the in-scan twin of a
    ``Scenario``'s (S, N) tensor.

    Array leaves (so specs stack/vmap/shard exactly like arrivals did):

    * ``gen_id``   — () int32 registry index, the ``lax.switch`` selector
      (the ``CapacityConfig.policy_id`` pattern);
    * ``rates``    — (N,) float32 base rates;
    * ``knobs``    — (KNOB_SLOTS,) float32 generator parameters;
    * ``key_data`` — (2,) uint32 raw PRNG key (``jax.random.key_data``; raw
      so it stacks under ``jnp.stack`` like any other leaf).

    ``name`` and ``num_steps`` are static aux data: the horizon is a trace
    constant (it sizes the scan), never a traced value.
    """

    gen_id: jnp.ndarray
    rates: jnp.ndarray
    knobs: jnp.ndarray
    key_data: jnp.ndarray
    name: str = "workload"
    num_steps: int = 100

    def tree_flatten(self):
        return (
            (self.gen_id, self.rates, self.knobs, self.key_data),
            (self.name, self.num_steps),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, name=aux[0], num_steps=aux[1])


def make_spec(
    gen: str,
    rates,
    num_steps: int,
    key: jax.Array | None = None,
    knobs: Sequence[float] = (),
    name: str | None = None,
) -> WorkloadSpec:
    """Build a ``WorkloadSpec`` for a registered generator.

    ``key`` defaults to ``jax.random.key(0)`` for deterministic generators
    (they never consume it).  ``num_steps`` must stay below the reserved
    init fold slot (2³¹−1) so step and init draws cannot collide.
    """
    with spans.span("workload.make_spec", gen=gen):
        if len(knobs) > KNOB_SLOTS:
            raise ValueError(f"at most {KNOB_SLOTS} knobs, got {len(knobs)}")
        if not 0 < int(num_steps) < _INIT_FOLD:
            raise ValueError(
                f"num_steps must be in (0, 2**31-1), got {num_steps}"
            )
        kv = np.zeros(KNOB_SLOTS, np.float32)
        kv[: len(knobs)] = np.asarray(knobs, np.float32)
        if key is None:
            key = jax.random.key(0)
        return WorkloadSpec(
            gen_id=jnp.asarray(workload_id(gen), jnp.int32),
            rates=jnp.asarray(rates, jnp.float32),
            knobs=jnp.asarray(kv),
            key_data=jax.random.key_data(key),
            name=gen if name is None else name,
            num_steps=int(num_steps),
        )


def workload_init(spec: WorkloadSpec) -> jnp.ndarray:
    """The generator's t=0 carry state, drawn from the reserved init fold."""
    key_init = jax.random.fold_in(
        jax.random.wrap_key_data(spec.key_data), _INIT_FOLD
    )
    return jax.lax.switch(
        spec.gen_id,
        [g.init for g in _WORKLOADS.values()],
        spec.rates, spec.knobs, key_init,
    )


def workload_step(
    spec: WorkloadSpec,
    state: jnp.ndarray,
    t: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Step t's (N,) arrival row + next carry state, by switch dispatch.

    Pure in t: the key is ``fold_in(spec.key, t)``, so the same (spec,
    state, t) triple always yields the same draw — inside a scan, under
    vmap, or called eagerly (the oracle's python loop).  Under ``vmap`` the
    switch lowers to evaluate-all-branches-and-select, so every scenario
    column pays every registered generator.  The sweep keeps it anyway:
    one scan over all columns is cheaper on the chip than one scan per
    generator (``sweep._stream_grid``).
    """
    key_t = jax.random.fold_in(jax.random.wrap_key_data(spec.key_data), t)
    return jax.lax.switch(
        spec.gen_id,
        [g.step for g in _WORKLOADS.values()],
        t, spec.rates, spec.knobs, state, key_t,
    )


# Unroll cap for the generators' small recurrence scans (the MMPP state
# threading in the block implementations above): XLA CPU compile time grows
# superlinearly in unrolled-body size, so blocks longer than this run as a
# rolled loop over MAX_UNROLL-step unrolled chunks.  Only these tiny bodies
# unroll at all — unrolling the streaming kernel's full physics step was
# measured a net loss on XLA CPU (~1.7× slower execution and ~6× longer
# compiles at B=128 than the rolled loop), so the simulator keeps its inner
# scan rolled.
MAX_UNROLL = 16


def step_block(
    spec: WorkloadSpec,
    state: jnp.ndarray,
    ts: jnp.ndarray,
    unroll: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Synthesize a whole (B, N) arrival block in one call.

    ``ts`` is the (B,) int32 step-counter vector of the block.  The per-step
    keys are the same counter-based ``fold_in(spec.key, t)`` draws the
    scalar path makes — batched through ``vmap`` (pure integer hashing, so
    bit-exact under batching) — and each generator's registered *block*
    function synthesizes its rows from them: stateless generators as one
    vmapped batched call (one RNG kernel per block instead of B), stateful
    MMPP generators by presampling their uniforms in batch and scanning
    only the cheap state recurrence, unrolled ``unroll`` steps at a time
    (default ``min(B, MAX_UNROLL)``).  One ``lax.switch`` dispatch per
    block replaces B per-step dispatches; every route is bit-identical to
    B sequential ``workload_step`` calls (same draws per ``(spec, t)``,
    same recurrence ops, same state threading — the parity property in
    tests/test_workload_synthesis.py checks each generator).
    """
    b = ts.shape[0]
    u = min(b, MAX_UNROLL) if unroll is None else int(unroll)
    keys = jax.vmap(
        lambda t: jax.random.fold_in(jax.random.wrap_key_data(spec.key_data), t)
    )(ts)

    def branch(g: _WorkloadGen):
        return lambda: g.block(ts, spec.rates, spec.knobs, state, keys, u)

    rows, new_state = jax.lax.switch(
        spec.gen_id, [branch(g) for g in _WORKLOADS.values()]
    )
    return rows, new_state


@functools.partial(jax.jit, static_argnames=("num_steps",))
def _materialize_jit(spec: WorkloadSpec, num_steps: int) -> jnp.ndarray:
    def step(state, t):
        lam, state = workload_step(spec, state, t)
        return state, lam

    _, rows = jax.lax.scan(
        step, workload_init(spec), jnp.arange(num_steps, dtype=jnp.int32)
    )
    return rows


def materialize(spec: WorkloadSpec, num_steps: int | None = None) -> jnp.ndarray:
    """Scan the per-step generator into the classic (S, N) arrival tensor.

    This IS the materialized parity path: it runs the very same registered
    step functions the streaming scan runs in its body, so synthesized and
    materialized arrivals are bit-for-bit identical by construction — there
    is no second generator implementation to drift.
    """
    steps = spec.num_steps if num_steps is None else int(num_steps)
    return _materialize_jit(spec, steps)


def stack_specs(specs: Sequence[WorkloadSpec], name: str = "stacked") -> WorkloadSpec:
    """Stack specs along a new leading axis (the scenario column of a sweep).

    All horizons must agree (the scan length is one static trace constant);
    leaves gain the axis exactly as ``jnp.stack`` over arrivals tensors did,
    so stacked specs shard/vmap under the same partition specs as arrivals.
    Already-stacked specs stack again — the (F, W, ...) fleet-sweep block.
    """
    specs = list(specs)
    if not specs:
        raise ValueError("stack_specs needs at least one spec")
    steps = {s.num_steps for s in specs}
    if len(steps) != 1:
        raise ValueError(f"specs must share one horizon, got {sorted(steps)}")
    return WorkloadSpec(
        gen_id=jnp.stack([s.gen_id for s in specs]),
        rates=jnp.stack([s.rates for s in specs]),
        knobs=jnp.stack([s.knobs for s in specs]),
        key_data=jnp.stack([s.key_data for s in specs]),
        name=name,
        num_steps=steps.pop(),
    )


# -- registered generators ---------------------------------------------------
#
# Per-generator ``knobs`` layout (unused slots zero):
#   constant    —
#   poisson     —
#   spike       (agent, start, length, magnitude)
#   diurnal     (period, depth)
#   bursty      (on_factor, off_factor, p_enter, p_exit)
#   correlated  (surge_factor, p_enter, p_exit)
#
# ``scaled``/``dominated``/``overload`` scenarios are ``constant`` specs over
# transformed rate vectors — a rate transform, not a distinct process.


@register_workload("constant", stateless=True)
def _constant_step(t, rates, knobs, state, key_t):
    return rates, state


@register_workload("poisson", stateless=True)
def _poisson_step(t, rates, knobs, state, key_t):
    draws = jax.random.poisson(key_t, rates, shape=rates.shape)
    return draws.astype(jnp.float32), state


@register_workload("spike", stateless=True)
def _spike_step(t, rates, knobs, state, key_t):
    agent, start, length, magnitude = knobs[0], knobs[1], knobs[2], knobs[3]
    tf = t.astype(jnp.float32)  # exact for any horizon below 2**24
    in_spike = (tf >= start) & (tf < start + length)
    col = jnp.arange(rates.shape[0], dtype=jnp.float32) == agent
    return jnp.where(in_spike & col, rates * magnitude, rates), state


@register_workload("diurnal", stateless=True)
def _diurnal_step(t, rates, knobs, state, key_t):
    period, depth = knobs[0], knobs[1]
    mod = 1.0 + depth * jnp.sin(2.0 * jnp.pi * t.astype(jnp.float32) / period)
    return rates * mod, state


def _bursty_init(rates, knobs, key):
    return jax.random.bernoulli(key, 0.5, rates.shape).astype(jnp.float32)


def _bursty_advance(rates, knobs, state, u):
    # The one MMPP recurrence implementation — step and block both go
    # through it, so the two paths cannot drift.
    on, off, p_enter, p_exit = knobs[0], knobs[1], knobs[2], knobs[3]
    nxt = jnp.where(state > 0.5, u >= p_exit, u < p_enter)
    lam = rates * jnp.where(nxt, on, off)
    return lam, nxt.astype(jnp.float32)


def _bursty_block(ts, rates, knobs, state, keys, unroll):
    # Presample the whole block's uniforms in one batched draw; only the
    # cheap where-threading recurrence stays sequential.
    u = jax.vmap(lambda k: jax.random.uniform(k, rates.shape))(keys)

    def body(st, u_t):
        lam, st = _bursty_advance(rates, knobs, st, u_t)
        return st, lam

    new_state, rows = jax.lax.scan(body, state, u, unroll=unroll)
    return rows, new_state


@register_workload("bursty", init=_bursty_init, block=_bursty_block)
def _bursty_step(t, rates, knobs, state, key_t):
    return _bursty_advance(
        rates, knobs, state, jax.random.uniform(key_t, rates.shape)
    )


def _correlated_advance(rates, knobs, state, u):
    surge, p_enter, p_exit = knobs[0], knobs[1], knobs[2]
    nxt = jnp.where(state[0] > 0.5, u >= p_exit, u < p_enter)
    lam = rates * jnp.where(nxt, surge, 1.0)
    # The shared chain's single bit, broadcast so every generator's state
    # leaf has one (N,) shape under the switch.
    return lam, jnp.broadcast_to(nxt.astype(jnp.float32), rates.shape)


def _correlated_block(ts, rates, knobs, state, keys, unroll):
    u = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)

    def body(st, u_t):
        lam, st = _correlated_advance(rates, knobs, st, u_t)
        return st, lam

    new_state, rows = jax.lax.scan(body, state, u, unroll=unroll)
    return rows, new_state


@register_workload("correlated", block=_correlated_block)
def _correlated_step(t, rates, knobs, state, key_t):
    return _correlated_advance(
        rates, knobs, state, jax.random.uniform(key_t, ())
    )


# -- spec constructors (one per scenario type) -------------------------------


def constant_spec(rates, num_steps: int, name: str = "constant") -> WorkloadSpec:
    return make_spec("constant", rates, num_steps, name=name)


def poisson_spec(rates, num_steps: int, key: jax.Array) -> WorkloadSpec:
    return make_spec("poisson", rates, num_steps, key=key)


def spike_spec(
    rates,
    num_steps: int,
    spike_agent: int,
    spike_start: int,
    spike_len: int,
    magnitude: float = 10.0,
) -> WorkloadSpec:
    return make_spec(
        "spike", rates, num_steps,
        knobs=(float(spike_agent), float(spike_start), float(spike_len), magnitude),
    )


def scaled_spec(rates, num_steps: int, factor: float, name: str = "scaled") -> WorkloadSpec:
    rates = jnp.asarray(rates, jnp.float32) * factor
    return make_spec("constant", rates, num_steps, name=name)


def dominated_spec(
    rates, num_steps: int, agent: int, share: float = 0.9
) -> WorkloadSpec:
    return make_spec(
        "constant", dominated_rates(rates, agent, share), num_steps,
        name="dominated",
    )


def diurnal_spec(
    rates, num_steps: int, period: int = 50, depth: float = 0.5
) -> WorkloadSpec:
    return make_spec("diurnal", rates, num_steps, knobs=(float(period), depth))


def bursty_spec(
    rates,
    num_steps: int,
    key: jax.Array,
    on_factor: float = 4.0,
    off_factor: float = 0.25,
    p_enter: float = 0.08,
    p_exit: float = 0.25,
) -> WorkloadSpec:
    return make_spec(
        "bursty", rates, num_steps, key=key,
        knobs=(on_factor, off_factor, p_enter, p_exit),
    )


def correlated_spec(
    rates,
    num_steps: int,
    key: jax.Array,
    surge_factor: float = 4.0,
    p_enter: float = 0.05,
    p_exit: float = 0.2,
) -> WorkloadSpec:
    return make_spec(
        "correlated", rates, num_steps, key=key,
        knobs=(surge_factor, p_enter, p_exit),
    )


def scenario_specs(
    rates, num_steps: int = 100, seed: int = 0
) -> tuple[WorkloadSpec, ...]:
    """The standard 8-scenario library as O(N) specs — the in-scan twin of
    ``sweep.scenario_library`` (same names, same scenario semantics; the
    stochastic per-step draws come from fold_in counters rather than one
    pre-split (S, N) block, so values differ from the legacy tensors but are
    equally reproducible from ``seed``)."""
    rates = jnp.asarray(rates, jnp.float32)
    n = int(rates.shape[0])
    k_poisson, k_bursty, k_corr = jax.random.split(jax.random.key(seed), 3)
    return (
        constant_spec(rates, num_steps),
        poisson_spec(rates, num_steps, k_poisson),
        spike_spec(
            rates, num_steps,
            spike_agent=n - 1,
            spike_start=num_steps // 2,
            spike_len=max(num_steps // 10, 1),
        ),
        scaled_spec(rates, num_steps, 3.0, name="overload_3x"),
        dominated_spec(rates, num_steps, agent=0, share=0.9),
        diurnal_spec(rates, num_steps),
        bursty_spec(rates, num_steps, k_bursty),
        correlated_spec(rates, num_steps, k_corr),
    )


def fleet_scenario_specs(
    rate_vectors: Sequence,
    n_max: int,
    num_steps: int = 100,
    seed: int = 0,
) -> tuple[tuple[str, ...], tuple[tuple[WorkloadSpec, ...], ...]]:
    """Per-fleet spec columns at a common padded width — the spec twin of
    ``sweep.fleet_scenario_library``.

    Rate transforms (spike target, dominated redistribution) are computed at
    each fleet's *true* width, then the rate vector is zero-padded to
    ``n_max``: every registered generator yields exactly zero arrivals for a
    zero-rate agent, so padded slots stay inert without any masking beyond
    what the simulator already applies.  Returns ``(scenario_names,
    specs[fleet][scenario])``; stack with ``stack_specs`` for the (F, W)
    grid or ``materialize`` each for the parity arm.
    """
    names: tuple[str, ...] | None = None
    rows = []
    for rates in rate_vectors:
        r = np.asarray(rates, np.float32)
        true_n = int(r.shape[-1])
        if true_n > n_max:
            raise ValueError(f"rate vector wider ({true_n}) than n_max={n_max}")
        padded = np.pad(r, (0, n_max - true_n))
        k_poisson, k_bursty, k_corr = jax.random.split(jax.random.key(seed), 3)
        dom = np.zeros(n_max, np.float32)
        dom[:true_n] = np.asarray(dominated_rates(r, agent=0, share=0.9))
        lib = (
            constant_spec(padded, num_steps),
            poisson_spec(padded, num_steps, k_poisson),
            spike_spec(
                padded, num_steps,
                spike_agent=true_n - 1,
                spike_start=num_steps // 2,
                spike_len=max(num_steps // 10, 1),
            ),
            scaled_spec(padded, num_steps, 3.0, name="overload_3x"),
            make_spec("constant", dom, num_steps, name="dominated"),
            diurnal_spec(padded, num_steps),
            bursty_spec(padded, num_steps, k_bursty),
            correlated_spec(padded, num_steps, k_corr),
        )
        lib_names = tuple(s.name for s in lib)
        if names is None:
            names = lib_names
        elif names != lib_names:
            raise ValueError("scenario spec libraries diverged across fleets")
        rows.append(lib)
    return names, tuple(rows)
