"""Plain reference of a sweep cell's arrivals: the (S, N) arrival rows of
each scenario, drawn from the traffic file's parameters and the grid's
seed.

Each scenario multiplies a base rate vector, step by step:

* ``constant``: the rates, times ``rate_factor`` (``overload_3x``), or
  redistributed so that ``dominant_agent`` carries ``share`` of the total;
* ``poisson``: Poisson(rates) draws;
* ``spike``: ``magnitude`` times the rate of one agent for a stretch;
* ``diurnal``: ``1 + depth * sin(2 pi t / period)`` times the rates;
* ``bursty``: per agent, a two-state chain (a lull enters a burst with
  ``p_enter``, a burst ends with ``p_exit``) scales the rate by ``on`` or
  ``off``;
* ``correlated``: one chain shared by every agent scales all rates by
  ``surge`` during a surge.

Randomness is counter-based, as the traffic file states: the grid seed
gives ``split(key(seed), 3)``, one key for each of the ``poisson``,
``bursty`` and ``correlated`` scenarios; step ``t`` draws from
``fold_in(key, t)``, and a chain's first state from
``fold_in(key, 2**31 - 1)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INIT_FOLD = 0x7FFFFFFF


def keys(seed: int):
    return jax.random.split(jax.random.key(seed), 3)


def _steps(key, num_steps: int):
    return jax.vmap(lambda t: jax.random.fold_in(key, t))(
        jnp.arange(num_steps, dtype=jnp.int32))


def scenario_rates(scn: dict, rates: np.ndarray) -> np.ndarray:
    """The scenario's base rate vector (float32, as the traffic states)."""
    r = np.asarray(rates, np.float32)
    if "rate_factor" in scn:
        return r * np.float32(scn["rate_factor"])
    if "dominant_agent" in scn:
        total = r.sum(dtype=np.float32)
        share = np.float32(scn["share"])
        out = np.full(r.shape, total * (np.float32(1.0) - share) / np.float32(len(r) - 1),
                      np.float32)
        out[int(scn["dominant_agent"])] = total * share
        return out
    return r


KNOBS = {
    "constant": (),
    "poisson": (),
    "spike": ("agent", "start", "length", "magnitude"),
    "diurnal": ("period", "depth"),
    "bursty": ("on", "off", "p_enter", "p_exit"),
    "correlated": ("surge", "p_enter", "p_exit"),
}


def knobs(scn: dict, n: int, num_steps: int) -> tuple[float, ...]:
    """The scenario's parameters in the order ``KNOBS`` names them, with
    the spike's ``last`` agent, ``half`` start and ``tenth`` length
    resolved for ``n`` agents and ``num_steps`` steps."""
    named = {
        "last": n - 1, "half": num_steps // 2, "tenth": max(num_steps // 10, 1),
    }
    values = (scn[k] for k in KNOBS[scn["gen"]])
    return tuple(float(named[v] if isinstance(v, str) else v) for v in values)


def arrivals(scn: dict, rates, num_steps: int, seed: int) -> np.ndarray:
    """(S, N) float64 arrival rows of one scenario."""
    r = scenario_rates(scn, rates)
    n = r.shape[0]
    gen = scn["gen"]
    t = np.arange(num_steps, dtype=np.float32)[:, None]
    if gen == "constant":
        return np.broadcast_to(r, (num_steps, n)).astype(np.float64)
    if gen == "spike":
        agent, start, length, magnitude = knobs(scn, n, num_steps)
        agent, start, length = int(agent), int(start), int(length)
        out = np.broadcast_to(r, (num_steps, n)).copy()
        out[start:start + length, agent] = r[agent] * np.float32(magnitude)
        return out.astype(np.float64)
    if gen == "diurnal":
        rows = jnp.asarray(r)[None, :] * (
            1.0 + np.float32(scn["depth"]) * jnp.sin(
                2.0 * jnp.pi * jnp.asarray(t) / np.float32(scn["period"])))
        return np.asarray(rows, np.float64)
    key = keys(seed)[int(scn["key"])]
    step_keys = _steps(key, num_steps)
    rj = jnp.asarray(r)
    if gen == "poisson":
        rows = jax.jit(jax.vmap(lambda k: jax.random.poisson(k, rj, shape=rj.shape)))(step_keys)
        return np.asarray(rows, np.float64)
    if gen == "bursty":
        on, off = np.float32(scn["on"]), np.float32(scn["off"])
        u = np.asarray(jax.jit(jax.vmap(lambda k: jax.random.uniform(k, rj.shape)))(step_keys))
        state = np.asarray(jax.random.bernoulli(
            jax.random.fold_in(key, INIT_FOLD), 0.5, rj.shape))
        out = np.empty((num_steps, n), np.float32)
        for i in range(num_steps):
            state = np.where(state, u[i] >= np.float32(scn["p_exit"]),
                             u[i] < np.float32(scn["p_enter"]))
            out[i] = r * np.where(state, on, off)
        return out.astype(np.float64)
    if gen == "correlated":
        u = np.asarray(jax.jit(jax.vmap(lambda k: jax.random.uniform(k, ())))(step_keys))
        state = False
        out = np.empty((num_steps, n), np.float32)
        for i in range(num_steps):
            state = (u[i] >= np.float32(scn["p_exit"])) if state else (
                u[i] < np.float32(scn["p_enter"]))
            out[i] = r * (np.float32(scn["surge"]) if state else np.float32(1.0))
        return out.astype(np.float64)
    raise ValueError(f"the reference has no arrival process {gen!r}")
