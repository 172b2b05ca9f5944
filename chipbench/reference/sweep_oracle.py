"""Plain float64 reference of one sweep cell: the fleet's queues stepped
in a Python loop under one allocation policy, and the cell's metric vector
reduced from the trajectories.

The queue dynamics, the nine policies and the metric definitions follow
the paper's model (arXiv:2512.22149, Algorithm 1 and Eq. (2)) as the
program states them: per step, every agent's intake is its exogenous
arrivals; the policy grants each agent a share ``g_i`` of ``g_total``;
agent ``i`` serves ``min(g_i * T_i, q_i + lam_i)``; the rest queues; its
latency is the backlog over the capacity, capped at ``latency_cap``.
Only the plain deployment is covered: no workflow routing, no autoscaler,
no failures.

``round_to`` (a numpy dtype such as ``ml_dtypes.bfloat16``) rounds every
state the loop carries to that precision after each step: the control
of the comparison, computed below the float32 the program states.
"""
from __future__ import annotations

import numpy as np

EPS = 1e-9

METRIC_NAMES = (
    "avg_latency", "latency_std", "total_throughput", "gpu_utilization",
    "mean_queue", "littles_law_latency", "sink_throughput",
    "critical_path_latency", "cost", "utilization", "cold_start_stall_time",
    "mean_warm_instances", "dropped", "retried", "slo_violations",
    "recovery_time", "misrouted",
)

POLICIES = (
    "static_equal", "round_robin", "adaptive", "water_filling", "predictive",
    "throughput_greedy", "objective_descent", "sqrt_demand", "ema_water_filling",
)


def _normalize(g, g_total):
    s = g.sum()
    return g * (g_total / max(s, EPS)) if s > g_total else g


def _adaptive(src, R, P, g_total):
    d = src * R / P
    if d.sum() <= 0:
        return np.zeros_like(src)
    return _normalize(np.maximum(R, d / d.sum() * g_total), g_total)


def _water_fill(pressure, R, g_total):
    if pressure.sum() <= 0:
        return np.zeros_like(pressure)
    prop = pressure / pressure.sum() * g_total
    return _normalize(np.maximum(np.where(pressure > 0, R, 0.0), prop), g_total)


def _throughput_greedy(q, lam, T, R, g_total):
    x = q + lam
    busy = x > 0
    g = np.where(busy, R, 0.0)
    need = np.where(busy, x / np.maximum(T, EPS), 0.0)
    extra = np.maximum(need - g, 0.0)
    residual = max(g_total - g.sum(), 0.0)
    order = np.argsort(-T, kind="stable")  # highest throughput first
    want = extra[order]
    grant_sorted = np.clip(residual - (np.cumsum(want) - want), 0.0, want)
    grant = np.zeros_like(g)
    grant[order] = grant_sorted
    return _normalize(g + grant, g_total)


def _objective_descent(q, lam, T, R, P, g_total, alpha=1.0, gamma=10.0,
                       steps=12, lr=0.05, latency_cap=1000.0):
    """Projected gradient descent on the one-step lookahead of Eq. (2),
    with the gradient written out by hand; a kink gets the 0.5/0.5 split."""
    n = len(T)
    x = q + lam
    busy = x > 0
    if not busy.any():
        return np.zeros(n)
    floor = np.where(busy, R, 0.0)

    def project(g):
        return _normalize(np.clip(g, floor, 1.0), g_total)

    def grad(g):
        c = g * T
        denom = np.maximum(c, 1e-6)
        new_q = x - np.minimum(c, x)
        r = new_q / denom
        ds_dc = np.where(c < x, 1.0, np.where(c > x, 0.0, 0.5))
        dden_dc = np.where(c > 1e-6, 1.0, np.where(c < 1e-6, 0.0, 0.5))
        dr_dc = (-ds_dc * denom - new_q * dden_dc) / denom ** 2
        dlat_dc = dr_dc * np.where(r < latency_cap, 1.0,
                                   np.where(r > latency_cap, 0.0, 0.5))
        return (alpha * dlat_dc / n - gamma * ds_dc) * T

    g = project(_adaptive(lam, R, P, g_total))
    for _ in range(steps):
        g = project(g - lr * grad(g))
    return g


def simulate(policy, arrivals, T, R, P, *, g_total=1.0, latency_cap=1000.0,
             ema_alpha=0.3, round_to=None):
    """Per-step trajectories (S, N) of one cell: allocation, served, queue,
    latency."""
    if policy not in POLICIES:
        raise ValueError(f"the reference has no policy {policy!r}")
    rnd = (lambda a: a) if round_to is None else (
        lambda a: np.asarray(a).astype(round_to).astype(np.float64))
    arrivals = np.asarray(arrivals, np.float64)
    T, R, P = (np.asarray(a, np.float64) for a in (T, R, P))
    steps, n = arrivals.shape
    q = np.zeros(n)
    ema = arrivals[0].copy()
    out = {k: np.empty((steps, n)) for k in ("allocation", "served", "queue", "latency")}
    for t in range(steps):
        lam = arrivals[t]
        if t > 0:  # the forecast is seeded with the first observation
            ema = rnd(ema_alpha * lam + (1 - ema_alpha) * ema)
        if policy == "static_equal":
            g = np.full(n, g_total / n)
        elif policy == "round_robin":
            g = np.zeros(n)
            g[t % n] = g_total
        elif policy in ("adaptive", "predictive"):
            g = _adaptive(lam if policy == "adaptive" else ema, R, P, g_total)
        elif policy == "water_filling":
            g = _water_fill((q + lam) / np.maximum(T, EPS), R, g_total)
        elif policy == "ema_water_filling":
            g = _water_fill((q + ema) / np.maximum(T, EPS), R, g_total)
        elif policy == "sqrt_demand":
            g = _water_fill(np.sqrt((q + lam) / np.maximum(T, EPS)), R, g_total)
        elif policy == "throughput_greedy":
            g = _throughput_greedy(q, lam, T, R, g_total)
        else:
            g = _objective_descent(q, lam, T, R, P, g_total)
        g = rnd(g)
        cap = rnd(g * T)
        served = rnd(np.minimum(cap, q + lam))
        q = rnd(q + lam - served)
        lat = rnd(np.minimum(q / np.maximum(cap, EPS), latency_cap))
        out["allocation"][t] = g
        out["served"][t] = served
        out["queue"][t] = q
        out["latency"][t] = lat
    return out


def metrics(traj, *, g_total=1.0, price_per_hour=0.72) -> np.ndarray:
    """The metric vector, in ``METRIC_NAMES`` order, of one cell's
    trajectories (every agent active, budget fixed at ``g_total``)."""
    steps = traj["latency"].shape[0]
    per_lat = traj["latency"].sum(0) / steps
    per_tput = traj["served"].sum(0) / steps
    per_queue = traj["queue"].sum(0) / steps
    alloc_sum = traj["allocation"].sum()
    warm_sum = g_total * steps
    lat_mean = per_lat.mean()
    return np.array([
        lat_mean,
        np.sqrt(((per_lat - lat_mean) ** 2).mean()),
        per_tput.sum(),
        alloc_sum / steps,
        per_queue.mean(),
        (per_queue / np.maximum(per_tput, EPS)).mean(),
        per_tput.sum(),             # every request leaves at its own agent
        per_lat.max(),              # one-stage paths: the slowest agent
        warm_sum / 3600.0 * price_per_hour,
        alloc_sum / max(warm_sum, EPS),
        0.0,                        # no autoscaler: nothing is ever cold
        warm_sum / steps,
        0.0, 0.0, 0.0, 0.0, 0.0,    # no failures, no routing
    ])
