"""Multi-agent serving engine: the paper's allocator as a first-class
scheduler over a fleet of real models.

The TPU-native reading of "allocate GPU fraction g_i to agent i" (DESIGN.md
§3) is per-tick *token budgets*: every scheduler tick the engine

  1. observes per-agent arrivals and queue depths,
  2. runs the allocation policy (Algorithm 1 by default),
  3. grants agent i a compute budget of ``g_i * budget_tokens`` decode
     tokens (prefills are charged their prompt length),
  4. steps each agent's batched prefill/decode within its budget,
  5. records the same metrics as the paper's simulator (latency,
     throughput, allocation, queue length, cost),
  6. with a ``Workflow`` (``core/routing.py``): routes each *finished*
     request to its downstream runtimes — the generated tokens become the
     child request's prompt, fractional routing weights accumulate as
     credit and spawn whole child requests deterministically, and the
     children count as next-tick arrivals, exactly like the simulator's
     endogenous-arrival path,
  7. with a ``CapacityConfig`` (``core/capacity.py``): runs the warm-pool
     autoscaler each tick *before* the allocation policy — the tick's token
     budget is ``warm(t) · budget_tokens`` (``budget_tokens`` is per
     instance), so a scaled-to-zero pool decodes nothing and a cold-starting
     pool stalls exactly as in the simulator; billing is warm-instance-ticks
     through the same ``billing_cost`` helper.

The same engine runs reduced configs on the CPU (examples/serve_fleet.py,
Pallas kernels replaced by their jnp references) and published-width
configs on one TPU chip (chip_smoke.py, ``launch/serve.py::ONE_CHIP_FLEET``),
where prefill and decode run the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import allocator as alloc
from repro.core import capacity as cap_mod
from repro.core import failures as fail_mod
from repro.core.agents import Fleet, T4_PRICE_PER_HOUR
from repro.core.capacity import CapacityConfig, billing_cost
from repro.core.failures import FailureSpec
from repro.core.routing import Workflow, check_workflow
from repro.models.model import ModelApi


@dataclasses.dataclass
class Request:
    agent: str
    prompt: np.ndarray           # (prompt_len,) int32
    max_new_tokens: int
    arrival_tick: int
    id: int = -1
    tokens_out: list = dataclasses.field(default_factory=list)
    finish_tick: int = -1
    parent_id: int = -1          # upstream request that spawned this one
    retries: int = 0             # deadline retries consumed so far


@dataclasses.dataclass
class AgentRuntime:
    """One model + its queue + fixed decode batch slots."""

    name: str
    api: ModelApi
    params: object
    max_len: int
    batch_slots: int
    queue: deque = dataclasses.field(default_factory=deque)
    active: list = dataclasses.field(default_factory=list)  # per-slot Request|None
    caches: object = None
    pos: np.ndarray | None = None            # per-slot next position
    _prefill_jit: Callable | None = None
    _decode_jit: Callable | None = None

    def __post_init__(self):
        self.active = [None] * self.batch_slots
        self.pos = np.zeros(self.batch_slots, np.int64)

    def free_slots(self):
        return [i for i, r in enumerate(self.active) if r is None]


def _pad_to(x, n, fill=0):
    return np.concatenate([x, np.full(n - len(x), fill, x.dtype)])


class FleetEngine:
    def __init__(
        self,
        fleet: Fleet,
        runtimes: dict[str, AgentRuntime],
        policy: str = "adaptive",
        budget_tokens: int = 64,
        g_total: float = 1.0,
        ema_alpha: float = 0.3,
        workflow: Workflow | None = None,
        capacity: CapacityConfig | None = None,
        num_gpus: float = 1.0,
        price_per_hour: float = T4_PRICE_PER_HOUR,
        failures: FailureSpec | None = None,
    ):
        assert set(fleet.names) == set(runtimes)
        alloc.get_policy(policy)  # fail fast on unregistered policies
        if workflow is not None:
            check_workflow(workflow, fleet.num_agents)
        if capacity is not None:
            cap_mod.check_capacity(capacity, g_total, num_gpus)
        else:
            cap_mod.check_budget_ceiling(g_total, num_gpus)
        failures = fail_mod.resolve_failures(failures)
        if failures is not None:
            if failures.batched:
                raise ValueError(
                    "FleetEngine takes a single FailureSpec; stacked specs "
                    "only flow through sweep(..., failures=[...])"
                )
            fail_mod.check_failures(failures)
        self.fleet = fleet
        self.runtimes = [runtimes[n] for n in fleet.names]
        self.policy = policy
        self.ema_alpha = ema_alpha
        self.budget_tokens = budget_tokens
        self.g_total = g_total
        self.workflow = workflow
        self.capacity = capacity
        self.num_gpus = num_gpus
        self.price_per_hour = price_per_hour
        self.failures = failures
        # Failure-chain state + counters (same chains as the simulator:
        # ``failure_uniforms`` is counter-based in the tick, so an engine
        # run and a simulator run on the same spec see identical draws).
        self._rev_on = 0.0
        self._down = np.zeros(fleet.num_agents)
        self.dropped = 0
        self.retried = 0
        self.slo_violations = 0
        self._deadline = (
            None if failures is None else
            np.broadcast_to(
                np.asarray(failures.deadline_s, np.float64),
                (fleet.num_agents,),
            ).copy()
        )
        # Warm-pool state: the same eager ``capacity_step`` the simulator
        # scans over, so engine and simulator cannot drift.
        self._cap_state = cap_mod.init_capacity_state(g_total)
        self.tick = 0
        self._next_id = 0
        self._arrivals_this_tick = np.zeros(fleet.num_agents)
        self._ema = np.zeros(fleet.num_agents)
        self._ema_seeded = False
        # Fractional routing credit per (upstream, downstream) pair: whole
        # child requests spawn when a cell accumulates >= 1.  The routed
        # weight per finished request is fixed, so it is materialized on
        # the host once rather than per tick.
        self._route_credit = np.zeros((fleet.num_agents, fleet.num_agents))
        self._route_weights = (
            None if workflow is None else
            np.asarray(workflow.route, np.float64)
            * np.asarray(workflow.fan_out, np.float64)[:, None]
        )
        self._source_flags = (
            None if workflow is None else np.asarray(workflow.source, np.float64)
        )
        self.history: list[dict] = []
        self.completed: list[Request] = []

    # -- request intake ------------------------------------------------------

    def submit(self, agent: str, prompt: np.ndarray, max_new_tokens: int,
               parent_id: int = -1):
        idx = self.fleet.names.index(agent)
        # Same contract as the simulator, which zeroes exogenous arrivals at
        # non-source agents: outside traffic may only enter at sources.
        # Routed children (parent_id >= 0) are the endogenous path and land
        # wherever the matrix sends them.
        if (self.workflow is not None and parent_id < 0
                and self._source_flags[idx] == 0.0):
            raise ValueError(
                f"agent {agent!r} is not a source of workflow "
                f"{self.workflow.name!r}; exogenous requests may only enter "
                "at source agents"
            )
        # Routed children are submitted while tick T is still being served
        # but only become servable (and are counted in lam) at T+1 — stamp
        # them with their effective arrival, matching the simulator's
        # endogenous-arrival-at-t+1 semantics.
        arrival = self.tick + 1 if parent_id >= 0 else self.tick
        req = Request(agent, np.asarray(prompt, np.int32), max_new_tokens, arrival,
                      id=self._next_id, parent_id=parent_id)
        self._next_id += 1
        self.runtimes[idx].queue.append(req)
        self._arrivals_this_tick[idx] += 1
        return req

    # -- allocation ----------------------------------------------------------

    def _forecast(self, lam: np.ndarray) -> jnp.ndarray:
        """Same EMA semantics as the simulator's scan: seed with the first
        observation, update thereafter — at the first tick the policy sees
        lam_ema == lam instead of a drifted zero-seeded forecast."""
        lam_j = jnp.asarray(lam, jnp.float32)
        if not self._ema_seeded:
            ema_j = lam_j
            self._ema_seeded = True
        else:
            ema_j = alloc.ema_forecast(
                jnp.asarray(self._ema, jnp.float32), lam_j, self.ema_alpha
            )
        self._ema = np.asarray(ema_j)
        return ema_j

    def _capacity_tick(
        self, lam_tot: float, ema_tot: float, queue_tot: float
    ) -> tuple[float, float]:
        """One warm-pool autoscaler update; returns (warm, pending).  The
        simulator's exact ``capacity_step``, run eagerly per tick."""
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        self._cap_state, warm, pending = cap_mod.capacity_step(
            self._cap_state, self.capacity, jnp.asarray(self.tick),
            f32(lam_tot), f32(ema_tot), f32(queue_tot),
            self.g_total, self.num_gpus,
        )
        return float(warm), float(pending)

    def _allocate(
        self, lam: np.ndarray, queues: np.ndarray, ema_j: jnp.ndarray,
        g_total_t: float,
    ) -> np.ndarray:
        t = jnp.asarray(self.tick)
        lam_j, q_j = jnp.asarray(lam, jnp.float32), jnp.asarray(queues, jnp.float32)
        g = alloc.dispatch(self.policy, t, lam_j, ema_j, q_j, self.fleet, g_total_t)
        return np.asarray(g)

    # -- failure injection ---------------------------------------------------

    def _failure_tick(self) -> tuple[float, np.ndarray]:
        """Advance the revocation/outage chains for this tick.

        Returns ``(phi, up)``: the fraction of warm capacity revoked and
        the per-agent availability gate.  Also claws revoked instances
        out of the warm-pool state so an elastic autoscaler must
        re-provision them through its cold-start pipeline — the engine
        analogue of the simulator's post-step ``warm *= (1 - phi)``.
        """
        if self.failures is None:
            return 0.0, np.ones(self.fleet.num_agents)
        u_rev, u_down = fail_mod.failure_uniforms(
            self.failures, self.tick, self.fleet.num_agents
        )
        phi, up, rev_nxt, down_nxt = fail_mod.advance_failures(
            self.failures, self.tick, self._rev_on, self._down, u_rev, u_down
        )
        self._rev_on = float(rev_nxt)
        self._down = np.asarray(down_nxt, np.float64)
        phi = float(phi)
        if phi > 0.0 and self.capacity is not None:
            st = self._cap_state
            self._cap_state = cap_mod.CapacityState(
                st.warm * (1.0 - phi), st.pipeline, st.idle_s
            )
        return phi, np.asarray(up, np.float64)

    def _enforce_deadlines(self):
        """Retry or drop queued requests whose sojourn exceeds the deadline.

        A request waiting longer than its agent's ``deadline_s`` (ticks)
        violates its SLO: while it has retry budget left it re-enters the
        back of the queue with a fresh arrival stamp, afterwards it is
        dropped.  In-service (admitted) requests are past queueing and are
        never expired — matching the fluid model, where only backlog mass
        is subject to the deadline.
        """
        if self.failures is None:
            return
        budget = int(np.clip(
            float(np.asarray(self.failures.retry_budget)),
            0, fail_mod.RETRY_CLASSES - 1,
        ))
        for i, rt in enumerate(self.runtimes):
            deadline = self._deadline[i]
            if deadline <= 0 or not rt.queue:
                continue
            survivors = deque()
            while rt.queue:
                req = rt.queue.popleft()
                if self.tick - req.arrival_tick <= deadline:
                    survivors.append(req)
                    continue
                self.slo_violations += 1
                if req.retries < budget:
                    req.retries += 1
                    req.arrival_tick = self.tick
                    survivors.append(req)
                    self.retried += 1
                else:
                    self.dropped += 1
            rt.queue = survivors

    # -- workflow routing ----------------------------------------------------

    def _route_finished(self, finished: list[Request]) -> int:
        """Fan finished requests out to downstream runtimes.

        Each finished request at agent i adds ``route[i] * fan_out[i]`` to
        the per-edge credit; every whole unit of credit spawns one child
        request (prompt = the parent's generated tokens) via ``submit``, so
        children are counted as next-tick arrivals — the engine analogue of
        the simulator's ``arrivals_endogenous = (served * fan_out) @ route``.
        """
        if self.workflow is None or not finished:
            return 0
        spawned = 0
        for req in finished:
            i = self.fleet.names.index(req.agent)
            self._route_credit[i] += self._route_weights[i]
            for j in np.nonzero(self._route_credit[i] >= 1.0)[0]:
                k = int(self._route_credit[i, j])
                self._route_credit[i, j] -= k
                prompt = np.asarray(req.tokens_out, np.int32)
                if prompt.size == 0:
                    prompt = req.prompt
                for _ in range(k):
                    self.submit(self.fleet.names[j], prompt,
                                req.max_new_tokens, parent_id=req.id)
                    spawned += 1
        return spawned

    # -- model stepping ------------------------------------------------------

    def _admit(self, rt: AgentRuntime, budget: int) -> int:
        """Prefill queued requests into free slots; returns tokens spent."""
        spent = 0
        while rt.queue and rt.free_slots():
            req = rt.queue[0]
            cost = len(req.prompt)
            if spent + cost > budget:
                break
            rt.queue.popleft()
            slot = rt.free_slots()[0]
            self._prefill_into_slot(rt, slot, req)
            spent += cost
        return spent

    def _prefill_into_slot(self, rt: AgentRuntime, slot: int, req: Request):
        cfg = rt.api.cfg
        s = len(req.prompt)
        batch = {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}
        if cfg.frontend == "vision":
            fe = min(cfg.frontend_tokens, s)
            batch["frontend_embeds"] = jnp.zeros((1, fe, cfg.d_model), jnp.bfloat16)
        if cfg.arch_type == "encdec":
            batch["frontend_embeds"] = jnp.zeros((1, 64, cfg.d_model), jnp.bfloat16)
        if rt._prefill_jit is None:
            ml = rt.max_len
            rt._prefill_jit = jax.jit(lambda p, b: rt.api.prefill(p, b, ml))
        logits, caches1 = rt._prefill_jit(rt.params, batch)
        tok = int(jnp.argmax(logits[0]))
        req.tokens_out.append(tok)
        if rt.caches is None:
            rt.caches = self._empty_caches(rt)
        rt.caches = _scatter_slot(rt.caches, caches1, slot)
        rt.active[slot] = req
        rt.pos[slot] = s

    def _empty_caches(self, rt: AgentRuntime):
        from repro.models.params import init_params

        decls = rt.api.cache_decls(rt.batch_slots, rt.max_len)
        return init_params(decls, jax.random.key(0), dtype=jnp.bfloat16)

    def _decode_once(self, rt: AgentRuntime) -> int:
        """One batched decode step over occupied slots; returns tokens made."""
        occupied = [i for i, r in enumerate(rt.active) if r is not None]
        if not occupied:
            return 0
        tokens = np.zeros(rt.batch_slots, np.int32)
        for i in occupied:
            tokens[i] = rt.active[i].tokens_out[-1]
        pos = int(max(rt.pos[i] for i in occupied))
        if rt._decode_jit is None:
            ml = rt.max_len
            rt._decode_jit = jax.jit(
                lambda p, c, t, pp: rt.api.decode_step(p, c, t, pp, ml)
            )
        logits, rt.caches = rt._decode_jit(
            rt.params, rt.caches, jnp.asarray(tokens), jnp.int32(pos)
        )
        made = 0
        lg = np.asarray(jax.device_get(logits))
        for i in occupied:
            req = rt.active[i]
            req.tokens_out.append(int(lg[i].argmax()))
            rt.pos[i] += 1
            made += 1
            if len(req.tokens_out) >= req.max_new_tokens or rt.pos[i] >= rt.max_len - 1:
                req.finish_tick = self.tick
                self.completed.append(req)
                rt.active[i] = None
        return made

    # -- main loop -----------------------------------------------------------

    def step(self):
        self._enforce_deadlines()
        lam = self._arrivals_this_tick.copy()
        self._arrivals_this_tick[:] = 0.0
        queues = np.array(
            [len(rt.queue) + sum(r is not None for r in rt.active) for rt in self.runtimes],
            np.float32,
        )
        ema_j = self._forecast(lam)
        if self.capacity is not None:
            warm, pending = self._capacity_tick(
                float(lam.sum()), float(np.asarray(ema_j).sum()),
                float(queues.sum()),
            )
        else:
            warm, pending = self.g_total, 0.0
        phi, up = self._failure_tick()
        # Revoked capacity gates the tick's token budget exactly like the
        # simulator's g_eff = g · up with cap_eff scaled by (1 - phi).
        warm_eff = warm * (1.0 - phi)
        g = self._allocate(lam, queues, ema_j, warm_eff)
        served = np.zeros(len(self.runtimes))
        done_before = len(self.completed)
        for i, rt in enumerate(self.runtimes):
            if up[i] < 0.5:
                # Agent outage: queue (and in-flight slots) preserved,
                # nothing admitted or decoded this tick.
                continue
            # g sums to at most the warm pool, so the fleet-wide spend is
            # capped at warm · budget_tokens: the warm pool gates the
            # token budget.
            budget = int(round(g[i] * self.budget_tokens))
            spent = self._admit(rt, budget)
            while spent < budget:
                made = self._decode_once(rt)
                if made == 0:
                    break
                spent += made
                served[i] += made
        # Requests that finished this tick flow downstream; their children
        # land in _arrivals_this_tick, i.e. they arrive at tick+1.
        routed = self._route_finished(self.completed[done_before:])
        self.history.append(
            {"tick": self.tick, "allocation": g.tolist(), "arrivals": lam.tolist(),
             "queues": queues.tolist(), "decode_tokens": served.tolist(),
             "routed": routed, "warm": warm, "pending": pending,
             "revoked_frac": phi, "down": (up < 0.5).sum().item()}
        )
        self.tick += 1

    # -- metrics (same definitions as the paper simulator) --------------------

    def metrics(self) -> dict:
        lat = [r.finish_tick - r.arrival_tick for r in self.completed]
        per_agent = {}
        for n in self.fleet.names:
            ls = [r.finish_tick - r.arrival_tick for r in self.completed if r.agent == n]
            per_agent[n] = float(np.mean(ls)) if ls else float("nan")
        toks = sum(len(r.tokens_out) for r in self.completed)
        warm_ticks = sum(h["warm"] for h in self.history)
        out = {
            "completed": len(self.completed),
            "avg_latency_ticks": float(np.mean(lat)) if lat else float("nan"),
            "per_agent_latency": per_agent,
            "tokens_generated": toks,
            "throughput_tokens_per_tick": toks / max(self.tick, 1),
            "mean_allocation": np.mean(
                [h["allocation"] for h in self.history], axis=0
            ).tolist() if self.history else [],
            # Billing: one tick = one second of warm capacity.
            "warm_instance_ticks": float(warm_ticks),
            "mean_warm_instances": (
                float(warm_ticks / len(self.history)) if self.history else 0.0
            ),
            "cost_usd": float(billing_cost(warm_ticks, self.price_per_hour)),
            # Failure accounting (zeros when failures=None — the counters
            # exist unconditionally so dashboards need no schema branch).
            "dropped": self.dropped,
            "retried": self.retried,
            "slo_violations": self.slo_violations,
        }
        if self.workflow is not None:
            # End-to-end view: a request finishing at a sink closes the
            # whole workflow chain that began at its root submission.
            sink = np.asarray(self.workflow.sink)
            by_id = {r.id: r for r in self.completed}

            def root(req: Request) -> Request:
                while req.parent_id >= 0 and req.parent_id in by_id:
                    req = by_id[req.parent_id]
                return req

            done = [
                r for r in self.completed
                if sink[self.fleet.names.index(r.agent)] > 0
            ]
            e2e = [r.finish_tick - root(r).arrival_tick for r in done]
            out["sink_completed"] = len(done)
            out["end_to_end_latency_ticks"] = (
                float(np.mean(e2e)) if e2e else float("nan")
            )
            out["routed_requests"] = sum(h.get("routed", 0) for h in self.history)
        return out


def _scatter_slot(caches, caches1, slot: int):
    """Write a batch-1 cache tree into slot `slot` of the batched cache."""

    def upd(full, one):
        # Caches carry batch in dim 0 (transformer) or dim 1 (stacked layers).
        if full.ndim == one.ndim and one.shape[0] == 1 and full.shape[0] != 1:
            return full.at[slot].set(one[0].astype(full.dtype))
        if full.ndim == one.ndim and one.shape[1] == 1:
            return full.at[:, slot].set(one[:, 0].astype(full.dtype))
        raise ValueError((full.shape, one.shape))

    return jax.tree_util.tree_map(upd, caches, caches1)
