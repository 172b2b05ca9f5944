"""Served cell: closed-loop waves of requests through the serving engine.

Set-up builds one ``AgentRuntime`` per agent of the configuration, on the
backbone the configuration names, at the widths it states, with weights
drawn from the run's seed (``chipbench/weights.py``), and a
``FleetEngine`` over them under the traffic's policy and token budget.
Two ticks of short warm-up waves compile every program the window runs.

The loop is closed, per agent: whenever an agent has nothing queued or in
flight, a wave of ``batch_slots`` requests is submitted to it, every
prompt of the agent's role length, token ids uniform over the backbone's
vocabulary.  Then ``FleetEngine.step`` runs one tick.  The window ends at
the end of the first tick that ends ``seconds`` after it began, so it
holds whole ticks only.

End to end: ``served_tokens_per_s`` (tokens generated by requests
completed in the window, over the window's seconds) and
``served_latency_p90_s``, the 90th percentile of the latency of every
request completed in the window, from its submission to the end of the
tick in which it finished: a 30 s window completes two or three ticks of
64 requests, and the 90th is the highest percentile with ten of them
beyond it in both.  Every wave finishes in its tick, so this reads the
slowest tick's length.

``correct``: once the window has closed and the engine is freed, requests
drawn from the seed, for every agent, are run again by the float32
reference (``chipbench/reference/models.py``) over their prompt and the
tokens they were served.  Per agent the traffic file's ``check.limit``
names what is compared: the widest gap by which a served token's logit
lies below the reference's best (``logit_gap``), or, where the program's
widest gap and the control's do not separate, the mean gap per served
token (``logit_gap_mean``).
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench import weights
from chipbench.counts import models as counts
from chipbench.harness import log, seed_words
from chipbench.reference import models as ref_models
from chipbench.trace import span


def mrope_counts(sections, half: int) -> list[int]:
    """Rotary frequencies per M-RoPE section, split as the program splits
    ``half`` of them in proportion to ``sections``."""
    total = sum(sections)
    counts = [(half * s) // total for s in sections[:-1]]
    return counts + [half - sum(counts)]


def describe(mcfg) -> dict:
    """The program's model configuration in the configuration file's terms:
    every field of its ``ModelConfig`` (tuples as lists), ``arch`` for its
    ``arch_type``, the M-RoPE sections as the program splits the rotary
    frequencies, and no frontend positions without a vision frontend."""
    import inspect

    from repro.models import layers

    out = {}
    for f in dataclasses.fields(mcfg):
        v = getattr(mcfg, f.name)
        out[f.name] = list(v) if isinstance(v, tuple) else v
    out["arch"] = mcfg.arch_type
    if mcfg.mrope:
        sections = inspect.signature(layers.apply_mrope).parameters["sections"].default
        out["mrope_sections"] = mrope_counts(sections, mcfg.head_dim // 2)
    if mcfg.frontend != "vision":
        out["frontend_tokens"] = 0
    return out


def served_models(config: dict) -> dict:
    """Each backbone as it is served: its published values, with the
    departures the configuration lists applied."""
    served = config["served"]
    out = {}
    for arch, published in served["models"].items():
        m = dict(published)
        for k, dep in served.get("departures", {}).get(arch, {}).items():
            m[k] = dep["served"]
        out[arch] = m
    return out


def model_configs(config: dict) -> dict:
    """Each backbone's program configuration, checked against the
    configuration file: every key of its entry but ``source``, published
    values with the listed departures.  A key the program's configuration
    lacks passes only where it is served ``null`` or ``false``: not
    applied."""
    from repro.configs import get_config

    served = config["served"]
    wanted = served_models(config)
    out = {}
    for arch in sorted(set(served["backbones"].values())):
        mcfg = get_config(arch)
        have, want = describe(mcfg), wanted[arch]
        bad = {k: (v, have[k]) for k, v in want.items()
               if k != "source" and k in have and v != have[k]}
        bad.update({k: (v, "not in the program") for k, v in want.items()
                    if k not in have and v is not None and v is not False})
        if bad:
            raise ValueError(f"{arch}: the program's configuration differs from the "
                             f"configuration file (wanted, found): {bad}")
        out[arch] = mcfg
    return out


def setup(ctx):
    from repro.core.agents import AgentSpec, Fleet
    from repro.models.model import build_model
    from repro.serving.engine import AgentRuntime, FleetEngine

    cfg, tr = ctx.config, ctx.traffic
    served = cfg["served"]
    w = seed_words(ctx.seed, 3)
    mcfgs = model_configs(cfg)
    rows = cfg["fleet"]["agents"]
    apis = [build_model(mcfgs[served["backbones"][a["name"]]]) for a in rows]
    t0 = time.perf_counter()
    params = weights.make([api.param_decls for api in apis],
                          [weights.agent_key(w[0], i) for i in range(len(rows))])
    import jax

    jax.block_until_ready(params)
    log(f"served: weights of {len(rows)} agents made in {time.perf_counter() - t0!r} s")
    specs, rts = [], {}
    for a, api, p in zip(rows, apis, params):
        specs.append(AgentSpec(a["name"], a["model_size_mb"], a["base_throughput"],
                               a["min_gpu"], a["priority"]))
        rts[a["name"]] = AgentRuntime(a["name"], api, p, max_len=tr["max_len"],
                                      batch_slots=tr["batch_slots"])
    engine = FleetEngine(Fleet.from_specs(specs), rts, policy=tr["policy"],
                         budget_tokens=tr["budget_tokens"])
    state = {"engine": engine, "rng": np.random.default_rng(w[1]), "pick": w[2],
             "submitted": {}, "waves": []}
    t0 = time.perf_counter()
    # Two ticks of warm-up waves, at the window's prompt lengths but two
    # tokens long: the prefill and decode programs compile in the first;
    # the second compiles the forecast's update and the cache writes of an
    # SSM state that the first decode has widened to float32.
    with span("warm-up waves"):
        for _ in range(2):
            _submit_waves(ctx, state, output=2)
            engine.step()
    unfinished = [r for r in state["submitted"].values() if r[0].finish_tick < 0]
    if unfinished:
        raise RuntimeError("a warm-up wave did not finish in its tick; "
                           "the token budget cannot admit a whole wave")
    log(f"served: warm-up ticks (compile on a cold cache) {time.perf_counter() - t0!r} s")
    state["submitted"], state["waves"] = {}, []
    state["done_before"] = len(engine.completed)
    return state


def _submit_waves(ctx, state, output: int | None = None) -> None:
    """A wave for every agent that has nothing queued or in flight;
    ``output`` overrides the roles' output lengths."""
    tr = ctx.traffic
    engine = state["engine"]
    for rt in engine.runtimes:
        if rt.queue or any(r is not None for r in rt.active):
            continue
        role = tr["roles"][rt.name]
        vocab = rt.api.cfg.vocab_size
        now = time.perf_counter()
        for _ in range(tr["batch_slots"]):
            prompt = state["rng"].integers(0, vocab, role["prompt"])
            req = engine.submit(rt.name, prompt, output or role["output"])
            state["submitted"][req.id] = (req, now)
        state["waves"].append({"agent": rt.name, "batch": tr["batch_slots"],
                               "prompt": role["prompt"], "output": output or role["output"]})


def _check_positions(engine) -> None:
    """Every occupied slot of an agent decodes at one position (the engine
    decodes all slots at the largest)."""
    for rt in engine.runtimes:
        pos = {int(rt.pos[i]) for i, r in enumerate(rt.active) if r is not None}
        if len(pos) > 1:
            raise RuntimeError(f"{rt.name}: occupied slots at positions {sorted(pos)}")


def window(ctx, state, seconds):
    engine = state["engine"]
    latencies, tick_s = {}, []
    t0 = time.perf_counter()
    while True:
        t_tick = time.perf_counter()
        with span("submit"):
            _submit_waves(ctx, state)
        done = len(engine.completed)
        with span("engine.step"):
            engine.step()
        t_end = time.perf_counter()
        tick_s.append(t_end - t_tick)
        for r in engine.completed[done:]:
            latencies[r.id] = t_end - state["submitted"][r.id][1]
        _check_positions(engine)
        if t_end - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    finished = [state["submitted"][i][0] for i in latencies]
    tokens = sum(len(r.tokens_out) for r in finished)
    lat = np.asarray(list(latencies.values()))
    p90 = float(np.percentile(lat, 90))
    ticks = len(tick_s)
    log(f"served: {ticks} ticks, {len(finished)} requests, {tokens} tokens in {window_s!r} s; "
        f"latency median {float(np.median(lat))!r} s, p90 {p90!r} s over {len(lat)} requests; "
        f"tick seconds {tick_s}")
    state["finished"] = finished
    return {
        "window_s": window_s,
        "attempted": len(state["submitted"]),
        "failed": len(state["submitted"]) - len(finished),
        "metrics": {"served_tokens_per_s": tokens / window_s,
                    "served_latency_p90_s": p90},
        "records": {"ticks": ticks, "waves": list(state["waves"]),
                    "models": {rt.name: served_models(ctx.config)[
                        ctx.config["served"]["backbones"][rt.name]]
                        for rt in engine.runtimes}},
    }


def release(ctx, state):
    engine = state.pop("engine")
    state["agents"] = [(rt.name, rt.api.param_decls) for rt in engine.runtimes]
    del engine
    gc.collect()


def sample(ctx, state) -> dict:
    """Requests drawn from the seed, ``check.requests_per_agent`` per agent."""
    rng = np.random.default_rng(state["pick"])
    by_agent = {}
    for r in state["finished"]:
        by_agent.setdefault(r.agent, []).append(r)
    k = int(ctx.traffic["check"]["requests_per_agent"])
    out = {}
    for name, _ in state["agents"]:
        reqs = by_agent.get(name, [])
        idx = rng.choice(len(reqs), size=min(k, len(reqs)), replace=False) if reqs else []
        out[name] = [reqs[i] for i in sorted(idx)]
    return out


def reference_gaps(ctx, state, lower=None) -> dict:
    """The numbers ``check`` can compare, per agent, over the served
    tokens of its sampled requests: ``logit_gap.<agent>``, the widest gap
    by which a served token's logit lies below the reference's best, and
    ``logit_gap_mean.<agent>``, the mean gap per served token.  With
    ``lower``, the gaps of the tokens the lower-precision reference puts
    first at the same positions."""
    import jax

    w = seed_words(ctx.seed, 3)
    served = ctx.config["served"]
    picked = sample(ctx, state)
    out = {}
    for i, (name, decls) in enumerate(state["agents"]):
        reqs = picked[name]
        if not reqs:
            out[f"logit_gap.{name}"] = out[f"logit_gap_mean.{name}"] = float("inf")
            continue
        m = served_models(ctx.config)[served["backbones"][name]]
        params, = weights.make([decls], [weights.agent_key(w[0], i)])
        prompts = np.stack([r.prompt for r in reqs])
        toks = np.stack([np.asarray(r.tokens_out, np.int32) for r in reqs])
        gaps, dropped = ref_models.served_gaps(params, prompts, toks, m, lower)
        gaps = np.asarray(jax.device_get(gaps), np.float64)
        out[f"logit_gap.{name}"] = float(gaps.max())
        out[f"logit_gap_mean.{name}"] = float(gaps.mean())
        worst = np.unravel_index(np.argmax(gaps), gaps.shape)
        blocks = [float(gaps[:, j:j + 128].max()) for j in range(0, gaps.shape[1], 128)]
        log(f"served check {name}: {len(reqs)} requests x {toks.shape[1]} tokens, "
            f"widest gap {gaps.max()!r} (request {int(worst[0])}, token {int(worst[1])}), "
            f"per request {gaps.max(axis=1).tolist()}, mean {gaps.mean()!r}, "
            f"tokens below the best {(gaps > 0).mean()!r}, widest per 128 tokens {blocks}, "
            f"prompt routings over capacity {float(dropped)!r}")
        del params
        gc.collect()
    return out


def check(ctx, state, result):
    """The numbers the traffic file's ``check.limit`` names, each against
    its limit; every agent has at least one."""
    limits = ctx.traffic["check"].get("limit")
    if limits is None:
        raise ValueError("the traffic file states no limits for logit_gap: set them from "
                         "the readings of chipbench/calibrate.py on the chip")
    agents = {name for name, _ in state["agents"]}
    unchecked = agents - {k.split(".", 1)[1] for k in limits}
    if unchecked:
        raise ValueError(f"no limit for agents {sorted(unchecked)}")
    got = reference_gaps(ctx, state)
    return [(name, got[name], float(limit)) for name, limit in limits.items()]


def calls(records: dict) -> tuple[list, list]:
    """(model calls, Pallas kernel calls) the window's waves made, each as
    {"flops", "bytes"}: per wave one prefill per request and one decode
    step per generated token after the first."""
    model_calls, kernel_calls = [], []
    for wave in records["waves"]:
        m = records["models"][wave["agent"]]
        b, s, n = wave["batch"], wave["prompt"], wave["output"]
        for _ in range(b):
            model_calls.append(counts.prefill(m, s))
            kernel_calls.extend(counts.kernels_prefill(m, s))
        for j in range(n - 1):
            model_calls.append(counts.decode(m, b, s + j))
            kernel_calls.extend(counts.kernels_decode(m, b, s + j))
    return model_calls, kernel_calls
