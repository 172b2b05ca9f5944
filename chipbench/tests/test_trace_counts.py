"""Self-checks of the trace reduction and the operation and byte counts.

The trace reduction runs on a small trace recorded on a TPU v5e chip,
committed under ``chipbench/testdata`` (a traced window of one grid of the
``fleet1024-sweep`` cell).  The counts are held against the program's own
parameter counts and against XLA's cost analysis of plain reference
computations, at small sizes on the CPU.
"""
from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench.counts import models as counts
from chipbench.trace import Events, Traced, merge

DATA = Path(__file__).resolve().parents[1] / "testdata"
TRACE = DATA / "fleet1024-sweep.xplane.pb.gz"


def test_merge_unions_overlapping_intervals():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    assert merge([]) == []


@pytest.fixture(scope="module")
def traced():
    if not TRACE.exists():
        pytest.skip(f"no recorded trace at {TRACE}")
    return Traced(Events.from_file(str(TRACE)), 0.0, str(TRACE))


def test_recorded_trace_has_one_chip_and_the_grid_program(traced):
    planes = traced.events.device_planes()
    assert planes == ["/device:TPU:0"]
    ops = traced.ops(planes[0])
    assert len(ops) > 100
    modules = {n.split("(")[0] for n, *_ in traced.events.modules(planes[0])}
    assert any(m.startswith("jit__stream_grid") for m in modules), sorted(modules)[:20]


def test_busy_and_idle_from_the_recorded_trace(traced):
    plane = traced.events.device_planes()[0]
    lo, hi = traced.window_bounds()
    assert hi > lo
    busy = traced.busy_ns(plane)
    assert 0 < busy <= hi - lo
    summary = traced.summary([0])
    assert 0 < summary["busy_s"] <= summary["window_s"]
    assert math.isclose(summary["window_s"], (hi - lo) / 1e9)
    bd = summary["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    idle = sum(v for _, v in traced.idle_gaps(plane, lo, hi))
    assert math.isclose(idle + busy / 1e9, (hi - lo) / 1e9, rel_tol=1e-6)


MODELS = {
    "qwen2-vl-2b": {"arch": "dense", "num_layers": 28, "d_model": 1536, "num_heads": 12,
                    "num_kv_heads": 2, "head_dim": 128, "d_ff": 8960, "vocab_size": 151936},
    "granite-moe-1b-a400m": {"arch": "moe", "num_layers": 24, "d_model": 1024,
                             "num_heads": 16, "num_kv_heads": 8, "head_dim": 64, "d_ff": 512,
                             "vocab_size": 49155, "num_experts": 32, "experts_per_token": 8},
    "mamba2-370m": {"arch": "ssm", "num_layers": 48, "d_model": 1024, "vocab_size": 50280,
                    "ssm_state_dim": 128, "ssm_head_dim": 64, "ssm_expand": 2,
                    "ssm_conv_width": 4},
}


@pytest.mark.parametrize("arch", sorted(MODELS))
def test_weight_bytes_match_the_programs_parameter_count(arch):
    from repro.configs import get_config

    m = MODELS[arch]
    cfg = get_config(arch)
    read_w, _ = counts.layer_weights(m)
    v = -(-m["vocab_size"] // 256) * 256
    matrices = m["num_layers"] * read_w + 2 * v * m["d_model"]
    # The program's count adds norms, biases and SSM scalars: under 0.5%.
    assert matrices <= cfg.param_count <= matrices * 1.005
    dec = counts.decode(m, batch=1, pos=0)
    assert dec["bytes"] >= 2 * (cfg.param_count - v * m["d_model"]) * 0.99


def _xla_flops(fn, *shapes) -> float:
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return float(jax.jit(fn).lower(*args).compile().cost_analysis()["flops"])


def test_attention_flops_against_xla():
    s, h, kv, hd = 256, 4, 2, 64
    m = {"arch": "dense", "num_layers": 1, "num_heads": h, "num_kv_heads": kv, "head_dim": hd}

    def full(q, k, v):
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        w = jax.nn.softmax(jnp.einsum("qhd,khd->hqk", q, k), axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v)

    xla = _xla_flops(full, (s, h, hd), (s, kv, hd), (s, kv, hd))
    causal = counts.kernels_prefill(m, s)[0]["flops"]
    # XLA computes every query-key pair; the count takes the causal half.
    assert 0.98 <= (4 * h * hd * s * s) / xla <= 1.05
    assert math.isclose(causal, 4 * h * hd * s * (s + 1) / 2)
    dec = counts.kernels_decode(m, batch=1, pos=s - 1)[0]["flops"]
    assert math.isclose(dec, 4 * h * hd * s)


def test_ssd_flops_against_xla():
    s, nh, p, n = 64, 4, 16, 32
    m = {"arch": "ssm", "num_layers": 1, "d_model": nh * p // 2, "ssm_expand": 2,
         "ssm_head_dim": p, "ssm_state_dim": n, "ssm_conv_width": 4}

    def recurrence(x, dt, b, c):
        def step(h, inp):
            xt, dtt, bt, ct = inp
            h = h * 0.9 + dtt[:, None, None] * xt[:, :, None] * bt[None, None, :]
            return h, jnp.einsum("hpn,n->hp", h, ct)
        return jax.lax.scan(step, jnp.zeros((nh, p, n)), (x, dt, b, c))[1]

    per_step = _xla_flops(lambda x, dt, b, c: recurrence(x[:1], dt[:1], b[:1], c[:1]),
                          (s, nh, p), (s, nh), (s, n), (s, n))
    count = counts.kernels_prefill(m, s)[0]["flops"] / s
    # The count takes the update and the read-out as two multiply-adds per
    # state element each; XLA's cost analysis counts the fused update as
    # fewer operations.
    assert 1.0 <= count / per_step <= 2.0
