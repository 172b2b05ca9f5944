"""Plain float32 forward passes of the three served backbones, for the
served cell's ``correct``.

Each takes the model's weights (as drawn by ``chipbench/weights.py``, in
the tree the model is served from), a batch of token sequences of one
length, and returns the logits at the positions asked for.  Every matrix
product runs in float32 at ``highest`` precision; the weights are the
served bfloat16 values, widened layer by layer.  No kernels, no cache,
no batching tricks: one full causal pass over each sequence.

* ``dense``: Qwen2-VL's decoder (arXiv:2409.12191): RMSNorm, grouped-query
  attention with multimodal RoPE (three sections of the rotary
  frequencies, 2:1:1, each turned by its own position channel), SwiGLU.
* ``moe``: Granite-3.0-1B-A400M: the same attention with RoPE, and a
  mixture of 32 SwiGLU experts, the 8 most probable per token, their
  probabilities renormalized to sum to one.
* ``ssm``: Mamba-2 (arXiv:2405.21060): in-projection to (z, x, B, C, dt),
  causal depthwise convolution of (x, B, C), SiLU, the state-space
  recurrence ``h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t`` stepped token by token, gated RMSNorm,
  out-projection.

Where the served program departs from the published models, the
reference follows the program and the departure is listed here:

* the vision encoder is a stub: the first ``frontend_tokens`` prompt
  positions of a Qwen2-VL backbone are embedded as zeros (the engine's
  stand-in for image features), and every M-RoPE section gets the text
  position, image positions included;
* Granite's embedding, attention, residual and logit multipliers are not
  applied;
* the program's MoE prefill drops an expert's tokens beyond its capacity
  (GShard, capacity factor 1.25 of the prompt's even share, in token
  order); the reference applies the same rule to the prompt's positions
  and none to the generated ones, which the program decodes one by one.

``lower`` (a float8 dtype) rounds every matrix product's operands to that
type, each tensor scaled by its largest magnitude first: the control of
the comparison, computed below the bfloat16 the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round(x, lower):
    if lower is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = float(jnp.finfo(lower).max) / amax
    return (x * scale).astype(lower).astype(F32) / scale


def _mm(a, b, lower=None):
    return jnp.matmul(_round(a.astype(F32), lower), _round(b.astype(F32), lower))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, pos, theta, sections=None):
    """Rotate-half RoPE over the last axis of x (B, S, H, D).  With
    ``sections``, the D/2 frequencies are split into contiguous sections
    in those proportions, each turned by its own channel of ``pos``
    (B, S, 3); otherwise ``pos`` is (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if sections is None:
        ang = pos[..., None].astype(F32) * freqs
    else:
        total = sum(sections)
        owner, start = [], 0
        for i, s in enumerate(sections):
            n = half - start if i == len(sections) - 1 else (half * s) // total
            owner += [i] * n
            start += n
        ang = pos[..., jnp.asarray(owner)].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(x, p, m, pos, lower):
    b, s, _ = x.shape
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _mm(x, p["w_q"], lower).reshape(b, s, h, hd)
    k = _mm(x, p["w_k"], lower).reshape(b, s, kv, hd)
    v = _mm(x, p["w_v"], lower).reshape(b, s, kv, hd)
    sections = m.get("mrope_sections")
    if sections:
        pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
    q = _rope(q, pos, m["rope_theta"], sections)
    k = _rope(k, pos, m["rope_theta"], sections)
    g = h // kv
    q = q.reshape(b, s, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", _round(q, lower), _round(k, lower))
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", _round(w, lower), _round(v, lower))
    return _mm(out.reshape(b, s, h * hd), p["w_o"], lower)


def _swiglu(x, wg, wu, wd, lower):
    return _mm(jax.nn.silu(_mm(x, wg, lower)) * _mm(x, wu, lower), wd, lower)


def moe_capacity(prompt_len: int, m: dict) -> int:
    """Tokens each expert takes from a prompt of ``prompt_len``: the even
    share times the capacity factor, rounded up to a multiple of 8."""
    cap = math.ceil(prompt_len * m["experts_per_token"] / m["num_experts"]
                    * m["moe_capacity_factor"])
    return max(8, -(-cap // 8) * 8)


def _moe(x, p, m, prompt_len, lower):
    """x (B, S, D) -> (B, S, D), and the count of dropped routings."""
    b, s, d = x.shape
    e, k = m["num_experts"], m["experts_per_token"]
    probs = jax.nn.softmax(_mm(x, p["router"], lower), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_i, e, dtype=F32)                   # (B,S,K,E)
    # Capacity over the prompt: a routing keeps its place when fewer than
    # `cap` earlier routings (in (token, k) order) went to its expert.
    in_prompt = (jnp.arange(s) < prompt_len)[None, :, None, None]
    flat = (onehot * in_prompt).reshape(b, s * k, e)
    before = (jnp.cumsum(flat, axis=1) - flat).reshape(b, s, k, e)
    place = jnp.sum(before * onehot, axis=-1)                       # (B,S,K)
    keep = jnp.where(in_prompt[..., 0], place < moe_capacity(prompt_len, m), True)
    gate = jnp.einsum("bske,bsk->bse", onehot, top_p * keep)        # (B,S,E)
    out = jnp.zeros((b, s, d), F32)
    for j in range(e):
        y = _swiglu(x, p["w_gate"][j], p["w_up"][j], p["w_down"][j], lower)
        out = out + gate[..., j:j + 1] * y
    return out, jnp.sum(1.0 - keep)


def _ssm(x, p, m, lower):
    b, s, _ = x.shape
    n, pdim = m["ssm_state_dim"], m["ssm_head_dim"]
    di = m["ssm_expand"] * m["d_model"]
    nh = di // pdim
    zxbcdt = _mm(x, p["w_in"], lower)
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n], zxbcdt[..., 2 * di + 2 * n:]
    w = p["conv_w"].astype(F32)                                     # (W, C)
    width = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * w[i] for i in range(width)) + p["conv_b"].astype(F32)
    conv = jax.nn.silu(conv)
    xs = conv[..., :di].reshape(b, s, nh, pdim)
    bm, cm = conv[..., di:di + n], conv[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))             # (B,S,H)
    a = -jnp.exp(p["A_log"].astype(F32))

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(a * dtt)[..., None, None] * h + (dtt[..., None, None]
                                                      * xt[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct)

    h0 = jnp.zeros((b, nh, pdim, n), F32)
    _, ys = jax.lax.scan(step, h0, (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(dt, 1, 0),
                                    jnp.moveaxis(bm, 1, 0), jnp.moveaxis(cm, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1) + p["D"].astype(F32)[:, None] * xs
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = _rms(y, p["norm_scale"], m["norm_eps"])
    return _mm(y, p["w_out"], lower)


def _layer(kind, x, p, m, pos, prompt_len, lower):
    eps = m["norm_eps"]
    if kind == "ssm":
        return x + _ssm(_rms(x, p["ln1"]["scale"], eps), p["ssm"], m, lower), 0.0
    x = x + _attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], m, pos, lower)
    y = _rms(x, p["ln2"]["scale"], eps)
    if kind == "moe":
        out, dropped = _moe(y, p["moe"], m, prompt_len, lower)
        return x + out, dropped
    return x + _swiglu(y, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"], lower), 0.0


def forward(params, tokens, m: dict, prompt_len: int, at, lower=None):
    """Logits (B, len(at), V) at sequence positions ``at`` of ``tokens``
    (B, S), and the MoE routings the prompt's capacity dropped."""
    kind = m["arch"]
    b, s = tokens.shape
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    fe = min(m.get("frontend_tokens", 0), prompt_len)
    if fe:
        x = x.at[:, :fe].set(0.0)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    (name, blocks), = params["blocks"].items()

    def body(carry, lp):
        xx, dropped = carry
        xx, dr = _layer(kind, xx, lp, m, pos, prompt_len, lower)
        return (xx, dropped + dr), None

    with jax.default_matmul_precision("highest"):
        (x, dropped), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), blocks)
        x = _rms(x[:, jnp.asarray(at)], params["final_norm"]["scale"], m["norm_eps"])
        logits = _mm(x, params["embed"]["lm_head"], lower)
    return logits, dropped


def served_gaps(params, prompts, served, m: dict, lower=None):
    """For each served token, the gap by which its logit lies below the
    reference's best at its position; with ``lower``, the gap of the
    token the lower-precision pass puts first there.

    ``prompts`` (B, P) and ``served`` (B, T) int arrays of one length each.
    Returns (gaps (B, T), dropped routings)."""
    prompts, served = jnp.asarray(prompts), jnp.asarray(served)
    p_len, t_len = prompts.shape[1], served.shape[1]
    tokens = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    at = list(range(p_len - 1, p_len + t_len - 1))
    ref, dropped = jax.jit(forward, static_argnums=(2, 3, 4, 5))(
        params, tokens, _frozen(m), p_len, tuple(at), None)
    best = ref.max(-1)
    if lower is None:
        pick = served
    else:
        low, _ = jax.jit(forward, static_argnums=(2, 3, 4, 5))(
            params, tokens, _frozen(m), p_len, tuple(at), lower)
        pick = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return best - chosen, dropped


class _frozen(dict):
    """A model description that can be a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
