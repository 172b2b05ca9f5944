"""The pieces the plain references of the served backbones are built from,
and the walker that runs a model's layers in the program's order.

Every matrix product runs in float32 at ``highest`` precision; the weights
are the served bfloat16 values, widened layer by layer.  No kernels, no
cache, no batching tricks: one full causal pass over each sequence.

``lower`` (a float8 dtype) rounds every matrix product's operands to that
type, each tensor scaled by its largest magnitude first: the control of
the comparison, computed below the bfloat16 the configuration states.

A model's layers are stacked as the program stacks them: ``blocks`` holds
superblocks along a leading axis, each a dict of sub-blocks ``b{i}_{kind}``,
and ``tail`` the sub-blocks ``t{i}_{kind}`` left over after the last whole
superblock.  An architecture (``chipbench/reference/archs/<arch>.py``)
gives one layer function per kind, ``layer(x, p, m, pos, prompt_len,
lower) -> (x, dropped routings)``.
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


def in_order(blocks: dict) -> list[tuple[str, str]]:
    """(name, kind) of each sub-block ``<letter><i>_<kind>``, by the
    integer ``i``: ``b10`` comes after ``b2``."""
    named = [(int(name[1:].split("_", 1)[0]), name) for name in blocks]
    return [(name, name.split("_", 1)[1]) for _, name in sorted(named)]


def walk(layers: dict, params, x, m: dict, pos, prompt_len: int, lower):
    """``x`` through every layer: a scan over the stacked superblocks of
    ``params["blocks"]``, each sub-block in index order, then the entries
    of ``params["tail"]``.  ``layers`` maps a sub-block's kind to its layer
    function.  Returns (x, dropped routings)."""

    def chain(x, dropped, blocks):
        for name, kind in in_order(blocks):
            x, dr = layers[kind](x, blocks[name], m, pos, prompt_len, lower)
            dropped = dropped + dr
        return x, dropped

    def body(carry, superblock):
        return chain(*carry, superblock), None

    (x, dropped), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["blocks"])
    return chain(x, dropped, params.get("tail", {}))


def forward(layers: dict, params, tokens, m: dict, prompt_len: int, at, lower=None):
    """Logits (B, len(at), V) at sequence positions ``at`` of ``tokens``
    (B, S), and the MoE routings the prompt's capacity dropped: the token
    embedding (the first ``frontend_tokens`` prompt positions zero, the
    engine's stand-in for a modality frontend's features), the layers
    ``walk`` runs, the final RMSNorm and the output head."""
    b, s = tokens.shape
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    fe = min(m.get("frontend_tokens", 0), prompt_len)
    if fe:
        x = x.at[:, :fe].set(0.0)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    with jax.default_matmul_precision("highest"):
        x, dropped = walk(layers, params, x, m, pos, prompt_len, lower)
        x = _rms(x[:, jnp.asarray(at)], params["final_norm"]["scale"], m["norm_eps"])
        logits = _mm(x, params["embed"]["lm_head"], lower)
    return logits, dropped
