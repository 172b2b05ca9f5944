"""GQA attention block: projections + RoPE + kernel-dispatched core.

Supports full-causal, sliding-window (Mixtral), local (RecurrentGemma),
bidirectional (encoder) and cross (enc-dec decoder) attention, plus
one-token decode against a fixed-size or rolling KV cache.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.attention import ops as attn_ops
from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.params import decl


def attention_decls(cfg: ModelConfig, *, kv_from: str = "self"):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "w_q": decl((d, h * hd), ("embed", "heads")),
        "w_k": decl((d, kv * hd), ("embed", "kv")),
        "w_v": decl((d, kv * hd), ("embed", "kv")),
        "w_o": decl((h * hd, d), ("heads", "embed")),
    }


def _project_qkv(x, kv_x, p, cfg: ModelConfig):
    b, s, _ = x.shape
    s_kv = kv_x.shape[1]
    q = (x @ p["w_q"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = (kv_x @ p["w_k"]).reshape(b, s_kv, cfg.num_kv_heads, cfg.head_dim)
    v = (kv_x @ p["w_v"]).reshape(b, s_kv, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _rope(x, positions, cfg: ModelConfig):
    if cfg.mrope:
        return layers.apply_mrope(x, positions, cfg.rope_theta)
    return layers.apply_rope(x, positions, cfg.rope_theta)


def self_attention(
    x: jnp.ndarray,
    p,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    use_rope: bool = True,
):
    """Full-sequence self-attention (train / prefill).

    Returns (out, (k, v)) so prefill can seed the decode cache.
    """
    q, k, v = _project_qkv(x, x, p, cfg)
    if use_rope:
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)
    out = attn_ops.flash_attention(q, k, v, causal=causal, window=window)
    b, s, _, _ = q.shape
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["w_o"]
    return out, (k, v)


def cross_attention(x, enc_kv, p, cfg: ModelConfig):
    """Decoder-to-encoder attention; enc_kv = (k, v) precomputed once."""
    b, s, _ = x.shape
    q = (x @ p["w_q"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k, v = enc_kv
    out = attn_ops.flash_attention(q, k, v, causal=False)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["w_o"]
    return out


def cross_kv(enc_out, p, cfg: ModelConfig):
    b, s, _ = enc_out.shape
    k = (enc_out @ p["w_k"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["w_v"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# Decode (one new token, KV cache)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Fixed-size cache; rolling when window > 0 (slot = pos % size)."""
    size: int
    window: int = 0


def kv_cache_decls(cfg: ModelConfig, batch: int, spec: KVCacheSpec):
    """K and V stored as the decode kernel reads them: (B, KV, Dh, S_cache),
    positions in the lanes, dense for every head_dim (``seed_kv_cache``)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    axes = ("cache_batch", "kv_heads", None, "kv_seq")
    return {
        "k": decl((batch, kv, hd, spec.size), axes, init="zeros"),
        "v": decl((batch, kv, hd, spec.size), axes, init="zeros"),
    }


def seed_kv_cache(x: jnp.ndarray, spec: KVCacheSpec, s: int) -> jnp.ndarray:
    """Prefill's keys or values (..., B, S, KV, Dh) over `s` tokens -> the
    decode cache (..., B, KV, Dh, spec.size): transposed once a request,
    so that no decode step relayouts the cache."""
    if s >= spec.size:
        x = x[..., s - spec.size : s, :, :]
        if spec.window > 0:  # rolling layout: token t lives at t % size
            x = jnp.roll(x, s % spec.size, axis=-3)
    else:
        pad = [(0, 0)] * (x.ndim - 3) + [(0, spec.size - s), (0, 0), (0, 0)]
        x = jnp.pad(x, pad)
    return jnp.moveaxis(x, -3, -1)


def decode_self_attention(
    x: jnp.ndarray,            # (B, 1, D)
    cache,                     # {"k","v"}: (B, KV, Dh, S_cache)
    p,
    cfg: ModelConfig,
    pos: jnp.ndarray,          # () current token index
    spec: KVCacheSpec,
    *,
    use_rope: bool = True,
    positions3: jnp.ndarray | None = None,  # M-RoPE (B,1,3)
):
    b = x.shape[0]
    q, k, v = _project_qkv(x, x, p, cfg)
    if use_rope:
        pos_b = jnp.broadcast_to(pos[None, None], (b, 1))
        if cfg.mrope:
            p3 = positions3 if positions3 is not None else jnp.broadcast_to(
                pos[None, None, None], (b, 1, 3)
            )
            q = layers.apply_mrope(q, p3, cfg.rope_theta)
            k = layers.apply_mrope(k, p3, cfg.rope_theta)
        else:
            q = layers.apply_rope(q, pos_b, cfg.rope_theta)
            k = layers.apply_rope(k, pos_b, cfg.rope_theta)
    slot = jnp.mod(pos, spec.size) if spec.window > 0 else pos
    k_cache = _update_cache(cache["k"], k[:, 0], slot)
    v_cache = _update_cache(cache["v"], v[:, 0], slot)
    cache_len = jnp.minimum(pos + 1, spec.size)
    out = attn_ops.decode_attention(
        q[:, 0], k_cache, v_cache, cache_len,
        window=0 if spec.window == 0 else min(spec.window, spec.size),
    )
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim) @ p["w_o"]
    return out, {"k": k_cache, "v": v_cache}


def _update_cache(cache: jnp.ndarray, new: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """cache (B,KV,Dh,S) <- new (B,KV,Dh) as the column at position `slot`."""
    return jax.lax.dynamic_update_slice(
        cache, new[..., None].astype(cache.dtype), (0, 0, 0, slot)
    )
