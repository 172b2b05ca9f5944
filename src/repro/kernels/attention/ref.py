"""Pure-jnp oracle for the attention kernels.

Shapes (GQA throughout):
  q:      (B, S_q, H, D)
  k, v:   (B, S_kv, KV, D)   with H % KV == 0
Decode:
  q:      (B, H, D)          one new token
  cache:  (B, KV, D, S_max)  the decode cache layout, positions minor

``window > 0`` = sliding-window causal attention (Mixtral / local attention
in RecurrentGemma).  ``causal=False, window=0`` = bidirectional (encoder) or
cross attention.
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def _expand_kv(x: jnp.ndarray, num_q_heads: int) -> jnp.ndarray:
    """(B, S, KV, D) -> (B, S, H, D) by repeating each KV head."""
    kv = x.shape[2]
    if kv == num_q_heads:
        return x
    assert num_q_heads % kv == 0, (num_q_heads, kv)
    return jnp.repeat(x, num_q_heads // kv, axis=2)


def attention_mask(
    s_q: int,
    s_kv: int,
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
) -> jnp.ndarray:
    """(S_q, S_kv) boolean mask; True = attend."""
    q_pos = jnp.arange(s_q)[:, None] + q_offset
    k_pos = jnp.arange(s_kv)[None, :]
    mask = jnp.ones((s_q, s_kv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    return mask


def mha(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Reference multi-head (GQA) attention, fp32 softmax.

    GQA is expressed as a grouped einsum (q reshaped to (B,S,KV,G,D)) rather
    than repeating K/V: repetition materializes a group-times larger KV
    tensor, which under SPMD forces the partitioner into full-cache copies
    (§Perf iteration log).
    """
    b, s_q, h, d = q.shape
    s_kv, kv = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, s_q, kv, g, d)
    logits = jnp.einsum("bqngd,bknd->bngqk", qg, k).astype(jnp.float32)
    logits *= 1.0 / jnp.sqrt(d).astype(jnp.float32)
    mask = attention_mask(s_q, s_kv, causal=causal, window=window, q_offset=q_offset)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bngqk,bknd->bqngd", probs.astype(v.dtype), v)
    return out.reshape(b, s_q, h, d).astype(q.dtype)


def mha_banded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    window: int,
) -> jnp.ndarray:
    """Sliding-window causal attention computed BANDED: with block size ==
    window, query block b attends only kv blocks (b-1, b), so compute is
    2·W per query instead of S — a ~S/(2W) FLOP/byte reduction at long
    prefill (§Perf pair 5).  Exact match of ``mha(causal=True, window=W)``
    when S % W == 0 (asserted by the caller/ops dispatch).
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    w = window
    assert s % w == 0 and s >= w, (s, w)
    nb = s // w

    qg = q.reshape(b, nb, w, kv, g, d)
    kb = k.reshape(b, nb, w, kv, d)
    vb = v.reshape(b, nb, w, kv, d)
    # previous kv block per q block (block 0's "previous" is fully masked)
    kp = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vp = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)

    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    lg_cur = jnp.einsum("bcqngd,bcknd->bcngqk", qg, kb).astype(jnp.float32) * scale
    lg_prev = jnp.einsum("bcqngd,bcknd->bcngqk", qg, kp).astype(jnp.float32) * scale

    qi = jnp.arange(w)[:, None]
    kj = jnp.arange(w)[None, :]
    # current block: causal (and k > q - w holds automatically: same block)
    mask_cur = kj <= qi
    # previous block: k_pos = kj + (c-1)w, q_pos = qi + cw -> k > q - w <=> kj > qi
    mask_prev = kj > qi
    lg_cur = jnp.where(mask_cur, lg_cur, NEG_INF)
    lg_prev = jnp.where(mask_prev, lg_prev, NEG_INF)
    block0 = jnp.arange(nb)[None, :, None, None, None, None] == 0
    lg_prev = jnp.where(block0, NEG_INF, lg_prev)

    lg = jnp.concatenate([lg_prev, lg_cur], axis=-1)          # (B,C,N,G,W,2W)
    probs = jnp.exp(lg - lg.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    p_prev, p_cur = jnp.split(probs.astype(v.dtype), 2, axis=-1)
    out = jnp.einsum("bcngqk,bcknd->bcqngd", p_cur, vb)
    out = out + jnp.einsum("bcngqk,bcknd->bcqngd", p_prev, vp)
    return out.reshape(b, s, h, d).astype(q.dtype)


def decode_gqa(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    window: int = 0,
) -> jnp.ndarray:
    """One-token decode attention against a (possibly rolling) KV cache.

    q: (B, H, D); caches: (B, KV, D, S_max); cache_len: () or (B,) number of
    valid entries.  For rolling (sliding-window) caches the valid region is
    the whole buffer once cache_len >= S_max; masking uses entry validity
    only — relative order is irrelevant to softmax(QK^T)V.  No KV
    repetition (see ``mha``).
    """
    b, h, d = q.shape
    kv, s_max = k_cache.shape[1], k_cache.shape[3]
    g = h // kv
    qg = q.reshape(b, kv, g, d)
    logits = jnp.einsum("bngd,bndk->bngk", qg, k_cache).astype(jnp.float32)
    logits *= 1.0 / jnp.sqrt(d).astype(jnp.float32)
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (b,))
    pos = jnp.arange(s_max)[None, :]
    valid = pos < cache_len[:, None]
    if window > 0:
        valid &= pos >= (cache_len[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bngk,bndk->bngd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, h, d).astype(q.dtype)
