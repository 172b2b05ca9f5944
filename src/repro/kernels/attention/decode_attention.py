"""GQA decode attention for TPU (Pallas): one query token vs a large KV
cache.  This op is memory-bound (arithmetic intensity ~ O(group)); the
kernel streams the KV cache HBM->VMEM in ``block_k``-sized slabs along the
innermost grid dimension and keeps the whole q-head *group* resident, so
each cache byte is read exactly once per kv-head regardless of group size.

Masking supports both plain caches (valid = pos < cache_len) and rolling
sliding-window caches (cache size == window; all written slots valid).

Validated against ``ref.decode_gqa`` in interpret mode by
tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale, window, bk, s_max,
):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[pl.program_id(0)]
    pos = si * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    valid = pos < jnp.minimum(cache_len, s_max)
    if window > 0:
        valid &= pos >= cache_len - window

    @pl.when((si * bk) < jnp.minimum(cache_len, s_max))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # (G, BK)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(
    q, k_cache, v_cache, cache_len, *, window=0, block_k=512, interpret=False,
):
    """q (B,H,D) x caches (B,S_max,KV,D) -> (B,H,D)."""
    b, h, d = q.shape
    s_max, kv = k_cache.shape[1], k_cache.shape[2]
    group = h // kv
    bk = min(block_k, s_max)

    qt = q.reshape(b, kv, group, d)                   # (B,KV,G,D)
    kt = jnp.swapaxes(k_cache, 1, 2)                  # (B,KV,S,D)
    vt = jnp.swapaxes(v_cache, 1, 2)
    pad = (-s_max) % bk
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ns = kt.shape[2] // bk

    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (b,))

    # cache_len is scalar-prefetched into SMEM: Mosaic refuses a rank-1
    # (1,) VMEM block, and the kernel only ever reads one scalar of it.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda b_, kv_, si, lens: (b_, kv_, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, kv_, si, lens: (b_, kv_, si, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, kv_, si, lens: (b_, kv_, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), lambda b_, kv_, si, lens: (b_, kv_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / (d ** 0.5), window=window, bk=bk, s_max=s_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, group, d), q.dtype),
        interpret=interpret,
    )(cache_len, qt, kt, vt)
    return out.reshape(b, h, d)
