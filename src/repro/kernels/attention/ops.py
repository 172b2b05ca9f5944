"""Backend dispatch for attention: Pallas TPU kernel vs pure-jnp reference.

The Pallas kernels are written for the TPU memory hierarchy (HBM->VMEM
streaming, MXU-aligned tiles) and validated on CPU in ``interpret=True``
mode by the kernel tests.  Production model code calls these wrappers; on a
CPU backend (this container, smoke tests, the multi-pod dry-run) they fall
back to the reference, which is bit-for-bit the oracle the kernels are
tested against.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.attention import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _default_impl() -> str:
    return "pallas" if _on_tpu() else "ref"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset", "impl"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0, impl=None):
    """(B,S_q,H,D) x (B,S_kv,KV,D)^2 -> (B,S_q,H,D)."""
    impl = impl or _default_impl()
    if impl == "ref":
        s_q, s_kv = q.shape[1], k.shape[1]
        if (causal and window > 0 and q_offset == 0 and s_q == s_kv
                and s_q % window == 0 and s_q >= 2 * window):
            # Banded SWA: 2W work per query instead of S (§Perf pair 5).
            return ref.mha_banded(q, k, v, window=window)
        return ref.mha(q, k, v, causal=causal, window=window, q_offset=q_offset)
    from repro.kernels.attention import flash_attention as fa

    interpret = not _on_tpu()
    return fa.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("window", "impl"))
def decode_attention(q, k_cache, v_cache, cache_len, *, window=0, impl=None):
    """(B,H,D) x (B,KV,D,S_max)^2 -> (B,H,D), masked to `cache_len` entries."""
    impl = impl or _default_impl()
    if impl == "ref":
        return ref.decode_gqa(q, k_cache, v_cache, cache_len, window=window)
    from repro.kernels.attention import decode_attention as da

    interpret = not _on_tpu()
    return da.decode_attention(
        q, k_cache, v_cache, cache_len, window=window, interpret=interpret
    )
