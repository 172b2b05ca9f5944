"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes, dtypes, GQA group sizes and mask modes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import ref as aref
from repro.kernels.attention.decode_attention import _block_len, decode_attention
from repro.kernels.attention.flash_attention import flash_attention
from repro.kernels.ssd import ref as sref
from repro.kernels.ssd.ssd_scan import ssd
from repro.models.attention import KVCacheSpec, seed_kv_cache


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


FLASH_CASES = [
    # (b, s_q, s_kv, h, kv, d, causal, window)
    (2, 128, 128, 4, 2, 64, True, 0),
    (1, 200, 200, 8, 8, 128, True, 0),     # MHA, non-divisible seq (padding)
    (2, 64, 256, 4, 1, 32, False, 0),      # cross/bidirectional, MQA
    (1, 256, 256, 4, 2, 64, True, 64),     # sliding window
    (2, 96, 96, 6, 3, 64, True, 0),
    (1, 128, 512, 4, 4, 128, True, 0),     # q shorter than kv (continuation)
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, s_q, s_kv, h, kv, d, causal, window = case
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = _rand(keys[0], b, s_q, h, d, dtype=dtype)
    k = _rand(keys[1], b, s_kv, kv, d, dtype=dtype)
    v = _rand(keys[2], b, s_kv, kv, d, dtype=dtype)
    off = s_kv - s_q if causal else 0
    want = aref.mha(q, k, v, causal=causal, window=window, q_offset=off)
    got = flash_attention(q, k, v, causal=causal, window=window, q_offset=off,
                          block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=_tol(dtype)
    )


DECODE_CASES = [
    # (b, h, kv, d, s_max, cache_len, window, block_k); caches (B, KV, D, S_max)
    (2, 8, 2, 64, 300, 150, 0, 128),      # s_max no multiple of 128: one block
    (1, 4, 4, 128, 512, 512, 0, 128),
    (3, 16, 2, 64, 256, 256, 128, 128),   # rolling sliding-window cache
    (2, 4, 1, 32, 1024, 700, 0, 128),     # MQA, partially filled
    (1, 8, 8, 64, 96, 1, 0, 128),         # single valid entry
    (2, 16, 8, 64, 1280, 700, 0, 512),    # granite heads, blocks of 256
    (2, 12, 2, 128, 1280, 1280, 0, 512),  # qwen2-vl heads, blocks of 256
    (2, 16, 8, 64, 80, 50, 0, 512),       # one whole block of 80
    (2, 12, 2, 128, 48, 48, 0, 512),      # one whole block of 48
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(case, dtype):
    b, h, kv, d, s_max, clen, window, block_k = case
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = _rand(keys[0], b, h, d, dtype=dtype)
    kc = _rand(keys[1], b, kv, d, s_max, dtype=dtype)
    vc = _rand(keys[2], b, kv, d, s_max, dtype=dtype)
    want = aref.decode_gqa(q, kc, vc, jnp.int32(clen), window=window)
    got = decode_attention(q, kc, vc, jnp.int32(clen), window=window,
                           block_k=block_k, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=_tol(dtype)
    )


def test_decode_attention_per_example_lengths():
    b, h, kv, d, s_max = 3, 4, 2, 32, 256
    keys = jax.random.split(jax.random.key(7), 3)
    q = _rand(keys[0], b, h, d)
    kc = _rand(keys[1], b, kv, d, s_max)
    vc = _rand(keys[2], b, kv, d, s_max)
    lens = jnp.asarray([5, 177, 256], jnp.int32)
    want = aref.decode_gqa(q, kc, vc, lens)
    got = decode_attention(q, kc, vc, lens, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("s_max, block_k, want", [
    (1280, 512, 256), (4096, 512, 512), (2048, 512, 512), (384, 512, 384),
    (640, 256, 128), (128, 64, 128), (300, 512, 300), (80, 512, 80), (48, 512, 48),
])
def test_decode_block_divides_the_cache(s_max, block_k, want):
    assert _block_len(s_max, block_k) == want


@pytest.mark.parametrize("case", [(2, 8, 2, 64, 0), (1, 12, 2, 128, 0), (2, 4, 1, 32, 16)])
def test_decode_reference_matches_last_prefill_row(case):
    """The decode oracle over the cache layout equals full attention's
    last query row over the projected (B, S, KV, D) keys and values."""
    b, h, kv, d, window = case
    s = 40
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = _rand(keys[0], b, s, h, d)
    k = _rand(keys[1], b, s, kv, d)
    v = _rand(keys[2], b, s, kv, d)
    want = aref.mha(q, k, v, causal=True, window=window)[:, -1]
    spec = KVCacheSpec(size=s)
    got = aref.decode_gqa(q[:, -1], seed_kv_cache(k, spec, s), seed_kv_cache(v, spec, s),
                          jnp.int32(s), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


SSD_CASES = [
    # (b, s, h, p, n, chunk)
    (2, 128, 4, 32, 16, 32),
    (1, 96, 2, 64, 32, 32),
    (2, 64, 8, 16, 8, 16),
    (1, 100, 2, 32, 16, 32),  # non-divisible seq (padding path)
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_matches_naive(case, dtype):
    b, s, h, p, n, chunk = case
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 5)
    x = (_rand(keys[0], b, s, h, p) * 0.5).astype(dtype)
    dt = jax.nn.softplus(_rand(keys[1], b, s, h))
    A = -jnp.exp(_rand(keys[2], h) * 0.3)
    Bm = _rand(keys[3], b, s, n).astype(dtype)
    Cm = _rand(keys[4], b, s, n).astype(dtype)
    D = jnp.ones((h,))
    want_y, want_h = sref.ssd_naive(x, dt, A, Bm, Cm, D)
    got_y, got_h = ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), atol=tol
    )
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), atol=tol)


def test_ssd_chunked_ref_matches_naive():
    b, s, h, p, n = 2, 128, 4, 32, 16
    keys = jax.random.split(jax.random.key(3), 5)
    x = _rand(keys[0], b, s, h, p) * 0.5
    dt = jax.nn.softplus(_rand(keys[1], b, s, h))
    A = -jnp.exp(_rand(keys[2], h) * 0.3)
    Bm, Cm = _rand(keys[3], b, s, n), _rand(keys[4], b, s, n)
    D = jnp.ones((h,))
    y0, h0 = sref.ssd_naive(x, dt, A, Bm, Cm, D)
    y1, h1 = sref.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-4)


def test_ssd_initial_state():
    """Decode restart: SSD with h0 == continuing the naive recurrence."""
    b, s, h, p, n = 1, 64, 2, 16, 8
    keys = jax.random.split(jax.random.key(9), 6)
    x = _rand(keys[0], b, s, h, p) * 0.5
    dt = jax.nn.softplus(_rand(keys[1], b, s, h))
    A = -jnp.exp(_rand(keys[2], h) * 0.3)
    Bm, Cm = _rand(keys[3], b, s, n), _rand(keys[4], b, s, n)
    D = jnp.ones((h,))
    h0 = _rand(keys[5], b, h, p, n)
    want_y, want_h = sref.ssd_naive(x, dt, A, Bm, Cm, D, h0=h0)
    got_y, got_h = ssd(x, dt, A, Bm, Cm, D, h0=h0, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), atol=1e-4)


def test_ssd_decode_step_consistency():
    """Step-by-step decode equals the full scan."""
    b, s, h, p, n = 1, 8, 2, 16, 8
    keys = jax.random.split(jax.random.key(11), 5)
    x = _rand(keys[0], b, s, h, p) * 0.5
    dt = jax.nn.softplus(_rand(keys[1], b, s, h))
    A = -jnp.exp(_rand(keys[2], h) * 0.3)
    Bm, Cm = _rand(keys[3], b, s, n), _rand(keys[4], b, s, n)
    D = jnp.ones((h,))
    want_y, want_h = sref.ssd_naive(x, dt, A, Bm, Cm, D)
    hstate = jnp.zeros((b, h, p, n))
    for t in range(s):
        y_t, hstate = sref.ssd_decode_step(
            x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, hstate
        )
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(want_y[:, -1]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hstate), np.asarray(want_h), atol=1e-5)


@pytest.mark.parametrize("case", [(2, 256, 4, 2, 32, 64), (1, 128, 8, 8, 64, 32),
                                  (2, 512, 6, 3, 32, 128), (1, 64, 4, 1, 16, 32)])
def test_banded_swa_matches_masked_full(case):
    """Banded sliding-window prefill == full attention with window mask."""
    b, s, h, kv, d, w = case
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = _rand(keys[0], b, s, h, d)
    k = _rand(keys[1], b, s, kv, d)
    v = _rand(keys[2], b, s, kv, d)
    want = aref.mha(q, k, v, causal=True, window=w)
    got = aref.mha_banded(q, k, v, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
