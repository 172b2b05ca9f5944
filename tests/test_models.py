"""Per-architecture smoke tests (reduced configs) + serving consistency.

Each assigned architecture instantiates its REDUCED family variant, runs a
train step and a prefill->decode chain on CPU, and asserts shapes + no
NaNs + decode/prefill agreement.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.kernels.attention import decode_attention as da
from repro.kernels.attention import ops as attn_ops
from repro.models.model import (
    INPUT_SHAPES, InputShape, build_model, concrete_inputs, shape_applicable,
)
from repro.training.optimizer import OptimizerConfig
from repro.training.train_step import build_train_step

KEY = jax.random.key(0)
SMALL_TRAIN = InputShape("train_small", 32, 2, "train")
SMALL_PREFILL = InputShape("prefill_small", 16, 2, "prefill")


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch(request):
    return request.param


class TestSmoke:
    def test_train_step(self, arch):
        cfg = get_config(arch, reduced=True)
        api = build_model(cfg)
        params = api.init(KEY)
        batch = concrete_inputs(cfg, SMALL_TRAIN, KEY)
        step = build_train_step(api, OptimizerConfig(warmup_steps=1, total_steps=10))
        from repro.training.optimizer import init_opt_state

        opt = init_opt_state(params)
        params2, opt2, metrics = jax.jit(step)(params, opt, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(opt2["step"]) == 1
        # parameters actually moved
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
            params, params2,
        )
        assert max(jax.tree_util.tree_leaves(moved)) > 0

    def test_logits_shape_and_finite(self, arch):
        cfg = get_config(arch, reduced=True)
        api = build_model(cfg)
        params = api.init(KEY)
        batch = concrete_inputs(cfg, SMALL_PREFILL, KEY)
        logits, caches = api.prefill(params, batch, SMALL_PREFILL.seq_len + 4)
        assert logits.shape == (2, cfg.padded_vocab)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    def test_decode_matches_prefill(self, arch):
        _check_decode_matches_prefill(arch, max_len=16 + 4)

    def test_full_config_declares(self, arch):
        """FULL configs build decl trees + ShapeDtypeStructs w/o allocation."""
        cfg = get_config(arch)
        api = build_model(cfg)
        sds = api.abstract()
        n = sum(
            np.prod(l.shape) for l in jax.tree_util.tree_leaves(sds)
        )
        assert n > 0.5 * cfg.param_count  # stacked decls cover the model

    def test_shape_applicability_matrix(self, arch):
        cfg = get_config(arch)
        ok_500k, _ = shape_applicable(cfg, INPUT_SHAPES["long_500k"])
        expect = arch in ("mamba2-370m", "recurrentgemma-9b", "mixtral-8x7b")
        assert ok_500k == expect
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(cfg, INPUT_SHAPES[s])[0]


def _check_decode_matches_prefill(arch: str, max_len: int):
    """Prefill 16 tokens, decode 4 more into caches of `max_len`, and
    compare the last logits with a prefill of all 20."""
    cfg = get_config(arch, reduced=True)
    api = build_model(cfg)
    params = api.init(KEY, dtype=jnp.float32)
    s, extra = 16, 4
    batch = concrete_inputs(cfg, SMALL_PREFILL, KEY, dtype=jnp.float32)
    _, caches = api.prefill(params, batch, max_len)
    toks = jax.random.randint(jax.random.key(2), (2, extra), 0, cfg.vocab_size)
    last = None
    for i in range(extra):
        last, caches = api.decode_step(params, caches, toks[:, i], jnp.int32(s + i), max_len)
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([batch["tokens"], toks], axis=1)
    if "positions3" in batch2:
        base = jnp.arange(s + extra, dtype=jnp.int32)[None, :, None]
        batch2["positions3"] = jnp.broadcast_to(base, (2, s + extra, 3))
    want, _ = api.prefill(params, batch2, max_len)
    np.testing.assert_allclose(np.asarray(last), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("arch", [
    "qwen2-vl-2b", "granite-moe-1b-a400m", "mixtral-8x7b", "recurrentgemma-9b",
])
def test_decode_matches_prefill_through_kernel(arch, monkeypatch):
    """The same check with the attention of every decode step in the Pallas
    decode kernel (interpreted): caches of 1,280 positions, no multiple of
    the kernel's 512-position block, are read in blocks of 256 with no pad;
    the rolling-window models keep a 16-position cache of their window."""
    calls = []
    kernel = da.decode_attention

    def counted(q, k_cache, *args, **kw):
        calls.append(k_cache.shape)
        return kernel(q, k_cache, *args, **kw)

    monkeypatch.setattr(attn_ops, "_default_impl", lambda: "pallas")
    monkeypatch.setattr(da, "decode_attention", counted)
    jax.clear_caches()  # no trace of the reference path may be reused
    try:
        _check_decode_matches_prefill(arch, max_len=1280)
    finally:
        jax.clear_caches()
    assert calls and all(shape[-1] in (16, 1280) for shape in calls), calls


class TestTrainingConvergence:
    def test_loss_decreases_on_synthetic_data(self):
        """granite-8b reduced on the Markov-Zipf pipeline: loss must drop."""
        from repro.data.pipeline import DataConfig, SyntheticTokens

        cfg = get_config("granite-8b", reduced=True)
        api = build_model(cfg)
        params = api.init(KEY, dtype=jnp.float32)
        data = SyntheticTokens(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                          global_batch=8, seed=0))
        from repro.training.optimizer import init_opt_state

        step = jax.jit(build_train_step(api, OptimizerConfig(
            lr=3e-3, warmup_steps=2, total_steps=40)))
        opt = init_opt_state(params)
        losses = []
        for i in range(15):
            b = data.batch(i)
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.3, losses
