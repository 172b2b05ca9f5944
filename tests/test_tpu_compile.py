"""Compile-only checks against a described TPU v5e chip.

The Pallas kernels and the streaming sweep kernel are compiled at the
served models' full widths for a ``v5e:2x2`` topology that is described,
not attached: the TPU compiler refuses here what interpret mode accepts
(block shapes off the (8, 128) tiling, rank-1 VMEM blocks), at no chip
time.  Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scope fixture and never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports every test file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import allocator as alloc
from repro.core import workload
from repro.core.agents import stack_fleets, synthetic_fleet
from repro.core.simulator import SimConfig
from repro.core.sweep import _stream_grid_jit, synth_gen_groups
from repro.kernels.attention.decode_attention import decode_attention
from repro.kernels.attention.flash_attention import flash_attention
from repro.kernels.ssd.ssd_scan import ssd

QWEN = get_config("qwen2-vl-2b")     # H=12, KV=2, D=128
MAMBA = get_config("mamba2-370m")    # 32 SSD heads, P=64, N=128


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: entries compiled for an absent chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("seq", [64, 2048])
def test_flash_attention_compiles_at_qwen2_vl_heads(one_chip, seq):
    h, kv, d = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    q = _sds((1, seq, h, d), jnp.bfloat16, one_chip)
    k = _sds((1, seq, kv, d), jnp.bfloat16, one_chip)
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, k
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("s_max", [48, 4096])
def test_decode_attention_compiles_at_qwen2_vl_heads(one_chip, s_max):
    h, kv, d = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    q = _sds((4, h, d), jnp.bfloat16, one_chip)
    cache = _sds((4, s_max, kv, d), jnp.bfloat16, one_chip)
    clen = _sds((), jnp.int32, one_chip)
    txt = _compiled_text(decode_attention, q, cache, cache, clen)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("seq", [128, 512])
def test_ssd_compiles_at_mamba2_heads(one_chip, seq):
    h, p, n = MAMBA.ssm_num_heads, MAMBA.ssm_head_dim, MAMBA.ssm_state_dim
    args = (
        _sds((1, seq, h, p), jnp.bfloat16, one_chip),
        _sds((1, seq, h), jnp.float32, one_chip),
        _sds((h,), jnp.float32, one_chip),
        _sds((1, seq, n), jnp.bfloat16, one_chip),
        _sds((1, seq, n), jnp.bfloat16, one_chip),
        _sds((h,), jnp.bfloat16, one_chip),
    )
    txt = _compiled_text(
        lambda *a: ssd(*a, chunk=MAMBA.ssm_chunk_size), *args
    )
    assert "tpu_custom_call" in txt


def test_streaming_sweep_kernel_compiles_at_1024_agents(one_chip):
    """The sweep_fleets grid kernel: 2 fleets of N=1024, the whole policy
    registry, the in-scan synthesized 8-scenario library."""
    n, steps = 1024, 1000
    fleets = [synthetic_fleet(n, seed=i) for i in range(2)]
    rates = [workload.synthetic_rates(n, seed=i) for i in range(2)]
    _, rows = workload.fleet_scenario_specs(rates, n, steps, 0)
    wspec = workload.stack_specs(
        [workload.stack_specs(r, name=f"fleet{i}") for i, r in enumerate(rows)]
    )
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: _sds(np.shape(a), jnp.asarray(a).dtype, one_chip), tree
    )
    compiled = _stream_grid_jit.lower(
        None, place(stack_fleets(fleets)), None, None, place(wspec), None,
        config=SimConfig(), names=alloc.policy_names(), batch_axis="fleet",
        block_size=1, gen_groups=synth_gen_groups(wspec),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
