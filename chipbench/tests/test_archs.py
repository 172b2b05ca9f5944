"""The served path's references and counts, found by architecture.

* The counts and the references of the three served architectures give
  what they gave before they were split into ``archs/`` modules: the
  constants below were recorded from commit
  7ff3cdaf2d06f202b2f756b8c39a5ddc5be3d471, on the CPU with the installed
  JAX (the reference's checksums are of float32 logits, bit for bit).
* The layer walker runs sub-blocks in index order, superblocks then tail.
* A new architecture needs only its two modules.
* The model check compares every key the configuration file states.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_archs.py
"""
from __future__ import annotations

import copy
import hashlib
import sys
import types

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from cells import reduced_models
from chipbench import harness, weights
from chipbench.counts import layers as count_layers
from chipbench.counts import models as counts
from chipbench.drivers import served_waves
from chipbench.reference import layers
from chipbench.reference import models as ref_models
from chipbench.reference.archs import moe as ref_moe
from chipbench.reference.archs import ssm as ref_ssm

SERVED = "table1-served-waves"

# Per role, one wave of the cell's traffic at published widths: summed
# operations and bytes of the model calls, of the Pallas kernel calls, and
# the number of each.
PARENT_CALLS = {
    "coordinator": (50910881120256.0, 508811083776.0, 1815353229312.0, 67309928448.0,
                    144, 4032),
    "specialist_nlp": (3849491316736.0, 643962109952.0, 63653806080.0, 30374363136.0,
                       230, 5520),
    "specialist_vision": (14505179086848.0, 233481568256.0, 152153358336.0, 8989245440.0,
                          73, 2044),
    "specialist_reasoning": (6004753301504.0, 810422829056.0, 129654325248.0, 1897365504.0,
                             353, 768),
}
PARENT_LAYER_WEIGHTS = {
    "qwen2-vl-2b": (46792704, 46792704),
    "granite-moe-1b-a400m": (53510144, 15761408),
    "mamba2-370m": (6586368, 6586368),
}
# sha256 of the logits, dropped routings.
PARENT_REFERENCE = {
    ("qwen2-vl-2b", "float32"):
        ("a59dac5558aa488007c79146353e33d10338cc9f0b696b14d6354cc29cdd115b", 0.0),
    ("qwen2-vl-2b", "float8"):
        ("d612841830a15987e9f48dc497bc4792b435b26a8f71c4aa78b03b0030a88067", 0.0),
    ("granite-moe-1b-a400m", "float32"):
        ("66a4d3e2bda87176e4048ab7b57b67d27b59149ddbf261366298807afab2b745", 17.0),
    ("granite-moe-1b-a400m", "float8"):
        ("62cf8f112148b8d95df03857986fd0c297b799ef6c3d75c2c178f5c6290890a8", 17.0),
    ("mamba2-370m", "float32"):
        ("99894d70a4222c05359d121c6952c248e5fbf2a37581e989dd33b0fc590e4504", 0.0),
    ("mamba2-370m", "float8"):
        ("11044234f6a46049500c8804f17cfe3ae3abc832556e843b2d4dc601d0a08b29", 0.0),
}
LOWER = {"float32": None, "float8": ml_dtypes.float8_e4m3fn}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(SERVED)


@pytest.mark.parametrize("role", sorted(PARENT_CALLS))
def test_counts_of_a_wave_are_the_parents(cell, role):
    tr, cfg = cell.traffic, cell.config
    arch = cfg["served"]["backbones"][role]
    records = {"waves": [{"agent": role, "batch": tr["batch_slots"],
                          "prompt": tr["roles"][role]["prompt"],
                          "output": tr["roles"][role]["output"]}],
               "models": {role: served_waves.served_models(cfg)[arch]}}
    model_calls, kernel_calls = served_waves.calls(records)
    got = (sum(c["flops"] for c in model_calls), sum(c["bytes"] for c in model_calls),
           sum(c["flops"] for c in kernel_calls), sum(c["bytes"] for c in kernel_calls),
           len(model_calls), len(kernel_calls))
    assert got == PARENT_CALLS[role]


@pytest.mark.parametrize("arch", sorted(PARENT_LAYER_WEIGHTS))
def test_layer_weights_are_the_parents(cell, arch):
    m = served_waves.served_models(cell.config)[arch]
    assert counts.layer_weights(m) == PARENT_LAYER_WEIGHTS[arch]


def _reduced(cell, arch: str):
    """The reduced backbone's description and weights; MoE capacity 1.0,
    so that the prompt's capacity drops routings."""
    from repro.configs import get_config
    from repro.models.model import build_model

    m = dict(reduced_models(cell.config)[arch])
    if m["arch"] == "moe":
        m["moe_capacity_factor"] = 1.0
    index = sorted(PARENT_LAYER_WEIGHTS).index(arch)
    decls = build_model(get_config(arch, reduced=True)).param_decls
    params, = weights.make([decls], [weights.agent_key(2**31 + 77, index)])
    tokens = np.random.default_rng(1234 + index).integers(0, m["vocab_size"], (2, 24))
    return m, params, jnp.asarray(tokens, jnp.int32)


@pytest.mark.parametrize("arch,precision", sorted(PARENT_REFERENCE))
def test_reference_logits_are_the_parents(cell, arch, precision):
    m, params, tokens = _reduced(cell, arch)
    forward = jax.jit(harness.arch_module("reference", m["arch"]).forward,
                      static_argnums=(2, 3, 4, 5))
    logits, dropped = forward(params, tokens, ref_models._frozen(m), 16,
                              tuple(range(15, 24)), LOWER[precision])
    digest = hashlib.sha256(np.asarray(logits, np.float32).tobytes()).hexdigest()
    assert (digest, float(dropped)) == PARENT_REFERENCE[(arch, precision)]


def _unstacked(decls):
    from repro.models.params import decl, tree_map_decls

    return tree_map_decls(lambda d: decl(d.shape[1:], d.axes[1:], d.init, d.scale, d.dtype),
                          decls)


def test_walker_runs_sub_blocks_in_index_order():
    """Eleven sub-blocks of two kinds a superblock, two superblocks and a
    tail, through the walker and chained by hand."""
    from repro.configs import get_config
    from repro.models.model import build_model

    kinds = ["ssm", "attn_moe", "ssm", "ssm", "attn_moe", "ssm", "ssm", "ssm", "ssm",
             "attn_moe", "ssm"]
    tail = ["ssm", "attn_moe"]
    moe_decls = build_model(get_config("granite-moe-1b-a400m", reduced=True)).param_decls
    ssm_decls = build_model(get_config("mamba2-370m", reduced=True)).param_decls
    stacked = {"attn_moe": moe_decls["blocks"]["b0_attn_moe"],
               "ssm": ssm_decls["blocks"]["b0_ssm"]}
    decls = {"embed": moe_decls["embed"], "final_norm": moe_decls["final_norm"],
             "blocks": {f"b{i}_{k}": stacked[k] for i, k in enumerate(kinds)},
             "tail": {f"t{i}_{k}": _unstacked(stacked[k]) for i, k in enumerate(tail)}}
    params, = weights.make([decls], [weights.agent_key(2**31 + 11, 0)])
    models = reduced_models({"served": {"models": {"granite-moe-1b-a400m": {},
                                                   "mamba2-370m": {}}}})
    m = dict(models["granite-moe-1b-a400m"], moe_capacity_factor=1.0)
    m.update({k: v for k, v in models["mamba2-370m"].items() if k.startswith("ssm_")})
    fns = {"attn_moe": ref_moe.attn_moe, "ssm": ref_ssm.ssm}
    n_super = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
    assert n_super == 2
    x = jax.random.normal(jax.random.key(5), (2, 16, m["d_model"]), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None], (2, 16))

    def by_hand(x, order):
        dropped = jnp.float32(0.0)
        for j in range(n_super):
            for name in order:
                p = jax.tree_util.tree_map(lambda a: a[j], params["blocks"][name])
                x, dr = fns[name.split("_", 1)[1]](x, p, m, pos, 12, None)
                dropped = dropped + dr
        for i, k in enumerate(tail):
            x, dr = fns[k](x, params["tail"][f"t{i}_{k}"], m, pos, 12, None)
            dropped = dropped + dr
        return x, dropped

    with jax.default_matmul_precision("highest"):
        walked, dropped = jax.jit(lambda x: layers.walk(fns, params, x, m, pos, 12, None))(x)
        chained, dropped_by_hand = jax.jit(
            lambda x: by_hand(x, [f"b{i}_{k}" for i, k in enumerate(kinds)]))(x)
        as_named = jax.jit(lambda x: by_hand(x, sorted(params["blocks"])))(x)[0]
    assert sorted(params["blocks"])[:3] == ["b0_ssm", "b10_ssm", "b1_attn_moe"]
    assert bool(jnp.array_equal(walked, chained))
    assert float(dropped) == float(dropped_by_hand) > 0
    # Name order differs from index order by far more than rounding.
    assert float(jnp.abs(as_named - chained).max()) > 1e-2 * float(jnp.abs(chained).max())


def _toy_layer(x, p, m, pos, prompt_len, lower):
    return x + jnp.tanh(layers._mm(x, p["w"], lower)), 0.0


def _toy_modules():
    reference = types.ModuleType("chipbench.reference.archs.toy")
    reference.LAYERS = {"toy_mix": _toy_layer}
    reference.forward = lambda params, tokens, m, prompt_len, at, lower=None: layers.forward(
        reference.LAYERS, params, tokens, m, prompt_len, at, lower)
    count = types.ModuleType("chipbench.counts.archs.toy")
    count.layer_weights = lambda m: (m["d_model"] ** 2, m["d_model"] ** 2)
    count.prefill = lambda m, s: count_layers.call(
        m, s, 1, [(count.layer_weights(m), {"flops": 0, "bytes": 0})] * m["num_layers"])
    count.decode = lambda m, batch, pos: count_layers.call(
        m, batch, batch, [(count.layer_weights(m), {"flops": 0, "bytes": 0})] * m["num_layers"])
    count.kernels_prefill = lambda m, s: []
    count.kernels_decode = lambda m, batch, pos: []
    return reference, count


def test_a_new_architecture_needs_only_its_modules(monkeypatch):
    import repro.configs
    from repro.models.config import ModelConfig

    reference, count = _toy_modules()
    monkeypatch.setitem(sys.modules, reference.__name__, reference)
    monkeypatch.setitem(sys.modules, count.__name__, count)
    d, v = 8, 16
    m = {"source": "a toy", "arch": "toy", "num_layers": 2, "d_model": d, "vocab_size": v,
         "norm_eps": 1e-5, "toy_gain": None}
    program = ModelConfig(name="toy-1", arch_type="toy", num_layers=2, d_model=d, vocab_size=v)
    monkeypatch.setattr(repro.configs, "get_config", lambda name, reduced=False: program)
    config = {"served": {"backbones": {"a": "toy-1"}, "models": {"toy-1": m}}}
    assert served_waves.model_configs(config) == {"toy-1": program}

    k = jax.random.split(jax.random.key(3), 4)
    params = {"embed": {"embedding": jax.random.normal(k[0], (v, d)),
                        "lm_head": jax.random.normal(k[1], (d, v))},
              "final_norm": {"scale": jnp.ones(d)},
              "blocks": {"b0_toy_mix": {"w": jax.random.normal(k[2], (2, d, d)) / d ** 0.5}}}
    # A layer that mixes no positions: the greedy token after each token
    # is the reference's best at that token.
    best = jnp.argmax(reference.forward(params, jnp.arange(v)[None], m, v, tuple(range(v)))[0][0],
                      axis=-1)
    prompts = np.asarray(jax.random.randint(k[3], (2, 5), 0, v))
    served = [best[prompts[:, -1]]]
    for _ in range(2):
        served.append(best[served[-1]])
    served = np.stack(served, axis=1)
    gaps, dropped = ref_models.served_gaps(params, prompts, served, m)
    assert gaps.shape == (2, 3) and float(jnp.abs(gaps).max()) == 0.0 and float(dropped) == 0
    served[0, 1] = (served[0, 1] + 1) % v
    gaps, _ = ref_models.served_gaps(params, prompts, served, m)
    assert float(gaps[0, 1]) > 0

    records = {"waves": [{"agent": "a", "batch": 2, "prompt": 5, "output": 3}],
               "models": {"a": m}}
    model_calls, kernel_calls = served_waves.calls(records)
    head = 2 * d * count_layers.padded_vocab(m)
    assert [c["flops"] for c in model_calls] == [
        2 * 2 * d * d * 5 + head, 2 * 2 * d * d * 5 + head,
        2 * 2 * d * d * 2 + 2 * head, 2 * 2 * d * d * 2 + 2 * head]
    assert kernel_calls == []


def test_an_unknown_architecture_names_the_missing_file():
    with pytest.raises(ValueError, match=r"chipbench/counts/archs/nonesuch\.py is missing"):
        counts.prefill({"arch": "nonesuch"}, 8)
    with pytest.raises(ValueError, match=r"chipbench/reference/archs/nonesuch\.py is missing"):
        ref_models.served_gaps({}, np.zeros((1, 2), int), np.zeros((1, 2), int),
                               {"arch": "nonesuch"})


def test_the_table1_onechip_models_pass_the_model_check(cell):
    got = served_waves.model_configs(cell.config)
    assert sorted(got) == ["granite-moe-1b-a400m", "mamba2-370m", "qwen2-vl-2b"]


def _stated(config, model, key, value):
    config["served"]["models"][model][key] = value


def _served(config, model, key, value):
    config["served"]["departures"][model][key]["served"] = value


@pytest.mark.parametrize("edit,key", [
    # A key the old fixed list of compared keys left out.
    (lambda c: _stated(c, "qwen2-vl-2b", "sliding_window", 4096), "sliding_window"),
    # A published key the program lacks, served as published.
    (lambda c: _served(c, "granite-moe-1b-a400m", "residual_multiplier", 0.22),
     "residual_multiplier"),
    # Served 0, which is applied (0 == False in Python).
    (lambda c: _served(c, "granite-moe-1b-a400m", "embedding_multiplier", 0.0),
     "embedding_multiplier"),
], ids=["a_key_outside_the_old_list", "a_lacking_key_applied", "a_lacking_key_served_0"])
def test_the_model_check_refuses(cell, edit, key):
    config = copy.deepcopy(cell.config)
    edit(config)
    with pytest.raises(ValueError, match=key):
        served_waves.model_configs(config)
