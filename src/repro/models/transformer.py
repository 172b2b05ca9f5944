"""Decoder-only language model covering dense / MoE / SSM / hybrid families.

Layer stacks are organized as *superblocks*: the repeating unit of the
config's block pattern (a single block for homogeneous models, Griffin's
(rglru, rglru, attn) for hybrids).  Superblock parameters are stacked along
a leading "layers" axis and the stack is traversed with ``lax.scan`` so the
lowered HLO is depth-independent — essential for compiling a 126-layer
405B model with 512 host devices in the dry-run.

Three entry points per model:
  forward_train(params, batch)          -> (loss, aux)
  prefill(params, tokens, ...)          -> (logits_last, caches)
  decode_step(params, caches, token, pos) -> (logits, caches)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import griffin, layers, moe, ssm
from repro.models.attention import KVCacheSpec
from repro.models.config import ModelConfig
from repro.models.params import decl, is_decl, tree_map_decls


# ---------------------------------------------------------------------------
# Block pattern handling
# ---------------------------------------------------------------------------

def block_pattern(cfg: ModelConfig) -> tuple[str, ...]:
    if cfg.arch_type == "dense":
        return ("attn_mlp",)
    if cfg.arch_type == "moe":
        return ("attn_moe",)
    if cfg.arch_type == "ssm":
        return ("ssm",)
    if cfg.arch_type == "hybrid":
        return tuple("attn_mlp" if b == "attn" else "rglru_mlp" for b in cfg.block_pattern)
    raise ValueError(cfg.arch_type)


def super_counts(cfg: ModelConfig) -> tuple[int, int]:
    """(full superblocks, remainder sub-blocks)."""
    p = len(block_pattern(cfg))
    return cfg.num_layers // p, cfg.num_layers % p


def _block_decls(kind: str, cfg: ModelConfig) -> dict:
    if kind == "attn_mlp":
        return {
            "ln1": layers.rmsnorm_decls(cfg.d_model),
            "attn": attn.attention_decls(cfg),
            "ln2": layers.rmsnorm_decls(cfg.d_model),
            "mlp": layers.ffn_decls(cfg.d_model, cfg.d_ff, cfg.ffn_type),
        }
    if kind == "attn_moe":
        return {
            "ln1": layers.rmsnorm_decls(cfg.d_model),
            "attn": attn.attention_decls(cfg),
            "ln2": layers.rmsnorm_decls(cfg.d_model),
            "moe": moe.moe_decls(cfg),
        }
    if kind == "ssm":
        return {"ln1": layers.rmsnorm_decls(cfg.d_model), "ssm": ssm.ssm_decls(cfg)}
    if kind == "rglru_mlp":
        return {
            "ln1": layers.rmsnorm_decls(cfg.d_model),
            "rec": griffin.rglru_decls(cfg),
            "ln2": layers.rmsnorm_decls(cfg.d_model),
            "mlp": layers.ffn_decls(cfg.d_model, cfg.d_ff, cfg.ffn_type),
        }
    raise ValueError(kind)


def _superblock_decls(cfg: ModelConfig) -> dict:
    return {f"b{i}_{k}": _block_decls(k, cfg) for i, k in enumerate(block_pattern(cfg))}


def _stack(decl_tree, n: int):
    return tree_map_decls(
        lambda d: decl((n, *d.shape), ("layers", *d.axes), d.init, d.scale), decl_tree
    )


def model_decls(cfg: ModelConfig) -> dict:
    n_super, rem = super_counts(cfg)
    pat = block_pattern(cfg)
    out: dict[str, Any] = {
        "embed": layers.embed_decls(cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings),
        "final_norm": layers.rmsnorm_decls(cfg.d_model),
        "blocks": _stack(_superblock_decls(cfg), n_super),
    }
    if rem:
        out["tail"] = {
            f"t{i}_{pat[i]}": _block_decls(pat[i], cfg) for i in range(rem)
        }
    return out


# ---------------------------------------------------------------------------
# Block forward (full sequence)
# ---------------------------------------------------------------------------

def _window_for(kind_idx_window: int, cfg: ModelConfig) -> int:
    if cfg.arch_type == "hybrid":
        return cfg.attention_window
    return cfg.sliding_window


def _block_fwd(kind: str, x, p, cfg: ModelConfig, positions):
    """Full-sequence forward.  Returns (x, aux_loss, cache_seed)."""
    if kind in ("attn_mlp", "attn_moe"):
        h, kv = attn.self_attention(
            layers.rms_norm(x, p["ln1"], cfg.norm_eps), p["attn"], cfg, positions,
            causal=True, window=_window_for(0, cfg),
        )
        x = x + h
        y = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind == "attn_mlp":
            x = x + layers.ffn(y, p["mlp"], cfg.ffn_type)
            return x, jnp.float32(0.0), kv
        moe_fn = moe.moe_ffn_grouped if cfg.moe_impl == "grouped" else moe.moe_ffn
        mo, aux = moe_fn(y, p["moe"], cfg, capacity_factor=cfg.moe_capacity_factor)
        return x + mo, aux, kv
    if kind == "ssm":
        h, state = ssm.ssm_block(layers.rms_norm(x, p["ln1"], cfg.norm_eps), p["ssm"], cfg)
        return x + h, jnp.float32(0.0), state  # state = (conv_tail, h)
    if kind == "rglru_mlp":
        h, state = griffin.recurrent_block(
            layers.rms_norm(x, p["ln1"], cfg.norm_eps), p["rec"], cfg
        )
        x = x + h
        x = x + layers.ffn(layers.rms_norm(x, p["ln2"], cfg.norm_eps), p["mlp"], cfg.ffn_type)
        return x, jnp.float32(0.0), state
    raise ValueError(kind)


def _superblock_fwd(x, sp, cfg: ModelConfig, positions, collect_cache: bool):
    aux_total = jnp.float32(0.0)
    seeds = {}
    for name, p in sp.items():
        kind = name.split("_", 1)[1]
        x, aux, seed = _block_fwd(kind, x, p, cfg, positions)
        aux_total = aux_total + aux
        if collect_cache:
            seeds[name] = seed
    return x, aux_total, seeds


def _run_stack(x, params, cfg: ModelConfig, positions, collect_cache: bool = False):
    """Scan over stacked superblocks + unrolled tail."""
    def body(carry, sp):
        xx, aux = carry
        xx, aux_sb, seeds = _superblock_fwd(xx, sp, cfg, positions, collect_cache)
        return (xx, aux + aux_sb), seeds if collect_cache else 0

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), seeds = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), params["blocks"])
    tail_seeds = {}
    if "tail" in params:
        for name, p in params["tail"].items():
            kind = name.split("_", 1)[1]
            x, a, seed = _block_fwd(kind, x, p, cfg, positions)
            aux = aux + a
            if collect_cache:
                tail_seeds[name] = seed
    return x, aux, seeds, tail_seeds


# ---------------------------------------------------------------------------
# Frontend (VLM stub): precomputed patch embeddings overwrite the first
# `frontend_tokens` positions of the token embedding sequence.
# ---------------------------------------------------------------------------

def _apply_frontend(x, batch):
    fe = batch.get("frontend_embeds")
    if fe is None:
        return x
    return jax.lax.dynamic_update_slice(x, fe.astype(x.dtype), (0, 0, 0))


def _positions(batch, cfg: ModelConfig, b: int, s: int):
    if cfg.mrope:
        p3 = batch.get("positions3")
        if p3 is None:
            base = jnp.arange(s, dtype=jnp.int32)[None, :, None]
            p3 = jnp.broadcast_to(base, (b, s, 3))
        return p3
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward_logits(params, batch, cfg: ModelConfig):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = layers.embed(tokens, params["embed"])
    x = _apply_frontend(x, batch)
    positions = _positions(batch, cfg, b, s)
    x, aux, _, _ = _run_stack(x, params, cfg, positions)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return layers.unembed(x, params["embed"]), aux


def forward_train(params, batch, cfg: ModelConfig, aux_weight: float = 0.01):
    logits, aux = forward_logits(params, batch, cfg)
    loss = layers.cross_entropy_loss(logits, batch["labels"], cfg.padded_vocab)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# -- caches ------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, max_len: int) -> KVCacheSpec:
    window = cfg.sliding_window or (cfg.attention_window if cfg.arch_type == "hybrid" else 0)
    if window:
        return KVCacheSpec(size=min(window, max_len), window=window)
    return KVCacheSpec(size=max_len, window=0)


def _block_cache_decls(kind: str, cfg: ModelConfig, batch: int, spec: KVCacheSpec):
    if kind in ("attn_mlp", "attn_moe"):
        return attn.kv_cache_decls(cfg, batch, spec)
    if kind == "ssm":
        return ssm.ssm_cache_decls(cfg, batch)
    if kind == "rglru_mlp":
        return griffin.rglru_cache_decls(cfg, batch)
    raise ValueError(kind)


def cache_decls(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    spec = cache_spec(cfg, max_len)
    n_super, rem = super_counts(cfg)
    pat = block_pattern(cfg)
    sb = {
        f"b{i}_{k}": _block_cache_decls(k, cfg, batch, spec)
        for i, k in enumerate(pat)
    }
    out = {"blocks": _stack(sb, n_super)}
    if rem:
        out["tail"] = {
            f"t{i}_{pat[i]}": _block_cache_decls(pat[i], cfg, batch, spec)
            for i in range(rem)
        }
    return out


# -- prefill -----------------------------------------------------------------

def _seed_to_cache(kind: str, seed, cfg: ModelConfig, spec: KVCacheSpec, s: int):
    """Convert a full-sequence cache seed into the decode cache layout."""
    if kind in ("attn_mlp", "attn_moe"):
        k, v = seed  # (..., B, S, KV, Dh); leading layer axis when stacked
        return {"k": attn.seed_kv_cache(k, spec, s), "v": attn.seed_kv_cache(v, spec, s)}
    conv_tail, h = seed
    return {"conv": conv_tail, "h": h.astype(jnp.float32)}


def prefill(params, batch, cfg: ModelConfig, max_len: int):
    """Full-sequence forward that also builds decode caches.

    Returns (logits_last (B, V), caches, aux).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    spec = cache_spec(cfg, max_len)
    x = layers.embed(tokens, params["embed"])
    x = _apply_frontend(x, batch)
    positions = _positions(batch, cfg, b, s)
    x, aux, seeds, tail_seeds = _run_stack(x, params, cfg, positions, collect_cache=True)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(x[:, -1:], params["embed"])[:, 0]
    caches = {
        "blocks": {
            name: _seed_to_cache(name.split("_", 1)[1], seed, cfg, spec, s)
            for name, seed in seeds.items()
        }
    }
    if tail_seeds:
        caches["tail"] = {
            name: _seed_to_cache(name.split("_", 1)[1], seed, cfg, spec, s)
            for name, seed in tail_seeds.items()
        }
    return logits, caches, aux


# -- decode ------------------------------------------------------------------

def _block_decode(kind: str, x, cache, p, cfg: ModelConfig, pos, spec: KVCacheSpec):
    if kind in ("attn_mlp", "attn_moe"):
        h, new_cache = attn.decode_self_attention(
            layers.rms_norm(x, p["ln1"], cfg.norm_eps), cache, p["attn"], cfg, pos, spec
        )
        x = x + h
        y = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind == "attn_mlp":
            return x + layers.ffn(y, p["mlp"], cfg.ffn_type), new_cache
        moe_fn = moe.moe_ffn_grouped if cfg.moe_impl == "grouped" else moe.moe_ffn
        mo, _ = moe_fn(y, p["moe"], cfg, capacity_factor=2.0)
        return x + mo, new_cache
    if kind == "ssm":
        h, new_cache = ssm.ssm_decode_step(
            layers.rms_norm(x, p["ln1"], cfg.norm_eps), cache, p["ssm"], cfg
        )
        return x + h, new_cache
    if kind == "rglru_mlp":
        h, new_cache = griffin.recurrent_decode_step(
            layers.rms_norm(x, p["ln1"], cfg.norm_eps), cache, p["rec"], cfg
        )
        x = x + h
        x = x + layers.ffn(layers.rms_norm(x, p["ln2"], cfg.norm_eps), p["mlp"], cfg.ffn_type)
        return x, new_cache
    raise ValueError(kind)


def decode_step(params, caches, token, pos, cfg: ModelConfig, max_len: int):
    """token (B,) int32; pos () int32 -> (logits (B,V), new caches)."""
    spec = cache_spec(cfg, max_len)
    x = layers.embed(token[:, None], params["embed"])

    def body(carry, scanned):
        xx = carry
        sp, scache = scanned
        new_caches = {}
        for name in sp:
            kind = name.split("_", 1)[1]
            xx, nc = _block_decode(kind, xx, scache[name], sp[name], cfg, pos, spec)
            new_caches[name] = nc
        return xx, new_caches

    x, new_block_caches = jax.lax.scan(body, x, (params["blocks"], caches["blocks"]))
    new_caches = {"blocks": new_block_caches}
    if "tail" in params:
        new_caches["tail"] = {}
        for name, p in params["tail"].items():
            kind = name.split("_", 1)[1]
            x, nc = _block_decode(kind, x, caches["tail"][name], p, cfg, pos, spec)
            new_caches["tail"][name] = nc
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(x, params["embed"])
    return logits[:, 0], new_caches
