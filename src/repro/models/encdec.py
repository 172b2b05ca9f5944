"""Encoder-decoder transformer (SeamlessM4T v2 text/speech backbone,
arXiv:2308.11596).

The audio frontend (mel filterbank + conformer feature extractor) is a STUB
per the assignment: the encoder consumes precomputed frame embeddings
(B, S_enc, d_model).  Encoder blocks are bidirectional self-attention +
FFN; decoder blocks add causal self-attention with a KV cache plus cross
attention against encoder output (cross K/V computed once per request).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers
from repro.models.attention import KVCacheSpec
from repro.models.config import ModelConfig
from repro.models.params import decl, tree_map_decls


def _stack(decl_tree, n: int):
    return tree_map_decls(
        lambda d: decl((n, *d.shape), ("layers", *d.axes), d.init, d.scale, d.dtype),
        decl_tree,
    )


def _enc_block_decls(cfg: ModelConfig):
    return {
        "ln1": layers.rmsnorm_decls(cfg.d_model),
        "attn": attn.attention_decls(cfg),
        "ln2": layers.rmsnorm_decls(cfg.d_model),
        "mlp": layers.ffn_decls(cfg.d_model, cfg.d_ff, cfg.ffn_type),
    }


def _dec_block_decls(cfg: ModelConfig):
    return {
        "ln1": layers.rmsnorm_decls(cfg.d_model),
        "self_attn": attn.attention_decls(cfg),
        "ln_x": layers.rmsnorm_decls(cfg.d_model),
        "cross_attn": attn.attention_decls(cfg),
        "ln2": layers.rmsnorm_decls(cfg.d_model),
        "mlp": layers.ffn_decls(cfg.d_model, cfg.d_ff, cfg.ffn_type),
    }


def model_decls(cfg: ModelConfig) -> dict:
    return {
        "embed": layers.embed_decls(cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings),
        "enc_blocks": _stack(_enc_block_decls(cfg), cfg.encoder_layers),
        "enc_norm": layers.rmsnorm_decls(cfg.d_model),
        "dec_blocks": _stack(_dec_block_decls(cfg), cfg.num_layers),
        "final_norm": layers.rmsnorm_decls(cfg.d_model),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(params, frame_embeds: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """frame_embeds (B, S_enc, D) -> encoder output (B, S_enc, D)."""
    b, s, _ = frame_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, p):
        h, _ = attn.self_attention(
            layers.rms_norm(x, p["ln1"], cfg.norm_eps), p["attn"], cfg, positions,
            causal=False,
        )
        x = x + h
        x = x + layers.ffn(layers.rms_norm(x, p["ln2"], cfg.norm_eps), p["mlp"], cfg.ffn_type)
        return x, 0

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, frame_embeds.astype(params["enc_norm"]["scale"].dtype),
                        params["enc_blocks"])
    return layers.rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Decoder (full sequence: train / prefill)
# ---------------------------------------------------------------------------

def _decode_stack(params, x, enc_out, cfg: ModelConfig, positions, collect_cache: bool):
    def body(xx, p):
        h, kv = attn.self_attention(
            layers.rms_norm(xx, p["ln1"], cfg.norm_eps), p["self_attn"], cfg, positions,
            causal=True,
        )
        xx = xx + h
        ckv = attn.cross_kv(enc_out, p["cross_attn"], cfg)
        xx = xx + attn.cross_attention(
            layers.rms_norm(xx, p["ln_x"], cfg.norm_eps), ckv, p["cross_attn"], cfg
        )
        xx = xx + layers.ffn(layers.rms_norm(xx, p["ln2"], cfg.norm_eps), p["mlp"], cfg.ffn_type)
        out = (kv, ckv) if collect_cache else 0
        return xx, out

    body_fn = jax.checkpoint(body) if cfg.remat else body
    return jax.lax.scan(body_fn, x, params["dec_blocks"])


def forward_train(params, batch, cfg: ModelConfig, aux_weight: float = 0.0):
    """batch: frontend_embeds (B,S_enc,D), tokens (B,S_dec), labels."""
    enc_out = encode(params, batch["frontend_embeds"], cfg)
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = layers.embed(tokens, params["embed"])
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _ = _decode_stack(params, x, enc_out, cfg, positions, collect_cache=False)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(x, params["embed"])
    loss = layers.cross_entropy_loss(logits, batch["labels"], cfg.padded_vocab)
    return loss, {"ce": loss, "aux": jnp.float32(0.0)}


# ---------------------------------------------------------------------------
# Serving: prefill (encoder + decoder prompt) and one-token decode
# ---------------------------------------------------------------------------

def cache_decls(cfg: ModelConfig, batch: int, max_len: int, enc_len: int) -> dict:
    spec = KVCacheSpec(size=max_len)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "self": _stack(attn.kv_cache_decls(cfg, batch, spec), cfg.num_layers),
        "cross_k": decl(
            (cfg.num_layers, batch, enc_len, kv, hd),
            ("layers", "cache_batch", "kv_seq", "kv_heads", None), init="zeros",
        ),
        "cross_v": decl(
            (cfg.num_layers, batch, enc_len, kv, hd),
            ("layers", "cache_batch", "kv_seq", "kv_heads", None), init="zeros",
        ),
    }


def prefill(params, batch, cfg: ModelConfig, max_len: int):
    """Encoder pass + decoder prompt pass; returns (logits_last, caches)."""
    enc_out = encode(params, batch["frontend_embeds"], cfg)
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = layers.embed(tokens, params["embed"])
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, caches_seq = _decode_stack(params, x, enc_out, cfg, positions, collect_cache=True)
    (k_seq, v_seq), (ck, cv) = caches_seq
    spec = KVCacheSpec(size=max_len)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(x[:, -1:], params["embed"])[:, 0]
    caches = {
        "self": {"k": attn.seed_kv_cache(k_seq, spec, s), "v": attn.seed_kv_cache(v_seq, spec, s)},
        "cross_k": ck, "cross_v": cv,
    }
    return logits, caches


def decode_step(params, caches, token, pos, cfg: ModelConfig, max_len: int):
    """token (B,) -> (logits (B,V), new caches); cross K/V are static."""
    spec = KVCacheSpec(size=max_len)
    x = layers.embed(token[:, None], params["embed"])

    def body(xx, scanned):
        p, kcache, ckv_k, ckv_v = scanned
        h, nc = attn.decode_self_attention(
            layers.rms_norm(xx, p["ln1"], cfg.norm_eps), kcache, p["self_attn"],
            cfg, pos, spec,
        )
        xx = xx + h
        xx = xx + attn.cross_attention(
            layers.rms_norm(xx, p["ln_x"], cfg.norm_eps), (ckv_k, ckv_v),
            p["cross_attn"], cfg,
        )
        xx = xx + layers.ffn(layers.rms_norm(xx, p["ln2"], cfg.norm_eps), p["mlp"], cfg.ffn_type)
        return xx, nc

    x, new_self = jax.lax.scan(
        body, x,
        (params["dec_blocks"], caches["self"], caches["cross_k"], caches["cross_v"]),
    )
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(x, params["embed"])
    new_caches = {"self": new_self, "cross_k": caches["cross_k"], "cross_v": caches["cross_v"]}
    return logits[:, 0], new_caches
