"""Operations and bytes that the served models' calls need, from their
shapes: the least the algorithm must do, not what a kernel happens to do.

A model is described by its entry in the configuration file
(``served.models``).  Operations count multiply-adds as two; bytes count
bfloat16 weights and caches as two bytes and float32 state as four.

* Every call reads each weight matrix once (the output head included),
  and of the embedding table only the rows it looks up.
* Prefill of one prompt of ``s`` tokens: every layer's projections for
  ``s`` tokens, causal attention over ``s(s+1)/2`` query-key pairs, the
  SSM recurrence for ``s`` steps, the output head for the last position;
  it writes the attention cache for ``s`` positions, or the SSM state.
* Decode of ``batch`` slots at position ``pos``: every layer's projections
  for ``batch`` tokens, attention over ``pos + 1`` cached positions per
  slot, one recurrence step, the output head for every slot; it reads the
  cache of ``pos`` positions and writes one, or reads and writes the SSM
  state.
* MoE layers count the ``experts_per_token`` experts a token uses for
  operations, and every expert's weights for bytes (a batch of tokens
  touches them all).
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def _ssm_dims(m):
    di = m["ssm_expand"] * m["d_model"]
    return di, m["ssm_state_dim"], di // m["ssm_head_dim"], m["ssm_head_dim"]


def layer_weights(m: dict) -> tuple[int, int]:
    """(weight elements read per layer, weight elements a token multiplies
    through per layer)."""
    d = m["d_model"]
    if m["arch"] == "ssm":
        di, n, nh, _ = _ssm_dims(m)
        w = d * (2 * di + 2 * n + nh) + di * d
        return w, w
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = d * h * hd * 2 + d * kv * hd * 2
    if m["arch"] == "moe":
        one = 3 * d * m["d_ff"]
        router = d * m["num_experts"]
        return attn + m["num_experts"] * one + router, attn + m["experts_per_token"] * one + router
    return attn + 3 * d * m["d_ff"], attn + 3 * d * m["d_ff"]


def _per_token_mixing_flops(m: dict, context: int) -> float:
    """Attention over ``context`` keys, or one SSM step, for one token and
    one layer."""
    if m["arch"] == "ssm":
        di, n, nh, p = _ssm_dims(m)
        return 4 * nh * p * n + 2 * m["ssm_conv_width"] * (di + 2 * n)
    return 4 * m["num_heads"] * m["head_dim"] * context


def _state_bytes(m: dict) -> int:
    """One slot's SSM state per layer (f32 recurrent state, bf16 conv tail)."""
    di, n, nh, p = _ssm_dims(m)
    return nh * p * n * F32 + (m["ssm_conv_width"] - 1) * (di + 2 * n) * BF16


def _kv_bytes_per_position(m: dict) -> int:
    return 2 * m["num_kv_heads"] * m["head_dim"] * BF16


def prefill(m: dict, s: int) -> dict:
    layers, d, v = m["num_layers"], m["d_model"], _padded_vocab(m)
    read_w, used_w = layer_weights(m)
    flops = layers * 2 * used_w * s + 2 * d * v
    if m["arch"] == "ssm":
        flops += layers * s * _per_token_mixing_flops(m, 0)
        cache = layers * _state_bytes(m)
    else:
        flops += layers * 4 * m["num_heads"] * m["head_dim"] * s * (s + 1) / 2
        cache = layers * s * _kv_bytes_per_position(m)
    weights = (layers * read_w + d * v) * BF16 + s * d * BF16
    return {"flops": float(flops), "bytes": float(weights + cache)}


def decode(m: dict, batch: int, pos: int) -> dict:
    layers, d, v = m["num_layers"], m["d_model"], _padded_vocab(m)
    read_w, used_w = layer_weights(m)
    flops = layers * batch * (2 * used_w + _per_token_mixing_flops(m, pos + 1))
    flops += batch * 2 * d * v
    if m["arch"] == "ssm":
        cache = layers * batch * 2 * _state_bytes(m)
    else:
        cache = layers * batch * (pos + 1) * _kv_bytes_per_position(m)
    weights = (layers * read_w + d * v) * BF16 + batch * d * BF16
    return {"flops": float(flops), "bytes": float(weights + cache)}


def kernels_prefill(m: dict, s: int) -> list[dict]:
    """The Pallas kernel calls of one prefill: per layer, flash attention
    (causal) or the SSD scan."""
    if m["arch"] == "ssm":
        di, n, nh, p = _ssm_dims(m)
        one = {"kernel": "ssd_scan",
               "flops": float(4 * s * nh * p * n),
               "bytes": float(s * nh * p * BF16 * 2 + s * nh * F32 + 2 * s * n * BF16
                              + nh * p * n * F32)}
    else:
        h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
        one = {"kernel": "flash_attention",
               "flops": float(4 * h * hd * s * (s + 1) / 2),
               "bytes": float((2 * s * h * hd + 2 * s * kv * hd) * BF16)}
    return [one] * m["num_layers"]


def kernels_decode(m: dict, batch: int, pos: int) -> list[dict]:
    """The Pallas kernel calls of one decode step: per attention layer,
    decode attention over ``pos + 1`` cached positions.  (The SSM decode
    step is plain XLA.)"""
    if m["arch"] == "ssm":
        return []
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    ctx = pos + 1
    one = {"kernel": "decode_attention",
           "flops": float(4 * batch * h * hd * ctx),
           "bytes": float((batch * ctx * 2 * kv * hd + 2 * batch * h * hd) * BF16)}
    return [one] * m["num_layers"]


def roofline_s(call: dict, peaks: dict) -> float:
    return max(call["flops"] / peaks["bf16_flops_per_s"],
               call["bytes"] / peaks["hbm_bytes_per_s"])
