"""Operations and bytes of the pieces a served model's calls are made of,
from their shapes (the rules are in ``chipbench/counts/models.py``), and
the totals of one call over its layers.

A layer's part of a call is its weights, ``(read, used)``: the weight
elements read, and those a token multiplies through, and its mixing,
``{"flops", "bytes"}``: attention over the cached positions or the SSM
recurrence, and the cache or state it reads and writes.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def ssm_dims(m: dict):
    """(inner width, state size, heads, head width) of a Mamba-2 mixer."""
    di = m["ssm_expand"] * m["d_model"]
    return di, m["ssm_state_dim"], di // m["ssm_head_dim"], m["ssm_head_dim"]


def attention_weights(m: dict) -> int:
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return d * h * hd * 2 + d * kv * hd * 2


def swiglu_weights(m: dict, width: int) -> int:
    return 3 * m["d_model"] * width


def ssm_weights(m: dict) -> int:
    di, n, nh, _ = ssm_dims(m)
    return m["d_model"] * (2 * di + 2 * n + nh) + di * m["d_model"]


def state_bytes(m: dict) -> int:
    """One slot's SSM state per layer (f32 recurrent state, bf16 conv tail)."""
    di, n, nh, p = ssm_dims(m)
    return nh * p * n * F32 + (m["ssm_conv_width"] - 1) * (di + 2 * n) * BF16


def kv_bytes_per_position(m: dict) -> int:
    return 2 * m["num_kv_heads"] * m["head_dim"] * BF16


def attention_prefill(m: dict, s: int) -> dict:
    """Causal attention over ``s(s+1)/2`` query-key pairs; the cache of
    ``s`` positions written."""
    return {"flops": 4 * m["num_heads"] * m["head_dim"] * s * (s + 1) / 2,
            "bytes": s * kv_bytes_per_position(m)}


def attention_decode(m: dict, batch: int, pos: int) -> dict:
    """Attention over ``pos + 1`` positions per slot; the cache of ``pos``
    positions read and one written."""
    return {"flops": batch * 4 * m["num_heads"] * m["head_dim"] * (pos + 1),
            "bytes": batch * (pos + 1) * kv_bytes_per_position(m)}


def ssm_step_flops(m: dict) -> int:
    """One recurrence step and the convolution, for one token."""
    di, n, nh, p = ssm_dims(m)
    return 4 * nh * p * n + 2 * m["ssm_conv_width"] * (di + 2 * n)


def ssm_prefill(m: dict, s: int) -> dict:
    """The recurrence over ``s`` steps; the state written."""
    return {"flops": s * ssm_step_flops(m), "bytes": state_bytes(m)}


def ssm_decode(m: dict, batch: int) -> dict:
    """One step per slot; the state read and written."""
    return {"flops": batch * ssm_step_flops(m), "bytes": batch * 2 * state_bytes(m)}


def call(m: dict, tokens: int, logits: int, layers: list) -> dict:
    """One call's totals: ``layers``, a ``(weights, mixing)`` pair per
    layer, for ``tokens`` tokens, then the output head for ``logits``
    positions.  Every weight is read once; of the embedding table only the
    ``tokens`` rows looked up."""
    d, v = m["d_model"], padded_vocab(m)
    flops = 2 * d * v * logits
    moved = (d * v + tokens * d) * BF16
    for (read, used), mixing in layers:
        flops += 2 * used * tokens + mixing["flops"]
        moved += read * BF16 + mixing["bytes"]
    return {"flops": float(flops), "bytes": float(moved)}


def flash_attention(m: dict, s: int) -> dict:
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return {"kernel": "flash_attention",
            "flops": float(4 * h * hd * s * (s + 1) / 2),
            "bytes": float((2 * s * h * hd + 2 * s * kv * hd) * BF16)}


def decode_attention(m: dict, batch: int, pos: int) -> dict:
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    ctx = pos + 1
    return {"kernel": "decode_attention",
            "flops": float(4 * batch * h * hd * ctx),
            "bytes": float((batch * ctx * 2 * kv * hd + 2 * batch * h * hd) * BF16)}


def ssd_scan(m: dict, s: int) -> dict:
    di, n, nh, p = ssm_dims(m)
    return {"kernel": "ssd_scan",
            "flops": float(4 * s * nh * p * n),
            "bytes": float(s * nh * p * BF16 * 2 + s * nh * F32 + 2 * s * n * BF16
                           + nh * p * n * F32)}
