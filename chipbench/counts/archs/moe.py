"""Counts of a mixture-of-experts decoder: per layer, attention, a router
and ``num_experts`` SwiGLU experts, of which a token multiplies through
``experts_per_token``; the kernels are flash attention in prefill and
decode attention."""
from __future__ import annotations

from chipbench.counts import layers as c


def layer_weights(m: dict) -> tuple[int, int]:
    attn = c.attention_weights(m)
    one = c.swiglu_weights(m, m["d_ff"])
    router = m["d_model"] * m["num_experts"]
    return attn + m["num_experts"] * one + router, attn + m["experts_per_token"] * one + router


def prefill(m: dict, s: int) -> dict:
    return c.call(m, s, 1, [(layer_weights(m), c.attention_prefill(m, s))] * m["num_layers"])


def decode(m: dict, batch: int, pos: int) -> dict:
    return c.call(m, batch, batch,
                  [(layer_weights(m), c.attention_decode(m, batch, pos))] * m["num_layers"])


def kernels_prefill(m: dict, s: int) -> list[dict]:
    return [c.flash_attention(m, s)] * m["num_layers"]


def kernels_decode(m: dict, batch: int, pos: int) -> list[dict]:
    return [c.decode_attention(m, batch, pos)] * m["num_layers"]
