"""Counts of a dense decoder: per layer, attention and a SwiGLU MLP; the
Pallas kernels are flash attention in prefill and decode attention."""
from __future__ import annotations

from chipbench.counts import layers as c


def layer_weights(m: dict) -> tuple[int, int]:
    w = c.attention_weights(m) + c.swiglu_weights(m, m["d_ff"])
    return w, w


def prefill(m: dict, s: int) -> dict:
    return c.call(m, s, 1, [(layer_weights(m), c.attention_prefill(m, s))] * m["num_layers"])


def decode(m: dict, batch: int, pos: int) -> dict:
    return c.call(m, batch, batch,
                  [(layer_weights(m), c.attention_decode(m, batch, pos))] * m["num_layers"])


def kernels_prefill(m: dict, s: int) -> list[dict]:
    return [c.flash_attention(m, s)] * m["num_layers"]


def kernels_decode(m: dict, batch: int, pos: int) -> list[dict]:
    return [c.decode_attention(m, batch, pos)] * m["num_layers"]
