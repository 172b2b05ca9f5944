"""Qwen2-VL's decoder (arXiv:2409.12191): RMSNorm, grouped-query attention
with multimodal RoPE (three sections of the rotary frequencies, 2:1:1, each
turned by its own position channel), SwiGLU.

Where the served program departs from the published model, the reference
follows the program: the vision encoder is a stub, so the first
``frontend_tokens`` prompt positions are embedded as zeros (the engine's
stand-in for image features), and every M-RoPE section gets the text
position, image positions included.
"""
from __future__ import annotations

from chipbench.reference import layers
from chipbench.reference.layers import _attention, _rms, _swiglu


def attn_mlp(x, p, m, pos, prompt_len, lower):
    eps = m["norm_eps"]
    x = x + _attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], m, pos, lower)
    y = _rms(x, p["ln2"]["scale"], eps)
    return x + _swiglu(y, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"], lower), 0.0


LAYERS = {"attn_mlp": attn_mlp}


def forward(params, tokens, m: dict, prompt_len: int, at, lower=None):
    return layers.forward(LAYERS, params, tokens, m, prompt_len, at, lower)
