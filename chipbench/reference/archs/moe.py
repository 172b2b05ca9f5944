"""Granite-3.0-1B-A400M: grouped-query attention with RoPE, and a mixture
of SwiGLU experts, the ``experts_per_token`` most probable per token, their
probabilities renormalized to sum to one.

Where the served program departs from the published model, the reference
follows the program: Granite's embedding, attention, residual and logit
multipliers are not applied; the program's MoE prefill drops an expert's
tokens beyond its capacity (GShard, ``moe_capacity_factor`` of the
prompt's even share, in token order), so the reference applies the same
rule to the prompt's positions and none to the generated ones, which the
program decodes one by one.
"""
from __future__ import annotations

from chipbench.reference import layers
from chipbench.reference.layers import _attention, _moe, _rms


def attn_moe(x, p, m, pos, prompt_len, lower):
    eps = m["norm_eps"]
    x = x + _attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], m, pos, lower)
    out, dropped = _moe(_rms(x, p["ln2"]["scale"], eps), p["moe"], m, prompt_len, lower)
    return x + out, dropped


LAYERS = {"attn_moe": attn_moe}


def forward(params, tokens, m: dict, prompt_len: int, at, lower=None):
    return layers.forward(LAYERS, params, tokens, m, prompt_len, at, lower)
