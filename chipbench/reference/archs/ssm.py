"""Mamba-2 (arXiv:2405.21060): in-projection to (z, x, B, C, dt), causal
depthwise convolution of (x, B, C), SiLU, the state-space recurrence
``h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``
stepped token by token, gated RMSNorm, out-projection.
"""
from __future__ import annotations

from chipbench.reference import layers
from chipbench.reference.layers import _rms, _ssm


def ssm(x, p, m, pos, prompt_len, lower):
    return x + _ssm(_rms(x, p["ln1"]["scale"], m["norm_eps"]), p["ssm"], m, lower), 0.0


LAYERS = {"ssm": ssm}


def forward(params, tokens, m: dict, prompt_len: int, at, lower=None):
    return layers.forward(LAYERS, params, tokens, m, prompt_len, at, lower)
