"""The plain references of the served backbones, for the served cell's
``correct``: one entry, ``served_gaps``, for every architecture.

A backbone's ``arch`` (its entry under ``served.models`` in the
configuration file) names its reference,
``chipbench/reference/archs/<arch>.py``: a float32 forward pass over the
model's weights (as drawn by ``chipbench/weights.py``, in the tree the
model is served from), built from the pieces and the layer walker of
``chipbench/reference/layers.py``.  Where the served program departs from
the published model, the reference follows the program, and the
architecture's module lists the departure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.harness import arch_module


def served_gaps(params, prompts, served, m: dict, lower=None):
    """For each served token, the gap by which its logit lies below the
    reference's best at its position; with ``lower``, the gap of the
    token the lower-precision pass puts first there.

    ``prompts`` (B, P) and ``served`` (B, T) int arrays of one length each.
    Returns (gaps (B, T), dropped routings)."""
    prompts, served = jnp.asarray(prompts), jnp.asarray(served)
    p_len, t_len = prompts.shape[1], served.shape[1]
    tokens = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    at = list(range(p_len - 1, p_len + t_len - 1))
    forward = jax.jit(arch_module("reference", m["arch"]).forward, static_argnums=(2, 3, 4, 5))
    ref, dropped = forward(params, tokens, _frozen(m), p_len, tuple(at), None)
    best = ref.max(-1)
    if lower is None:
        pick = served
    else:
        low, _ = forward(params, tokens, _frozen(m), p_len, tuple(at), lower)
        pick = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return best - chosen, dropped


class _frozen(dict):
    """A model description that can be a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
