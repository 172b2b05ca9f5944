"""Operations and bytes that the served models' calls need, from their
shapes: the least the algorithm must do, not what a kernel happens to do.

A model is described by its entry in the configuration file
(``served.models``); its ``arch`` names the module that counts it,
``chipbench/counts/archs/<arch>.py``, built from the pieces of
``chipbench/counts/layers.py``.  Operations count multiply-adds as two;
bytes count bfloat16 weights and caches as two bytes and float32 state as
four.

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

from chipbench.harness import arch_module


def _arch(m: dict):
    return arch_module("counts", m["arch"])


def layer_weights(m: dict) -> tuple[int, int]:
    """(weight elements read per layer, weight elements a token multiplies
    through per layer)."""
    return _arch(m).layer_weights(m)


def prefill(m: dict, s: int) -> dict:
    """Operations and bytes of the prefill of one prompt of ``s`` tokens."""
    return _arch(m).prefill(m, s)


def decode(m: dict, batch: int, pos: int) -> dict:
    """Operations and bytes of one decode step of ``batch`` slots at ``pos``."""
    return _arch(m).decode(m, batch, pos)


def kernels_prefill(m: dict, s: int) -> list[dict]:
    """The Pallas kernel calls of one prefill: per layer, flash attention
    (causal) or the SSD scan."""
    return _arch(m).kernels_prefill(m, s)


def kernels_decode(m: dict, batch: int, pos: int) -> list[dict]:
    """The Pallas kernel calls of one decode step: per attention layer,
    decode attention over ``pos + 1`` cached positions.  (The SSM decode
    step is plain XLA.)"""
    return _arch(m).kernels_decode(m, batch, pos)


def roofline_s(call: dict, peaks: dict) -> float:
    return max(call["flops"] / peaks["bf16_flops_per_s"],
               call["bytes"] / peaks["hbm_bytes_per_s"])
