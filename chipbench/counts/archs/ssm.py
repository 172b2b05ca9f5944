"""Counts of a Mamba-2 model: per layer, the SSM mixer's projections, its
convolution and recurrence, and its state; the Pallas kernel is the SSD
scan in prefill (the decode step is plain XLA)."""
from __future__ import annotations

from chipbench.counts import layers as c


def layer_weights(m: dict) -> tuple[int, int]:
    w = c.ssm_weights(m)
    return w, w


def prefill(m: dict, s: int) -> dict:
    return c.call(m, s, 1, [(layer_weights(m), c.ssm_prefill(m, s))] * m["num_layers"])


def decode(m: dict, batch: int, pos: int) -> dict:
    return c.call(m, batch, batch, [(layer_weights(m), c.ssm_decode(m, batch))] * m["num_layers"])


def kernels_prefill(m: dict, s: int) -> list[dict]:
    return [c.ssd_scan(m, s)] * m["num_layers"]


def kernels_decode(m: dict, batch: int, pos: int) -> list[dict]:
    return []
