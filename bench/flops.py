"""Model FLOPs of a training step, from the configuration and the
documents of the batch.

Per target-bearing token: 6 x the parameters of every matrix product a
token passes through (the kept layers' projections and MLP, and the
output head; the embedding lookup is a gather). Attention: 6 x the
multiply-adds of QK^T and PV over each document's causal triangle (2
products, 2 FLOPs each, 3x for forward and backward). A document of n
tokens carries n - 1 targets; its last token feeds no loss, so it is not
counted. Recomputation, padding slots and masked tiles are not counted.
"""

from __future__ import annotations

from typing import Dict, Iterable


def matmul_params(cfg: Dict) -> int:
    d, hq, hkv, dh, f, v, n = (int(cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "num_hidden_layers"))
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f
    return n * per_layer + v * d


def step_flops(cfg: Dict, doc_lengths: Iterable[int]) -> float:
    hq, dh, n_layers = (int(cfg[k]) for k in (
        "num_attention_heads", "head_dim", "num_hidden_layers"))
    p = matmul_params(cfg)
    total = 0.0
    for n in doc_lengths:
        t = n - 1
        if t <= 0:
            continue
        pairs = t * (t + 1) / 2
        total += 6.0 * p * t + 3 * 2 * 2 * pairs * hq * dh * n_layers
    return total
