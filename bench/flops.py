"""Model FLOPs of a training step, from the configuration and the
documents of the batch.

Per target-bearing token: 6 x the parameters of every matrix product a
token passes through (the kept layers' projections and MLP, and the
output head; the embedding lookup is a gather). Attention: 6 x the
multiply-adds of QK^T and PV over each document's causal triangle (2
products, 2 FLOPs each, 3x for forward and backward). A document of n
tokens carries n - 1 targets; its last token feeds no loss, so it is not
counted. Recomputation, padding slots and masked tiles are not counted.

The count of a step is its family's (``step_flops`` of the program
adapter that the configuration names, ``bench/configs/__init__.py``);
the output head's is the same in every family.
"""

from __future__ import annotations

from typing import Dict, Iterable

import configs


def step_flops(cfg: Dict, doc_lengths: Iterable[int]) -> float:
    return configs.program(cfg).step_flops(cfg, doc_lengths)


def head_flops(cfg: Dict, doc_lengths: Iterable[int]) -> float:
    """The output head's share of a step: 6 x ``hidden_size`` x
    ``vocab_size`` per target-bearing token."""
    return (6.0 * int(cfg["hidden_size"]) * int(cfg["vocab_size"])
            * sum(max(n - 1, 0) for n in doc_lengths))


def head_bytes(cfg: Dict) -> float:
    """The least HBM traffic of the output head in a step: its
    ``vocab_size`` x ``hidden_size`` matrix read in the forward and the
    backward and its gradient written once, at 2 bytes an element."""
    return 3 * 2.0 * int(cfg["hidden_size"]) * int(cfg["vocab_size"])
