"""The one traffic generator: a mix file of parameters -> a replay set.

A mix (``bench/traffic/<name>.json``) describes documents by a lognormal
length law (``log_mu``, ``log_sigma``, clipped to ``[min_len, max_len]``,
with a share ``long_frac`` pinned at ``max_len``) and a per-step token
``budget``: each batch draws documents until the budget is full and cuts
the last one to fit. Token ids follow a Zipf law of exponent ``zipf_a``
over the vocabulary. The length law and the token law are copied from the
program's synthetic corpora (``src/repro/data/synth.py``), not imported.

The sizes of the ``batches`` batches come from the mix's own
``sizes_seed``, so every run seed trains the same set of documents and
compiles the same buckets; ``--seed`` draws the order of the batches and
every token id.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent
KEYS = ("log_mu", "log_sigma", "min_len", "max_len", "long_frac", "budget",
        "batches", "sizes_seed", "zipf_a")


def load_mix(name: str, directory: Path = HERE) -> Dict:
    mix = json.loads((directory / f"{name}.json").read_text())
    missing = [k for k in KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {name!r} lacks {missing}")
    return mix


def batch_sizes(mix: Dict) -> List[List[int]]:
    """Document lengths of each batch of the replay set (seed-free)."""
    rng = np.random.default_rng(int(mix["sizes_seed"]))
    lo, hi, budget = int(mix["min_len"]), int(mix["max_len"]), \
        int(mix["budget"])
    out = []
    for _ in range(int(mix["batches"])):
        sizes, total = [], 0
        while total < budget:
            n = int(rng.lognormal(mix["log_mu"], mix["log_sigma"]))
            n = min(max(n, lo), hi)
            if rng.random() < mix["long_frac"]:
                n = hi
            n = min(n, budget - total)
            sizes.append(n)
            total += n
        out.append(sizes)
    return out


def _rng(seed: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng(seed)


def replay_set(mix: Dict, seed: int, vocab: int):
    """``(order, batches)``: the batches in the mix's canonical order, each
    a list of int32 token arrays (one per document), and the order in
    which this seed runs them."""
    rng = _rng(seed)
    sizes = batch_sizes(mix)
    order = [int(i) for i in rng.permutation(len(sizes))]
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks ** float(mix["zipf_a"]))
    cdf /= cdf[-1]
    batches = []
    for lens in sizes:
        docs = []
        for n in lens:
            ids = np.searchsorted(cdf, rng.random(n), side="right")
            docs.append(np.minimum(ids, vocab - 1).astype(np.int32))
        batches.append(docs)
    return order, batches


def real_tokens(docs) -> int:
    """Target-bearing tokens of a batch: every token but each document's
    last."""
    return int(sum(len(d) - 1 for d in docs))
