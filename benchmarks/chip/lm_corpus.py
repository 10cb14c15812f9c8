"""The benchmark's own federated token corpus, from a seed.

The law is the program's (``data/lm_data.py``): silo ``l`` of ``L``
draws every token from a Zipf law (exponent ``zipf_a``) over its own
window of ``vocab / 2`` ids of the vocabulary slice, shifted by
``l * vocab / (2 L)``: id ``lo + r - 1`` for the law's rank r.  Each
document is ``seq_len + 1`` tokens, the inputs and the next-token labels.
The program receives only the arrays made here.
"""
from __future__ import annotations

import numpy as np


def generate(*, vocab: int, nodes: int, docs_per_node: int, seq_len: int,
             seed: int, zipf_a: float) -> np.ndarray:
    """(nodes, docs_per_node, seq_len + 1) int32 token ids."""
    out = np.empty((nodes, docs_per_node, seq_len + 1), np.int32)
    width = max(vocab // 2, 2)
    p = np.arange(1, width + 1, dtype=np.float64) ** -zipf_a
    cdf = np.cumsum(p / p.sum())
    for node, child in enumerate(np.random.SeedSequence(int(seed))
                                 .spawn(nodes)):
        lo = node * vocab // (2 * nodes)
        u = np.random.default_rng(child).random(out.shape[1:])
        ranks = np.minimum(np.searchsorted(cdf, u, side="right"), width - 1)
        out[node] = np.minimum(lo + ranks, vocab - 1)
    return out
