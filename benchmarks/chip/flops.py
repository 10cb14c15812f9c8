"""Operations that ProdLDA's training step requires, from its shapes.

Per document and local SGD step the matmuls are V*H1 + H1*H2 + 2*H2*K +
K*V multiply-adds forward.  Backward needs the weight gradient of every
matmul and the input gradient of every matmul but the first (the
bag-of-words is data, not a parameter).  Elementwise work (softplus,
softmax, the KL) is not counted: it is a few per cent at these widths.
"""
from __future__ import annotations

from typing import Sequence


def macs_per_doc(vocab: int, topics: int, hidden: Sequence[int]) -> int:
    dims = [vocab] + list(hidden)
    enc = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return enc + 2 * dims[-1] * topics + topics * vocab


def train_flops_per_doc(vocab: int, topics: int,
                        hidden: Sequence[int]) -> int:
    """Forward + backward FLOPs of one document in one local step."""
    m = macs_per_doc(vocab, topics, hidden)
    first = vocab * hidden[0]
    return 2 * m + 2 * m + 2 * (m - first)


def round_flops(cfg, clients: int, epochs: int, batch: int) -> int:
    """Training FLOPs of one round: every client's E local steps."""
    return clients * epochs * batch * train_flops_per_doc(
        cfg["vocab_size"], cfg["num_topics"], cfg["hidden"])
