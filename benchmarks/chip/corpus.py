"""The benchmark's own synthetic LDA federation (paper §4.1), from a seed.

The law is the LDA generative process the paper uses:

    beta_k  ~ Dirichlet(eta)                 per-topic word distribution
    theta_d ~ Dirichlet(alpha) over the node's visible topics
    n_d     ~ U[len_min, len_max]
    w_di    ~ Mult(theta_d . beta)           then a count per term

``shared`` topics are visible to every node and the rest are split
evenly as private topics, with the same arithmetic as the program's
``make_federated_topic_split`` (at L=1,000 nodes and 50 topics, 10 shared,
no node has a private topic).  Tokens are drawn as a mixture (topic, then
term), which is the same law as one draw from ``theta_d . beta``, and
counted per term; the documents are made in chunks on a few threads, each
chunk from its own child of the seed, so the corpus does not depend on
the thread count.  The program receives only the arrays made here.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

CHUNK = 1000          # documents per chunk (one seed child each)


@dataclass
class Corpus:
    """The federation in the shape the program takes: per-node views into
    one dense ``(docs, vocab)`` float32 count matrix."""
    beta: np.ndarray                 # (K, V) float32
    node_bows: List[np.ndarray]      # per node (docs_per_node, V)
    node_val_bows: List[np.ndarray]  # per node (val_docs_per_node, V)
    node_thetas: List[np.ndarray]
    node_val_thetas: List[np.ndarray]
    node_topics: List[np.ndarray]
    shared_topics: np.ndarray
    alpha: float
    eta: float

    def concat_bows(self) -> np.ndarray:
        return np.concatenate(self.node_bows, axis=0)

    def concat_val_bows(self) -> np.ndarray:
        return np.concatenate(self.node_val_bows, axis=0)


def topic_split(num_topics: int, shared: int, num_nodes: int,
                rng: np.random.Generator):
    """``shared`` topics seen by all nodes, the rest split evenly."""
    if shared > num_topics:
        raise ValueError(f"{shared} shared topics of {num_topics}")
    perm = rng.permutation(num_topics)
    shared_ids, rest = perm[:shared], perm[shared:]
    per_node = len(rest) // num_nodes
    nodes = [np.sort(np.concatenate(
        [shared_ids, rest[l * per_node:(l + 1) * per_node]]))
        for l in range(num_nodes)]
    return np.sort(shared_ids), nodes


def _docs(seed, n: int, topics: np.ndarray, cdf_flat: np.ndarray,
          vocab: int, alpha: float, len_range: Tuple[int, int],
          out: np.ndarray) -> np.ndarray:
    """Fill ``out`` (n, V) with n documents over ``topics``; return theta."""
    rng = np.random.default_rng(seed)
    kv = len(topics)
    theta = rng.dirichlet(np.full(kv, alpha), size=n)
    lengths = rng.integers(len_range[0], len_range[1] + 1, size=n)
    doc = np.repeat(np.arange(n), lengths)
    z = (rng.random(doc.size)[:, None] > np.cumsum(theta, 1)[doc]).sum(1)
    # the global topic id is the row of the stacked per-topic CDF table
    gz = np.asarray(topics, np.int64)[np.minimum(z, kv - 1)]
    w = np.searchsorted(cdf_flat, gz + rng.random(doc.size)) - gz * vocab
    w = np.clip(w, 0, vocab - 1)
    out[:] = np.bincount(doc * vocab + w, minlength=n * vocab).reshape(
        n, vocab)
    return theta


def generate(*, vocab: int, topics: int, nodes: int, shared: int,
             docs_per_node: int, val_docs_per_node: int, seed: int,
             eta: float = 0.01, alpha: float = None,
             len_range: Tuple[int, int] = (150, 250),
             threads: int = 0) -> Corpus:
    """The whole federation: ``nodes`` x (train + held-out) documents."""
    if alpha is None:
        alpha = 50.0 / topics                  # paper: alpha = 50/K
    root = np.random.SeedSequence(int(seed))
    head, body = root.spawn(2)
    rng = np.random.default_rng(head)
    beta = rng.dirichlet(np.full(vocab, eta), size=topics)
    shared_ids, node_topics = topic_split(topics, shared, nodes, rng)
    cdf = np.cumsum(beta, axis=1)
    cdf /= cdf[:, -1:]
    cdf_flat = (cdf + np.arange(topics)[:, None]).ravel()

    per_node = docs_per_node + val_docs_per_node
    total = nodes * per_node
    bows = np.empty((total, vocab), np.float32)
    thetas = np.zeros((total, topics), np.float32)
    # documents of nodes that see the same topics are drawn together
    groups = {}
    for l, t in enumerate(node_topics):
        groups.setdefault(tuple(t), []).append(l)
    jobs = []
    for tkey, members in sorted(groups.items()):
        rows = np.concatenate([np.arange(l * per_node, (l + 1) * per_node)
                               for l in members])
        jobs.append((np.asarray(tkey), rows))
    chunks = []
    for t, rows in jobs:
        for i in range(0, len(rows), CHUNK):
            chunks.append((t, rows[i:i + CHUNK]))
    seeds = body.spawn(len(chunks))

    def run(i):
        t, rows = chunks[i]
        contiguous = rows[-1] - rows[0] + 1 == len(rows)
        out = bows[rows[0]:rows[-1] + 1] if contiguous \
            else np.empty((len(rows), vocab), np.float32)
        th = _docs(seeds[i], len(rows), t, cdf_flat, vocab, alpha,
                   len_range, out)
        if not contiguous:
            bows[rows] = out
        full = np.zeros((len(rows), topics), np.float32)
        full[:, t] = th
        thetas[rows] = full

    workers = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as ex:
        list(ex.map(run, range(len(chunks))))

    def split(a, lo, hi):
        return [a[l * per_node + lo:l * per_node + hi] for l in range(nodes)]
    return Corpus(
        beta=beta.astype(np.float32),
        node_bows=split(bows, 0, docs_per_node),
        node_val_bows=split(bows, docs_per_node, per_node),
        node_thetas=split(thetas, 0, docs_per_node),
        node_val_thetas=split(thetas, docs_per_node, per_node),
        node_topics=node_topics, shared_topics=shared_ids,
        alpha=alpha, eta=eta)
