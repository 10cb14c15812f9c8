"""Set-up shared by the drivers: seeds, corpus, and the model check."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

import corpus as corpus_mod
from harness import BenchError


def derive_seeds(seed: int) -> Dict[str, int]:
    """Corpus, weights and program seeds from the run's ``--seed``."""
    a, b, c = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"corpus": int(a), "weights": int(b), "program": int(c) % 2 ** 31}


def make_corpus(cfg: Dict[str, Any], seed: int):
    c = cfg["corpus"]
    return corpus_mod.generate(
        vocab=cfg["vocab_size"], topics=cfg["num_topics"],
        nodes=cfg["num_clients"], shared=c["shared_topics"],
        docs_per_node=cfg["docs_per_client"],
        val_docs_per_node=cfg["val_docs_per_client"], seed=seed,
        eta=c["eta"], alpha=c["alpha"], len_range=tuple(c["doc_length"]))


def check_model(cfg: Dict[str, Any], model_cfg) -> None:
    """The program must run the model the configuration states."""
    got = {"vocab_size": model_cfg.vocab_size,
           "num_topics": model_cfg.num_topics,
           "hidden": list(model_cfg.ntm_hidden),
           "dropout": model_cfg.ntm_dropout,
           "learn_priors": model_cfg.learn_priors}
    want = {k: cfg[k] for k in got}
    if got != want:
        raise BenchError(f"the program runs {got}, the configuration "
                         f"states {want}")


def host(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: np.array(x, copy=True), tree)
