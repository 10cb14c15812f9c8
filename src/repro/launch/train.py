"""Production training launcher.

Runs the gFedNTM-protocol training loop for any registered architecture:
synchronous federated data parallelism (Eq. 2 weighted aggregation via the
global token-weighted loss; Eq. 3 server update with --optimizer sgd),
over whatever mesh the current process backs (the production 16x16 /
2x16x16 meshes on a real pod; a small host mesh for local runs).

Examples:
  # end-to-end ~100M-param federated LM training on CPU (example driver)
  python -m repro.launch.train --arch phi3-mini-3.8b --reduced \
      --steps 200 --batch 16 --seq 256 --num-clients 4

  # the paper's NTM under the literal Algorithm-1 trainer
  python -m repro.launch.train --arch prodlda-synthetic --ntm --steps 100
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import ARCHS, get_config
from repro.configs.base import NTM, FederatedConfig
from repro.data.lm_data import SyntheticLMStream
from repro.data.synthetic_lda import generate_lda_corpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import transformer as tfm
from repro.optim.optimizers import get_optimizer


def train_lm(args) -> float:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    opt = get_optimizer(args.optimizer, args.lr)
    params = tfm.init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt, dtype=jnp.float32
                                      if args.reduced else None))
    stream = SyntheticLMStream(cfg, args.batch, args.seq,
                               num_clients=args.num_clients, seed=args.seed)
    t0 = time.time()
    loss = float("nan")
    for step, batch in zip(range(args.steps), stream):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, loss = step_fn(params, opt_state, batch, step)
        if step % args.log_every == 0:
            print(f"[step {step:5d}] loss={float(loss):.4f} "
                  f"({time.time() - t0:.1f}s)")
    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, args.steps, params)
        print(f"saved checkpoint to {args.checkpoint_dir}")
    print(f"final loss: {float(loss):.4f}")
    return float(loss)


def train_ntm(args) -> float:
    """The paper's own experiment: federated ProdLDA/CTM via Algorithm 1."""
    from repro.core.ntm import prodlda
    from repro.core.protocol import ClientState, FederatedTrainer
    from repro.core.vocab import Vocabulary, merge_vocabularies

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    syn = generate_lda_corpus(
        vocab_size=cfg.vocab_size, num_topics=cfg.num_topics,
        num_nodes=args.num_clients, shared_topics=max(cfg.num_topics // 5, 1),
        docs_per_node=args.docs_per_node, val_docs_per_node=50,
        seed=args.seed)

    # stage 1: vocabulary consensus (here vocabularies already share ids —
    # the merge is still executed to mirror Algorithm 1's information flow)
    terms = [f"term{i}" for i in range(cfg.vocab_size)]
    vocabs = [Vocabulary.from_bow(b, terms) for b in syn.node_bows]
    v_global = merge_vocabularies(vocabs)
    print(f"vocabulary consensus: |V| = {len(v_global)} "
          f"from {len(vocabs)} clients")

    loss_fn = lambda p, b: prodlda.elbo_loss(p, cfg, b)  # noqa: E731
    init = prodlda.init_params(jax.random.PRNGKey(args.seed), cfg)
    fed = FederatedConfig(num_clients=args.num_clients,
                          learning_rate=args.lr, max_rounds=args.steps,
                          local_steps=args.local_steps,
                          secure_aggregation=args.secure_agg,
                          compression_topk=args.topk)
    clients = [ClientState(data={"bow": b}, num_docs=len(b))
               for b in syn.node_bows]
    trainer = FederatedTrainer(loss_fn, init, clients, fed,
                               optimizer=get_optimizer(args.optimizer,
                                                       args.lr),
                               batch_size=args.batch)
    trainer.fit(seed=args.seed, verbose=True)
    print(f"final loss: {trainer.history[-1]['loss']:.4f} after "
          f"{len(trainer.history)} rounds")
    return trainer.history[-1]["loss"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="phi3-mini-3.8b",
                    choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ntm", action="store_true",
                    help="Algorithm-1 NTM trainer (paper experiment)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--num-clients", type=int, default=4)
    ap.add_argument("--docs-per-node", type=int, default=500)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--secure-agg", action="store_true")
    ap.add_argument("--topk", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default="")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.ntm or cfg.kind == NTM:
        return train_ntm(args)
    return train_lm(args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
