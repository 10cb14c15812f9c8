"""gFedNTM federated training protocol (paper Algorithm 1).

Three faithful realizations of the same math (DESIGN.md §2):

1. ``FederatedTrainer`` — the literal Algorithm 1: a server object and L
   client objects in one process (the gRPC transport of the reference
   implementation replaced by function calls; the *information flow* is
   identical — the server sees vocabularies and gradients, never
   documents).  Used for the paper's NTM experiments, runs on CPU.
   Since PR 3 it is a thin preset over the unified
   :class:`~repro.core.engine.FederationEngine` (``message="grad"``,
   E = 1, K = L, server = the wrapped client optimizer) — one code path
   maintains the equivalence guarantee for every execution stack.

2. ``make_federated_train_step`` — the TPU-native in-graph protocol:
   ``shard_map`` over the mesh client axis; each device computes its
   client's gradient, Eq. (2) runs as a weighted ``psum`` (the ICI
   all-reduce is the server), Eq. (3) updates identical replicas.
   Supports the beyond-paper secure-aggregation masks / top-k compression
   / local DP on the client side of the reduction.

3. ``weighted_global_loss`` — the GSPMD formulation used by the
   production launcher for the large architectures: the global loss
   ``sum_l sum-loss_l / sum_l n_l`` differentiates into *exactly* the
   Eq. (2) weighted gradient average (linearity of grad), so a plain
   ``jit`` with batch sharded over the client axis compiles to the same
   protocol with XLA-scheduled collectives.  Equivalence of all three
   paths is asserted in tests/test_protocol.py.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FederatedConfig, RoundConfig
from repro.core import aggregation as agg
# the client-side primitives and the engine live in core/engine.py since
# the PR-3 unification; re-exported here so every historical import path
# (`from repro.core.protocol import ClientState, ...`) keeps working
from repro.core.engine import (  # noqa: F401
    EXEC_MODES, ClientState, FederationEngine, _check_vmap_preconditions,
    _rel_change, masked_mean_loss, param_delta)
from repro.optim.optimizers import Optimizer, sgd

Pytree = Any


# ---------------------------------------------------------------------------
# (3) GSPMD path — weighted global loss
# ---------------------------------------------------------------------------
def weighted_global_loss(loss_sum_fn: Callable[..., Tuple[jnp.ndarray,
                                                          jnp.ndarray]]):
    """Wrap a (sum_loss, count) fn into the Eq.-(2)-equivalent global mean."""
    def loss(params, batch, **kw):
        s, n = loss_sum_fn(params, batch, **kw)
        return s / jnp.maximum(n, 1.0)
    return loss


# ---------------------------------------------------------------------------
# (2) in-graph shard_map protocol step
# ---------------------------------------------------------------------------
def make_federated_train_step(
    loss_sum_fn: Callable[..., Tuple[jnp.ndarray, jnp.ndarray]],
    optimizer: Optimizer,
    mesh,
    *,
    client_axes: Tuple[str, ...] = ("data",),
    fed: Optional[FederatedConfig] = None,
):
    """Build the explicit federated step for replicated-parameter models.

    Batch arrays must have their leading (batch) dim shardable over
    ``client_axes``; params/opt_state are replicated.  Each mesh slice
    along the client axes is one federated client N_l.
    """
    from jax.sharding import PartitionSpec as P

    fed = fed or FederatedConfig()
    axis = client_axes if len(client_axes) > 1 else client_axes[0]

    def step(params, opt_state, batch, step_idx, rng):
        def body(params, opt_state, batch, step_idx, rng):
            # ---- client side -------------------------------------------
            # fold the client id into the rng so clients draw independent
            # dropout/reparametrization noise (deterministic per client)
            cid = jax.lax.axis_index(client_axes[0])
            if len(client_axes) > 1:
                for ax in client_axes[1:]:
                    cid = cid * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
            num_clients = 1
            for ax in client_axes:
                num_clients *= jax.lax.axis_size(ax)
            local_rng = jax.random.fold_in(rng, cid)
            lbatch = dict(batch)
            if "rng" in lbatch:
                lbatch["rng"] = local_rng

            def local_mean_loss(p):
                s, n = loss_sum_fn(p, lbatch)
                return s / jnp.maximum(n, 1.0), n

            (loss, n_l), grads = jax.value_and_grad(
                local_mean_loss, has_aux=True)(params)

            if fed.dp_noise_multiplier > 0:
                grads = agg.dp_privatize(
                    grads, jax.random.fold_in(local_rng, 7),
                    clip_norm=fed.dp_clip_norm,
                    noise_multiplier=fed.dp_noise_multiplier)
            if fed.secure_aggregation:
                round_key = jax.random.fold_in(rng, step_idx)
                grads = agg.secure_mask_grads(
                    grads, round_key, cid, num_clients, n_l)

            # ---- server side: Eq. (2) then Eq. (3) ----------------------
            gbar = agg.aggregate_psum(grads, n_l, axis)
            new_params, new_opt = optimizer.update(
                params, gbar, opt_state, step_idx)
            mean_loss = jax.lax.psum(loss * n_l, axis) \
                / jax.lax.psum(n_l, axis)
            return new_params, new_opt, mean_loss

        batch_specs = jax.tree_util.tree_map(lambda _: P(axis), batch)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), batch_specs, P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(params, opt_state, batch, step_idx, rng)

    return step


# ---------------------------------------------------------------------------
# (1) Algorithm 1, literal: the grad-message preset of FederationEngine
# ---------------------------------------------------------------------------
def _wrap_client_optimizer(optimizer: Optimizer) -> agg.ServerOptimizer:
    """Adapt a client-side Eq. (3) ``Optimizer`` to the engine's server
    stage: the combined message IS the Eq. (2) gradient average, and the
    server applies ``optimizer.update`` to it verbatim."""
    return agg.ServerOptimizer(
        "client-optimizer", optimizer.init,
        lambda params, gbar, state, round_idx=0:
            optimizer.update(params, gbar, state, round_idx))


class FederatedTrainer(FederationEngine):
    """The gFedNTM server loop (Alg. 1) over explicit client objects.

    DEPRECATED-as-a-class, preserved-as-an-entry-point: this is the
    ``message="grad"`` preset of :class:`FederationEngine` (full
    participation, one minibatch gradient per client per round, Eq. (2)
    combine, client ``Optimizer`` applied as the server stage) and
    produces the identical parameter trajectory the pre-unification
    class did (tests/test_engine_unified.py).

    ``loss_fn(params, batch) -> scalar mean loss`` is the client's local
    objective (grad of it == G_l of Eq. 2 for that minibatch).

    ``exec_mode="loop"`` (default) polls clients one by one — the literal
    Alg. 1 composition.  ``exec_mode="vmap"`` stacks all L client
    minibatches on a leading axis and runs every client gradient, the
    grad-level privacy/compression transforms (derived automatically
    from the ``FederatedConfig`` knobs, applied as vectorized in-graph
    ops since PR 4 — loop/vmap parity tested), the Eq. (2) combine and
    the Eq. (3) update in ONE jitted graph — same trajectory (same keys,
    same math; tested), one dispatch per round (DESIGN.md §4).  Ragged
    clients additionally need the mask-aware ``loss_sum_fn`` (see
    ``engine.masked_mean_loss``).
    """

    def __init__(self, loss_fn, init_params: Pytree,
                 clients: Sequence[ClientState],
                 fed: FederatedConfig,
                 optimizer: Optional[Optimizer] = None,
                 batch_size: int = 64,
                 num_clients_for_masks: Optional[int] = None,
                 exec_mode: str = "loop",
                 loss_sum_fn=None):
        optimizer = optimizer or sgd(fed.learning_rate)
        # grad transforms exactly as the pre-unification trainer wired
        # them: dp -> top-k error feedback -> secure masks
        names = []
        if fed.message_precision:
            names.append("precision")
        if fed.dp_noise_multiplier > 0:
            names.append("dp")
        if fed.compression_topk > 0:
            names.append("topk")
        if fed.secure_aggregation:
            names.append("secure")
        super().__init__(
            loss_fn, init_params, clients, fed, RoundConfig(),
            batch_size=batch_size, exec_mode=exec_mode,
            loss_sum_fn=loss_sum_fn, message="grad",
            server=_wrap_client_optimizer(optimizer),
            transforms=tuple(names),
            num_clients_for_masks=num_clients_for_masks)
        self.optimizer = optimizer

    # the historical name for the server stage's state
    @property
    def opt_state(self):
        return self.server_state

    @opt_state.setter
    def opt_state(self, value):
        self.server_state = value

    # kept because the protocol equivalence tests drive it directly
    def _client_grad(self, l: int, c: ClientState, round_key):
        """GETCLIENTGRAD(N_l, W): local minibatch grad + count (Alg. 1)."""
        msg, n, loss = self._local_message(l, round_key)
        return loss, msg, n


# ---------------------------------------------------------------------------
# FedAvg-style local steps (beyond paper — collective-volume optimization)
# ---------------------------------------------------------------------------
class FedAvgTrainer(FederationEngine):
    """K local SGD steps between synchronizations [McMahan et al. 2017].

    Beyond-paper: the paper's Sync-Opt syncs every minibatch; FedAvg
    divides the synchronization (collective) volume by
    ``fed.local_steps`` at the cost of update staleness.  Now the
    ``message="delta"`` preset of :class:`FederationEngine` with
    ``local_epochs = fed.local_steps`` and a FedAvg(server_lr=1) server
    — the weighted average of client weights IS ``W +`` the weighted
    average of client deltas.  Loop-only, as before;
    ``RoundEngine(exec_mode='vmap')`` is the batched path for
    multi-local-step clients.
    """

    def __init__(self, loss_fn, init_params: Pytree,
                 clients: Sequence[ClientState],
                 fed: FederatedConfig,
                 optimizer: Optional[Optimizer] = None,
                 batch_size: int = 64,
                 num_clients_for_masks: Optional[int] = None,
                 exec_mode: str = "loop",
                 loss_sum_fn=None):
        if exec_mode != "loop":
            raise NotImplementedError(
                "FedAvgTrainer averages full client weights and is "
                "loop-only; RoundEngine(exec_mode='vmap') is the batched "
                "path for multi-local-step clients")
        super().__init__(
            loss_fn, init_params, clients, fed,
            RoundConfig(local_epochs=fed.local_steps),
            batch_size=batch_size, exec_mode="loop",
            loss_sum_fn=loss_sum_fn, message="delta",
            num_clients_for_masks=num_clients_for_masks)
        # kept for signature compatibility; the FedAvg update rule ignores
        # the client optimizer (plain local SGD + weight averaging)
        self.optimizer = optimizer


# ---------------------------------------------------------------------------
# baselines: the paper's scenarios 1 and 2
# ---------------------------------------------------------------------------
def train_centralized(loss_fn, init_params: Pytree,
                      data: Dict[str, np.ndarray], *,
                      optimizer: Optimizer, batch_size: int,
                      steps: int, seed: int = 0,
                      verbose: bool = False) -> Pytree:
    """Scenario 2: trusted server trains on the concatenated corpus C."""
    params = init_params
    opt_state = optimizer.init(params)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    n_docs = len(next(iter(data.values())))
    key = jax.random.PRNGKey(seed)
    for e in range(steps):
        key, k1, k2 = jax.random.split(key, 3)
        idx = np.asarray(jax.random.choice(
            k1, n_docs, (min(batch_size, n_docs),), replace=False))
        batch = {k: jnp.asarray(v[idx]) for k, v in data.items()}
        batch["rng"] = k2
        loss, grads = grad_fn(params, batch)
        params, opt_state = optimizer.update(params, grads, opt_state, e)
        if verbose and e % 50 == 0:
            print(f"[centralized {e:4d}] loss={float(loss):.4f}")
    return params


def train_non_collaborative(loss_fn, init_fn, node_data, *,
                            optimizer_factory, batch_size: int,
                            steps: int, seed: int = 0) -> List[Pytree]:
    """Scenario 1: every node trains its own model on its own corpus."""
    out = []
    for l, data in enumerate(node_data):
        params = init_fn(jax.random.PRNGKey(seed + 17 * l))
        out.append(train_centralized(
            loss_fn, params, data, optimizer=optimizer_factory(),
            batch_size=batch_size, steps=steps, seed=seed + 31 * l))
    return out
