"""Partition a corpus across L federated clients + per-round batch iterators.

The partitioner REGISTRY at the top is the scenario-diversity layer
(DESIGN.md §3): every named partitioner maps ``(n_docs, num_clients,
labels, seed, **kwargs)`` to disjoint per-client index arrays covering
``[0, n_docs)``:

  * ``iid`` — uniform random equal-size split (the homogeneous baseline);
  * ``by_label`` (alias ``topic``) — each client holds documents of
    distinct categories (the paper's §4.2 fields-of-study setup);
  * ``dirichlet`` — per-label Dirichlet(alpha) allocation across clients
    [Hsu et al. 2019]: alpha → 0 gives one-label clients, alpha → ∞
    recovers ``iid`` (tested in tests/test_scenarios.py);
  * ``quantity_skew`` — content-iid but per-client corpus SIZES drawn
    from Dirichlet(alpha): the size-imbalance regime of the federated
    short-text literature (arXiv:2205.13300).

Specs are strings — ``"dirichlet(0.3)"``, ``"quantity_skew(0.5)"`` —
parsed by :func:`parse_partition_spec` so configs/CLIs can carry them
verbatim (``RoundConfig.partition``, ``simulate.py --partition``).

The minibatch samplers at the bottom are the single source of truth for
how a client draws data inside one federated round: ``sample_minibatch``
is the Alg.-1 draw used by ``FederatedTrainer``, and ``round_minibatches``
extends it to E local epochs for the unified engine (``core/engine.py``)
with the FedAvg key schedule — epoch 0 reuses the round key, so
``local_epochs=1`` draws the exact same minibatch Sync-Opt would.
"""
from __future__ import annotations

import re
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans


# ---------------------------------------------------------------------------
# partitioner registry
# ---------------------------------------------------------------------------
def _partition_iid(n_docs: int, num_clients: int, *, labels=None,
                   seed: int = 0) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    idx = rng.permutation(n_docs)
    return [np.sort(part) for part in np.array_split(idx, num_clients)]


def _partition_by_label(n_docs: int, num_clients: int, *, labels=None,
                        seed: int = 0) -> List[np.ndarray]:
    if labels is None:
        raise ValueError("by_label split needs labels")
    labels = np.asarray(labels)
    uniq = np.unique(labels)
    groups = [np.where(np.isin(labels, u))[0]
              for u in np.array_split(uniq, num_clients)]
    return [np.sort(g) for g in groups]


def _partition_dirichlet(n_docs: int, num_clients: int, *, labels=None,
                         seed: int = 0,
                         alpha: float = 0.5) -> List[np.ndarray]:
    if labels is None:
        raise ValueError("dirichlet split needs labels")
    if alpha <= 0:
        raise ValueError(f"dirichlet alpha must be > 0, got {alpha}")
    labels = np.asarray(labels)
    rng = np.random.default_rng(seed)
    rng.permutation(n_docs)     # keep the historical stream position
    out = [[] for _ in range(num_clients)]
    for u in np.unique(labels):
        members = rng.permutation(np.where(labels == u)[0])
        props = rng.dirichlet(np.full(num_clients, alpha))
        cuts = (np.cumsum(props)[:-1] * len(members)).astype(int)
        for c, part in enumerate(np.split(members, cuts)):
            out[c].extend(part.tolist())
    return [np.sort(np.array(o, dtype=np.int64)) for o in out]


def _partition_quantity_skew(n_docs: int, num_clients: int, *, labels=None,
                             seed: int = 0,
                             alpha: float = 0.5) -> List[np.ndarray]:
    """Content-iid split with Dirichlet(alpha)-skewed client sizes.

    Every client is guaranteed at least one document (a zero-size client
    has no round message and would break the Eq. (2) weighting), so the
    skew operates on the remaining ``n_docs - num_clients`` documents.
    """
    if alpha <= 0:
        raise ValueError(f"quantity_skew alpha must be > 0, got {alpha}")
    if n_docs < num_clients:
        raise ValueError(f"cannot give {num_clients} clients >=1 of "
                         f"{n_docs} docs")
    rng = np.random.default_rng(seed)
    props = rng.dirichlet(np.full(num_clients, alpha))
    spare = n_docs - num_clients
    sizes = 1 + np.floor(props * spare).astype(np.int64)
    # distribute the flooring remainder to the largest shares
    for c in np.argsort(-props)[: n_docs - int(sizes.sum())]:
        sizes[c] += 1
    idx = rng.permutation(n_docs)
    cuts = np.cumsum(sizes)[:-1]
    return [np.sort(part) for part in np.split(idx, cuts)]


PARTITIONERS: Dict[str, Callable[..., List[np.ndarray]]] = {
    "iid": _partition_iid,
    "by_label": _partition_by_label,
    "topic": _partition_by_label,        # the paper's name for the regime
    "dirichlet": _partition_dirichlet,
    "quantity_skew": _partition_quantity_skew,
}

_SPEC_RE = re.compile(r"^\s*([a-z_]+)\s*(?:\(\s*(.*?)\s*\))?\s*$")

# partitioners that accept an '(alpha)' argument; every other name must
# appear bare — 'iid(0.3)' is a user error, not a silently-ignored knob
_PARAMETRIC = frozenset({"dirichlet", "quantity_skew"})


def parse_partition_spec(spec: str) -> Tuple[str, Dict[str, float]]:
    """``"dirichlet(0.3)"`` -> ``("dirichlet", {"alpha": 0.3})``.

    A bare parametric name parses to no kwargs (partitioner defaults
    apply).  Everything malformed raises ``ValueError`` with an
    actionable message instead of silently dropping intent: unknown
    names, arguments on non-parametric partitioners (``iid(0.3)``),
    empty parentheses (``dirichlet()``), non-numeric or non-positive
    alphas.
    """
    m = _SPEC_RE.match(spec or "")
    if not m or m.group(1) not in PARTITIONERS:
        raise ValueError(f"unknown partition spec {spec!r}; known: "
                         f"{sorted(set(PARTITIONERS))} "
                         "(optionally with '(alpha)')")
    name, arg = m.group(1), m.group(2)
    if arg is None:
        return name, {}
    if name not in _PARAMETRIC:
        raise ValueError(f"partition spec {spec!r}: {name!r} takes no "
                         "argument — drop the parentheses")
    if arg == "":
        raise ValueError(f"partition spec {spec!r} has empty parentheses "
                         f"— give an explicit alpha, e.g. '{name}(0.3)', "
                         "or drop the parentheses for the default")
    try:
        alpha = float(arg)
    except ValueError:
        raise ValueError(f"partition spec {spec!r}: malformed alpha "
                         f"{arg!r} (expected a number, e.g. "
                         f"'{name}(0.3)')") from None
    if not alpha > 0:
        raise ValueError(f"partition spec {spec!r}: alpha must be > 0, "
                         f"got {alpha!r}")
    return name, {"alpha": alpha}


def partition_corpus(n_docs: int, num_clients: int, spec: str = "iid", *,
                     labels: Optional[Sequence[int]] = None,
                     seed: int = 0) -> List[np.ndarray]:
    """Registry front-door: spec string -> per-client doc index arrays."""
    name, kw = parse_partition_spec(spec)
    return PARTITIONERS[name](n_docs, num_clients, labels=labels, seed=seed,
                              **kw)


def split_corpus_across_clients(
    n_docs: int,
    num_clients: int,
    *,
    mode: str = "iid",
    labels: Optional[Sequence[int]] = None,
    dirichlet_alpha: float = 0.5,
    seed: int = 0,
) -> List[np.ndarray]:
    """Pre-registry entry point, kept for API compatibility.

    Delegates to the :data:`PARTITIONERS` registry; ``mode`` accepts any
    registered name (``dirichlet_alpha`` feeds the alpha-parameterized
    partitioners).
    """
    if mode not in PARTITIONERS:
        raise ValueError(f"unknown split mode {mode!r}")
    kw = {"alpha": dirichlet_alpha} if mode in ("dirichlet",
                                                "quantity_skew") else {}
    return PARTITIONERS[mode](n_docs, num_clients, labels=labels, seed=seed,
                              **kw)


# ---------------------------------------------------------------------------
# per-round client minibatch iterators
# ---------------------------------------------------------------------------
def _draw_indices(rng, num_docs: int,
                  batch_size: int) -> Tuple[np.ndarray, Any, int]:
    """The single source of truth for one client draw: the index set, the
    in-batch model rng, and the draw size.  Shared by the per-client
    iterators and the stacked (vmap-path) builder so both execution modes
    see byte-identical document selections and noise keys."""
    n = min(batch_size, num_docs)
    idx = np.asarray(jax.random.choice(rng, num_docs, (n,), replace=False))
    return idx, jax.random.fold_in(rng, 1), n


def _epoch_key(round_rng, s: int):
    """Epoch-s draw key.  Epoch 0 reuses ``round_rng`` itself (the
    minibatch Sync-Opt would draw); s>0 folds in s+1 — NOT s, because
    fold_in(round_rng, 1) is already spent as epoch 0's in-batch model
    rng and reusing it as a draw key would correlate epoch-1 document
    selection with epoch-0 dropout/reparametrization noise."""
    return round_rng if s == 0 else jax.random.fold_in(round_rng, s + 1)


def sample_minibatch(data: Dict[str, np.ndarray], num_docs: int, rng,
                     batch_size: int) -> Tuple[Dict[str, Any], int]:
    """One Alg.-1 client draw: ``batch_size`` docs without replacement.

    Returns ``(batch, n)`` with ``batch["rng"]`` set to the fold of the
    draw key — the key schedule FederatedTrainer has always used, kept
    byte-identical here so the round engine reproduces its trajectory.
    """
    idx, model_rng, n = _draw_indices(rng, num_docs, batch_size)
    batch = {k: jnp.asarray(v[idx]) for k, v in data.items()}
    batch["rng"] = model_rng
    return batch, n


def round_minibatches(data: Dict[str, np.ndarray], num_docs: int, round_rng,
                      *, batch_size: int,
                      local_epochs: int = 1) -> Iterator[Tuple[Dict[str, Any],
                                                               int]]:
    """Yield the E local-epoch minibatches of one client in one round.

    The epoch-s key schedule lives in :func:`_epoch_key`; ``local_epochs=1``
    reduces the round engine to the synchronous protocol exactly.
    """
    for s in range(local_epochs):
        yield sample_minibatch(data, num_docs, _epoch_key(round_rng, s),
                               batch_size)


# ---------------------------------------------------------------------------
# stacked cohort batches (the vmap execution path, DESIGN.md §4)
# ---------------------------------------------------------------------------
_DRAW_FN_CACHE: Dict[Tuple[int, int, int], Any] = {}


def _stacked_draw_fn(num_docs: int, n: int, local_epochs: int):
    """One jitted call drawing ALL (client, epoch) index sets of a
    same-shape client group: ``(round_key, client_ids (G,)) ->
    (idx (G, E, n), model_rngs (G, E, 2))``.

    The key schedule inside the trace is the SAME composition of
    ``fold_in``s the loop path runs eagerly (:func:`_epoch_key`,
    :func:`_draw_indices`), and threefry is a pure function of
    (key, data) — so the vmapped draws are bit-identical to K*E separate
    ``sample_minibatch`` calls while paying one dispatch instead of
    O(K*E) (the dominant host cost of small-model federated rounds).
    """
    key = (num_docs, n, local_epochs)
    if key in _DRAW_FN_CACHE:
        return _DRAW_FN_CACHE[key]

    def draw(round_key, client_ids):
        def per_client(cid):
            crng = jax.random.fold_in(round_key, cid)
            keys = jnp.stack([_epoch_key(crng, s)
                              for s in range(local_epochs)])

            def per_epoch(k):
                idx = jax.random.choice(k, num_docs, (n,), replace=False)
                return idx, jax.random.fold_in(k, 1)

            return jax.vmap(per_epoch)(keys)
        return jax.vmap(per_client)(client_ids)

    fn = jax.jit(draw)
    _DRAW_FN_CACHE[key] = fn
    return fn


def stacked_round_batches(
    datas: Sequence[Dict[str, np.ndarray]],
    num_docs: Sequence[int],
    round_key,
    client_ids: Sequence[int],
    *,
    batch_size: int,
    local_epochs: int = 1,
    pad_to: Optional[int] = None,
    shard_multiple: Optional[int] = None,
) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Assemble one round's cohort minibatches into a leading client axis.

    For each cohort member ``i`` (global client id ``client_ids[i]``,
    round key ``fold_in(round_key, id)``) and each local epoch ``s``,
    draws exactly the minibatch :func:`round_minibatches` would (same
    keys via :func:`_epoch_key` / :func:`_draw_indices`, batched into one
    jitted dispatch per same-shape client group), then stacks everything
    into fixed-shape arrays so all K clients' local updates can run in
    ONE jitted/vmapped graph:

      * every data key ``k`` -> ``(K, E, P, ...)`` with ``P = batch_size``,
        rows beyond a client's draw size zero-padded;
      * ``"doc_mask"``       -> ``(K, E, P)`` float32, 1 for real rows —
        mask-aware losses (e.g. ``prodlda.elbo_loss_sum``) use it to keep
        padded rows out of the objective AND its gradient;
      * ``"rng"``            -> ``(K, E, 2)`` uint32 — the same in-batch
        model keys the loop path puts in ``batch["rng"]``.

    Returns ``(stacked, counts)`` where ``counts`` is ``(K, E)`` float32
    draw sizes (the Eq. (2) weights are ``counts.sum(axis=1)``).

    ``pad_to`` (>= the cohort size) widens the stacked axis to a FIXED
    K: rows beyond the cohort stay all-zero (data, doc_mask, rng and
    counts), i.e. zero-weight padding — the retrace-free fixed-K
    contract of DESIGN.md §4.  The real rows are byte-identical to the
    unpadded call, so padding never perturbs a draw.

    The gathering itself is host-side numpy; the single resulting
    transfer replaces the per-client-per-epoch device round-trips of the
    loop path.

    ``shard_multiple`` (the engine's ``execution.mesh`` data-axis size)
    asserts the stacked width divides the device mesh: an indivisible
    cohort is REFUSED here, at the data layer, before any array reaches
    a sharded graph — cohorts are never silently repartitioned.
    """
    k_clients = len(datas)
    k_stack = k_clients if pad_to is None else int(pad_to)
    if k_stack < k_clients:
        raise ValueError(f"pad_to={pad_to} is smaller than the cohort "
                         f"({k_clients} clients); the stacked axis cannot "
                         "drop cohort members")
    if shard_multiple and k_stack % shard_multiple:
        raise ValueError(
            f"stacked cohort width {k_stack} is not divisible by the "
            f"device-mesh data axis ({shard_multiple}) — cohorts are "
            "never silently repartitioned; enable execution.pad_cohorts "
            "(fixed-K padding) or resize the cohort/mesh")
    e = local_epochs
    p = batch_size
    # group cohort members by draw shape so each group is one jitted call
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, nd in enumerate(num_docs):
        groups.setdefault((int(nd), min(batch_size, int(nd))), []).append(i)

    draws = []
    with spans.span(spans.DRAW):
        for (nd, n), members in groups.items():
            fn = _stacked_draw_fn(nd, n, e)
            ids = jnp.asarray([int(client_ids[i]) for i in members],
                              jnp.uint32)
            idx_g, rng_g = fn(round_key, ids)
            draws.append((n, members, np.asarray(idx_g),     # (G, E, n)
                          np.asarray(rng_g, np.uint32)))     # (G, E, 2)

    # zero pages: the fill below touches them, inside its span
    stacked: Dict[str, np.ndarray] = {
        key: np.zeros((k_stack, e, p) + v.shape[1:],
                      np.asarray(v).dtype)
        for key, v in datas[0].items()
    }
    stacked["doc_mask"] = np.zeros((k_stack, e, p), np.float32)
    stacked["rng"] = np.zeros((k_stack, e, 2), np.uint32)
    counts = np.zeros((k_stack, e), np.float32)
    with spans.span(spans.GATHER,
                    bytes=sum(a.nbytes for a in stacked.values())):
        for n, members, idx_g, rng_g in draws:
            for g, i in enumerate(members):
                for key, v in datas[i].items():
                    # one (E, n)-index gather per (client, key)
                    stacked[key][i, :, :n] = np.asarray(v)[idx_g[g]]
                stacked["doc_mask"][i, :, :n] = 1.0
                stacked["rng"][i] = rng_g[g]
                counts[i, :] = n
    return stacked, counts
