"""FederationEngine — the ONE federated execution stack (DESIGN.md §3-§4).

Until PR 3 the repo maintained the paper's equivalence guarantee twice,
in two divergent engines (``FederatedTrainer`` in ``core/protocol.py``
and ``RoundEngine`` in ``core/rounds.py``).  This module collapses both
into a single composable pipeline of stages

    sampler -> local-update -> transforms -> combine -> server-opt

over which the legacy classes are thin config presets:

  * ``FederatedTrainer``  = ``message="grad"``, E = 1, K = L, server =
    the wrapped client optimizer (Eq. (3) verbatim);
  * ``FedAvgTrainer``     = ``message="delta"``, E = ``fed.local_steps``,
    FedAvg(server_lr=1) server (weight averaging == W + delta average);
  * ``RoundEngine``       = ``message="delta"`` with the full
    ``RoundConfig`` regime surface.

``exec_mode`` ("loop" | "vmap") is a property of THIS engine, not
duplicated per class:

  * ``"loop"`` steps the cohort client-by-client on the host — the
    literal Alg.-1 composition and the reference every fused path is
    tested against;
  * ``"vmap"`` stacks the cohort's minibatches on a leading client axis
    and runs all K local-update loops, the Eq. (2) combine and the
    server optimizer in ONE jitted graph.  Where the vmapped cohort
    would not fit the device (:func:`scan_clients`), the synchronous
    graph scans the K clients instead, summing the combine's numerator
    as it goes.  With stragglers enabled the
    combine runs through an IN-GRAPH fixed-capacity ring buffer of
    stacked deltas (age counters + weights as arrays) instead of the
    host-side pending list — the straggler regime is now exactly as
    fused as the synchronous one, with :func:`combine_arrivals` kept as
    the loop-mode reference the fused buffer is tested against
    (tests/test_vmap_equivalence.py, tests/test_engine_unified.py).

Message transforms (``core/transforms.py`` registry) plug into the
transform stage by name: ``"dp"`` (clip + Gaussian local DP), ``"topk"``
(top-k sparsification with error feedback), ``"secure"`` (pairwise
cancelling masks, bitwise-exact sum-to-zero).  They apply to whatever
the engine's message kind is — gradients for the Algorithm-1 preset,
deltas for round engines — and run on BOTH execution paths: the loop
mode applies them per client on the host, the vmap mode applies the
stacked implementations INSIDE the fused graph (same keys, same state
semantics; loop/vmap parity <1e-5 is a tested invariant).

Cohorts on the vmap path are padded to a FIXED K (the scheduler's
``clients_per_round``) with zero-weight rows, so mid-training
dropout/join churn and shrunken active sets reuse ONE compiled graph
instead of retracing per distinct cohort size (``trace_counts`` records
every trace; tests pin it to exactly one).  Zero-weight rows are
treated as absent everywhere: they are re-zeroed after the transform
stage, contribute nothing to the Eq. (2) combine (numerator or
denominator), never enter the straggler ring, and never update
transform state.

Scenario diversity (per-client heterogeneous local epochs, mid-training
client dropout/join) threads through ``RoundConfig`` — see
docs/scenarios.md for the knob -> regime map.  The declarative,
serializable front-door over this engine is ``repro.api``
(``FederationSpec`` + the ``Federation`` facade, docs/api.md);
``state_dict()`` / ``load_state_dict()`` snapshot the FULL engine state
(params, server-opt state, transform state, straggler ring/pending) for
bit-identical resume.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.base import FederatedConfig, RoundConfig
from repro.core import aggregation as agg
# the transform registry's canonical home is core/transforms.py (PR 4);
# the engine consumes it under private aliases so the public re-export
# surface below can be an explicitly deprecated shim
from repro.core.transforms import StackedTransformCtx as _StackedCtx
from repro.core.transforms import TransformCtx as _TransformCtx
from repro.core.transforms import build_transforms as _build_transforms
from repro.data.federated_split import (place_corpus, round_minibatches,
                                        row_nbytes, sample_minibatch,
                                        stacked_round_batches)
from repro.kernels import ops as kops
from repro.optim.optimizers import global_norm
from repro.parallel import sharding

Pytree = Any

EXEC_MODES = ("loop", "vmap")
KERNEL_BACKENDS = kops.KERNEL_BACKENDS
MESSAGE_KINDS = ("delta", "grad")

# DEPRECATED re-export shim: until PR 5 this module re-exported the
# transform registry names; the canonical import surface is
# repro.core.transforms.  Attribute access still works but warns —
# tests/test_api_spec.py pins the warning.
_DEPRECATED_TRANSFORM_REEXPORTS = (
    "TRANSFORMS", "MessageTransform", "StackedTransformCtx",
    "TransformCtx", "build_transforms", "pairwise_mask_stack")


def __getattr__(name):
    if name in _DEPRECATED_TRANSFORM_REEXPORTS:
        warnings.warn(
            f"importing {name!r} from repro.core.engine is deprecated; "
            "its canonical home is repro.core.transforms",
            DeprecationWarning, stacklevel=2)
        from repro.core import transforms as _transforms
        return getattr(_transforms, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# shared client-side primitives
# ---------------------------------------------------------------------------
@dataclass
class ClientState:
    """What lives on one node N_l: its corpus, never shared."""
    data: Dict[str, np.ndarray]
    num_docs: int
    error_memory: Optional[Pytree] = None   # top-k error feedback
    rng: Any = None


def param_delta(old: Pytree, new: Pytree) -> Pytree:
    """The client's round message in delta form: W_l - W (DESIGN.md §3)."""
    return jax.tree_util.tree_map(lambda a, b: b - a, old, new)


def sgd_step_fn(loss_fn, learning_rate: float):
    """One jitted local SGD step ``(params, batch) -> (loss, params')``.

    The gradient and the update ``p - lr * g`` compile as one function,
    as they do inside the stacked path's graph: run op by op, the update
    rounds twice (``lr * g``, then the subtraction) where the compiled
    graph fuses it into a single multiply-add, and those last-place
    differences are enough to tip a top-k selection between the paths.
    """
    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, batch):
        loss, grads = grad_fn(params, batch)
        return loss, jax.tree_util.tree_map(
            lambda p, g: p - learning_rate * g.astype(p.dtype),
            params, grads)
    return jax.jit(step)


def client_round_update(step_fn, params: Pytree, client: ClientState,
                        round_rng, *, local_epochs: int = 1,
                        batch_size: int = 64) -> Tuple[Pytree, float, float]:
    """Run E local SGD epochs on one client starting from the server
    weights; return ``(delta, n_total, mean_loss)``.

    With ``local_epochs=1`` the delta is exactly ``-lr * G_l`` for the
    minibatch the Algorithm-1 trainer would draw from ``round_rng`` — the
    identity that makes the engine reproduce Algorithm 1 (tested in
    tests/test_rounds.py).  ``step_fn`` is :func:`sgd_step_fn` of the
    client's local mean loss.
    """
    local = params
    tot_loss, tot_n = 0.0, 0.0
    for batch, n in round_minibatches(client.data, client.num_docs,
                                      round_rng, batch_size=batch_size,
                                      local_epochs=local_epochs):
        loss, local = step_fn(local, batch)
        tot_loss += float(loss) * n
        tot_n += n
    return param_delta(params, local), float(tot_n), \
        tot_loss / max(tot_n, 1.0)


def masked_mean_loss(loss_fn, loss_sum_fn=None):
    """Client objective for the stacked (vmap) execution path.

    The stacked batches of :func:`stacked_round_batches` carry a
    ``doc_mask`` marking padded rows.  A mask-aware ``loss_sum_fn(params,
    batch) -> (sum_loss, count)`` (e.g. ``prodlda.elbo_loss_sum``) keeps
    those rows out of the objective and its gradient; the masked mean
    ``sum/count`` then equals the plain mean the loop path takes over the
    unpadded batch (DESIGN.md §4).  Without a ``loss_sum_fn`` the plain
    mean ``loss_fn`` is used with the mask stripped — only valid when no
    client pads (every ``num_docs >= batch_size``); the engines enforce
    that precondition at construction.

    CAVEAT (stochastic losses + padding): in-batch noise (dropout /
    reparametrization) inside the loss is drawn over the PADDED row count
    P, and threefry's counter layout is shape-dependent, so those draws
    differ from the loop path's n-row draws even on the real rows.  A
    padded client under a ``train=True`` loss therefore trains correctly
    (same noise distribution, masked objective) but does NOT retrace the
    loop trajectory bit-for-bit; the vmap==loop guarantee for stochastic
    losses holds exactly when no client pads.  Deterministic losses
    (``train=False``, the equivalence-test setting) are unaffected.
    """
    if loss_sum_fn is not None:
        def mean_loss(params, batch):
            s, n, *counters = loss_sum_fn(params, batch)
            return s / jnp.maximum(n, 1.0), counters[0] if counters else {}
        return mean_loss

    def mean_loss(params, batch):
        return loss_fn(params, {k: v for k, v in batch.items()
                                if k != "doc_mask"}), {}
    return mean_loss


def _check_vmap_preconditions(fed: FederatedConfig, clients, batch_size: int,
                              loss_sum_fn, *, what: str) -> None:
    """The stacked path's constructor-time guards (never silent).

    Message transforms are NOT refused here anymore: since PR 4 the
    ``dp``/``topk``/``secure`` registry entries carry stacked in-graph
    implementations (core/transforms.py) and ride the fused path.
    """
    if loss_sum_fn is None and any(c.num_docs < batch_size for c in clients):
        raise ValueError(
            f"{what} exec_mode='vmap' with ragged clients (num_docs < "
            f"batch_size={batch_size}) needs a mask-aware loss_sum_fn "
            "(e.g. prodlda.elbo_loss_sum) so padded rows stay out of the "
            "objective; pass loss_sum_fn= or use exec_mode='loop'")


def _rel_change(old: Pytree, new: Pytree) -> jnp.ndarray:
    num = global_norm(jax.tree_util.tree_map(lambda a, b: a - b, old, new))
    den = jnp.maximum(global_norm(old), 1e-12)
    return num / den


def device_free_bytes(device) -> Optional[int]:
    """``bytes_limit`` less ``bytes_in_use`` as ``device`` reports them,
    or None where it reports no limit (the CPU)."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def scan_clients(param_bytes: int, clients: int,
                 free_bytes: Optional[int]) -> bool:
    """The synchronous round's cohort schedule: True scans the K clients
    one by one, False vmaps them.  The vmapped cohort holds, for each
    client, its local parameters, their gradient and its message,
    ``3 * K * param_bytes``; the round vmaps when that fits in what the
    device reports free, or when it reports no limit."""
    return free_bytes is not None and 3 * clients * param_bytes > free_bytes


# ---------------------------------------------------------------------------
# stage 1: client sampling
# ---------------------------------------------------------------------------
def _cycle_per_client(values: Optional[Sequence[int]], num_clients: int,
                      default: int) -> np.ndarray:
    """Per-client int schedule: cycle a (possibly shorter) tuple over L."""
    if not values:
        return np.full(num_clients, default, np.int64)
    v = np.asarray(values, np.int64)
    return v[np.arange(num_clients) % len(v)]


class RoundScheduler:
    """Samples the K-of-L client cohort for each round.

    Modes:
      * ``uniform`` — K clients uniformly without replacement per round;
      * ``weighted`` — sampling probability proportional to per-client
        corpus size (larger nodes are polled more often);
      * ``deterministic`` — a fixed seeded permutation walked round-robin,
        K at a time: zero sampling variance and every client is selected
        at least once per ceil(L/K) rounds (exactly once when K divides
        L; the wrap-around block repeats a few clients otherwise).

    Mid-training availability (``join_rounds`` / ``leave_rounds``,
    per-client, 0-in-leave = never leaves): client l is *active* at round
    r iff ``join[l] <= r < leave[l]``; every mode samples only among the
    active set (weighted renormalizes over it, deterministic walks the
    fixed permutation restricted to it).  With all clients always active
    the selection is byte-identical to the pre-availability scheduler.

    All modes are deterministic functions of ``(seed, round_idx)`` — two
    schedulers built with the same arguments produce identical cohorts,
    which is what makes simulation sweeps reproducible.
    """

    MODES = ("uniform", "weighted", "deterministic")

    def __init__(self, num_clients: int, clients_per_round: int = 0, *,
                 mode: str = "uniform",
                 weights: Optional[Sequence[float]] = None, seed: int = 0,
                 join_rounds: Optional[Sequence[int]] = None,
                 leave_rounds: Optional[Sequence[int]] = None):
        if mode not in self.MODES:
            raise ValueError(f"unknown sampling mode {mode!r}; "
                             f"one of {self.MODES}")
        self.num_clients = num_clients
        k = clients_per_round or num_clients
        self.clients_per_round = min(k, num_clients)
        self.mode = mode
        self.seed = seed
        if mode == "weighted":
            if weights is None:
                raise ValueError("weighted sampling needs per-client weights")
            w = np.asarray(weights, np.float64)
            self.probs = w / w.sum()
        else:
            self.probs = None
        self.join = _cycle_per_client(join_rounds, num_clients, 0)
        leave = _cycle_per_client(leave_rounds, num_clients, 0)
        # 0 = "never leaves" sentinel -> effectively +inf
        self.leave = np.where(leave <= 0, np.iinfo(np.int64).max, leave)
        self._has_availability = bool(
            (self.join > 0).any()
            or (self.leave < np.iinfo(np.int64).max).any())
        # deterministic mode: one fixed permutation, walked K at a time
        self._perm = np.random.default_rng(seed).permutation(num_clients)

    def active(self, round_idx: int) -> np.ndarray:
        """Client ids present in the federation at round ``round_idx``."""
        return np.where((self.join <= round_idx)
                        & (round_idx < self.leave))[0]

    def select(self, round_idx: int) -> np.ndarray:
        """Sorted client ids of the round-``round_idx`` cohort."""
        act = self.active(round_idx) if self._has_availability \
            else np.arange(self.num_clients)
        a, k = len(act), min(self.clients_per_round, len(act))
        if k >= a:
            return act.copy()        # full participation among active
        if self.mode == "deterministic":
            walk = self._perm[np.isin(self._perm, act)]
            start = (round_idx * k) % a
            idx = walk[np.arange(start, start + k) % a]
            return np.sort(idx)
        rng = np.random.default_rng([self.seed, round_idx])
        if self.probs is None:
            p = None
        elif a == self.num_clients:
            p = self.probs
        else:
            p = self.probs[act] / self.probs[act].sum()
        idx = act[rng.choice(a, k, replace=False, p=p)]
        return np.sort(idx)


# ---------------------------------------------------------------------------
# staleness: host-side reference path
# ---------------------------------------------------------------------------
@dataclass
class PendingUpdate:
    """A straggler's in-flight round message (loop-mode reference)."""
    client: int
    issued_round: int
    due_round: int
    delta: Pytree
    weight: float


def combine_arrivals(arrivals: Sequence[Any],
                     staleness_decay: float, *,
                     clients: Optional[Sequence[int]] = None) -> Pytree:
    """Eq. (2) weighted mean of one round's arriving deltas.

    ``arrivals`` is a non-empty list of ``(age, delta, weight)`` and
    ``staleness_decay`` must lie in [0, 1] — violations raise
    ``ValueError`` up front instead of surfacing as NaN params (decay
    outside [0, 1] amplifies or sign-flips stale updates) or an opaque
    IndexError from the empty weighted mean.

    ``clients`` (optional, aligned with ``arrivals``) enables the
    duplicate-client guard: two weight>0 arrivals from one client id in
    a single delivery window double-count that client's Eq. (2) weight,
    so they are REFUSED.  The engine upholds the supersede-at-message
    contract (a client's newest message replaces its in-flight older
    delta — the same last-write-wins rule the async service documents in
    docs/serving.md), so a duplicate reaching this function indicates a
    routing bug upstream, never a tolerable input.

    Zero-weight arrivals are treated as ABSENT, mirroring the fused
    path's fixed-K padding contract: a padded row must not advance any
    staleness bookkeeping, weigh into the combine, or turn the weighted
    mean into 0/0 — and a round whose arrivals are ALL zero-weight is an
    empty round (same ``ValueError`` as an empty list: the caller must
    skip the combine, not average nothing).

    INVARIANT: the ``staleness_decay ** age`` discount scales the DELTA,
    not the Eq. (2) weight — a weight-only discount would cancel in the
    weighted-mean normalization whenever a round's arrivals all share one
    age (e.g. any single-arrival round), silently trusting stale updates
    fully.  The loop execution mode goes through this one function, and
    the fused in-graph ring buffer is tested against it
    (tests/test_vmap_equivalence.py, tests/test_engine_unified.py).
    """
    if not 0.0 <= staleness_decay <= 1.0:
        raise ValueError(f"staleness_decay must be in [0, 1], got "
                         f"{staleness_decay!r} (values outside amplify or "
                         "sign-flip stale deltas)")
    arrivals = list(arrivals)
    if clients is not None:
        if len(clients) != len(arrivals):
            raise ValueError(
                f"combine_arrivals got {len(clients)} client ids for "
                f"{len(arrivals)} arrivals — the alignment is the whole "
                "point of the duplicate guard")
        live = [int(c) for c, a in zip(clients, arrivals) if a[2] > 0]
        dupes = sorted({c for c in live if live.count(c) > 1})
        if dupes:
            raise ValueError(
                f"combine_arrivals got multiple weight>0 arrivals from "
                f"client(s) {dupes} in one delivery window — a duplicated "
                "client double-counts its Eq. (2) weight; the engine "
                "supersedes in-flight deltas at message time (newest "
                "wins), so this is a routing bug upstream")
    arrivals = [a for a in arrivals if a[2] > 0]
    if not arrivals:
        raise ValueError("combine_arrivals needs at least one (age, delta, "
                         "weight) arrival with weight > 0; an all-straggler "
                         "(or all-padded) round must skip the combine, not "
                         "average nothing")
    scaled = [d if age == 0 else jax.tree_util.tree_map(
        lambda x: x * staleness_decay ** age, d)
        for age, d, _ in arrivals]
    return agg.aggregate_host(scaled, [w for _, _, w in arrivals])


def init_delta_buffer(params: Pytree, capacity: int, *,
                      int_fields: Optional[Mapping[str, int]] = None
                      ) -> Dict[str, Any]:
    """The ONE fixed-capacity stacked delta-slot layout.

    Both in-flight delta stores build on this: the fused straggler ring
    (``FederationEngine._init_ring`` adds ``due``/``age`` bookkeeping)
    and the buffered-async service's aggregation buffer
    (``repro.serve.buffer.DeltaBuffer`` adds ``base_version``).  A slot
    is one client message: ``delta`` leaves are stacked ``(capacity,
    *leaf.shape)`` zeros, ``weight`` is the Eq. (2) sample count (0 =
    free slot — zero-weight rows are masked by every combine), and
    ``client`` records the owning client id (-1 = free) so duplicate
    deltas from one client can be superseded instead of double-counted.

    ``int_fields`` maps extra per-slot int32 field names to their fill
    values (e.g. ``{"due": -1}``).
    """
    c = int(capacity)
    if c < 1:
        raise ValueError(f"delta buffer capacity must be >= 1, got "
                         f"{capacity!r}")
    buf: Dict[str, Any] = {
        "delta": jax.tree_util.tree_map(
            lambda p: jnp.zeros((c,) + p.shape, p.dtype), params),
        "weight": jnp.zeros((c,), jnp.float32),
        "client": jnp.full((c,), -1, jnp.int32),
    }
    for name, fill in (int_fields or {}).items():
        buf[name] = jnp.full((c,), int(fill), jnp.int32)
    return buf


# ---------------------------------------------------------------------------
# stage 3: message transforms — registry + both (loop/stacked) application
# modes live in core/transforms.py; TRANSFORMS / build_transforms /
# TransformCtx are re-exported above for the historical import surface
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# the unified engine
# ---------------------------------------------------------------------------
class FederationEngine:
    """One composable federated execution stack (module docstring).

    ``loss_fn(params, batch) -> scalar mean loss`` is the client's local
    objective.  ``message`` selects what a client's round message is:

      * ``"delta"`` — E local SGD epochs, message = W_l - W, combined by
        Eq. (2) and handed to the ``RoundConfig`` server optimizer
        (the round-engine model; supports every scenario knob);
      * ``"grad"``  — one minibatch gradient (E must be 1), combined by
        Eq. (2) and handed to the wrapped client ``Optimizer`` — the
        literal Algorithm-1 information flow.

    Execution modes (``exec_mode`` kwarg overrides
    ``RoundConfig.exec_mode``): see the class docstrings of the legacy
    presets and DESIGN.md §4.  Ragged federations (some ``num_docs <
    batch_size``) under ``"vmap"`` need a mask-aware ``loss_sum_fn``.
    """

    def __init__(self, loss_fn, init_params: Pytree,
                 clients: Sequence[ClientState], fed: FederatedConfig,
                 rounds: Optional[RoundConfig] = None, *,
                 batch_size: int = 64, exec_mode: Optional[str] = None,
                 loss_sum_fn=None, message: str = "delta",
                 server: Optional[agg.ServerOptimizer] = None,
                 transforms: Optional[Sequence[str]] = None,
                 num_clients_for_masks: Optional[int] = None):
        if message not in MESSAGE_KINDS:
            raise ValueError(f"unknown message kind {message!r}; "
                             f"one of {MESSAGE_KINDS}")
        if message == "grad" and server is None:
            raise ValueError(
                "message='grad' needs an explicit server stage: gradient "
                "messages point UPHILL, so the delta-convention "
                "RoundConfig server optimizers (which ADD their step) "
                "would train by ascent — wrap the client optimizer, e.g. "
                "protocol._wrap_client_optimizer(sgd(lr)), or use the "
                "FederatedTrainer preset")
        self.loss_fn = loss_fn
        self.params = init_params
        self.clients = list(clients)
        self.fed = fed
        self.rc = rounds or RoundConfig()
        self.batch_size = batch_size
        self.message = message
        self.exec_mode = exec_mode or self.rc.exec_mode
        if self.exec_mode not in EXEC_MODES:
            raise ValueError(f"unknown exec_mode {self.exec_mode!r}; "
                             f"one of {EXEC_MODES}")
        # aggregation kernel backend for the fused vmap graphs.  Like
        # pad_cohorts this is accepted-but-inert under loop mode: the
        # host loop is always plain XLA and IS the reference every vmap
        # backend is held to (docs/scenarios.md)
        self.kernel_backend = self.rc.kernel_backend
        if self.kernel_backend not in KERNEL_BACKENDS:
            raise ValueError(
                f"unknown kernel_backend {self.kernel_backend!r}; "
                f"one of {KERNEL_BACKENDS}")
        self._nmask = num_clients_for_masks or len(self.clients)

        if not 0.0 <= self.rc.staleness_decay <= 1.0:
            raise ValueError(
                f"staleness_decay must be in [0, 1], got "
                f"{self.rc.staleness_decay!r} — both the loop-mode "
                "combine_arrivals and the fused ring buffer would "
                "amplify or sign-flip stale deltas outside that range")

        # -- transform stage resolution --------------------------------
        names = tuple(transforms if transforms is not None
                      else self.rc.transforms)
        if not names and (fed.dp_noise_multiplier > 0
                          or fed.compression_topk > 0
                          or fed.secure_aggregation
                          or bool(fed.message_precision)):
            raise NotImplementedError(
                "FederatedConfig requests message-level "
                "privacy/compression/precision but no transform stage is "
                "configured for this engine; declare the intent explicitly "
                "via RoundConfig.transforms="
                "('dp'|'topk'|'secure'|'precision', ...) "
                "(or use the FederatedTrainer preset, which derives its "
                "grad transforms from FederatedConfig automatically) — "
                "the knobs are never silently dropped")
        if self.exec_mode == "vmap":
            _check_vmap_preconditions(fed, self.clients, batch_size,
                                      loss_sum_fn, what=type(self).__name__)
        self._transforms = _build_transforms(names, fed)
        # stacked transform state (e.g. the topk error memory, one row
        # per GLOBAL client) — threaded through every fused call
        self._tstate: Dict[str, Any] = {}
        if self.exec_mode == "vmap":
            for name, t in self._transforms:
                st = t.init_state(init_params, len(self.clients))
                if st is not None:
                    self._tstate[name] = st

        # -- local-update stage ----------------------------------------
        self._epochs = self._resolve_epochs()
        if len(self.clients) and (self._epochs < 1).any():
            raise ValueError(
                "every client needs >= 1 local epoch (got "
                f"local_epochs={self.rc.local_epochs}, "
                f"local_epochs_by_client={self.rc.local_epochs_by_client}) "
                "— a zero-epoch client has no round message and would "
                "divide the Eq. (2) combine by zero")
        self._e_max = int(self._epochs.max()) if len(self.clients) else 1
        self._hetero = bool((self._epochs != self._epochs[0]).any()) \
            if len(self.clients) else False
        if message == "grad" and self._e_max != 1:
            raise ValueError("message='grad' is the single-minibatch "
                             "Algorithm-1 protocol; local_epochs must be 1 "
                             "(use message='delta' for multi-epoch clients)")
        self._mean_loss = masked_mean_loss(loss_fn, loss_sum_fn)
        self._grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        self._step_fn = sgd_step_fn(loss_fn, self.fed.learning_rate)
        self._fused_sync = None
        self._fused_stale = None
        self._deliver_only = None
        self._zero_stacked = None      # all-padded round template (vmap)
        self._scan = False             # scan the cohort (scan_clients)
        self._corpus = None            # the clients' rows on the device
        self._corpus_open = True       # decided at the first dispatch
        # one entry per TRACE of each fused graph (the bodies bump it at
        # trace time only) — the retrace-free fixed-K contract is
        # asserted against this in tests and the CI bench payload
        self.trace_counts: Dict[str, int] = {}

        # -- sampler stage ---------------------------------------------
        self.scheduler = RoundScheduler(
            len(self.clients), self.rc.clients_per_round,
            mode=self.rc.sampling,
            weights=[c.num_docs for c in self.clients]
            if self.rc.sampling == "weighted" else None,
            seed=self.rc.sampling_seed,
            join_rounds=self.rc.client_join_round,
            leave_rounds=self.rc.client_leave_round)
        self._check_secure_compat()

        # -- combine / staleness stage ---------------------------------
        # buffer active <=> both knobs on; decides whether the vmap path
        # routes the round through the fused ring buffer
        self._stale_enabled = (self.rc.straggler_prob > 0.0
                               and self.rc.max_staleness > 0)
        # fixed-K stacking: pad shrunken cohorts (availability churn)
        # with zero-weight rows up to clients_per_round so every round
        # reuses ONE compiled graph (trace_counts pins this)
        self._pad = (self.exec_mode == "vmap" and self.rc.pad_cohorts
                     and len(self.clients) > 0)
        # -- device mesh (RoundConfig.mesh_data / execution.mesh) -------
        # a ("data",)-axis mesh sharding the stacked (K, ...) cohort,
        # the (L, ...) transform state and the (C, ...) straggler ring;
        # None = unsharded.  Like kernel_backend, accepted-but-inert
        # under loop mode — the host loop stays the unsharded reference.
        self._mesh = None
        mesh_data = int(getattr(self.rc, "mesh_data", 0) or 0)
        if mesh_data and self.exec_mode == "vmap" and len(self.clients):
            k_fix = self.scheduler.clients_per_round
            n_state = len(self.clients)
            if k_fix % mesh_data or n_state % mesh_data:
                raise ValueError(
                    f"execution.mesh data={mesh_data} does not divide the "
                    f"cohort width K={k_fix} and the client count "
                    f"L={n_state} — cohorts and per-client state are "
                    "never silently repartitioned; resize the federation "
                    "or the mesh")
            self._mesh = sharding.fed_mesh(mesh_data)
        self.pending: List[PendingUpdate] = []   # loop-mode reference
        self._ring = None                        # vmap-mode device buffer

        # -- server stage ----------------------------------------------
        self.server_opt = server or self._make_server_opt(self.rc)
        self.server_state = self.server_opt.init(init_params)
        self.history: List[Dict[str, float]] = []
        self._round = 0

    # -- construction helpers ---------------------------------------------
    def _resolve_epochs(self) -> np.ndarray:
        return _cycle_per_client(self.rc.local_epochs_by_client,
                                 len(self.clients), self.rc.local_epochs)

    def _check_secure_compat(self) -> None:
        """Pairwise masks only cancel when every mask-holder's message
        lands in the SAME Eq. (2) combine, unscaled — refuse configs
        that would silently break the cancellation."""
        if not any(n == "secure" for n, _ in self._transforms):
            return
        if any(n == "precision" for n, _ in self._transforms):
            raise ValueError(
                "the 'secure' transform is incompatible with 'precision' "
                "(bf16 messages): the pairwise masks cancel BITWISE only "
                "on the fp32 dyadic grid — rounding the masked messages "
                "to bfloat16 destroys the cancellation, which would be a "
                "silent privacy downgrade, not an approximation")
        if self.rc.straggler_prob > 0 and self.rc.max_staleness > 0:
            raise ValueError(
                "the 'secure' transform is incompatible with the straggler "
                "buffer: a stale masked message arrives in a later combine "
                "than its pair partners (and is decay-scaled), so the "
                "pairwise masks no longer cancel")
        if (self.scheduler.clients_per_round < len(self.clients)
                or self.scheduler._has_availability):
            raise ValueError(
                "the 'secure' transform needs synchronous full "
                "participation (K = L, no client dropout/join): pairwise "
                "masks over the full population only cancel when every "
                "client's message joins the same combine")

    @staticmethod
    def _make_server_opt(rc: RoundConfig) -> agg.ServerOptimizer:
        # every registered factory takes server_lr; per-name extras on top
        # (unknown names raise the registry KeyError before kwargs apply)
        kw = {"server_lr": rc.server_lr}
        if rc.server_optimizer == "fedavgm":
            kw["momentum"] = rc.server_momentum
        elif rc.server_optimizer == "fedadam":
            kw.update(b1=rc.server_momentum, b2=rc.server_beta2,
                      eps=rc.server_eps)
        return agg.get_server_optimizer(rc.server_optimizer, **kw)

    # -- staleness --------------------------------------------------------
    def _straggler_delay(self, round_idx: int, client: int) -> int:
        """0 = delivered this round; d>0 = arrives d rounds late."""
        rc = self.rc
        if rc.straggler_prob <= 0.0 or rc.max_staleness <= 0:
            return 0
        rng = np.random.default_rng(
            [rc.sampling_seed, 0x57A1E, round_idx, client])
        if rng.random() >= rc.straggler_prob:
            return 0
        return int(rng.integers(1, rc.max_staleness + 1))

    # -- arrival delivery (loop-mode reference) ---------------------------
    def _deliver_and_apply(self, r: int, fresh, fresh_clients=None) -> tuple:
        """Merge this round's fresh arrivals with due stragglers, run the
        Eq. (2) combine (staleness-discounted) + server-optimizer update.
        Returns ``(rel_change, num_arrived)``."""
        due = [p for p in self.pending if p.due_round <= r]
        self.pending = [p for p in self.pending if p.due_round > r]
        superseded = 0
        if fresh_clients is not None:
            # newest-wins dedupe within the delivery window (the
            # supersede contract the async service documents,
            # docs/serving.md): a fresh message beats the same client's
            # due straggler delta, and among due deltas from one client
            # the latest issue wins.  Without this, a client landing
            # twice in one window double-counts its Eq. (2) weight —
            # the combine_arrivals duplicate guard refuses downstream.
            fresh_ids = set(fresh_clients)
            best: Dict[int, PendingUpdate] = {}
            for p in due:
                if p.client in fresh_ids:
                    superseded += 1
                    continue
                b = best.get(p.client)
                if b is None:
                    best[p.client] = p
                else:
                    superseded += 1
                    if p.issued_round > b.issued_round:
                        best[p.client] = p
            due = [p for p in due if best.get(p.client) is p]
        arrivals = list(fresh) + [(r - p.issued_round, p.delta, p.weight)
                                  for p in due]
        clients = None
        if fresh_clients is not None:
            clients = list(fresh_clients) + [p.client for p in due]
        rel = 0.0
        if arrivals:
            delta_bar = combine_arrivals(arrivals, self.rc.staleness_decay,
                                         clients=clients)
            old = self.params
            self.params, self.server_state = self.server_opt.apply(
                self.params, delta_bar, self.server_state, r)
            rel = float(_rel_change(old, self.params))
        return rel, len(arrivals), superseded

    # -- local update + transforms, one client (loop mode) ----------------
    def _local_message(self, l: int, round_key):
        c = self.clients[l]
        rng = jax.random.fold_in(round_key, l)
        if self.message == "grad":
            batch, n = sample_minibatch(c.data, c.num_docs, rng,
                                        self.batch_size)
            loss, msg = self._grad_fn(self.params, batch)
            loss, n = float(loss), float(n)
        else:
            msg, n, loss = client_round_update(
                self._step_fn, self.params, c, rng,
                local_epochs=int(self._epochs[l]),
                batch_size=self.batch_size)
        if self._transforms:
            ctx = _TransformCtx(round_key, rng, l, self._nmask, n, c)
            for _, fn in self._transforms:
                msg = fn(msg, ctx)
        return msg, n, loss

    # -- one round, loop mode ---------------------------------------------
    def _round_loop(self, r: int, round_key, cohort) -> Dict[str, float]:
        losses, loss_w = [], []
        fresh, fresh_clients = [], []      # (age=0, message, weight)
        for l in cohort:
            l = int(l)
            msg, n, loss = self._local_message(l, round_key)
            losses.append(loss)
            loss_w.append(n)
            d = self._straggler_delay(r, l)
            if d == 0:
                fresh.append((0, msg, n))
                fresh_clients.append(l)
            else:
                self.pending.append(PendingUpdate(l, r, r + d, msg, n))

        rel, arrived, superseded = self._deliver_and_apply(
            r, fresh, fresh_clients)
        return {"round": r,
                "loss": float(np.average(losses, weights=loss_w))
                if losses else float("nan"),
                "rel_change": rel,
                "participants": len(cohort),
                "arrived": arrived,
                "superseded": superseded,
                "in_flight": len(self.pending)}

    # -- vmap graph builders ----------------------------------------------
    def _build_client_update(self):
        """The vmappable E-epoch local update for ONE client."""
        lr = self.fed.learning_rate
        grad_fn = jax.value_and_grad(self._mean_loss, has_aux=True)
        tmap = jax.tree_util.tree_map
        e_max, gate = self._e_max, self._hetero

        if self.message == "grad":
            def client_update(params, batches, n_epochs):
                # single-minibatch gradient message (E axis is size 1)
                (loss, counters), g = grad_fn(params,
                                              tmap(lambda v: v[0], batches))
                return g, loss[None], counters
            return client_update

        def client_update(params, batches, n_epochs):
            # batches: pytree of (E, ...) leaves — one client's epoch stack
            def epoch(local, xs):
                b, s = xs
                (loss, counters), grads = grad_fn(local, b)
                stepped = tmap(lambda p, g: p - lr * g.astype(p.dtype),
                               local, grads)
                if gate:
                    # heterogeneous-E cohorts: epochs beyond this client's
                    # count are no-ops (same trajectory as a loop client
                    # that never ran them)
                    keep = s < n_epochs
                    stepped = tmap(lambda a, b_: jnp.where(keep, b_, a),
                                   local, stepped)
                    loss = jnp.where(keep, loss, 0.0)
                    counters = tmap(lambda c: jnp.where(keep, c, 0),
                                    counters)
                return stepped, (loss, counters)
            local, (losses, counters) = jax.lax.scan(
                epoch, params, (batches, jnp.arange(e_max)))
            return (tmap(lambda a, b: b - a, params, local), losses,
                    tmap(lambda c: c.sum(0), counters))

        return client_update

    def _build_vmap_fns(self):
        """Trace-once builders for the stacked execution graphs."""
        tmap = jax.tree_util.tree_map
        client_update = self._build_client_update()
        server_opt = self.server_opt
        decay = float(self.rc.staleness_decay)
        transforms = self._transforms
        nmask = self._nmask
        counts = self.trace_counts
        # static at trace time: selects the aggregation kernel backend
        # ("xla" keeps every expression below byte-identical to pre-PR-7)
        kb = self.kernel_backend
        # static at trace time: the cohort schedule (scan_clients)
        scan = self._scan
        # static at trace time: the ("data",)-axis device mesh (or None).
        # Sharded runs keep the SAME graphs below — inputs arrive with
        # the K/L/C axes row-sharded (in_shardings), the per-row stages
        # partition by GSPMD propagation, and the cross-row reductions
        # (Eq. (2) combine, ring delivery) run as kernels/ops.py
        # shard_map islands of per-device partials + one psum.
        mesh = self._mesh
        if mesh is not None:
            row_ns = sharding.shardings_for(mesh, sharding.P("data"))

            def pin_rows(tree):
                return tmap(lambda x: jax.lax.with_sharding_constraint(
                    x, row_ns), tree)
        else:
            pin_rows = lambda tree: tree  # noqa: E731

        def transform_stage(msgs, tstate, round_key, ids, w):
            """Stage 3 INSIDE the fused graph: every registry transform
            applied to the stacked (K, ...) messages, then zero-weight
            (padded) rows re-zeroed so neither transform output nor
            local-update garbage from an all-zero padded batch can leak
            into the combine or the ring (a NaN delta times a zero
            weight is still NaN)."""
            if transforms:
                ctx = _StackedCtx(
                    round_key=round_key, client_ids=ids, valid=w > 0.0,
                    weights=w, num_clients=nmask, kernel_backend=kb,
                    mesh=mesh)
                tstate = dict(tstate)
                for name, t in transforms:
                    with spans.scope(f"transform/{name}"):
                        msgs, st = t.stacked(msgs, ctx, tstate.get(name))
                    if name in tstate:
                        tstate[name] = st
            valid = w > 0.0
            msgs = tmap(
                lambda m: jnp.where(
                    valid.reshape((-1,) + (1,) * (m.ndim - 1)), m, 0.0),
                msgs)
            return msgs, tstate

        def stacked_messages(params, stacked, e_counts):
            """All K clients' local updates in one graph -> (K, ...)."""
            with spans.scope(spans.LOCAL_UPDATE):
                return jax.vmap(client_update, in_axes=(None, 0, 0))(
                    params, stacked, e_counts)

        def scanned_numerator(params, tstate, stacked, e_counts, w, ids,
                              round_key):
            """The client-scan schedule: each client's local update, the
            transforms on its one-row stack, and its term of the Eq. (2)
            numerator, one client at a time -> (numerator, tstate,
            (K, E) losses, (K, ...) counters)."""
            acc0 = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def one(carry, xs):
                acc, tstate = carry
                batches, e_k, w_k, id_k = xs
                with spans.scope(spans.LOCAL_UPDATE):
                    msg, losses, counters = client_update(params, batches,
                                                          e_k)
                row, tstate = transform_stage(
                    tmap(lambda m: m[None], msg), tstate, round_key,
                    id_k[None], w_k[None])
                with spans.scope(spans.AGGREGATE):
                    acc = kops.fed_weighted_accumulate(
                        acc, tmap(lambda m: m[0], row), w_k, backend=kb)
                return (acc, tstate), (losses, counters)

            (acc, tstate), (losses, counters) = jax.lax.scan(
                one, (acc0, tstate), (stacked, e_counts, w, ids))
            return acc, tstate, losses, counters

        def fused_sync(params, server_state, tstate, stacked, e_counts,
                       weights, ids, round_key, round_idx):
            """messages -> transforms -> Eq. (2) combine -> server
            update, zero host hops (the synchronous fast path).  The
            update is gated on any positive weight: an all-padded
            (empty) cohort leaves params AND server state untouched —
            momentum must not decay on a no-arrival round.  Under the
            client-scan schedule (``self._scan``) the combine's numerator
            is summed client by client; the rest is the same."""
            counts["fused_sync"] = counts.get("fused_sync", 0) + 1
            w = weights.astype(jnp.float32)
            if scan:
                num, tstate, losses, counters = scanned_numerator(
                    params, tstate, stacked, e_counts, w, ids, round_key)
            else:
                msgs, losses, counters = stacked_messages(params, stacked,
                                                          e_counts)
                msgs = pin_rows(msgs)
                msgs, tstate = transform_stage(msgs, tstate, round_key,
                                               ids, w)
            with spans.scope(spans.AGGREGATE):
                bar = tmap(lambda a: a / jnp.maximum(w.sum(), 1e-12), num) \
                    if scan else kops.fed_weighted_combine(
                        msgs, w, backend=kb, mesh=mesh)
                upd_p, upd_s = server_opt.apply(params, bar, server_state,
                                                round_idx)
                has = w.sum() > 0.0
                sel = lambda o, n_: tmap(  # noqa: E731
                    lambda a, b: jnp.where(has, b, a), o, n_)
                new_params, new_state = sel(params, upd_p), sel(server_state,
                                                                upd_s)
                rel = jnp.where(has, _rel_change(params, new_params), 0.0)
            # the model's counters of the positive-weight clients
            counters = tmap(lambda c: jnp.sum(jnp.where(
                (w > 0.0).reshape((-1,) + (1,) * (c.ndim - 1)), c, 0), 0),
                counters)
            return new_params, new_state, tstate, losses, rel, counters

        def ring_deliver(params, server_state, ring, round_idx,
                         fresh=None):
            """The in-graph equivalent of ``_deliver_and_apply``:
            fresh (K,)-stacked messages (optional) + due ring slots ->
            newest-wins window dedupe -> staleness-discounted Eq. (2)
            combine -> gated server update -> cleared slots.  Matches
            :func:`combine_arrivals` + the ``_deliver_and_apply``
            supersede contract on the same arrivals up to float32
            reduction order (tested)."""
            occupied = ring["weight"] > 0.0
            due = occupied & (ring["due"] <= round_idx)
            # newest-wins dedupe within the delivery window (the loop
            # path's supersede contract, docs/serving.md): among due
            # slots sharing a client the youngest (smallest age ==
            # latest issue) wins; a fresh arrival beats any due slot
            # from the same client.  Padded fresh rows (w == 0) never
            # supersede — their ids alias client 0.
            cl, age = ring["client"], ring["age"]
            idx = jnp.arange(cl.shape[0])
            same = due[:, None] & due[None, :] \
                & (cl[:, None] == cl[None, :]) \
                & (idx[:, None] != idx[None, :])
            beat = same & ((age[None, :] < age[:, None])
                           | ((age[None, :] == age[:, None])
                              & (idx[None, :] < idx[:, None])))
            sup = beat.any(axis=1)
            if fresh is not None:
                f_live = (fresh[2] == 0) \
                    & (fresh[1].astype(jnp.float32) > 0.0)
                dup_f = (cl[:, None] == fresh[3][None, :]) \
                    & f_live[None, :]
                sup = sup | (due & dup_f.any(axis=1))
            n_sup = sup.sum()
            due = due & ~sup
            due_w = jnp.where(due, ring["weight"], 0.0)          # (C,)
            discount = jnp.power(decay, ring["age"].astype(jnp.float32))
            total_w = due_w.sum()
            fresh_w = None
            if fresh is not None:
                msgs, weights, delays, _ids = fresh
                fresh_w = jnp.where(delays == 0,
                                    weights.astype(jnp.float32), 0.0)
                total_w = total_w + fresh_w.sum()
            has = total_w > 0.0
            denom = jnp.maximum(total_w, 1e-12)
            ring_coef = due_w * discount                         # (C,)

            with spans.scope(spans.AGGREGATE):
                # the ring and fresh numerators through the selected
                # backend (on a mesh: per-device partials + one psum
                # each, kernels/ops.py), then the replicated division
                acc = kops.fed_weighted_sum(ring["delta"], ring_coef,
                                            backend=kb, mesh=mesh)
                if fresh is not None:
                    acc = tmap(lambda a, b: a + b, acc,
                               kops.fed_weighted_sum(fresh[0], fresh_w,
                                                     backend=kb, mesh=mesh))
                bar = tmap(lambda a: a / denom, acc)
                upd_p, upd_s = server_opt.apply(params, bar, server_state,
                                                round_idx)
                # an all-straggler round leaves params AND server state
                # alone (momentum must not decay on a no-arrival round)
                sel = lambda o, n_: tmap(  # noqa: E731
                    lambda a, b: jnp.where(has, b, a), o, n_)
                new_params, new_state = sel(params, upd_p), sel(server_state,
                                                                upd_s)
                rel = jnp.where(has, _rel_change(params, new_params), 0.0)
            # delivered AND superseded slots both leave the ring — a
            # superseded delta will never deliver
            gone = due | sup
            ring = dict(ring,
                        weight=jnp.where(gone, 0.0, ring["weight"]),
                        due=jnp.where(gone, -1, ring["due"]),
                        client=jnp.where(gone, -1, ring["client"]))
            return new_params, new_state, ring, rel, due.sum(), has, n_sup

        def fused_stale(params, server_state, tstate, ring, stacked,
                        e_counts, weights, delays, ids, round_key,
                        round_idx):
            """One straggler-regime round, fully in-graph: local updates,
            message transforms, ring delivery + combine + server update,
            straggler insertion.  The per-client deltas never leave the
            device.  Padded zero-weight rows are absent throughout: they
            contribute no fresh weight, are never inserted into the ring
            (so no staleness age ever starts for them), and an
            all-padded cohort degenerates to a deliver-only round."""
            counts["fused_stale"] = counts.get("fused_stale", 0) + 1
            msgs, losses, _ = stacked_messages(params, stacked, e_counts)
            msgs = pin_rows(msgs)
            w = weights.astype(jnp.float32)
            msgs, tstate = transform_stage(msgs, tstate, round_key, ids, w)
            new_params, new_state, ring, rel, n_due, _, n_sup = \
                ring_deliver(params, server_state, ring, round_idx,
                             (msgs, w, delays, ids))
            # insert this round's stragglers into the freed slots:
            # j-th straggler (cohort order) -> j-th free slot (slot order),
            # computed with cumsum ranks so the scatter is one fixed-shape
            # .at[].set per leaf (index C = the dropped dummy row)
            c = ring["weight"].shape[0]
            free = ring["weight"] <= 0.0
            slot_of_rank = jnp.sort(jnp.where(free, jnp.arange(c), c))
            is_strag = (delays > 0) & (w > 0)
            rank = jnp.cumsum(is_strag.astype(jnp.int32)) - 1
            tgt = jnp.where(is_strag,
                            slot_of_rank[jnp.clip(rank, 0, c - 1)], c)
            ring = dict(
                delta=jax.tree_util.tree_map(
                    lambda buf, m: buf.at[tgt].set(m.astype(buf.dtype),
                                                   mode="drop"),
                    ring["delta"], msgs),
                weight=ring["weight"].at[tgt].set(w, mode="drop"),
                due=ring["due"].at[tgt].set(
                    round_idx + delays, mode="drop"),
                age=ring["age"].at[tgt].set(delays, mode="drop"),
                client=ring["client"].at[tgt].set(ids, mode="drop"))
            arrived = ((delays == 0) & (w > 0)).sum() + n_due
            in_flight = (ring["weight"] > 0).sum()
            return (new_params, new_state, tstate, ring, losses, rel,
                    arrived, in_flight, n_sup)

        def deliver_only(params, server_state, ring, round_idx):
            """Empty-cohort round (unpadded mode): due stragglers still
            deliver.  With ``pad_cohorts`` the all-padded cohort runs
            through ``fused_stale`` instead — one graph for every round."""
            counts["deliver_only"] = counts.get("deliver_only", 0) + 1
            new_params, new_state, ring, rel, n_due, _, n_sup = \
                ring_deliver(params, server_state, ring, round_idx)
            in_flight = (ring["weight"] > 0).sum()
            return (new_params, new_state, ring, rel, n_due, in_flight,
                    n_sup)

        # donation reuses the param/server-state/transform-state/ring
        # buffers in place on accelerators; CPU ignores donation, skip
        # the warning
        dn = jax.default_backend() != "cpu"
        if mesh is None:
            self._fused_sync = jax.jit(
                fused_sync, donate_argnums=(0, 1, 2) if dn else ())
            self._fused_stale = jax.jit(
                fused_stale, donate_argnums=(0, 1, 2, 3) if dn else ())
            self._deliver_only = jax.jit(
                deliver_only, donate_argnums=(0, 1, 2) if dn else ())
            return
        # sharded-jit: pytree-prefix shardings place every client-axis
        # operand (stacked batches, weights/ids/delays, transform state,
        # ring slots, per-client losses) row-first over "data" and keep
        # params/server state replicated — one compile, no host-side
        # resharding between rounds (outputs already carry the input
        # shardings of the next call).
        row = sharding.shardings_for(mesh, sharding.P("data"))
        rep = sharding.shardings_for(mesh, sharding.P())
        self._fused_sync = jax.jit(
            fused_sync, donate_argnums=(0, 1, 2) if dn else (),
            # (params, server_state, tstate, stacked, e_counts, weights,
            #  ids, round_key, round_idx)
            in_shardings=(rep, rep, row, row, row, row, row, rep, rep),
            out_shardings=(rep, rep, row, row, rep, rep))
        self._fused_stale = jax.jit(
            fused_stale, donate_argnums=(0, 1, 2, 3) if dn else (),
            # (params, server_state, tstate, ring, stacked, e_counts,
            #  weights, delays, ids, round_key, round_idx)
            in_shardings=(rep, rep, row, row, row, row, row, row, row,
                          rep, rep),
            out_shardings=(rep, rep, row, row, row, rep, rep, rep, rep))
        self._deliver_only = jax.jit(
            deliver_only, donate_argnums=(0, 1, 2) if dn else (),
            in_shardings=(rep, rep, row, rep),
            out_shardings=(rep, rep, row, rep, rep, rep, rep))

    def _place(self, tree, rows: bool = False):
        """An engine-owned copy of ``tree``, placed as the fused graphs'
        ``in_shardings`` expect: replicated, or row-sharded over
        ``"data"`` for the client-axis state (transform state, ring)."""
        if self._mesh is None:
            return jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True), tree)
        spec = sharding.P("data") if rows else sharding.P()
        return jax.device_put(tree, sharding.shardings_for(self._mesh, spec),
                              may_alias=False)

    def _own_state(self) -> None:
        """Give the fused graphs state that this engine owns.

        The graphs donate params, server state, transform state and the
        ring on accelerators, which deletes the donated buffers: the
        caller's ``init_params`` tree (or a restored snapshot) must
        never be donated itself, so every array is copied once, when the
        graphs are built and after a restore.  On a mesh the copies go
        straight to the replicated / row shardings — an array's sharding
        is part of its type, so round 1 then sees the same input types
        as every later round (one trace).
        """
        self.params = self._place(self.params)
        self.server_state = self._place(self.server_state)
        self._tstate = self._place(self._tstate, rows=True)
        if self._ring is not None:
            self._ring = self._place(self._ring, rows=True)

    def _choose_schedule(self) -> bool:
        """:func:`scan_clients` on the sizes the engine observes: its
        parameters' bytes, the cohort width K and what the device of its
        state reports free.  A mesh keeps the vmap (its rows are
        sharded).  A transform that spans the cohort (``secure``) is
        refused under the scan: its masks cancel only in one stacked
        combine."""
        if self._mesh is not None:
            return False
        leaves = jax.tree_util.tree_leaves(self.params)
        (device,) = leaves[0].devices()
        scan = scan_clients(sum(x.nbytes for x in leaves),
                            self.scheduler.clients_per_round,
                            device_free_bytes(device))
        if scan and any(n == "secure" for n, _ in self._transforms):
            raise ValueError(
                "the 'secure' transform is refused under the client-scan "
                "round (a cohort too large to vmap on this device): its "
                "pairwise masks span the cohort and cancel only in one "
                "stacked combine")
        return scan

    def _resident_corpus(self, fused, args, stacked):
        """Every client's rows placed on the device of the engine's state
        (``place_corpus``), or None: each cohort is then filled in host
        numpy and copied.

        Decided at the first round's dispatch of the round program
        ``fused(*args)`` on the cohort ``stacked``, from what the device
        reports: ``bytes_limit`` less ``bytes_in_use`` (the placed state
        and straggler ring, and whatever else the process holds) must
        hold the rows, the cohort and the larger of the round program's
        temporaries (its compiled ``memory_analysis``; the call reuses
        that compile) and the gather's (one cohort of rows as wide as
        the resident ones).  A device that reports no limit (the CPU)
        takes them.  A mesh keeps the host fill: its clients' rows would
        have to be sharded by client.
        """
        if self._mesh is not None:
            return None
        datas = [c.data for c in self.clients]
        (device,) = jax.tree_util.tree_leaves(self.params)[0].devices()
        free = device_free_bytes(device)
        if free is not None:
            row, docs = row_nbytes(datas[0]), sum(
                len(next(iter(d.values()))) for d in datas)
            temp = getattr(fused.lower(*args).compile().memory_analysis(),
                           "temp_size_in_bytes", 0)
            need = row * (docs + 1) + sum(
                np.asarray(v).nbytes for v in stacked.values()) + max(
                temp, row * np.size(stacked["doc_mask"]))
            if need > free:
                return None
        return place_corpus(datas, device)

    def _init_ring(self):
        """Fixed-capacity device ring buffer for in-flight deltas.

        Capacity C = K_max * max_staleness can never overflow: a round
        inserts at most K stragglers and every entry lives at most
        max_staleness rounds, so at the insertion point of round r at
        most K*(max_staleness-1) older entries are still in flight.
        """
        c = max(1, self.scheduler.clients_per_round * self.rc.max_staleness)
        return self._place(init_delta_buffer(
            self.params, c, int_fields={"due": -1, "age": 0}), rows=True)

    def _zero_cohort(self, k_fix: int):
        """All-padded stacked round template (cached): the fixed-K shape
        with every row zero-weight, used when nobody is active but the
        round must still run the fused graph (straggler delivery) —
        keeping even empty rounds retrace-free."""
        if self._zero_stacked is None:
            e, p = self._e_max, self.batch_size
            st = {k: np.zeros((k_fix, e, p) + np.asarray(v).shape[1:],
                              np.asarray(v).dtype)
                  for k, v in self.clients[0].data.items()}
            st["doc_mask"] = np.zeros((k_fix, e, p), np.float32)
            st["rng"] = np.zeros((k_fix, e, 2), np.uint32)
            self._zero_stacked = (st, np.zeros((k_fix, e), np.float32))
        return self._zero_stacked

    # -- one round, vmap mode ---------------------------------------------
    def _round_vmap(self, r: int, round_key, cohort) -> Dict[str, float]:
        cohort = [int(l) for l in cohort]
        if self._fused_sync is None:
            self._own_state()
            self._scan = self._choose_schedule()
            self._build_vmap_fns()
        ri = np.int32(r)
        # fixed-K stacking: availability churn shrinks the cohort, the
        # stacked axis stays clients_per_round wide (zero-weight rows)
        k_fix = self.scheduler.clients_per_round if self._pad \
            else len(cohort)

        if not cohort and not self._pad:
            # unpadded mode: nobody active; due stragglers still deliver
            rel, arrived, in_flight, superseded = 0.0, 0, 0, 0
            if self._stale_enabled and self._ring is not None:
                (self.params, self.server_state, self._ring, rel, arrived,
                 in_flight, n_sup) = self._deliver_only(
                    self.params, self.server_state, self._ring, ri)
                rel, arrived = float(rel), int(arrived)
                in_flight, superseded = int(in_flight), int(n_sup)
            return {"round": r, "loss": float("nan"), "rel_change": rel,
                    "participants": 0, "arrived": arrived,
                    "superseded": superseded, "in_flight": in_flight}

        if cohort:
            stacked, counts = stacked_round_batches(
                [self.clients[l].data for l in cohort],
                [self.clients[l].num_docs for l in cohort], round_key,
                cohort, batch_size=self.batch_size,
                local_epochs=self._e_max, pad_to=k_fix,
                shard_multiple=self._mesh.shape["data"]
                if self._mesh is not None else None,
                resident=self._corpus)
        else:
            stacked, counts = self._zero_cohort(k_fix)
        e_counts = np.zeros((k_fix,), np.int32)
        e_counts[:len(cohort)] = self._epochs[cohort]
        ids = np.zeros((k_fix,), np.int32)
        ids[:len(cohort)] = cohort
        # epochs beyond a client's count are gated off in-graph; their
        # draws must not weigh into Eq. (2) or the loss bookkeeping
        # (padded rows have e_count 0, so their counts zero out here)
        counts = counts * (np.arange(self._e_max)[None, :]
                           < e_counts[:, None])
        weights = counts.sum(axis=1)        # (K,) Eq. (2) weights, pad=0

        if not self._stale_enabled:
            # fast path: one jitted call per round, donated buffers
            fused = self._fused_sync
            args = (self.params, self.server_state, self._tstate, stacked,
                    e_counts, weights, ids, round_key, ri)
        else:
            # straggler regime, equally fused: the stacked deltas go
            # straight into the in-graph ring buffer — no host round-trip
            if self._ring is None:
                self._ring = self._init_ring()
            delays = np.zeros((k_fix,), np.int32)
            delays[:len(cohort)] = [self._straggler_delay(r, l)
                                    for l in cohort]
            fused = self._fused_stale
            args = (self.params, self.server_state, self._tstate,
                    self._ring, stacked, e_counts, weights, delays, ids,
                    round_key, ri)
        if self._corpus_open:
            self._corpus_open = False
            self._corpus = self._resident_corpus(fused, args, stacked)
        with spans.span(spans.DISPATCH, scan=int(self._scan)):
            out = fused(*args)
        counters = {}
        if not self._stale_enabled:
            (self.params, self.server_state, self._tstate, losses, rel,
             counters) = out
            arrived, in_flight, n_sup = len(cohort), 0, 0
        else:
            (self.params, self.server_state, self._tstate, self._ring,
             losses, rel, arrived, in_flight, n_sup) = out

        with spans.span(spans.FETCH):
            rel, arrived = float(rel), int(arrived)
            in_flight, superseded = int(in_flight), int(n_sup)
            losses = np.asarray(losses)         # (K, E) per-epoch means
            # zero-count epochs (padded rows under homogeneous E, where
            # the in-scan loss gate is compiled out; gated-off hetero
            # epochs) may carry garbage values — 0-weighting alone would
            # keep a NaN/inf (0 * inf = nan), so mask them out before the
            # weighted average
            losses = np.where(counts > 0, losses, 0.0)
            client_loss = (losses * counts).sum(axis=1) \
                / np.maximum(counts.sum(axis=1), 1.0)
            return {"round": r,
                    "loss": float(np.average(client_loss, weights=weights))
                    if cohort else float("nan"),
                    "rel_change": rel,
                    "participants": len(cohort),
                    "arrived": arrived,
                    "superseded": superseded,
                    "in_flight": in_flight,
                    # the model's counters summed over the round's clients
                    # and steps (expert_tokens: pairs per held expert)
                    **{k: np.asarray(v).tolist()
                       for k, v in counters.items()}}

    # -- stopping ---------------------------------------------------------
    @staticmethod
    def stop_criterion(rec: Mapping[str, Any], rel_tol: float) -> bool:
        """The Alg.-1 stopping rule — only applied to rounds where an
        update landed.  The ONE implementation shared by :meth:`fit`
        and the ``repro.api.Federation`` facade, so the facade's
        step-for-step-``fit`` trajectory contract cannot drift."""
        return bool(rec["arrived"]) and rec["rel_change"] < rel_tol

    # -- snapshot / resume -------------------------------------------------
    # format 2: the straggler ring gained a per-slot "client" array (the
    # supersede-at-message contract) — format-1 rings cannot be resumed
    STATE_FORMAT = 2

    def state_dict(self) -> Dict[str, Any]:
        """Host-numpy snapshot of EVERYTHING the next round depends on.

        Covers params, server-optimizer state, transform state (the
        top-k error memories, both the vmap-mode ``(L, ...)`` device
        tree and the loop-mode per-``ClientState`` memories), the
        straggler state (fused ring buffer / host pending list), the
        round counter and the history.  The cohort schedule, straggler
        delays and transform keys are pure functions of
        ``(config, round_idx)``, so restoring this dict into an
        identically-constructed engine (``load_state_dict``) resumes
        the trajectory BIT-IDENTICALLY to an uninterrupted run —
        pinned in tests/test_api_federation.py and
        examples/resume_demo.py.
        """
        host = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: np.asarray(jax.device_get(x)), t)
        return {
            "format": self.STATE_FORMAT,
            "exec_mode": self.exec_mode,
            "message": self.message,
            "round": self._round,
            "params": host(self.params),
            "server_state": host(self.server_state),
            "transform_state": {k: host(v)
                                for k, v in self._tstate.items()},
            "ring": host(self._ring) if self._ring is not None else None,
            "pending": [{"client": p.client,
                         "issued_round": p.issued_round,
                         "due_round": p.due_round,
                         "weight": p.weight,
                         "delta": host(p.delta)} for p in self.pending],
            "client_error_memory": [
                host(c.error_memory) if c.error_memory is not None
                else None for c in self.clients],
            "history": [dict(h) for h in self.history],
        }

    def load_state_dict(self, state: Mapping[str, Any]) -> None:
        """Restore a :meth:`state_dict` snapshot into this engine.

        The engine must be constructed with the same configuration the
        snapshot was taken under (same exec_mode/message at minimum —
        checked; the rest is the caller's resume contract, enforced
        spec-level by ``repro.api.Federation.load_state_dict``).
        """
        fmt = state.get("format")
        if fmt != self.STATE_FORMAT:
            raise ValueError(f"unsupported engine state format {fmt!r} "
                             f"(this build writes {self.STATE_FORMAT})")
        for key in ("exec_mode", "message"):
            if state.get(key) != getattr(self, key):
                raise ValueError(
                    f"snapshot was taken under {key}={state.get(key)!r} "
                    f"but this engine runs {key}={getattr(self, key)!r}; "
                    "rebuild the engine with the snapshot's "
                    "configuration")
        mems = state["client_error_memory"]
        if len(mems) != len(self.clients):
            raise ValueError(
                f"snapshot carries error memory for {len(mems)} clients "
                f"but this engine has {len(self.clients)}")
        dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)  # noqa: E731
        self._round = int(state["round"])
        self.params = dev(state["params"])
        self.server_state = dev(state["server_state"])
        self._tstate = {k: dev(v)
                        for k, v in state["transform_state"].items()}
        self._ring = dev(state["ring"]) if state["ring"] is not None \
            else None
        self.pending = [
            PendingUpdate(client=int(p["client"]),
                          issued_round=int(p["issued_round"]),
                          due_round=int(p["due_round"]),
                          delta=dev(p["delta"]),
                          weight=float(p["weight"]))
            for p in state["pending"]]
        for c, m in zip(self.clients, mems):
            c.error_memory = dev(m) if m is not None else None
        self.history = [dict(h) for h in state["history"]]
        if self._fused_sync is not None:
            self._own_state()

    # -- one round --------------------------------------------------------
    def round(self, seed: Optional[int] = None) -> Dict[str, float]:
        """Sample cohort -> local updates -> transforms -> staleness
        routing -> Eq. (2) combine -> server-optimizer update."""
        r = self._round
        round_key = jax.random.PRNGKey(seed if seed is not None else r)
        cohort = self.scheduler.select(r)
        if self.exec_mode == "vmap":
            rec = self._round_vmap(r, round_key, cohort)
        else:
            rec = self._round_loop(r, round_key, cohort)
        self.history.append(rec)
        self._round += 1
        return rec

    def fit(self, *, seed: int = 0, verbose: bool = False) -> Pytree:
        """Run ``fed.max_rounds`` rounds with the fixed per-round seed
        schedule (trajectory-comparable across presets/exec modes) and
        the Alg.-1 stopping criterion — only applied to rounds where an
        update landed."""
        for e in range(self.fed.max_rounds):
            rec = self.round(seed=seed * 100003 + e)
            if verbose and e % 10 == 0:
                print(f"[round {e:4d}] loss={rec['loss']:.4f} "
                      f"rel={rec['rel_change']:.2e} "
                      f"K={rec['participants']} "
                      f"arrived={rec['arrived']}")
            if self.stop_criterion(rec, self.fed.rel_tol):
                break
        return self.params
