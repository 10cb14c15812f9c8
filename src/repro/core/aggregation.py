"""Gradient aggregation: paper Eq. (2) plus the beyond-paper extensions.

Two execution flavors of the same math:
  * host-side (``aggregate_host``) — explicit list-of-client-grads, used by
    the Algorithm-1-faithful ``FederatedTrainer`` that runs the NTM
    experiments (one process simulating L nodes + server);
  * in-graph (``aggregate_psum``) — ``jax.lax.psum`` over the mesh client
    axis inside ``shard_map``, used by ``federated_train_step`` for the
    production architectures.  On TPU the ICI all-reduce IS the server
    rendezvous (DESIGN.md §2).

Beyond-paper (each is an EXPERIMENTS.md §Perf / privacy feature, all
composable with Eq. (2)):
  * secure aggregation — pairwise antisymmetric PRG masks that cancel in
    the sum: the server (or the wire) only ever sees masked gradients;
  * top-k sparsification with error feedback — collective-bytes reduction;
  * local differential privacy — per-client clip + Gaussian noise
    [Wang et al. 2020 ref 25].
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.optim.optimizers import clip_by_global_norm

Pytree = Any


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


# ---------------------------------------------------------------------------
# Eq. (2): weighted average
# ---------------------------------------------------------------------------
def aggregate_host(grads: Sequence[Pytree],
                   weights: Sequence[float]) -> Pytree:
    """G = sum_l n_l G_l / sum_l n_l  over an explicit client list."""
    w = jnp.asarray(weights, jnp.float32)
    total = jnp.sum(w)

    def combine(*gs):
        acc = sum(wi * g.astype(jnp.float32) for wi, g in zip(w, gs))
        return acc / total

    return _tmap(combine, *grads)


def aggregate_stacked(tree: Pytree, weights) -> Pytree:
    """In-graph Eq. (2) over a stacked leading client axis.

    Every leaf is ``(K, ...)`` — one slice per cohort member — and
    ``weights`` is ``(K,)``.  Used by the vmap execution path
    (``core/engine.py``): the per-client deltas/grads never leave the
    device, the weighted mean happens inside the same jitted graph that
    produced them.

    Zero-weight rows are ABSENT, not merely down-weighted: their values
    are ``where``-masked out before the multiply, so the fixed-K padding
    rows of DESIGN.md §4 — whose local-update output on an all-zero
    batch is unconstrained garbage, possibly non-finite — can never
    poison the sum (``0 * nan`` is ``nan``; ``where`` is not), and the
    result matches ``aggregate_host`` over the positive-weight
    survivors.  An ALL-zero weight vector yields a zero combine (guarded
    denominator), never 0/0 — callers gate the server update on
    ``weights.sum() > 0``.
    """
    w = jnp.asarray(weights, jnp.float32)
    total = jnp.maximum(jnp.sum(w), 1e-12)

    def combine(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        contrib = jnp.where(wb > 0.0, leaf.astype(jnp.float32), 0.0)
        return jnp.sum(wb * contrib, axis=0) / total

    return _tmap(combine, tree)


def aggregate_psum(grad: Pytree, n_samples, axis_name) -> Pytree:
    """In-graph Eq. (2): every client holds its local grad and sample count;
    returns the identical weighted average on all clients."""
    n = jnp.asarray(n_samples, jnp.float32)
    total = jax.lax.psum(n, axis_name)
    return _tmap(
        lambda g: jax.lax.psum(n * g.astype(jnp.float32), axis_name) / total,
        grad)


# ---------------------------------------------------------------------------
# secure aggregation (pairwise antisymmetric masks)
# ---------------------------------------------------------------------------
def pairwise_mask(tree: Pytree, round_key, client: int,
                  num_clients: int, scale: float = 1.0) -> Pytree:
    """Mask for one client such that the sum over clients is exactly zero.

    mask_l = sum_{m>l} PRG(l,m) - sum_{m<l} PRG(m,l):  every pair (l,m)
    contributes +PRG to one side and -PRG to the other, so psum cancels.
    The PRG seed folds in (round, min, max) — both parties can derive it
    from a shared secret without revealing gradients to the server.
    """
    client = jnp.asarray(client)   # may be a traced axis_index

    def mask_leaf(path_idx, leaf):
        total = jnp.zeros_like(leaf, jnp.float32)
        for other in range(num_clients):
            lo = jnp.minimum(client, other)
            hi = jnp.maximum(client, other)
            k = jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(round_key, lo), hi), path_idx)
            noise = scale * jax.random.normal(k, leaf.shape, jnp.float32)
            sign = jnp.where(client < other, 1.0,
                             jnp.where(client > other, -1.0, 0.0))
            total = total + sign * noise
        return total

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    masked = [mask_leaf(i, l) for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, masked)


def secure_mask_grads(grads: Pytree, round_key, client: int,
                      num_clients: int, n_samples,
                      scale: float = 1.0) -> Pytree:
    """Apply the cancelling mask to the Eq. (2) numerator contribution.

    Masks must be added to ``n_l * G_l`` (the summed quantity), so the
    caller passes the already-weighted gradient... to keep call sites
    simple we mask g and divide the mask by n_l, which is equivalent.
    """
    mask = pairwise_mask(grads, round_key, client, num_clients, scale)
    n = jnp.maximum(jnp.asarray(n_samples, jnp.float32), 1e-9)
    return _tmap(lambda g, m: g + m / n, grads, mask)


# ---------------------------------------------------------------------------
# top-k sparsification + error feedback
# ---------------------------------------------------------------------------
def bf16_magnitude_key(x):
    """int32 rank key of ``|x|`` rounded to the bf16 grid.

    The bits of a non-negative float32 order like the float itself, so
    rounding them to the upper 16 (round-to-nearest-even, the rounding
    of an fp32 -> bf16 cast) gives an integer key in ``[0, 0x8000]``
    whose order is the bf16-quantized magnitude's.  Integer arithmetic
    keeps the quantization exact under any compiler: no backend may
    elide it as excess float precision.
    """
    bits = jax.lax.bitcast_convert_type(
        jnp.abs(x.astype(jnp.float32)), jnp.int32)
    q = bits >> 16
    rem = bits & 0xFFFF
    up = (rem > 0x8000) | ((rem == 0x8000) & ((q & 1) == 1))
    return q + up.astype(jnp.int32)


_KEY_END = 0x8001      # one past the largest bf16_magnitude_key


def topk_keep_from_keys(key, idx, k: int, n: int, count):
    """Mask of the ``k`` largest ``key`` entries, ties toward lower ``idx``.

    ``count(pred)`` sums a boolean array over the selection axes with
    kept dims; ``idx`` is each entry's position (``0 <= idx < n``).
    Entries with a negative key are padding: never counted, never kept.
    Two bisections replace a sort, so the rule lowers anywhere a
    compare and a sum do (XLA and the Pallas kernel alike):

    * the threshold ``t`` is the largest key with ``count(key >= t) >=
      k`` — the k-th largest key;
    * of the entries tied at ``t``, the first ``k - count(key > t)`` by
      index are kept: ``j`` is the smallest bound with that many ties
      below it.
    """
    total = count(key >= 0)
    shape = total.shape
    need_k = jnp.asarray(k, total.dtype)

    def key_step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        ok = count(key >= mid) >= need_k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo = jnp.zeros(shape, jnp.int32)
    t, _ = jax.lax.fori_loop(0, _KEY_END.bit_length(), key_step,
                             (lo, jnp.full(shape, _KEY_END, jnp.int32)))
    greater = key > t
    tie = key == t
    need = need_k - count(greater)

    def idx_step(_, lohi):
        lo_j, hi_j = lohi
        mid = (lo_j + hi_j) >> 1
        ok = count(tie & (idx < mid)) >= need
        return jnp.where(ok, lo_j, mid), jnp.where(ok, mid, hi_j)

    _, j = jax.lax.fori_loop(0, max(int(n), 1).bit_length(), idx_step,
                             (lo, jnp.full(shape, n, jnp.int32)))
    return greater | (tie & (idx < j))


def topk_keep_mask(mag, k: int):
    """Boolean mask keeping EXACTLY the ``k`` largest entries of the last
    axis, ranked on bf16-QUANTIZED magnitude with ties broken
    deterministically toward the LOWER index.

    The naive ``mag >= top_k(mag, k)[-1]`` selection is a knife edge: it
    keeps every entry tied with the threshold (count > k at ties), and a
    ~1e-7 reduction-order difference between execution paths flips the
    threshold-sitting coordinate itself in and out of the kept set.  Two
    ingredients remove both failure modes:

    * lexicographic (magnitude desc, index asc) ranking keeps exactly
      ``k`` entries and resolves EXACT ties identically on every path;
    * ranking on the bf16 rounding of ``mag``
      (:func:`bf16_magnitude_key`) collapses NEAR-ties — coordinates
      whose fp32 magnitudes differ by less than the ~2^-8 relative bf16
      grid — into exact ties.

    A perturbation that pushes a magnitude across a bf16 grid boundary
    still flips the choice, so paths held to one trajectory must hand
    this function bitwise-equal inputs (the engine's loop and vmap
    paths run one local-update function for that reason).  The
    quantization affects only WHICH coordinates are kept among
    near-equals (immaterial under error feedback — the residual of a
    skipped coordinate transmits next round); kept values are sent at
    full precision.  Shared by :func:`topk_sparsify` and the fused
    Pallas kernel (``kernels/fed_aggregate.py``) — one selection rule,
    every backend; ``kernels/ref.py`` holds a sort-based oracle.
    """
    key = bf16_magnitude_key(mag)
    n = mag.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, mag.shape, mag.ndim - 1)

    def count(pred):
        return jnp.sum(pred, axis=-1, keepdims=True, dtype=jnp.int32)

    return topk_keep_from_keys(key, idx, k, n, count)


def topk_sparsify(tree: Pytree, frac: float) -> Pytree:
    """Keep the top ``frac`` fraction (by magnitude) of each leaf,
    exactly ``max(int(frac * size), 1)`` entries per leaf (deterministic
    index tie-breaking, :func:`topk_keep_mask`)."""
    def spars(leaf):
        flat = leaf.reshape(-1)
        k = max(int(frac * flat.size), 1)
        mask = topk_keep_mask(jnp.abs(flat), k).reshape(leaf.shape)
        return jnp.where(mask, leaf, 0.0)
    return _tmap(spars, tree)


def compress_with_error_feedback(grads: Pytree, error: Optional[Pytree],
                                 frac: float) -> Tuple[Pytree, Pytree]:
    """(compressed grad, new error memory).  error may be None (round 0)."""
    if error is None:
        error = _tmap(lambda g: jnp.zeros_like(g, jnp.float32), grads)
    corrected = _tmap(lambda g, e: g.astype(jnp.float32) + e, grads, error)
    sent = topk_sparsify(corrected, frac)
    new_error = _tmap(lambda c, s: c - s, corrected, sent)
    return sent, new_error


# ---------------------------------------------------------------------------
# server optimizers (round engine, DESIGN.md §3)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ServerOptimizer:
    """Server-side update rule applied to the aggregated client delta.

    ``apply(params, delta_bar, state, round_idx) -> (new_params, state)``
    where ``delta_bar`` is the Eq.-(2)-weighted average of the per-client
    parameter deltas (W_l - W).  Sign convention: deltas point in the
    descent direction already, so every rule ADDS its step.
    [Reddi et al. 2021, Adaptive Federated Optimization]
    """
    name: str
    init: Callable[[Pytree], Any]
    apply: Callable[..., Tuple[Pytree, Any]]


def fedavg_server(server_lr: float = 1.0) -> ServerOptimizer:
    """W <- W + eta_s * delta_bar.  With eta_s=1, E=1 local step and full
    participation this IS the paper's Eq. (3) server SGD update."""
    def init(params):
        return {}

    def apply(params, delta, state, round_idx=0):
        new = _tmap(lambda p, d: p + server_lr * d.astype(p.dtype),
                    params, delta)
        return new, state

    return ServerOptimizer("fedavg", init, apply)


def fedavgm_server(server_lr: float = 1.0,
                   momentum: float = 0.9) -> ServerOptimizer:
    """Server momentum: m <- beta m + delta_bar; W <- W + eta_s m."""
    def init(params):
        return {"m": _tmap(
            lambda p: jnp.zeros_like(p, jnp.float32), params)}

    def apply(params, delta, state, round_idx=0):
        m = _tmap(lambda m_, d: momentum * m_ + d.astype(jnp.float32),
                  state["m"], delta)
        new = _tmap(lambda p, m_: p + server_lr * m_.astype(p.dtype),
                    params, m)
        return new, {"m": m}

    return ServerOptimizer("fedavgm", init, apply)


def fedadam_server(server_lr: float = 1e-2, b1: float = 0.9,
                   b2: float = 0.999, eps: float = 1e-3) -> ServerOptimizer:
    """FedAdam [Reddi et al. 2021]: Adam on the server pseudo-gradient
    (no bias correction, per the paper's Algorithm 2; ``eps`` = tau)."""
    def init(params):
        z = _tmap(lambda p: jnp.zeros_like(p, jnp.float32), params)
        return {"m": z, "v": _tmap(jnp.zeros_like, z)}

    def apply(params, delta, state, round_idx=0):
        m = _tmap(lambda m_, d: b1 * m_ + (1 - b1)
                  * d.astype(jnp.float32), state["m"], delta)
        v = _tmap(lambda v_, d: b2 * v_ + (1 - b2)
                  * jnp.square(d.astype(jnp.float32)),
                  state["v"], delta)
        new = _tmap(
            lambda p, m_, v_: p + (server_lr * m_
                                   / (jnp.sqrt(v_) + eps)).astype(p.dtype),
            params, m, v)
        return new, {"m": m, "v": v}

    return ServerOptimizer("fedadam", init, apply)


SERVER_OPTIMIZERS: Dict[str, Callable[..., ServerOptimizer]] = {
    "fedavg": fedavg_server,
    "fedavgm": fedavgm_server,
    "fedadam": fedadam_server,
}


def get_server_optimizer(name: str, **kw) -> ServerOptimizer:
    """Registry lookup; kwargs are forwarded to the factory."""
    if name not in SERVER_OPTIMIZERS:
        raise KeyError(f"unknown server optimizer {name!r}; "
                       f"available: {sorted(SERVER_OPTIMIZERS)}")
    return SERVER_OPTIMIZERS[name](**kw)


# ---------------------------------------------------------------------------
# local differential privacy
# ---------------------------------------------------------------------------
def dp_privatize(grads: Pytree, key, *, clip_norm: float,
                 noise_multiplier: float) -> Pytree:
    """Per-client clip to ``clip_norm`` + Gaussian noise (local DP)."""
    clipped, _ = clip_by_global_norm(grads, clip_norm)
    if noise_multiplier <= 0:
        return clipped
    leaves, treedef = jax.tree_util.tree_flatten(clipped)
    keys = jax.random.split(key, len(leaves))
    noisy = [l + noise_multiplier * clip_norm
             * jax.random.normal(k, l.shape, jnp.float32)
             for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, noisy)
