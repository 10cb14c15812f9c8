"""Plain reference of what the timed paths compute, and the weights.

Nothing here imports the program.  Each function is the straightforward
``jax.numpy`` form of what the configuration states, run in float32 at
``highest`` matmul precision (``dtype=jnp.bfloat16`` gives the control:
the same arithmetic one precision lower):

* :func:`init_params` — the benchmark's own ProdLDA weights from a seed
  (fan-in truncated normal, the AVITM layout with learned priors), made
  on the device in one jitted call and handed to the program;
* :func:`elbo` — ProdLDA's negative ELBO (AVITM, arXiv:1703.01488):
  softplus encoder, dropout, logistic-normal reparametrization, product
  of experts decoder, KL to the learned Laplace prior;
* :func:`sync_rounds` — the cross-device rounds: uniform K-of-L cohort,
  E local SGD steps per client on minibatches without replacement, local
  DP (clip to the global norm, Gaussian noise), top-k with error
  feedback (the k largest bf16-rounded magnitudes per leaf, ties to the
  lower index), the Eq. (2) weighted mean and FedAvg's server step;
* :func:`fedbuff_fold` / :func:`posterior` — the buffered-async service:
  each aggregation is the staleness-discounted (1/sqrt(1+age)) Eq. (2)
  mean of the buffered deltas added to the model; infer returns the
  posterior mean topic mixture softmax(mu).

The seed schedule is the one the configuration's program documents
(round key ``PRNGKey(seed * 100003 + round)``, client key
``fold_in(round_key, id)``, epoch keys, the dp key ``fold_in(client_key,
7)``), so the reference draws the same minibatches and noise.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

tmap = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def init_params(seed: int, vocab: int, topics: int, hidden: Sequence[int]):
    """ProdLDA weights (float32, on the default device) from ``seed``."""
    hidden = tuple(int(h) for h in hidden)

    def make(key):
        dims = (vocab,) + hidden
        keys = jax.random.split(key, len(dims) + 3)

        def dense(k, shape):
            return shape[0] ** -0.5 * jax.random.truncated_normal(
                k, -2.0, 2.0, shape, jnp.float32)
        enc = [{"w": dense(keys[i], (a, b)), "b": jnp.zeros((b,))}
               for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]
        h, k = dims[-1], topics
        a = 1.0 / k                     # Dirichlet(1/K) prior, as AVITM
        var0 = (1.0 / a) * (1.0 - 2.0 / k) + 1.0 / (a * k)
        return {"encoder": enc,
                "mu_head": {"w": dense(keys[-3], (h, k)), "b": jnp.zeros(k)},
                "lv_head": {"w": dense(keys[-2], (h, k)), "b": jnp.zeros(k)},
                "beta": dense(keys[-1], (k, vocab)),
                "mu_scale": jnp.ones(k), "lv_scale": jnp.ones(k),
                "dec_scale": jnp.ones(vocab),
                "prior_mu": jnp.zeros(k),
                "prior_logvar": jnp.full((k,), math.log(var0), jnp.float32)}
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def elbo(params, bow, rng, dropout: float, train: bool = True):
    """Per-document negative ELBO (B,) of ProdLDA; ``rng`` is the batch's
    (2,) key, split into the dropout and the reparametrization key."""
    h = bow
    for layer in params["encoder"]:
        h = _softplus(jnp.dot(h, layer["w"]) + layer["b"])
    d_rng, s_rng = jax.random.split(rng)
    if train and dropout > 0:
        keep = jax.random.bernoulli(d_rng, 1 - dropout, h.shape)
        h = h * keep.astype(h.dtype) / jnp.asarray(1 - dropout, h.dtype)
    mu = (jnp.dot(h, params["mu_head"]["w"]) + params["mu_head"]["b"]) \
        * params["mu_scale"]
    lv = (jnp.dot(h, params["lv_head"]["w"]) + params["lv_head"]["b"]) \
        * params["lv_scale"]
    z = mu
    if train:
        eps = jax.random.normal(s_rng, mu.shape).astype(mu.dtype)
        z = mu + jnp.exp(0.5 * lv) * eps
    theta = jax.nn.softmax(z, axis=-1)
    logits = jnp.dot(theta, params["beta"]) * params["dec_scale"]
    log_recon = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    recon = -jnp.sum(bow * log_recon, axis=-1)
    pm, plv = params["prior_mu"], params["prior_logvar"]
    kl = 0.5 * jnp.sum(jnp.exp(lv - plv) + (mu - pm) ** 2 / jnp.exp(plv)
                       - 1.0 + (plv - lv), axis=-1)
    return recon + kl


def posterior(params, bow):
    """Posterior-mean topic mixture softmax(mu) (B, K)."""
    h = bow
    for layer in params["encoder"]:
        h = _softplus(jnp.dot(h, layer["w"]) + layer["b"])
    mu = (jnp.dot(h, params["mu_head"]["w"]) + params["mu_head"]["b"]) \
        * params["mu_scale"]
    return jax.nn.softmax(mu, axis=-1)


def local_update(params, bows, rngs, lr: float, dropout: float):
    """E plain SGD steps on the mean loss; returns (delta, losses (E,))."""
    def loss(p, b, r):
        return jnp.mean(elbo(p, b, r, dropout))
    grad = jax.value_and_grad(loss)

    def step(p, xs):
        b, r = xs
        l, g = grad(p, b, r)
        return tmap(lambda a, d: a - lr * d, p, g), l
    local, losses = jax.lax.scan(step, params, (bows, rngs))
    return tmap(lambda a, b: b - a, params, local), losses


# ---------------------------------------------------------------------------
# message transforms
# ---------------------------------------------------------------------------
def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


def dp(delta, key, clip: float, mult: float):
    """Clip to global norm ``clip``, add N(0, (mult*clip)^2) per entry."""
    scale = jnp.minimum(1.0, clip / jnp.maximum(global_norm(delta), 1e-12))
    leaves, treedef = jax.tree_util.tree_flatten(delta)
    keys = jax.random.split(key, len(leaves))
    out = [x * scale.astype(x.dtype) + jnp.asarray(mult * clip, x.dtype)
           * jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
           for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def topk_rows(x, frac: float):
    """Keep the k = max(int(frac*n), 1) entries of each row of ``x``
    (B, n) with the largest bf16-rounded magnitude, ties to the lower
    index; return (sent, residual)."""
    n = x.shape[1]
    k = max(int(frac * n), 1)
    key = jnp.abs(x).astype(jnp.bfloat16).astype(jnp.float32)
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), x.shape)
    sk, si = jax.lax.sort((-key, idx), dimension=1, num_keys=2)
    tk, ti = -sk[:, k - 1:k], si[:, k - 1:k]
    keep = (key > tk) | ((key == tk) & (idx <= ti))
    sent = jnp.where(keep, x, jnp.zeros_like(x))
    return sent, x - sent


def topk_ef(msg, err, frac: float):
    """Error-corrected top-k of a (B, ...) stacked tree."""
    corrected = tmap(lambda m, e: m + e, msg, err)
    pairs = tmap(lambda c: topk_rows(c.reshape(c.shape[0], -1), frac),
                 corrected)
    is_pair = lambda p: isinstance(p, tuple)  # noqa: E731
    sent = tmap(lambda p, c: p[0].reshape(c.shape), pairs, corrected,
                is_leaf=is_pair)
    resid = tmap(lambda p, c: p[1].reshape(c.shape), pairs, corrected,
                 is_leaf=is_pair)
    return sent, resid


# ---------------------------------------------------------------------------
# the synchronous cross-device rounds
# ---------------------------------------------------------------------------
def cohort(num_clients: int, k: int, seed: int, r: int) -> np.ndarray:
    """Uniform K-of-L draw of round r, sorted."""
    if k >= num_clients:
        return np.arange(num_clients)
    rng = np.random.default_rng([seed, r])
    return np.sort(rng.choice(num_clients, k, replace=False))


def _epoch_keys(client_key, epochs: int):
    return [client_key if s == 0 else jax.random.fold_in(client_key, s + 1)
            for s in range(epochs)]


def draws(round_key, ids, num_docs: int, batch: int, epochs: int):
    """(idx (B, E, n), model keys (B, E, 2)) of the cohort's minibatches."""
    n = min(batch, num_docs)
    idx, keys = [], []
    for cid in ids:
        ck = jax.random.fold_in(round_key, int(cid))
        row_i, row_k = [], []
        for ek in _epoch_keys(ck, epochs):
            row_i.append(jax.random.choice(ek, num_docs, (n,),
                                           replace=False))
            row_k.append(jax.random.fold_in(ek, 1))
        idx.append(jnp.stack(row_i))
        keys.append(jnp.stack(row_k))
    return np.asarray(jnp.stack(idx)), jnp.stack(keys)


def _block_fn(cfg: Dict[str, Any], dtype):
    lr, drop = cfg["lr"], cfg["dropout"]
    clip, mult, frac = cfg["dp_clip_norm"], cfg["dp_noise_multiplier"], \
        cfg["topk"]

    def block(params, bows, rngs, dp_keys, err):
        p = tmap(lambda x: x.astype(dtype), params)
        delta, losses = jax.vmap(
            lambda b, r: local_update(p, b.astype(dtype), r, lr, drop))(
                bows, rngs)
        raw = tmap(lambda d: jnp.sum(d.astype(jnp.float32), 0), delta)
        msg = jax.vmap(lambda d, k: dp(d, k, clip, mult))(delta, dp_keys)
        sent, resid = topk_ef(msg, tmap(lambda e: e.astype(dtype), err),
                              frac)
        return (tmap(lambda s: s.astype(jnp.float32), sent),
                tmap(lambda e: e.astype(jnp.float32), resid),
                losses.astype(jnp.float32), raw)
    return jax.jit(block)


def sync_rounds(params0, node_bows: List[np.ndarray], cfg: Dict[str, Any],
                seed: int, rounds: int, *, dtype=jnp.float32,
                block: int = 32):
    """Run ``rounds`` rounds from ``params0``; return the params after
    each round, each round's loss, and the norm of each leaf of round 1's
    mean plain local delta (the gradient the check's leaf rule reads)."""
    L, K, E, P = cfg["num_clients"], cfg["clients_per_round"], \
        cfg["local_epochs"], cfg["batch"]
    num_docs = len(node_bows[0])
    fn = _block_fn(cfg, dtype)
    zeros = tmap(lambda x: jnp.zeros_like(x, jnp.float32), params0)
    memory: Dict[int, Any] = {}
    params = tmap(lambda x: x.astype(jnp.float32), params0)
    out_params, out_loss, grad_norms = [], [], None
    prec = "highest" if dtype == jnp.float32 else None
    for r in range(rounds):
        rk = jax.random.PRNGKey(seed * 100003 + r)
        ids = cohort(L, K, seed, r)
        idx, keys = draws(rk, ids, num_docs, P, E)
        n = idx.shape[2]
        acc = tmap(jnp.zeros_like, zeros)
        raw_acc = tmap(jnp.zeros_like, zeros)
        losses = []
        for i in range(0, len(ids), block):
            b_ids = ids[i:i + block]
            bows = np.stack([node_bows[c][idx[i + j]]
                             for j, c in enumerate(b_ids)])
            dpk = jnp.stack([jax.random.fold_in(
                jax.random.fold_in(rk, int(c)), 7) for c in b_ids])
            err = _stack_memory(memory, b_ids, zeros)
            with jax.default_matmul_precision(prec):
                sent, resid, l, raw = fn(params, jnp.asarray(bows),
                                         keys[i:i + block], dpk, err)
            w = float(E * n)
            acc = tmap(lambda a, s: a + w * jnp.sum(s, 0), acc, sent)
            raw_acc = tmap(lambda a, s: a + s, raw_acc, raw)
            for j, c in enumerate(b_ids):
                memory[int(c)] = tmap(lambda e, j=j: e[j], resid)
            losses.append(np.asarray(l))
        total = float(E * n * len(ids))
        bar = tmap(lambda a: a / total, acc)
        params = tmap(lambda p, d: p + d, params, bar)
        out_params.append(tmap(np.asarray, params))
        # each client's mean over its epochs, then the weighted mean
        out_loss.append(float(np.concatenate(losses).mean(1).mean()))
        if grad_norms is None:
            grad_norms = tmap(lambda a: float(jnp.linalg.norm(
                a.reshape(-1)) / len(ids)), raw_acc)
    return out_params, out_loss, grad_norms


def _stack_memory(memory, ids, zeros):
    rows = [memory.get(int(c), zeros) for c in ids]
    return tmap(lambda *xs: jnp.stack(xs), *rows)


# ---------------------------------------------------------------------------
# the buffered-async service
# ---------------------------------------------------------------------------
def fedbuff_fold(params0, aggregations: List[List[Dict[str, Any]]],
                 pool: List[Any], *, dtype=jnp.float32):
    """The model after each aggregation.  ``aggregations[v]`` lists the
    buffered deltas folded at version v -> v+1: ``{"pool", "weight",
    "age"}``; ``pool[i]`` is delta i of the upload pool."""
    params = tmap(lambda x: jnp.asarray(x, jnp.float32), params0)
    versions = [tmap(np.asarray, params)]

    @jax.jit
    def fold(p, deltas, weights, ages):
        disc = jax.lax.rsqrt(1.0 + ages.astype(dtype))
        w = weights.astype(dtype)
        bar = tmap(lambda d: jnp.tensordot(w * disc, d.astype(dtype), 1)
                   / jnp.sum(w), deltas)
        return tmap(lambda a, b: (a.astype(dtype) + b).astype(jnp.float32),
                    p, bar)
    for agg in aggregations:
        deltas = tmap(lambda *xs: jnp.stack(xs), *[pool[a["pool"]]
                                                   for a in agg])
        params = fold(params, deltas,
                      jnp.asarray([a["weight"] for a in agg], jnp.float32),
                      jnp.asarray([a["age"] for a in agg], jnp.float32))
        versions.append(tmap(np.asarray, params))
    return versions
