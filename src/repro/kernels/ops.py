"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to what the platform allows: False on TPU
backends, True elsewhere, where the kernel body executes in Python for
validation.  Model code imports from here, never from the kernel modules.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# defined BEFORE the repro.core import below: core/__init__ -> engine
# reads this constant off the partially-initialized module when the
# import cycle is entered from the repro.kernels side
KERNEL_BACKENDS = ("xla", "pallas")

from repro.core.aggregation import (aggregate_stacked,  # noqa: E402
                                    topk_keep_mask)
from repro.kernels.fed_aggregate import (  # noqa: E402
    fed_dp_secure_apply_pallas, fed_topk_ef_pallas, fed_weighted_sum_pallas)
from repro.kernels.flash_attention import flash_attention_bhsd  # noqa: E402
from repro.kernels.ssd_scan import ssd_scan_pallas  # noqa: E402
from repro.kernels.topic_decoder import topic_decoder_pallas  # noqa: E402


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q (B,S,Hq,D), k/v (B,S,Hkv,D) -> (B,S,Hq,D)."""
    interpret = _auto_interpret() if interpret is None else interpret
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return jnp.moveaxis(out, 1, 2)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 128,
             interpret: bool | None = None):
    """x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N) -> (y, h_last)."""
    interpret = _auto_interpret() if interpret is None else interpret
    return ssd_scan_pallas(x, dt, a, b, c, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("block_b", "block_v", "interpret"))
def topic_decoder_loss(theta, beta, bow, dec_scale=None, *,
                       block_b: int = 128, block_v: int = 512,
                       interpret: bool | None = None):
    """Fused ProdLDA reconstruction loss, per document (B,)."""
    interpret = _auto_interpret() if interpret is None else interpret
    return topic_decoder_pallas(theta, beta, bow, dec_scale,
                                block_b=block_b, block_v=block_v,
                                interpret=interpret)


# ---------------------------------------------------------------------------
# Federation aggregation (Eq. (2) + transforms hot path).
#
# Every wrapper takes ``backend`` ("xla" | "pallas") as a STATIC argument;
# "xla" is the parity reference — its branches are byte-for-byte the
# expressions the engine ran before this module existed, so routing the
# fused graphs through here with the default backend changes nothing.
# These are called from inside the engine's jitted round functions, so no
# jit here except on the standalone-use paths exercised by tests/benches.
#
# Every wrapper also takes ``mesh`` (a ("data",)-axis jax Mesh, or None):
# with a mesh the reduction runs as a shard_map island — each device
# applies the SAME backend kernel to its K/N local cohort rows and the
# cross-device Eq. (2) reduction is one psum of the per-device partial
# numerators (DESIGN.md §5: per-device partials of the secure-mask stack
# stay on the dyadic grid, so the psum order cannot break cancellation).
# ``check_vma=False`` everywhere a pallas_call sits inside the island —
# jax has no varying-manual-axes rule for pallas_call.
# ---------------------------------------------------------------------------
def _check_backend(backend: str) -> None:
    if backend not in KERNEL_BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of "
            f"{KERNEL_BACKENDS}")


def _flat2(leaf):
    """Stacked leaf (K, ...) -> (K, D) without copying when already 2-D."""
    return leaf.reshape((leaf.shape[0], -1))


def _coef_matvec(c, leaf):
    """``sum_k c_k x_k`` of a stacked leaf as one matvec over its
    flattened rows (no ring-sized temporary)."""
    return (c @ _flat2(leaf).astype(jnp.float32)).reshape(leaf.shape[1:])


def _local_weighted_num(tree, w, backend: str, interpret: bool):
    """Per-leaf masked partial numerator ``sum_k w_k x_k`` over the rows
    this device holds (the single-device numerator when unsharded)."""
    if backend == "xla":
        def num(leaf):
            wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
            contrib = jnp.where(wb > 0.0, leaf.astype(jnp.float32), 0.0)
            return jnp.sum(wb * contrib, axis=0)
        return jax.tree_util.tree_map(num, tree)
    return jax.tree_util.tree_map(
        lambda leaf: fed_weighted_sum_pallas(
            _flat2(leaf), w, interpret=interpret).reshape(leaf.shape[1:]),
        tree)


def fed_weighted_combine(tree, weights, *, backend: str = "xla",
                         interpret: bool | None = None, mesh=None):
    """Eq. (2): per-leaf ``sum_k w_k x_k / max(sum w, 1e-12)`` over a
    stacked ``(K, ...)`` pytree, zero-weight rows masked out.

    With ``mesh`` the K axis is row-sharded: each device reduces its own
    rows with the selected backend kernel, then one ``psum`` over
    ``"data"`` forms the cross-device numerator and denominator — the
    replicated output is the same Eq. (2) mean up to fp32 summation
    order (bitwise for the secure-mask stack, which lives on the dyadic
    grid).
    """
    _check_backend(backend)
    if mesh is None:
        if backend == "xla":
            return aggregate_stacked(tree, weights)
        interpret = _auto_interpret() if interpret is None else interpret
        w = jnp.asarray(weights, jnp.float32)
        total = jnp.maximum(jnp.sum(w), 1e-12)

        def combine(leaf):
            num = fed_weighted_sum_pallas(_flat2(leaf), w,
                                          interpret=interpret)
            return (num / total).reshape(leaf.shape[1:])

        return jax.tree_util.tree_map(combine, tree)

    itp = _auto_interpret() if interpret is None else interpret

    def local(tree_l, w_l):
        w32 = jnp.asarray(w_l, jnp.float32)
        num = _local_weighted_num(tree_l, w32, backend, itp)
        num = jax.tree_util.tree_map(lambda x: jax.lax.psum(x, "data"), num)
        total = jnp.maximum(jax.lax.psum(jnp.sum(w32), "data"), 1e-12)
        return jax.tree_util.tree_map(lambda n: n / total, num)

    return jax.shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=P(), check_vma=False)(
                             tree, jnp.asarray(weights, jnp.float32))


def fed_weighted_sum(tree, coefs, *, backend: str = "xla",
                     interpret: bool | None = None, mesh=None):
    """NUMERATOR-only per-leaf ``sum_k c_k x_k`` over a stacked pytree —
    the ring buffer's staleness-discounted combine (denominator handled
    by the caller, which also folds in the fresh-cohort term).  With
    ``mesh``, per-device partial sums + one psum, as in
    :func:`fed_weighted_combine`."""
    _check_backend(backend)
    c = jnp.asarray(coefs, jnp.float32)
    if mesh is None:
        if backend == "xla":
            return jax.tree_util.tree_map(
                lambda leaf: _coef_matvec(c, leaf), tree)
        interpret = _auto_interpret() if interpret is None else interpret
        return jax.tree_util.tree_map(
            lambda leaf: fed_weighted_sum_pallas(
                _flat2(leaf), c,
                interpret=interpret).reshape(leaf.shape[1:]),
            tree)

    itp = _auto_interpret() if interpret is None else interpret

    def local(tree_l, c_l):
        if backend == "xla":
            num = jax.tree_util.tree_map(
                lambda leaf: _coef_matvec(c_l, leaf), tree_l)
        else:
            num = jax.tree_util.tree_map(
                lambda leaf: fed_weighted_sum_pallas(
                    _flat2(leaf), c_l,
                    interpret=itp).reshape(leaf.shape[1:]), tree_l)
        return jax.tree_util.tree_map(lambda x: jax.lax.psum(x, "data"),
                                      num)

    return jax.shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=P(), check_vma=False)(tree, c)


def fed_topk_ef(msgs, err_state, ids, *, frac: float, backend: str = "xla",
                interpret: bool | None = None, mesh=None):
    """Fused correct -> exactly-k top-k -> residual per cohort row.

    ``msgs``: stacked ``(K, ...)`` message pytree; ``err_state``: the
    ``(L, ...)`` error-memory pytree; ``ids``: ``(K,)`` int32 global
    client ids, pre-clipped to ``[0, L)``.  Per leaf,
    ``k_keep = max(int(frac * row_size), 1)``.  Returns
    ``(sent, new_err)`` pytrees of ``(K, ...)`` fp32 rows; scattering
    ``new_err`` back into the ``(L, ...)`` state (padded rows dropped)
    stays with the caller.

    The cohort's error rows are gathered before the per-row math (with
    ``mesh``, K and L both row-sharded over ``"data"``, GSPMD lowers
    ``err[ids]`` into the cross-shard collective and each device then
    runs the per-row correct/top-k/residual on its own rows).  Same
    math everywhere: ``corrected = msg + err[ids]`` row by row, no
    cross-row term anywhere.
    """
    _check_backend(backend)
    ids = jnp.asarray(ids, jnp.int32)
    itp = _auto_interpret() if interpret is None else interpret
    gathered = jax.tree_util.tree_map(lambda e: e[ids], err_state)

    def rows(msgs_l, err_l):
        def one_leaf(m, e):
            m2, e2 = _flat2(m), _flat2(e)
            k_keep = max(int(frac * m2.shape[1]), 1)
            if backend == "xla":
                corrected = m2.astype(jnp.float32) + e2.astype(jnp.float32)
                mask = topk_keep_mask(jnp.abs(corrected), k_keep)
                sent = jnp.where(mask, corrected, 0.0)
                new_err = corrected - sent
            else:
                sent, new_err = fed_topk_ef_pallas(m2, e2, k_keep=k_keep,
                                                   interpret=itp)
            return sent.reshape(m.shape), new_err.reshape(m.shape)

        pairs = jax.tree_util.tree_map(one_leaf, msgs_l, err_l)
        is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
        return (jax.tree_util.tree_map(lambda p: p[0], pairs,
                                       is_leaf=is_pair),
                jax.tree_util.tree_map(lambda p: p[1], pairs,
                                       is_leaf=is_pair))

    if mesh is None:
        return rows(msgs, gathered)
    return jax.shard_map(rows, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P("data")),
                         check_vma=False)(msgs, gathered)


def fed_dp_secure_apply(tree, *, noise=None, masks=None, clip_coef=None,
                        weights=None, noise_scale: float = 0.0,
                        backend: str = "xla",
                        interpret: bool | None = None, mesh=None):
    """Per-leaf ``x * clip_coef + noise_scale * noise + mask / max(w,1e-9)``
    over stacked ``(K, ...)`` pytrees, terms present only when given.
    ``dp`` passes (noise, clip_coef); ``secure`` passes (masks, weights).

    Strictly per-row, so the ``mesh`` path is an embarrassingly-parallel
    shard_map island: every operand row-sharded over ``"data"``, no
    collectives — each device's kernel output is bitwise the rows the
    single-device kernel would produce."""
    _check_backend(backend)
    if mesh is not None:
        packed = {"x": tree}
        if noise is not None:
            packed["noise"] = noise
        if masks is not None:
            packed["masks"] = masks
        if clip_coef is not None:
            packed["clip_coef"] = jnp.asarray(clip_coef, jnp.float32)
        if weights is not None:
            packed["weights"] = jnp.asarray(weights, jnp.float32)

        def local(p):
            return fed_dp_secure_apply(
                p["x"], noise=p.get("noise"), masks=p.get("masks"),
                clip_coef=p.get("clip_coef"), weights=p.get("weights"),
                noise_scale=noise_scale, backend=backend,
                interpret=interpret, mesh=None)

        specs = {k: P("data") for k in packed}
        return jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                             out_specs=P("data"), check_vma=False)(packed)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    noise_leaves = (jax.tree_util.tree_leaves(noise) if noise is not None
                    else [None] * len(leaves))
    mask_leaves = (jax.tree_util.tree_leaves(masks) if masks is not None
                   else [None] * len(leaves))

    def one_leaf(leaf, nz, mk):
        x2 = _flat2(leaf)
        if backend == "xla":
            out = x2.astype(jnp.float32)
            if clip_coef is not None:
                out = out * jnp.asarray(clip_coef, jnp.float32)[:, None]
            if nz is not None:
                out = out + noise_scale * _flat2(nz).astype(jnp.float32)
            if mk is not None:
                w = jnp.maximum(jnp.asarray(weights, jnp.float32), 1e-9)
                out = out + _flat2(mk).astype(jnp.float32) / w[:, None]
        else:
            itp = _auto_interpret() if interpret is None else interpret
            out = fed_dp_secure_apply_pallas(
                x2, noise=None if nz is None else _flat2(nz),
                masks=None if mk is None else _flat2(mk),
                clip_coef=clip_coef, weights=weights,
                noise_scale=noise_scale, interpret=itp)
        return out.reshape(leaf.shape)

    return jax.tree_util.tree_unflatten(
        treedef, [one_leaf(l, n, m)
                  for l, n, m in zip(leaves, noise_leaves, mask_leaves)])
