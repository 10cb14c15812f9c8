"""Pallas TPU kernels for the federation transform→combine hot path.

Three stages of a fused round fuse into one kernel each (house idiom:
``topic_decoder.py`` / ``ssd_scan.py``; oracles in ``ref.py``; jit'd
public wrappers in ``ops.py`` — model/engine code never imports this
module directly):

  * :func:`fed_weighted_sum_pallas` — the Eq. (2) NUMERATOR
    ``sum_k w_k * x_k`` with zero-weight padded rows ``where``-masked
    IN-KERNEL (their values may be non-finite local-update garbage) and
    fp32 accumulation regardless of message dtype (the bf16-deltas /
    fp32-accumulate mixed-precision contract).  Grid
    ``(d_blocks, k_blocks)``, K innermost/sequential, the ``(1, bd)``
    output block resident across the K sweep as the accumulator.  The
    division by ``max(sum w, 1e-12)`` stays in the wrapper so the kernel
    also serves the ring buffer's coefficient combine (numerator with
    staleness-discounted weights).
  * :func:`fed_topk_ef_pallas` — fused correct → top-k select →
    residual, one cohort row per grid step.  Each row is laid out as
    ``(rows, 128)`` lane-dense tiles and the selection runs in VMEM as
    ``aggregation.topk_keep_from_keys`` — the SAME count-based bisection
    over bf16 magnitude keys the XLA paths run (``topk_keep_mask``), so
    every backend keeps identical coordinates.
  * :func:`fed_dp_secure_apply_pallas` — one elementwise pass computing
    ``x * clip_coef + noise_scale * noise + mask / max(w, 1e-9)`` with
    each term statically gated (absent operands are not passed at all),
    replacing the 3-kernel XLA chain.  The expressions are literally the
    XLA transforms': the clip and secure-mask terms come out
    BIT-identical to the XLA path; only the ``noise_scale * noise`` add
    may drift ≤ 2 ulp when the compiler contracts it into an fma
    (immaterial for random dp noise, far inside the 1e-5 parity budget).
    The dyadic-grid secure-mask cancellation guarantee is untouched: the
    masks themselves are generated outside, and ``sum_l mask_l == 0.0``
    stays bitwise under ANY in-kernel summation order (DESIGN.md)
    because every partial sum of grid-integers stays exact in fp32.

Block shapes follow the TPU tiling rule (the last two block dims are
multiples of (8, 128), or the whole array dim); per-row vectors travel
as ``(K, 1)`` columns.  Grids use ``cdiv`` with a partial last block
wherever the math is column-independent, so no operand is padded on
that axis.  ``interpret`` is required: the ``ops.py`` wrappers pick it
from the platform, tests pass it explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregation import bf16_magnitude_key, topk_keep_from_keys

LANES = 128


def _pad_axis(x, mult: int, axis: int):
    size = x.shape[axis]
    pad = -(-size // mult) * mult - size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# (a) Eq. (2) weighted sum / combine
# ---------------------------------------------------------------------------
def _weighted_sum_kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)              # (bk, 1)
    x = x_ref[...].astype(jnp.float32)              # (bk, bd)
    # zero-weight rows are ABSENT, not down-weighted: padded cohort rows
    # may hold non-finite garbage and 0 * nan is nan; where is not
    contrib = jnp.where(w > 0.0, x, 0.0)
    o_ref[...] += jnp.sum(w * contrib, axis=0, keepdims=True)


def fed_weighted_sum_pallas(x, w, *, interpret: bool, block_k: int = 256,
                            block_d: int = 2048):
    """``sum_k w_k * x_k`` over a stacked ``(K, D)`` leaf -> ``(D,)`` fp32.

    Zero-weight rows masked in-kernel; fp32 accumulation (bf16 inputs
    upcast per block).  Matches the numerator of ``ref.fed_combine_ref``.
    ``block_k`` must be a multiple of 16 and ``block_d`` of 128 on TPU
    (either may exceed its axis, which then forms one block).
    """
    k, d = x.shape
    if k == 0:
        return jnp.zeros((d,), jnp.float32)
    bk, bd = min(k, block_k), min(d, block_d)
    w = jnp.asarray(w, jnp.float32).reshape(k, 1)
    if k % bk:
        # the K sweep accumulates, so its tail rows must be real zeros
        # (zero weight masks them) — not a partial block's garbage
        x, w = _pad_axis(x, bk, 0), _pad_axis(w, bk, 0)
    out = pl.pallas_call(
        _weighted_sum_kernel,
        grid=(pl.cdiv(d, bd), x.shape[0] // bk),    # K innermost
        in_specs=[
            pl.BlockSpec((bk, bd), lambda di, ki: (ki, di)),
            pl.BlockSpec((bk, 1), lambda di, ki: (ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda di, ki: (0, di)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out.reshape(d)


# ---------------------------------------------------------------------------
# (b) fused top-k select + error feedback
# ---------------------------------------------------------------------------
def _count(pred):
    """Kept-dims count of a ``(rows, 128)`` predicate; f32 is exact for
    any row that fits VMEM (< 2**24 entries)."""
    per_row = jnp.sum(pred.astype(jnp.float32), axis=1, keepdims=True)
    return jnp.sum(per_row, axis=0, keepdims=True)


def _topk_ef_kernel(msg_ref, err_ref, sent_ref, new_ref, *, k_keep: int,
                    n: int):
    corrected = msg_ref[0].astype(jnp.float32) \
        + err_ref[0].astype(jnp.float32)            # (rows, 128)
    shape = corrected.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1] \
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # lanes past the row's real length are padding: key -1, never kept
    key = jnp.where(idx < n, bf16_magnitude_key(corrected), -1)
    mask = topk_keep_from_keys(key, idx, k_keep, n, _count)
    sent = jnp.where(mask, corrected, 0.0)
    sent_ref[0] = sent
    new_ref[0] = corrected - sent


def fed_topk_ef_pallas(msgs, err_rows, *, k_keep: int, interpret: bool):
    """Fused correct -> top-k -> residual over a ``(K, D)`` cohort.

    ``err_rows`` is the cohort's ``(K, D)`` error-memory rows (gathered
    from the ``(L, D)`` state by the caller, which also scatters the
    residual back).  Each row is padded to whole 128-lane tiles and
    held in VMEM for the selection's passes.  Returns ``(sent,
    new_err)``, both ``(K, D)`` fp32 — matches ``ref.fed_topk_ef_ref``
    bit-for-bit.
    """
    k, d = msgs.shape
    if k == 0:
        z = jnp.zeros((0, d), jnp.float32)
        return z, z
    rows = pl.cdiv(d, LANES)

    def tiles(a):
        return _pad_axis(a, LANES, 1).reshape(k, rows, LANES)

    block = pl.BlockSpec((1, rows, LANES), lambda ki: (ki, 0, 0))
    row_bytes = rows * LANES * 4
    sent, new_err = pl.pallas_call(
        functools.partial(_topk_ef_kernel, k_keep=int(k_keep), n=d),
        grid=(k,),
        in_specs=[block, block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((k, rows, LANES), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # 4 double-buffered operands + the selection's temporaries
            vmem_limit_bytes=min(16 * row_bytes + (16 << 20), 100 << 20)),
        interpret=interpret,
    )(tiles(msgs), tiles(err_rows))
    unpad = lambda a: a.reshape(k, rows * LANES)[:, :d]  # noqa: E731
    return unpad(sent), unpad(new_err)


# ---------------------------------------------------------------------------
# (c) dp-noise + secure-mask application
# ---------------------------------------------------------------------------
def _dp_secure_kernel(*refs, noise_scale: float, use_clip: bool,
                      use_noise: bool, use_mask: bool):
    refs = list(refs)
    o_ref = refs.pop()
    x_ref = refs.pop(0)
    noise_ref = refs.pop(0) if use_noise else None
    mask_ref = refs.pop(0) if use_mask else None
    coef_ref = refs.pop(0) if use_clip else None
    w_ref = refs.pop(0) if use_mask else None
    out = x_ref[...].astype(jnp.float32)            # (bk, bd)
    # term order and association mirror the XLA transforms exactly:
    # (x * coef) + (scale * noise) + (mask / max(w, 1e-9))
    if use_clip:
        out = out * coef_ref[...].astype(jnp.float32)
    if use_noise:
        out = out + noise_scale * noise_ref[...].astype(jnp.float32)
    if use_mask:
        w = jnp.maximum(w_ref[...].astype(jnp.float32), 1e-9)
        out = out + mask_ref[...].astype(jnp.float32) / w
    o_ref[...] = out


def fed_dp_secure_apply_pallas(x, noise=None, masks=None, clip_coef=None,
                               weights=None, *, interpret: bool,
                               noise_scale: float = 0.0,
                               block_k: int = 256, block_d: int = 2048):
    """One fused elementwise pass over a ``(K, D)`` cohort:

        out = x * clip_coef + noise_scale * noise + mask / max(w, 1e-9)

    with each term present only when its operand is given (statically
    gated — absent terms cost nothing and, unlike adding a zero, cannot
    flip signed zeros).  ``dp`` passes (noise, clip_coef); ``secure``
    passes (masks, weights); matches ``ref.fed_dp_secure_apply_ref``.
    Every output is a function of its own row and column, so both grid
    axes end in a partial block instead of padding.
    """
    k, d = x.shape
    if k == 0:
        return jnp.zeros((0, d), jnp.float32)
    use_clip = clip_coef is not None
    use_noise = noise is not None
    use_mask = masks is not None
    bk, bd = min(k, block_k), min(d, block_d)
    tile = pl.BlockSpec((bk, bd), lambda ki, di: (ki, di))
    column = pl.BlockSpec((bk, 1), lambda ki, di: (ki, 0))
    operands = [(x, tile)]
    if use_noise:
        operands.append((noise, tile))
    if use_mask:
        operands.append((masks, tile))
    if use_clip:
        operands.append(
            (jnp.asarray(clip_coef, jnp.float32).reshape(k, 1), column))
    if use_mask:
        operands.append(
            (jnp.asarray(weights, jnp.float32).reshape(k, 1), column))
    kernel = functools.partial(
        _dp_secure_kernel, noise_scale=float(noise_scale),
        use_clip=use_clip, use_noise=use_noise, use_mask=use_mask)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(k, bk), pl.cdiv(d, bd)),
        in_specs=[spec for _, spec in operands],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((k, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*[a for a, _ in operands])
