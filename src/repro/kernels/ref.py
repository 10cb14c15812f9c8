"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Each function is the mathematically-direct implementation with no tiling,
no online accumulation, fp32 math — deliberately simple so a human can
audit it against the equations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """q (B,H,S,D), k/v (B,Hkv,S,D) -> (B,H,S,D).  GQA by head repeat."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    rep = h // hkv
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.astype(q.dtype)


def ssd_scan_ref(x, dt, a, b, c, h0=None):
    """Naive per-step SSD recurrence (the definition, O(S) sequential).

    x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N).
    Returns (y (B,S,H,P), h_last (B,H,P,N)).
    """
    bs, s, h, p = x.shape
    n = b.shape[-1]
    f32 = jnp.float32
    xf, dtf = x.astype(f32), dt.astype(f32)
    bf, cf = b.astype(f32), c.astype(f32)

    def step(hst, inp):
        xt, dtt, bt, ct = inp                       # (B,H,P),(B,H),(B,N),(B,N)
        decay = jnp.exp(dtt * a)                    # (B,H)
        hst = hst * decay[:, :, None, None] \
            + jnp.einsum("bh,bn,bhp->bhpn", dtt, bt, xt)
        y = jnp.einsum("bn,bhpn->bhp", ct, hst)
        return hst, y

    if h0 is None:
        h0 = jnp.zeros((bs, h, p, n), f32)
    hl, ys = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
         jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1)
    return y.astype(x.dtype), hl


def fed_combine_ref(stacked, weights):
    """Eq. (2) weighted combine over one stacked ``(K, ...)`` leaf.

    Mirrors ``core.aggregation.aggregate_stacked`` on a single leaf:
    zero-weight (padded) rows are ``where``-masked OUT before the
    multiply — their values may be non-finite garbage and must never
    poison the sum — and an all-zero weight vector yields a zero combine
    (guarded denominator), never 0/0.  fp32 accumulation regardless of
    the message dtype (the bf16-deltas / fp32-accumulate contract).
    """
    w = jnp.asarray(weights, jnp.float32)
    total = jnp.maximum(jnp.sum(w), 1e-12)
    wb = w.reshape((-1,) + (1,) * (stacked.ndim - 1))
    contrib = jnp.where(wb > 0.0, stacked.astype(jnp.float32), 0.0)
    return jnp.sum(wb * contrib, axis=0) / total


def fed_accumulate_ref(acc, msg, weight):
    """One client's term of the Eq. (2) numerator added to ``acc``: the
    client-scan round's running sum.  A zero weight adds nothing, even to
    a non-finite message; fp32 accumulation."""
    w = jnp.asarray(weight, jnp.float32)
    return acc.astype(jnp.float32) + jnp.where(
        w > 0.0, w * msg.astype(jnp.float32), 0.0)


def fed_topk_ef_ref(msgs, err_rows, k_keep: int):
    """Fused top-k select + error feedback over a ``(K, D)`` cohort.

    Per row: corrected = msg + err;  sent = the EXACTLY-``k_keep``
    largest-|corrected| entries (index tie-breaking, matching
    ``core.aggregation.topk_keep_mask``);  new_err = corrected - sent.
    Returns ``(sent, new_err)``, both ``(K, D)`` fp32.
    """
    corrected = msgs.astype(jnp.float32) + err_rows.astype(jnp.float32)
    sent = jnp.where(topk_mask_ref(corrected, k_keep), corrected, 0.0)
    return sent, corrected - sent


def topk_mask_ref(x, k: int):
    """The ``k`` largest bf16-rounded ``|x|`` of the last axis, ties
    toward the lower index — sort-based (``lax.top_k`` for the
    threshold, a cumulative tie rank for the index rule), independent of
    the bisection ``core.aggregation.topk_keep_mask`` and the Pallas
    kernel share, so parity against it checks that rule rather than
    restating it."""
    from repro.core.aggregation import bf16_magnitude_key
    key = bf16_magnitude_key(x)
    thresh = jax.lax.top_k(key, k)[0][..., -1:]
    greater = key > thresh
    tie = key == thresh
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1) - 1
    return greater | (tie & (tie_rank < k - jnp.sum(
        greater, axis=-1, keepdims=True)))


def fed_dp_secure_apply_ref(msgs, noise=None, masks=None, clip_coef=None,
                            weights=None, noise_scale: float = 0.0):
    """dp-noise + secure-mask application over a ``(K, D)`` cohort.

    out = msg * clip_coef + noise_scale * noise + mask / max(w, 1e-9)
    with each term present only when its operand is given — EXACTLY the
    expressions the XLA transforms evaluate (``core/transforms.py``):
    ``dp`` passes (noise, clip_coef), ``secure`` passes (masks, weights).
    """
    out = msgs.astype(jnp.float32)
    if clip_coef is not None:
        out = out * clip_coef.reshape((-1,) + (1,) * (out.ndim - 1))
    if noise is not None:
        out = out + noise_scale * noise.astype(jnp.float32)
    if masks is not None:
        w = jnp.maximum(weights.astype(jnp.float32), 1e-9)
        out = out + masks.astype(jnp.float32) \
            / w.reshape((-1,) + (1,) * (out.ndim - 1))
    return out


def topic_decoder_ref(theta, beta, bow, dec_scale=None):
    """ProdLDA reconstruction term, materialized:
        recon_d = -sum_v bow_dv * log softmax_v(theta_d . beta_v * scale)
    theta (B,K), beta (K,V), bow (B,V) -> (B,) fp32.
    """
    logits = theta.astype(jnp.float32) @ beta.astype(jnp.float32)
    if dec_scale is not None:
        logits = logits * dec_scale.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(bow.astype(jnp.float32) * logp, axis=-1)
