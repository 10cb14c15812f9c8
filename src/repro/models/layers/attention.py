"""Attention layers: GQA (llama/qwen family), MLA (minicpm3/deepseek).

Supports four execution modes driven by the caller:
  * full-sequence (train / prefill): causal, sliding-window-causal, or
    bidirectional (encoder-only) masks;
  * single-token decode against a KV cache — either a full-length cache
    (``decode_32k``) or a ring-buffer sliding-window cache (``long_500k``
    for dense archs, DESIGN.md §7).

All attention math accumulates in fp32 and casts back to the activation
dtype.  Shapes: x (B, S, D); q (B, S, Hq, hd); k/v (B, S, Hkv, hd).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import spans
from repro.models.layers.init import dense_init
from repro.models.layers.norms import rmsnorm, rmsnorm_init
from repro.models.layers.rope import apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------
def make_mask(q_pos, k_pos, *, causal: bool, window: int = 0):
    """Boolean attention mask (..., Sq, Sk): True = may attend."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                 dtype=bool)
    if causal:
        m = m & (k_pos[..., None, :] <= q_pos[..., :, None])
    if window:
        m = m & (k_pos[..., None, :] > q_pos[..., :, None] - window)
    return m


def _sdpa(q, k, v, mask, scale):
    """q (B,Sq,Hq,hd) k/v (B,Sk,Hkv,hd) mask (B,Sq,Sk) -> (B,Sq,Hq,hd).

    Materializes the (Sq, Sk) score matrix — used for decode (Sq == 1)
    and as the small-sequence oracle.  Full-sequence paths use
    ``chunked_attention`` below (flash-structured, O(chunk) memory).
    """
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(b, sq, hq, hd).astype(q.dtype)


def _chunk_mask(kpb, q_pos, causal: bool, window: int):
    """(B,ck) key positions x (B,Sq) query positions -> (B,1,1,Sq,ck)."""
    kk = kpb[:, None, None, None, :]
    qq = q_pos[:, None, None, :, None]
    mask = kk >= 0
    if causal:
        mask &= kk <= qq
    if window:
        mask &= kk > qq - window
    return mask


def _flash_fwd_scan(qf, kc, vc, kp, q_pos, causal, window, scale, unroll):
    b, sq, hkv, g, hd = qf.shape
    hd_v = vc.shape[-1]
    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, hd_v), jnp.float32)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, kpb = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb) * scale
        mask = _chunk_mask(kpb, q_pos, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vb)
        return (m_new, l_new, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, kp),
                                  unroll=unroll)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]               # (B,Hkv,g,Sq,hd_v)
    lse = m + jnp.log(l_safe)
    return out, lse


# Memory-correct flash VJP: the naive scan VJP would stash the per-chunk
# probability tiles for every chunk and layer (O(Sq x Sk) — exactly what
# flash attention exists to avoid), so the backward pass is hand-written:
# residuals are only (q, k, v, out, lse) and d(q,k,v) are recomputed
# chunk-by-chunk in a second scan.  Mirrors kernels/flash_attention.py.
@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_vjp(q, k, v, q_pos, k_pos, causal, window, scale, chunk, unroll):
    qf, kc, vc, kp, _ = _prep(q, k, v, k_pos, chunk)
    return _flash_fwd_scan(qf, kc, vc, kp, q_pos, causal, window, scale,
                           unroll)


def _prep(q, k, v, k_pos, chunk):
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    g = hq // hkv
    ck = min(chunk, sk)
    nc = -(-sk // ck)
    if nc * ck != sk:
        pad = nc * ck - sk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, hd)
    kc = jnp.moveaxis(
        k.astype(jnp.float32).reshape(b, nc, ck, hkv, hd), 1, 0)
    vc = jnp.moveaxis(
        v.astype(jnp.float32).reshape(b, nc, ck, hkv, hd_v), 1, 0)
    kp = jnp.moveaxis(k_pos.reshape(b, nc, ck), 1, 0)
    return qf, kc, vc, kp, (b, sq, sk, hq, hkv, g, hd, hd_v, ck, nc)


def _flash_vjp_fwd(q, k, v, q_pos, k_pos, causal, window, scale, chunk,
                   unroll):
    qf, kc, vc, kp, dims = _prep(q, k, v, k_pos, chunk)
    out, lse = _flash_fwd_scan(qf, kc, vc, kp, q_pos, causal, window,
                               scale, unroll)
    return (out, lse), (q, k, v, q_pos, k_pos, out, lse)


def _flash_vjp_bwd(causal, window, scale, chunk, unroll, res, cts):
    q, k, v, q_pos, k_pos, out, lse = res
    d_out = cts[0].astype(jnp.float32)          # (B,Hkv,g,Sq,hd_v)
    qf, kc, vc, kp, dims = _prep(q, k, v, k_pos, chunk)
    b, sq, sk, hq, hkv, g, hd, hd_v, ck, nc = dims
    delta = jnp.sum(d_out * out, axis=-1)       # (B,Hkv,g,Sq)

    def step(dq_acc, xs):
        kb, vb, kpb = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb) * scale
        mask = _chunk_mask(kpb, q_pos, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dv_b = jnp.einsum("bhgqk,bhgqd->bkhd", p, d_out)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", d_out, vb)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kb)
        dk_b = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qf)
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros((b, sq, hkv, g, hd), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(step, dq0, (kc, vc, kp), unroll=unroll)
    dq = dq.reshape(b, sq, hq, hd).astype(q.dtype)
    dk = jnp.moveaxis(dk_c, 0, 1).reshape(b, nc * ck, hkv, hd)[:, :sk]
    dv = jnp.moveaxis(dv_c, 0, 1).reshape(b, nc * ck, hkv, hd_v)[:, :sk]
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def chunked_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                      scale: float, chunk: int = 512,
                      unroll: bool = False):
    """Flash-structured attention in pure jnp (see ``_flash_core``).

    q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D), q_pos (B,Sq), k_pos (B,Sk).
    ``unroll=True`` unrolls the chunk scans in HLO — used by the roofline
    analysis lowering so cost_analysis counts every chunk (XLA counts
    while-loop bodies once).
    """
    b, sq, hq, hd = q.shape
    hd_v = v.shape[-1]
    out, _ = _flash_vjp(q, k, v, q_pos, k_pos, causal, window, scale,
                        chunk, unroll)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, hq, hd_v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def gqa_init(key, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nq * hd)),
        "wk": dense_init(ks[1], (d, nkv * hd)),
        "wv": dense_init(ks[2], (d, nkv * hd)),
        "wo": dense_init(ks[3], (nq * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((nkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((nkv * hd,), jnp.float32)
    return p


def _project_qkv(params, cfg, x):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


def gqa_full(params, cfg, x, angles, *, positions, causal=True):
    """Train / prefill attention over the full sequence.

    Returns (out, kv) — kv is reused by prefill to build the cache.
    """
    q, k, v = _project_qkv(params, cfg, x)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    out = chunked_attention(q, k, v, positions, positions, causal=causal,
                            window=cfg.sliding_window,
                            scale=cfg.resolved_head_dim ** -0.5,
                            unroll=cfg.unroll_chunks)
    out = out.reshape(x.shape[0], x.shape[1], -1)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return out, (k, v)


def gqa_decode(params, cfg, x, angles, *, cache_k, cache_v, pos):
    """One-token decode. x (B,1,D); cache (B, C, Hkv, hd); pos scalar int.

    With ``cfg.sliding_window`` the cache is a ring buffer of length
    C == window; otherwise C == max sequence length and slot ``pos`` is
    written directly.
    """
    b = x.shape[0]
    cache_len = cache_k.shape[1]
    q, k, v = _project_qkv(params, cfg, x)      # (B,1,·,hd)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    slot = pos % cache_len if cfg.sliding_window > 0 else pos
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), slot, axis=1)
    # validity: slot index -> original position
    idx = jnp.arange(cache_len)
    if cfg.sliding_window > 0:
        # ring buffer: entry i holds position p with p % C == i and
        # pos - C < p <= pos
        orig = pos - ((slot - idx) % cache_len)
        valid = (orig >= 0) & (orig <= pos) & (orig > pos - cfg.sliding_window)
    else:
        valid = idx <= pos
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, cache_len))
    out = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                mask, cfg.resolved_head_dim ** -0.5)
    out = out.reshape(b, 1, -1)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return out, (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (minicpm3-4b / deepseek-v2 style)
# ---------------------------------------------------------------------------
def mla_init(key, cfg):
    """MLA weights; ``mla_q_lora_rank == 0`` (DeepSeek-V2-Lite, Moonlight:
    ``q_lora_rank`` null) projects the query directly, ``w_q``."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq = cfg.num_heads
    qr, kr, rr = cfg.mla_q_lora_rank, cfg.mla_kv_lora_rank, cfg.mla_rope_head_dim
    ks = jax.random.split(key, 7)
    q = {"w_dq": dense_init(ks[0], (d, qr)),
         "q_norm": rmsnorm_init(qr),
         "w_uq": dense_init(ks[1], (qr, nq * (hd + rr)))} if qr \
        else {"w_q": dense_init(ks[0], (d, nq * (hd + rr)))}
    return {
        **q,
        "w_dkv": dense_init(ks[2], (d, kr)),
        "kv_norm": rmsnorm_init(kr),
        "w_kr": dense_init(ks[3], (d, rr)),
        "w_ukv": dense_init(ks[4], (kr, nq * 2 * hd)),
        "wo": dense_init(ks[5], (nq * hd, d)),
    }


def _mla_q(params, cfg, x, angles):
    b, s, _ = x.shape
    nq, hd, rr = cfg.num_heads, cfg.resolved_head_dim, cfg.mla_rope_head_dim
    if "w_q" in params:
        q = jnp.einsum("bsd,de->bse", x, params["w_q"].astype(x.dtype))
    else:
        cq = jnp.einsum("bsd,dr->bsr", x, params["w_dq"].astype(x.dtype))
        cq = rmsnorm(params["q_norm"], cq, cfg.norm_eps)
        q = jnp.einsum("bsr,re->bse", cq, params["w_uq"].astype(x.dtype))
    q = q.reshape(b, s, nq, hd + rr)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    if angles is not None:
        q_rope = apply_rope(q_rope, angles[..., : rr // 2])
    return q_nope, q_rope


def _mla_kv_latent(params, cfg, x, angles):
    ckv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(x.dtype))
    kr = jnp.einsum("bsd,dr->bsr", x, params["w_kr"].astype(x.dtype))
    if angles is not None:
        kr = apply_rope(kr[:, :, None, :],
                        angles[..., : cfg.mla_rope_head_dim // 2])[:, :, 0, :]
    return ckv, kr


def _mla_expand_kv(params, cfg, ckv):
    b, s, _ = ckv.shape
    nq, hd = cfg.num_heads, cfg.resolved_head_dim
    c = rmsnorm(params["kv_norm"], ckv, cfg.norm_eps)
    kv = jnp.einsum("bsr,re->bse", c, params["w_ukv"].astype(ckv.dtype))
    kv = kv.reshape(b, s, nq, 2 * hd)
    return kv[..., :hd], kv[..., hd:]


def _mla_attend(params, cfg, q_nope, q_rope, k_nope, k_rope, v, mask):
    scale = (cfg.resolved_head_dim + cfg.mla_rope_head_dim) ** -0.5
    s_nope = jnp.einsum("bqhd,bkhd->bhqk",
                        q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bkd->bhqk",
                        q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    scores = (s_nope + s_rope) * scale
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    b, sq = out.shape[0], out.shape[1]
    out = out.reshape(b, sq, -1).astype(q_nope.dtype)
    return jnp.einsum("bse,ed->bsd", out, params["wo"].astype(q_nope.dtype))


def mla_full(params, cfg, x, angles, *, positions, causal=True):
    with spans.scope(spans.ATTENTION_MLA):
        return _mla_full(params, cfg, x, angles, positions=positions,
                         causal=causal)


def _mla_full(params, cfg, x, angles, *, positions, causal):
    q_nope, q_rope = _mla_q(params, cfg, x, angles)
    ckv, kr = _mla_kv_latent(params, cfg, x, angles)
    k_nope, v = _mla_expand_kv(params, cfg, ckv)
    # fold the decoupled rope channel into the head dim and reuse the
    # flash-structured chunked core: scores = q_nope.k_nope + q_rope.k_rope
    nq = cfg.num_heads
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    kr_b = jnp.broadcast_to(kr[:, :, None, :],
                            kr.shape[:2] + (nq, kr.shape[-1]))
    k_cat = jnp.concatenate([k_nope, kr_b], axis=-1)
    scale = (cfg.resolved_head_dim + cfg.mla_rope_head_dim) ** -0.5
    out = chunked_attention(q_cat, k_cat, v, positions, positions,
                            causal=causal, window=cfg.sliding_window,
                            scale=scale, unroll=cfg.unroll_chunks)
    b, s = x.shape[0], x.shape[1]
    out = out.reshape(b, s, -1)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    if cfg.mla_absorb:
        # absorbed decode reads the cache pre-normalized (see
        # mla_decode_absorbed) — normalize at write time
        ckv = rmsnorm(params["kv_norm"], ckv, cfg.norm_eps)
    return out, (ckv, kr)


def mla_decode_absorbed(params, cfg, x, angles, *, cache_ckv, cache_kr,
                        pos):
    """MLA decode with weight absorption (DeepSeek-V2 serving trick).

    Mathematically identical to ``mla_decode`` (tested), but reassociated:
        scores = (q_nope W_uk^T) . c_kv   — queries mapped INTO the latent
        out    = (p . c_kv) W_uv          — combine in latent, expand once
    so the (B, C, H, hd) K/V expansion of the whole cache never happens;
    per-step work drops from O(C*kr*H*hd) to O(C*H*kr) and the cache is
    read once in latent form.
    """
    b = x.shape[0]
    cache_len = cache_ckv.shape[1]
    nq, hd = cfg.num_heads, cfg.resolved_head_dim
    kr = cfg.mla_kv_lora_rank

    from repro.parallel.sharding import constrain_batch, constrain_heads
    q_nope, q_rope = _mla_q(params, cfg, x, angles)     # (B,1,H,hd)
    ckv_new, kr_new = _mla_kv_latent(params, cfg, x, angles)
    ckv_new = rmsnorm(params["kv_norm"], ckv_new, cfg.norm_eps)
    # the per-step latent is r-sharded by w_dkv's TP sharding; gather the
    # KB-sized new entry instead of letting the cache write reshard the
    # whole GB-sized cache (EXPERIMENTS.md §Perf C4)
    ckv_new = constrain_batch(ckv_new)
    kr_new = constrain_batch(kr_new)
    slot = pos % cache_len if cfg.sliding_window > 0 else pos
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(
        cache_ckv, ckv_new.astype(cache_ckv.dtype), slot, axis=1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(
        cache_kr, kr_new.astype(cache_kr.dtype), slot, axis=1)

    w_ukv = params["w_ukv"].astype(x.dtype).reshape(kr, nq, 2 * hd)
    w_k = w_ukv[..., :hd]                                # (kr, H, hd)
    w_v = w_ukv[..., hd:]                                # (kr, H, hd)

    # cache is stored PRE-NORMALIZED under mla_absorb (mla_full /
    # the decode write below apply kv_norm at write time): no per-step
    # f32 renormalization sweep over all 32k cached positions
    c_n = cache_ckv                                      # (B, C, kr) bf16

    q_eff = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_k,
                       preferred_element_type=jnp.float32)  # (B,1,H,kr)
    q_eff = constrain_heads(q_eff, 2)
    s_nope = jnp.einsum("bqhr,bkr->bhqk", q_eff.astype(x.dtype), c_n,
                        preferred_element_type=jnp.float32)
    s_nope = constrain_heads(s_nope, 1)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, cache_kr,
                        preferred_element_type=jnp.float32)
    s_rope = constrain_heads(s_rope, 1)
    scale = (hd + cfg.mla_rope_head_dim) ** -0.5
    scores = (s_nope + s_rope) * scale

    idx = jnp.arange(cache_len)
    if cfg.sliding_window > 0:
        orig = pos - ((slot - idx) % cache_len)
        valid = (orig >= 0) & (orig <= pos) & (orig > pos - cfg.sliding_window)
    else:
        valid = idx <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)              # (B,H,1,C)

    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs.astype(x.dtype), c_n,
                       preferred_element_type=jnp.float32)  # (B,1,H,kr)
    o_lat = constrain_heads(o_lat, 2)
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat.astype(x.dtype), w_v,
                     preferred_element_type=jnp.float32)    # (B,1,H,hd)
    out = constrain_heads(out, 2)
    out = out.reshape(b, 1, nq * hd).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return out, (cache_ckv, cache_kr)


def mla_decode(params, cfg, x, angles, *, cache_ckv, cache_kr, pos):
    """MLA decode: the cache holds the compressed latent + shared rope key.

    cache_ckv (B, C, kv_lora_rank), cache_kr (B, C, rope_dim).
    """
    b = x.shape[0]
    cache_len = cache_ckv.shape[1]
    q_nope, q_rope = _mla_q(params, cfg, x, angles)
    ckv_new, kr_new = _mla_kv_latent(params, cfg, x, angles)
    slot = pos % cache_len if cfg.sliding_window > 0 else pos
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(
        cache_ckv, ckv_new.astype(cache_ckv.dtype), slot, axis=1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(
        cache_kr, kr_new.astype(cache_kr.dtype), slot, axis=1)
    k_nope, v = _mla_expand_kv(params, cfg, cache_ckv.astype(x.dtype))
    idx = jnp.arange(cache_len)
    if cfg.sliding_window > 0:
        orig = pos - ((slot - idx) % cache_len)
        valid = (orig >= 0) & (orig <= pos) & (orig > pos - cfg.sliding_window)
    else:
        valid = idx <= pos
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, cache_len))
    out = _mla_attend(params, cfg, q_nope, q_rope, k_nope,
                      cache_kr.astype(x.dtype), v, mask)
    return out, (cache_ckv, cache_kr)
