"""Plain reference of the Moonlight-16B-A3B block, one chip's share.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no grouping of
tokens by expert, no scan over clients.  It reads the program's
parameter tree (``models/transformer.py`` ``init_params``) and the numbers
:func:`arch_of` takes from a ``ModelConfig``.  ``q_block`` and
``head_block`` split attention by query rows and the head by tokens, each
block under ``jax.checkpoint``, so that one 8k sequence fits a chip; the
result does not depend on them.

Layer equations (DeepSeek-V3, ``model_type`` deepseek_v3; x is one
sequence (S, D), positions 0..S-1):

* Pre-norm residual blocks, RMSNorm ``x / sqrt(mean(x^2) + eps) * g``
  with eps 1e-5; a final RMSNorm, then the head over the vocabulary
  slice; the loss is next-token cross-entropy over the slice.
* MLA with a direct query: ``q = x W_q`` split per head into 128 nope
  and 64 rope channels; ``[c, k_r] = x W_kva`` (512 + 64; the program
  keeps ``W_kva`` as ``w_dkv`` and ``w_kr``); ``c = RMSNorm(c)``;
  ``[k_nope, v] = c W_kvb`` per head (128 + 128); ``q_rope`` and ``k_r``
  are roped, ``k_r`` shared by all heads; scores
  ``(q_nope.k_nope + q_rope.k_r) / sqrt(192)``, causal; out ``W_o``.
* Rope, theta 50,000, no scaling, in the rotate-half convention the
  program uses (the rotary channels split into halves rotated as
  complex pairs at frequencies ``theta^(-i/half)``).  The published
  checkpoint pairs interleaved channels; with random weights the two
  differ only by a fixed permutation of W_q's and W_kr's rope columns.
* Layer 0 (``first_k_dense_replace`` 1): a dense SwiGLU of width 11,264,
  ``(silu(x W_g) * (x W_u)) W_d``.
* MoE layers, router in fp32 over all 64 experts: ``s = sigmoid(x W_r)``,
  ``T = top6(s + b)`` with ``b`` the selection bias
  (``e_score_correction_bias``; ``n_group`` = ``topk_group`` = 1, so the
  group stage selects everything), gates ``g_i = 2.446 s_i /
  sum_{j in T} s_j``; ``y = sum_{i in T and held} g_i SwiGLU_i(x) +
  SwiGLU_shared(x)``, the shared expert of width 2 x 1,408.  Each held
  expert is computed on every token and weighted by its gate, zero where
  it was not chosen.

Departures from the published model, all stated in the benchmark's
configuration file: the chip's share (the experts of other chips add
nothing here, nor in the program); no auxiliary loss (noaux_tc), and the
selection bias is a buffer drawn from the seed — DeepSeek-V3's online
update of ``b`` is a pre-training rule and is left out, so ``b`` does not
move (its gradient is zero: only the chosen indices depend on it).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def arch_of(cfg) -> Dict[str, Any]:
    """The numbers the reference reads, from a ``ModelConfig``."""
    m = cfg.moe
    held = m.num_experts // m.ep_size
    return {"heads": cfg.num_heads, "nope": cfg.resolved_head_dim,
            "rope": cfg.mla_rope_head_dim, "v": cfg.resolved_head_dim,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta,
            "top_k": m.top_k, "scaling": m.routed_scaling_factor,
            "held_lo": m.ep_rank * held, "held": held}


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, pos, theta):
    """Rotate-half rope of x (S, ..., r) at positions pos (S,)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * freq).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def mla(p, a, x, q_block: Optional[int] = None):
    s, h = x.shape[0], a["heads"]
    dn, dr, dv = a["nope"], a["rope"], a["v"]
    pos = jnp.arange(s)
    q = (x @ p["w_q"]).reshape(s, h, dn + dr)
    qn, qr = q[..., :dn], rope(q[..., dn:], pos, a["theta"])
    c = rmsnorm(x @ p["w_dkv"], p["kv_norm"]["scale"], a["eps"])
    kr = rope(x @ p["w_kr"], pos, a["theta"])                  # (S, dr)
    kv = (c @ p["w_ukv"]).reshape(s, h, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5

    @jax.checkpoint
    def rows(qn_b, qr_b, qpos):
        sc = (jnp.einsum("qhd,khd->hqk", qn_b, kn)
              + jnp.einsum("qhd,kd->hqk", qr_b, kr)) * scale
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    qb = q_block or s
    out = jnp.concatenate([rows(qn[i:i + qb], qr[i:i + qb], pos[i:i + qb])
                           for i in range(0, s, qb)])
    return out.reshape(s, h * dv) @ p["wo"]


def moe(p, a, x):
    """This chip's part of one MoE layer: its held experts, dense over
    every token and weighted by their gates, plus the shared experts."""
    s = x.shape[0]
    f32 = jnp.float32
    scores = jax.nn.sigmoid(x.astype(f32) @ p["router"].astype(f32))
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(f32),
                           a["top_k"])
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    gates = a["scaling"] * chosen / jnp.sum(chosen, -1, keepdims=True)
    dense = jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(
        gates).astype(x.dtype)                                 # (S, E)
    y = swiglu(p["shared"], x)
    for e in range(a["held"]):
        y = y + dense[:, a["held_lo"] + e, None] * swiglu(
            tmap(lambda w, e=e: w[e], {k: p[k] for k in
                                       ("w_gate", "w_up", "w_down")}), x)
    return y


def block(p, a, x, q_block=None):
    h = rmsnorm(x, p["attn_norm"]["scale"], a["eps"])
    x = x + mla(p["mixer"], a, h, q_block)
    h = rmsnorm(x, p["ffn_norm"]["scale"], a["eps"])
    ffn = p["ffn"]
    return x + (moe(ffn, a, h) if "router" in ffn else swiglu(ffn, h))


def loss_sum(params, a, tokens, labels, *, dtype=jnp.float32,
             q_block: Optional[int] = None,
             head_block: Optional[int] = None):
    """(sum of the next-token cross-entropy, token count) of one
    sequence ``tokens`` (S,) with ``labels`` (S,), every layer under
    ``jax.checkpoint``."""
    p = tmap(lambda w: w.astype(dtype), params)
    x = p["embed"]["table"][tokens]
    layer = jax.checkpoint(lambda lp, x: block(lp, a, x, q_block))
    for stack in ("dense_layers", "layers"):
        n = jax.tree_util.tree_leaves(p[stack])[0].shape[0]
        for i in range(n):
            x = layer(tmap(lambda w, i=i: w[i], p[stack]), x)
    x = rmsnorm(x, p["final_norm"]["scale"], a["eps"])

    @jax.checkpoint
    def xent(xb, lb):
        logits = xb @ p["lm_head"]["w"]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, lb[:, None], 1)[:, 0])

    hb = head_block or tokens.shape[0]
    total = sum(xent(x[i:i + hb], labels[i:i + hb])
                for i in range(0, tokens.shape[0], hb))
    return total.astype(jnp.float32), float(tokens.shape[0])


def mean_loss(params, a, tokens, labels, **kw):
    """Mean next-token cross-entropy over a batch (B, S)."""
    s, n = 0.0, 0.0
    for t, l in zip(tokens, labels):
        si, ni = loss_sum(params, a, t, l, **kw)
        s, n = s + si, n + ni
    return s / n


def loss_and_grad(params, a, tokens, labels, **kw):
    """The mean loss of a batch and its gradient, at ``highest``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, t, l: mean_loss(p, a, t, l, **kw)))(params, tokens,
                                                          labels)
