"""Decoder/encoder transformer assembly for the architecture zoo.

One scan-over-layers implementation covers all six assigned families
(dense, moe, ssm, hybrid, vlm, audio); per-family behaviour is config
dispatch, not code forks.  Layer parameters are stacked with a leading
``num_layers`` axis and consumed by ``jax.lax.scan`` so the HLO is O(1)
in depth — a 94-layer qwen3-moe lowers in seconds on CPU.

Public entry points (all pure functions of (params, cfg, batch)):
  * ``init_params``      — parameter pytree (fp32 masters)
  * ``forward_train``    — full-sequence logits (+ MoE aux loss)
  * ``prefill``          — logits + populated decode cache
  * ``decode_step``      — ONE token against the cache
  * ``init_cache``       — zeroed decode cache for a given batch/seq
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AUDIO, DENSE, HYBRID, MOE, SSM, VLM, ModelConfig
from repro.models.layers import attention as attn_lib
from repro.models.layers import hymba as hymba_lib
from repro.models.layers import mamba2 as mamba_lib
from repro.models.layers import moe as moe_lib
from repro.models.layers.embedding import (
    embed, embedding_init, lm_head, lm_head_init, lm_head_tied,
    masked_prediction_embed, merge_patch_embeds)
from repro.models.layers.init import dense_init, embed_init
from repro.models.layers.mlp import gelu_mlp, gelu_mlp_init, swiglu, swiglu_init
from repro.models.layers.norms import (layernorm, layernorm_init, rmsnorm,
                                       rmsnorm_init)
from repro.models.layers.rope import (mrope_angles, rope_angles,
                                      text_mrope_positions)
from repro.parallel.sharding import constrain_batch, constrain_batch_and_last


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------
def _norm_init(cfg, dim):
    return layernorm_init(dim) if cfg.kind == AUDIO else rmsnorm_init(dim)


def _apply_norm(cfg, p, x):
    if cfg.kind == AUDIO:
        return layernorm(p, x, cfg.norm_eps)
    return rmsnorm(p, x, cfg.norm_eps)


def _ffn_init(key, cfg, moe_layer: bool, d_ff: int = 0):
    if moe_layer:
        return moe_lib.moe_init(key, cfg)
    if cfg.activation == "gelu":
        return gelu_mlp_init(key, cfg.d_model, cfg.d_ff)
    return swiglu_init(key, cfg.d_model, d_ff or cfg.d_ff)


def _layer_init(key, cfg: ModelConfig, moe_layer: bool, d_ff: int = 0):
    d = cfg.d_model
    if cfg.kind == SSM:
        k1, _ = jax.random.split(key)
        return {"norm": _norm_init(cfg, d),
                "mixer": mamba_lib.mamba2_init(k1, cfg)}
    k1, k2 = jax.random.split(key)
    if cfg.kind == HYBRID:
        mixer = hymba_lib.hymba_init(k1, cfg)
    elif cfg.use_mla:
        mixer = attn_lib.mla_init(k1, cfg)
    else:
        mixer = attn_lib.gqa_init(k1, cfg)
    return {
        "attn_norm": _norm_init(cfg, d),
        "mixer": mixer,
        "ffn_norm": _norm_init(cfg, d),
        "ffn": _ffn_init(k2, cfg, moe_layer, d_ff),
    }


def _unit_layout(cfg: ModelConfig) -> Tuple[int, bool]:
    """(layers scanned per unit, unit contains a dense sub-layer?)."""
    if cfg.kind == MOE and cfg.moe.moe_every > 1:
        assert cfg.moe.moe_every == 2, "moe_every in {1,2} supported"
        return 2, True
    return 1, False


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    per_unit, has_dense_sub = _unit_layout(cfg)
    num_units = (cfg.num_layers - cfg.first_k_dense) // per_unit

    def one_unit(k):
        if has_dense_sub:
            ka, kb = jax.random.split(k)
            return {"dense_sub": _layer_init(ka, cfg, moe_layer=False),
                    "moe_sub": _layer_init(kb, cfg, moe_layer=True)}
        return _layer_init(k, cfg, moe_layer=(cfg.kind == MOE))

    unit_keys = jax.random.split(keys[0], num_units)
    layers = jax.vmap(one_unit)(unit_keys)

    params: Dict[str, Any] = {
        "layers": layers,
        "final_norm": _norm_init(cfg, cfg.d_model),
    }
    if cfg.first_k_dense:
        # leading dense layers (DeepSeek's first_k_dense_replace), a
        # stack of their own ahead of the scanned units
        params["dense_layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe_layer=False,
                                  d_ff=cfg.dense_d_ff))(
            jax.random.split(keys[5], cfg.first_k_dense))
    if cfg.kind == AUDIO:
        params["frontend_proj"] = {
            "w": dense_init(keys[1], (cfg.frontend_embed_dim, cfg.d_model)),
        }
        params["mask_embed"] = 0.02 * jax.random.normal(
            keys[2], (cfg.d_model,), jnp.float32)
        params["pos_embed"] = 0.02 * jax.random.normal(
            keys[3], (cfg.max_seq_len, cfg.d_model), jnp.float32)
        params["pred_head"] = lm_head_init(keys[4], cfg.d_model,
                                           cfg.vocab_size)
        return params

    params["embed"] = embedding_init(keys[1], cfg.vocab_size, cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = lm_head_init(keys[2], cfg.d_model, cfg.vocab_size)
    return params


# ---------------------------------------------------------------------------
# full-sequence block application (train / prefill)
# ---------------------------------------------------------------------------
def _block_full(cfg, lp, x, angles, positions, *, causal):
    """One layer, full sequence.  Returns (x, cache_entry, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.kind == SSM:
        h = _apply_norm(cfg, lp["norm"], x)
        y, state = mamba_lib.mamba2_apply(lp["mixer"], cfg, h)
        return x + y.astype(x.dtype), state, aux
    h = _apply_norm(cfg, lp["attn_norm"], x)
    if cfg.kind == HYBRID:
        y, cache = hymba_lib.hymba_full(lp["mixer"], cfg, h, angles,
                                        positions=positions)
        (k, v), (cs, ss) = cache
        cache = (k, v, cs, ss)
    elif cfg.use_mla:
        y, cache = attn_lib.mla_full(lp["mixer"], cfg, h, angles,
                                     positions=positions, causal=causal)
    else:
        y, cache = attn_lib.gqa_full(lp["mixer"], cfg, h, angles,
                                     positions=positions, causal=causal)
    x = x + y.astype(x.dtype)
    h = _apply_norm(cfg, lp["ffn_norm"], x)
    if "router" in lp["ffn"]:
        y, aux = moe_lib.moe_apply(lp["ffn"], cfg, h)
    elif cfg.activation == "gelu":
        y = gelu_mlp(lp["ffn"], h)
    else:
        y = swiglu(lp["ffn"], h)
    return x + y.astype(x.dtype), cache, aux


def _embed_input(params, cfg, batch, dtype):
    """Resolve the input embedding per modality (stub carve-out)."""
    if cfg.kind == AUDIO:
        x = batch["frame_embeds"].astype(dtype)
        x = jnp.einsum("bsd,de->bse", x,
                       params["frontend_proj"]["w"].astype(dtype))
        x = masked_prediction_embed(
            {"mask_embed": params["mask_embed"]}, x, batch["frame_mask"])
        s = x.shape[1]
        return x + params["pos_embed"][:s].astype(dtype)
    x = embed(params["embed"], batch["tokens"], dtype)
    if cfg.kind == VLM and "patch_embeds" in batch:
        x = merge_patch_embeds(x, batch["patch_embeds"],
                               batch["patch_positions"])
    return x


def _angles_for(cfg, batch, positions):
    if cfg.kind == AUDIO:
        return None
    if cfg.use_mla:
        return rope_angles(positions, cfg.mla_rope_head_dim, cfg.rope_theta)
    if cfg.use_mrope:
        mpos = batch.get("mrope_positions")
        if mpos is None:
            mpos = text_mrope_positions(positions)
        return mrope_angles(mpos, cfg.resolved_head_dim, cfg.rope_theta,
                            cfg.mrope_sections)
    return rope_angles(positions, cfg.resolved_head_dim, cfg.rope_theta)


def _run_layers_full(params, cfg, x, angles, positions, *, causal,
                     want_cache: bool):
    per_unit, has_dense_sub = _unit_layout(cfg)
    if want_cache:
        _refuse_leading_dense(cfg)

    def layer_fn(carry, lp):
        x, aux = carry
        x = constrain_batch(x)     # keep batch on the client/data axes
        x, cache, a = _block_full(cfg, lp, x, angles, positions,
                                  causal=causal)
        return (constrain_batch(x), aux + a), cache if want_cache else None

    def unit_fn(carry, lp):
        if not has_dense_sub:
            return layer_fn(carry, lp)
        x, aux = carry
        x = constrain_batch(x)
        x, c1, a1 = _block_full(cfg, lp["dense_sub"], x, angles,
                                positions, causal=causal)
        x, c2, a2 = _block_full(cfg, lp["moe_sub"], x, angles,
                                positions, causal=causal)
        x = constrain_batch(x)
        return (x, aux + a1 + a2), (c1, c2) if want_cache else None

    lead_fn = layer_fn
    if cfg.remat_layers:
        lead_fn = jax.checkpoint(layer_fn, prevent_cse=False)
        unit_fn = jax.checkpoint(unit_fn, prevent_cse=False)

    carry = (constrain_batch(x), moe_lib.aux_zeros(cfg))
    if "dense_layers" in params:
        carry, _ = _over_stack(cfg, lead_fn, carry, params["dense_layers"],
                               want_cache=False)
    (x, aux), caches = _over_stack(cfg, unit_fn, carry, params["layers"],
                                   want_cache=want_cache)
    return x, aux, caches


def _refuse_leading_dense(cfg):
    if cfg.first_k_dense:
        raise NotImplementedError(
            f"{cfg.name}: prefill and decode do not cover the leading "
            "dense layers (first_k_dense); the model trains only")


def _over_stack(cfg, fn, carry, stack, *, want_cache: bool):
    """``fn`` over the leading axis of a stacked layer tree: one scan, or
    with ``scan_layers=False`` (analysis / tiny-model) a Python loop."""
    if cfg.scan_layers:
        return jax.lax.scan(fn, carry, stack)
    n = jax.tree_util.tree_leaves(stack)[0].shape[0]
    cache_list = []
    for i in range(n):
        lp = jax.tree_util.tree_map(lambda a: a[i], stack)
        carry, ys = fn(carry, lp)
        cache_list.append(ys)
    caches = None
    if want_cache:
        caches = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *cache_list)
    return carry, caches


def _logits(params, cfg, x):
    x = constrain_batch(x)
    if cfg.kind == AUDIO:
        logits = lm_head(params["pred_head"], x)
    elif cfg.tie_embeddings:
        logits = lm_head_tied(params["embed"], x)
    else:
        logits = lm_head(params["lm_head"], x)
    return constrain_batch_and_last(logits)


def forward_train(params, cfg: ModelConfig, batch, *, dtype=None):
    """Full-sequence forward.  Returns (logits fp32, moe_aux fp32)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.kind == AUDIO:
        b, s = batch["frame_embeds"].shape[:2]
    else:
        b, s = batch["tokens"].shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _embed_input(params, cfg, batch, dtype)
    angles = _angles_for(cfg, batch, positions)
    causal = not cfg.encoder_only
    x, aux, _ = _run_layers_full(params, cfg, x, angles, positions,
                                 causal=causal, want_cache=False)
    x = _apply_norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, x), aux


# ---------------------------------------------------------------------------
# decode path
# ---------------------------------------------------------------------------
def _cache_len(cfg, seq_len: int) -> int:
    return cfg.sliding_window if cfg.sliding_window else seq_len


def init_cache(cfg: ModelConfig, batch_size: int, seq_len: int,
               dtype=None) -> Dict[str, Any]:
    """Zeroed decode cache covering ``seq_len`` positions."""
    _refuse_leading_dense(cfg)
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    per_unit, has_dense_sub = _unit_layout(cfg)
    nu = L // per_unit
    c = _cache_len(cfg, seq_len)
    hd = cfg.resolved_head_dim

    def kv():
        return (jnp.zeros((nu, batch_size, c, cfg.num_kv_heads, hd), dtype),
                jnp.zeros((nu, batch_size, c, cfg.num_kv_heads, hd), dtype))

    def ssm_state():
        d_in, nh, conv_ch = mamba_lib.mamba2_dims(cfg)
        return (jnp.zeros((nu, batch_size, cfg.ssm.conv_width - 1, conv_ch),
                          jnp.float32),
                jnp.zeros((nu, batch_size, nh, cfg.ssm.head_dim,
                           cfg.ssm.state_dim), jnp.float32))

    if cfg.kind == SSM:
        cs, ss = ssm_state()
        return {"conv": cs, "ssm": ss, "pos": jnp.zeros((), jnp.int32)}
    if cfg.kind == HYBRID:
        k, v = kv()
        cs, ss = ssm_state()
        return {"k": k, "v": v, "conv": cs, "ssm": ss,
                "pos": jnp.zeros((), jnp.int32)}
    if cfg.use_mla:
        return {"ckv": jnp.zeros((nu, batch_size, c, cfg.mla_kv_lora_rank),
                                 dtype),
                "kr": jnp.zeros((nu, batch_size, c, cfg.mla_rope_head_dim),
                                dtype),
                "pos": jnp.zeros((), jnp.int32)}
    if has_dense_sub:
        k1, v1 = kv()
        k2, v2 = kv()
        return {"k": k1, "v": v1, "k2": k2, "v2": v2,
                "pos": jnp.zeros((), jnp.int32)}
    k, v = kv()
    return {"k": k, "v": v, "pos": jnp.zeros((), jnp.int32)}


def _cache_from_full(cfg, caches, seq_len: int, batch_size: int, dtype,
                     max_len: Optional[int] = None):
    """Convert prefill per-layer outputs into the decode cache layout.

    ``max_len`` sets the cache capacity (>= seq_len) so decode has
    headroom past the prefill; KV entries are written left-aligned at
    their true positions (ring-buffer layout when sliding window).
    """
    c = _cache_len(cfg, max_len or seq_len)

    def fit(arr):  # (nu, B, S, ...) -> (nu, B, c, ...) in decode layout
        s = arr.shape[2]
        if s > c:
            # ring buffer (sliding window): keep the last c positions and
            # place position p at slot p % c so decode writes line up
            arr = arr[:, :, s - c:]
            return jnp.roll(arr, shift=(s - c) % c, axis=2)
        if s < c:
            pad = [(0, 0)] * arr.ndim
            pad[2] = (0, c - s)
            arr = jnp.pad(arr, pad)
        return arr

    pos = jnp.asarray(seq_len, jnp.int32)
    if cfg.kind == SSM:
        cs, ss = caches
        return {"conv": cs, "ssm": ss, "pos": pos}
    if cfg.kind == HYBRID:
        k, v, cs, ss = caches
        return {"k": fit(k.astype(dtype)), "v": fit(v.astype(dtype)),
                "conv": cs, "ssm": ss, "pos": pos}
    if cfg.use_mla:
        ckv, kr = caches
        return {"ckv": fit(ckv.astype(dtype)), "kr": fit(kr.astype(dtype)),
                "pos": pos}
    per_unit, has_dense_sub = _unit_layout(cfg)
    if has_dense_sub:
        (k1, v1), (k2, v2) = caches
        return {"k": fit(k1.astype(dtype)), "v": fit(v1.astype(dtype)),
                "k2": fit(k2.astype(dtype)), "v2": fit(v2.astype(dtype)),
                "pos": pos}
    k, v = caches
    return {"k": fit(k.astype(dtype)), "v": fit(v.astype(dtype)), "pos": pos}


def prefill(params, cfg: ModelConfig, batch, *, dtype=None,
            max_len: Optional[int] = None):
    """Full-sequence forward that also returns the decode cache.

    ``max_len`` (>= seq_len) sets the decode-cache capacity; defaults to
    the prefill length (no decode headroom).
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    b, s = batch["tokens"].shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _embed_input(params, cfg, batch, dtype)
    angles = _angles_for(cfg, batch, positions)
    x, aux, caches = _run_layers_full(params, cfg, x, angles, positions,
                                      causal=True, want_cache=True)
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = _logits(params, cfg, x)
    cache = _cache_from_full(cfg, caches, s, b, dtype, max_len=max_len)
    return logits, cache


def _block_decode(cfg, lp, x, angles, cache_slices, pos):
    if cfg.kind == SSM:
        h = _apply_norm(cfg, lp["norm"], x)
        y, (cs, ss) = mamba_lib.mamba2_decode(
            lp["mixer"], cfg, h, conv_state=cache_slices["conv"],
            ssm_state=cache_slices["ssm"])
        return x + y.astype(x.dtype), {"conv": cs, "ssm": ss}
    h = _apply_norm(cfg, lp["attn_norm"], x)
    if cfg.kind == HYBRID:
        y, (ck, cv, cs, ss) = hymba_lib.hymba_decode(
            lp["mixer"], cfg, h, angles,
            cache_k=cache_slices["k"], cache_v=cache_slices["v"], pos=pos,
            conv_state=cache_slices["conv"], ssm_state=cache_slices["ssm"])
        new = {"k": ck, "v": cv, "conv": cs, "ssm": ss}
    elif cfg.use_mla:
        decode_fn = attn_lib.mla_decode_absorbed if cfg.mla_absorb \
            else attn_lib.mla_decode
        y, (ckv, kr) = decode_fn(
            lp["mixer"], cfg, h, angles,
            cache_ckv=cache_slices["ckv"], cache_kr=cache_slices["kr"],
            pos=pos)
        new = {"ckv": ckv, "kr": kr}
    else:
        y, (ck, cv) = attn_lib.gqa_decode(
            lp["mixer"], cfg, h, angles, cache_k=cache_slices["k"],
            cache_v=cache_slices["v"], pos=pos)
        new = {"k": ck, "v": cv}
    x = x + y.astype(x.dtype)
    h = _apply_norm(cfg, lp["ffn_norm"], x)
    if "router" in lp["ffn"]:
        y, _ = moe_lib.moe_apply(lp["ffn"], cfg, h)
    elif cfg.activation == "gelu":
        y = gelu_mlp(lp["ffn"], h)
    else:
        y = swiglu(lp["ffn"], h)
    return x + y.astype(x.dtype), new


def decode_step(params, cfg: ModelConfig, cache, tokens, *, batch=None,
                dtype=None):
    """Decode ONE token.  tokens (B, 1).  Returns (logits, new cache)."""
    _refuse_leading_dense(cfg)
    dtype = dtype or jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    x = embed(params["embed"], tokens, dtype)
    angles = _angles_for(cfg, batch or {}, positions)
    per_unit, has_dense_sub = _unit_layout(cfg)

    if cfg.kind == SSM:
        keys = ("conv", "ssm")
    elif cfg.kind == HYBRID:
        keys = ("k", "v", "conv", "ssm")
    elif cfg.use_mla:
        keys = ("ckv", "kr")
    elif has_dense_sub:
        keys = ("k", "v", "k2", "v2")
    else:
        keys = ("k", "v")

    xs_cache = {k: cache[k] for k in keys}

    def unit_fn(x, inp):
        lp, csl = inp
        if has_dense_sub:
            x, n1 = _block_decode(cfg, lp["dense_sub"], x, angles,
                                  {"k": csl["k"], "v": csl["v"]}, pos)
            x, n2 = _block_decode(cfg, lp["moe_sub"], x, angles,
                                  {"k": csl["k2"], "v": csl["v2"]}, pos)
            return x, {"k": n1["k"], "v": n1["v"],
                       "k2": n2["k"], "v2": n2["v"]}
        x, new = _block_decode(cfg, lp, x, angles, csl, pos)
        return x, new

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(unit_fn, x, (params["layers"], xs_cache))
    else:
        nu = cfg.num_layers // per_unit
        outs = []
        for i in range(nu):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            csl = jax.tree_util.tree_map(lambda a: a[i], xs_cache)
            x, new = unit_fn(x, (lp, csl))
            outs.append(new)
        new_cache = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    x = _apply_norm(cfg, params["final_norm"], x)
    logits = _logits(params, cfg, x)
    out_cache = dict(new_cache)
    out_cache["pos"] = pos + 1
    return logits, out_cache


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def xent_loss(logits, labels, mask=None):
    """Mean masked token cross-entropy; returns (sum_loss, num_tokens).

    Returning the (sum, count) pair instead of the mean is what lets the
    federated protocol apply the exact Eq. (2) sample-count weighting.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask), jnp.sum(mask)


def train_loss(params, cfg: ModelConfig, batch, *, dtype=None):
    """Scalar mean loss (+ MoE aux) for a local batch."""
    logits, aux = forward_train(params, cfg, batch, dtype=dtype)
    if cfg.kind == AUDIO:
        labels, mask = batch["targets"], batch["frame_mask"]
    else:
        labels = batch["labels"]
        mask = batch.get("loss_mask")
    s, n = xent_loss(logits, labels, mask)
    loss = s / jnp.maximum(n, 1.0)
    if _switch_aux(cfg):
        loss = loss + cfg.moe.router_aux_weight * aux
    return loss


def _switch_aux(cfg) -> bool:
    """Whether the MoE layers' second output is the Switch aux loss (the
    capacity router); ``noaux_tc`` routing has none and counts pairs."""
    return cfg.kind == MOE and cfg.moe.routing != "noaux_tc"


def train_loss_sum(params, cfg: ModelConfig, batch, *, dtype=None):
    """``(sum_loss, num_tokens)`` form of :func:`train_loss` — the
    mask-aware objective the federated stacked (vmap) path needs.

    A ``doc_mask`` row mask (zero-padded cohort rows, see
    ``data/federated_split.stacked_round_batches``) multiplies into the
    token mask so padded documents stay out of the objective AND its
    gradient; the MoE router aux folds in as ``aux * n`` so the masked
    mean ``sum / count`` equals :func:`train_loss` on the unpadded batch
    (aux is still computed over padded rows — all-zero token rows — so
    a PADDED MoE client deviates by the aux share of those rows;
    docs/lm_federation.md lists it as a known limit).

    With ``noaux_tc`` routing there is no aux loss, and a third item,
    ``{"expert_tokens": (E_held,)}``, gives the routed (token, held
    expert) pairs of every MoE layer (padded rows included), which the
    federated round sums into its record.
    """
    logits, aux = forward_train(params, cfg, batch, dtype=dtype)
    if cfg.kind == AUDIO:
        labels, mask = batch["targets"], batch["frame_mask"]
    else:
        labels = batch["labels"]
        mask = batch.get("loss_mask")
    mask = jnp.ones(labels.shape, jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    doc_mask = batch.get("doc_mask")
    if doc_mask is not None:
        mask = mask * doc_mask[..., None]
    s, n = xent_loss(logits, labels, mask)
    if _switch_aux(cfg):
        s = s + cfg.moe.router_aux_weight * aux * n
    elif cfg.kind == MOE:
        return s, n, {"expert_tokens": aux}
    return s, n
