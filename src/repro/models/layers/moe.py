"""Mixture-of-Experts block — scatter/gather (all-to-all) dispatch.

Expert-parallel: expert parameters lead with the ``E`` axis (sharding rule
``experts -> model``); tokens are scattered into per-expert capacity
buffers and gathered back, which GSPMD lowers to the canonical MoE
all-to-all when token sharding (data) differs from expert sharding
(model).  Unlike the GShard one-hot-einsum dispatch, no (T, E, C) tensor
is ever materialized and no fake matmul FLOPs pollute the roofline —
dispatch is real indexing.

Capacity semantics: global top-k with per-expert capacity
``C = ceil(T * k * cf / E)``; tokens routed past capacity are dropped
(combine weight zero) — standard TPU MoE.  With a large
``capacity_factor`` nothing drops and the layer is exactly the dense
top-k mixture (property-tested).

Router aux loss is the Switch load-balance term ``E * sum_e f_e * p_e``;
under the federated protocol it aggregates with the same Eq. (2) client
weights as the task loss.

``routing="noaux_tc"`` (DeepSeek-V3, Moonlight) is a second layer,
:func:`held_moe_apply`: sigmoid routing over all experts, computed for
the share of experts this chip holds, with no capacity and no aux loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro import spans
from repro.models.layers.init import dense_init
from repro.parallel.sharding import constrain_batch, constrain_expert_rows

# std of the seeded selection bias of ``noaux_tc`` routing: trained
# models learn it and publish none, so it is drawn, large enough that the
# chosen experts differ from a plain top-k of the scores
SELECTION_BIAS_STD = 0.05


def _expert_init(key, shape):
    """(E, a, b) expert weights, each at its own fan-in ``a``."""
    return jax.vmap(lambda k: dense_init(k, shape[1:]))(
        jax.random.split(key, shape[0]))


def moe_init(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.moe.num_held
    ks = jax.random.split(key, 5)
    # the capacity layer keeps its historical fan-in (the expert count)
    ex = _expert_init if cfg.moe.routing == "noaux_tc" else dense_init
    p = {
        "router": dense_init(ks[0], (d, cfg.moe.num_experts)),
        "w_gate": ex(ks[1], (e, d, f)),
        "w_up": ex(ks[2], (e, d, f)),
        "w_down": ex(ks[3], (e, f, d)),
    }
    if cfg.moe.num_shared_experts:
        sk = jax.random.split(ks[4], 3)
        ns = cfg.moe.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(sk[0], (d, ns * f)),
            "w_up": dense_init(sk[1], (d, ns * f)),
            "w_down": dense_init(sk[2], (ns * f, d)),
        }
    if cfg.moe.routing == "noaux_tc":
        # e_score_correction_bias: a buffer, not trained by gradient
        p["router_bias"] = SELECTION_BIAS_STD * jax.random.normal(
            jax.random.fold_in(key, 5), (cfg.moe.num_experts,), jnp.float32)
    return p


def aux_zeros(cfg):
    """The zero of a MoE layer's second output, summed over layers: the
    Switch aux loss (a scalar), or with ``noaux_tc`` routing the routed
    (token, held expert) pairs per held expert."""
    if cfg.kind == "moe" and cfg.moe.routing == "noaux_tc":
        return jnp.zeros((cfg.moe.num_held,), jnp.float32)
    return jnp.zeros((), jnp.float32)


def noaux_route(params, cfg, xt):
    """DeepSeek-V3 ``noaux_tc`` routing of tokens ``xt`` (T, D), in fp32,
    over all ``num_experts``: scores ``s = sigmoid(x W_r)``, experts
    ``top_k(s + b)`` with the selection bias ``b`` (no gradient), gates
    ``routed_scaling_factor * s_i / sum_{j chosen} s_j``.  Returns
    (expert ids (T, k), gates (T, k))."""
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(params["router_bias"])
    _, idx = jax.lax.top_k(scores + bias, cfg.moe.top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=1)
    gates = cfg.moe.routed_scaling_factor * chosen \
        / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, gates


def held_moe_apply(params, cfg, x):
    """x (B, S, D) -> (y (B, S, D), pairs per held expert (E_held,) fp32).

    The layer of one chip of an expert-parallel deployment: it routes
    every token over all ``num_experts`` (:func:`noaux_route`) and adds
    the part its ``num_held`` experts (ids ``ep_rank * num_held`` on)
    give, plus the shared experts.  No pair is dropped: the (token,
    expert) pairs are sorted by held expert, those of other chips' experts
    last, and the held experts run as one grouped matrix product
    (``jax.lax.ragged_dot``) over the sorted rows.  Rows past the held
    pairs are zeroed on the way in and out, so whatever the grouped
    product leaves there never reaches the result or its gradient.
    """
    b, s, d = x.shape
    t, k = b * s, cfg.moe.top_k
    nh = cfg.moe.num_held
    xt = x.reshape(t, d)
    with spans.scope(spans.MOE_ROUTE):
        idx, gates = noaux_route(params, cfg, xt)
        local = idx - cfg.moe.ep_rank * nh
        held = (local >= 0) & (local < nh)                      # (T, k)
        key = jnp.where(held, local, nh).reshape(t * k)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((nh + 1,), jnp.int32).at[key].add(1)[:nh]
        live = jnp.arange(t * k) < jnp.sum(sizes)
        rows = jnp.where(live[:, None], xt[order // k], 0)
    with spans.scope(spans.MOE_EXPERTS):
        def grouped(lhs, w):
            return grouped_matmul(lhs, w.astype(x.dtype), sizes)
        h = jax.nn.silu(grouped(rows, params["w_gate"])) \
            * grouped(rows, params["w_up"])
        out = grouped(h, params["w_down"])                      # (T*k, D)
    with spans.scope(spans.MOE_ROUTE):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        pairs = jnp.where(held[..., None], out[back].reshape(t, k, d), 0)
        y = jnp.einsum("tk,tkd->td", jnp.where(held, gates, 0.0).astype(
            x.dtype), pairs)
    if cfg.moe.num_shared_experts:
        with spans.scope(spans.MOE_SHARED):
            y = y + _shared(params["shared"], xt)
    return y.reshape(b, s, d), sizes.astype(jnp.float32)


def _per_row(fn):
    """``fn`` with a batching rule that runs it once per batch row
    (``lax.map``): ``ragged_dot`` batches only when every operand carries
    the batch at axis 0, and its weight gradient not at all, so a
    vmapped cohort runs its clients' grouped products one by one."""
    fn = custom_batching.custom_vmap(fn)

    @fn.def_vmap
    def rule(axis_size, batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, batched)]
        return jax.lax.map(lambda a: fn(*a), tuple(args)), True
    return fn


@_per_row
def _ragged(lhs, w, sizes):
    return jax.lax.ragged_dot(lhs, w, sizes)


@_per_row
def _ragged_weight_grad(lhs, dy, sizes):
    shape = jax.ShapeDtypeStruct((sizes.shape[0], lhs.shape[1],
                                  dy.shape[1]), lhs.dtype)
    (dw,) = jax.linear_transpose(
        lambda w: jax.lax.ragged_dot(lhs, w, sizes), shape)(dy)
    return dw


@jax.custom_vjp
def grouped_matmul(lhs, w, sizes):
    """``jax.lax.ragged_dot``: rows ``lhs`` (M, K) in consecutive groups
    of ``sizes`` (G,) rows times ``w`` (G, K, N).  Rows past
    ``sum(sizes)`` belong to no group.  The same products in every
    context, with a gradient that also holds under ``jax.vmap``."""
    return _ragged(lhs, w, sizes)


def _grouped_fwd(lhs, w, sizes):
    return _ragged(lhs, w, sizes), (lhs, w, sizes)


def _grouped_bwd(res, dy):
    lhs, w, sizes = res
    return (_ragged(dy, jnp.swapaxes(w, 1, 2), sizes),
            _ragged_weight_grad(lhs, dy, sizes), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def _shared(sp, xf):
    sg = jnp.einsum("td,df->tf", xf, sp["w_gate"].astype(xf.dtype))
    su = jnp.einsum("td,df->tf", xf, sp["w_up"].astype(xf.dtype))
    return jnp.einsum("tf,fd->td", jax.nn.silu(sg) * su,
                      sp["w_down"].astype(xf.dtype))


def capacity(num_tokens: int, cfg) -> int:
    e = cfg.moe.num_experts
    c = int(num_tokens * cfg.moe.top_k * cfg.moe.capacity_factor / e)
    return max(c, 1)


def _num_groups(cfg, batch: int) -> int:
    """Routing groups (GShard): groups align with the data-axis sharding
    so position assignment is shard-local — no cross-device cumsums."""
    g = cfg.moe.num_groups
    while batch % g:
        g //= 2
    return max(g, 1)


def moe_apply(params, cfg, x):
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar fp32); with
    ``noaux_tc`` routing, :func:`held_moe_apply`.

    GShard-style GROUPED dispatch (EXPERIMENTS.md §Perf pair B): tokens
    are routed within ``G`` groups laid out along the batch dim (aligned
    with the data-axis sharding), so the position-in-expert cumsum is
    local to a shard; each group owns a per-expert capacity slice of the
    dispatch buffer, and the scatter/gather across the expert-sharded
    buffer is the canonical MoE all-to-all.
    """
    if cfg.moe.routing == "noaux_tc":
        return held_moe_apply(params, cfg, x)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    grp = _num_groups(cfg, b)
    tg = t // grp                          # tokens per group
    cg = max(int(tg * k * cfg.moe.capacity_factor / e), 1)
    # pin the group dim to the data axis: groups == data shards, so all
    # routing math below is shard-local (no cross-device cumsums)
    xt = constrain_batch(x.reshape(grp, tg, d))

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (G, Tg, E)
    topk_p, topk_i = jax.lax.top_k(probs, k)                    # (G, Tg, k)
    if k > 1:
        topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

    # ---- slot-by-slot position assignment, group-local -------------------
    drop_row = e * grp * cg
    fill = jnp.zeros((grp, e), jnp.float32)
    dests, gates = [], []
    dispatch_frac = jnp.zeros((e,), jnp.float32)
    goff = jnp.arange(grp, dtype=jnp.int32)[:, None] * cg       # (G, 1)
    for slot in range(k):
        eid = topk_i[..., slot]                                 # (G, Tg)
        onehot = jax.nn.one_hot(eid, e, dtype=jnp.float32)      # (G, Tg, E)
        before = jnp.cumsum(onehot, axis=1) - onehot            # group-local
        pos = jnp.take_along_axis(
            before, eid[..., None], axis=2)[..., 0] \
            + jnp.take_along_axis(fill, eid, axis=1)            # (G, Tg)
        keep = pos < cg
        # buffer layout: expert-major, then group, then slot-in-group —
        # rows of one expert are contiguous, so expert-sharding the
        # buffer never splits a (group, expert) slice
        dest = jnp.where(keep,
                         eid * (grp * cg) + goff + pos.astype(jnp.int32),
                         drop_row)
        dests.append(dest)
        gates.append(topk_p[..., slot] * keep)
        fill = fill + jnp.sum(onehot * keep[..., None], axis=1)
        dispatch_frac = dispatch_frac + jnp.mean(onehot, axis=(0, 1))

    # ---- dispatch: scatter into (E*G*Cg [+pad], D) ------------------------
    pad_rows = 256
    expert_in = jnp.zeros((e * grp * cg + pad_rows, d), x.dtype)
    flat_x = xt.reshape(t, d)
    for dest in dests:
        expert_in = expert_in.at[dest.reshape(t)].add(flat_x)
    expert_in = expert_in[:e * grp * cg].reshape(e, grp * cg, d)

    # ---- expert FFN (expert-parallel; weights FSDP-gathered) -------------
    g_ = jnp.einsum("ecd,edf->ecf", expert_in,
                    params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", expert_in,
                   params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g_) * u
    expert_out = jnp.einsum("ecf,efd->ecd", h,
                            params["w_down"].astype(x.dtype))
    expert_out = jnp.concatenate(
        [expert_out.reshape(e * grp * cg, d),
         jnp.zeros((pad_rows, d), x.dtype)], axis=0)

    # ---- combine ----------------------------------------------------------
    y = jnp.zeros((t, d), x.dtype)
    for dest, gate in zip(dests, gates):
        y = y + gate.reshape(t)[:, None].astype(x.dtype) \
            * expert_out[dest.reshape(t)]

    # Switch load-balance aux: E * sum_e f_e p_e
    p_mean = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum((dispatch_frac / k) * p_mean)

    if cfg.moe.num_shared_experts:
        y = y + _shared(params["shared"], x.reshape(t, d))

    return y.reshape(b, s, d), aux.astype(jnp.float32)
