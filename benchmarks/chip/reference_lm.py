"""Plain reference of the Moonlight cut's rounds, and its weights.

Nothing here imports the program.  The model functions are a copy of
``src/repro/models/reference_moonlight.py`` (its docstring gives the layer
equations and every departure), in straightforward ``jax.numpy``, run in
float32 at ``highest`` matmul precision; ``dtype=jnp.bfloat16`` gives the
control, parameters, updates and activations one precision lower.  On
the chip one 8k sequence fits in blocks: attention by ``q_block`` query
rows and the head by ``head_block`` tokens, each block and each layer
under ``jax.checkpoint``; the held experts run dense over every token,
weighted by their gates.

* :func:`init_params` — the benchmark's own weights from a seed, in the
  program's layout (``models/transformer.py``), made on the device;
* :func:`sync_rounds` — the cross-silo rounds: all L silos each round,
  E plain SGD steps of one sequence each (the minibatch draw of
  ``reference.draws``), the Eq. (2) weighted mean of the deltas and
  FedAvg's server step (lr 1), summed in host memory.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference import cohort, draws

tmap = jax.tree_util.tree_map


def arch_of(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the model functions read, from the configuration."""
    return {"heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"],
            "scaling": cfg["routed_scaling_factor"],
            "held_lo": cfg["experts_held"][0],
            "held": cfg["n_routed_experts"]}


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


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def init_params(seed: int, cfg: Dict[str, Any]):
    """The cut's weights (float32, on the default device) from ``seed``:
    each matrix truncated-normal at its fan-in, the embedding N(0, 0.02),
    norms 1, the selection bias N(0, ``selection_bias_std``)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kv, f, v = cfg["kv_lora_rank"], cfg["moe_intermediate_size"], \
        cfg["vocab_size"]
    e, g = cfg["n_routed_experts"], cfg["router_outputs"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense

    def dense(k, shape):
        return shape[-2] ** -0.5 * jax.random.truncated_normal(
            k, -2.0, 2.0, shape, jnp.float32)

    def layer(k, ffn):
        ks = jax.random.split(k, 6)
        return {"attn_norm": {"scale": jnp.ones(d)},
                "ffn_norm": {"scale": jnp.ones(d)},
                "mixer": {"w_q": dense(ks[0], (d, h * (dn + dr))),
                          "w_dkv": dense(ks[1], (d, kv)),
                          "kv_norm": {"scale": jnp.ones(kv)},
                          "w_kr": dense(ks[2], (d, dr)),
                          "w_ukv": dense(ks[3], (kv, h * (dn + dv))),
                          "wo": dense(ks[4], (h * dv, d))},
                "ffn": ffn(ks[5])}

    def swiglu_w(k, width, lead=()):
        ks = jax.random.split(k, 3)
        return {"w_gate": dense(ks[0], lead + (d, width)),
                "w_up": dense(ks[1], lead + (d, width)),
                "w_down": dense(ks[2], lead + (width, d))}

    def moe_ffn(k):
        ks = jax.random.split(k, 3)
        return {"router": dense(ks[0], (d, g)),
                "router_bias": cfg["selection_bias_std"]
                * jax.random.normal(ks[1], (g,), jnp.float32),
                **swiglu_w(ks[2], f, (e,)),
                "shared": swiglu_w(jax.random.fold_in(ks[2], 1),
                                   cfg["n_shared_experts"] * f)}

    def make(key):
        ks = jax.random.split(key, 4)
        stack = lambda k, n, ffn: jax.vmap(  # noqa: E731
            lambda kk: layer(kk, ffn))(jax.random.split(k, n))
        return {"embed": {"table": 0.02 * jax.random.normal(
                    ks[0], (v, d), jnp.float32)},
                "lm_head": {"w": dense(ks[1], (d, v))},
                "final_norm": {"scale": jnp.ones(d)},
                "dense_layers": stack(ks[2], n_dense, lambda k: swiglu_w(
                    k, cfg["intermediate_size"])),
                "layers": stack(ks[3], n_moe, moe_ffn)}
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


# ---------------------------------------------------------------------------
# the cross-silo rounds
# ---------------------------------------------------------------------------
def _step_fn(arch, lr: float, dtype, q_block, head_block):
    def step(p, doc):
        def loss(p):
            return loss_sum(p, arch, doc[:-1], doc[1:], dtype=dtype,
                            q_block=q_block, head_block=head_block)[0] \
                / (doc.shape[0] - 1)
        value, g = jax.value_and_grad(loss)(p)
        return value, tmap(lambda a, b: (a - lr * b).astype(dtype), p, g)
    return jax.jit(step, donate_argnums=0)


def sync_rounds(params0, node_tokens, cfg: Dict[str, Any],
                rcfg: Dict[str, Any], seed: int, rounds: int, *,
                dtype=jnp.float32, q_block: Optional[int] = 1024,
                head_block: Optional[int] = 2048):
    """Run ``rounds`` rounds from ``params0``; return the params after
    each round, each round's loss, and the norm of each leaf of round 1's
    mean local delta (the gradient the check's leaf rule reads).  The
    global parameters and the Eq. (2) sum stay in host memory; the chip
    holds one client's local parameters and its step."""
    L, K, E, P = rcfg["num_clients"], rcfg["clients_per_round"], \
        rcfg["local_epochs"], rcfg["batch"]
    num_docs = len(node_tokens[0])
    step = _step_fn(arch_of(cfg), rcfg["lr"], dtype, q_block, head_block)
    prec = "highest" if dtype == jnp.float32 else None
    params = tmap(lambda x: np.array(x, np.float32), params0)
    out_params: List[Any] = []
    out_loss: List[float] = []
    grad_norms = None
    for r in range(rounds):
        rk = jax.random.PRNGKey(seed * 100003 + r)
        ids = cohort(L, K, seed, r)
        idx, _ = draws(rk, ids, num_docs, P, E)
        acc = tmap(np.zeros_like, params)
        total, losses = 0.0, []
        for j, c in enumerate(ids):
            local = tmap(lambda x: jnp.asarray(x, dtype), params)
            client = []
            for s in range(E):
                for doc in node_tokens[c][idx[j, s]]:
                    with jax.default_matmul_precision(prec):
                        value, local = step(local, jnp.asarray(doc))
                    client.append(float(value))
            w = float(E * idx.shape[2])
            for a, lo, p in zip(jax.tree_util.tree_leaves(acc),
                                jax.tree_util.tree_leaves(local),
                                jax.tree_util.tree_leaves(params)):
                a += w * (np.asarray(lo, np.float32) - p)
            del local
            total += w
            losses.append(np.mean(client))
        bar = tmap(lambda a: a / total, acc)
        if grad_norms is None:
            grad_norms = tmap(lambda a: float(np.linalg.norm(a.ravel())),
                              bar)
        params = tmap(lambda p, b: p + b, params, bar)
        out_params.append(params)
        out_loss.append(float(np.mean(losses)))
    return out_params, out_loss, grad_norms
