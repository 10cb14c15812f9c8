"""Sharding rules: ModelConfig + mesh -> PartitionSpecs for every pytree.

Scheme (DESIGN.md §5): FSDP x TP.
  * batch dims -> the client/data axes ("pod","data") — each slice along
    them is one federated client;
  * parameters -> fully sharded: the TP-natural dim over "model", the
    other large dim over "data" (ZeRO-3-like).  The paper's plain-SGD
    server update keeps optimizer state == params, so this is also the
    full optimizer-state sharding;
  * decode caches -> batch over data; kv-heads over "model" when
    divisible, else the sequence dim over "model" (granite's MQA kv=1).

Rules are *name-based* over the parameter pytree paths — one table, every
architecture.  Stacked (scanned) layer params get a leading None.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import AUDIO, HYBRID, MOE, NTM, SSM, ModelConfig


def fed_mesh(data: int) -> Mesh:
    """The federation's ``("data",)``-axis device mesh over the first
    ``data`` local devices (``FederationSpec`` ``execution.mesh``).

    The fused round graphs shard their client axes over it: the stacked
    ``(K, ...)`` cohort, the ``(L, ...)`` per-client state trees and the
    ``(C, ...)`` straggler ring all split along ``"data"`` while params
    and server state stay replicated.  ``data=1`` is a real one-device
    mesh (the sharded code path, no cross-device traffic), so the path
    is exercisable on single-device hosts.
    """
    if data < 1:
        raise ValueError(f"fed_mesh needs data >= 1, got {data}")
    devs = jax.devices()
    if len(devs) < data:
        raise ValueError(
            f"execution.mesh data={data} needs {data} devices but only "
            f"{len(devs)} are visible; on a CPU host export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data} "
            "before importing jax (the CI host-mesh leg does exactly "
            "this), or shrink the mesh")
    return Mesh(np.asarray(devs[:data]), ("data",))


# ---------------------------------------------------------------------------
# sharding profile
# ---------------------------------------------------------------------------
# "megatron" (baseline): batch over (pod, data); params TP over "model" on
#   the feature dim + ZeRO over "data" — per-layer activation all-reduces.
# "fsdp" (optimized, EXPERIMENTS.md §Perf): batch over ALL axes; params
#   fully sharded over the flattened mesh and all-gathered per use — the
#   per-layer wire volume is params, not activations.  Requires
#   global_batch >= total chips (true for train_4k/decode_32k).
# "tp" (decode-optimized, §Perf pair C): params sharded over "model"
#   ONLY (replicated across data) — decode steps stop re-gathering the
#   whole model every token; batch/caches stay on the data axes.
_PROFILE = "megatron"


def set_profile(name: str) -> None:
    global _PROFILE
    assert name in ("megatron", "fsdp", "tp"), name
    _PROFILE = name


def get_profile() -> str:
    return _PROFILE


def _axes(mesh: Mesh) -> Tuple[Any, str]:
    """(data-like axes tuple, model axis name) for this mesh."""
    names = mesh.axis_names
    if _PROFILE == "fsdp":
        flat = tuple(n for n in names if n in ("pod", "data", "model"))
        return (flat if len(flat) > 1 else flat[0]), None
    model = "model" if "model" in names else None
    data = tuple(n for n in names if n in ("pod", "data"))
    if len(data) == 1:
        return data[0], model
    return data, model


def _mesh_sizes(mesh) -> dict:
    return dict(mesh.shape)   # works for Mesh and AbstractMesh


def _model_size(mesh: Mesh) -> int:
    return _mesh_sizes(mesh).get("model", 1)


def _divisible(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def _leaf_spec(path: str, leaf, cfg: ModelConfig, dp, tp,
               mesh: Mesh) -> P:
    """Name-based rule table.  ``path`` is '/'-joined pytree keys."""
    ndim = leaf.ndim
    stacked = path.startswith("layers/")
    lead = (None,) if stacked else ()
    body = ndim - len(lead)
    name = path.rsplit("/", 1)[-1]
    parent = path.rsplit("/", 2)[-2] if path.count("/") >= 1 else ""

    def spec(*s):
        return P(*lead, *s)

    # ---- NTM (small, replicated-data-parallel) --------------------------
    if cfg.kind == NTM:
        if name == "beta":
            return P(None, tp)          # (K, V): vocab over model
        if name == "w" and body == 2:
            return P(None, tp)
        return P()

    # ---- norms / scalar-ish --------------------------------------------
    if body <= 1:
        return spec() if body == 0 else spec(None)

    # ---- embeddings ------------------------------------------------------
    if path == "embed/table":
        return P(tp, dp)                # (V, D)
    if path == "pos_embed":
        return P(None, tp)
    if path in ("lm_head/w", "pred_head/w"):
        return P(dp, tp)                # (D, V)
    if path == "frontend_proj/w":
        return P(dp, tp)

    # ---- MoE experts -----------------------------------------------------
    if name == "router":
        return spec(dp, None)           # (D, E) — E small, replicate
    if parent in ("ffn", "moe_sub/ffn") or "/ffn/" in path or \
            path.endswith(("w_gate", "w_up", "w_down")):
        if body == 3:                   # (E, D, F) expert-parallel
            if name == "w_down":
                return spec(tp, None, dp)
            return spec(tp, dp, None)
        if name == "w_down":            # (F, D)
            return spec(tp, dp)
        return spec(dp, tp)             # (D, F)

    # ---- attention -------------------------------------------------------
    if name in ("wq", "wk", "wv", "w_uq", "w_ukv", "w_dq", "w_dkv", "w_kr",
                "in_proj"):
        return spec(dp, tp)
    if name in ("wo", "out_proj"):
        return spec(tp, dp)
    if name == "conv_w":                # (W, ch) depthwise
        return spec(None, tp)

    # ---- fallback: shard the biggest dim over model ----------------------
    if body == 2:
        return spec(dp, tp) if leaf.shape[-1] >= leaf.shape[-2] \
            else spec(tp, dp)
    return spec(*([None] * body))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    sizes = _mesh_sizes(mesh)
    if isinstance(entry, (tuple, list)):
        n = 1
        for e in entry:
            n *= sizes[e]
        return n
    return sizes[entry]


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes whose size doesn't divide the dim — jit input
    shardings (unlike internal GSPMD ops) require exact divisibility."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        if dim % _axis_size(mesh, entry) == 0:
            out.append(entry)
            continue
        # try the individual axes of a tuple entry before giving up
        if isinstance(entry, (tuple, list)):
            kept = None
            for e in entry:
                if dim % _axis_size(mesh, e) == 0:
                    kept = e
                    break
            out.append(kept)
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_partition_specs(cfg: ModelConfig, mesh: Mesh, params_shape) -> Any:
    """PartitionSpec pytree matching ``params_shape`` (from eval_shape)."""
    dp, tp = _axes(mesh)

    pdp = None if _PROFILE == "tp" else dp   # tp: no data-axis sharding
    #   of parameters (replicated across data, sharded over model only)

    def to_spec(path, leaf):
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        spec = _leaf_spec(pstr, leaf, cfg, pdp, tp, mesh)
        return sanitize_spec(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(to_spec, params_shape)


# ---------------------------------------------------------------------------
# batches
# ---------------------------------------------------------------------------
def batch_partition_spec(cfg: ModelConfig, mesh: Mesh, batch_shape) -> Any:
    dp, tp = _axes(mesh)

    def to_spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "mrope_positions":       # (3, B, S)
            spec = P(None, dp, None)
        elif name == "rng":
            spec = P()
        else:
            # default: leading dim is batch
            spec = P(dp, *([None] * (leaf.ndim - 1)))
        return sanitize_spec(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(to_spec, batch_shape)


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------
def cache_partition_specs(cfg: ModelConfig, mesh: Mesh, cache_shape,
                          *, seq_over_model_threshold: bool = True) -> Any:
    dp, tp = _axes(mesh)
    tp_size = _model_size(mesh)
    kv_on_heads = _divisible(cfg.num_kv_heads, tp_size)

    def to_spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "pos":
            return P()
        if name in ("k", "v", "k2", "v2"):   # (nu, B, C, Hkv, hd)
            if kv_on_heads:
                return P(None, dp, None, tp, None)
            return P(None, dp, tp, None, None)   # shard the sequence
        if name in ("ckv", "kr"):            # (nu, B, C, r)
            if cfg.mla_absorb:
                # absorbed decode contracts r on-device; keep the latent
                # whole and shard batch only (EXPERIMENTS.md §Perf C)
                return P(None, dp, None, None)
            return P(None, dp, None, tp)
        if name == "conv":                   # (nu, B, W-1, ch)
            return P(None, dp, None, tp)
        if name == "ssm":                    # (nu, B, H, P, N)
            d_in = cfg.ssm.expand * cfg.d_model
            nh = d_in // cfg.ssm.head_dim
            if _divisible(nh, tp_size):
                return P(None, dp, tp, None, None)
            return P(None, dp, None, None, None)
        return P(*([None] * leaf.ndim))

    def sanitized(path, leaf):
        return sanitize_spec(to_spec(path, leaf), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(sanitized, cache_shape)


def shardings_for(mesh: Mesh, specs) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# in-model activation constraints (ambient-mesh aware, no-op off-mesh)
# ---------------------------------------------------------------------------
def _ambient():
    """(dp axes, model axis, sizes) from the ambient abstract mesh, or
    (None, None, {}) when no mesh is active (single-device tests).
    Respects the active sharding profile."""
    am = jax.sharding.get_abstract_mesh()
    if not am.axis_names:
        return None, None, {}
    sizes = dict(am.shape)
    if _PROFILE == "fsdp":
        dp = tuple(n for n in ("pod", "data", "model") if n in sizes)
        tp = None
    else:
        dp = tuple(n for n in ("pod", "data") if n in sizes)
        tp = "model" if "model" in sizes else None
    if not dp:
        dp = None
    elif len(dp) == 1:
        dp = dp[0]
    return dp, tp, sizes


def _fits(dim: int, entry, sizes) -> bool:
    if entry is None:
        return True
    es = entry if isinstance(entry, (tuple, list)) else (entry,)
    n = 1
    for e in es:
        n *= sizes[e]
    return dim % n == 0


def constrain_batch(x):
    """Pin dim 0 (batch) to the client/data axes — keeps GSPMD from
    replicating activations when params are FSDP-sharded (DESIGN.md §5)."""
    dp, _, sizes = _ambient()
    if dp is None or not _fits(x.shape[0], dp, sizes):
        return x
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_heads(x, head_axis: int):
    """Shard dim 0 (batch) on the data axes and ``head_axis`` on model —
    keeps the MLA-absorbed decode head-parallel instead of letting GSPMD
    replicate the latent cache (EXPERIMENTS.md §Perf pair C)."""
    dp, tp, sizes = _ambient()
    if dp is None:
        return x
    spec = [None] * x.ndim
    if _fits(x.shape[0], dp, sizes):
        spec[0] = dp
    if tp and _fits(x.shape[head_axis], tp, sizes):
        spec[head_axis] = tp
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_expert_rows(x):
    """Shard dim 0 (the flattened expert*capacity rows of the MoE dispatch
    buffer) over the model axis — the scatter becomes the canonical MoE
    all-to-all instead of a replicated buffer + all-reduce
    (EXPERIMENTS.md §Perf pair B)."""
    _, tp, sizes = _ambient()
    if tp is None or not _fits(x.shape[0], tp, sizes):
        return x
    spec = P(tp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_batch_and_last(x):
    """Batch on data axes, trailing (feature/vocab) dim on model."""
    dp, tp, sizes = _ambient()
    if dp is None:
        return x
    first = dp if _fits(x.shape[0], dp, sizes) else None
    last = tp if (tp and _fits(x.shape[-1], tp, sizes)) else None
    if first is None and last is None:
        return x
    spec = P(first, *([None] * (x.ndim - 2)), last)
    return jax.lax.with_sharding_constraint(x, spec)
