"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes from the same inputs."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone and is left out of the norm gaps
GRAD_FLOOR = 1e-3


def rel_gap(value: float, ref: float) -> float:
    return abs(float(value) - float(ref)) / max(abs(float(ref)), 1e-30)


def leaf_norm_gap(prog: Any, ref: Any,
                  grad_norms: Any = None) -> Tuple[float, Dict[str, Any]]:
    """Worst leaf's | |prog_leaf| - |ref_leaf| | over the larger of the
    reference's norm of that leaf and of the median leaf."""
    paths, p_leaves = zip(*jax.tree_util.tree_flatten_with_path(prog)[0])
    r_leaves = jax.tree_util.tree_leaves(ref)
    if len(p_leaves) != len(r_leaves):
        raise ValueError(f"{len(p_leaves)} leaves against {len(r_leaves)}")
    keep = [True] * len(p_leaves)
    if grad_norms is not None:
        g = np.asarray(jax.tree_util.tree_leaves(grad_norms), np.float64)
        keep = list(g >= GRAD_FLOOR * np.median(g))
    pn = np.array([np.linalg.norm(np.asarray(x, np.float64))
                   for x in p_leaves])
    rn = np.array([np.linalg.norm(np.asarray(x, np.float64))
                   for x in r_leaves])
    kept = np.flatnonzero(keep)
    med = float(np.median(rn[kept]))
    gaps = np.abs(pn[kept] - rn[kept]) / np.maximum(rn[kept], med)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), {
        "leaf": jax.tree_util.keystr(paths[kept[worst]]),
        "left_out": [jax.tree_util.keystr(paths[i])
                     for i in range(len(keep)) if not keep[i]]}


def leaf_dist(prog: Any, ref: Any,
              base: Any) -> Tuple[float, Dict[str, Any]]:
    """Worst leaf's |prog_leaf - ref_leaf| over the larger of how far the
    reference moved that leaf from ``base`` and how far the median leaf
    moved."""
    paths, p_leaves = zip(*jax.tree_util.tree_flatten_with_path(prog)[0])
    r_leaves = jax.tree_util.tree_leaves(ref)
    b_leaves = jax.tree_util.tree_leaves(base)
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    dist = np.array([np.linalg.norm(f64(p) - f64(r))
                     for p, r in zip(p_leaves, r_leaves)])
    moved = np.array([np.linalg.norm(f64(r) - f64(b))
                      for r, b in zip(r_leaves, b_leaves)])
    gaps = dist / np.maximum(moved, max(float(np.median(moved)), 1e-30))
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), {"leaf": jax.tree_util.keystr(paths[worst])}


def tree_sub(a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        a, b)
