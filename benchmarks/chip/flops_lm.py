"""Operations and bytes of the Moonlight cut's training rounds, from its
shapes and the routed pair counts (``lm_rounds.py``).

The held experts' work is counted from the (token, held expert) pairs the
router actually sent them (the program's ``expert_tokens``), never from
padded rows, so whatever implements the grouped product is read the same
way.  A matmul of an (m, k) by a (k, n) operand is m*k*n multiply-adds.

* Model FLOPs of a training step (what ``mfu.train`` reads): forward,
  and backward at twice the forward (the input and the weight gradient
  of every matmul: the embedding is a lookup, so no matmul's input is
  data alone).  Recomputation does not count.
* The grouped product as the device runs it under ``moe/experts``: its
  three matmuls forward, again in the layers' recomputation, and their
  two gradients, 24 * D * F FLOPs a pair.  Its bytes: each matmul reads
  the held experts' bf16 weights and its pairs' rows and writes its
  output rows, four times over (forward, recomputation, two gradients).
"""
from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The shapes flops_lm reads, from the configuration file."""
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "kv": cfg["kv_lora_rank"],
            "dense_f": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "experts": cfg["router_outputs"],
            "held": cfg["n_routed_experts"],
            "layers": cfg["num_hidden_layers"],
            "dense_layers": cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"]}


def attention_macs(m: Dict[str, int]) -> int:
    """MLA projections per token (direct query, latent kv, output)."""
    d, h = m["d"], m["h"]
    return (d * h * (m["dn"] + m["dr"]) + d * (m["kv"] + m["dr"])
            + m["kv"] * h * (m["dn"] + m["dv"]) + h * m["dv"] * d)


def core_macs(m: Dict[str, int], seq: int) -> int:
    """Causal scores and their values of one sequence, per layer."""
    return seq * (seq + 1) // 2 * m["h"] * (m["dn"] + m["dr"] + m["dv"])


def dense_token_macs(m: Dict[str, int]) -> int:
    """Every matmul of a token but the held experts, over all layers."""
    d, f = m["d"], m["f"]
    moe_layers = m["layers"] - m["dense_layers"]
    return (m["layers"] * attention_macs(m)
            + m["dense_layers"] * 3 * d * m["dense_f"]
            + moe_layers * (d * m["experts"] + 3 * d * m["shared"] * f)
            + d * m["vocab"])


def step_flops(m: Dict[str, int], seq: int, pairs: float) -> float:
    """Model FLOPs (forward and backward) of one local step on one
    sequence whose MoE layers routed ``pairs`` (token, held expert) pairs
    in all."""
    fwd = seq * dense_token_macs(m) + m["layers"] * core_macs(m, seq) \
        + pairs * 3 * m["d"] * m["f"]
    return 3 * 2 * fwd


def round_flops(cfg: Dict[str, Any], steps: int, seq: int,
                pairs: float) -> float:
    """Model FLOPs of a round of ``steps`` local steps (every client's)
    that routed ``pairs`` pairs in all (the round's ``expert_tokens``)."""
    m = dims(cfg)
    return steps * step_flops(m, seq, 0.0) + 6 * pairs * 3 * m["d"] * m["f"]


def expert_flops(cfg: Dict[str, Any], pairs: float) -> float:
    """FLOPs the grouped product runs for ``pairs`` routed pairs."""
    m = dims(cfg)
    return 24.0 * m["d"] * m["f"] * pairs


def expert_bytes(cfg: Dict[str, Any], pairs: float, calls: int) -> float:
    """HBM bytes of the grouped product: ``calls`` (MoE layer, step)
    pairs, ``pairs`` routed pairs in all."""
    m = dims(cfg)
    d, f, g = m["d"], m["f"], m["held"]
    weights = 3 * g * d * f * calls
    rows = pairs * 3 * (d + f)      # each matmul's rows in and out
    return 4.0 * BF16 * (weights + rows)
