"""Architecture config registry: ``--arch <id>`` -> ModelConfig."""
from __future__ import annotations

from repro.configs.base import (
    ARCH_KINDS, AUDIO, DENSE, HYBRID, INPUT_SHAPES, MOE, NTM, SSM, VLM,
    DECODE_32K, LONG_500K, PREFILL_32K, TRAIN_4K,
    FederatedConfig, ModelConfig, MoEConfig, RunConfig, ShapeConfig, SSMConfig,
)

from repro.configs.granite_34b import CONFIG as _granite
from repro.configs.qwen2_vl_7b import CONFIG as _qwen2vl
from repro.configs.hubert_xlarge import CONFIG as _hubert
from repro.configs.hymba_1_5b import CONFIG as _hymba
from repro.configs.qwen1_5_110b import CONFIG as _qwen15
from repro.configs.phi3_mini_3_8b import CONFIG as _phi3
from repro.configs.llama4_maverick_400b import CONFIG as _llama4
from repro.configs.qwen3_moe_235b import CONFIG as _qwen3
from repro.configs.minicpm3_4b import CONFIG as _minicpm3
from repro.configs.mamba2_1_3b import CONFIG as _mamba2
from repro.configs.moonlight_16b_a3b import CONFIG as _moonlight
from repro.configs.prodlda_synthetic import CONFIG as _prodlda
from repro.configs.ctm_s2orc import CONFIG as _ctm

# The 10 assigned architectures (public-pool ids, exact).
ASSIGNED_ARCHS = {
    "granite-34b": _granite,
    "qwen2-vl-7b": _qwen2vl,
    "hubert-xlarge": _hubert,
    "hymba-1.5b": _hymba,
    "qwen1.5-110b": _qwen15,
    "phi3-mini-3.8b": _phi3,
    "llama4-maverick-400b-a17b": _llama4,
    "qwen3-moe-235b-a22b": _qwen3,
    "minicpm3-4b": _minicpm3,
    "mamba2-1.3b": _mamba2,
}

# The paper's own models, selectable through the same registry.
PAPER_ARCHS = {
    "prodlda-synthetic": _prodlda,
    "ctm-s2orc": _ctm,
}

# Published models federated at their widths, as one chip's share of a
# stated deployment (docs/lm_federation.md).
PUBLISHED_ARCHS = {
    "moonlight-16b-a3b": _moonlight,
}

ARCHS = {**ASSIGNED_ARCHS, **PAPER_ARCHS, **PUBLISHED_ARCHS}


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(
            f"unknown arch {arch!r}; available: {sorted(ARCHS)}")
    return ARCHS[arch]


def get_shape(name: str) -> ShapeConfig:
    if name not in INPUT_SHAPES:
        raise KeyError(
            f"unknown input shape {name!r}; available: {sorted(INPUT_SHAPES)}")
    return INPUT_SHAPES[name]


def applicable_shapes(cfg: ModelConfig) -> list[str]:
    """Which of the four assigned shapes apply to this arch (DESIGN.md §7)."""
    shapes = ["train_4k", "prefill_32k"]
    if cfg.encoder_only:
        return shapes          # no autoregressive decode for encoder-only
    shapes.append("decode_32k")
    # long_500k needs a sub-quadratic path: SSM/hybrid natively; dense/moe/vlm
    # only via the sliding-window variant (applied by the launcher).
    shapes.append("long_500k")
    return shapes


__all__ = [
    "ARCHS", "ASSIGNED_ARCHS", "PAPER_ARCHS", "PUBLISHED_ARCHS",
    "INPUT_SHAPES",
    "get_config", "get_shape", "applicable_shapes",
    "ModelConfig", "MoEConfig", "SSMConfig", "FederatedConfig", "RunConfig",
    "ShapeConfig", "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "DENSE", "MOE", "SSM", "HYBRID", "VLM", "AUDIO", "NTM", "ARCH_KINDS",
]
