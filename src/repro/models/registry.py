"""Model registry: a uniform (init, loss, prefill, decode) bundle per arch.

``build_model(cfg)`` gives the launcher / protocol layer one stable surface
regardless of family — the NTMs (the paper's own models) implement the same
interface, which is what lets the gFedNTM protocol wrap every architecture
(DESIGN.md §7).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from repro.configs.base import NTM, ModelConfig


@dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init: Callable[..., Any]            # (key) -> params
    loss: Callable[..., Any]            # (params, batch) -> scalar loss
    forward: Callable[..., Any]         # (params, batch) -> model outputs
    # (params, batch) -> (sum_loss, count[, counters]): the mask-aware
    # form the federated stacked path weights by (Eq. (2) sample counts);
    # a model with per-round counters (noaux_tc MoE: expert_tokens)
    # returns them as a dict third
    loss_sum: Optional[Callable[..., Any]] = None
    prefill: Optional[Callable[..., Any]] = None
    decode_step: Optional[Callable[..., Any]] = None
    init_cache: Optional[Callable[..., Any]] = None


def build_model(cfg: ModelConfig, *, dtype=None) -> ModelBundle:
    if cfg.kind == NTM:
        from repro.core.ntm import prodlda

        def init(key):
            return prodlda.init_params(key, cfg)

        def loss(params, batch, **kw):
            return prodlda.elbo_loss(params, cfg, batch, **kw)

        def loss_sum(params, batch, **kw):
            return prodlda.elbo_loss_sum(params, cfg, batch, **kw)

        def forward(params, batch, **kw):
            return prodlda.forward(params, cfg, batch, **kw)

        return ModelBundle(cfg=cfg, init=init, loss=loss,
                           loss_sum=loss_sum, forward=forward)

    from repro.models import transformer as t

    def init(key):
        return t.init_params(key, cfg)

    def loss(params, batch, **kw):
        return t.train_loss(params, cfg, batch, dtype=dtype, **kw)

    def loss_sum(params, batch, **kw):
        return t.train_loss_sum(params, cfg, batch, dtype=dtype, **kw)

    def forward(params, batch, **kw):
        return t.forward_train(params, cfg, batch, dtype=dtype, **kw)

    def prefill(params, batch, **kw):
        return t.prefill(params, cfg, batch, dtype=dtype, **kw)

    def decode(params, cache, tokens, **kw):
        return t.decode_step(params, cfg, cache, tokens, dtype=dtype, **kw)

    def init_cache(batch_size, seq_len, **kw):
        return t.init_cache(cfg, batch_size, seq_len, dtype=dtype, **kw)

    return ModelBundle(cfg=cfg, init=init, loss=loss, loss_sum=loss_sum,
                       forward=forward, prefill=prefill, decode_step=decode,
                       init_cache=init_cache)
