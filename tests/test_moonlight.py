"""Moonlight-16B-A3B (DeepSeek-V3 block) against its plain reference, at a
tiny Moonlight-shaped preset: MLA with a direct query, a leading dense
layer, sigmoid routing with a selection bias over 8 experts of which a
chip holds 4, one shared expert; and the client-scan round against the
vmapped one.

Tolerances: the program and the reference both run float32 here, the
reference at ``highest``; they differ in summation order only (flash
chunks against one softmax, the grouped product against dense experts,
the scanned combine against the stacked one), a few float32 ulps
carried through a handful of layers, so 1e-5 relative on losses and
layer outputs and 1e-4 of a leaf's norm on gradients.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import max_param_dev, tiny_spec
from repro.configs import get_config
from repro.models import reference_moonlight as ref
from repro.models import transformer as tfm
from repro.models.layers import attention as attn_lib
from repro.models.layers import moe as moe_lib

BASE = get_config("moonlight-16b-a3b")


def tiny(ep_size=2, ep_rank=0, **kw):
    """d=64, 4 heads (nope 16, rope 16), latent 32, 1 dense + 2 MoE
    layers, 8 experts (``8 / ep_size`` held), top-2, 1 shared, 128 ids."""
    return dataclasses.replace(
        BASE, num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=16, d_ff=32, dense_d_ff=96, vocab_size=128,
        max_seq_len=64, mla_kv_lora_rank=32, mla_rope_head_dim=16,
        remat_layers=False,
        moe=dataclasses.replace(BASE.moe, num_experts=8, top_k=2,
                                num_shared_experts=1, ep_size=ep_size,
                                ep_rank=ep_rank), **kw)


def tokens(b=2, s=24, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0, 128)
    return t[:, :-1], t[:, 1:]


def close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.linalg.norm(want), 1e-30)
    assert np.linalg.norm(got - want) <= rtol * scale, (
        np.linalg.norm(got - want) / scale)


def test_loss_and_gradients_match_the_reference():
    """The program's mean loss and every gradient leaf against the plain
    reference, with the selection bias nonzero and the reference's
    attention and head in blocks."""
    cfg = tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert float(jnp.abs(params["layers"]["ffn"]["router_bias"]).min()) > 0
    toks, labels = tokens()

    def prog(p):
        s, n, _ = tfm.train_loss_sum(p, cfg, {"tokens": toks,
                                              "labels": labels},
                                     dtype=jnp.float32)
        return s / n
    loss, grads = jax.value_and_grad(prog)(params)
    want_loss, want_grads = ref.loss_and_grad(
        params, ref.arch_of(cfg), toks, labels, q_block=8, head_block=16)
    close(loss, want_loss, 1e-5)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        if "router_bias" in jax.tree_util.keystr(path):
            assert float(jnp.abs(g).max()) == 0.0     # a buffer
            continue
        close(g, w, 1e-4)


def _halves_and_whole():
    whole = tiny(ep_size=1)
    p = moe_lib.moe_init(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 64), jnp.float32)
    halves = []
    for rank in (0, 1):
        cfg = tiny(ep_size=2, ep_rank=rank)
        part = dict(p, **{k: p[k][4 * rank:4 * rank + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        halves.append(moe_lib.held_moe_apply(part, cfg, x))
    return whole, p, x, halves


def test_the_two_halves_add_up_to_the_whole_layer():
    """The chips of a 2-way split each give their held experts' part;
    with the shared expert counted once they add up to the uncut
    reference layer, and every routed pair is held by one of them."""
    whole, p, x, halves = _halves_and_whole()
    (y0, n0), (y1, n1) = halves
    shared = ref.swiglu(p["shared"], x[0])
    want = ref.moe(p, ref.arch_of(whole), x[0])
    close(y0[0] + y1[0] - shared, want, 1e-5)
    assert float(n0.sum() + n1.sum()) == 32 * 2


def test_no_pair_is_dropped_under_skewed_routing():
    """A selection bias that sends every token to the held experts 0 and
    1: each is routed all 32 tokens and computes all of them."""
    cfg = tiny(ep_size=2)
    p = moe_lib.moe_init(jax.random.PRNGKey(5), cfg)
    p["router_bias"] = jnp.asarray([9.0, 9.0] + [0.0] * 6)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 64), jnp.float32)
    y, pairs = moe_lib.held_moe_apply(p, cfg, x)
    np.testing.assert_array_equal(np.asarray(pairs), [32, 32, 0, 0])
    with jax.default_matmul_precision("highest"):
        close(y[0], ref.moe(p, ref.arch_of(cfg), x[0]), 1e-5)


def test_direct_query_mla_matches_the_reference():
    cfg = tiny()
    p = attn_lib.mla_init(jax.random.PRNGKey(7), cfg)
    assert "w_q" in p and "w_dq" not in p
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, 64), jnp.float32)
    pos = jnp.arange(24)[None]
    angles = tfm._angles_for(cfg, {}, pos)
    y, _ = attn_lib.mla_full(p, cfg, x, angles, positions=pos)
    with jax.default_matmul_precision("highest"):
        close(y[0], ref.mla(p, ref.arch_of(cfg), x[0], q_block=8), 1e-5)


def test_the_published_cut_keeps_every_width():
    """``model.published`` with the chip's share: every published width,
    the router's 64 outputs, 6 per token, 2 shared experts, the leading
    dense layer, 8 experts held and a 20,480-id slice; 568.5 M
    parameters, as the configuration states."""
    from repro.api import DataSpec, FederationSpec, ModelSpec
    spec = FederationSpec(
        model=ModelSpec(family="lm", arch="moonlight-16b-a3b",
                        published=True, layers=5, ep_size=8, vocab=20480,
                        seq_len=8192),
        data=DataSpec(num_clients=5, docs_per_node=64))
    cfg = spec.to_model_config()
    assert (cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.mla_rope_head_dim,
            cfg.mla_kv_lora_rank, cfg.mla_q_lora_rank, cfg.d_ff,
            cfg.dense_d_ff) == (2048, 16, 128, 64, 512, 0, 1408, 11264)
    assert (cfg.moe.num_experts, cfg.moe.num_held, cfg.moe.top_k,
            cfg.moe.num_shared_experts, cfg.first_k_dense,
            cfg.num_layers, cfg.vocab_size) == (64, 8, 6, 2, 1, 5, 20480)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.num_params()
    assert abs(n - 568.5e6) < 0.1e6
    with pytest.raises(ValueError, match="keeps every published width"):
        dataclasses.replace(spec.model, width=512)._validate()


def test_schedule_rule_keeps_the_benchmarked_cohort_on_vmap():
    from repro.core.engine import scan_clients
    # crossdevice-dp-topk: K=128 clients of 3.1 MB ProdLDA parameters,
    # on a chip with some 10 GB free
    assert not scan_clients(3_100_000, 128, 10 * 2**30)
    # the Moonlight cut: K=5 of 2.27 GB, whatever a 16 GB chip has free
    assert scan_clients(2_274_000_000, 5, 16 * 2**30)
    assert not scan_clients(2_274_000_000, 5, None)   # no limit reported


def _room(monkeypatch, free):
    used = 1 << 30
    monkeypatch.setattr(
        type(jax.devices()[0]), "memory_stats",
        lambda self: {"bytes_limit": used + free, "bytes_in_use": used})


def _lm_spec(**kw):
    from repro.api import spec_replace
    return spec_replace(tiny_spec(num_clients=3), {
        "model.family": "lm", "model.arch": "moonlight-16b-a3b",
        "model.vocab": 128, "model.seq_len": 16, "model.topics": 10,
        "model.hidden": 64, "data.docs_per_node": 8,
        "execution.batch_size": 2, "execution.learning_rate": 0.05,
        "schedule.rounds": 2, "schedule.local_epochs": 2, **kw})


@pytest.mark.parametrize("which", ["prodlda", "prodlda-dp-topk",
                                   "prodlda-pallas", "moonlight"])
def test_client_scan_round_equals_the_vmapped_round(which, monkeypatch,
                                                    corpus8):
    """A device too small for the vmapped cohort gets the client-scan
    round: the same parameters, losses and counters as the vmap round."""
    from repro.api import Federation
    if which == "moonlight":
        spec, corpus = _lm_spec(), None
    else:
        spec, corpus = tiny_spec(), corpus8
        from repro.api import spec_replace
        if which == "prodlda-dp-topk":
            spec = spec_replace(spec, {
                "transforms.names": ("dp", "topk"),
                "transforms.dp_noise_multiplier": 0.3,
                "transforms.dp_clip_norm": 0.5,
                "transforms.compression_topk": 0.25})
        if which == "prodlda-pallas":
            spec = spec_replace(spec, {"execution.kernel_backend": "pallas"})
    runs = {}
    for mode in ("vmap", "scan"):
        if mode == "scan":
            _room(monkeypatch, 4096)
        fed = Federation.from_spec(spec, corpus=corpus)
        runs[mode] = (fed, [fed.step() for _ in range(2)])
        assert fed.engine._scan == (mode == "scan")
    (fv, hv), (fs, hs) = runs["vmap"], runs["scan"]
    assert max_param_dev(fv.params, fs.params) <= 1e-5
    for a, b in zip(hv, hs):
        assert abs(a["loss"] - b["loss"]) <= 1e-5 * abs(a["loss"])
        assert a.get("expert_tokens") == b.get("expert_tokens")
    if which == "moonlight":
        # 3 clients x 2 steps x 2 docs x 16 tokens x top-2, 1 MoE layer
        # of the reduced preset, all 4 of its experts held
        assert sum(hv[0]["expert_tokens"]) == 3 * 2 * 2 * 16 * 2


def test_secure_is_refused_under_the_client_scan(monkeypatch):
    from repro.api import spec_replace
    spec = tiny_spec(num_clients=4)
    secure = {"transforms.names": ("secure",)}
    spec_replace(spec, secure)                  # fits: the vmap round
    _room(monkeypatch, 4096)
    with pytest.raises(ValueError, match="client-scan"):
        spec_replace(spec, secure)
