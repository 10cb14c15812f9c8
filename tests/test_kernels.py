"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
oracle in kernels/ref.py, swept over shapes and dtypes (assignment
requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # (b, hq, hkv, s, d, causal, window)
    (2, 4, 2, 256, 64, True, 0),
    (1, 4, 1, 128, 32, True, 0),      # MQA (granite-style)
    (2, 2, 2, 256, 64, True, 64),     # sliding window
    (1, 4, 4, 128, 64, False, 0),     # bidirectional (hubert-style)
    (1, 8, 2, 100, 32, True, 0),      # non-block-multiple sequence
]


@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal, window,
                                     dtype, rng):
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    r = ref.flash_attention_ref(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=causal, window=window)
    r = jnp.moveaxis(r, 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# SSD scan (mamba-2)
# ---------------------------------------------------------------------------
SSD_CASES = [
    # (b, s, h, p, n, chunk)
    (2, 256, 3, 32, 16, 64),
    (1, 100, 2, 16, 8, 32),           # ragged sequence
    (1, 64, 1, 64, 128, 64),          # mamba2-1.3b-like state
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_CASES)
def test_ssd_scan_matches_ref(b, s, h, p, n, chunk, rng):
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    y, hl = ops.ssd_scan(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    yr, hlr = ref.ssd_scan_ref(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr),
                               atol=1e-4, rtol=1e-4)


def test_ssd_model_layer_uses_same_math(rng):
    """The model's jnp ssd_chunked and the Pallas kernel agree."""
    from repro.models.layers.mamba2 import ssd_chunked
    b, s, h, p, n = 2, 128, 2, 16, 8
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    y1, h1 = ssd_chunked(x, dt, a, bb, cc, chunk=32)
    y2, h2 = ops.ssd_scan(x, dt, a, bb, cc, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused topic decoder (the paper's hot-spot)
# ---------------------------------------------------------------------------
TOPIC_CASES = [
    (16, 10, 1000), (7, 50, 5000), (128, 25, 531), (1, 2, 64),
]


@pytest.mark.parametrize("b,k,v", TOPIC_CASES)
def test_topic_decoder_matches_ref(b, k, v, rng):
    theta = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((b, k)), jnp.float32))
    beta = jnp.asarray(rng.standard_normal((k, v)), jnp.float32)
    bow = jnp.asarray(rng.poisson(0.1, (b, v)).astype(np.float32))
    sc = jnp.asarray(rng.uniform(0.5, 1.5, (v,)), jnp.float32)
    out = ops.topic_decoder_loss(theta, beta, bow, sc, interpret=True)
    r = ref.topic_decoder_ref(theta, beta, bow, sc)
    scale = float(jnp.maximum(jnp.max(jnp.abs(r)), 1.0))
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(r) / scale, atol=1e-5)


# uneven tails: (B, V) deliberately NOT multiples of (block_b, block_v),
# so the last doc/vocab blocks are partially padded — the padded logits
# must stay out of the online log-sum-exp AND the bow-weighted sums
TOPIC_TAIL_CASES = [
    # (b, k, v, block_b, block_v)
    (130, 8, 1100, 128, 512),    # tails on both grid axes
    (5, 4, 513, 4, 512),         # 1-column vocab tail, 1-row doc tail
    (33, 3, 96, 16, 32),         # multi-block with tails on both axes
    (2, 2, 17, 2, 16),           # tiny blocks, 1-wide vocab tail
]


@pytest.mark.parametrize("b,k,v,bb,bv", TOPIC_TAIL_CASES)
def test_topic_decoder_uneven_block_tails(b, k, v, bb, bv, rng):
    theta = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((b, k)), jnp.float32))
    beta = jnp.asarray(rng.standard_normal((k, v)), jnp.float32)
    bow = jnp.asarray(rng.poisson(0.2, (b, v)).astype(np.float32))
    sc = jnp.asarray(rng.uniform(0.5, 1.5, (v,)), jnp.float32)
    out = ops.topic_decoder_loss(theta, beta, bow, sc,
                                 block_b=bb, block_v=bv, interpret=True)
    r = ref.topic_decoder_ref(theta, beta, bow, sc)
    scale = float(jnp.maximum(jnp.max(jnp.abs(r)), 1.0))
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(r) / scale, atol=1e-5)


def test_topic_decoder_zero_bow_rows(rng):
    """bow=0 documents (all-padding rows in the stacked federated batches)
    must yield exactly 0 reconstruction loss: S = NB = 0, so the kernel's
    -(S - NB*lse) collapses to 0 regardless of the log-sum-exp value."""
    b, k, v = 12, 6, 300
    theta = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((b, k)), jnp.float32))
    beta = jnp.asarray(rng.standard_normal((k, v)), jnp.float32)
    bow = rng.poisson(0.3, (b, v)).astype(np.float32)
    zero_rows = np.asarray([0, 5, 11])
    bow[zero_rows] = 0.0
    bow = jnp.asarray(bow)
    out = ops.topic_decoder_loss(theta, beta, bow, interpret=True,
                                 block_b=8, block_v=128)
    r = ref.topic_decoder_ref(theta, beta, bow)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[zero_rows], 0.0, atol=1e-6)
    # the all-zero batch degenerates the same way
    out0 = ops.topic_decoder_loss(theta, beta, jnp.zeros_like(bow),
                                  interpret=True, block_b=8, block_v=128)
    np.testing.assert_allclose(np.asarray(out0), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# federation aggregation kernels (fed_aggregate.py) — oracle-first grid
# ---------------------------------------------------------------------------
# (K, D, block_k, block_d): uneven tails on BOTH grid axes, single-row
# cohorts, block-multiple shapes — every case also runs with zero-weight
# padded rows holding non-finite garbage (the fixed-K padding contract)
COMBINE_CASES = [
    (5, 300, 4, 128),      # K and D tails
    (1, 7, 8, 128),        # single client, tiny leaf
    (8, 128, 8, 128),      # exact block multiples
    (13, 1000, 8, 256),    # multi-block both axes, tails
    (3, 129, 2, 64),       # 1-col D tail, 1-row K tail
]


@pytest.mark.parametrize("k,d,bk,bd", COMBINE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed_combine_matches_ref(k, d, bk, bd, dtype, rng):
    from repro.kernels.fed_aggregate import fed_weighted_sum_pallas
    x = rng.standard_normal((k, d)).astype(np.float32)
    w = rng.uniform(0, 2, k).astype(np.float32)
    w[rng.random(k) < 0.4] = 0.0
    # zero-weight padded rows may hold non-finite local-update garbage;
    # the in-kernel where-mask must keep it out of the sum (0*nan is nan)
    x[w == 0.0] = np.nan
    x, w = jnp.asarray(x, dtype), jnp.asarray(w)
    num = fed_weighted_sum_pallas(x, w, block_k=bk, block_d=bd,
                                  interpret=True)
    got = num / jnp.maximum(jnp.sum(w), 1e-12)
    want = ref.fed_combine_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)


def test_fed_combine_empty_and_all_padded(rng):
    """All-zero weights -> zero combine (guarded denominator, matching
    aggregate_stacked); an empty K=0 cohort -> zeros without tracing a
    zero-size grid."""
    from repro.kernels.fed_aggregate import fed_weighted_sum_pallas
    out = fed_weighted_sum_pallas(jnp.full((4, 17), jnp.nan),
                                  jnp.zeros((4,)), interpret=True)
    assert np.all(np.asarray(out) == 0.0)
    out0 = fed_weighted_sum_pallas(jnp.zeros((0, 9)), jnp.zeros((0,)),
                                   interpret=True)
    assert out0.shape == (9,) and np.all(np.asarray(out0) == 0.0)


@pytest.mark.parametrize("num_clients", [2, 3, 4, 16])
def test_fed_combine_preserves_mask_cancellation(num_clients):
    """The dyadic-grid secure masks must sum to BITWISE +0.0 through the
    Pallas combine's block-tiled in-kernel summation order, exactly as
    they do under jnp.sum — the DESIGN.md argument that grid-integer
    partial sums never round, under a DIFFERENT association."""
    from repro.core.transforms import pairwise_mask_stack
    from repro.kernels.fed_aggregate import fed_weighted_sum_pallas
    tmpl = {"w": jnp.zeros((13, 7), jnp.float32),
            "b": jnp.zeros((257,), jnp.float32)}
    stack = pairwise_mask_stack(jax.random.PRNGKey(3), tmpl, num_clients)
    ones = jnp.ones((num_clients,), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(stack):
        flat = leaf.reshape((num_clients, -1))
        s = fed_weighted_sum_pallas(flat, ones, block_k=2, block_d=64,
                                    interpret=True) / num_clients
        assert float(jnp.sum(jnp.abs(s))) == 0.0


TOPK_EF_CASES = [
    # (k, l, d, k_keep)
    (3, 5, 40, 4),
    (6, 6, 129, 13),       # gather is identity-size, non-tiled D
    (2, 9, 8, 1),          # k_keep = 1
    (4, 4, 16, 16),        # keep everything -> zero residual
]


@pytest.mark.parametrize("k,l,d,kk", TOPK_EF_CASES)
def test_fed_topk_ef_matches_ref(k, l, d, kk, rng):
    from repro.kernels.fed_aggregate import fed_topk_ef_pallas
    msgs = rng.standard_normal((k, d)).astype(np.float32)
    msgs[0, : min(6, d)] = 0.5          # magnitude ties at the threshold
    state = rng.standard_normal((l, d)).astype(np.float32)
    ids = rng.integers(0, l, k).astype(np.int32)
    want_sent, want_err = ref.fed_topk_ef_ref(
        jnp.asarray(msgs), jnp.asarray(state)[ids], kk)
    sent, new_err = fed_topk_ef_pallas(jnp.asarray(msgs),
                                       jnp.asarray(state)[ids], k_keep=kk,
                                       interpret=True)
    # the kernel's count-based selection is BITWISE the sort-based
    # oracle's — identical coordinates, identical residuals
    np.testing.assert_array_equal(np.asarray(sent), np.asarray(want_sent))
    np.testing.assert_array_equal(np.asarray(new_err), np.asarray(want_err))
    assert np.all(np.count_nonzero(np.asarray(sent), axis=1) <= kk)


def test_fed_topk_ef_matches_loop_compression(rng):
    """Cross-implementation: the fused kernel equals the loop path's
    compress_with_error_feedback (gather done host-side) — one selection
    rule across host loop, vmapped XLA, and Pallas."""
    from repro.core.aggregation import compress_with_error_feedback
    from repro.kernels.fed_aggregate import fed_topk_ef_pallas
    k, l, d, frac = 4, 7, 60, 0.25
    kk = max(int(frac * d), 1)
    msgs = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    state = jnp.asarray(rng.standard_normal((l, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, l, k), jnp.int32)
    want = jax.vmap(
        lambda g, e: compress_with_error_feedback(g, e, frac))(
        msgs, state[ids])
    sent, new_err = fed_topk_ef_pallas(msgs, state[ids], k_keep=kk,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(sent), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(new_err), np.asarray(want[1]))


def _topk_rule_input(case, rng, shape=(3, 300)):
    x = rng.standard_normal(shape).astype(np.float32)
    if case == "ties":                  # long runs tied at every rank
        x = np.round(x * 4) / 4
    elif case == "constant":
        x = np.full(shape, -0.75, np.float32)
    elif case == "bf16_neighbours":     # fp32 values one bf16 step apart
        base = np.float32(1.0) + np.float32(2.0 ** -8) * rng.integers(
            0, 3, shape)
        x = (base + np.float32(2.0 ** -12) * rng.standard_normal(shape)
             ).astype(np.float32)
    elif case == "nonfinite":
        x[:, ::17] = np.inf
        x[:, 5::23] = np.nan
    return jnp.asarray(x)


TOPK_RULE_CASES = ["random", "ties", "constant", "bf16_neighbours",
                   "nonfinite"]


@pytest.mark.parametrize("case", TOPK_RULE_CASES)
@pytest.mark.parametrize("k", [1, 75, 299, 300])
def test_topk_keep_mask_matches_sort_oracle(case, k, rng):
    """The count-based bisection the XLA paths and the kernel share keeps
    exactly the sort-based oracle's coordinates: exactly k entries, the
    k largest bf16 keys, ties toward the lower index."""
    from repro.core.aggregation import topk_keep_mask
    x = _topk_rule_input(case, rng)
    got = np.asarray(topk_keep_mask(jnp.abs(x), k))
    np.testing.assert_array_equal(got, np.asarray(ref.topk_mask_ref(x, k)))
    assert np.all(got.sum(axis=-1) == k)


@pytest.mark.parametrize("case", TOPK_RULE_CASES)
def test_fed_topk_ef_kernel_selection_cases(case, rng):
    """The kernel's in-VMEM selection on tie-heavy and non-finite rows
    whose length is not a multiple of the 128-lane tile."""
    from repro.kernels.fed_aggregate import fed_topk_ef_pallas
    x = _topk_rule_input(case, rng, shape=(4, 333))
    err = jnp.zeros_like(x)
    want_sent, want_err = ref.fed_topk_ef_ref(x, err, 80)
    sent, new_err = fed_topk_ef_pallas(x, err, k_keep=80, interpret=True)
    np.testing.assert_array_equal(np.asarray(sent), np.asarray(want_sent))
    np.testing.assert_array_equal(np.asarray(new_err), np.asarray(want_err))


def test_bf16_magnitude_key_is_the_bf16_cast(rng):
    """The integer key rounds exactly like an fp32 -> bf16 cast
    (round-to-nearest-even), so ranking on it is ranking on the
    bf16-quantized magnitude."""
    from repro.core.aggregation import bf16_magnitude_key
    x = np.concatenate([
        rng.standard_normal(4096).astype(np.float32) * 10.0 ** rng.integers(
            -30, 30, 4096).astype(np.float32),
        np.float32([0.0, -0.0, np.inf, -np.inf, 1.0 + 2.0 ** -8,
                    1.0 + 3 * 2.0 ** -8, 3.4e38])])
    x = jnp.asarray(x)
    cast = jax.lax.bitcast_convert_type(jnp.abs(x).astype(jnp.bfloat16),
                                        jnp.uint16).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(bf16_magnitude_key(x)),
                                  np.asarray(cast))


DP_SECURE_CASES = [(5, 33), (8, 256), (3, 1), (9, 130)]


@pytest.mark.parametrize("k,d", DP_SECURE_CASES)
def test_fed_dp_secure_apply_matches_ref(k, d, rng):
    from repro.kernels.fed_aggregate import fed_dp_secure_apply_pallas
    x = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    nz = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    mk = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    cc = jnp.asarray(rng.uniform(0.1, 1.0, k), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 2, k), jnp.float32)
    # clip and mask terms are BITWISE the XLA expressions; only the
    # noise add may drift <= 2 ulp under fma contraction (kernel docs)
    for kwargs, bitwise in [
        (dict(), True),
        (dict(masks=mk, weights=w), True),
        (dict(clip_coef=cc), True),
        (dict(noise=nz, clip_coef=cc, noise_scale=0.37), False),
        (dict(noise=nz, masks=mk, clip_coef=cc, weights=w,
              noise_scale=1.5), False),
    ]:
        want = np.asarray(ref.fed_dp_secure_apply_ref(x, **kwargs))
        got = np.asarray(fed_dp_secure_apply_pallas(x, **kwargs,
                                                    interpret=True))
        if bitwise:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_fed_ops_wrappers_backend_parity(rng):
    """The pytree-level ops wrappers agree across backends on mixed-rank
    trees (the engine calls these, never the kernels directly)."""
    tree = {"a": jnp.asarray(rng.standard_normal((5, 3, 7)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((5, 11)), jnp.float32)}
    w = jnp.asarray([1.0, 0.0, 2.0, 0.5, 0.0])
    cx = ops.fed_weighted_combine(tree, w, backend="xla")
    cp = ops.fed_weighted_combine(tree, w, backend="pallas", interpret=True)
    sx = ops.fed_weighted_sum(tree, w, backend="xla")
    sp = ops.fed_weighted_sum(tree, w, backend="pallas", interpret=True)
    est = {"a": jnp.asarray(rng.standard_normal((7, 3, 7)), jnp.float32),
           "b": jnp.asarray(rng.standard_normal((7, 11)), jnp.float32)}
    ids = jnp.asarray([0, 6, 3, 3, 1], jnp.int32)
    tx = ops.fed_topk_ef(tree, est, ids, frac=0.3, backend="xla")
    tp = ops.fed_topk_ef(tree, est, ids, frac=0.3, backend="pallas",
                         interpret=True)
    ax = ops.fed_dp_secure_apply(tree, masks=tree, weights=w, backend="xla")
    ap = ops.fed_dp_secure_apply(tree, masks=tree, weights=w,
                                 backend="pallas", interpret=True)
    for key in tree:
        np.testing.assert_allclose(np.asarray(cx[key]), np.asarray(cp[key]),
                                   rtol=0, atol=2e-6)
        np.testing.assert_allclose(np.asarray(sx[key]), np.asarray(sp[key]),
                                   rtol=0, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(tx[0][key]),
                                      np.asarray(tp[0][key]))
        np.testing.assert_array_equal(np.asarray(tx[1][key]),
                                      np.asarray(tp[1][key]))
        np.testing.assert_array_equal(np.asarray(ax[key]),
                                      np.asarray(ap[key]))
    with pytest.raises(ValueError, match="kernel backend"):
        ops.fed_weighted_combine(tree, w, backend="mlir")


def test_fed_engine_backend_parity_end_to_end():
    """xla- and pallas-backend vmap engines walk the same trajectory
    (<=1e-5) on a small federation, secure transform included — and the
    pallas graph still compiles exactly once (fixed-K contract)."""
    from benchmarks.bench_scenarios import base_spec
    from repro.api import (Federation, build_corpus, max_param_dev,
                           spec_replace)
    base = base_spec(vocab=120, topics=4, hidden=16, num_clients=3,
                     docs_per_client=18, batch=8, lr=2e-3, seed=0,
                     rounds=2)
    syn = build_corpus(base)
    for overrides in ({}, {"transforms.names": ("secure",)}):
        engines = {}
        for kb in ("xla", "pallas"):
            spec = spec_replace(base, dict(
                overrides, **{"execution.exec_mode": "vmap",
                              "execution.kernel_backend": kb}))
            eng = Federation.from_spec(spec, corpus=syn).engine
            for r in range(2):
                eng.round(seed=7 + r)
            engines[kb] = eng
        dev = max_param_dev(engines["xla"].params, engines["pallas"].params)
        assert dev <= 1e-5, (overrides, dev)
        assert sum(engines["pallas"].trace_counts.values()) == 1


def test_topic_decoder_matches_prodlda_loss(rng):
    """The fused kernel computes exactly ProdLDA's reconstruction term."""
    from repro.configs import get_config
    from repro.core.ntm import prodlda
    cfg = get_config("prodlda-synthetic").reduced()
    params = prodlda.init_params(jax.random.PRNGKey(0), cfg)
    bow = jnp.asarray(rng.poisson(0.2, (8, cfg.vocab_size)).astype(np.float32))
    out = prodlda.forward(params, cfg, {"bow": bow}, train=False)
    recon_model = -jnp.sum(bow * out["log_recon"], axis=-1)
    recon_kernel = ops.topic_decoder_loss(
        out["theta"], params["beta"], bow, params["dec_scale"],
        interpret=True)
    np.testing.assert_allclose(np.asarray(recon_kernel),
                               np.asarray(recon_model), rtol=1e-4)
