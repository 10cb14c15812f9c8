"""vmap-vs-loop execution equivalence (the PR's headline property).

The vectorized path (``exec_mode="vmap"``: stacked cohort minibatches,
all K local-update loops + Eq. (2) combine + server optimizer in one
jitted graph, DESIGN.md §4) must retrace the host-side loop path — and
hence, via the existing anchor in tests/test_rounds.py, the paper's
Algorithm-1 trainer — on EVERY configuration, not just the degenerate
one.  Two layers:

  * a deterministic regime grid that always runs (partial participation,
    multi-epoch clients, ragged corpora with padding+masking, staleness
    buffer — under vmap the fused IN-GRAPH ring buffer, checked against
    the loop-mode ``combine_arrivals`` reference — adaptive server
    optimizers, weighted sampling, heterogeneous per-client epochs,
    mid-training dropout/join);
  * a hypothesis fuzz over random (L, K, E, vocab, topics, staleness,
    corpus-size) tuples (skipped when the optional [test] extra is not
    installed, like the other property suites).

Tolerance: per-round max |param| deviation < 1e-5 (acceptance bar) —
the two paths draw bit-identical minibatches and noise keys, so the only
daylight is float32 reduction-order inside vmapped/batched kernels.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import NTM, FederatedConfig, ModelConfig, RoundConfig
from repro.core.ntm import prodlda
from repro.core.protocol import ClientState, FederatedTrainer, FedAvgTrainer
from repro.core.rounds import RoundEngine
from repro.data.federated_split import stacked_round_batches
from conftest import make_tiny_federation, max_param_dev, tiny_spec

TOL = 1e-5
# single home for the deviation metric + tiny federation: tests/conftest.py
_max_dev = max_param_dev
_make_setup = make_tiny_federation


def _assert_trajectories_match(loss, loss_sum, init, clients, fed, rc, *,
                               batch_size, rounds=4, seed=0, tol=TOL):
    """Step both exec modes round-by-round; params must stay glued."""
    loop = RoundEngine(loss, init, clients, fed, rc,
                       batch_size=batch_size, exec_mode="loop")
    vm = RoundEngine(loss, init, clients, fed, rc,
                     batch_size=batch_size, exec_mode="vmap",
                     loss_sum_fn=loss_sum)
    for r in range(rounds):
        ra = loop.round(seed=seed * 100003 + r)
        rb = vm.round(seed=seed * 100003 + r)
        dev = _max_dev(loop.params, vm.params)
        assert dev < tol, f"round {r}: max param dev {dev:.2e} >= {tol}"
        # bookkeeping must agree too, not just the weights
        assert ra["participants"] == rb["participants"]
        assert ra["arrived"] == rb["arrived"]
        assert ra["in_flight"] == rb["in_flight"]
        if np.isfinite(ra["loss"]):
            np.testing.assert_allclose(ra["loss"], rb["loss"], rtol=1e-4)
    return loop, vm


# ---------------------------------------------------------------------------
# deterministic regime grid (always runs)
# ---------------------------------------------------------------------------
REGIMES = {
    "paper-degenerate": dict(),
    "partial-participation": dict(clients_per_round=2),
    "multi-epoch": dict(local_epochs=3),
    "k-of-l-multi-epoch": dict(clients_per_round=2, local_epochs=2),
    "weighted-sampling": dict(clients_per_round=2, sampling="weighted"),
    "deterministic-sampling": dict(clients_per_round=2,
                                   sampling="deterministic"),
    "fedavgm": dict(server_optimizer="fedavgm", server_momentum=0.5,
                    server_lr=0.5),
    "fedadam": dict(server_optimizer="fedadam", server_lr=0.05),
    "staleness": dict(straggler_prob=0.6, max_staleness=3,
                      staleness_decay=0.5),
    "staleness-partial": dict(clients_per_round=2, local_epochs=2,
                              straggler_prob=0.5, max_staleness=2,
                              staleness_decay=0.25),
    # PR 3 scenario knobs: under vmap the staleness regimes above now run
    # the fused in-graph ring buffer, so this grid doubles as the
    # fused-vs-combine_arrivals acceptance check
    "staleness-odd-decay": dict(straggler_prob=0.6, max_staleness=3,
                                staleness_decay=0.3),
    "hetero-epochs": dict(local_epochs_by_client=(1, 3, 2)),
    "hetero-epochs-staleness": dict(clients_per_round=2,
                                    local_epochs_by_client=(2, 1, 3),
                                    straggler_prob=0.5, max_staleness=2),
    "dropout-join": dict(client_join_round=(0, 0, 2),
                         client_leave_round=(0, 3, 0)),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_vmap_matches_loop_regime(regime):
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          rel_tol=0.0)
    _assert_trajectories_match(loss, loss_sum, init, clients, fed,
                               RoundConfig(**REGIMES[regime]),
                               batch_size=32)


def test_vmap_matches_loop_ragged_padding():
    """Clients smaller than the batch exercise the zero-pad + doc_mask
    path; masked rows must stay out of the objective AND its gradient."""
    cfg, loss, loss_sum, init, clients = _make_setup(docs=(48, 11, 23))
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          rel_tol=0.0)
    _assert_trajectories_match(loss, loss_sum, init, clients, fed,
                               RoundConfig(local_epochs=2), batch_size=32)


def test_vmap_matches_loop_stochastic_loss():
    """Train-mode ELBO (dropout + reparametrization noise): the stacked
    path must consume the SAME noise keys the loop path puts in
    batch["rng"].  Full batches on purpose — with padding, in-batch
    noise is drawn over the padded row count and threefry's counter
    layout is shape-dependent, so the exact-retrace guarantee for
    stochastic losses is scoped to unpadded cohorts (DESIGN.md §4,
    `masked_mean_loss` docstring)."""
    vocab, topics = 64, 4
    cfg = ModelConfig(name="vmap-eq-st", kind=NTM, vocab_size=vocab,
                      num_topics=topics, ntm_hidden=(16, 16))
    rng = np.random.default_rng(3)
    clients = [ClientState(
        data={"bow": rng.poisson(0.3, (40, vocab)).astype(np.float32)},
        num_docs=40) for _ in range(3)]
    loss = lambda p, b: prodlda.elbo_loss(p, cfg, b, train=True)  # noqa: E731,E501
    loss_sum = lambda p, b: prodlda.elbo_loss_sum(p, cfg, b, train=True)  # noqa: E731,E501
    init = prodlda.init_params(jax.random.PRNGKey(3), cfg)
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=3,
                          rel_tol=0.0)
    _assert_trajectories_match(loss, loss_sum, init, clients, fed,
                               RoundConfig(), batch_size=40, rounds=3)


def test_round_config_exec_mode_threads_through():
    """RoundConfig.exec_mode selects the path; the kwarg overrides it."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, max_rounds=2, rel_tol=0.0)
    eng = RoundEngine(loss, init, clients, fed,
                      RoundConfig(exec_mode="vmap"), batch_size=32,
                      loss_sum_fn=loss_sum)
    assert eng.exec_mode == "vmap"
    eng = RoundEngine(loss, init, clients, fed,
                      RoundConfig(exec_mode="vmap"), batch_size=32,
                      exec_mode="loop")
    assert eng.exec_mode == "loop"


def test_federated_trainer_vmap_fast_path():
    """FederatedTrainer(exec_mode="vmap") == the Alg.-1 loop trainer."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=5,
                          rel_tol=0.0)
    tr = FederatedTrainer(loss, init, clients, fed, batch_size=32)
    tv = FederatedTrainer(loss, init, clients, fed, batch_size=32,
                          exec_mode="vmap", loss_sum_fn=loss_sum)
    tr.fit(seed=0)
    tv.fit(seed=0)
    assert _max_dev(tr.params, tv.params) < TOL
    np.testing.assert_allclose([h["loss"] for h in tr.history],
                               [h["loss"] for h in tv.history], rtol=1e-4)


# ---------------------------------------------------------------------------
# constructor guards: the stacked path must refuse, never silently degrade
# ---------------------------------------------------------------------------
def test_vmap_ragged_without_mask_aware_loss_raises():
    cfg, loss, loss_sum, init, clients = _make_setup(docs=(48, 11, 23))
    fed = FederatedConfig(num_clients=3)
    with pytest.raises(ValueError, match="loss_sum_fn"):
        RoundEngine(loss, init, clients, fed, RoundConfig(),
                    batch_size=32, exec_mode="vmap")
    with pytest.raises(ValueError, match="loss_sum_fn"):
        FederatedTrainer(loss, init, clients, fed, batch_size=32,
                         exec_mode="vmap")
    # full batches need no mask-aware loss
    full = [c for c in clients if c.num_docs >= 32]
    RoundEngine(loss, init, full, fed, RoundConfig(), batch_size=32,
                exec_mode="vmap")


def test_vmap_applies_privacy_knobs_in_graph():
    """Since PR 4 the vmap path APPLIES the privacy transforms instead
    of refusing them: the Alg.-1 trainer with secure aggregation runs
    fused and the masks still cancel in the combine."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          rel_tol=0.0, secure_aggregation=True)
    fed_plain = FederatedConfig(num_clients=3, learning_rate=1e-2,
                                max_rounds=4, rel_tol=0.0)
    sec = FederatedTrainer(loss, init, clients, fed, batch_size=32,
                           exec_mode="vmap", loss_sum_fn=loss_sum)
    plain = FederatedTrainer(loss, init, clients, fed_plain, batch_size=32,
                             exec_mode="vmap", loss_sum_fn=loss_sum)
    sec.fit(seed=0)
    plain.fit(seed=0)
    assert _max_dev(sec.params, plain.params) < 1e-4   # masks cancel


def test_unknown_exec_mode_raises():
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3)
    with pytest.raises(ValueError, match="exec_mode"):
        RoundEngine(loss, init, clients, fed, RoundConfig(),
                    exec_mode="nope")
    with pytest.raises(ValueError, match="exec_mode"):
        FederatedTrainer(loss, init, clients, fed, exec_mode="nope")
    with pytest.raises(NotImplementedError):
        FedAvgTrainer(loss, init, clients, fed, exec_mode="vmap")
    with pytest.raises(NotImplementedError):
        # positionally-passed exec_mode must hit the same guard
        FedAvgTrainer(loss, init, clients, fed, None, 32, None, "vmap")


# ---------------------------------------------------------------------------
# stacked batch builder: draws must be bit-identical to the loop iterator
# ---------------------------------------------------------------------------
def test_stacked_batches_bitwise_match_loop_iterator():
    from repro.data.federated_split import round_minibatches
    vocab = 32
    rng = np.random.default_rng(7)
    datas = [{"bow": rng.poisson(0.5, (n, vocab)).astype(np.float32)}
             for n in (40, 9, 17)]
    num_docs = [40, 9, 17]
    round_key = jax.random.PRNGKey(42)
    stacked, counts = stacked_round_batches(
        datas, num_docs, round_key, [0, 1, 2], batch_size=16,
        local_epochs=2)
    for i in range(3):
        it = round_minibatches(datas[i], num_docs[i],
                               jax.random.fold_in(round_key, i),
                               batch_size=16, local_epochs=2)
        for s, (batch, n) in enumerate(it):
            assert counts[i, s] == n
            np.testing.assert_array_equal(
                stacked["bow"][i, s, :n], np.asarray(batch["bow"]))
            np.testing.assert_array_equal(
                stacked["bow"][i, s, n:], 0.0)       # zero padding
            np.testing.assert_array_equal(
                stacked["doc_mask"][i, s],
                (np.arange(16) < n).astype(np.float32))
            np.testing.assert_array_equal(
                stacked["rng"][i, s], np.asarray(batch["rng"], np.uint32))


# ---------------------------------------------------------------------------
# the resident corpus: the cohort gathered on the device, byte for byte
# ---------------------------------------------------------------------------
RESIDENT_CASES = {
    # (docs per client, RoundConfig kwargs, an int token key as well)
    "equal-sizes": ((48, 48, 48), {}, False),
    # n < P = 16 for one client: three draw groups, zero rows past n
    "mixed-sizes": ((40, 9, 17), {}, False),
    # client 1 leaves at round 2: the cohort is padded back to K = 3
    "padded-cohort": ((40, 9, 17), dict(client_leave_round=(0, 2, 0)), False),
    "int-token-key": ((40, 9, 17), {}, True),
}
RESIDENT_REGIMES = {
    "sync": {},
    "straggler": dict(straggler_prob=0.6, max_staleness=3,
                      staleness_decay=0.5),
}


@pytest.mark.parametrize("regime", sorted(RESIDENT_REGIMES))
@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_resident_gather_matches_host_fill(case, regime, monkeypatch):
    """With the clients' rows on the device (placed at the first
    round's dispatch, so from round 2 on) the engine's cohort arrays,
    counts and doc_mask are the host fill's, byte for byte, and so are
    the params after 4 rounds; the round program compiles once across
    the switch, and once where reading its temporaries for the fit rule
    keeps the host fill on a device that reports too little memory."""
    import repro.core.engine as engine_mod
    docs, rc_kw, tokens = RESIDENT_CASES[case]
    cfg, loss, loss_sum, init, clients = _make_setup(docs=docs)
    if tokens:
        gen = np.random.default_rng(1)
        for c in clients:
            c.data["tokens"] = gen.integers(
                0, 1000, (c.num_docs, 8)).astype(np.int32)
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          rel_tol=0.0)
    rc = RoundConfig(**rc_kw, **RESIDENT_REGIMES[regime])
    real = engine_mod.stacked_round_batches
    runs = {}
    for resident in (True, False):
        if not resident:
            monkeypatch.setattr(type(jax.devices()[0]), "memory_stats",
                                lambda self: {"bytes_limit": 1,
                                              "bytes_in_use": 0})
        seen = []

        def record(*a, **kw):
            stacked, counts = real(*a, **kw)
            seen.append((stacked, counts))
            return stacked, counts
        monkeypatch.setattr(engine_mod, "stacked_round_batches", record)
        eng = RoundEngine(loss, init, clients, fed, rc, batch_size=16,
                          exec_mode="vmap", loss_sum_fn=loss_sum)
        compiles = []

        def on(event, _, fun_name="", **__):
            if event.endswith("backend_compile_duration") \
                    and "fused_" in fun_name:
                compiles.append(fun_name)
        jax.monitoring.register_event_duration_secs_listener(on)
        try:
            for r in range(4):
                eng.round(seed=r)
        finally:
            jax.monitoring.unregister_event_duration_listener(on)
        assert [isinstance(st["bow"], np.ndarray) for st, _ in seen] \
            == [True] + [not resident] * 3
        assert len(compiles) == 1, compiles
        runs[resident] = seen, eng.params
    (dev_rounds, dev_params), (host_rounds, host_params) = \
        runs[True], runs[False]
    for (dev, dev_counts), (host, host_counts) in zip(dev_rounds,
                                                      host_rounds):
        assert sorted(dev) == sorted(host)
        for key in host:
            a, b = np.asarray(dev[key]), np.asarray(host[key])
            assert (a.dtype, a.shape) == (b.dtype, b.shape), key
            assert a.tobytes() == b.tobytes(), key
        assert dev_counts.tobytes() == host_counts.tobytes()
    if case == "padded-cohort":
        assert host_counts[-1].sum() == 0           # the padded row
    jax.tree_util.tree_map(np.testing.assert_array_equal, dev_params,
                           host_params)


def test_resident_gather_compiles_once_over_changing_cohorts(monkeypatch):
    """Cohorts of a quantity-skewed population change their client sizes
    and draw groups every round; the resident gather still compiles
    once."""
    import repro.core.engine as engine_mod
    from repro.api import Federation, build_corpus
    from repro.data import federated_split as fs
    spec = tiny_spec(**{"data.partition": "quantity_skew(0.5)",
                        "schedule.clients_per_round": 3,
                        "schedule.rounds": 8})
    fed = Federation.from_spec(spec, corpus=build_corpus(spec))
    real, cohorts = engine_mod.stacked_round_batches, []

    def record(datas, num_docs, *a, **kw):
        cohorts.append(tuple(num_docs))
        return real(datas, num_docs, *a, **kw)
    monkeypatch.setattr(engine_mod, "stacked_round_batches", record)
    fs._resident_gather.clear_cache()
    compiles = []
    for _ in range(8):
        fed.step()
        compiles.append(fs._resident_gather._cache_size())
    assert fed.engine._corpus is not None
    assert len(set(cohorts[1:])) >= 4       # cohorts of other sizes
    assert compiles == [0] + [1] * 7


# The hypothesis fuzz layer over random (L, K, E, vocab, topics,
# staleness) tuples lives in tests/test_vmap_property.py — it whole-module
# skips when the optional [test] extra is missing; the grid above always
# runs.
