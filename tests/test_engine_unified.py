"""The unified FederationEngine (DESIGN.md §3-§4, PR-3 tentpole).

Four properties:

  1. DEPRECATION SHIMS — the historical ``FederatedTrainer`` /
     ``FedAvgTrainer`` / ``RoundEngine`` entry points still import, are
     thin presets of :class:`FederationEngine`, and produce IDENTICAL
     params on a fixed seed to the explicitly-configured engine (one
     code path, so the equality is bitwise).
  2. TRANSFORM STAGE — the previously-orphaned privacy/compression ops
     (dp / topk / secure in ``core/aggregation.py``) wire into the
     engine's transform stage by name, with the mask-cancellation and
     error-feedback semantics intact and incompatible configs refused.
  3. FUSED RING BUFFER — the in-graph straggler path matches the
     loop-mode ``combine_arrivals`` reference under aggressive straggler
     regimes, never exceeds its K*max_staleness capacity, and delivers
     on empty-cohort rounds.
  4. ``combine_arrivals`` input validation (decay range, empty arrivals).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederatedConfig, RoundConfig
from repro.core.engine import FederationEngine, combine_arrivals
from repro.core.transforms import TRANSFORMS, build_transforms
from repro.core.protocol import (FedAvgTrainer, FederatedTrainer,
                                 _wrap_client_optimizer)
from repro.core.rounds import RoundEngine
from repro.optim import sgd
from conftest import make_tiny_federation, max_param_dev

TOL = 1e-5
_make_setup = make_tiny_federation
_max_dev = max_param_dev


def _leaves_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# 1. deprecation shims: old entry points == explicit engine presets
# ---------------------------------------------------------------------------
def test_legacy_classes_are_engine_presets():
    assert issubclass(FederatedTrainer, FederationEngine)
    assert issubclass(FedAvgTrainer, FederationEngine)
    assert issubclass(RoundEngine, FederationEngine)


def test_federated_trainer_shim_identical_params():
    """Old Alg.-1 entry point == FederationEngine grad preset, bitwise."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=5,
                          rel_tol=0.0)
    shim = FederatedTrainer(loss, init, clients, fed, batch_size=32)
    shim.fit(seed=11)
    eng = FederationEngine(
        loss, init, clients, fed, RoundConfig(), batch_size=32,
        message="grad",
        server=_wrap_client_optimizer(sgd(fed.learning_rate)))
    eng.fit(seed=11)
    _leaves_equal(shim.params, eng.params)
    np.testing.assert_array_equal([h["loss"] for h in shim.history],
                                  [h["loss"] for h in eng.history])


def test_round_engine_shim_identical_params():
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=5,
                          rel_tol=0.0)
    rc = RoundConfig(clients_per_round=2, local_epochs=2,
                     server_optimizer="fedavgm", server_momentum=0.5,
                     straggler_prob=0.4, max_staleness=2)
    shim = RoundEngine(loss, init, clients, fed, rc, batch_size=32)
    shim.fit(seed=11)
    eng = FederationEngine(loss, init, clients, fed, rc, batch_size=32,
                           message="delta")
    eng.fit(seed=11)
    _leaves_equal(shim.params, eng.params)


def test_fedavg_trainer_shim_identical_params():
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          local_steps=3, rel_tol=0.0)
    shim = FedAvgTrainer(loss, init, clients, fed, batch_size=32)
    shim.fit(seed=11)
    eng = FederationEngine(loss, init, clients, fed,
                           RoundConfig(local_epochs=fed.local_steps),
                           batch_size=32, message="delta")
    eng.fit(seed=11)
    _leaves_equal(shim.params, eng.params)


def test_grad_message_requires_single_epoch():
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3)
    with pytest.raises(ValueError, match="local_epochs"):
        FederationEngine(loss, init, clients, fed,
                         RoundConfig(local_epochs=2), message="grad",
                         server=_wrap_client_optimizer(sgd(1e-2)))
    with pytest.raises(ValueError, match="message"):
        FederationEngine(loss, init, clients, fed, message="weights")


# ---------------------------------------------------------------------------
# 2. transform stage
# ---------------------------------------------------------------------------
def test_round_engine_dp_transform_declared():
    """Delta-path local DP: declared via RoundConfig.transforms, driven
    by the FederatedConfig knobs, changes the trajectory but trains."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          rel_tol=0.0, dp_noise_multiplier=0.3,
                          dp_clip_norm=1.0)
    eng = RoundEngine(loss, init, clients, fed,
                      RoundConfig(transforms=("dp",)), batch_size=32)
    eng.fit(seed=0)
    base = RoundEngine(loss, init, clients,
                       FederatedConfig(num_clients=3, learning_rate=1e-2,
                                       max_rounds=4, rel_tol=0.0),
                       RoundConfig(), batch_size=32)
    base.fit(seed=0)
    assert _max_dev(eng.params, base.params) > 0
    assert np.isfinite(eng.history[-1]["loss"])


def test_secure_transform_masks_cancel_on_delta_path():
    """Pairwise masks hide each delta but vanish in the Eq. (2) combine."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=4,
                          rel_tol=0.0)
    masked = RoundEngine(loss, init, clients, fed,
                         RoundConfig(transforms=("secure",)), batch_size=32)
    plain = RoundEngine(loss, init, clients, fed, RoundConfig(),
                        batch_size=32)
    masked.fit(seed=0)
    plain.fit(seed=0)
    assert _max_dev(masked.params, plain.params) < 1e-4


def test_topk_transform_error_feedback_state():
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=3,
                          rel_tol=0.0, compression_topk=0.25)
    eng = RoundEngine(loss, init, clients, fed,
                      RoundConfig(transforms=("topk",)), batch_size=32)
    eng.fit(seed=0)
    # error feedback accumulated per client, and the sent deltas sparse
    for c in eng.clients:
        assert c.error_memory is not None
    assert np.isfinite(eng.history[-1]["loss"])


def test_transform_guards():
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3)
    # unknown transform name -> registry KeyError
    with pytest.raises(KeyError, match="unknown transform"):
        RoundEngine(loss, init, clients, fed,
                    RoundConfig(transforms=("nope",)))
    # topk transform without a configured fraction
    with pytest.raises(ValueError, match="compression_topk"):
        RoundEngine(loss, init, clients, fed,
                    RoundConfig(transforms=("topk",)))
    # secure masks cannot survive the straggler buffer
    with pytest.raises(ValueError, match="straggler"):
        RoundEngine(loss, init, clients, fed,
                    RoundConfig(transforms=("secure",), straggler_prob=0.5,
                                max_staleness=2))
    # ... nor partial participation
    with pytest.raises(ValueError, match="participation"):
        RoundEngine(loss, init, clients, fed,
                    RoundConfig(transforms=("secure",),
                                clients_per_round=2))
    # the vmap path ACCEPTS transforms since PR 4 (in-graph stacked
    # implementations) — but the config validation still fires there
    with pytest.raises(ValueError, match="dp_noise_multiplier"):
        RoundEngine(loss, init, clients, fed,
                    RoundConfig(transforms=("dp",), exec_mode="vmap"),
                    batch_size=32)
    RoundEngine(loss, init, clients,
                FederatedConfig(num_clients=3, dp_noise_multiplier=0.3),
                RoundConfig(transforms=("dp",), exec_mode="vmap"),
                batch_size=32)
    # undeclared FederatedConfig privacy knobs on a delta engine still
    # raise (the pre-unification guard, now with a pointer to transforms)
    with pytest.raises(NotImplementedError, match="transforms"):
        RoundEngine(loss, init, clients,
                    FederatedConfig(num_clients=3, dp_noise_multiplier=1.0),
                    RoundConfig())


def test_transform_registry_surface():
    assert set(TRANSFORMS) == {"dp", "topk", "secure", "precision"}
    fed = FederatedConfig(compression_topk=0.1, dp_noise_multiplier=0.5,
                          message_precision="bf16")
    built = build_transforms(("precision", "dp", "topk", "secure"), fed)
    assert [name for name, _ in built] == ["precision", "dp", "topk",
                                           "secure"]


def test_federated_trainer_grad_transforms_unchanged():
    """The Alg.-1 preset still derives its grad transforms from the
    FederatedConfig knobs: secure aggregation is a no-op on the combined
    update, DP noise is not."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed_plain = FederatedConfig(num_clients=3, learning_rate=1e-2,
                                max_rounds=4, rel_tol=0.0)
    fed_sec = FederatedConfig(num_clients=3, learning_rate=1e-2,
                              max_rounds=4, rel_tol=0.0,
                              secure_aggregation=True)
    fed_dp = FederatedConfig(num_clients=3, learning_rate=1e-2,
                             max_rounds=4, rel_tol=0.0,
                             dp_noise_multiplier=0.5)
    base = FederatedTrainer(loss, init, clients, fed_plain, batch_size=32)
    sec = FederatedTrainer(loss, init, clients, fed_sec, batch_size=32)
    dp = FederatedTrainer(loss, init, clients, fed_dp, batch_size=32)
    base.fit(seed=3)
    sec.fit(seed=3)
    dp.fit(seed=3)
    assert _max_dev(base.params, sec.params) < 1e-4    # masks cancel
    assert _max_dev(base.params, dp.params) > 1e-4     # noise is real


# ---------------------------------------------------------------------------
# 3. fused in-graph ring buffer vs the combine_arrivals reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("regime", [
    dict(straggler_prob=0.9, max_staleness=3, staleness_decay=0.3),
    dict(straggler_prob=1.0, max_staleness=2, staleness_decay=0.5),
    dict(clients_per_round=2, local_epochs=2, straggler_prob=0.7,
         max_staleness=3, staleness_decay=0.25),
])
def test_fused_ring_matches_loop_reference(regime):
    """Aggressive straggler regimes: the fused buffer must retrace the
    host-side pending-list + combine_arrivals path within 1e-5."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=10,
                          rel_tol=0.0)
    rc = RoundConfig(**regime)
    loop = RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                       exec_mode="loop")
    vm = RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                     exec_mode="vmap", loss_sum_fn=loss_sum)
    cap = vm.scheduler.clients_per_round * rc.max_staleness
    for r in range(10):
        ra = loop.round(seed=7 * 100003 + r)
        rb = vm.round(seed=7 * 100003 + r)
        assert _max_dev(loop.params, vm.params) < TOL
        assert ra["arrived"] == rb["arrived"]
        assert ra["in_flight"] == rb["in_flight"]
        assert rb["in_flight"] <= cap          # capacity invariant
    # the regime actually exercised the buffer
    assert any(h["in_flight"] > 0 for h in vm.history)
    assert sum(h["arrived"] for h in vm.history) > 0


def test_fused_ring_delivers_on_empty_cohort_round():
    """A round where every client has left must still deliver due
    stragglers from the ring (and must not crash the stacked path)."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=6,
                          rel_tol=0.0)
    # everyone leaves at round 2 -> rounds 2+ have no cohort, but round
    # 0/1 stragglers (prob 1) are still in flight with delays up to 3
    rc = RoundConfig(straggler_prob=1.0, max_staleness=3,
                     staleness_decay=0.5, client_leave_round=(2, 2, 2))
    loop = RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                       exec_mode="loop")
    vm = RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                     exec_mode="vmap", loss_sum_fn=loss_sum)
    for r in range(6):
        ra = loop.round(seed=5 * 100003 + r)
        rb = vm.round(seed=5 * 100003 + r)
        assert ra["participants"] == rb["participants"]
        assert ra["arrived"] == rb["arrived"]
        assert ra["in_flight"] == rb["in_flight"]
        assert _max_dev(loop.params, vm.params) < TOL
    assert loop.history[2]["participants"] == 0
    assert sum(h["arrived"] for h in loop.history[2:]) > 0
    assert loop.history[-1]["in_flight"] == 0      # buffer drained


def test_fused_state_never_aliases_the_callers_trees():
    """On accelerators the fused graphs donate params, server state and
    transform state, deleting those buffers.  Before the first fused
    round the engine copies them into buffers of its own, so a caller's
    init tree — shared here with a loop twin, as the parity tests do —
    is never what gets donated."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=2,
                          rel_tol=0.0)
    rc = RoundConfig(server_optimizer="fedavgm", server_lr=0.5)
    vm = RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                     exec_mode="vmap", loss_sum_fn=loss_sum)
    given = (vm.params, vm.server_state)
    vm._build_vmap_fns()
    vm._own_state()

    def buffers(tree):
        return {x.unsafe_buffer_pointer()
                for x in jax.tree_util.tree_leaves(tree)}

    assert not buffers(given) & buffers((vm.params, vm.server_state))
    _leaves_equal(given[0], vm.params)
    loop = RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                       exec_mode="loop")
    for r in range(2):
        vm.round(seed=r)
        loop.round(seed=r)
    assert _max_dev(loop.params, vm.params) < TOL


def test_restored_state_never_aliases_the_snapshot():
    """A snapshot restored into an engine whose fused graphs already
    exist is copied before the next (donating) round: the caller's
    device arrays in the snapshot survive, and the run continues on the
    same trajectory as an uninterrupted one."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    fed = FederatedConfig(num_clients=3, learning_rate=1e-2, max_rounds=3,
                          rel_tol=0.0)
    rc = RoundConfig(server_optimizer="fedavgm", server_lr=0.5)

    def engine():
        return RoundEngine(loss, init, clients, fed, rc, batch_size=32,
                           exec_mode="vmap", loss_sum_fn=loss_sum)

    ref, vm = engine(), engine()
    for r in range(3):
        ref.round(seed=r)
    vm.round(seed=0)
    snap = vm.state_dict()
    snap["params"] = jax.tree_util.tree_map(jnp.asarray, snap["params"])
    vm.load_state_dict(snap)

    def buffers(tree):
        return {x.unsafe_buffer_pointer()
                for x in jax.tree_util.tree_leaves(tree)}

    assert not buffers(snap["params"]) & buffers(vm.params)
    for r in (1, 2):
        vm.round(seed=r)
    _leaves_equal(ref.params, vm.params)


# ---------------------------------------------------------------------------
# 4. combine_arrivals validation (satellite fix)
# ---------------------------------------------------------------------------
def test_guards_symmetric_across_message_kinds_and_exec_modes():
    """REGRESSION (review findings): the refuse-never-drop guards must
    fire on EVERY path, not just one — grad+loop used to silently drop
    FederatedConfig privacy knobs, vmap used to accept out-of-range
    staleness_decay, and zero-epoch clients crashed loop mode only."""
    cfg, loss, loss_sum, init, clients = _make_setup()
    # grad-message engine without a declared transform stage must refuse
    # privacy knobs exactly like the delta engine does
    with pytest.raises(NotImplementedError, match="transforms"):
        FederationEngine(loss, init, clients,
                         FederatedConfig(num_clients=3,
                                         dp_noise_multiplier=0.5),
                         RoundConfig(), message="grad",
                         server=_wrap_client_optimizer(sgd(1e-2)))
    # grad messages with the delta-convention default server would train
    # by ASCENT (the server ADDS its step) — must be refused, not allowed
    with pytest.raises(ValueError, match="server"):
        FederationEngine(loss, init, clients,
                         FederatedConfig(num_clients=3), RoundConfig(),
                         message="grad")
    # the 'dp' transform with a zero noise multiplier would silently
    # degrade to clip-only while claiming local DP
    with pytest.raises(ValueError, match="dp_noise_multiplier"):
        RoundEngine(loss, init, clients, FederatedConfig(num_clients=3),
                    RoundConfig(transforms=("dp",)))
    # out-of-range decay is refused at construction on BOTH exec modes
    for mode in ("loop", "vmap"):
        with pytest.raises(ValueError, match="staleness_decay"):
            RoundEngine(loss, init, clients, FederatedConfig(num_clients=3),
                        RoundConfig(straggler_prob=0.5, max_staleness=2,
                                    staleness_decay=1.5),
                        exec_mode=mode)
    # zero-epoch clients are refused up front instead of dividing the
    # Eq. (2) combine by zero mid-training
    for rc in (RoundConfig(local_epochs=0),
               RoundConfig(local_epochs_by_client=(0, 2))):
        with pytest.raises(ValueError, match="local epoch"):
            RoundEngine(loss, init, clients, FederatedConfig(num_clients=3),
                        rc)


def test_combine_arrivals_rejects_bad_decay():
    delta = {"w": jnp.ones((2,), jnp.float32)}
    for bad in (-0.1, 1.5, np.nan):
        with pytest.raises(ValueError, match="staleness_decay"):
            combine_arrivals([(1, delta, 1.0)], bad)
    # the boundary values are legal (drop-stale / trust-stale regimes)
    combine_arrivals([(1, delta, 1.0)], 0.0)
    combine_arrivals([(1, delta, 1.0)], 1.0)


def test_combine_arrivals_rejects_empty():
    with pytest.raises(ValueError, match="at least one"):
        combine_arrivals([], 0.5)
    with pytest.raises(ValueError, match="at least one"):
        combine_arrivals(iter(()), 0.5)
