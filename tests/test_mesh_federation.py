"""Mesh-sharded cohort execution (``execution.mesh``): the fused vmap
graphs with the stacked ``(K, ...)`` cohort, the ``(L, ...)`` per-client
transform state and the straggler ring row-sharded over a
``("data",)``-axis device mesh.

Two tiers, following the conftest policy (no XLA_FLAGS here — tests in
the default run see ONE device):

  * always-run — spec-construction refusals, the data=1 degenerate
    mesh (buildable on any host), the runtime shard-divisibility guard
    and the too-few-devices refusal;
  * ``host_mesh_devices``-gated — the full sharded-vs-unsharded parity
    grid at data=2/4/8, L >> K top-k error feedback, churn/empty
    rounds, bitwise resume and the single-trace contract.  These skip
    with the ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    incantation unless the CI host-mesh leg (or a local run) exported
    it before jax imported.

The unsharded vmap run is the parity reference everywhere (the loop
path is in turn ITS reference, pinned by the engine suites); the
acceptance bound is the repo-wide 1e-5.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DataSpec, ExecutionSpec, Federation, FederationSpec,
                       MeshSpec, ModelSpec, ScheduleSpec, build_corpus,
                       spec_replace)
from repro.core.transforms import pairwise_mask_stack
from repro.data.federated_split import stacked_round_batches
from repro.parallel import sharding
from conftest import max_param_dev

_max_dev = max_param_dev


def _spec(num_clients=8, mesh=None, **overrides):
    # lr and corpus seed chosen so the tiny federation CONVERGES over
    # the test horizon: a diverging model grows params without bound and
    # turns the absolute 1e-5 parity bound into noise measurement (at
    # lr 1e-3 the corpora of data seeds 0, 3 and 5 blow up to inf by
    # round 3; seed 1 trains down smoothly at L=8 and L=16)
    base = FederationSpec(
        model=ModelSpec(vocab=128, topics=4, hidden=16),
        data=DataSpec(num_clients=num_clients, docs_per_node=40,
                      val_docs_per_node=8, seed=1),
        schedule=ScheduleSpec(rounds=3),
        execution=ExecutionSpec(
            exec_mode="vmap", batch_size=16, learning_rate=1e-3,
            mesh=MeshSpec.from_value(mesh) if mesh is not None else None))
    return spec_replace(base, overrides) if overrides else base


@pytest.fixture(scope="module")
def corpus8():
    return build_corpus(_spec())


@pytest.fixture(scope="module")
def corpus16():
    return build_corpus(_spec(num_clients=16))


def _run_pair(spec, corpus, rounds=None):
    """The sharded run and its unsharded twin (mesh stripped, all else
    byte-identical) — returns both facades after ``run``."""
    sharded = Federation.from_spec(spec, corpus=corpus)
    sharded.run(rounds=rounds)
    unsharded = Federation.from_spec(
        spec_replace(spec, {"execution.mesh": None}), corpus=corpus)
    unsharded.run(rounds=rounds)
    return sharded, unsharded


# ---------------------------------------------------------------------------
# always-run: refusals + the degenerate data=1 mesh
# ---------------------------------------------------------------------------
def test_mesh_data1_matches_unsharded(corpus8):
    """A 1-device mesh is buildable on ANY host: same per-shard math,
    one-term psum — must match the unsharded run within the repo
    bound, single-trace, and report its shape through the facade."""
    sharded, unsharded = _run_pair(_spec(mesh={"data": 1}), corpus8)
    assert sharded.mesh_shape == {"data": 1}
    assert unsharded.mesh_shape is None
    assert _max_dev(sharded.params, unsharded.params) < 1e-5
    assert sum(sharded.engine.trace_counts.values()) == 1


def test_divisibility_refused_at_spec_construction():
    # L = 5 not divisible by the data axis: refused when the spec is
    # BUILT, never deferred to runtime repartitioning
    with pytest.raises(ValueError, match="never silently repartitioned"):
        _spec(num_clients=5, mesh={"data": 2})
    # K (cohort width) must divide too, even when L does
    with pytest.raises(ValueError, match="never silently repartitioned"):
        _spec(num_clients=8, mesh={"data": 2},
              **{"schedule.clients_per_round": 3})
    # the refusal is spec-level policy: it fires under exec_mode="loop"
    # as well, even though the loop path never builds the mesh
    with pytest.raises(ValueError, match="never silently repartitioned"):
        _spec(num_clients=5, mesh={"data": 2},
              **{"execution.exec_mode": "loop"})


def test_mesh_inert_under_loop_mode(corpus8):
    """Like kernel_backend, the mesh knob is accepted-but-inert on the
    host loop — the loop run of a mesh cell never needs the devices."""
    fed = Federation.from_spec(
        _spec(mesh={"data": 8}, **{"execution.exec_mode": "loop"}),
        corpus=corpus8)
    fed.run(rounds=1)
    assert fed.mesh_shape is None


def test_too_few_devices_refused():
    n = jax.device_count()
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        sharding.fed_mesh(n + 1)


def test_runtime_shard_multiple_refusal(rng):
    """The engine-level backstop: a cohort whose stacked width does not
    divide the mesh axis is refused by ``stacked_round_batches`` with
    the pad_cohorts remedy in the message."""
    datas = [{"bow": rng.random((6, 8), dtype=np.float32)}
             for _ in range(3)]
    with pytest.raises(ValueError, match="pad_cohorts"):
        stacked_round_batches(datas, [6, 6, 6], jax.random.PRNGKey(0),
                              [0, 1, 2], batch_size=2, shard_multiple=2)
    # divisible width sails through
    stacked, _ = stacked_round_batches(datas, [6, 6, 6],
                                       jax.random.PRNGKey(0), [0, 1, 2],
                                       batch_size=2, pad_to=4,
                                       shard_multiple=2)
    assert stacked["bow"].shape[0] == 4


def test_mesh_spec_roundtrip_and_round_config():
    s = _spec(mesh="data=4")
    assert s.execution.mesh == MeshSpec(data=4)
    assert FederationSpec.from_json(s.to_json()) == s
    assert s.to_round_config().mesh_data == 4
    assert _spec().to_round_config().mesh_data == 0


# ---------------------------------------------------------------------------
# host-mesh tier: the parity grid on 8 forced devices
# ---------------------------------------------------------------------------
_REGIMES = {
    "sync": {},
    "dp-straggler": {"transforms.names": ("dp",),
                     "transforms.dp_noise_multiplier": 0.3,
                     "transforms.dp_clip_norm": 0.05,
                     "schedule.straggler_prob": 0.4,
                     "schedule.max_staleness": 2,
                     "schedule.staleness_decay": 0.5},
    "topk": {"transforms.names": ("topk",),
             "transforms.compression_topk": 0.25},
    "secure": {"transforms.names": ("secure",)},
    "churn": {"schedule.client_join_round": (0,) * 7 + (2,),
              "schedule.client_leave_round": (0,) * 7 + (3,)},
}


@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize("data", [2, 4])
def test_sharded_matches_unsharded(host_mesh_devices, corpus8, regime,
                                   data):
    """The acceptance grid: every regime's sharded run lands within
    1e-5 of the unsharded vmap run, compiling exactly one fused graph
    per regime (stragglers add the warm-up deliver/stale graphs but
    never a SECOND trace of any of them)."""
    sharded, unsharded = _run_pair(
        _spec(mesh={"data": data}, **_REGIMES[regime]), corpus8)
    assert sharded.mesh_shape == {"data": data}
    assert _max_dev(sharded.params, unsharded.params) < 1e-5
    assert all(v == 1 for v in sharded.engine.trace_counts.values()), \
        sharded.engine.trace_counts
    assert sharded.engine.trace_counts == unsharded.engine.trace_counts


@pytest.mark.parametrize("data", [2, 8])
def test_pallas_backend_under_mesh(host_mesh_devices, corpus8, data):
    """kernel_backend='pallas' keeps working per-shard inside the
    shard_map islands (check_rep=False plumbing)."""
    sharded, unsharded = _run_pair(
        _spec(mesh={"data": data},
              **{"execution.kernel_backend": "pallas"}), corpus8)
    assert _max_dev(sharded.params, unsharded.params) < 1e-5


def test_topk_state_sharded_L_much_greater_K(host_mesh_devices, corpus16):
    """L=16 clients, K=4 cohort, data=4: the (L, ...) error-feedback
    tree shards over the mesh while each round touches only a K-row
    gather/scatter of it — parity must hold across client resampling."""
    spec = _spec(num_clients=16, mesh={"data": 4},
                 **{"schedule.clients_per_round": 4,
                    "schedule.sampling": "uniform",
                    "schedule.rounds": 4,
                    "transforms.names": ("topk",),
                    "transforms.compression_topk": 0.25})
    sharded, unsharded = _run_pair(spec, corpus16)
    assert _max_dev(sharded.params, unsharded.params) < 1e-5
    assert sum(sharded.engine.trace_counts.values()) == 1


def test_empty_and_all_padded_rounds(host_mesh_devices, corpus8):
    """Rounds where NO client is active (everyone joins late) run the
    all-padded cohort through the same sharded graph — zero-weight
    rows, no retrace, and still parity with the unsharded run."""
    spec = _spec(mesh={"data": 4},
                 **{"schedule.rounds": 4,
                    "schedule.client_join_round": (2,) * 8})
    sharded, unsharded = _run_pair(spec, corpus8)
    assert _max_dev(sharded.params, unsharded.params) < 1e-5
    assert all(v == 1 for v in sharded.engine.trace_counts.values()), \
        sharded.engine.trace_counts


def test_resume_bitwise_under_mesh(host_mesh_devices, corpus8):
    """snapshot -> resume is BITWISE under the mesh, and the
    interrupted trajectory equals the uninterrupted one."""
    spec = _spec(mesh={"data": 4}, **{"schedule.rounds": 4,
                                      "schedule.straggler_prob": 0.3,
                                      "schedule.max_staleness": 2})
    a = Federation.from_spec(spec, corpus=corpus8)
    a.run(rounds=2)
    snap = a.state_dict()
    a.run()
    b = Federation.from_spec(spec, corpus=corpus8)
    b.load_state_dict(snap)
    b.run()
    assert _max_dev(a.params, b.params) == 0.0
    assert a.history == b.history


def test_trace_pinned_under_churn(host_mesh_devices, corpus8):
    """dropout-join churn at data=4: the cohort composition changes
    every round, the fused graph never retraces."""
    spec = _spec(mesh={"data": 4},
                 **{"schedule.rounds": 5,
                    "schedule.client_join_round": (0,) * 7 + (2,),
                    "schedule.client_leave_round": (0,) * 7 + (4,)})
    fed = Federation.from_spec(spec, corpus=corpus8)
    fed.run()
    assert fed.engine.trace_counts == {"fused_sync": 1}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("data", [2, 4, 8])
def test_mask_cancellation_bitwise_cross_device(host_mesh_devices,
                                                backend, data):
    """DESIGN.md's dyadic-grid argument, re-derived cross-device: each
    device's partial sum over its row shard is an exact grid integer,
    so the <= N-term psum is exact — the pairwise secure masks cancel
    BITWISE (exactly 0.0) through the sharded combine, either
    backend."""
    from repro.kernels import ops as kops
    tmpl = {"w": jnp.zeros((13, 7), jnp.float32),
            "b": jnp.zeros((11,), jnp.float32)}
    mesh = sharding.fed_mesh(data)
    for num_clients in (data, 2 * data, 3 * data):
        stack = pairwise_mask_stack(jax.random.PRNGKey(0), tmpl,
                                    num_clients)
        total = kops.fed_weighted_sum(
            stack, jnp.ones((num_clients,), jnp.float32),
            backend=backend, mesh=mesh)
        worst = max(float(np.abs(np.asarray(l)).max())
                    for l in jax.tree_util.tree_leaves(total))
        assert worst == 0.0, (num_clients, worst)


def test_sharding_compat_layer_under_fed_mesh(host_mesh_devices):
    """fed_mesh composes with the JAX mesh API the sharding rules call
    directly: jax.lax.axis_size resolves the data axis inside a
    jax.shard_map body, and jax.sharding.use_abstract_mesh scopes the
    mesh that the in-model constraints read."""
    mesh = sharding.fed_mesh(4)
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert dict(jax.sharding.get_abstract_mesh().shape) == {"data": 4}
        # divisible dim keeps the axis, non-divisible drops it
        assert sharding.sanitize_spec(
            sharding.P("data"), (8, 3), mesh) == sharding.P("data")
        assert sharding.sanitize_spec(
            sharding.P("data"), (7, 3), mesh) == sharding.P()
    assert not jax.sharding.get_abstract_mesh().axis_names

    def body(x):
        return jnp.sum(x, keepdims=True) * jax.lax.axis_size("data")

    out = jax.shard_map(body, mesh=mesh, in_specs=sharding.P("data"),
                        out_specs=sharding.P("data"))(
                            jnp.ones((8,), jnp.float32))
    assert out.shape == (4,)
    assert float(jnp.sum(out)) == 8.0 * 4
