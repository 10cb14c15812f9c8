"""Compile for a described TPU v5e: what interpret mode cannot show.

Nothing here runs on a chip.  Each test lowers and compiles one program
for a v5e that is described, not attached (``get_topology_desc``), so
the chip's compiler refuses here what it would refuse on the machine:
block shapes off the (8, 128) tiling, more VMEM than a kernel may use,
a program that does not fit.  Shapes are the paper's ProdLDA widths:

  * the three ``kernels/fed_aggregate.py`` kernels (the Pallas backend's
    combine, top-k and dp/secure passes) over a K=16 cohort of the
    model's two largest leaves, 50x5000 and 5000x100, top-k keeping 25%;
  * the XLA ``fused_sync`` round graph at the widths of
    ``chip_smoke.py``'s train phase (V=5000, 50 topics, 100-100
    encoder, L=K=5, E=1, batch 64).

The kernels are called with ``interpret=False`` and the round graph is
taken from the engine and lowered for the described device, steered
from here: the program's own platform checks still see this host's CPU.
The topology is described inside a fixture, never at import (see the
``topo`` fixture), and the persistent compilation cache is off around
the compiles — what they would write cannot be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import (DataSpec, ExecutionSpec, Federation, FederationSpec,
                       ModelSpec, ScheduleSpec)
from repro.data.federated_split import stacked_round_batches
from repro.kernels.fed_aggregate import (fed_dp_secure_apply_pallas,
                                         fed_topk_ef_pallas,
                                         fed_weighted_sum_pallas)

COHORT = 16
LEAVES = {"beta_50x5000": 50 * 5000, "encoder_5000x100": 5000 * 100}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _kernel_call(kernel, d, sharding):
    """(function, abstract args) of one kernel over a (K, d) cohort."""
    x = jax.ShapeDtypeStruct((COHORT, d), jnp.float32, sharding=sharding)
    vec = jax.ShapeDtypeStruct((COHORT,), jnp.float32, sharding=sharding)
    if kernel == "weighted_sum":
        return (lambda x, w: fed_weighted_sum_pallas(x, w, interpret=False),
                (x, vec))
    if kernel == "topk_ef":
        return (lambda m, e: fed_topk_ef_pallas(m, e, k_keep=d // 4,
                                                interpret=False), (x, x))
    return (lambda x, n, m, c, w: fed_dp_secure_apply_pallas(
        x, noise=n, masks=m, clip_coef=c, weights=w, noise_scale=0.3,
        interpret=False), (x, x, x, vec, vec))


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("kernel", ["weighted_sum", "topk_ef",
                                    "dp_secure"])
def test_aggregation_kernel_compiles_for_v5e(kernel, leaf, one_chip,
                                             no_persistent_cache):
    fn, args = _kernel_call(kernel, LEAVES[leaf], one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_sync_round_compiles_for_v5e(one_chip, no_persistent_cache):
    spec = FederationSpec(
        model=ModelSpec(vocab=5000, topics=50, hidden=100),
        data=DataSpec(num_clients=5, docs_per_node=64, val_docs_per_node=0),
        schedule=ScheduleSpec(rounds=1),
        execution=ExecutionSpec(exec_mode="vmap", batch_size=64))
    eng = Federation.from_spec(spec).engine
    eng._build_vmap_fns()
    k = eng.scheduler.clients_per_round
    cohort = list(range(k))
    stacked, counts = stacked_round_batches(
        [eng.clients[l].data for l in cohort],
        [eng.clients[l].num_docs for l in cohort], jax.random.PRNGKey(0),
        cohort, batch_size=eng.batch_size, local_epochs=eng._e_max,
        pad_to=k)
    args = (eng.params, eng.server_state, eng._tstate, stacked,
            np.ones(k, np.int32), counts.sum(axis=1),
            np.arange(k, dtype=np.int32), jax.random.PRNGKey(0),
            np.int32(0))
    assert stacked["bow"].shape == (5, 1, 64, 5000)
    compiled = eng._fused_sync.lower(*_abstract(args, one_chip)).compile()
    mem = compiled.memory_analysis()
    # the round's operands and scratch are megabytes; a chip holds 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30
