"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see ONE device;
multi-device protocol tests spawn subprocesses that set the flag first.

:func:`pytest_collection_modifyitems` below guards xfail debt: an
xfail marker must cite an open item, so triaged breakage can never
silently accumulate.
"""
import re

import numpy as np
import pytest

# an xfail marker is only acceptable when its reason cites an open item
# (a ROADMAP/ISSUE entry, a PR/tracker number, or an issue URL) — an
# unreferenced xfail is exactly how the 49-entry seed triage block
# accumulated unnoticed
_XFAIL_REF = re.compile(r"(ROADMAP|ISSUE|DESIGN\.md|PR\s*#?\d+|#\d+|"
                        r"https?://\S+)", re.IGNORECASE)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# devices the mesh-sharded execution tests need (tests/test_mesh_federation.py
# and the CI host-mesh leg, which exports the XLA flag before pytest starts)
HOST_MESH_DEVICES = 8


@pytest.fixture
def host_mesh_devices():
    """The visible device count for mesh-execution tests, or a skip.

    XLA fixes the device count at backend init, so a fixture cannot
    grow it after jax is imported — the CI host-mesh leg (and anyone
    running the mesh suite locally) must export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE
    pytest starts.  Everywhere else the mesh tests skip with that
    incantation as the reason instead of failing on a 1-device host."""
    import jax
    n = jax.device_count()
    if n < HOST_MESH_DEVICES:
        pytest.skip(
            f"needs {HOST_MESH_DEVICES} devices, {n} visible — export "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{HOST_MESH_DEVICES} before importing jax (the CI "
            "host-mesh leg does exactly this)")
    return n


# ---------------------------------------------------------------------------
# shared federated-engine test helpers (import via `from conftest import …`;
# the single home for the loop==vmap deviation metric and the tiny
# synthetic federation used across the equivalence/engine/scenario suites)
# ---------------------------------------------------------------------------
def max_param_dev(a, b) -> float:
    """Max abs leafwise deviation between two param pytrees — the metric
    behind every loop-vs-vmap acceptance bound."""
    import jax
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def make_tiny_federation(vocab=64, topics=4, docs=(48, 48, 48), seed=0,
                         name="tiny-fed"):
    """Tiny synthetic federation (per-client poisson BoW corpora):
    returns ``(cfg, loss, loss_sum, init, clients)``."""
    import jax
    from repro.configs.base import NTM, ModelConfig
    from repro.core.ntm import prodlda
    from repro.core.protocol import ClientState
    cfg = ModelConfig(name=name, kind=NTM, vocab_size=vocab,
                      num_topics=topics, ntm_hidden=(16, 16))
    gen = np.random.default_rng(seed)
    clients = [ClientState(
        data={"bow": gen.poisson(0.3, (n, vocab)).astype(np.float32)},
        num_docs=n) for n in docs]
    loss = lambda p, b: prodlda.elbo_loss(p, cfg, b, train=False)  # noqa: E731,E501
    loss_sum = lambda p, b: prodlda.elbo_loss_sum(p, cfg, b, train=False)  # noqa: E731,E501
    init = prodlda.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, loss, loss_sum, init, clients


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


def pytest_collection_modifyitems(config, items):
    """xfail-debt guard (module docstring): every xfail marker must cite
    an open item in its reason; offenders fail collection loudly."""
    offenders = []
    for item in items:
        for marker in item.iter_markers(name="xfail"):
            reason = str(marker.kwargs.get("reason", "") or "")
            if not _XFAIL_REF.search(reason):
                offenders.append(f"{item.nodeid}  (reason={reason!r})")
    if offenders:
        raise pytest.UsageError(
            "xfail marker(s) without an open-item reference — cite the "
            "ROADMAP/ISSUE entry or tracker number in the reason (e.g. "
            "reason='ROADMAP.md: sharded cohorts') so xfail debt stays "
            "visible:\n  " + "\n  ".join(offenders))
